"""f32-vs-f64 parity evidence for BASELINE config #1 (SURVEY.md §7 numerics).

The reference runs f64 on the JVM; the TPU runs f32 (MXU/VPU native). This
harness quantifies what that costs on the a1a-shaped logistic-regression
fit (config #1): it runs the SAME deterministic fit at a given dtype and
prints loss/AUC/coefficients; ``compare`` mode spawns one f64 CPU leg (the
reference numerics) and one f32 leg on the requested platform (the real
chip when available) and reports the deltas.

Usage:
  python scripts/f32_parity.py run --dtype float32            # one leg
  python scripts/f32_parity.py compare [--platform tpu]       # both + deltas

Exit code in compare mode: 0 if |dAUC| < 1e-3 and relative loss delta
< 1e-4, else 1 (the tolerance a TPU fit must meet for AUC parity with the
reference's f64 numbers — BASELINE.md).
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys


def _run_leg(dtype: str) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from photon_ml_tpu.evaluation import get_evaluator
    from photon_ml_tpu.ops.objective import make_objective
    from photon_ml_tpu.optimize import OptimizerConfig, get_optimizer
    from photon_ml_tpu.testing import synthetic_glm_data
    from photon_ml_tpu.types import make_batch, SparseFeatures

    jdtype = jnp.float64 if dtype == "float64" else jnp.float32
    # a1a shape: ~1.6k train rows, 123 binary features, sparse
    data = synthetic_glm_data(2000, 123, density=0.11, seed=1)
    Xtr, ytr = data.X[:1600], data.y[:1600]
    Xv, yv = data.X[1600:], data.y[1600:]

    def to_sparse(X):
        # ELL layout like the LIBSVM reader produces
        nz = [np.nonzero(r)[0] for r in X]
        k = max(max((len(i) for i in nz), default=0), 1)
        idx = np.zeros((len(X), k), np.int32)
        val = np.zeros((len(X), k))
        for i, cols in enumerate(nz):
            idx[i, : len(cols)] = cols
            val[i, : len(cols)] = X[i, cols]
        return SparseFeatures(jnp.asarray(idx), jnp.asarray(val, jdtype),
                              dim=X.shape[1])

    batch = make_batch(to_sparse(Xtr), ytr, dtype=jdtype)
    vbatch = make_batch(to_sparse(Xv), yv, dtype=jdtype)
    obj = make_objective("logistic")
    res = get_optimizer("lbfgs")(
        lambda w: obj.value_and_grad(w, batch, 1.0),
        jnp.zeros(123, jdtype),
        OptimizerConfig(max_iters=200, tolerance=1e-10),
    )
    scores = np.asarray(obj.margins(res.w, vbatch), np.float64)
    auc = get_evaluator("auc").evaluate(scores, yv)
    val_loss = float(obj.value(res.w, vbatch, 0.0)) / len(yv)
    import jax as _jax

    return {
        "dtype": dtype,
        "platform": _jax.devices()[0].platform,
        "train_loss": float(res.value),
        "val_loss_per_row": val_loss,
        "auc": float(auc),
        "iterations": int(res.iterations),
        "converged": bool(res.converged),
        "w_norm": float(jnp.linalg.norm(res.w)),
        "w": np.asarray(res.w, np.float64).tolist(),
    }


def _spawn(dtype: str, platform: str | None, x64: bool) -> dict:
    env = dict(os.environ)
    if platform:
        env["JAX_PLATFORMS"] = platform
    env["JAX_ENABLE_X64"] = "1" if x64 else "0"
    # APPEND the repo root: keep whatever PYTHONPATH the caller set
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = os.pathsep.join(
        [p for p in (env.get("PYTHONPATH"), repo) if p])
    out = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "run", "--dtype", dtype],
        env=env, capture_output=True, text=True, timeout=1200,
    )
    if out.returncode != 0:
        raise RuntimeError(f"leg {dtype}/{platform} failed:\n{out.stderr[-2000:]}")
    return json.loads(out.stdout.strip().splitlines()[-1])


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("mode", choices=["run", "compare"])
    ap.add_argument("--dtype", default="float32",
                    choices=["float32", "float64"])
    ap.add_argument("--platform", default=None,
                    help="platform for the f32 leg (default: jax default, "
                         "i.e. the TPU when reachable)")
    args = ap.parse_args()

    if args.mode == "run":
        import jax

        if args.dtype == "float64":
            jax.config.update("jax_enable_x64", True)
        print(json.dumps(_run_leg(args.dtype)))
        return 0

    ref = _spawn("float64", "cpu", x64=True)
    f32 = _spawn("float32", args.platform, x64=False)
    import numpy as np

    w_ref = np.asarray(ref.pop("w"))
    w_f32 = np.asarray(f32.pop("w"))
    d_auc = abs(f32["auc"] - ref["auc"])
    d_loss = abs(f32["val_loss_per_row"] - ref["val_loss_per_row"]) / max(
        abs(ref["val_loss_per_row"]), 1e-30)
    d_w = float(np.linalg.norm(w_f32 - w_ref)
                / max(np.linalg.norm(w_ref), 1e-30))
    report = {
        "f64_cpu": ref,
        "f32": f32,
        "delta_auc": d_auc,
        "rel_delta_val_loss": d_loss,
        "rel_delta_w": d_w,
        "pass": bool(d_auc < 1e-3 and d_loss < 1e-4),
    }
    print(json.dumps(report, indent=2))
    return 0 if report["pass"] else 1


if __name__ == "__main__":
    sys.exit(main())
