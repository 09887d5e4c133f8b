"""A/B the 1-D table-gather strategies on the live chip.

The 2026-07-31 builder session measured the serial word-granular gather at
~1 GB/s (0.1% of HBM peak) and attributed the whole fit iteration to it;
``types.table_gather`` replaces it with a row-gather + lane-select form.
This harness times the two modes head-to-head on the bench shape for the
two hot passes (margins; CSC contrib gather + blocked combine), plus the
end-to-end L-BFGS fit in each mode — the direct evidence for the 'auto'
default. Device-synthesized data, salted timed runs, scalar-fetch sync
(the bench.py discipline: a timed run is never bit-identical to its
warm-up, and a device->host read of the result ends the timing).
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from photon_ml_tpu import types as T
from photon_ml_tpu.ops.objective import make_objective
from photon_ml_tpu.optimize import OptimizerConfig
from photon_ml_tpu.parallel.data_parallel import build_csc, fit_distributed
from photon_ml_tpu.parallel.mesh import make_mesh

REPS = 5


def timed(fn, *args):
    """Compile+warm on salt 0, then time REPS salted executions."""
    float(fn(jnp.float32(0.0), *args))
    t0 = time.perf_counter()
    for r in range(1, REPS + 1):
        float(fn(jnp.float32(r * 1e-8), *args))
    return (time.perf_counter() - t0) / REPS


def main() -> None:
    platform = jax.devices()[0].platform
    small = platform == "cpu"
    n, d, k = ((1 << 14, 1 << 12, 39) if small else (1 << 21, 1 << 18, 39))
    print(f"platform={platform} n={n} d={d} k={k}", flush=True)

    @jax.jit
    def make_data(key):
        k_idx, k_w, k_lab = jax.random.split(key, 3)
        idx = jax.random.randint(k_idx, (n, k), 0, d, jnp.int32)
        w = jax.random.normal(k_w, (d,), jnp.float32) * 0.5
        labels = (jax.random.uniform(k_lab, (n,)) < 0.5).astype(jnp.float32)
        return idx, w, labels

    idx, w, labels = jax.block_until_ready(make_data(jax.random.key(0)))
    feats = T.SparseFeatures(idx, None, dim=d)
    batch = T.LabeledBatch(feats, labels, jnp.zeros((n,), jnp.float32),
                           jnp.ones((n,), jnp.float32))
    mesh = make_mesh()
    obj = make_objective("logistic")
    # distributed (shard-stacked) view for the fit; LOCAL view for the
    # bare csc-apply pass (csc_transpose_apply runs per-shard inside
    # shard_map — the stacked arrays are not its interface)
    csc = jax.block_until_ready(build_csc(obj, batch, mesh))
    csc_local = jax.block_until_ready(
        jax.jit(T.build_csc_transpose, static_argnums=(2,))(idx, None, d))
    d_vec = jax.block_until_ready(
        jax.random.normal(jax.random.key(9), (n,), jnp.float32))

    results = {}
    for mode in ("scalar", "vector"):
        T.set_gather_mode(mode)  # invalidates traced caches: fresh compiles

        # arrays enter via ARGUMENTS, never closures: a closed-over device
        # array becomes a program constant baked into the executable
        # (82M nnz of constants at the bench shape)
        @jax.jit
        def margins_pass(salt, f_, w_):
            return T.margins(f_, w_ + salt).sum()

        @jax.jit
        def csc_pass(salt, c_, dv):
            return T.csc_transpose_apply(c_, dv + salt).sum()

        def fit_pass(salt):
            res = fit_distributed(
                obj, batch, mesh, jnp.zeros((d,), jnp.float32) + salt,
                l2=1.0, optimizer="lbfgs",
                config=OptimizerConfig(max_iters=5, tolerance=0.0),
                sparse_grad="csc", precomputed_csc=csc)
            return res.value

        r = {
            "margins_ms": timed(margins_pass, feats, w) * 1e3,
            "csc_apply_ms": timed(csc_pass, csc_local, d_vec) * 1e3,
            "fit5_ms": timed(fit_pass) * 1e3,
        }
        results[mode] = r
        print(f"{mode}: " + "  ".join(f"{k_}={v:.2f}" for k_, v in r.items()),
              flush=True)
    T.set_gather_mode("auto")

    speedup = {k_: results["scalar"][k_] / results["vector"][k_]
               for k_ in results["scalar"]}
    print(json.dumps({
        "metric": "vector_gather_speedup",
        "platform": platform,
        "scalar_ms": results["scalar"],
        "vector_ms": results["vector"],
        "speedup": speedup,
    }), flush=True)


if __name__ == "__main__":
    main()
