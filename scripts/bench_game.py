"""GAME / random-effect hardware bench (VERDICT r2 #5).

Times the second HOT call stack (SURVEY.md §4.3) on the current backend:

1. ``re_solve``: the vmap-of-solvers random-effect path — entities/sec for
   one bucketed solve sweep at realistic shapes (many small entities).
2. ``cd_iteration``: one full coordinate-descent iteration — fixed effect
   (sparse, margin-space L-BFGS) + two random-effect coordinates —
   wall-clock, compile excluded (one warm iteration first).

Prints one JSON line per metric (these feed docs/PERF.md, not the driver's
single-line BENCH contract — bench.py remains the headline).
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def _arm_watchdog(timeout_s: float = 1500.0) -> None:
    """A wedged run dies loudly instead of hanging."""
    import threading

    def fire():
        print(json.dumps({"metric": "game_bench", "value": 0.0,
                          "unit": f"TIMEOUT after {timeout_s:.0f}s"}),
              flush=True)
        os._exit(2)

    t = threading.Timer(timeout_s, fire)
    t.daemon = True
    t.start()


def main():
    _arm_watchdog(float(os.environ.get("BENCH_TIMEOUT_S", 1500)))
    import jax
    import jax.numpy as jnp

    from photon_ml_tpu.game.data import REBucket, RandomEffectTrainData
    from photon_ml_tpu.game.descent import (
        CoordinateConfig, CoordinateDescent, make_game_dataset,
    )
    from photon_ml_tpu.game.random_effect import train_random_effect
    from photon_ml_tpu.optimize import OptimizerConfig

    platform = jax.devices()[0].platform
    if platform == "cpu":
        n_entities, rows_per, local_d = 2000, 32, 16
        n_fixed, fixed_d, k = 1 << 14, 1 << 12, 24
    else:
        # per-member scale: 100k entities x 64 rows x 32 local features.
        # Everything large is synthesized ON DEVICE (a bulk host->device
        # transfer would time the pipe) and the host-built CD dataset is
        # kept to tens of MB.
        n_entities, rows_per, local_d = 100_000, 64, 32
        n_fixed, fixed_d, k = 1 << 17, 1 << 16, 39

    rng = np.random.default_rng(0)

    # -- 1. raw vmap-of-solvers throughput --------------------------------
    # One size bucket of E entities, padded layout [E, N, kk] — built
    # directly on device (the host path build_random_effect_data is
    # ingestion code; its output layout is what matters to the solver).
    n_re = n_entities * rows_per
    kk = 8  # nonzeros per row within the local_d-dim subspace

    @jax.jit
    def make_re(key):
        k_idx, k_val, k_lab = jax.random.split(key, 3)
        idx = jax.random.randint(
            k_idx, (n_entities, rows_per, kk), 0, local_d, jnp.int32)
        val = jax.random.normal(k_val, (n_entities, rows_per, kk),
                                jnp.float32)
        lab = (jax.random.uniform(k_lab, (n_entities, rows_per))
               < 0.5).astype(jnp.float32)
        wts = jnp.ones((n_entities, rows_per), jnp.float32)
        sidx = jnp.arange(n_re, dtype=jnp.int32).reshape(
            n_entities, rows_per)
        proj = jnp.broadcast_to(jnp.arange(local_d, dtype=jnp.int32),
                                (n_entities, local_d))
        return idx, val, lab, wts, sidx, proj

    idx, val, lab, wts, sidx, proj = jax.block_until_ready(
        make_re(jax.random.key(0)))
    bucket = REBucket(entity_ids=np.arange(n_entities), indices=idx,
                      values=val, labels=lab, weights=wts, sample_idx=sidx,
                      projection=proj, local_maps=[])
    data = RandomEffectTrainData("random", [bucket], n_re, {})
    offsets = jnp.zeros((n_re,), jnp.float32)
    cfg = OptimizerConfig(max_iters=10, tolerance=0.0)

    def re_solve(l2, optimizer):
        # l2 is a traced scalar: varying it between warm-up and timed run
        # makes the timed call a distinct computation without
        # recompiling. train_random_effect np.asarray()s the coefficients,
        # which host-syncs the result.
        return train_random_effect(data, offsets, l2=l2, config=cfg,
                                   optimizer=optimizer)

    # both RE solvers: the vmapped sparse L-BFGS and the batched dense
    # Newton (einsum/MXU) — which wins is the hardware question
    rates = {}
    for opt_name in ("lbfgs", "newton"):
        re_solve(0.5, opt_name)  # compile + warm-up
        t0 = time.perf_counter()
        fit = re_solve(0.5000001, opt_name)
        dt = time.perf_counter() - t0
        assert float(np.abs(fit.coefficients[0]).sum()) > 0
        rates[opt_name] = n_entities / dt
        print(json.dumps({
            "metric": f"game_re_{opt_name}_entities_per_sec",
            "value": round(n_entities / dt, 1),
            "unit": (f"entities/sec ({platform}, E={n_entities}, "
                     f"rows/entity={rows_per}, d_local={local_d}, "
                     f"optimizer={opt_name}, mean_iters="
                     f"{fit.mean_iterations:.1f})"),
        }), flush=True)
    winner = max(rates, key=rates.get)
    print(f"suggested _RE_SOLVER_DEFAULT entry: '{platform}': '{winner}' "
          f"({rates[winner]/max(min(rates.values()), 1e-9):.2f}x — wire in "
          "photon_ml_tpu/game/random_effect.py and add the platform to "
          "_RE_SOLVER_MEASURED)", flush=True)

    # -- 2. one full CD iteration (fixed + 2 random effects) --------------
    users = rng.integers(0, n_entities, size=n_fixed)
    items = rng.integers(0, max(n_entities // 10, 10), size=n_fixed)
    Xf_idx = rng.integers(0, fixed_d, size=(n_fixed, k)).astype(np.int32)
    from photon_ml_tpu.game.data import HostSparse

    # implicit-ones layout: no values array -> half the host->device bytes
    feats = HostSparse(Xf_idx, None, fixed_d)
    y = (rng.random(n_fixed) < 0.5).astype(np.float64)
    train = make_game_dataset({"global": feats}, y,
                              entity_ids={"user": users, "item": items})
    coord_configs = [
            CoordinateConfig("fixed", coordinate_type="fixed",
                             reg_type="l2", reg_weight=1.0, max_iters=10,
                             tolerance=0.0),
            CoordinateConfig("per_user", coordinate_type="random",
                             entity_column="user", max_iters=5,
                             num_buckets=2, reg_type="l2", reg_weight=1.0),
            CoordinateConfig("per_item", coordinate_type="random",
                             entity_column="item", max_iters=5,
                             num_buckets=2, reg_type="l2", reg_weight=1.0),
    ]
    cd = CoordinateDescent(coord_configs, task="logistic", n_iterations=3)
    # ONE run of 3 CD iterations: iteration 0 pays data prep + compiles
    # (states/jits are per-run), the LAST iteration is the warm number
    t0 = time.perf_counter()
    _, hist = cd.run(train)
    total = time.perf_counter() - t0
    n_coords = len(coord_configs)
    last = hist[-n_coords:]
    warm_iter = sum(r["seconds"] for r in last)
    per_coord = str([round(r["seconds"], 2) for r in last])
    print(json.dumps({
        "metric": "game_cd_iteration_seconds",
        "value": round(warm_iter, 3),
        "unit": (f"s/warm-CD-iteration ({platform}, n={n_fixed}, "
                 f"d={fixed_d}, 2 RE coords E~{n_entities}; full 3-iter run "
                 f"incl prep+compile={total:.1f}s; warm per-coord s: "
                 f"{per_coord}"),
    }), flush=True)


if __name__ == "__main__":
    main()
