"""Quickstart: the three API layers in one runnable script (CPU-friendly).

    python examples/quickstart.py

1. Functional core  — objective + optimizer on a sparse batch.
2. GAME estimator   — fixed effect + per-user random effect, scored back.
3. Driver surface   — the same model trained through the CLI entry point
                      (what production jobs call via spark-submit's
                      equivalent, `photon-game-train`).

Everything here runs in seconds on CPU; on a TPU host the identical code
picks the measured-fastest strategies automatically ('auto' sparse
gradients / solvers — docs/PERF.md).
"""

import json
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np
import jax.numpy as jnp


def part1_functional_core():
    from photon_ml_tpu.ops.objective import make_objective
    from photon_ml_tpu.optimize import OptimizerConfig, get_optimizer
    from photon_ml_tpu.types import LabeledBatch, SparseFeatures

    rng = np.random.default_rng(0)
    n, d, k = 4096, 512, 8
    idx = jnp.asarray(rng.integers(0, d, (n, k)), jnp.int32)
    w_true = rng.normal(size=d) * 0.5
    logits = w_true[np.asarray(idx)].sum(axis=1)
    y = (rng.random(n) < 1 / (1 + np.exp(-logits))).astype(np.float32)

    batch = LabeledBatch(
        SparseFeatures(idx, None, dim=d),  # implicit-ones one-hot rows
        jnp.asarray(y),
        jnp.zeros((n,), jnp.float32),
        jnp.ones((n,), jnp.float32),
    )
    obj = make_objective("logistic")
    res = get_optimizer("lbfgs")(
        lambda w: obj.value_and_grad(w, batch, 1.0),
        jnp.zeros((d,), jnp.float32),
        OptimizerConfig(max_iters=50, tolerance=1e-8),
    )
    corr = np.corrcoef(np.asarray(res.w), w_true)[0, 1]
    print(f"[1] L-BFGS converged={bool(res.converged)} "
          f"iters={int(res.iterations)} corr(w, w_true)={corr:.3f}")


def part2_game_estimator():
    from photon_ml_tpu.estimators import GameEstimator
    from photon_ml_tpu.game.data import HostSparse
    from photon_ml_tpu.game.descent import CoordinateConfig, make_game_dataset

    rng = np.random.default_rng(1)
    n, d, k, users = 4000, 256, 6, 80
    idx = rng.integers(0, d, (n, k)).astype(np.int32)
    uid = rng.integers(0, users, n)
    per_user_bias = rng.normal(size=users)
    y = (rng.random(n) < 1 / (1 + np.exp(-per_user_bias[uid]))).astype(float)

    train = make_game_dataset({"global": HostSparse(idx, None, d)}, y,
                              entity_ids={"user": uid})
    est = GameEstimator(task="logistic", n_iterations=2, evaluators=["auc"])
    results = est.fit(train, None, config_grid=[[
        CoordinateConfig("fixed", coordinate_type="fixed", reg_type="l2",
                         reg_weight=1.0, max_iters=20),
        CoordinateConfig("per_user", coordinate_type="random",
                         entity_column="user", reg_type="l2", reg_weight=1.0),
    ]])
    best = est.select_best(results)
    from photon_ml_tpu.game.scoring import score_game_model

    scores = np.asarray(score_game_model(
        best.model, {"global": HostSparse(idx, None, d)}, {"user": uid}))
    from photon_ml_tpu.evaluation import get_evaluator

    auc = get_evaluator("auc").evaluate(scores, y, np.ones(n))
    print(f"[2] GAME fixed+per_user trained; train AUC={auc:.3f}")


def part3_driver_surface():
    from photon_ml_tpu.cli.game_training_driver import main as train_main
    from photon_ml_tpu.io.data_reader import write_training_examples

    rng = np.random.default_rng(2)
    n, vocab = 2000, 60
    rows, uid = [], rng.integers(0, 40, n)
    bias = rng.normal(size=40)
    for i in range(n):
        cols = rng.choice(vocab, size=4, replace=False)
        rows.append([(f"f{c}", "", 1.0) for c in cols])
    y = (rng.random(n) < 1 / (1 + np.exp(-bias[uid]))).astype(float)

    with tempfile.TemporaryDirectory() as tmp:
        path = os.path.join(tmp, "train.avro")
        write_training_examples(path, rows, y,
                                entity_ids={"userId": uid.astype(str)})
        coords = [
            {"name": "fixed", "coordinate_type": "fixed",
             "reg_type": "l2", "reg_weight": 1.0, "max_iters": 20},
            {"name": "per_user", "coordinate_type": "random",
             "entity_column": "userId", "reg_type": "l2", "reg_weight": 1.0},
        ]
        cpath = os.path.join(tmp, "coords.json")
        with open(cpath, "w") as f:
            json.dump(coords, f)
        out = os.path.join(tmp, "out")
        rc = train_main([
            "--train-data", path, "--output-dir", out,
            "--task", "logistic_regression", "--coordinates", cpath,
            "--n-iterations", "2", "--checkpoint", "--auto-resume",
        ])
        saved = os.path.exists(os.path.join(out, "best", "metadata.json"))
        print(f"[3] driver rc={rc} model_saved={saved}")


if __name__ == "__main__":
    part1_functional_core()
    part2_game_estimator()
    part3_driver_surface()
