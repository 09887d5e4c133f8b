"""Test scaffolding: 8 virtual CPU devices, f64 enabled for math-parity tests.

The moral equivalent of the reference's ``SparkTestUtils`` local-mode
SparkSession (SURVEY.md §8): "distributed" code is exercised on
``--xla_force_host_platform_device_count=8`` CPU devices without real TPUs.
Must run before jax initializes, hence the env mutation at import time.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

# the tests run on the CPU whatever the caller's environment says
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture
def rng():
    return np.random.default_rng(0)


@pytest.fixture(scope="session")
def saved_game_model(tmp_path_factory):
    """A small trained GAME model (fixed + per-user random effect) saved
    to disk in the io/model_io layout, shared by the serving tests.
    Returns (model_dir, bundle) where bundle carries the raw arrays and
    the in-memory model for parity references."""
    import jax.numpy as jnp

    from photon_ml_tpu.game.descent import (
        CoordinateConfig, CoordinateDescent, make_game_dataset,
    )
    from photon_ml_tpu.io.index_map import IndexMap
    from photon_ml_tpu.io.model_io import load_game_model, save_game_model

    r = np.random.default_rng(11)
    n, d_fix, d_re, n_entities = 160, 6, 4, 9
    Xg = r.normal(size=(n, d_fix))
    Xu = r.normal(size=(n, d_re))
    uid = r.integers(0, n_entities, n)
    y = (r.random(n) < 0.5).astype(float)
    ds = make_game_dataset({"g": Xg, "u": Xu}, y,
                           entity_ids={"userId": uid})
    cd = CoordinateDescent(
        [CoordinateConfig("fixed", feature_shard="g", reg_type="l2",
                          reg_weight=1.0),
         CoordinateConfig("per-user", coordinate_type="random",
                          feature_shard="u", entity_column="userId",
                          reg_type="l2", reg_weight=1.0)],
        task="logistic", dtype=jnp.float64)
    model, _ = cd.run(ds)
    model_dir = str(tmp_path_factory.mktemp("serving") / "model")
    save_game_model(model, model_dir, {
        "g": IndexMap({f"g{j}": j for j in range(d_fix)}),
        "u": IndexMap({f"u{j}": j for j in range(d_re)}),
    })
    bundle = {
        "Xg": Xg, "Xu": Xu, "uid": uid, "d_fix": d_fix, "d_re": d_re,
        "n_entities": n_entities, "loaded": load_game_model(model_dir),
    }
    return model_dir, bundle


def serving_rows(bundle, row_idx, entity_ids=None, offsets=None):
    """Request rows (the serving JSON shape) for a slice of the shared
    fixture's data — used by several serving test files."""
    Xg, Xu = bundle["Xg"], bundle["Xu"]
    uid = bundle["uid"] if entity_ids is None else entity_ids
    rows = []
    for pos, i in enumerate(row_idx):
        feats = [{"name": f"g{j}", "value": float(Xg[i, j])}
                 for j in range(bundle["d_fix"])]
        feats += [{"name": f"u{j}", "value": float(Xu[i, j])}
                  for j in range(bundle["d_re"])]
        row = {"features": feats, "entityIds": {"userId": str(uid[i])}}
        if offsets is not None:
            row["offset"] = float(offsets[pos])
        rows.append(row)
    return rows


@pytest.fixture
def game_dataset_pair():
    """Small logistic train/validation GameDataset pair (shared by tuning
    and estimator tests)."""
    from photon_ml_tpu.game.descent import make_game_dataset

    r = np.random.default_rng(7)
    n, d = 500, 8
    X = r.normal(size=(n, d))
    w = r.normal(size=d)
    y = (r.random(n) < 1 / (1 + np.exp(-X @ w))).astype(float)
    tr, va = np.arange(350), np.arange(350, n)
    return (make_game_dataset(X[tr], y[tr]), make_game_dataset(X[va], y[va]))
