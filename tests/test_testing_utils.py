"""The test-scaffolding module itself (photon-test-utils role, SURVEY.md
§3.5): generators must produce learnable data with the promised structure."""

import os

import numpy as np

from photon_ml_tpu.testing import (
    game_dataset_from_synthetic,
    synthetic_game_data,
    synthetic_glm_data,
    write_game_avro_fixture,
)


def test_synthetic_glm_learnable():
    from sklearn.metrics import roc_auc_score

    import jax.numpy as jnp

    from photon_ml_tpu.ops.objective import make_objective
    from photon_ml_tpu.optimize import OptimizerConfig, get_optimizer
    from photon_ml_tpu.types import make_batch

    data = synthetic_glm_data(600, 12, with_offsets=True, with_weights=True)
    batch = make_batch(data.X, data.y, data.offsets, data.weights,
                       dtype=jnp.float64)
    obj = make_objective("logistic")
    res = get_optimizer("lbfgs")(
        lambda w: obj.value_and_grad(w, batch, 1.0),
        jnp.zeros(12, jnp.float64), OptimizerConfig()
    )
    assert bool(res.converged)
    auc = roc_auc_score(data.y, np.asarray(obj.predict(res.w, batch)))
    assert auc > 0.8


def test_synthetic_game_crossed_effects_learnable():
    from photon_ml_tpu.estimators import GameTransformer
    from photon_ml_tpu.evaluation import get_evaluator
    from photon_ml_tpu.game.descent import CoordinateConfig, CoordinateDescent

    data = synthetic_game_data({"userId": 12, "itemId": 8}, seed=3)
    assert set(data.entity_ids) == {"userId", "itemId"}
    assert data.random_effects["itemId"].shape == (8, 3)
    train = game_dataset_from_synthetic(data)
    cd = CoordinateDescent(
        [
            CoordinateConfig("fixed", coordinate_type="fixed",
                             feature_shard="global", reg_type="l2",
                             reg_weight=0.1, max_iters=60),
            CoordinateConfig("per-user", coordinate_type="random",
                             feature_shard="entity", entity_column="userId",
                             reg_type="l2", reg_weight=1.0, max_iters=40),
            CoordinateConfig("per-item", coordinate_type="random",
                             feature_shard="entity", entity_column="itemId",
                             reg_type="l2", reg_weight=1.0, max_iters=40),
        ],
        task="logistic", n_iterations=2,
    )
    model, _ = cd.run(train)
    scores = GameTransformer(model).transform(train)
    auc = get_evaluator("auc").evaluate(np.asarray(scores), train.labels,
                                        train.weights)
    assert auc > 0.8, auc


def test_avro_fixture_roundtrip(tmp_path):
    from photon_ml_tpu.io.avro import read_avro_file

    data = synthetic_game_data({"userId": 5}, seed=1)
    path = str(tmp_path / "fixture.avro")
    write_game_avro_fixture(path, data)
    records, _ = read_avro_file(path)
    assert len(records) == len(data.labels)
    r0 = records[0]
    names = {f["name"] for f in r0["features"]}
    # both shards present under their prefixes
    assert any(n.startswith("g") for n in names)
    assert any(n.startswith("u") for n in names)
    assert r0["metadataMap"]["userId"] == str(data.entity_ids["userId"][0])


def test_profile_trace_writes_output(tmp_path):
    import jax.numpy as jnp

    from photon_ml_tpu.obs.trace import profile, span

    out = str(tmp_path / "trace")
    with profile(out):
        with span("tiny-op"):
            (jnp.ones((8, 8)) @ jnp.ones((8, 8))).block_until_ready()
    found = []
    for root, _, files in os.walk(out):
        found += files
    assert found, "profiler trace produced no files"
    # no-op path
    with profile(None):
        pass
