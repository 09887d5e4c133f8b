"""Random-effect tables that live on the device, bucketed by size.

* a second ``CoordinateDescent.run`` over one ``dataset_cache`` compiles
  nothing, uploads nothing but the run's offsets and a few scalars, and
  returns the first run's model;
* the size buckets under a heavy skew: every row in exactly one slot,
  padding under half of all slots, and coefficients that do not depend on
  the grouping (held against the equal-count layout the buckets replaced,
  kept here as a loop);
* the solver's entity block follows a byte budget, and blocks solved
  separately add up to the bucket solved whole.
"""

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from photon_ml_tpu.game import random_effect as re_mod
from photon_ml_tpu.game.data import (
    RandomEffectTrainData,
    REBucket,
    build_random_effect_data,
    build_score_view,
    host_sparse_from_features,
    ladder_splits,
    materialize_ones,
)
from photon_ml_tpu.game.descent import (
    CoordinateConfig,
    CoordinateDescent,
    make_game_dataset,
)
from photon_ml_tpu.game.random_effect import (
    block_entities,
    entity_bytes,
    place_random_effect,
    score_random_effect,
    train_random_effect,
)
from photon_ml_tpu.obs.metrics import training_metrics
from photon_ml_tpu.optimize import OptimizerConfig


def skewed(rng, n=1500, entities=40, d=9):
    """Zipf-like rows per entity: a few heavy entities, many of 1-3."""
    p = 1.0 / (np.arange(entities) + 1.0) ** 1.3
    ids = rng.choice(entities, size=n, p=p / p.sum())
    X = rng.normal(size=(n, d)) * (rng.random((n, d)) < 0.5)
    X[:, 0] = 1.0
    y = (rng.random(n) < 0.5).astype(float)
    return X, y, ids


def equal_count_layout(X, y, w, ids, num_buckets) -> RandomEffectTrainData:
    """The layout before the ladder, entity by entity: ``num_buckets``
    groups of equal entity count by ascending row count, each padded to
    its largest member."""
    sp = materialize_ones(host_sparse_from_features(X))
    uniq, codes = np.unique(ids, return_inverse=True)
    rows_of = [np.flatnonzero(codes == e) for e in range(len(uniq))]
    maps = []
    for rows in rows_of:
        feats = np.unique(sp.indices[rows][sp.values[rows] != 0])
        maps.append({int(g): i for i, g in enumerate(feats)})
    counts = np.array([len(r) for r in rows_of])
    order = np.argsort(counts, kind="mergesort")
    buckets, slots = [], {}
    for b, members in enumerate(np.array_split(order, num_buckets)):
        E, N = len(members), int(counts[members].max())
        D = max(len(maps[e]) for e in members)
        k = sp.indices.shape[1]
        idx = np.zeros((E, N, k), np.int32)
        val = np.zeros((E, N, k))
        lab, wts = np.zeros((E, N)), np.zeros((E, N))
        sidx = np.full((E, N), -1, np.int32)
        proj = np.full((E, D), -1, np.int32)
        for r, e in enumerate(members):
            rows, lm = rows_of[e], maps[e]
            for p, i in enumerate(rows):
                for j in range(k):
                    if sp.values[i, j] != 0:
                        idx[r, p, j] = lm[int(sp.indices[i, j])]
                        val[r, p, j] = sp.values[i, j]
                lab[r, p], wts[r, p], sidx[r, p] = y[i], w[i], i
            for g, s in lm.items():
                proj[r, s] = g
            slots[uniq[e]] = (b, r)
        buckets.append(REBucket([uniq[e] for e in members], idx, val, lab,
                                wts, sidx, proj, [maps[e] for e in members]))
    return RandomEffectTrainData("re", buckets, len(y), slots)


def by_entity(data, coefficients):
    """{entity id: {global feature id: coefficient}}: a layout-free view."""
    out = {}
    for bucket, W in zip(data.buckets, coefficients):
        W = np.asarray(W)
        for r, eid in enumerate(bucket.entity_ids):
            out[eid] = {int(g): W[r, s]
                        for s, g in enumerate(bucket.projection[r]) if g >= 0}
    return out


# -- buckets by size -------------------------------------------------------

@pytest.mark.parametrize("num_buckets", [1, 2, 3, 4, 16])
def test_bucket_invariants_under_skew(rng, num_buckets):
    X, y, ids = skewed(rng)
    n = len(y)
    data = build_random_effect_data(X, y, np.ones(n), ids,
                                    num_buckets=num_buckets)
    assert 1 <= len(data.buckets) <= num_buckets
    assert data.num_entities == len(np.unique(ids))
    seen = np.concatenate([b.sample_idx[b.sample_idx >= 0]
                           for b in data.buckets])
    assert sorted(seen.tolist()) == list(range(n))  # one slot a row
    for b in data.buckets:  # an entity's rows are its own
        rows = np.where(b.sample_idx >= 0, b.sample_idx, 0)
        same = ids[rows] == np.asarray(b.entity_ids)[:, None]
        assert same[b.sample_idx >= 0].all()
        assert (b.weights[b.sample_idx < 0] == 0).all()
    slots = sum(b.sample_idx.size for b in data.buckets)
    if num_buckets >= 16:  # a rung an octave: under half is padding
        assert (slots - n) / slots < 0.5
        counts = np.bincount(ids)
        assert len(data.buckets) == len(np.unique(
            np.ceil(np.log2(counts[counts > 0]))))
    if num_buckets == 1:  # one bucket pads everyone to the largest
        assert data.buckets[0].sample_idx.shape[1] >= np.bincount(ids).max()


def test_ladder_merges_the_cheapest_rungs_first():
    counts = np.array([1, 1, 1, 2, 2, 3, 9, 9, 60, 64, 1000])
    assert ladder_splits(counts, 16) == [0, 3, 5, 6, 8, 10, 11]
    # 4 buckets: the small rungs go together, the giant stays alone
    cuts = ladder_splits(counts, 4)
    assert len(cuts) == 5 and cuts[-2] == 10
    assert ladder_splits(counts, 1) == [0, 11]
    assert ladder_splits(np.zeros(0, np.int64), 4) == [0, 0]


@pytest.mark.parametrize("optimizer", ["lbfgs", "newton"])
def test_coefficients_do_not_depend_on_the_grouping(rng, optimizer):
    """The ladder's coefficients against the equal-count layout's."""
    X, y, ids = skewed(rng, n=400, entities=17, d=6)
    n = len(y)
    offs = rng.normal(size=n) * 0.1
    kw = dict(l2=0.6, optimizer=optimizer, dtype=jnp.float64,
              config=OptimizerConfig(max_iters=80, tolerance=1e-12))
    new = build_random_effect_data(X, y, np.ones(n), ids)
    old = equal_count_layout(X, y, np.ones(n), ids, num_buckets=3)
    assert [b.sample_idx.shape for b in new.buckets] != [
        b.sample_idx.shape for b in old.buckets]
    got = by_entity(new, train_random_effect(new, offs, **kw).coefficients)
    want = by_entity(old, train_random_effect(old, offs, **kw).coefficients)
    assert got.keys() == want.keys()
    for eid in want:
        assert got[eid].keys() == want[eid].keys()
        np.testing.assert_allclose(
            [got[eid][g] for g in want[eid]], list(want[eid].values()),
            rtol=1e-7, atol=1e-9)
    # and the scores through each layout's own view
    s_new = score_random_effect(
        build_score_view(new, X, ids),
        train_random_effect(new, offs, **kw).coefficients, n, jnp.float64)
    s_old = score_random_effect(
        build_score_view(old, X, ids),
        train_random_effect(old, offs, **kw).coefficients, n, jnp.float64)
    np.testing.assert_allclose(s_new, s_old, rtol=1e-7, atol=1e-9)


def test_empty_bucket_is_carried_not_solved(rng):
    """A bucket with no entities: empty results, and no block loop of
    step zero (ROADMAP R2)."""
    X, y, ids = skewed(rng, n=200, entities=9, d=4)
    n = len(y)
    data = build_random_effect_data(X, y, np.ones(n), ids)
    b = data.buckets[0]
    empty = dataclasses.replace(
        b, entity_ids=[], indices=b.indices[:0], values=b.values[:0],
        labels=b.labels[:0], weights=b.weights[:0],
        sample_idx=b.sample_idx[:0], projection=b.projection[:0],
        local_maps=[])
    data = dataclasses.replace(data, buckets=[empty] + list(data.buckets))
    for optimizer in ("lbfgs", "newton"):
        fit = train_random_effect(data, np.zeros(n), l2=1.0,
                                  optimizer=optimizer)
        assert fit.coefficients[0].shape == (0, b.local_dim)
        assert fit.entities_solved == len(np.unique(ids))
        assert 0.0 <= fit.converged_fraction <= 1.0
    view = build_score_view(data, X, ids)
    assert view[0].sample_idx.shape[0] == 0
    scores = score_random_effect(view, fit.coefficients, n)
    assert scores.shape == (n,) and np.isfinite(np.asarray(scores)).all()


# -- the block from a byte budget ------------------------------------------

@pytest.mark.parametrize("optimizer", ["newton", "lbfgs"])
@pytest.mark.parametrize("N,k,D", [(8, 3, 4), (9254, 11, 21), (64, 5, 36)])
def test_block_never_exceeds_its_budget(optimizer, N, k, D):
    per = entity_bytes(N, k, D, 4, optimizer)
    assert per >= N * k * 8  # at least the rows themselves
    for budget in (1 << 16, 1 << 24, re_mod._RE_BLOCK_BYTES):
        for entities in (1, 7, 10_000_000):
            bs = block_entities(entities, per, budget=budget)
            assert 1 <= bs <= entities
            assert bs == 1 or bs * per <= budget
            # a mesh rounds up to its devices, by less than one round
            assert 0 <= block_entities(entities, per, 4, budget) - bs < 4


@pytest.mark.parametrize("optimizer", ["newton", "lbfgs"])
def test_blocks_add_up_to_the_bucket(rng, monkeypatch, optimizer):
    """Entity blocks solved separately give the bucket solved whole."""
    X, y, ids = skewed(rng, n=600, entities=23, d=5)
    n = len(y)
    data = build_random_effect_data(X, y, np.ones(n), ids, num_buckets=2)
    kw = dict(l2=0.5, optimizer=optimizer, dtype=jnp.float64,
              compute_variance="diagonal",
              config=OptimizerConfig(max_iters=50, tolerance=1e-12))
    whole = train_random_effect(data, np.zeros(n), **kw)
    assert whole.blocks == len(data.buckets)
    b0 = data.buckets[0]
    per = entity_bytes(b0.indices.shape[1], b0.indices.shape[2],
                       b0.local_dim, 8, optimizer)
    monkeypatch.setattr(re_mod, "_RE_BLOCK_BYTES", 3 * per)
    parts = train_random_effect(data, np.zeros(n), **kw)
    assert parts.blocks > whole.blocks
    for a, b in zip(whole.coefficients, parts.coefficients):
        np.testing.assert_allclose(a, b, rtol=1e-12, atol=1e-12)
    for a, b in zip(whole.variances, parts.variances):
        np.testing.assert_allclose(a, b, rtol=1e-12, atol=1e-12)
    assert parts.counts() == whole.counts()


def test_dense_blocks_are_kept_up_to_their_budget(rng, monkeypatch):
    X, y, ids = skewed(rng, n=300, entities=11, d=4)
    n = len(y)
    data = build_random_effect_data(X, y, np.ones(n), ids)
    kw = dict(l2=0.5, optimizer="newton", dtype=jnp.float32)
    placed = place_random_effect(data, jnp.float32)
    first = train_random_effect(data, np.zeros(n), placed=placed, **kw)
    assert all(len(b.dense) == 1 for b in placed.buckets)
    assert placed.dense_bytes == sum(
        x.nbytes for b in placed.buckets for x in b.dense.values())
    again = train_random_effect(data, np.zeros(n), placed=placed, **kw)
    for a, b in zip(first.coefficients, again.coefficients):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # no room: every solve densifies again, to the same coefficients
    monkeypatch.setattr(re_mod, "_RE_DENSE_KEEP_BYTES", 0)
    tight = place_random_effect(data, jnp.float32)
    third = train_random_effect(data, np.zeros(n), placed=tight, **kw)
    assert all(not b.dense for b in tight.buckets)
    for a, b in zip(first.coefficients, third.coefficients):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# -- a second run builds nothing -------------------------------------------

def glmix_problem(rng, n=900):
    Xg = rng.normal(size=(n, 7))
    Xu, y, users = skewed(rng, n=n, entities=25, d=5)
    Xi, _, items = skewed(rng, n=n, entities=8, d=4)
    return make_game_dataset({"g": Xg, "u": Xu, "i": Xi}, y,
                             entity_ids={"user": users, "item": items})


def glmix_configs(l2, optimizer="newton"):
    random = dict(coordinate_type="random", optimizer=optimizer, max_iters=4,
                  tolerance=0.0, reg_type="l2", reg_weight=l2,
                  active_set=False)
    return [CoordinateConfig(name="fixed", feature_shard="g", max_iters=3,
                             optimizer="lbfgs", tolerance=0.0, reg_type="l2",
                             reg_weight=l2),
            CoordinateConfig(name="per-user", feature_shard="u",
                             entity_column="user", **random),
            CoordinateConfig(name="per-item", feature_shard="i",
                             entity_column="item", **random)]


def model_vectors(model):
    out = [np.asarray(model.coordinates["fixed"].model.coefficients.means)]
    for name in ("per-user", "per-item"):
        out += [np.asarray(b.coefficients)
                for b in model.coordinates[name].buckets]
    return out


@pytest.mark.parametrize("optimizer", ["newton", "lbfgs"])
def test_second_run_compiles_and_uploads_nothing(rng, optimizer):
    train = glmix_problem(rng)
    n = train.num_samples
    cache = {}
    tm = training_metrics()

    def run(l2):
        before = tm.transfer_counts()
        model, history = CoordinateDescent(
            glmix_configs(l2, optimizer), n_iterations=2, dtype=jnp.float32,
            dataset_cache=cache).run(train)
        after = tm.transfer_counts()
        return model, history, after.since(before)

    first, _, moved1 = run(1.0)
    keys = set(cache)
    # tables, views, the shard
    assert moved1["compiles"] > 0 and moved1["h2d_bytes"] > n * 4 * 10
    second, history, moved2 = run(1.0)
    assert set(cache) == keys  # nothing was built again
    assert moved2["compiles"] == 0
    # the run's offsets, and the regularisation scalars of each solve
    assert n * 4 <= moved2["h2d_bytes"] <= n * 4 + 256
    # the model comes to the host once, with a few scalars a step
    assert moved2["d2h_bytes"] < 64 * 1024
    for a, b in zip(model_vectors(first), model_vectors(second)):
        np.testing.assert_array_equal(a, b)
    # a grid point: other weights, the same programs and tables
    _, history, moved3 = run(1.0 + 1e-3)
    assert moved3["compiles"] == 0 and moved3["h2d_bytes"] <= n * 4 + 256
    # the sweep records say the same of each sweep
    for rec in tm.sweep_records()[-2:]:
        assert rec["compiles"] == 0 and rec["h2d_bytes"] <= 64
        assert [c["name"] for c in rec["coordinates"]] == [
            "fixed", "per-user", "per-item"]
        for c in rec["coordinates"][1:]:
            assert c["real_slots"] == n
            assert c["entities_solved"] > 0 and c["iterations_max"] <= 4
            assert c["fit_seconds"] > 0 and c["rescore_seconds"] > 0
        assert np.isfinite(rec["train_loss"])
    assert history[-1]["train_loss"] == tm.sweep_records()[-1]["train_loss"]


def test_sweep_spans_reach_a_trace_export(rng, tmp_path):
    """What ``PHOTON_TRACE=<dir>`` writes for a run: the sweep's spans
    with their arguments."""
    import json

    from photon_ml_tpu.obs import trace as obs_trace

    train = glmix_problem(rng, n=300)
    obs_trace.start(str(tmp_path), export_thread=False)
    try:
        CoordinateDescent(glmix_configs(1.0), n_iterations=1,
                          dtype=jnp.float32, dataset_cache={}).run(train)
    finally:
        obs_trace.stop()
    with open(tmp_path / "trace-rank0.json") as f:
        events = json.load(f)["traceEvents"]
    spans = {}
    for e in events:
        spans.setdefault(e["name"], []).append(e.get("args", {}))
    for name in ("cd.sweep", "cd.coordinate", "re.solve", "re.solve.bucket",
                 "re.rescore", "fe.rescore", "re.place"):
        assert name in spans, sorted(spans)
    assert len(spans["cd.coordinate"]) == 3
    solve = spans["re.solve"][0]
    assert solve["entities"] > 0 and solve["blocks"] >= solve["buckets"]
    bucket = spans["re.solve.bucket"][0]
    assert {"bucket", "entities", "N", "D", "optimizer"} <= set(bucket)
    assert all(p["bytes"] > 0 for p in spans["re.place"])
