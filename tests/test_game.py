"""GAME layer tests: random-effect data building, vmapped entity solves,
score views, coordinate descent on synthetic mixed-effect data (the
reference's GameTestUtils-style synthetic structure — SURVEY.md §8)."""

import jax.numpy as jnp
import numpy as np
import pytest

from photon_ml_tpu.game.data import build_random_effect_data, build_score_view
from photon_ml_tpu.game.descent import CoordinateConfig, CoordinateDescent, make_game_dataset
from photon_ml_tpu.game.random_effect import score_random_effect, train_random_effect
from photon_ml_tpu.game.sampling import down_sample
from photon_ml_tpu.optimize import OptimizerConfig


def _mixed_effect_data(rng, n_users=20, rows_per_user=(5, 40), d_global=8, d_user=4):
    """fixed effect on global features + per-user effect on user features."""
    w_fixed = rng.normal(size=d_global)
    rows = []
    Xg_all, Xu_all, y_all, uid_all = [], [], [], []
    user_coefs = rng.normal(size=(n_users, d_user)) * 1.5
    for u in range(n_users):
        m = rng.integers(*rows_per_user)
        Xg = rng.normal(size=(m, d_global))
        Xu = rng.normal(size=(m, d_user))
        margin = Xg @ w_fixed + Xu @ user_coefs[u]
        y = (rng.random(m) < 1 / (1 + np.exp(-margin))).astype(float)
        Xg_all.append(Xg); Xu_all.append(Xu); y_all.append(y)
        uid_all.append(np.full(m, u))
    return (np.concatenate(Xg_all), np.concatenate(Xu_all),
            np.concatenate(y_all), np.concatenate(uid_all), w_fixed, user_coefs)


def test_re_data_roundtrip(rng):
    n, d = 60, 10
    X = rng.normal(size=(n, d)) * (rng.random((n, d)) < 0.5)
    y = (rng.random(n) < 0.5).astype(float)
    w = rng.random(n) + 0.5
    ids = rng.integers(0, 7, size=n)
    data = build_random_effect_data(X, y, w, ids, num_buckets=3)
    assert data.num_entities == len(np.unique(ids))
    # every row appears exactly once across buckets (no cap -> all active)
    seen = np.concatenate([b.sample_idx[b.sample_idx >= 0] for b in data.buckets])
    assert sorted(seen.tolist()) == list(range(n))
    # labels/weights round-trip and local features match global through projection
    for b in data.buckets:
        for r in range(b.num_entities):
            for j in range(b.sample_idx.shape[1]):
                i = b.sample_idx[r, j]
                if i < 0:
                    continue
                assert b.labels[r, j] == y[i]
                assert b.weights[r, j] == w[i]
                # reconstruct dense global row from local representation
                dense = np.zeros(d)
                for slot, v in zip(b.indices[r, j], b.values[r, j]):
                    if v != 0:
                        gid = b.projection[r, slot]
                        dense[gid] += v
                np.testing.assert_allclose(dense, X[i], atol=1e-12)


def test_re_active_cap(rng):
    n = 100
    X = rng.normal(size=(n, 5))
    ids = np.zeros(n, int)  # one entity
    data = build_random_effect_data(X, np.zeros(n), np.ones(n), ids, active_cap=10)
    active = data.buckets[0].sample_idx
    assert (active >= 0).sum() == 10


def test_score_view_matches_direct(rng):
    n, d = 50, 8
    X = rng.normal(size=(n, d)) * (rng.random((n, d)) < 0.6)
    ids = rng.integers(0, 5, size=n)
    data = build_random_effect_data(X, np.zeros(n), np.ones(n), ids, num_buckets=2)
    view = build_score_view(data, X, ids)
    # random per-entity coefficients in local space
    coeffs = [rng.normal(size=(b.num_entities, b.local_dim)) for b in data.buckets]
    scores = np.asarray(score_random_effect(view, coeffs, n, dtype=jnp.float64))
    # direct: w_e in global space
    for b, bucket in enumerate(data.buckets):
        for r, eid in enumerate(bucket.entity_ids):
            w_global = np.zeros(d)
            for slot in range(bucket.local_dim):
                gid = bucket.projection[r, slot]
                if gid >= 0:
                    w_global[gid] = coeffs[b][r, slot]
            for i in np.nonzero(ids == eid)[0]:
                np.testing.assert_allclose(scores[i], X[i] @ w_global, rtol=1e-8,
                                           atol=1e-8)


def test_train_random_effect_matches_direct_fit(rng):
    # one entity's vmapped solve == direct single-problem fit
    from photon_ml_tpu.ops.objective import make_objective
    from photon_ml_tpu.optimize import lbfgs
    from photon_ml_tpu.types import make_batch

    n, d = 80, 6
    X = rng.normal(size=(n, d))
    y = (rng.random(n) < 0.5).astype(float)
    ids = np.zeros(n, int)
    data = build_random_effect_data(X, y, np.ones(n), ids)
    fit = train_random_effect(data, np.zeros(n), l2=0.5, dtype=jnp.float64,
                              config=OptimizerConfig(max_iters=100, tolerance=1e-10))
    # map local coefficients back to global space
    bucket = data.buckets[0]
    w_global = np.zeros(d)
    for slot in range(bucket.local_dim):
        gid = bucket.projection[0, slot]
        if gid >= 0:
            w_global[gid] = fit.coefficients[0][0, slot]
    obj = make_objective("logistic")
    batch = make_batch(jnp.asarray(X), y, dtype=jnp.float64)
    ref = lbfgs(lambda w: obj.value_and_grad(w, batch, 0.5), jnp.zeros(d),
                OptimizerConfig(max_iters=100, tolerance=1e-10))
    np.testing.assert_allclose(w_global, np.asarray(ref.w), rtol=1e-4, atol=1e-6)
    assert fit.converged_fraction == 1.0


def test_coordinate_descent_fixed_only_matches_direct(rng):
    from photon_ml_tpu.ops.objective import make_objective
    from photon_ml_tpu.optimize import lbfgs
    from photon_ml_tpu.types import make_batch

    n, d = 120, 7
    X = rng.normal(size=(n, d))
    y = (rng.random(n) < 0.5).astype(float)
    ds = make_game_dataset(X, y)
    cd = CoordinateDescent(
        [CoordinateConfig("fixed", reg_type="l2", reg_weight=1.0,
                          tolerance=1e-10, max_iters=200)],
        task="logistic", n_iterations=1, dtype=jnp.float64,
    )
    model, history = cd.run(ds)
    w = np.asarray(model["fixed"].model.coefficients.means)
    obj = make_objective("logistic")
    batch = make_batch(jnp.asarray(X), y, dtype=jnp.float64)
    ref = lbfgs(lambda w: obj.value_and_grad(w, batch, 1.0), jnp.zeros(d),
                OptimizerConfig(max_iters=200, tolerance=1e-10))
    np.testing.assert_allclose(w, np.asarray(ref.w), rtol=1e-5, atol=1e-7)


def test_coordinate_descent_mixed_effects_beats_fixed_only(rng):
    Xg, Xu, y, uid, w_fixed, user_coefs = _mixed_effect_data(rng)
    n = len(y)
    split = int(n * 0.8)
    perm = rng.permutation(n)
    tr, va = perm[:split], perm[split:]
    feats = {"global": Xg, "per_user": Xu}
    ds_tr = make_game_dataset({k: v[tr] for k, v in feats.items()}, y[tr],
                              entity_ids={"userId": uid[tr]})
    ds_va = make_game_dataset({k: v[va] for k, v in feats.items()}, y[va],
                              entity_ids={"userId": uid[va]})
    fixed_cfg = CoordinateConfig("fixed", feature_shard="global",
                                 reg_type="l2", reg_weight=1.0)
    re_cfg = CoordinateConfig("per-user", coordinate_type="random",
                              feature_shard="per_user", entity_column="userId",
                              reg_type="l2", reg_weight=1.0)
    cd_fixed = CoordinateDescent([fixed_cfg], task="logistic",
                                 evaluators=["auc"], dtype=jnp.float64)
    _, hist_fixed = cd_fixed.run(ds_tr, ds_va)
    cd_game = CoordinateDescent([fixed_cfg, re_cfg], task="logistic",
                                n_iterations=2, evaluators=["auc"], dtype=jnp.float64)
    model, hist_game = cd_game.run(ds_tr, ds_va)
    auc_fixed = hist_fixed[-1]["auc"]
    auc_game = hist_game[-1]["auc"]
    assert auc_game > auc_fixed + 0.02, (auc_fixed, auc_game)
    # residual trick: training AUC from model scoring should be high
    assert model["per-user"].num_entities == 20


def test_coordinate_descent_warm_start_and_locked(rng):
    Xg, Xu, y, uid, *_ = _mixed_effect_data(rng, n_users=10)
    ds = make_game_dataset({"global": Xg, "per_user": Xu}, y,
                           entity_ids={"userId": uid})
    fixed_cfg = CoordinateConfig("fixed", feature_shard="global",
                                 reg_type="l2", reg_weight=1.0)
    re_cfg = CoordinateConfig("per-user", coordinate_type="random",
                              feature_shard="per_user", entity_column="userId",
                              reg_type="l2", reg_weight=1.0)
    cd = CoordinateDescent([fixed_cfg, re_cfg], task="logistic", dtype=jnp.float64)
    model1, _ = cd.run(ds)
    # warm start + lock the fixed coordinate: fixed coefficients unchanged
    model2, _ = cd.run(ds, warm_start=model1, locked=["fixed"])
    np.testing.assert_allclose(
        np.asarray(model2["fixed"].model.coefficients.means),
        np.asarray(model1["fixed"].model.coefficients.means), rtol=1e-12,
    )
    with pytest.raises(ValueError, match="locked"):
        cd.run(ds, warm_start=model1, locked=["nope"])


def test_down_sample_binary_keeps_positives(rng):
    y = (rng.random(1000) < 0.2).astype(float)
    w = np.ones(1000)
    idx, w2 = down_sample(y, w, 0.25, task="logistic", seed=1)
    assert set(np.nonzero(y > 0.5)[0]).issubset(set(idx))
    neg_mask = y[idx] <= 0.5
    np.testing.assert_allclose(w2[neg_mask], 4.0)
    np.testing.assert_allclose(w2[~neg_mask], 1.0)
    # uniform sampler preserves expected total weight
    idx_u, w_u = down_sample(y, w, 0.5, task="squared", seed=2)
    assert abs(w_u.sum() - 1000) < 150


def test_duplicate_coordinate_names_rejected():
    with pytest.raises(ValueError, match="duplicate"):
        CoordinateDescent([CoordinateConfig("a"), CoordinateConfig("a")])


@pytest.mark.parametrize("optimizer", ["lbfgs", "newton"])
def test_train_random_effect_entity_sharded_matches(rng, optimizer):
    # entity-axis shard_map path == unsharded path (review/verify regression)
    from photon_ml_tpu.parallel import make_mesh

    n, d = 120, 6
    X = rng.normal(size=(n, d))
    y = (rng.random(n) < 0.5).astype(float)
    ids = rng.integers(0, 11, size=n)  # 11 entities, not divisible by mesh axis
    data = build_random_effect_data(X, y, np.ones(n), ids, num_buckets=2)
    mesh = make_mesh({"entity": 4})
    cfg = OptimizerConfig(max_iters=60, tolerance=1e-10)
    fit_plain = train_random_effect(data, np.zeros(n), l2=0.4, dtype=jnp.float64,
                                    config=cfg, optimizer=optimizer)
    fit_mesh = train_random_effect(data, np.zeros(n), l2=0.4, dtype=jnp.float64,
                                   config=cfg, mesh=mesh, optimizer=optimizer)
    for a, b in zip(fit_plain.coefficients, fit_mesh.coefficients):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-8)
    assert fit_mesh.converged_fraction == 1.0


def test_random_effect_l1_regularization(rng):
    # review finding: RE coordinates must honor L1 (auto-routed to OWL-QN)
    n, d = 150, 8
    X = rng.normal(size=(n, d))
    y = (rng.random(n) < 0.5).astype(float)
    ids = np.zeros(n, int)
    data = build_random_effect_data(X, y, np.ones(n), ids)
    cfg = OptimizerConfig(max_iters=150, tolerance=1e-10)
    fit_l1 = train_random_effect(data, np.zeros(n), l1=5.0, dtype=jnp.float64,
                                 config=cfg)
    fit_none = train_random_effect(data, np.zeros(n), dtype=jnp.float64, config=cfg)
    nz_l1 = (np.abs(fit_l1.coefficients[0]) > 1e-8).sum()
    nz_none = (np.abs(fit_none.coefficients[0]) > 1e-8).sum()
    assert nz_l1 < nz_none  # L1 produces sparsity


def test_locked_without_warm_start_rejected(rng):
    from photon_ml_tpu.game.descent import make_game_dataset

    X = rng.normal(size=(50, 4))
    y = (rng.random(50) < 0.5).astype(float)
    ds = make_game_dataset(X, y)
    cd = CoordinateDescent([CoordinateConfig("fixed")])
    with pytest.raises(ValueError, match="warm_start"):
        cd.run(ds, locked=["fixed"])


def test_random_coordinate_normalization_sketch_rejected():
    from photon_ml_tpu.ops.normalization import NormalizationContext
    import jax.numpy as jnp2

    ctx = NormalizationContext(jnp2.ones(3), None)
    with pytest.raises(ValueError, match="projection='random'"):
        CoordinateConfig("re", coordinate_type="random", entity_column="u",
                         normalization=ctx, projection="random",
                         projection_dim=8)


def test_random_effect_normalization_matches_materialized(rng):
    """Per-entity normalization inside the solve == training on explicitly
    standardized features: identical predictions (coefficients come back in
    raw feature space)."""
    from photon_ml_tpu.game.data import build_random_effect_data, build_score_view
    from photon_ml_tpu.game.random_effect import (
        score_random_effect,
        train_random_effect,
    )
    from photon_ml_tpu.ops.normalization import NormalizationContext

    n, d = 240, 6
    X = rng.normal(size=(n, d)) * np.array([30.0, 0.05, 1.0, 4.0, 1.0, 2.0])
    X = X * (rng.random((n, d)) < 0.7)
    Xi = np.concatenate([X, np.ones((n, 1))], axis=1)  # intercept col = d
    ids = rng.integers(0, 8, n)
    u_eff = rng.normal(size=(8, d + 1))
    y = (rng.random(n) < 1 / (1 + np.exp(-np.sum(Xi * u_eff[ids], axis=1)))
         ).astype(float)
    weights = rng.uniform(0.5, 2.0, n)

    mean = Xi.mean(axis=0)
    std = np.where(Xi.std(axis=0) > 0, Xi.std(axis=0), 1.0)
    ctx = NormalizationContext(jnp.asarray(1.0 / std), jnp.asarray(mean),
                               intercept_index=d)

    kw = dict(task="logistic", l2=0.5, optimizer="lbfgs", dtype=jnp.float64)
    data_raw = build_random_effect_data(Xi, y, weights, ids, num_buckets=2)
    fit_norm = train_random_effect(data_raw, np.zeros(n), normalization=ctx,
                                   **kw)

    # reference: explicitly standardized dense features, no context
    Xn = (Xi - mean) / std
    Xn[:, d] = 1.0  # intercept untouched
    data_mat = build_random_effect_data(Xn, y, weights, ids, num_buckets=2)
    fit_mat = train_random_effect(data_mat, np.zeros(n), **kw)

    view_raw = build_score_view(data_raw, Xi, ids)
    view_mat = build_score_view(data_mat, Xn, ids)
    s_norm = np.asarray(score_random_effect(view_raw, fit_norm.coefficients,
                                            n, jnp.float64))
    s_mat = np.asarray(score_random_effect(view_mat, fit_mat.coefficients,
                                           n, jnp.float64))
    np.testing.assert_allclose(s_norm, s_mat, rtol=1e-6, atol=1e-8)
    assert fit_norm.converged_fraction == 1.0


def test_random_effect_full_variance(rng):
    """compute_variance='full' on random effects: per-entity diag(H^-1),
    distinct from the diagonal approximation but equal for a single-feature
    entity (where H is 1x1)."""
    n, d = 120, 5
    X = rng.normal(size=(n, d))
    y = (rng.random(n) < 0.5).astype(float)
    ids = np.repeat(np.arange(4), n // 4)
    data = build_random_effect_data(X, y, np.ones(n), ids, num_buckets=1)
    kw = dict(l2=0.5, dtype=jnp.float64,
              config=OptimizerConfig(max_iters=100, tolerance=1e-10))
    fit_d = train_random_effect(data, np.zeros(n), compute_variance="diagonal", **kw)
    fit_f = train_random_effect(data, np.zeros(n), compute_variance="full", **kw)
    vd, vf = fit_d.variances[0], fit_f.variances[0]
    assert vd.shape == vf.shape
    np.testing.assert_allclose(fit_d.coefficients[0], fit_f.coefficients[0],
                               rtol=1e-12)
    assert not np.allclose(vd, vf, rtol=1e-12)  # correlations matter
    np.testing.assert_allclose(vd, vf, rtol=1.0)  # but same scale


def test_coordinate_config_validates_variance():
    import pytest as _pytest

    with _pytest.raises(ValueError, match="compute_variance"):
        CoordinateConfig(name="x", compute_variance="Full")
    with _pytest.raises(ValueError, match="streaming"):
        CoordinateConfig(name="x", compute_variance="full", streaming=True)
    CoordinateConfig(name="x", compute_variance="full")  # ok


def test_game_with_implicit_ones_features(rng):
    """A full GAME run (fixed + random effect + transformer scoring) over
    the implicit-ones layout == the same run with explicit 1.0 values."""
    from photon_ml_tpu.estimators import GameTransformer
    from photon_ml_tpu.game.data import HostSparse

    n, d, k = 600, 50, 5
    idx = rng.integers(0, d, (n, k)).astype(np.int32)
    y = (rng.random(n) < 0.5).astype(float)
    users = rng.integers(0, 12, n)
    configs = [
        CoordinateConfig(name="fe", feature_shard="global", reg_type="l2",
                         reg_weight=1.0, max_iters=20),
        CoordinateConfig(name="per_user", coordinate_type="random",
                         entity_column="user", reg_type="l2",
                         reg_weight=1.0, max_iters=8, num_buckets=2),
    ]
    preds = {}
    for name, vals in (("binary", None), ("explicit", np.ones((n, k)))):
        train = make_game_dataset({"global": HostSparse(idx, vals, d)}, y,
                                  entity_ids={"user": users})
        cd = CoordinateDescent(configs, task="logistic", n_iterations=2)
        model, _ = cd.run(train)
        preds[name] = GameTransformer(model).predict_mean(train)
    np.testing.assert_allclose(preds["binary"], preds["explicit"],
                               rtol=1e-6, atol=1e-7)


def test_newton_dense_re_solver_matches_lbfgs(rng):
    """The batched dense-Newton RE solver (optimizer='newton') matches the
    vmapped L-BFGS path: coefficients, variances (diagonal + full),
    offsets, weights, and per-entity normalization all agree."""
    from photon_ml_tpu.game.data import build_random_effect_data
    from photon_ml_tpu.game.random_effect import train_random_effect
    from photon_ml_tpu.ops.normalization import NormalizationContext

    n, d, E = 360, 5, 12
    X = rng.normal(size=(n, d)) * np.array([10.0, 0.2, 1.0, 3.0, 1.0])
    X = X * (rng.random((n, d)) < 0.8)
    ids = rng.integers(0, E, n)
    u = rng.normal(size=(E, d))
    y = (rng.random(n) < 1 / (1 + np.exp(-np.sum(X * u[ids], axis=1)))
         ).astype(float)
    weights = rng.uniform(0.5, 2.0, n)
    offs = rng.normal(size=n) * 0.3

    from photon_ml_tpu.optimize import OptimizerConfig

    data = build_random_effect_data(X, y, weights, ids, num_buckets=2)
    cfg_kw = dict(task="logistic", l2=0.7, dtype=jnp.float64,
                  config=OptimizerConfig(max_iters=100, tolerance=1e-10))
    f_lb = train_random_effect(data, offs, optimizer="lbfgs",
                               compute_variance="full", **cfg_kw)
    f_nt = train_random_effect(data, offs, optimizer="newton",
                               compute_variance="full", **cfg_kw)
    assert f_nt.converged_fraction == 1.0
    assert f_nt.mean_iterations <= f_lb.mean_iterations  # Newton is quadratic
    for b in range(len(f_lb.coefficients)):
        np.testing.assert_allclose(f_nt.coefficients[b], f_lb.coefficients[b],
                                   rtol=1e-4, atol=1e-6)
        np.testing.assert_allclose(f_nt.variances[b], f_lb.variances[b],
                                   rtol=1e-4, atol=1e-8)
    d_lb = train_random_effect(data, offs, optimizer="lbfgs",
                               compute_variance="diagonal", **cfg_kw)
    d_nt = train_random_effect(data, offs, optimizer="newton",
                               compute_variance="diagonal", **cfg_kw)
    for b in range(len(d_lb.variances)):
        np.testing.assert_allclose(d_nt.variances[b], d_lb.variances[b],
                                   rtol=1e-4, atol=1e-8)

    # normalization (factors + shifts through the intercept) parity
    Xi = np.concatenate([X, np.ones((n, 1))], axis=1)
    mean = Xi.mean(axis=0)
    std = np.where(Xi.std(axis=0) > 0, Xi.std(axis=0), 1.0)
    ctx = NormalizationContext(jnp.asarray(1.0 / std), jnp.asarray(mean),
                               intercept_index=d)
    data_i = build_random_effect_data(Xi, y, weights, ids, num_buckets=2)
    g_lb = train_random_effect(data_i, offs, optimizer="lbfgs",
                               normalization=ctx, **cfg_kw)
    g_nt = train_random_effect(data_i, offs, optimizer="newton",
                               normalization=ctx, **cfg_kw)
    for b in range(len(g_lb.coefficients)):
        np.testing.assert_allclose(g_nt.coefficients[b], g_lb.coefficients[b],
                                   rtol=1e-4, atol=1e-6)


def test_newton_rejected_for_fixed_coordinates():
    from photon_ml_tpu.game.descent import CoordinateConfig

    with pytest.raises(ValueError, match="newton"):
        CoordinateConfig("fixed", coordinate_type="fixed",
                         optimizer="newton")


def test_fixed_effect_rejects_unknown_sparse_grad(rng):
    """A fixed-effect coordinate asks ``resolve_sparse_grad``: a name legal
    nowhere ("csc_segment" was, one layer down) raises where it used to
    train through the scatter transpose without a word."""
    from photon_ml_tpu.game.data import HostSparse

    n, d, k = 64, 20, 3
    idx = rng.integers(0, d, (n, k)).astype(np.int32)
    ds = make_game_dataset({"global": HostSparse(idx, None, d)},
                           (rng.random(n) < 0.5).astype(float))
    cd = CoordinateDescent(
        [CoordinateConfig(name="fe", feature_shard="global", reg_type="l2",
                          reg_weight=1.0, max_iters=3,
                          sparse_grad="csc_segment")],
        task="logistic", n_iterations=1)
    with pytest.raises(ValueError, match="csc_segment"):
        cd.run(ds)


def test_re_optimizer_auto_resolves_per_platform(rng):
    """optimizer="auto" picks the measured per-platform default (CPU:
    vmapped L-BFGS) and produces the same fit as naming it explicitly
    (VERDICT r3 #7: the default is data-driven, one table entry per
    platform in random_effect._RE_SOLVER_DEFAULT)."""
    from photon_ml_tpu.game.data import build_random_effect_data
    from photon_ml_tpu.game.random_effect import (
        resolve_re_optimizer, train_random_effect)
    from photon_ml_tpu.optimize import OptimizerConfig

    assert resolve_re_optimizer("newton") == "newton"
    assert resolve_re_optimizer("auto") == "lbfgs"  # tests run on CPU

    n, d, E = 120, 4, 6
    X = rng.normal(size=(n, d))
    ids = rng.integers(0, E, n)
    y = (rng.random(n) < 0.5).astype(float)
    data = build_random_effect_data(X, y, np.ones(n), ids, num_buckets=1)
    kw = dict(task="logistic", l2=0.5,
              config=OptimizerConfig(max_iters=50, tolerance=1e-8))
    f_auto = train_random_effect(data, np.zeros(n), optimizer="auto", **kw)
    f_lb = train_random_effect(data, np.zeros(n), optimizer="lbfgs", **kw)
    for b in range(len(f_lb.coefficients)):
        np.testing.assert_array_equal(f_auto.coefficients[b],
                                      f_lb.coefficients[b])


def _timed_fill(W, bucket, prev_bucket, prs):
    import time

    from photon_ml_tpu.game.descent import _warm_fill_bucket

    t0 = time.perf_counter()
    _warm_fill_bucket(W, bucket, np.arange(bucket.num_entities),
                      prev_bucket, prs)
    return time.perf_counter() - t0


def test_warm_fill_bucket_vectorized_matches_loop_and_scales(rng):
    """The warm-start slot remap is a numpy composite-key join, not a
    per-entity/per-slot Python loop (VERDICT r4 #7): it must match the
    straightforward loop on a small case AND warm-start 100k entities
    well under 2s."""
    import time

    from photon_ml_tpu.game.descent import _warm_fill_bucket
    from photon_ml_tpu.models import RandomEffectBucket

    def make_pair(E, D_prev, D_cur, gid_space):
        prev_proj = np.full((E, D_prev), -1, np.int32)
        cur_proj = np.full((E, D_cur), -1, np.int32)
        for r in range(E):
            gids = rng.choice(gid_space, size=D_prev + D_cur // 2,
                              replace=False)
            prev_proj[r] = np.sort(gids[:D_prev])
            # current subspace overlaps ~half the previous one
            cur = np.concatenate([gids[D_prev // 2: D_prev],
                                  gids[D_prev:]])[:D_cur]
            cur_proj[r, : len(cur)] = np.sort(cur)
        coefs = rng.normal(size=(E, D_prev))
        return prev_proj, cur_proj, coefs

    # correctness vs the reference loop
    E, Dp, Dc = 40, 6, 8
    prev_proj, cur_proj, coefs = make_pair(E, Dp, Dc, 200)
    prev_bucket = RandomEffectBucket([f"e{i}" for i in range(E)],
                                     coefs, prev_proj)
    local_maps = [{int(g): s for s, g in enumerate(cur_proj[r])
                   if g >= 0} for r in range(E)]
    bucket = type("B", (), {})()
    bucket.num_entities = E
    bucket.local_maps = local_maps
    bucket.projection = cur_proj
    rows = np.arange(E)
    prs = rng.permutation(E)
    W = np.zeros((E, Dc))
    _warm_fill_bucket(W, bucket, rows, prev_bucket, prs)
    W_ref = np.zeros((E, Dc))
    for r in range(E):
        pr = prs[r]
        for slot, gid in enumerate(prev_proj[pr]):
            if gid >= 0 and int(gid) in local_maps[r]:
                W_ref[r, local_maps[r][int(gid)]] = coefs[pr, slot]
    np.testing.assert_allclose(W, W_ref)

    # scale: 100k entities x 16 slots in well under 2s
    E, Dp, Dc = 100_000, 16, 16
    prev_proj = rng.integers(0, 1 << 20, (E, Dp)).astype(np.int32)
    prev_proj.sort(axis=1)
    prs = rng.permutation(E)
    # each current row carries its MATCHED prev row's subspace: every slot
    # should remap
    cur_proj = prev_proj[prs]
    coefs = rng.normal(size=(E, Dp))
    prev_bucket = RandomEffectBucket(np.arange(E), coefs, prev_proj)
    bucket = type("B", (), {})()
    bucket.num_entities = E
    bucket.local_maps = [None]  # only [0] is touched, for the sketch check
    bucket.projection = cur_proj
    W = np.zeros((E, Dc))
    # min-of-3: the bound is about algorithmic complexity, and a single
    # wall-clock sample on a 1-core box loses to unrelated process
    # contention (observed flaking in full-suite runs)
    dt = min(_timed_fill(W, bucket, prev_bucket, prs)
             for _ in range(3))
    assert dt < 2.0, f"warm-fill at 100k entities took {dt:.2f}s"
    assert np.count_nonzero(W) > 0.99 * E * Dp


def test_warm_start_prev_subspace_into_sketch(rng):
    """A previous exact-subspace model warm-starts a sketched coordinate by
    pushing (gid, coef) through the sketch (the projector's own embedding);
    the old per-slot loop raised TypeError on this path."""
    from photon_ml_tpu.game.data import SketchProjection
    from photon_ml_tpu.game.descent import _warm_fill_bucket
    from photon_ml_tpu.models import RandomEffectBucket

    E, Dp, dim = 10, 4, 32
    sketch = SketchProjection(dim, seed=3)
    prev_proj = rng.integers(0, 1000, (E, Dp)).astype(np.int32)
    coefs = rng.normal(size=(E, Dp))
    prev_bucket = RandomEffectBucket(np.arange(E), coefs, prev_proj)
    bucket = type("B", (), {})()
    bucket.num_entities = E
    bucket.local_maps = [sketch] * E
    bucket.projection = np.full((E, dim), -1, np.int32)
    W = np.zeros((E, dim))
    _warm_fill_bucket(W, bucket, np.arange(E), prev_bucket, np.arange(E))
    for r in range(3):  # spot-check the embedding
        expect = np.zeros(dim)
        slots, signs = sketch.slots_signs(prev_proj[r])
        for j in range(Dp):
            expect[slots[j]] += signs[j] * coefs[r, j]
        np.testing.assert_allclose(W[r], expect, rtol=1e-12)


@pytest.mark.parametrize("use_mesh", [False, True])
def test_train_random_effect_blocked_matches_unblocked(rng, monkeypatch,
                                                       use_mesh):
    """Entity-block bounded execution (the v5e HBM guard) must reproduce
    the single-program solve exactly — including with an entity mesh,
    where the block width rounds to the mesh axis."""
    from photon_ml_tpu.game import random_effect as re_mod
    from photon_ml_tpu.parallel import make_mesh

    n, d = 160, 6
    X = rng.normal(size=(n, d))
    y = (rng.random(n) < 0.5).astype(float)
    ids = rng.integers(0, 13, size=n)  # 13 entities
    data = build_random_effect_data(X, y, np.ones(n), ids)
    cfg = OptimizerConfig(max_iters=60, tolerance=1e-10)
    mesh = make_mesh({"entity": 4}) if use_mesh else None
    want = train_random_effect(data, np.zeros(n), l2=0.4, dtype=jnp.float64,
                               config=cfg, mesh=mesh)
    monkeypatch.setattr(re_mod, "_RE_BLOCK_BYTES", 20_000)  # forces blocks
    got = train_random_effect(data, np.zeros(n), l2=0.4, dtype=jnp.float64,
                              config=cfg, mesh=mesh)
    for a, b in zip(want.coefficients, got.coefficients):
        np.testing.assert_allclose(a, b, rtol=1e-12, atol=1e-12)
    assert got.converged_fraction == want.converged_fraction
    assert got.mean_iterations == want.mean_iterations


def test_re_auto_solver_dimension_gate(monkeypatch):
    """'auto' only picks dense-Newton up to _RE_NEWTON_MAX_DIM: its
    [block, d, d] Hessians exhaust HBM (and crashed the Mosaic batched-
    Cholesky compile at the d=351 CD bucket on the v5e); wide subspaces
    route to the O(d)-memory vmapped L-BFGS."""
    from photon_ml_tpu.game import random_effect as re_mod

    monkeypatch.setattr(re_mod, "_RE_SOLVER_DEFAULT",
                        {"cpu": "newton", "tpu": "newton"})
    assert re_mod.resolve_re_optimizer("auto", 32) == "newton"
    assert re_mod.resolve_re_optimizer("auto",
                                       re_mod._RE_NEWTON_MAX_DIM) == "newton"
    assert re_mod.resolve_re_optimizer("auto",
                                       re_mod._RE_NEWTON_MAX_DIM + 1) == "lbfgs"
    assert re_mod.resolve_re_optimizer("auto", None) == "newton"
    assert re_mod.resolve_re_optimizer("newton", 351) == "newton"  # explicit
