"""The linear solve of the random effects' Newton step
(``game/random_effect._spd_solve``): against ``numpy.linalg.solve`` in
float64, bit-equal whatever the entity-batch width, NaN for a singular
entity and for no other, and through a whole GLMix run against the
pivoted LU it replaced (kept here as the oracle)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from photon_ml_tpu.game import random_effect as re_mod
from photon_ml_tpu.game.random_effect import _spd_solve
from photon_ml_tpu.optimize import OptimizerConfig

SHAPES = [(5, 21), (445, 36), (8858, 21), (64, 1), (64, 2), (256, 64),
          (128, 128)]


def spd_batch(seed, E, D, dtype):
    """``X^T X / N + I`` with N = 2 D: what a Newton step hands the solve."""
    r = np.random.default_rng(seed)
    Z = r.standard_normal((E, D, 2 * D))
    H = np.einsum("edn,efn->edf", Z, Z) / (2 * D) + np.eye(D)
    return H.astype(dtype), r.standard_normal((E, D)).astype(dtype)


def lu_solve(H, rhs):
    """The solve this path called before: a pivoted LU custom call."""
    if rhs.ndim == 2:
        return jnp.linalg.solve(H, rhs[..., None])[..., 0]
    return jnp.linalg.solve(H, rhs)


@pytest.mark.parametrize("dtype,limit", [(np.float32, 5e-6),
                                         (np.float64, 1e-12)],
                         ids=["f32", "f64"])
@pytest.mark.parametrize("rhs", ["vector", "identity"])
@pytest.mark.parametrize("E,D", SHAPES)
def test_spd_solve_matches_float64_solve(E, D, rhs, dtype, limit):
    H, g = spd_batch(E * 1000 + D, E, D, dtype)
    if rhs == "identity":  # the inverse compute_variance="full" reads
        g = np.broadcast_to(np.eye(D, dtype=dtype), (E, D, D))
    out = jax.jit(_spd_solve)(jnp.asarray(H), jnp.asarray(g))
    assert out.dtype == dtype and out.shape == g.shape
    ref = np.linalg.solve(H.astype(np.float64),
                          g.astype(np.float64).reshape(E, D, -1))
    got = np.asarray(out, np.float64).reshape(ref.shape)
    err = np.linalg.norm(got - ref, axis=1) / np.linalg.norm(ref, axis=1)
    assert err.max() < limit, err.max()


@pytest.fixture(scope="module")
def wide():
    H, g = spd_batch(33, 300, 21, np.float32)
    return H, g, np.asarray(jax.jit(_spd_solve)(H, g))


@pytest.mark.parametrize("width", [1, 2, 3, 7, 8, 64, 127, 128, 129, 300])
def test_an_entitys_result_does_not_depend_on_the_batch_width(wide, width):
    # what docs/sharding.md promises of entity sharding and the active
    # set: the same entity solved in a narrower call gives the same bits
    H, g, all_of_them = wide
    narrow = np.asarray(jax.jit(_spd_solve)(H[:width], g[:width]))
    np.testing.assert_array_equal(narrow, all_of_them[:width])
    last = np.asarray(jax.jit(_spd_solve)(H[-width:], g[-width:]))
    np.testing.assert_array_equal(last, all_of_them[-width:])


@pytest.mark.parametrize("a,b", [(0, 1), (2, 9), (3, 20)])
@pytest.mark.parametrize("dtype", [np.float32, np.float64],
                         ids=["f32", "f64"])
def test_a_singular_matrix_gives_nan_for_its_entity_alone(a, b, dtype):
    r = np.random.default_rng(a * 100 + b)
    E, D, N = 9, 21, 50
    X = r.standard_normal((E, D, N)).astype(dtype)
    X[4, b] = X[4, a]  # a duplicated column, and no ridge
    H = np.einsum("edn,efn->edf", X, X)
    g = r.standard_normal((E, D)).astype(dtype)
    out = np.asarray(jax.jit(_spd_solve)(H, g))
    assert not np.isfinite(out[4]).any()
    others = np.arange(E) != 4
    ref = np.linalg.solve(H[others].astype(np.float64),
                          g[others].astype(np.float64)[..., None])[..., 0]
    np.testing.assert_allclose(out[others], ref,
                               rtol=0, atol=2e-3 * np.abs(ref).max()
                               if dtype == np.float32 else 1e-10)


def test_a_pivot_that_is_not_positive_gives_nan():
    H = np.stack([np.diag([2.0, -1.0, 3.0]), np.zeros((3, 3)), np.eye(3)])
    out = np.asarray(_spd_solve(jnp.asarray(H), jnp.ones((3, 3))))
    assert np.isnan(out[0]).any() and np.isnan(out[1]).all()
    np.testing.assert_array_equal(out[2], np.ones(3))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.float64],
                         ids=["f32", "f64"])
def test_a_singular_hessian_leaves_its_entity_unchanged(dtype):
    """The Newton step's contract: a rank-deficient entity at ``l2 = 0``
    keeps its incoming coefficients, no NaN is written, and every other
    entity of the batch is solved."""
    r = np.random.default_rng(5)
    E, D, N = 6, 5, 40
    X = r.standard_normal((E, D, N))
    X[2, 3] = X[2, 1]
    labels = (r.random((E, N)) < 0.5).astype(float)
    w0 = 0.1 * r.standard_normal((E, D))
    solver = re_mod._newton_dense_solver(
        D, "logistic", OptimizerConfig(max_iters=25, tolerance=1e-9), False)
    args = [jnp.asarray(a, dtype) for a in
            (X, labels, np.ones((E, N)), np.zeros((E, N)), w0)]
    W, _, converged, iters = jax.jit(solver.solve_dense)(
        *args, jnp.asarray(0.0, dtype))
    W = np.asarray(W)
    assert np.isfinite(W).all()
    np.testing.assert_array_equal(W[2], np.asarray(args[4])[2])
    others = np.arange(E) != 2
    assert np.asarray(converged)[others].all()
    m = np.einsum("edn,ed->en", X, W)
    grad = np.einsum("edn,en->ed", X, 1 / (1 + np.exp(-m)) - labels)
    tol = 1e-3 if dtype == jnp.float32 else 1e-6
    assert np.abs(grad[others]).max() < tol
    assert np.abs(grad[2]).max() > 0.1  # its step was never taken


def _clear_solver_caches():
    for cached in (re_mod._jitted_placed_solver, re_mod._jitted_newton_halves,
                   re_mod._jitted_sharded_solver):
        cached.cache_clear()


def test_glmix_run_agrees_with_the_same_run_through_the_lu(monkeypatch):
    from photon_ml_tpu.game.descent import (
        CoordinateConfig, CoordinateDescent, make_game_dataset,
    )

    r = np.random.default_rng(7)
    n, users, items = 900, 23, 11
    Xg, Xu, Xi = (r.normal(size=(n, d)) for d in (6, 5, 4))
    uid = r.integers(0, users, n) ** 2 % users  # skewed sizes
    iid = r.integers(0, items, n)
    y = (r.random(n) < 0.5).astype(float)

    def run():
        _clear_solver_caches()
        ds = make_game_dataset({"g": Xg, "u": Xu, "i": Xi}, y,
                               entity_ids={"user": uid, "item": iid})
        random = dict(coordinate_type="random", optimizer="newton",
                      reg_type="l2", reg_weight=1.0, max_iters=4,
                      tolerance=0.0, active_set=False)
        cd = CoordinateDescent(
            [CoordinateConfig("fixed", feature_shard="g", reg_type="l2",
                              reg_weight=1.0, max_iters=3),
             CoordinateConfig("per-user", feature_shard="u",
                              entity_column="user", **random),
             CoordinateConfig("per-item", feature_shard="i",
                              entity_column="item", **random)],
            task="logistic", n_iterations=2, dtype=jnp.float32)
        model, _ = cd.run(ds)
        return model

    ours = run()
    traced = []
    monkeypatch.setattr(re_mod, "_spd_solve",
                        lambda H, g: traced.append(H.shape) or lu_solve(H, g))
    try:
        oracle = run()
    finally:
        _clear_solver_caches()
    assert traced  # the oracle's solves did go through the LU
    for name in ("per-user", "per-item"):
        mine = ours.coordinates[name].buckets
        theirs = oracle.coordinates[name].buckets
        assert len(mine) == len(theirs) >= 1
        for a, b in zip(mine, theirs):
            a, b = np.asarray(a.coefficients), np.asarray(b.coefficients)
            assert np.abs(b).max() > 1e-2
            np.testing.assert_allclose(a, b, rtol=0,
                                       atol=1e-5 * np.abs(b).max())
    a, b = (np.asarray(m.coordinates["fixed"].model.coefficients.means)
            for m in (ours, oracle))
    np.testing.assert_allclose(a, b, rtol=0, atol=1e-5 * np.abs(b).max())
