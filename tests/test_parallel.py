"""Distributed fixed-effect tests on 8 virtual CPU devices — the moral
equivalent of the reference's local-mode-Spark integration tier
(SURVEY.md §8)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from photon_ml_tpu.ops.objective import make_objective
from photon_ml_tpu.optimize import OptimizerConfig
from photon_ml_tpu.parallel import (
    distributed_hvp,
    distributed_value_and_grad,
    fit_distributed,
    make_mesh,
    pad_batch,
    shard_batch,
)
from photon_ml_tpu.types import make_batch, sparse_from_scipy
import scipy.sparse as sp


@pytest.fixture(scope="module")
def mesh():
    assert jax.device_count() == 8, "conftest must force 8 cpu devices"
    return make_mesh({"data": 8})


def _problem(rng, n=203, d=12, sparse=False):  # n deliberately not divisible by 8
    X = rng.normal(size=(n, d))
    if sparse:
        X = X * (rng.random((n, d)) < 0.4)
    w_true = rng.normal(size=d)
    y = (rng.random(n) < 1 / (1 + np.exp(-X @ w_true))).astype(float)
    feats = sparse_from_scipy(sp.csr_matrix(X), dtype=jnp.float64) if sparse else jnp.asarray(X)
    batch = make_batch(feats, y, weights=rng.random(n) + 0.5, dtype=jnp.float64)
    return batch, X, y


def test_pad_batch_noop_semantics(rng):
    batch, X, y = _problem(rng)
    obj = make_objective("logistic")
    w = jnp.asarray(rng.normal(size=X.shape[1]))
    padded = pad_batch(batch, 8)
    assert padded.num_examples % 8 == 0
    f1, g1 = obj.value_and_grad(w, batch, 0.7)
    f2, g2 = obj.value_and_grad(w, padded, 0.7)
    np.testing.assert_allclose(f1, f2, rtol=1e-12)
    np.testing.assert_allclose(g1, g2, rtol=1e-12)


@pytest.mark.parametrize("sparse", [False, True])
def test_distributed_grad_matches_single_device(rng, mesh, sparse):
    batch, X, y = _problem(rng, sparse=sparse)
    obj = make_objective("logistic")
    w = jnp.asarray(rng.normal(size=X.shape[1]) * 0.2)
    sharded = shard_batch(batch, mesh)
    fg = distributed_value_and_grad(obj, mesh)
    f_d, g_d = jax.jit(fg)(w, sharded, 0.5)
    f_s, g_s = obj.value_and_grad(w, pad_batch(batch, 8), 0.5)
    np.testing.assert_allclose(f_d, f_s, rtol=1e-10)
    np.testing.assert_allclose(g_d, g_s, rtol=1e-10)


def test_distributed_hvp_matches_single_device(rng, mesh):
    batch, X, y = _problem(rng)
    obj = make_objective("logistic")
    w = jnp.asarray(rng.normal(size=X.shape[1]) * 0.2)
    v = jnp.asarray(rng.normal(size=X.shape[1]))
    sharded = shard_batch(batch, mesh)
    hvp = distributed_hvp(obj, mesh)
    hv_d = jax.jit(hvp)(w, v, sharded, 0.5)
    hv_s = obj.hvp(w, v, pad_batch(batch, 8), 0.5)
    np.testing.assert_allclose(hv_d, hv_s, rtol=1e-9)


@pytest.mark.parametrize("optimizer", ["lbfgs", "tron", "owlqn"])
def test_fit_distributed_matches_single_device_fit(rng, mesh, optimizer):
    from photon_ml_tpu.optimize import get_optimizer

    batch, X, y = _problem(rng)
    d = X.shape[1]
    obj = make_objective("logistic")
    cfg = OptimizerConfig(max_iters=150, tolerance=1e-10)
    l2, l1 = 0.5, (0.3 if optimizer == "owlqn" else 0.0)
    res_d = fit_distributed(obj, batch, mesh, jnp.zeros(d), l2=l2, l1=l1,
                            optimizer=optimizer, config=cfg)
    fg = lambda w: obj.value_and_grad(w, batch, l2)
    if optimizer == "owlqn":
        res_s = get_optimizer(optimizer)(fg, jnp.zeros(d), l1, cfg)
    else:
        res_s = get_optimizer(optimizer)(fg, jnp.zeros(d), cfg)
    np.testing.assert_allclose(res_d.value, res_s.value, rtol=1e-8)
    np.testing.assert_allclose(res_d.w, res_s.w, rtol=1e-5, atol=1e-7)


def test_mesh_validation():
    with pytest.raises(ValueError, match="devices"):
        make_mesh({"data": 64})


@pytest.mark.parametrize("sparse", [False, True])
def test_margin_line_search_matches_full(rng, mesh, sparse):
    """The margin-space L-BFGS (2 data passes/iter) must walk the same
    trajectory as the black-box path: identical math, only the line-search
    evaluation is restructured (optimize/lbfgs_margin.py)."""
    batch, X, y = _problem(rng, sparse=sparse)
    d = X.shape[1]
    obj = make_objective("logistic")
    cfg = OptimizerConfig(max_iters=100, tolerance=1e-10)
    res_full = fit_distributed(obj, batch, mesh, jnp.zeros(d), l2=0.5,
                               config=cfg, line_search="full")
    res_marg = fit_distributed(obj, batch, mesh, jnp.zeros(d), l2=0.5,
                               config=cfg, line_search="margin")
    np.testing.assert_allclose(res_marg.value, res_full.value, rtol=1e-9)
    np.testing.assert_allclose(res_marg.w, res_full.w, rtol=1e-5, atol=1e-8)


def test_margin_line_search_with_normalization(rng, mesh):
    """Margin-space search composes with normalization's coefficient-space
    map (both are linear in w)."""
    from photon_ml_tpu.ops.normalization import NormalizationContext

    batch, X, y = _problem(rng, sparse=True)
    d = X.shape[1]
    norm = NormalizationContext(
        factors=jnp.asarray(rng.uniform(0.5, 2.0, d)),
        shifts=jnp.asarray(rng.normal(size=d) * 0.1),
        intercept_index=0,
    )
    obj = make_objective("logistic", normalization=norm, intercept_index=0)
    cfg = OptimizerConfig(max_iters=100, tolerance=1e-10)
    res_full = fit_distributed(obj, batch, mesh, jnp.zeros(d), l2=0.5,
                               config=cfg, line_search="full")
    res_marg = fit_distributed(obj, batch, mesh, jnp.zeros(d), l2=0.5,
                               config=cfg, line_search="margin")
    np.testing.assert_allclose(res_marg.value, res_full.value, rtol=1e-9)
    np.testing.assert_allclose(res_marg.w, res_full.w, rtol=1e-5, atol=1e-8)


def test_precomputed_csc_reused_across_fits(rng, mesh):
    """build_csc once + two fits at different l2 == per-fit csc builds: the
    per-dataset column sort must be reusable (VERDICT r2 — the sort was
    re-paid per calibration fit)."""
    from photon_ml_tpu.parallel.data_parallel import build_csc

    batch, X, y = _problem(rng, sparse=True)
    d = X.shape[1]
    obj = make_objective("logistic")
    cfg = OptimizerConfig(max_iters=60, tolerance=1e-10)
    csc = build_csc(obj, batch, mesh)
    for l2 in (0.1, 2.0):
        res_pre = fit_distributed(obj, batch, mesh, jnp.zeros(d), l2=l2,
                                  config=cfg, sparse_grad="csc",
                                  precomputed_csc=csc)
        res_own = fit_distributed(obj, batch, mesh, jnp.zeros(d), l2=l2,
                                  config=cfg, sparse_grad="csc")
        np.testing.assert_allclose(res_pre.w, res_own.w, rtol=1e-12)


def test_tolerance_zero_disables_convergence_tests(rng, mesh):
    """An explicit tolerance<=0 disables the convergence tests entirely so
    the bench's iteration count is exact (VERDICT r2 weak #4: the 4*eps
    clamp silently stopped the f32 bench at 15/20 "pinned" iterations).
    Termination then only happens at max_iters or on a genuine line-search
    stall (no representable progress left)."""
    from photon_ml_tpu.optimize.common import converged_check

    # the r2 failure mode: f32, relative loss change ~1e-7 < 4*eps(f32)
    f_prev = jnp.float32(100.0)
    f = f_prev * (1 - 1e-7)
    assert bool(converged_check(f_prev, f, jnp.float32(1.0),
                                jnp.float32(1.0), 1e-9))  # clamp still on
    assert not bool(converged_check(f_prev, f, jnp.float32(1.0),
                                    jnp.float32(1.0), 0.0))  # honored exactly
    # even bitwise-equal losses / zero gradient don't "converge" at tol=0
    assert not bool(converged_check(f_prev, f_prev, jnp.float32(0.0),
                                    jnp.float32(1.0), 0.0))

    # integration: a short fit mid-descent runs all its iterations
    batch, X, y = _problem(rng)
    d = X.shape[1]
    obj = make_objective("logistic")
    for ls in ("margin", "full"):
        res = fit_distributed(obj, batch, mesh, jnp.zeros(d), l2=0.5,
                              config=OptimizerConfig(max_iters=8, tolerance=0.0),
                              line_search=ls)
        assert int(res.iterations) == 8, ls


def test_fit_distributed_implicit_ones(rng, mesh):
    """The implicit-ones layout fits identically to explicit 1.0 values on
    every sparse_grad mode, through row padding (weight-0 pad rows
    neutralize the implicit 1.0 slots) and the margin line search."""
    from photon_ml_tpu.types import LabeledBatch, SparseFeatures

    n, d, k = 203, 32, 5  # 203: forces row padding to the 8-way mesh
    indices = jnp.asarray(rng.integers(0, d, (n, k)), jnp.int32)
    y = (rng.random(n) < 0.5).astype(float)
    mk = lambda vals: LabeledBatch(
        SparseFeatures(indices, vals, dim=d), jnp.asarray(y),
        jnp.zeros(n), jnp.ones(n))
    bb, be = mk(None), mk(jnp.ones((n, k)))
    cfg = OptimizerConfig(max_iters=40, tolerance=1e-10)
    for mode in ("scatter", "csc", "csc_pallas"):
        rb = fit_distributed(make_objective("logistic"), bb, mesh,
                             jnp.zeros(d), l2=0.5, config=cfg,
                             sparse_grad=mode)
        re = fit_distributed(make_objective("logistic"), be, mesh,
                             jnp.zeros(d), l2=0.5, config=cfg,
                             sparse_grad=mode)
        np.testing.assert_allclose(rb.w, re.w, rtol=1e-9, err_msg=mode)
        np.testing.assert_allclose(rb.value, re.value, rtol=1e-11,
                                   err_msg=mode)


@pytest.mark.parametrize("name", ["csc_segment", "csc_precise", "csc_typo"])
def test_unknown_sparse_grad_is_rejected(rng, mesh, name):
    """``sparse_grad`` has one gate: a name that is not one of its four
    values raises — the two modes that lost the calibration, and a typo,
    which used to train through the scatter transpose without a word."""
    from photon_ml_tpu.parallel.data_parallel import resolve_sparse_grad

    batch, X, y = _problem(rng, sparse=True)
    with pytest.raises(ValueError, match="csc_pallas"):
        resolve_sparse_grad(name)
    with pytest.raises(ValueError, match=name):
        fit_distributed(make_objective("logistic"), batch, mesh,
                        jnp.zeros(X.shape[1]), l2=0.5, sparse_grad=name,
                        config=OptimizerConfig(max_iters=2))


@pytest.mark.parametrize("mode", ["csc", "csc_pallas"])
def test_csc_modes_single_vs_eight_device_equivalence(rng, mesh, mode):
    """Every dryrun sparse-gradient variant asserted allclose between a
    1-device and the 8-device mesh — not merely finite (VERDICT r4 #6).
    Covers the margin line search WITH a precomputed csc on both widths,
    the exact headline-bench configuration."""
    from photon_ml_tpu.parallel.data_parallel import build_csc

    batch, X, y = _problem(rng, sparse=True)
    d = X.shape[1]
    obj = make_objective("logistic")
    cfg = OptimizerConfig(max_iters=60, tolerance=1e-10)
    mesh1 = make_mesh({"data": 1})
    res = {}
    for name, m in (("one", mesh1), ("eight", mesh)):
        csc = build_csc(obj, batch, m)
        res[name] = fit_distributed(obj, batch, m, jnp.zeros(d), l2=0.5,
                                    config=cfg, sparse_grad=mode,
                                    precomputed_csc=csc,
                                    line_search="margin")
    np.testing.assert_allclose(res["eight"].w, res["one"].w,
                               rtol=1e-6, atol=1e-9, err_msg=mode)
    np.testing.assert_allclose(res["eight"].value, res["one"].value,
                               rtol=1e-9, err_msg=mode)


@pytest.mark.parametrize("optimizer", ["tron", "owlqn"])
def test_tron_owlqn_single_vs_eight_device_sparse(rng, mesh, optimizer):
    """TRON and OWL-QN on SPARSE data: 1-device mesh == 8-device mesh
    (the dense variants are covered against the raw single-device
    optimizers above; the dryrun exercises these on sparse batches)."""
    batch, X, y = _problem(rng, sparse=True)
    d = X.shape[1]
    obj = make_objective("logistic")
    cfg = OptimizerConfig(max_iters=80, tolerance=1e-10)
    l1 = 0.3 if optimizer == "owlqn" else 0.0
    r1 = fit_distributed(obj, batch, make_mesh({"data": 1}), jnp.zeros(d),
                         l2=0.5, l1=l1, optimizer=optimizer, config=cfg)
    r8 = fit_distributed(obj, batch, mesh, jnp.zeros(d),
                         l2=0.5, l1=l1, optimizer=optimizer, config=cfg)
    np.testing.assert_allclose(r8.w, r1.w, rtol=1e-6, atol=1e-9)
    np.testing.assert_allclose(r8.value, r1.value, rtol=1e-9)


def test_fit_runner_compilation_reused(rng, mesh):
    """Repeated fit_distributed calls (same objective/config, different l2
    or data) must reuse ONE jitted runner — round 2's per-call
    jax.jit(lambda...) recompiled every fit, so the bench timed compile,
    not compute (docs/PERF.md r3 item 0)."""
    from photon_ml_tpu.parallel import data_parallel as dp

    obj = make_objective("logistic")
    batch, X, y = _problem(rng)
    d = X.shape[1]
    cfg = OptimizerConfig(max_iters=5, tolerance=0.0)
    for l2 in (0.1, 1.0, 10.0):
        fit_distributed(obj, batch, mesh, jnp.zeros(d), l2=l2, config=cfg)
    entries = [e for e in dp._RUNNER_CACHE.values() if e[0] is obj]
    assert len(entries) == 1
    runners = entries[0][1]
    assert len(runners) == 1  # one runner for the one fit configuration
    run = next(iter(runners.values()))
    n_compiled = getattr(run, "_cache_size", lambda: 1)()
    assert n_compiled == 1, f"l2 sweep recompiled: {n_compiled} executables"
    # a second sparse_grad mode is a second runner, not a new namespace
    batch_s, _, _ = _problem(rng, sparse=True)
    fit_distributed(obj, batch_s, mesh, jnp.zeros(d), l2=1.0, config=cfg,
                    sparse_grad="csc")
    assert len(entries[0][1]) == 2


def test_resolve_sparse_grad_auto():
    """'auto' resolves per measured platform table (scatter on CPU),
    explicit names pass through, dense features force scatter."""
    from photon_ml_tpu.parallel.data_parallel import resolve_sparse_grad
    from photon_ml_tpu.types import SparseFeatures
    import jax.numpy as jnp

    sp = SparseFeatures(jnp.zeros((4, 2), jnp.int32), None, dim=8)
    assert resolve_sparse_grad("auto", sp) == "scatter"  # tests run on CPU
    assert resolve_sparse_grad("auto", jnp.zeros((4, 8))) == "scatter"
    assert resolve_sparse_grad("csc_pallas", sp) == "csc_pallas"
    assert resolve_sparse_grad("auto") == "scatter"


@pytest.mark.parametrize("mode", ["scatter", "csc", "csc_pallas"])
def test_vector_gather_single_vs_eight_device_equivalence(rng, mesh, mode):
    """The TPU vector-gather path under shard_map: an 8-device mesh fit
    with gather_mode='vector' must reproduce the 1-device scalar-mode
    fit (bit-identical gather arithmetic composed with per-shard psum) —
    the multichip x vector-gather seam the dryrun exercises on hardware."""
    from photon_ml_tpu import types as T
    from photon_ml_tpu.parallel.data_parallel import build_csc

    batch, X, y = _problem(rng, sparse=True)
    d = X.shape[1]
    obj = make_objective("logistic")
    cfg = OptimizerConfig(max_iters=40, tolerance=1e-10)
    # force the vector path despite CPU (and below-threshold sizes)
    monkey_min = T._GATHER_MIN_SIZE
    T._GATHER_MIN_SIZE = 0
    T.set_gather_mode("scalar")
    try:
        csc = build_csc(obj, batch, make_mesh({"data": 1}))
        ref = fit_distributed(obj, batch, make_mesh({"data": 1}),
                              jnp.zeros(d), l2=0.5, config=cfg,
                              sparse_grad=mode,
                              precomputed_csc=csc if mode != "scatter" else None)
        T.set_gather_mode("vector")
        csc8 = build_csc(obj, batch, mesh)
        got = fit_distributed(obj, batch, mesh, jnp.zeros(d), l2=0.5,
                              config=cfg, sparse_grad=mode,
                              precomputed_csc=csc8 if mode != "scatter" else None)
    finally:
        T._GATHER_MIN_SIZE = monkey_min
        T.set_gather_mode("auto")
    np.testing.assert_allclose(got.w, ref.w, rtol=1e-6, atol=1e-9,
                               err_msg=mode)
    np.testing.assert_allclose(got.value, ref.value, rtol=1e-9, err_msg=mode)
