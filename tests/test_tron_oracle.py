"""The three pieces of ``make_csc_path``'s second-order oracle (ISSUE 37):
the curvature vector ``d2(w)`` of an iterate, the Hessian-vector product and
the Jacobi diagonal that read it — against ``GLMObjective``'s own
``diagonal_hessian`` / ``hvp`` (autodiff and scatter-adds) on 1 and 4
virtual CPU devices, and against the one-call ``hvp(w, v)`` bit for bit.
And the halves that share a point's margins (``value``, ``grad_at``,
``d2_at``) against the one-call ``fg`` and ``curvature``, and OWL-QN and
TRON handed them as a ``MarginOracle`` against the same optimizers handed
the black box, bit for bit."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from photon_ml_tpu.ops.normalization import NormalizationContext
from photon_ml_tpu.ops.objective import make_objective
from photon_ml_tpu.parallel import make_mesh, shard_batch
from photon_ml_tpu.parallel.data_parallel import make_csc_path
from photon_ml_tpu.types import LabeledBatch, SparseFeatures

N, DIM, K = 203, 48, 5  # 203 rows: padded to the 4-way mesh
L2 = 0.7


def _batch(rng, valued, dtype=jnp.float64, n=N, dim=DIM, k=K):
    indices = jnp.asarray(rng.integers(0, dim, (n, k)), jnp.int32)
    values = (jnp.asarray(rng.normal(size=(n, k)), dtype) if valued
              else None)
    return LabeledBatch(
        SparseFeatures(indices, values, dim=dim),
        jnp.asarray(rng.poisson(0.5, n), dtype),
        jnp.asarray(rng.normal(size=n) * 0.3 - 1.0, dtype),  # offsets
        jnp.asarray(rng.random(n) + 0.5, dtype))


def _oracle(obj, batch, chips, use_pallas=False):
    mesh = make_mesh({"data": chips}, devices=jax.devices()[:chips])
    path = make_csc_path(obj, mesh, use_pallas=use_pallas)
    sharded = shard_batch(batch, mesh)
    return path, sharded, jax.jit(path.build)(sharded)


@pytest.mark.parametrize("chips", [1, 4])
@pytest.mark.parametrize("valued", [False, True], ids=["ones", "valued"])
@pytest.mark.parametrize("task", ["logistic", "poisson"])
def test_diagonal_through_the_view_is_the_scatter_diagonal(rng, task, valued,
                                                           chips):
    obj = make_objective(task)
    batch = _batch(rng, valued)
    w = jnp.asarray(rng.normal(size=DIM) * 0.2)
    path, sharded, csc = _oracle(obj, batch, chips)
    d2 = path.curvature(w, sharded)
    assert d2.shape == sharded.labels.shape  # one a row, padding included
    want = obj.diagonal_hessian(w, batch, L2)
    np.testing.assert_allclose(path.diag_at(d2, csc, L2), want, rtol=1e-12)
    assert float(jnp.min(want)) >= L2  # a positive diagonal


@pytest.mark.parametrize("chips", [1, 4])
@pytest.mark.parametrize("shifts", [False, True])
def test_diagonal_under_a_normalization_and_an_intercept(rng, shifts, chips):
    """``diagonal_hessian``'s expansion of the shifted, scaled square through
    two transposes of ``d2``, the intercept's slot pinned to factor 1 and
    shift 0 and left out of the L2 term."""
    norm = NormalizationContext(
        factors=jnp.asarray(rng.uniform(0.5, 2.0, DIM)),
        shifts=jnp.asarray(rng.normal(size=DIM) * 0.1) if shifts else None,
        intercept_index=0)
    obj = make_objective("logistic", normalization=norm, intercept_index=0)
    batch = _batch(rng, valued=True)
    batch = batch.replace(labels=jnp.minimum(batch.labels, 1.0))
    w = jnp.asarray(rng.normal(size=DIM) * 0.2)
    path, sharded, csc = _oracle(obj, batch, chips)
    got = path.diag_at(path.curvature(w, sharded), csc, L2)
    want = obj.diagonal_hessian(w, batch, L2)
    np.testing.assert_allclose(got, want, rtol=1e-12, atol=1e-13)
    v = jnp.asarray(rng.normal(size=DIM))
    np.testing.assert_allclose(
        path.hvp_at(path.curvature(w, sharded), v, sharded, csc, L2),
        obj.hvp(w, v, batch, L2), rtol=1e-10, atol=1e-12)


@pytest.mark.parametrize("mode", ["csc", "csc_pallas"])
def test_float32_diagonal_of_all_positive_curvature(rng, mode):
    """``d2`` is all-positive: the case a global f32 prefix sum loses (its
    prefix grows linearly over all 2^19 nonzeros) and the blocked cumsum
    holds. A column's sum is a difference of block-local prefixes, so its
    error is a few ulps of the largest such prefix, whatever the number of
    blocks (a block of 2^16 nonzeros; 1.7e-5 of a column at this shape,
    where a column holds 512 of them). Under ``csc_pallas`` the diagonal
    takes the same cumsum and not the Pallas scan, whose MXU dots round
    their inputs to bfloat16 on the chip (``make_csc_path``): the bound is
    the same."""
    n, dim, k = 1 << 14, 1 << 10, 32
    obj = make_objective("poisson")
    batch = _batch(rng, valued=False, dtype=jnp.float32, n=n, dim=dim, k=k)
    w = jnp.asarray(rng.normal(size=dim) * 0.1, jnp.float32)
    path, sharded, csc = _oracle(obj, batch, 1, use_pallas=mode == "csc_pallas")
    d2 = path.curvature(w, sharded)
    assert d2.dtype == jnp.float32 and float(jnp.min(d2)) > 0
    got = np.asarray(path.diag_at(d2, csc, 0.0), np.float64)
    wide = jax.tree.map(lambda a: a.astype(jnp.float64)
                        if a.dtype == jnp.float32 else a, batch)
    want = np.asarray(obj.diagonal_hessian(w.astype(jnp.float64), wide, 0.0))
    blocks = np.asarray(d2, np.float64)[np.asarray(csc.rows[0])].reshape(
        -1, 1 << 16).sum(axis=1)
    ulp = float(jnp.finfo(jnp.float32).eps) * blocks.max()
    rtol = 4 * ulp / float(want.min())
    assert blocks.size == 8 and rtol < 1e-4
    assert float(np.max(np.abs(got - want) / want)) < rtol


@pytest.mark.parametrize("chips", [1, 4])
@pytest.mark.parametrize("mode", ["csc", "csc_pallas"])
def test_product_at_the_curvature_is_the_one_call_product(rng, mode, chips):
    """``hvp(w, v)`` is ``hvp_at(curvature(w), v)``: one body, so equal to
    the bit, and both are the objective's autodiff product."""
    obj = make_objective("poisson")
    batch = _batch(rng, valued=True)
    w = jnp.asarray(rng.normal(size=DIM) * 0.2)
    v = jnp.asarray(rng.normal(size=DIM))
    path, sharded, csc = _oracle(obj, batch, chips,
                                 use_pallas=mode == "csc_pallas")
    one_call = jax.jit(path.hvp)(w, v, sharded, csc, L2)
    at = jax.jit(lambda w, v, b, c: path.hvp_at(path.curvature(w, b), v, b,
                                                c, L2))(w, v, sharded, csc)
    np.testing.assert_array_equal(np.asarray(one_call), np.asarray(at))
    np.testing.assert_allclose(one_call, obj.hvp(w, v, batch, L2),
                               rtol=1e-10, atol=1e-12)


def _margin_oracle(path, sharded, csc, l2=L2):
    from photon_ml_tpu.optimize import MarginOracle

    return MarginOracle(
        value=lambda w: path.value(w, sharded, l2),
        grad=lambda w, m: path.grad_at(w, m, sharded, csc, l2),
        curvature=lambda m: path.d2_at(m, sharded))


@pytest.mark.parametrize("chips", [1, 4])
@pytest.mark.parametrize("mode", ["csc", "csc_pallas"])
@pytest.mark.parametrize("normalized", [False, True])
def test_halves_are_the_one_call_oracle(rng, normalized, mode, chips):
    """``fg`` is ``grad_at`` of ``value``'s margins and ``curvature`` is
    ``d2_at`` of them: one body each, so equal to the bit; and both are the
    objective's own (f, g) and d2."""
    if normalized:
        norm = NormalizationContext(
            factors=jnp.asarray(rng.uniform(0.5, 2.0, DIM)),
            shifts=jnp.asarray(rng.normal(size=DIM) * 0.1),
            intercept_index=0)
        obj = make_objective("poisson", normalization=norm, intercept_index=0)
    else:
        obj = make_objective("poisson")
    batch = _batch(rng, valued=True)
    w = jnp.asarray(rng.normal(size=DIM) * 0.2)
    path, sharded, csc = _oracle(obj, batch, chips,
                                 use_pallas=mode == "csc_pallas")
    oracle = _margin_oracle(path, sharded, csc)

    def halves(w):
        f, m = oracle.value(w)
        return f, oracle.grad(w, m)

    f, g = jax.jit(halves)(w)
    d2 = jax.jit(lambda w: oracle.curvature(oracle.value(w)[1]))(w)
    m = jax.jit(oracle.value)(w)[1]
    assert m.shape == sharded.labels.shape  # one a row, padding included
    f1, g1 = jax.jit(lambda w: path.fg(w, sharded, csc, L2))(w)
    c1 = jax.jit(lambda w: path.curvature(w, sharded))(w)
    for got, want in ((f, f1), (g, g1), (d2, c1)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    f2, g2 = obj.value_and_grad(w, batch, L2)
    np.testing.assert_allclose(f, f2, rtol=1e-12)
    np.testing.assert_allclose(g, g2, rtol=1e-10, atol=1e-12)


@pytest.mark.parametrize("chips", [1, 4])
@pytest.mark.parametrize("optimizer", ["owlqn", "tron"])
def test_margin_form_is_the_black_box_form(rng, optimizer, chips):
    """The same fit twice over one sorted view: handed ``fg`` alone (TRON
    ``fg`` in halves whose "margins" are ``w`` itself, so its curvature
    gathers ``X w`` again: the parent's program), and handed the halves.
    The margins an evaluation reads are the array a gather made, so every
    number is the same to the bit; OWL-QN's count of gathers differs by the
    evaluations that read margins (``margins_reused``), its gradient at
    every accepted point."""
    from photon_ml_tpu.optimize import OptimizerConfig, owlqn, tron

    obj = make_objective("poisson")
    batch = _batch(rng, valued=True)
    path, sharded, csc = _oracle(obj, batch, chips)
    oracle = _margin_oracle(path, sharded, csc)
    fg = lambda w: path.fg(w, sharded, csc, L2)
    w0 = jnp.zeros(DIM)
    if optimizer == "owlqn":
        cfg = OptimizerConfig(max_iters=12, tolerance=0.0)
        black = jax.jit(lambda w: owlqn(fg, w, 0.3, cfg))(w0)
        halves = jax.jit(lambda w: owlqn(fg, w, 0.3, cfg, margins=oracle))(w0)
        passes = int(black.iterations)
        assert int(black.margins_reused) == 0
        assert int(halves.margins_reused) == passes > 5
        assert int(black.gather_products) - int(halves.gather_products) == (
            passes)
        assert int(halves.gather_products) == 1 + int(halves.line_search_trials)
        same = halves._replace(margins_reused=None, gather_products=None)
        want = black._replace(margins_reused=None, gather_products=None)
    else:
        from photon_ml_tpu.optimize import MarginOracle

        cfg = OptimizerConfig(max_iters=8, tolerance=0.0)
        second = dict(hvp=lambda d2, v: path.hvp_at(d2, v, sharded, csc, L2),
                      precond=lambda d2: path.diag_at(d2, csc, L2))
        at_w = MarginOracle(value=lambda w: (fg(w)[0], w),
                            grad=lambda w, _: fg(w)[1],
                            curvature=lambda w: path.curvature(w, sharded))
        black = jax.jit(lambda w: tron(fg, w, cfg, margins=at_w,
                                       **second))(w0)
        halves = jax.jit(lambda w: tron(fg, w, cfg, margins=oracle,
                                        **second))(w0)
        assert int(halves.margins_reused) == (
            int(halves.curvature_passes) - 1) >= 2
        same, want = halves, black
    for a, b in zip(jax.tree.leaves(same), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert len(jax.tree.leaves(same)) == len(jax.tree.leaves(want)) > 8
