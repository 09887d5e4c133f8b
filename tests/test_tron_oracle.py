"""The three pieces of ``make_csc_path``'s second-order oracle (ISSUE 37):
the curvature vector ``d2(w)`` of an iterate, the Hessian-vector product and
the Jacobi diagonal that read it — against ``GLMObjective``'s own
``diagonal_hessian`` / ``hvp`` (autodiff and scatter-adds) on 1 and 4
virtual CPU devices, and against the one-call ``hvp(w, v)`` bit for bit."""

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
