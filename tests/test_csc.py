"""Scatter-free CSC sparse-gradient path: exact parity with the autodiff/
scatter path for values, gradients, HVPs, and full fits across optimizers
(the TPU hot-loop alternative — types.CSCTranspose)."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from photon_ml_tpu.ops.objective import make_objective
from photon_ml_tpu.optimize import OptimizerConfig
from photon_ml_tpu.parallel.data_parallel import (
    distributed_hvp,
    distributed_value_and_grad,
    fit_distributed,
    make_csc_path,
)
from photon_ml_tpu.parallel.mesh import make_mesh, shard_batch
from photon_ml_tpu.types import (
    blocked_boundary_combine,
    build_csc_transpose,
    csc_transpose_apply,
    make_batch,
    SparseFeatures,
    sparse_from_scipy,
    transpose_apply,
)


@pytest.fixture
def sparse_batch(rng):
    import scipy.sparse as sp

    n, d = 512, 48  # n divisible by the 8-device mesh
    X = sp.random(n, d, density=0.15, random_state=3, format="csr")
    w_true = rng.normal(size=d)
    y = (rng.random(n) < 1 / (1 + np.exp(-np.asarray(X @ w_true)))).astype(float)
    feats = sparse_from_scipy(X, dtype=jnp.float64)
    return make_batch(
        feats, y,
        offsets=rng.normal(size=n) * 0.1,
        weights=rng.uniform(0.5, 2.0, size=n),
        dtype=jnp.float64,
    )


def _dense_xt_d(indices, values, d, dim):
    """``X^T d`` in float64 by ``np.add.at`` over the ELL slots: the truth
    the applies are held to, independent of the code under test."""
    indices = np.asarray(indices)
    contrib = np.broadcast_to(np.asarray(d, np.float64)[:, None],
                              indices.shape)
    if values is not None:
        contrib = contrib * np.asarray(values, np.float64)
    out = np.zeros(dim)
    np.add.at(out, indices.reshape(-1), contrib.reshape(-1))
    return out


def test_csc_transpose_apply_matches_scatter(sparse_batch, rng):
    feats = sparse_batch.features
    d_vec = jnp.asarray(rng.normal(size=feats.num_rows))
    csc = build_csc_transpose(feats.indices, feats.values, feats.dim)
    got = csc_transpose_apply(csc, d_vec)
    want = transpose_apply(feats, d_vec)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-12, atol=1e-12)
    np.testing.assert_allclose(
        np.asarray(got),
        _dense_xt_d(feats.indices, feats.values, d_vec, feats.dim),
        rtol=1e-12, atol=1e-12)


def test_csc_fg_and_hvp_match_autodiff(sparse_batch, rng):
    obj = make_objective("logistic")
    mesh = make_mesh()
    batch = shard_batch(sparse_batch, mesh, "data")
    build, fg, hvp = make_csc_path(obj, mesh)[:3]
    csc = jax.jit(build)(batch)

    fg_ad = distributed_value_and_grad(obj, mesh)
    hvp_ad = distributed_hvp(obj, mesh)
    w = jnp.asarray(rng.normal(size=sparse_batch.dim))
    v = jnp.asarray(rng.normal(size=sparse_batch.dim))

    f_csc, g_csc = fg(w, batch, csc, 0.7)
    f_ad, g_ad = fg_ad(w, batch, 0.7)
    np.testing.assert_allclose(float(f_csc), float(f_ad), rtol=1e-12)
    np.testing.assert_allclose(np.asarray(g_csc), np.asarray(g_ad),
                               rtol=1e-9, atol=1e-11)

    h_csc = hvp(w, v, batch, csc, 0.7)
    h_ad = hvp_ad(w, v, batch, 0.7)
    np.testing.assert_allclose(np.asarray(h_csc), np.asarray(h_ad),
                               rtol=1e-9, atol=1e-11)


@pytest.mark.parametrize("optimizer,l1", [("lbfgs", 0.0), ("tron", 0.0),
                                          ("owlqn", 0.05)])
def test_fit_csc_matches_scatter(sparse_batch, optimizer, l1):
    obj = make_objective("logistic")
    mesh = make_mesh()
    cfg = OptimizerConfig(max_iters=150, tolerance=1e-12)
    w0 = jnp.zeros(sparse_batch.dim)
    kw = dict(l2=0.3, l1=l1, optimizer=optimizer, config=cfg)
    res_sc = fit_distributed(obj, sparse_batch, mesh, w0, **kw)
    res_csc = fit_distributed(obj, sparse_batch, mesh, w0,
                              sparse_grad="csc", **kw)
    assert bool(res_csc.converged)
    np.testing.assert_allclose(float(res_csc.value), float(res_sc.value),
                               rtol=1e-10)
    np.testing.assert_allclose(np.asarray(res_csc.w), np.asarray(res_sc.w),
                               rtol=1e-5, atol=1e-8)


def _normalized_batch(rng, norm_type):
    """Sparse batch with an explicit intercept column (standardization
    needs one) plus its NormalizationContext."""
    import scipy.sparse as sp

    from photon_ml_tpu.ops.normalization import build_normalization_context
    from photon_ml_tpu.ops.statistics import summarize_features
    from photon_ml_tpu.types import SparseFeatures

    n, d = 256, 24
    X = sp.random(n, d, density=0.2, random_state=5, format="csr").toarray()
    X[:, 3] *= 40.0  # wild scales so normalization actually matters
    X[:, 7] *= 0.01
    Xi = np.concatenate([X, np.ones((n, 1))], axis=1)  # intercept col = d
    w_true = rng.normal(size=d + 1)
    y = (rng.random(n) < 1 / (1 + np.exp(-(Xi @ w_true)))).astype(float)
    feats = sparse_from_scipy(sp.csr_matrix(Xi), dtype=jnp.float64)
    batch = make_batch(feats, y, weights=rng.uniform(0.5, 2.0, size=n),
                       dtype=jnp.float64)
    ctx = build_normalization_context(
        norm_type, summarize_features(batch), intercept_index=d)
    return batch, ctx, d


@pytest.mark.parametrize("norm_type", ["scale_with_standard_deviation",
                                       "standardization"])
@pytest.mark.parametrize("optimizer", ["lbfgs", "tron"])
def test_csc_normalized_fit_matches_scatter(rng, norm_type, optimizer):
    """Normalization on the CSC fast path: full fits match the autodiff/
    scatter path (gradient chain rule + HVP both normalized)."""
    batch, ctx, d = _normalized_batch(rng, norm_type)
    obj = make_objective("logistic", normalization=ctx, intercept_index=d)
    mesh = make_mesh()
    w0 = jnp.zeros(d + 1, jnp.float64)
    kw = dict(l2=0.3, optimizer=optimizer,
              config=OptimizerConfig(max_iters=60, tolerance=1e-12))
    res_sc = fit_distributed(obj, batch, mesh, w0, **kw)
    res_csc = fit_distributed(obj, batch, mesh, w0, sparse_grad="csc", **kw)
    np.testing.assert_allclose(float(res_csc.value), float(res_sc.value),
                               rtol=1e-9)
    np.testing.assert_allclose(np.asarray(res_csc.w), np.asarray(res_sc.w),
                               rtol=1e-4, atol=1e-7)


def test_csc_normalized_fg_hvp_exact(rng):
    """Pointwise value/grad/HVP parity (tighter than whole-fit parity)."""
    batch, ctx, d = _normalized_batch(rng, "standardization")
    obj = make_objective("logistic", normalization=ctx, intercept_index=d)
    mesh = make_mesh()
    sharded = shard_batch(batch, mesh)
    fg_ref = distributed_value_and_grad(obj, mesh)
    hvp_ref = distributed_hvp(obj, mesh)
    build, fg_csc, hvp_csc = make_csc_path(obj, mesh)[:3]
    csc = jax.jit(build)(sharded)
    w = jnp.asarray(rng.normal(size=d + 1))
    v = jnp.asarray(rng.normal(size=d + 1))
    f_ref, g_ref = fg_ref(w, sharded, 0.2)
    f_csc, g_csc = fg_csc(w, sharded, csc, 0.2)
    np.testing.assert_allclose(float(f_csc), float(f_ref), rtol=1e-12)
    np.testing.assert_allclose(np.asarray(g_csc), np.asarray(g_ref),
                               rtol=1e-9, atol=1e-11)
    np.testing.assert_allclose(
        np.asarray(hvp_csc(w, v, sharded, csc, 0.2)),
        np.asarray(hvp_ref(w, v, sharded, 0.2)), rtol=1e-9, atol=1e-11)

def test_game_fixed_coordinate_csc_matches_scatter():
    from photon_ml_tpu.estimators import GameTransformer
    from photon_ml_tpu.game.descent import CoordinateConfig, CoordinateDescent
    from photon_ml_tpu.testing import game_dataset_from_synthetic, synthetic_game_data

    data = synthetic_game_data({"userId": 8}, seed=6)
    train = game_dataset_from_synthetic(data)

    def run(sparse_grad):
        cd = CoordinateDescent([
            CoordinateConfig("fixed", coordinate_type="fixed",
                             feature_shard="global", reg_type="l2",
                             reg_weight=0.5, max_iters=60,
                             sparse_grad=sparse_grad),
        ], task="logistic", dtype=jnp.float64)
        model, _ = cd.run(train)
        return np.asarray(GameTransformer(model).transform(train))

    s_scatter = run("scatter")
    s_csc = run("csc")
    np.testing.assert_allclose(s_csc, s_scatter, rtol=1e-6, atol=1e-8)


@pytest.mark.parametrize("implicit", [False, True],
                         ids=["explicit", "implicit_ones"])
def test_build_csc_view_has_three_leaves(rng, implicit):
    """The view ``build_csc`` leaves in HBM is ``values`` / ``rows`` /
    ``col_starts`` and nothing else: 4 B a nonzero and 4 B a column
    boundary a shard under the implicit-ones layout, the values' bytes on
    top under the explicit one."""
    import dataclasses

    from photon_ml_tpu.parallel.data_parallel import build_csc
    from photon_ml_tpu.types import CSCTranspose

    n, k, dim, shards = 256, 6, 40, 8
    indices = jnp.asarray(rng.integers(0, dim, (n, k)), jnp.int32)
    values = None if implicit else jnp.asarray(rng.normal(size=(n, k)),
                                               jnp.float32)
    batch = make_batch(SparseFeatures(indices, values, dim=dim),
                       np.zeros(n), dtype=jnp.float32)
    csc = build_csc(make_objective("logistic"), batch, make_mesh())
    assert [f.name for f in dataclasses.fields(CSCTranspose)] == [
        "values", "rows", "col_starts"]
    nnz = n * k // shards
    assert csc.rows.shape == (shards, nnz) and csc.rows.dtype == jnp.int32
    assert csc.col_starts.shape == (shards, dim + 1)
    assert (csc.values is None) == implicit
    per_shard = sum(a.nbytes for a in jax.tree.leaves(csc)) // shards
    assert per_shard == 4 * nnz + 4 * (dim + 1) + (0 if implicit else 4 * nnz)


def test_blocked_prefix_accuracy_at_scale(rng):
    """The f32 cumsum-difference transpose must not lose accuracy with nnz.

    All-positive contributions (the HVP d2 path) are the worst case: a
    global f32 prefix grows linearly, so boundary differences cancel
    catastrophically — at 4M nnz a naive global prefix is off by ~1e-2
    relative per column. The blocked two-level scheme keeps the error at
    the sqrt(block)*eps level regardless of nnz."""
    from photon_ml_tpu.types import csc_transpose_apply

    n, k, dim = 1 << 17, 32, 1 << 12
    nnz = n * k
    indices = jnp.asarray(rng.integers(0, dim, (n, k)), jnp.int32)
    csc = build_csc_transpose(indices, None, dim)
    # all-positive d (like weights * loss.d2 * direction-margin^2 terms)
    d32 = jnp.asarray(rng.random(n) + 0.5, jnp.float32)

    got = csc_transpose_apply(csc, d32)  # blocked f32 path
    ref = _dense_xt_d(indices, None, d32, dim)  # f64 ground truth
    rel = np.abs(np.asarray(got, np.float64) - ref) / np.maximum(ref, 1e-30)
    assert float(rel.max()) < 1e-4, float(rel.max())

    # naive global f32 prefix, for contrast: demonstrably degraded
    contrib = np.asarray(d32, np.float32)[np.asarray(csc.rows)]
    prefix = np.concatenate([[0.0], np.cumsum(contrib, dtype=np.float32)])
    cs = np.asarray(csc.col_starts)
    naive = prefix[cs[1:]] - prefix[cs[:-1]]
    rel_naive = np.abs(naive - ref) / np.maximum(ref, 1e-30)
    assert float(rel_naive.max()) > float(rel.max()) * 10

    # sign-mixed small case stays exact vs dense in f64
    d64 = jnp.asarray(rng.normal(size=n), jnp.float64)
    got64 = csc_transpose_apply(csc, d64)
    np.testing.assert_allclose(got64, _dense_xt_d(indices, None, d64, dim),
                               rtol=1e-9, atol=1e-9)


def test_pallas_blocked_accuracy_all_positive(rng):
    """The Pallas per-tile scan + blocked combine must match the f64
    reference on all-positive contributions at a scale where a global f32
    scan would already be degraded (several hundred tiles of growth)."""
    from photon_ml_tpu.ops.pallas_kernels import csc_transpose_apply_pallas

    n, k, dim = 1 << 14, 32, 1 << 10
    indices = jnp.asarray(rng.integers(0, dim, (n, k)), jnp.int32)
    csc32 = build_csc_transpose(indices, None, dim)
    d32 = jnp.asarray(rng.random(n) + 0.5, jnp.float32)
    got = np.asarray(csc_transpose_apply_pallas(csc32, d32), np.float64)
    ref = _dense_xt_d(indices, None, d32, dim)
    rel = np.abs(got - ref) / np.maximum(ref, 1e-30)
    assert float(rel.max()) < 1e-4, float(rel.max())


# -- the boundary combine against the four-gather formula (ISSUE 27) --------
def _four_gather_combine(local_flat, bt, col_starts, T):
    """The oracle: ``blocked_boundary_combine`` as it stood up to PR 26,
    which read the block totals for every column (``bt[b0]``, ``BP[b1]``,
    ``BP[b0 + 1]``: three gathers over dim) and chose per column."""
    B = bt.shape[0]
    BP = jnp.concatenate([jnp.zeros((1,), bt.dtype), jnp.cumsum(bt)])
    cs = col_starts.astype(jnp.int32)
    b, r = cs // T, cs % T
    lp = jnp.where(r > 0, local_flat[jnp.maximum(cs - 1, 0)],
                   jnp.zeros((), local_flat.dtype))
    b0, b1 = b[:-1], b[1:]
    lp0, lp1 = lp[:-1], lp[1:]
    suffix0 = bt[jnp.minimum(b0, B - 1)] - lp0
    mid = BP[b1] - BP[jnp.minimum(b0 + 1, B)]
    return jnp.where(b0 == b1, lp1 - lp0, suffix0 + mid + lp1)


def _spread(r, nnz, dim):
    return r.multinomial(nnz, np.full(dim, 1.0 / dim))


# name -> (T, nonzeros per column, drawn from the generator handed in)
COMBINE_CASES = {
    "nnz_multiple_of_T": (8, lambda r: _spread(r, 64, 40)),
    "nnz_not_multiple_of_T": (8, lambda r: _spread(r, 61, 40)),
    # column 5 holds [3, 33): a suffix, three whole blocks, a head of one
    "column_wider_than_two_blocks":
        (8, lambda r: [1, 2, 0, 0, 0, 30, 3, 0, 4, 1, 0, 2]),
    # column 3 holds [5, 16): spans block 0 to the very end of block 1
    "column_ends_on_block_boundary": (8, lambda r: [2, 3, 0, 11, 5, 0, 3]),
    # columns 3-5 are empty at 8, columns 8-9 at 16 == nnz == B * T
    "empty_columns_on_boundary":
        (8, lambda r: [3, 0, 5, 0, 0, 0, 6, 2, 0, 0]),
    "dim_1": (8, lambda r: [29]),
    "dim_below_B": (4, lambda r: _spread(r, 203, 7)),  # B = 51 blocks
    "zipf_columns": (16, lambda r: np.bincount(
        np.minimum(r.zipf(1.3, 1000) - 1, 299), minlength=300)),
}


def _combine_problem(case, seed):
    """Block-local f32 prefixes, block totals and column starts of one
    draw of ``case``; the values differ from seed to seed, and the
    columns too where the case draws them."""
    T, counts = COMBINE_CASES[case]
    r = np.random.default_rng(seed)
    counts = np.asarray(counts(r))
    col_starts = np.concatenate([[0], np.cumsum(counts)]).astype(np.int32)
    nnz = int(col_starts[-1])
    B = -(-nnz // T)
    contrib = np.pad(r.normal(size=nnz).astype(np.float32), (0, B * T - nnz))
    local = np.cumsum(contrib.reshape(B, T), axis=1, dtype=np.float32)
    return (jnp.asarray(local.reshape(-1)), jnp.asarray(local[:, -1]),
            jnp.asarray(col_starts), T)


# -- where `lp` takes the vector form (ISSUE 29) -----------------------------
# On a TPU the gather over the column boundaries reads 128-lane rows once
# dim >= _GATHER_MIN_SIZE. Here: gather mode "vector", dim = 2^14 + 37 and
# _GATHER_CHUNK = 2^12, so `lax.map` runs four whole chunks and a ragged
# fifth. name -> (T, nonzeros per column of the named layout's head; the
# columns after it share what is left of VECTOR_NNZ, some none of it)
VECTOR_DIM = (1 << 14) + 37
VECTOR_CHUNK = 1 << 12
VECTOR_NNZ = (1 << 16) + 61
VECTOR_COMBINE_CASES = {
    # columns 1-4, 6-7 and 9-11 are empty: 6-7 at a block edge (256)
    "vector_empty_columns":
        (128, lambda r: [5, 0, 0, 0, 0, 251, 0, 0, 7, 0, 0, 0]),
    # column 2 holds [9, 700): a suffix, four whole blocks, a head of 60
    "vector_column_spanning_several_blocks": (128, lambda r: [4, 5, 691, 3]),
    # column 1 ends on 256 == 2 * T; column 3 starts and ends on edges
    "vector_boundary_on_a_block_edge":
        (128, lambda r: [100, 156, 0, 128, 1]),
    # prefixes that only grow: what d2 * mv of the HVP path looks like
    "vector_all_positive_d": (128, lambda r: r.integers(0, 4, 64)),
    # columns wider than T, and wider than 3 T, among the first 300
    "vector_zipf_columns":
        (64, lambda r: np.minimum(r.zipf(1.3, 300), 200)),
}


def _vector_combine_problem(case, seed):
    T, head = VECTOR_COMBINE_CASES[case]
    r = np.random.default_rng(seed)
    head = np.asarray(head(r))
    counts = np.concatenate([head, _spread(
        r, VECTOR_NNZ - head.sum(), VECTOR_DIM - head.shape[0])])
    col_starts = np.concatenate([[0], np.cumsum(counts)]).astype(np.int32)
    nnz = int(col_starts[-1])
    B = -(-nnz // T)
    d = (r.random(nnz) + 0.5 if case == "vector_all_positive_d"
         else r.normal(size=nnz))
    contrib = np.pad(d.astype(np.float32), (0, B * T - nnz))
    local = np.cumsum(contrib.reshape(B, T), axis=1, dtype=np.float32)
    return (jnp.asarray(local.reshape(-1)), jnp.asarray(local[:, -1]),
            jnp.asarray(col_starts), T)


@pytest.fixture
def gather_form(request, monkeypatch):
    """The TPU's gather form, for the cases named ``vector_*``."""
    from photon_ml_tpu import types

    if not request.node.callspec.params["case"].startswith("vector_"):
        yield None
        return
    monkeypatch.setattr(types, "_GATHER_CHUNK", VECTOR_CHUNK)
    before = types.gather_mode()
    types.set_gather_mode("vector")
    yield "vector"
    types.set_gather_mode(before)


@pytest.mark.parametrize("under", ["jit", "shard_map"])
@pytest.mark.parametrize(
    "case", list(COMBINE_CASES) + list(VECTOR_COMBINE_CASES))
def test_boundary_combine_bit_equal_to_four_gathers(case, under, gather_form):
    problem = _combine_problem
    if gather_form == "vector":
        from photon_ml_tpu.types import _GATHER_MIN_SIZE

        problem = _vector_combine_problem
        assert VECTOR_DIM + 1 >= _GATHER_MIN_SIZE
        assert VECTOR_DIM % VECTOR_CHUNK  # a ragged last chunk
        # the form engaged: 128-wide rows, chunk by chunk, and no gather
        # of single words over the column boundaries
        local, bt, cs, T = problem(case, 0)
        text = jax.jit(blocked_boundary_combine, static_argnums=3).lower(
            local, bt, cs, T).as_text()
        assert "slice_sizes = array<i64: 1, 128>" in text
        assert f"tensor<{VECTOR_CHUNK}x128xf32>" in text
        assert not re.search(
            rf"tensor<{VECTOR_DIM}(x1)?xi32>\) -> tensor<{VECTOR_DIM}xf32>",
            text)
    if under == "jit":
        local, bt, cs, T = problem(case, 0)
        got = jax.jit(blocked_boundary_combine, static_argnums=3)(
            local, bt, cs, T)
        want = _four_gather_combine(local, bt, cs, T)
        spanning = np.asarray(cs[:-1] // T != cs[1:] // T)
        assert spanning.any()  # every case has the branch to get wrong
        if case == "dim_below_B":
            assert spanning.sum() >= cs.shape[0] - 2
    else:
        # four different draws, one a device of a 4-device mesh
        from jax.sharding import PartitionSpec as P

        mesh = make_mesh({"data": 4}, devices=jax.devices()[:4])
        draws = [problem(case, seed) for seed in range(4)]
        T = draws[0][3]
        local, bt, cs = (jnp.stack(leaf) for leaf in zip(
            *(draw[:3] for draw in draws)))
        got = jax.jit(jax.shard_map(
            lambda l, b, c: blocked_boundary_combine(l[0], b[0], c[0],
                                                     T)[None],
            mesh=mesh, in_specs=P("data"), out_specs=P("data")))(
                local, bt, cs)
        want = jnp.stack([_four_gather_combine(*draw) for draw in draws])
    assert got.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
