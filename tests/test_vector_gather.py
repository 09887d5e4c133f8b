"""Vectorized 1-D table gather (``types.table_gather``).

XLA's word-granular gather ran at ~1 GB/s on the chip (builder-measured
on a v5e, 2026-07-31, not re-measured since) — a serial lowering that
bounded the whole fit. ``table_gather`` replaces it with a (1,128)-slice row gather
plus a one-hot lane select, which is bit-identical arithmetic (one real
value + 127 exact zeros per output element). These tests pin that
bit-identity on every path (direct, chunked, values/implicit-ones, and
through margins + every CSC apply) so the fast path can be enabled on
TPU with zero accuracy caveats.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from photon_ml_tpu import types as T


@pytest.fixture
def vector_mode():
    T.set_gather_mode("vector")
    yield
    T.set_gather_mode("auto")


def _rand_table_idx(rng, d, shape):
    table = jnp.asarray(rng.standard_normal(d), jnp.float32)
    idx = jnp.asarray(rng.integers(0, d, size=shape), jnp.int32)
    return table, idx


@pytest.mark.parametrize("d", [1000, 4096, 130])  # incl. non-multiples of 128
@pytest.mark.parametrize("shape", [(1 << 15,), (1 << 11, 16)])
def test_bit_identical_to_scalar_gather(vector_mode, d, shape):
    rng = np.random.default_rng(0)
    table, idx = _rand_table_idx(rng, d, shape)
    out = jax.jit(T.table_gather)(table, idx)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(table)[idx])


def test_chunked_path_bit_identical(vector_mode, monkeypatch):
    # force the lax.map chunking with an uneven final chunk
    monkeypatch.setattr(T, "_GATHER_CHUNK", 1 << 12)
    rng = np.random.default_rng(1)
    table, idx = _rand_table_idx(rng, 2048, ((1 << 14) + 123,))
    out = jax.jit(T.table_gather)(table, idx)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(table)[idx])


@pytest.mark.parametrize("rows_cap", [5, 8, 16])  # 4, 2 runs; one, whole
def test_table_read_in_runs_bit_identical(vector_mode, monkeypatch, rows_cap):
    """A table of more than ``_GATHER_TABLE_BYTES`` is gathered run by run
    (ISSUE 29: out of a table too large for the TPU's fast memory space a
    row costs 9-15 ns, out of a run that fits 1.8): the last run is
    ragged, indices sit on every run's edges, and the chunk loop runs
    inside each run."""
    monkeypatch.setattr(T, "_GATHER_TABLE_BYTES", rows_cap * 128 * 4)
    monkeypatch.setattr(T, "_GATHER_CHUNK", 1 << 12)
    rng = np.random.default_rng(4)
    d = 15 * 128 + 77  # 16 rows of 128 once padded
    table, idx = _rand_table_idx(rng, d, ((1 << 14) + 5,))
    edges = jnp.asarray([0, d - 1] + [r * 128 + o for r in range(1, 16)
                                      for o in (-1, 0, 1) if r * 128 + o < d],
                        jnp.int32)
    idx = idx.at[:edges.shape[0]].set(edges)
    lowered = jax.jit(T.table_gather).lower(table, idx).as_text()
    runs = -(-16 // rows_cap)
    assert lowered.count("stablehlo.while") == runs  # a chunk loop a run
    out = jax.jit(T.table_gather)(table, idx)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(table)[idx])
    sorted_idx = jnp.sort(idx)  # what the boundary combine hands in
    np.testing.assert_array_equal(
        np.asarray(jax.jit(T.table_gather)(table, sorted_idx)),
        np.asarray(table)[sorted_idx])


def test_small_and_scalar_modes_fall_through(vector_mode):
    rng = np.random.default_rng(2)
    table, idx = _rand_table_idx(rng, 512, (64,))  # below _GATHER_MIN_SIZE
    np.testing.assert_array_equal(
        np.asarray(T.table_gather(table, idx)), np.asarray(table)[idx])
    T.set_gather_mode("scalar")
    table, idx = _rand_table_idx(rng, 4096, (1 << 15,))
    np.testing.assert_array_equal(
        np.asarray(T.table_gather(table, idx)), np.asarray(table)[idx])


def test_set_gather_mode_rejects_unknown():
    with pytest.raises(ValueError):
        T.set_gather_mode("fast")


def _sparse_batch(rng, n=4096, d=700, k=5, implicit=False):
    idx = jnp.asarray(rng.integers(0, d, size=(n, k)), jnp.int32)
    vals = (None if implicit
            else jnp.asarray(rng.standard_normal((n, k)), jnp.float32))
    return T.SparseFeatures(idx, vals, dim=d)


@pytest.mark.parametrize("implicit", [False, True])
def test_margins_parity_vector_vs_scalar(implicit):
    rng = np.random.default_rng(3)
    feats = _sparse_batch(rng, implicit=implicit)
    w = jnp.asarray(rng.standard_normal(700), jnp.float32)
    T.set_gather_mode("scalar")
    ref = jax.jit(T.margins)(feats, w)
    try:
        T.set_gather_mode("vector")
        out = jax.jit(T.margins)(feats, w)
    finally:
        T.set_gather_mode("auto")
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


@pytest.mark.parametrize("implicit", [False, True])
@pytest.mark.parametrize("apply_name",
                         ["csc_transpose_apply", "pallas"])
def test_csc_applies_parity_vector_vs_scalar(implicit, apply_name):
    rng = np.random.default_rng(4)
    feats = _sparse_batch(rng, n=8192, k=4, implicit=implicit)
    csc = T.build_csc_transpose(feats.indices, feats.values, feats.dim)
    d = jnp.asarray(rng.standard_normal(8192), jnp.float32)
    if apply_name == "pallas":
        from photon_ml_tpu.ops.pallas_kernels import csc_transpose_apply_pallas
        fn = jax.jit(lambda c, x: csc_transpose_apply_pallas(c, x))
    else:
        fn = jax.jit(getattr(T, apply_name))
    T.set_gather_mode("scalar")
    ref = fn(csc, d)
    try:
        T.set_gather_mode("vector")
        out = fn(csc, d)
    finally:
        T.set_gather_mode("auto")
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
