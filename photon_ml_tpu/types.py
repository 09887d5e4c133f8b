"""Core batched data types.

TPU-native equivalent of the reference's per-example data model
(``data.LabeledPoint(label, features, offset, weight)`` — SURVEY.md §3.1;
reference mount empty, paths unverified). Instead of one object per example we
hold batched device-resident arrays: a :class:`LabeledBatch` is a pytree so it
crosses ``jit``/``shard_map`` boundaries and can be sharded over a mesh axis.

Sparse features use a row-padded ELL layout (``indices``/``values`` of shape
``[n, k]``): every row is padded to the same nnz width with ``value == 0``
entries, which contribute nothing to margins or gradients regardless of the
padding index. This gives XLA static shapes (no CSR pointer chasing) and keeps
the hot ops — margin gather and gradient scatter-add — vectorized.
"""

from __future__ import annotations

from typing import Optional, Union

import jax
import jax.numpy as jnp
import numpy as np
from flax import struct

# ---------------------------------------------------------------------------
# 1-D table gather for the sparse hot path.
#
# XLA:TPU lowers a word-granular gather (slice size 1) to a serial loop:
# 7.5-18.5 ns an index on the v5e (PERF.md section 6, PR 29: the boundary
# combine's ``lp`` before it took this form), where the form below reads
# 2.8 ns an element. It is the standard TPU embedding-lookup
# shape: reshape the table to [d/128, 128] so each gathered element is a
# full 128-lane row (a vectorizable (1,128)-slice gather), then select the
# wanted lane with a one-hot multiply+reduce on the VPU. The sum adds
# exactly one real value and 127 zeros, so the result is bit-identical to
# ``table[idx]``.
#
# The row-gather materializes a [m, 128] intermediate; for large m it runs
# under ``lax.map`` over fixed-size chunks so the intermediate stays ~128 MB
# regardless of nnz (the bench shape's 82M nnz would otherwise need 42 GB).
# ---------------------------------------------------------------------------

_LANES = 128
_GATHER_CHUNK = 1 << 18  # rows per lax.map step: [2^18, 128] f32 = 128 MB
_GATHER_MIN_SIZE = 1 << 14  # below this, the serial gather costs < ~20 us
# bytes a table may have and still be gathered whole. A row costs 1.8 ns to
# fetch out of a table the TPU compiler keeps in its fast memory space
# (``S(1)`` in a compiled layout) and 9-15 ns out of one it leaves in HBM;
# the largest table seen kept there has 159,744 rows of 128 f32 (PERF.md
# section 7.7; tests/test_tpu_compile.py holds the placement)
_GATHER_TABLE_BYTES = 80 << 20
_gather_mode = "auto"


def set_gather_mode(mode: str) -> None:
    """'auto' (vector on TPU, scalar elsewhere), 'scalar', or 'vector'.

    The seam the tests use to run the vector form on a CPU, where 'auto'
    never picks it; no driver and no benchmark cell sets it. The mode is
    read at TRACE time, so a change must invalidate every cached executable
    that baked the old mode in — otherwise a parity test would compare the
    cached path with itself."""
    global _gather_mode
    if mode not in ("auto", "scalar", "vector"):
        raise ValueError(f"unknown gather mode {mode!r}")
    if mode != _gather_mode:
        _gather_mode = mode
        jax.clear_caches()


def gather_mode() -> str:
    return _gather_mode


def _vector_gather_rows(table2d: jax.Array, idx: jax.Array,
                        scope: str) -> jax.Array:
    # mode="clip": the default 'fill' pays an out-of-bounds select per
    # element (~12% of the pass on the v5e); table_gather's indices are
    # in-bounds by construction (idx < d => idx>>7 < rows), so clamping
    # is semantically a no-op and results stay bit-identical
    with jax.named_scope(scope + "rows"):
        rows = jnp.take(table2d, jnp.right_shift(idx, 7), axis=0,
                        mode="clip")
    with jax.named_scope(scope + "select"):
        lane = jnp.bitwise_and(idx, 127)
        onehot = lane[:, None] == jnp.arange(_LANES, dtype=idx.dtype)[None, :]
        return jnp.sum(jnp.where(onehot, rows, 0), axis=-1)


def _gather_1d(table: jax.Array, idx: jax.Array, scope: str) -> jax.Array:
    """``table_gather``'s body: the choice of form, both forms, and the
    vector form's reading of a long table run by run. The vector form's
    row gather and lane select run under the scopes
    ``scope + "rows"`` and ``scope + "select"``: ``photon-trace kernels``
    files an op under the innermost ``photon.*`` scope of its name stack,
    so a caller that keeps its gather's time under its own scope passes
    ``""`` (the boundary combine's ``lp``) and ``photon.table_gather/*``
    holds the product gathers alone."""
    mode = _gather_mode
    if mode == "auto":
        # TPU only: the serial-gather pathology is a TPU lowering property
        # (PERF.md section 5); GPUs and CPUs gather words natively and
        # would only pay the [m, 128] expansion
        mode = "vector" if jax.default_backend() == "tpu" else "scalar"
    if (mode == "scalar" or table.ndim != 1
            or idx.size < _GATHER_MIN_SIZE or table.shape[0] < _LANES):
        return table[idx]
    d = table.shape[0]
    dp = -(-d // _LANES) * _LANES
    table2d = jnp.pad(table, (0, dp - d)).reshape(dp // _LANES, _LANES)
    flat = idx.reshape(-1).astype(jnp.int32)
    runs = -(-table2d.size * table2d.dtype.itemsize // _GATHER_TABLE_BYTES)
    if runs == 1:
        return _gather_chunks(table2d, flat, scope).reshape(idx.shape)
    # a longer table is read in equal runs of rows that fit, one after the
    # other: every index is gathered from every run (clamped into it) and
    # kept from the run that holds it. `runs` times the work, at a fifth
    # to an eighth of the cost a row
    per_run = -(-table2d.shape[0] // runs)
    for r in range(runs):
        if r:  # cut a run out when the one before is done: one is live
            table2d, out = jax.lax.optimization_barrier((table2d, out))
        part = table2d[r * per_run:(r + 1) * per_run]
        local = flat - r * per_run * _LANES
        got = _gather_chunks(
            part, jnp.clip(local, 0, part.shape[0] * _LANES - 1), scope)
        out = got if r == 0 else jnp.where(local >= 0, got, out)
    return out.reshape(idx.shape)


def _gather_chunks(table2d: jax.Array, flat: jax.Array,
                   scope: str) -> jax.Array:
    """Rows and lanes of ``flat`` out of ``table2d``, ``_GATHER_CHUNK``
    indices at a time."""
    m = flat.shape[0]
    if m <= _GATHER_CHUNK:
        return _vector_gather_rows(table2d, flat, scope)
    c = -(-m // _GATHER_CHUNK)
    flat = jnp.pad(flat, (0, c * _GATHER_CHUNK - m))  # pad idx 0: valid
    return jax.lax.map(
        lambda ix: _vector_gather_rows(table2d, ix, scope),
        flat.reshape(c, _GATHER_CHUNK),
    ).reshape(-1)[:m]


@jax.named_scope("photon.table_gather")
def table_gather(table: jax.Array, idx: jax.Array) -> jax.Array:
    """``table[idx]`` for a 1-D table, vectorized for TPU when profitable.

    Bit-identical to the serial gather on every path for normal floats
    (the lane select adds one real value and 127 zeros). The one
    exception, found by the property fuzz: SUBNORMAL table values
    (|x| < 1.2e-38 f32) flush to zero through the select-sum on
    flush-to-zero backends — the same flush every arithmetic op on TPU
    applies to them anyway, whereas the serial gather is a pure memory
    move and preserves the bits. 'auto' resolves per trace-time backend:
    the vector form pays an extra [m, 128] stream, which wins ~15x on TPU
    where the serial gather is the bottleneck but loses on CPU where the
    serial gather is already fast.
    """
    return _gather_1d(table, idx, "photon.table_gather/")


@struct.dataclass
class SparseFeatures:
    """Row-padded sparse feature matrix (ELL layout).

    Attributes:
      indices: int32 ``[n, k]`` column ids; padding slots may hold any valid
        index (conventionally 0) because their value is 0.
      values: ``[n, k]`` feature values; 0.0 in padding slots. ``None``
        declares the implicit-ones (binary/categorical) layout: every slot
        is a real feature of value 1.0 — Criteo-style one-hot rows with a
        uniform slot count. This halves the bytes every sparse pass touches
        (the TPU hot loop is HBM-bound — docs/PERF.md) and is only valid
        when NO slot is padding (row-level padding with weight-0 rows stays
        safe: their loss/gradient contributions are weight-multiplied).
      dim: static number of feature columns (the dense width).
    """

    indices: jax.Array
    values: Optional[jax.Array]
    dim: int = struct.field(pytree_node=False)

    @property
    def num_rows(self) -> int:
        return self.indices.shape[0]

    def slice_rows(self, start: int, size: int) -> "SparseFeatures":
        return SparseFeatures(
            indices=jax.lax.dynamic_slice_in_dim(self.indices, start, size, 0),
            values=(None if self.values is None else
                    jax.lax.dynamic_slice_in_dim(self.values, start, size, 0)),
            dim=self.dim,
        )

    def todense(self) -> jax.Array:
        n, k = self.indices.shape
        dtype = jnp.float32 if self.values is None else self.values.dtype
        out = jnp.zeros((n, self.dim), dtype)
        rows = jnp.broadcast_to(jnp.arange(n)[:, None], (n, k))
        vals = (jnp.ones((n, k), dtype) if self.values is None
                else self.values)
        return out.at[rows, self.indices].add(vals)


Features = Union[jax.Array, SparseFeatures]


@struct.dataclass
class CSCTranspose:
    """Column-sorted view of a SparseFeatures batch for scatter-free
    transpose products.

    TPU rationale: XLA lowers ``.at[idx].add`` (the reference's gradient-side
    ``treeAggregate`` axpy) to a serialized scatter on TPU. Because the
    sparsity pattern is FIXED across optimizer iterations, we sort the
    nonzeros by column once (argsort + searchsorted, on device, inside the
    jitted fit) and compute ``X^T d`` as gather → cumsum → boundary
    difference: every step vectorizes on the VPU, and the result is
    deterministic (no atomics, no scatter ordering).

    Attributes:
      values: [nnz] feature values sorted by column id.
      rows: [nnz] int32 row id of each sorted nonzero.
      col_starts: [dim+1] int32; column j's nonzeros occupy
        ``values[col_starts[j]:col_starts[j+1]]``.
    """

    values: Optional[jax.Array]  # None under the implicit-ones layout
    rows: jax.Array
    col_starts: jax.Array


@jax.named_scope("photon.csc/build")
def build_csc_transpose(indices: jax.Array, values: Optional[jax.Array],
                        dim: int) -> CSCTranspose:
    """Sort the padded ELL nonzeros by column (pure jax; jit/shard_map safe).
    Padding slots (value 0) are kept — they land in their index's run and
    contribute 0 to every product. ``values=None`` (implicit ones) keeps
    the sorted view value-free too."""
    n, k = indices.shape
    flat_idx = indices.reshape(-1)
    order = jnp.argsort(flat_idx)
    sorted_cols = flat_idx[order]
    return CSCTranspose(
        values=None if values is None else values.reshape(-1)[order],
        rows=(order // k).astype(jnp.int32),
        col_starts=jnp.searchsorted(
            sorted_cols, jnp.arange(dim + 1, dtype=jnp.int32), side="left"
        ).astype(jnp.int32),
    )


def csc_transpose_apply(csc: CSCTranspose, d: jax.Array,
                        block: int = 1 << 16) -> jax.Array:
    """``X^T d`` from the column-sorted view, with no scatter.

    A single global prefix sum followed by boundary differencing is
    numerically unsound in f32: the difference ``prefix[b] - prefix[a]``
    cancels catastrophically once the running prefix dwarfs a column's own
    sum — ~sqrt(nnz)*eps relative error for sign-mixed gradients (~1e-3 at
    82M nnz, measured on hardware), and *unbounded* relative error for the
    all-positive ``d2`` contributions of the HVP path, where the prefix
    grows linearly.

    This is therefore a BLOCKED two-level scheme whose error does
    not grow with nnz: contributions reshape to [B, block]; each block
    gets a local f32 cumsum (magnitudes bounded by one block); a column
    contained in one block differences only local prefixes; a column
    spanning blocks takes (suffix of its first block) + (sum of interior
    block totals) + (head of its last block). Interior sums fall back to
    a block-total prefix difference, but only columns wider than a whole
    block (>= ``block`` nonzeros) ever take it — and for those the
    interior sum *is* the dominant term, so no cancellation. Cost: the
    same one pass of cumsum traffic, plus one gather over the column
    boundaries (``dim`` of them: the first is always 0; in
    ``table_gather``'s form, so 128-lane rows on a TPU) and B-long ones
    for the <= B spanning columns."""
    dg = table_gather(d, csc.rows)
    contrib = dg if csc.values is None else csc.values * dg
    nnz = contrib.shape[0]
    if nnz == 0:
        return jnp.zeros((csc.col_starts.shape[0] - 1,), d.dtype)
    T = min(block, nnz)
    B = -(-nnz // T)
    with jax.named_scope("photon.csc/prefix_sum"):
        padded = jnp.pad(contrib, (0, B * T - nnz)).reshape(B, T)
        local = jnp.cumsum(padded, axis=1)  # [B, T] inclusive, block-local
        bt = local[:, -1]  # [B] block totals
    return blocked_boundary_combine(local.reshape(-1), bt, csc.col_starts,
                                    T).astype(d.dtype)


@jax.named_scope("photon.csc/boundary_combine")
def blocked_boundary_combine(local_flat: jax.Array, bt: jax.Array,
                             col_starts: jax.Array, T: int) -> jax.Array:
    """Column sums from BLOCK-LOCAL inclusive prefixes.

    ``local_flat``: [B*T] inclusive prefix sums that restart at every block
    boundary; ``bt``: [B] block totals. Shared by the XLA cumsum path and
    the Pallas per-tile scan kernel (both produce exactly this pair).
    A column inside one block differences local prefixes only; a spanning
    column takes first-block suffix + interior block totals + last-block
    head, so no difference ever cancels against a prefix that outgrew the
    column's own sum (see ``csc_transpose_apply``).

    The columns' ranges are sorted and disjoint, so each of the B block
    boundaries ``k*T`` lies inside at most one column: at most B columns
    span. One gather runs over the column boundaries (``lp``), in the form
    ``table_gather`` chooses from what it can observe: on a TPU, from
    ``dim >= 2^14`` on, ``local_flat`` is read as 128-lane rows with a
    one-hot lane select (2.8 ns an index for each 80 MiB of prefixes,
    where the scalar gather took 7.5-18.5: PERF.md section 5), under
    ``lp/rows`` and ``lp/select``; elsewhere as ``local_flat[...]``.
    Column 0 starts at nonzero 0 (``col_starts[0] == 0``: nothing sorts
    below column 0), so ``lp[0]`` is the constant 0 and the gather runs
    over the ``dim`` other boundaries, a whole number of chunks at a
    power-of-two ``dim``. The block totals are read for the <= B spanning
    columns alone, which B binary searches in ``col_starts`` find."""
    B = bt.shape[0]
    dim = col_starts.shape[0] - 1
    # exclusive prefix of block totals; only consulted for columns spanning
    # >= 1 full interior block
    BP = jnp.concatenate([jnp.zeros((1,), bt.dtype), jnp.cumsum(bt)])

    cs = col_starts.astype(jnp.int32)
    # local exclusive prefix at each boundary: local[b, r-1], 0 at r == 0
    # (and at boundary 0, which is nonzero 0: r == 0 there)
    with jax.named_scope("lp"):
        zero = jnp.zeros((), local_flat.dtype)
        ends = cs[1:]
        lp = jnp.concatenate([zero[None], jnp.where(
            ends % T > 0, _gather_1d(local_flat, jnp.maximum(ends - 1, 0),
                                     ""), zero)])
    # every column that starts and ends in one block, the empty ones too
    out = lp[1:] - lp[:-1]
    with jax.named_scope("span"):
        # boundary k*T lies inside column j iff cs[j] < k*T <= cs[j+1]: j
        # is the last start below k*T, where there is one and it starts a
        # column (cs[dim] < B*T is the padding's, not a column's)
        edges = jnp.arange(1, B + 1, dtype=jnp.int32) * T
        j = jnp.searchsorted(cs, edges, side="left").astype(jnp.int32) - 1
        spans = (j >= 0) & (j < dim)
        j = jnp.where(spans, j, 0)
        b0, b1 = cs[j] // T, cs[j + 1] // T
        suffix0 = bt[jnp.minimum(b0, B - 1)] - lp[j]
        mid = BP[b1] - BP[jnp.minimum(b0 + 1, B)]  # exact 0 at b1 == b0 + 1
        # a column over several boundaries is written the same value again
        return out.at[jnp.where(spans, j, dim)].set(
            suffix0 + mid + lp[j + 1], mode="drop")


def margins(features: Features, w: jax.Array) -> jax.Array:
    """Per-row margin ``x_i . w`` for dense ``[n, d]`` or sparse features."""
    if isinstance(features, SparseFeatures):
        if features.values is None:  # implicit ones: no value read
            return jnp.sum(table_gather(w, features.indices), axis=-1)
        return jnp.sum(features.values * table_gather(w, features.indices),
                       axis=-1)
    return features @ w


def transpose_apply(features: Features, d: jax.Array) -> jax.Array:
    """``X^T d`` — the gradient-side contraction.

    Dense path is a plain matmul (MXU); sparse path is a scatter-add over the
    padded layout (padding contributes 0 because its value is 0; the
    implicit-ones layout scatters ``d`` directly).
    """
    if isinstance(features, SparseFeatures):
        if features.values is None:
            n, k = features.indices.shape
            contrib = jnp.broadcast_to(d[:, None], (n, k))
            out = jnp.zeros((features.dim,), d.dtype)
        else:
            contrib = features.values * d[:, None]
            out = jnp.zeros((features.dim,), contrib.dtype)
        return out.at[features.indices.reshape(-1)].add(contrib.reshape(-1))
    return features.T @ d


def feature_dim(features: Features) -> int:
    if isinstance(features, SparseFeatures):
        return features.dim
    return features.shape[1]


def num_rows(features: Features) -> int:
    if isinstance(features, SparseFeatures):
        return features.num_rows
    return features.shape[0]


def row_squares_apply(features: Features, d: jax.Array) -> jax.Array:
    """``sum_i d_i * x_i^2`` (elementwise square) — used for diagonal Hessians
    and per-feature second moments (variance computation, SURVEY.md §3.2)."""
    if isinstance(features, SparseFeatures):
        if features.values is None:  # 1^2 == 1
            return transpose_apply(features, d)
        contrib = (features.values**2) * d[:, None]
        out = jnp.zeros((features.dim,), contrib.dtype)
        return out.at[features.indices.reshape(-1)].add(contrib.reshape(-1))
    return (features**2).T @ d


@struct.dataclass
class LabeledBatch:
    """A batch of weighted, offset labeled examples (the reference's
    ``LabeledPoint`` batched — SURVEY.md §3.1).

    ``offsets`` are added to margins before the loss (the residual-score /
    GAME-coordinate mechanism rides on them); ``weights`` multiply per-example
    losses. Objectives use *sum* (not mean) semantics to match the reference's
    aggregation.
    """

    features: Features
    labels: jax.Array
    offsets: jax.Array
    weights: jax.Array

    @property
    def num_examples(self) -> int:
        return self.labels.shape[0]

    @property
    def dim(self) -> int:
        return feature_dim(self.features)

    def with_offsets(self, offsets: jax.Array) -> "LabeledBatch":
        return self.replace(offsets=offsets)

    def slice_rows(self, start: int, size: int) -> "LabeledBatch":
        sl = lambda a: jax.lax.dynamic_slice_in_dim(a, start, size, 0)
        feats = (
            self.features.slice_rows(start, size)
            if isinstance(self.features, SparseFeatures)
            else sl(self.features)
        )
        return LabeledBatch(feats, sl(self.labels), sl(self.offsets), sl(self.weights))


def make_batch(
    features,
    labels,
    offsets=None,
    weights=None,
    dtype=jnp.float32,
) -> LabeledBatch:
    """Build a LabeledBatch from host data (numpy / lists / scipy.sparse)."""
    dtype = jax.dtypes.canonicalize_dtype(dtype)
    labels = jnp.asarray(labels, dtype)
    n = labels.shape[0]
    if offsets is None:
        offsets = jnp.zeros((n,), dtype)
    else:
        offsets = jnp.asarray(offsets, dtype)
    if weights is None:
        weights = jnp.ones((n,), dtype)
    else:
        weights = jnp.asarray(weights, dtype)
    if not isinstance(features, (jax.Array, SparseFeatures)):
        features = _coerce_features(features, dtype)
    return LabeledBatch(features, labels, offsets, weights)


def _coerce_features(features, dtype) -> Features:
    try:
        import scipy.sparse as sp

        if sp.issparse(features):
            return sparse_from_scipy(features, dtype=dtype)
    except ImportError:  # pragma: no cover
        pass
    return jnp.asarray(np.asarray(features), dtype)


def sparse_from_scipy(
    mat, dtype=jnp.float32, pad_to: int | None = None, allow_truncate: bool = False
) -> SparseFeatures:
    """Convert a scipy.sparse matrix to the padded ELL layout (vectorized —
    this sits on the bulk ingestion path). Raises if ``pad_to`` would drop
    nonzeros, unless ``allow_truncate`` (deliberate feature capping)."""
    import scipy.sparse as sp

    csr = sp.csr_matrix(mat)
    n, d = csr.shape
    nnz_per_row = np.diff(csr.indptr)
    max_nnz = int(nnz_per_row.max()) if n else 0
    k = int(pad_to) if pad_to is not None else max_nnz
    if k < max_nnz and not allow_truncate:
        raise ValueError(
            f"pad_to={k} < max row nnz {max_nnz}; pass allow_truncate=True to cap"
        )
    k = max(k, 1)
    indices = np.zeros((n, k), np.int32)
    values = np.zeros((n, k), np.float64)
    # position of each nonzero within its row
    rows = np.repeat(np.arange(n), nnz_per_row)
    cols = np.arange(csr.nnz) - np.repeat(csr.indptr[:-1], nnz_per_row)
    keep = cols < k
    indices[rows[keep], cols[keep]] = csr.indices[keep]
    values[rows[keep], cols[keep]] = csr.data[keep]
    return SparseFeatures(jnp.asarray(indices), jnp.asarray(values, dtype), dim=d)


def sparse_from_rows(
    rows, dim, dtype=jnp.float32, pad_to: int | None = None, allow_truncate: bool = False
) -> SparseFeatures:
    """Build padded sparse features from per-row (index, value) pair lists."""
    n = len(rows)
    max_nnz = max((len(r) for r in rows), default=0)
    k = int(pad_to) if pad_to is not None else max_nnz
    if k < max_nnz and not allow_truncate:
        raise ValueError(
            f"pad_to={k} < max row nnz {max_nnz}; pass allow_truncate=True to cap"
        )
    k = max(k, 1)
    indices = np.zeros((n, k), np.int32)
    values = np.zeros((n, k), np.float64)
    for i, row in enumerate(rows):
        for j, (idx, val) in enumerate(row[:k]):
            indices[i, j] = idx
            values[i, j] = val
    # XLA gather/scatter silently clamp out-of-range indices, which would
    # train on the wrong feature — validate on host at construction instead.
    if n and indices.max() >= dim:
        raise ValueError(f"feature index {indices.max()} out of range for dim={dim}")
    if n and indices.min() < 0:
        raise ValueError(f"negative feature index {indices.min()}")
    return SparseFeatures(jnp.asarray(indices), jnp.asarray(values, dtype), dim=dim)
