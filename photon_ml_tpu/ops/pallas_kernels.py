"""Pallas TPU kernels for the sparse-gradient hot path.

The scatter-free CSC gradient (``types.CSCTranspose``) is bottlenecked by a
length-nnz prefix sum: XLA lowers ``jnp.cumsum`` over tens of millions of
elements to several log-tree passes over HBM. The kernel here streams the
array once: a sequential 1-D grid over row tiles with a running carry in
SMEM, computing each tile's inclusive scan as two small lower-triangular
**matmuls on the MXU** (cumsum-as-matmul — the TPU-native scan idiom; no
unsupported vector shifts or gathers), and fusing the
``contrib = values * d_gathered`` multiply into the same pass so the
contribution vector is never materialized in HBM.

Why matmul: a [T, 128] tile's per-lane inclusive prefix is ``x @ L`` with
``L[a, b] = 1 if a <= b``; the running offset across the tile's rows is a
strict-lower-triangular matmul of the per-row totals. Both hit the MXU with
static shapes.

``lax.platform_dependent`` picks the compiled Mosaic kernel when the
program is lowered for a TPU and interpret mode for any other platform (the
CPU tests run the same kernel body).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl

_LANES = 128


def _mps_kernel(v_ref, d_ref, out_ref):
    """One [T, 128] tile: fused multiply + TILE-LOCAL inclusive prefix sum,
    plus the tile's total. No cross-tile carry: a global running prefix
    would reintroduce the f32 boundary-difference cancellation the blocked
    scheme exists to avoid (types.blocked_boundary_combine), and dropping
    the sequential carry removes the only cross-tile dependency."""
    x = v_ref[:] * d_ref[:]  # fused contribution product
    rows = x.shape[0]
    dtype = x.dtype

    # match_vma: in interpret mode (CPU tests) the kernel body runs under
    # shard_map's varying-axis tracking, where fresh iota constants are
    # unvarying and may not meet varying data in a dot; on the compiled TPU
    # path the kernel traces standalone and this is a no-op.
    from photon_ml_tpu.optimize.common import match_vma

    # inclusive prefix along lanes: x @ L, L[a, b] = (a <= b)
    a = jax.lax.broadcasted_iota(jnp.int32, (_LANES, _LANES), 0)
    b = jax.lax.broadcasted_iota(jnp.int32, (_LANES, _LANES), 1)
    lane_cum = jnp.dot(x, match_vma((a <= b).astype(dtype), x),
                       preferred_element_type=dtype)

    # running offset across rows: strict lower-triangular matmul of row sums
    row_tot = lane_cum[:, _LANES - 1:_LANES]  # [rows, 1]
    ra = jax.lax.broadcasted_iota(jnp.int32, (rows, rows), 0)
    rb = jax.lax.broadcasted_iota(jnp.int32, (rows, rows), 1)
    row_excl = jnp.dot(match_vma((rb < ra).astype(dtype), x), row_tot,
                       preferred_element_type=dtype)  # [rows, 1]

    # the tile total is the local prefix's last element; the wrapper slices
    # it out of this output, so the kernel has no second (scalar-shaped)
    # output — Mosaic pads an [n_tiles, 1] SMEM output window to
    # 512 B/element, overflowing SMEM at bench-shape tile counts
    # (u8[1277952] > 1 MB; builder-measured on a v5e, 2026-07-31, not
    # re-measured since)
    out_ref[:] = lane_cum + row_excl


def _mps_call(v, d, n_tiles, block_rows, interpret):
    # under shard_map (manual mode) the output varies over the same mesh
    # axes as the inputs; plumb the vma through or check_vma rejects the call
    vma = frozenset(getattr(jax.typeof(v), "vma", frozenset()))
    def _shape(sh):
        return (jax.ShapeDtypeStruct(sh, v.dtype, vma=vma) if vma
                else jax.ShapeDtypeStruct(sh, v.dtype))
    return pl.pallas_call(
        _mps_kernel,
        grid=(n_tiles,),
        in_specs=[
            pl.BlockSpec((block_rows, _LANES), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, _LANES), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, _LANES), lambda i: (i, 0)),
        out_shape=_shape(v.shape),
        interpret=interpret,
        name="photon_multiply_prefix_sum",
    )(v, d)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
@jax.named_scope("photon.csc/prefix_sum")
def multiply_prefix_sum(
    values: jax.Array,
    d_sorted: jax.Array,
    block_rows: int = 256,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array, int]:
    """TILE-LOCAL inclusive prefix sums of ``values * d_sorted`` (both
    [nnz]) in one streamed pass, plus per-tile totals.

    Returns ``(local, totals, tile)``: ``local`` is [padded] with the
    prefix restarting every ``tile = block_rows * 128`` elements, exactly
    the pair ``types.blocked_boundary_combine`` consumes.

    ``interpret=None`` selects per LOWERING platform via
    ``lax.platform_dependent`` — the compiled Mosaic kernel for TPU,
    interpret mode elsewhere — by the platform the program is lowered
    for, not the current backend, so lowering for a TPU from a CPU host
    (jax.export / AOT) gets the kernel."""
    nnz = values.shape[0]
    tile = block_rows * _LANES
    n_tiles = max(pl.cdiv(nnz, tile), 1)
    padded = n_tiles * tile
    pad = padded - nnz
    v = jnp.pad(values, (0, pad)).reshape(-1, _LANES)
    d = jnp.pad(d_sorted, (0, pad)).reshape(-1, _LANES)

    if interpret is None:
        local = jax.lax.platform_dependent(
            v, d,
            tpu=functools.partial(_mps_call, n_tiles=n_tiles,
                                  block_rows=block_rows, interpret=False),
            default=functools.partial(_mps_call, n_tiles=n_tiles,
                                      block_rows=block_rows, interpret=True),
        )
    else:
        local = _mps_call(v, d, n_tiles, block_rows, interpret)
    totals = local.reshape(n_tiles, -1)[:, -1]
    return local.reshape(-1), totals, tile


def paged_gather_score(table: jax.Array, slots: jax.Array,
                       indices: jax.Array, values: jax.Array) -> jax.Array:
    """Per-row margin of a batch against a device-resident paged entity
    table: ``out[i] = sum_j table[slots[i], indices[i, j]] * values[i, j]``
    with ``slots[i] < 0`` (no resident entity model) scoring exactly 0.

    ``table`` is the paged coefficient buffer flattened to ``[S, D]``
    (``S = pages * page_rows`` slots, ``D`` dense global-feature dims);
    ``slots`` int32 ``[B]``; ``indices`` int32 / ``values`` ``[B, k]``
    are the batch's resolved sparse features for the table's shard.

    Lowering: ONE flat ``table_gather`` over ``slot * D + index`` — the
    same gather idiom as the margin kernels (``types.table_gather``), so
    the whole random-effect score is a single [B*k] gather + row-sum with
    no ``[B, D]`` dense intermediate and no host round-trip. Serving's
    fused executable calls this once per random coordinate per batch."""
    from photon_ml_tpu.types import table_gather

    dim = table.shape[-1]
    safe = jnp.maximum(slots, 0).astype(jnp.int32)
    flat_idx = safe[:, None] * dim + indices
    picked = table_gather(table.reshape(-1), flat_idx)  # [B, k]
    score = jnp.sum(picked * values, axis=-1)
    return jnp.where(slots >= 0, score, jnp.zeros((), table.dtype))


def csc_transpose_apply_pallas(csc, d: jax.Array) -> jax.Array:
    """``X^T d`` from the column-sorted view with the fused Pallas per-tile
    scan + the shared blocked boundary combine (drop-in for
    ``types.csc_transpose_apply``, same accuracy guarantee: error does not
    grow with nnz). The implicit-ones layout materializes a ones vector
    here (the kernel is a two-operand scan); prefer sparse_grad='csc' for
    binary data."""
    from photon_ml_tpu.types import blocked_boundary_combine, table_gather

    dg = table_gather(d, csc.rows)
    values = jnp.ones_like(dg) if csc.values is None else csc.values
    local, totals, tile = multiply_prefix_sum(values, dg)
    out = blocked_boundary_combine(local, totals, csc.col_starts, tile)
    return out.astype(d.dtype)
