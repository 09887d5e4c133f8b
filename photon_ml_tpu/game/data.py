"""GAME random-effect data layer: entity grouping, active/passive split,
per-entity feature-subspace projection, and size-bucketing into padded
arrays.

Equivalent of the reference's ``data.{RandomEffectDataset, LocalDataset,
RandomEffectDatasetPartitioner}`` + ``projector.LinearSubspaceProjector``
(SURVEY.md §3.2; reference mount empty). The reference groups samples by
entity id into an RDD of per-entity local datasets; each entity's features
are projected onto the subspace it has actually seen. TPU-native rebuild:

* entities are *bucketed by size* and padded to per-bucket shapes
  ``[E, N, k]`` so the per-entity solves run as one ``vmap`` per bucket with
  static shapes (SURVEY.md §7 "ragged entity data" hard part);
* **active** data (up to ``active_cap`` rows per entity, seeded random
  subset) trains the entity model; **passive** rows only receive scores —
  via a *score view* built over any dataset with the training-time
  projections (``build_score_view``);
* projections are built from active data, so features first seen in passive
  or validation rows contribute zero score, matching the projector
  semantics.

This is host-side preprocessing (the reference does it as a Spark shuffle
stage); it runs in vectorized numpy. The per-entity feature remapping is the
candidate for a native C++ kernel if it shows up in profiles at scale.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence

import numpy as np

from photon_ml_tpu.types import SparseFeatures


@dataclasses.dataclass(frozen=True)
class HostSparse:
    """Host-side padded sparse matrix (numpy twin of SparseFeatures)."""

    indices: np.ndarray  # [n, k] int32
    values: Optional[np.ndarray]  # [n, k]; None = implicit-ones layout
    dim: int

    @property
    def num_rows(self) -> int:
        return self.indices.shape[0]


def host_sparse_from_dense(X: np.ndarray) -> HostSparse:
    n, d = X.shape
    k = max(int((X != 0).sum(axis=1).max()) if n else 0, 1)
    indices = np.zeros((n, k), np.int32)
    values = np.zeros((n, k))
    for i in range(n):
        nz = np.nonzero(X[i])[0]
        indices[i, : len(nz)] = nz
        values[i, : len(nz)] = X[i, nz]
    return HostSparse(indices, values, d)


def materialize_ones(sp: HostSparse) -> HostSparse:
    """Give an implicit-ones HostSparse explicit 1.0 values. Per-entity
    subspace remapping carries explicit values through the local views, so
    the random-effect data layer materializes here (same footprint the
    caller would have paid with an explicit-values layout); fixed-effect
    paths stay value-free end to end."""
    if sp.values is None:
        return HostSparse(sp.indices, np.ones(sp.indices.shape), sp.dim)
    return sp


def host_sparse_from_features(features) -> HostSparse:
    """Accept SparseFeatures / HostSparse / dense numpy or jax array."""
    if isinstance(features, HostSparse):
        return features
    if isinstance(features, SparseFeatures):
        return HostSparse(
            np.asarray(features.indices),
            None if features.values is None else np.asarray(features.values),
            features.dim,
        )
    return host_sparse_from_dense(np.asarray(features))


@dataclasses.dataclass(frozen=True)
class REBucket:
    """One size bucket of entities, padded to common shapes.

    Training arrays (active data):
      indices/values: [E, N, k] local-subspace sparse rows (pad value 0).
      labels/weights: [E, N] (pad weight 0).
      sample_idx: int32 [E, N] row index into the source dataset, -1 pad.
    Projection:
      projection: int32 [E, D] global feature id per local slot, -1 pad.
      local_maps: per-entity dict global id -> local slot (host side, reused
        to build score views for other datasets).
    """

    entity_ids: Sequence
    indices: np.ndarray
    values: np.ndarray
    labels: np.ndarray
    weights: np.ndarray
    sample_idx: np.ndarray
    projection: np.ndarray
    local_maps: List[Dict[int, int]]

    @property
    def num_entities(self) -> int:
        return len(self.entity_ids)

    @property
    def local_dim(self) -> int:
        return self.projection.shape[1]


_U64 = (1 << 64) - 1


@dataclasses.dataclass(frozen=True)
class SketchProjection:
    """Count-sketch random projection: global feature id → (slot, ±1).

    The reference's older random-projection projector (SURVEY.md §3.2
    ``projector`` row, marked ``(?)``) for random effects whose entity
    feature spaces are too wide for exact subspace maps: every entity of the
    effect shares one signed hash into a fixed ``dim``-wide space, so entity
    problems have constant shape regardless of support size. Mixing is a
    splitmix64-style finalizer — stable across processes (the same reason
    ``io.hashing`` avoids Python's ``hash``)."""

    dim: int
    seed: int = 0

    def slots_signs(self, gids: np.ndarray):
        x = np.asarray(gids, np.uint64) + np.uint64(
            (self.seed * 0x9E3779B97F4A7C15 + 0x632BE59BD9B4E019) & _U64
        )
        x = (x ^ (x >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9) & _U64
        x = (x ^ (x >> np.uint64(27))) * np.uint64(0x94D049BB133111EB) & _U64
        x = x ^ (x >> np.uint64(31))
        slots = (x % np.uint64(self.dim)).astype(np.int64)
        signs = 1.0 - 2.0 * ((x >> np.uint64(32)) & np.uint64(1)).astype(np.float64)
        return slots, signs


def _local_map_arrays(lm: Dict[int, int]):
    """Sorted global ids + their local slots, for vectorized remapping."""
    if not lm:
        return np.zeros(0, np.int64), np.zeros(0, np.int64)
    gids = np.fromiter(lm.keys(), np.int64, len(lm))
    slots = np.fromiter(lm.values(), np.int64, len(lm))
    order = np.argsort(gids)
    return gids[order], slots[order]


def _remap_to_local(row_idx: np.ndarray, row_val: np.ndarray, lm):
    """Map global feature ids to entity-local slots in one vectorized pass
    (np.searchsorted); entries outside the local map are zeroed (projector
    semantics: their coefficient is structurally 0). ``lm`` is either a
    global-id→slot dict (subspace projector) or a SketchProjection."""
    if isinstance(lm, SketchProjection):
        slots, signs = lm.slots_signs(row_idx)
        present = row_val != 0
        loc = np.where(present, slots, 0).astype(row_idx.dtype)
        val = np.where(present, row_val * signs, 0.0)
        return loc, val
    gids, slots = _local_map_arrays(lm)
    if len(gids) == 0:
        return np.zeros_like(row_idx), np.zeros_like(row_val)
    pos = np.searchsorted(gids, row_idx)
    pos = np.minimum(pos, len(gids) - 1)
    known = (gids[pos] == row_idx) & (row_val != 0)
    loc = np.where(known, slots[pos], 0).astype(row_idx.dtype)
    val = np.where(known, row_val, 0.0)
    return loc, val


@dataclasses.dataclass(frozen=True)
class REScoreBucket:
    """Score view of one bucket over some dataset: every row of every entity
    (active + passive), features projected to the entity's local subspace."""

    indices: np.ndarray  # [E, M, k] local
    values: np.ndarray  # [E, M, k]
    sample_idx: np.ndarray  # [E, M], -1 pad


@dataclasses.dataclass(frozen=True)
class RandomEffectTrainData:
    effect_name: str
    buckets: List[REBucket]
    num_samples: int  # rows in the source dataset
    # entity id -> (bucket, row) for score-view building
    entity_to_slot: Dict

    @property
    def num_entities(self) -> int:
        return sum(b.num_entities for b in self.buckets)

    def table_bytes(self) -> int:
        """Host bytes of the padded per-entity training arrays — the
        memory the entity sharding bounds per process (score views and
        coefficients scale with the same entity slice)."""
        total = 0
        for b in self.buckets:
            for a in (b.indices, b.values, b.labels, b.weights,
                      b.sample_idx, b.projection):
                total += np.asarray(a).nbytes
        return total


# A bucket's row capacity follows a power-of-two ladder of active-row
# counts: entities with (N/2, N] rows share a rung, so fewer than half of a
# rung's slots are padding whatever the skew (about 30% expected). The rung
# pads to its largest member, rounded up to this many rows.
_ROW_ROUND = 8


def ladder_splits(counts_sorted: np.ndarray, num_buckets: int) -> List[int]:
    """Where to cut entities, sorted by ascending row count, into buckets:
    one bucket per occupied rung of the power-of-two ladder, and where
    that gives more than ``num_buckets``, the adjacent pair whose merge
    adds the fewest padded slots is merged until it does not. Returns
    the cut positions, first 0 and last ``len(counts_sorted)``."""
    E = len(counts_sorted)
    if E == 0:
        return [0, 0]
    rung = np.ceil(np.log2(np.maximum(counts_sorted, 1))).astype(np.int64)
    cuts = [0] + (np.flatnonzero(np.diff(rung)) + 1).tolist() + [E]
    while len(cuts) - 1 > max(num_buckets, 1):
        # merging bucket i into i + 1 pads i's entities to i + 1's rows
        cost = [(cuts[i + 1] - cuts[i])
                * int(counts_sorted[cuts[i + 2] - 1]
                      - counts_sorted[cuts[i + 1] - 1])
                for i in range(len(cuts) - 2)]
        del cuts[int(np.argmin(cost)) + 1]
    return cuts


def _padded_rows(max_count: int) -> int:
    n = max(int(max_count), 1)
    return n if n < _ROW_ROUND else -(-n // _ROW_ROUND) * _ROW_ROUND


class _SubspaceIndex:
    """(entity, global feature id) -> the entity's local slot, for all
    entities of one effect at once. Slots follow ascending feature id
    within an entity. A dense table where entities x dim is small (one
    gather a lookup), a sorted key list with binary search elsewhere."""

    _TABLE_MAX = 1 << 25

    def __init__(self, keys_sorted: np.ndarray, num_entities: int, dim: int):
        # keys_sorted: unique ascending entity * dim + feature id
        self.dim = int(dim)
        self.keys = keys_sorted
        ent = keys_sorted // self.dim
        self.starts = np.searchsorted(ent, np.arange(num_entities + 1))
        self.table = None
        if num_entities * self.dim <= self._TABLE_MAX:
            self.table = np.full(num_entities * self.dim, -1, np.int32)
            self.table[keys_sorted] = (
                np.arange(len(keys_sorted)) - self.starts[ent])

    @classmethod
    def from_rows(cls, ent_of_row, indices, values, num_entities, dim):
        if num_entities * dim <= cls._TABLE_MAX:
            # 32-bit keys, no masked copy: half the memory traffic
            keys = indices + (ent_of_row.astype(np.int32)
                              * np.int32(dim))[:, None]
            seen = np.zeros(num_entities * dim + 1, bool)
            keys[values == 0] = num_entities * dim  # the spare slot
            seen[keys.ravel()] = True
            uniq = np.flatnonzero(seen[:-1])
        else:
            keys = ent_of_row[:, None].astype(np.int64) * dim + indices
            uniq = np.unique(keys[values != 0])
        return cls(uniq, num_entities, dim)

    @classmethod
    def from_projections(cls, projections: Sequence[np.ndarray], dim: int):
        """From the buckets' ``projection`` arrays, entities numbered
        bucket by bucket."""
        keys, base = [], 0
        for proj in projections:
            proj = np.asarray(proj)
            e, _ = np.nonzero(proj >= 0)
            keys.append((e + base).astype(np.int64) * dim + proj[proj >= 0])
            base += proj.shape[0]
        keys = (np.concatenate(keys) if keys else np.zeros(0, np.int64))
        # slots ascend with the feature id, so the keys come out sorted
        return cls(keys, base, dim)

    def local_dims(self) -> np.ndarray:
        return np.diff(self.starts)

    def remap(self, ent_of_row, indices, values):
        """Local slots and values of rows [m, k]; an entry whose feature
        the entity never saw, or whose value is 0, becomes (0, 0.0)."""
        if indices.size == 0:
            return np.zeros_like(indices), np.zeros(indices.shape)
        if self.table is not None:
            keys = indices + (ent_of_row.astype(np.int32)
                              * np.int32(self.dim))[:, None]
            slot = self.table[keys]
            dead = slot < 0
        elif len(self.keys) == 0:
            slot = np.zeros(indices.shape, np.int64)
            dead = np.ones(indices.shape, bool)
        else:
            ent = ent_of_row[:, None].astype(np.int64)
            keys = ent * self.dim + indices
            pos = np.minimum(np.searchsorted(self.keys, keys),
                             len(self.keys) - 1)
            dead = self.keys[pos] != keys
            slot = pos - self.starts[ent]
        dead |= values == 0
        slot = slot.astype(indices.dtype, copy=False)
        slot[dead] = 0
        val = np.array(values, copy=True)
        val[dead] = 0
        return slot, val


def _slot_positions(counts: np.ndarray) -> np.ndarray:
    """0, 1, ..., c-1 for each count c, concatenated."""
    starts = np.cumsum(counts) - counts
    return np.arange(int(counts.sum())) - np.repeat(starts, counts)


def build_random_effect_data(
    features,
    labels: np.ndarray,
    weights: np.ndarray,
    entity_ids: Sequence,
    effect_name: str = "random",
    num_buckets: int = 16,
    active_cap: Optional[int] = None,
    seed: int = 0,
    projection: str = "subspace",
    projection_dim: Optional[int] = None,
    projection_seed: int = 0,
    entity_shard=None,
) -> RandomEffectTrainData:
    """Group rows by entity, split active/passive, project, bucket, pad.

    Entities are bucketed by size, not by rank: one bucket per occupied
    rung of a power-of-two ladder of active-row counts, so that padding
    is under half of all slots at any skew (``ladder_splits``).
    ``num_buckets`` is the most buckets — distinct ``(N, D)`` shapes,
    hence compiled solvers — the effect may have; rungs are merged,
    cheapest first, only where the ladder has more, and ``1`` pads every
    entity to the largest.

    ``projection``: "subspace" builds exact per-entity feature maps (the
    LinearSubspaceProjector role); "random" uses a shared count-sketch of
    width ``projection_dim`` (the RandomProjection role — constant-shape
    entity problems, non-invertible).

    ``entity_shard`` (a ``parallel.entity_shard.EntityShardSpec``):
    entity-sharded multi-controller training — this process grooms and
    buckets ONLY the entities its shard owns (stable-hash owner map);
    rows of unowned entities never enter a bucket or score view, so the
    per-process entity-table footprint is the owned slice. Note that
    ``active_cap`` sampling draws from one sequential rng stream, so a
    sharded run's sampled subsets differ from the single-host run's
    (full-data training — no cap — is bit-compatible across shard
    counts)."""
    sp = materialize_ones(host_sparse_from_features(features))
    labels = np.asarray(labels, np.float64)
    weights = np.asarray(weights, np.float64)
    n = sp.num_rows
    k = sp.indices.shape[1]
    ent = np.asarray(entity_ids)
    uniq, codes = np.unique(ent, return_inverse=True)
    rng = np.random.default_rng(seed)

    # rows per entity (stable order)
    order = np.argsort(codes, kind="mergesort")
    sorted_codes = codes[order]
    boundaries = np.searchsorted(sorted_codes, np.arange(len(uniq) + 1))

    if entity_shard is not None and entity_shard.num_shards > 1:
        keep = np.flatnonzero(entity_shard.owned_mask(uniq))
    else:
        keep = np.arange(len(uniq))

    active_rows: List[np.ndarray] = []
    for e in keep:
        rows = order[boundaries[e] : boundaries[e + 1]]
        if active_cap is not None and len(rows) > active_cap:
            rows = rng.choice(rows, size=active_cap, replace=False)
            rows.sort()
        active_rows.append(rows)
    uniq = uniq[keep]
    E_all = len(uniq)
    counts = np.array([len(r) for r in active_rows], np.int64)
    rows_flat = (np.concatenate(active_rows) if E_all
                 else np.zeros(0, np.int64))
    ent_of_row = np.repeat(np.arange(E_all), counts)
    row_idx = sp.indices[rows_flat]
    row_val = sp.values[rows_flat]

    # every active row's features in its entity's local space, at once
    sketch = index = None
    if projection == "random":
        if not projection_dim or projection_dim <= 0:
            raise ValueError("projection='random' needs a positive "
                             "projection_dim")
        sketch = SketchProjection(projection_dim, projection_seed)
        loc, val = _remap_to_local(row_idx, row_val, sketch)
        local_dims = np.full(E_all, projection_dim, np.int64)
    elif projection == "subspace":
        index = _SubspaceIndex.from_rows(ent_of_row, row_idx, row_val,
                                         E_all, sp.dim)
        loc, val = index.remap(ent_of_row, row_idx, row_val)
        local_dims = index.local_dims()
    else:
        raise ValueError(f"unknown projection '{projection}' "
                         "(subspace|random)")

    # bucket entities by active-row count
    ent_order = np.argsort(counts, kind="mergesort")
    cuts = ladder_splits(counts[ent_order], num_buckets)
    bucket_of_ent = np.zeros(E_all, np.int64)
    rank_of_ent = np.zeros(E_all, np.int64)
    for b in range(len(cuts) - 1):
        members = ent_order[cuts[b]:cuts[b + 1]]
        bucket_of_ent[members] = b
        rank_of_ent[members] = np.arange(len(members))
    bucket_of_row = bucket_of_ent[ent_of_row]
    rank_of_row = rank_of_ent[ent_of_row]
    pos_of_row = _slot_positions(counts)
    if index is not None:
        key_ent = index.keys // sp.dim
        key_gid = (index.keys % sp.dim).astype(np.int32)
        key_slot = _slot_positions(local_dims)

    buckets: List[REBucket] = []
    entity_to_slot: Dict = {}
    for b in range(len(cuts) - 1):
        members = ent_order[cuts[b]:cuts[b + 1]]
        E = len(members)
        if E == 0:
            continue
        N = _padded_rows(counts[members].max())
        D = max(int(local_dims[members].max()), 1)
        indices = np.zeros((E, N, k), np.int32)
        values = np.zeros((E, N, k))
        lab = np.zeros((E, N))
        wts = np.zeros((E, N))
        sidx = np.full((E, N), -1, np.int32)
        proj = np.full((E, D), -1, np.int32)
        sel = np.flatnonzero(bucket_of_row == b)
        r, p = rank_of_row[sel], pos_of_row[sel]
        indices[r, p] = loc[sel]
        values[r, p] = val[sel]
        lab[r, p] = labels[rows_flat[sel]]
        wts[r, p] = weights[rows_flat[sel]]
        sidx[r, p] = rows_flat[sel]
        if index is not None:
            ksel = np.flatnonzero(bucket_of_ent[key_ent] == b)
            proj[rank_of_ent[key_ent[ksel]], key_slot[ksel]] = key_gid[ksel]
            local_maps = [
                dict(zip(g[g >= 0].tolist(), range(int((g >= 0).sum()))))
                for g in proj]
        else:
            local_maps = [sketch] * E
        eids = [uniq[e] for e in members]
        for r_e, eid in enumerate(eids):
            entity_to_slot[eid] = (len(buckets), r_e)
        buckets.append(
            REBucket(eids, indices, values, lab, wts, sidx, proj, local_maps))
    return RandomEffectTrainData(effect_name, buckets, n, entity_to_slot)


def group_rows_by_slot(entity_ids, entity_to_slot, num_entities_per_bucket):
    """Group dataset row indices by (bucket, entity-row). Rows of unknown
    entities are dropped (they get no random-effect score)."""
    per_bucket_rows: List[List[List[int]]] = [
        [[] for _ in range(e)] for e in num_entities_per_bucket
    ]
    for i, eid in enumerate(np.asarray(entity_ids)):
        slot = entity_to_slot.get(eid)
        if slot is None:
            slot = entity_to_slot.get(str(eid))
        if slot is None:
            continue
        b, r = slot
        per_bucket_rows[b][r].append(i)
    return per_bucket_rows


def build_score_buckets(
    sp: HostSparse,
    per_bucket_rows: List[List[List[int]]],
    local_maps_per_bucket: List[List[Dict[int, int]]],
) -> List[REScoreBucket]:
    """Shared score-view construction: project rows onto each entity's local
    subspace (single code path for train-data views and model-based views)."""
    sp = materialize_ones(sp)
    out: List[REScoreBucket] = []
    for rows_per_entity, local_maps in zip(per_bucket_rows, local_maps_per_bucket):
        E = len(rows_per_entity)
        M = max(max((len(r) for r in rows_per_entity), default=0), 1)
        k = sp.indices.shape[1]
        indices = np.zeros((E, M, k), np.int32)
        values = np.zeros((E, M, k))
        sidx = np.full((E, M), -1, np.int32)
        for r in range(E):
            rows = rows_per_entity[r]
            if not rows:
                continue
            loc, rval = _remap_to_local(sp.indices[rows], sp.values[rows],
                                        local_maps[r])
            indices[r, : len(rows)] = loc
            values[r, : len(rows)] = rval
            sidx[r, : len(rows)] = rows
        out.append(REScoreBucket(indices, values, sidx))
    return out


def _entity_slots(train_data: RandomEffectTrainData, entity_ids):
    """Each row's entity as its number among the training entities,
    counted bucket by bucket; -1 for an entity training never saw. Ids
    are matched as they are, then as strings (a loaded model keys its
    entities as ``str``), as ``group_rows_by_slot`` does."""
    ids = np.asarray(entity_ids)
    known = [np.asarray(b.entity_ids) for b in train_data.buckets
             if b.num_entities]
    if not known or not len(ids):
        return np.full(len(ids), -1, np.int64)
    known = np.concatenate(known)
    if known.dtype.kind != ids.dtype.kind and "U" in (known.dtype.kind,
                                                      ids.dtype.kind):
        known, ids = known.astype(str), ids.astype(str)
    sorter = np.argsort(known, kind="mergesort")
    pos = np.minimum(np.searchsorted(known, ids, sorter=sorter),
                     len(known) - 1)
    hit = known[sorter[pos]] == ids
    return np.where(hit, sorter[pos], -1)


def build_score_view(
    train_data: RandomEffectTrainData, features, entity_ids: Sequence
) -> List[REScoreBucket]:
    """Project any dataset onto the training-time entity subspaces for
    device-side scoring. Rows of entities unseen in training contribute no
    score; features outside an entity's subspace are dropped (their
    coefficient is structurally zero — projector semantics). Whole-array
    numpy: one sort of the rows by entity, one lookup of every feature."""
    sp = materialize_ones(host_sparse_from_features(features))
    buckets = train_data.buckets
    sizes = np.array([b.num_entities for b in buckets], np.int64)
    ends = np.cumsum(sizes)
    slot_of_row = _entity_slots(train_data, entity_ids)
    rows = np.flatnonzero(slot_of_row >= 0)
    rows = rows[np.argsort(slot_of_row[rows], kind="mergesort")]
    ent_of_row = slot_of_row[rows]
    counts = np.bincount(ent_of_row, minlength=int(sizes.sum()))
    pos_of_row = _slot_positions(counts)
    row_idx, row_val = sp.indices[rows], sp.values[rows]
    lm0 = next((b.local_maps[0] for b in buckets if b.num_entities), None)
    if isinstance(lm0, SketchProjection):
        loc, val = _remap_to_local(row_idx, row_val, lm0)
    else:
        index = _SubspaceIndex.from_projections(
            [b.projection for b in buckets], sp.dim)
        loc, val = index.remap(ent_of_row, row_idx, row_val)
    bucket_of_row = np.searchsorted(ends, ent_of_row, side="right")
    k = sp.indices.shape[1]
    out: List[REScoreBucket] = []
    for b, E in enumerate(sizes):
        base = int(ends[b] - E)
        M = _padded_rows(counts[base:base + E].max() if E else 0)
        indices = np.zeros((E, M, k), np.int32)
        values = np.zeros((E, M, k))
        sidx = np.full((E, M), -1, np.int32)
        sel = np.flatnonzero(bucket_of_row == b)
        r, p = ent_of_row[sel] - base, pos_of_row[sel]
        indices[r, p] = loc[sel]
        values[r, p] = val[sel]
        sidx[r, p] = rows[sel]
        out.append(REScoreBucket(indices, values, sidx))
    return out
