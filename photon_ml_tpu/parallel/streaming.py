"""Streaming (larger-than-HBM) fixed-effect training.

The in-memory path (``fit_distributed``) holds the whole batch in device
memory and runs the optimizer as one XLA program. At Criteo-1TB scale the
dataset doesn't fit in HBM; the reference streams partitions through
executors on every ``treeAggregate`` pass (SURVEY.md §4.2 — one cluster pass
per optimizer iteration). The TPU-native equivalent here: the dataset lives
in host RAM as fixed-shape chunks, each optimizer iteration streams chunks
through the device accumulating (loss, gradient) partials with a jitted
per-chunk kernel (one compilation, static shapes), and the L-BFGS direction
/ update math stays on device via the same jitted two-loop recursion the
in-memory optimizer uses. Transfers overlap compute via a depth-K device
prefetch ring (:func:`iter_device_chunks`): a dedicated transfer thread
stages the next K chunks' host->device uploads while this thread dispatches
compute, and per-pass stall accounting (decode-wait / transfer /
compute-stall seconds, :class:`StreamStats`) rides the fit result so an
epoch-rate gap is attributable to a pipeline stage, not guessed at.

Cost model: the default margin-space L-BFGS pays exactly two sparse
passes per iteration (direction margins + accepted-point gradient) with
line-search trials streaming only cached margin vectors; the black-box
loops (``lbfgs_blackbox``, TRON, OWL-QN) match the reference's model of
one full pass per evaluation.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import logging
import os
import queue
import threading
import time
from typing import Callable, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from photon_ml_tpu.obs import metrics as obs_metrics
from photon_ml_tpu.obs import trace as obs_trace
from photon_ml_tpu.ops.objective import GLMObjective
from photon_ml_tpu.parallel import fault_injection
from photon_ml_tpu.parallel.resilience import (
    CollectiveGuard,
    current_transport,
    use_transport,
)
from photon_ml_tpu.parallel.data_parallel import cached_jit
from photon_ml_tpu.optimize.common import (
    OptimizationResult,
    OptimizerConfig,
    history_store,
    history_zeros,
)
from photon_ml_tpu.optimize.lbfgs import two_loop_direction
from photon_ml_tpu.types import LabeledBatch, SparseFeatures
from photon_ml_tpu.utils import transfer_budget

_log = logging.getLogger("photon_ml_tpu")

# Device-side prefetch depth of the streamed transfer ring: how many chunks
# the transfer thread may stage on device ahead of the chunk the consumer
# is dispatching. Depth 2 covers decode/transfer jitter without holding
# more than ~4 chunks of HBM (staged + in-flight + current); raise it when
# decode latency is spiky (cold page cache), lower to 0 for a synchronous
# single-thread loop (debugging).
DEFAULT_PREFETCH_DEPTH = 2


def resolve_prefetch_depth(depth: Optional[int] = None) -> int:
    """Explicit depth, else ``PHOTON_PREFETCH_DEPTH``, else the default."""
    if depth is None:
        env = os.environ.get("PHOTON_PREFETCH_DEPTH", "")
        depth = int(env) if env else DEFAULT_PREFETCH_DEPTH
    return max(int(depth), 0)


@dataclasses.dataclass
class StreamStats:
    """Host-side pipeline stall accounting for streamed passes.

    ``decode_s``: transfer-thread seconds blocked waiting on the chunk
    source (disk decode or the source's own producer queue);
    ``transfer_s``: seconds issuing budget-accounted host->device puts;
    ``stall_s``: consumer seconds blocked on an empty ring — the compute
    dispatcher starved of staged data; ``comm_s``: seconds in the
    once-per-pass cross-process reduction of the streamed partials (0 in
    single-process runs) — the stall-accounting leg the entity-sharded
    CD's ``comm_seconds`` mirrors. All accumulate across every pass of a
    fit; ``passes``/``chunks`` normalize them."""

    decode_s: float = 0.0
    transfer_s: float = 0.0
    stall_s: float = 0.0
    comm_s: float = 0.0
    chunks: int = 0
    passes: int = 0

    def as_dict(self) -> dict:
        return {"decode_s": round(self.decode_s, 6),
                "transfer_s": round(self.transfer_s, 6),
                "stall_s": round(self.stall_s, 6),
                "comm_s": round(self.comm_s, 6),
                "chunks": self.chunks, "passes": self.passes}


# consumer-side ring poll (seconds): each expiry rechecks transfer-thread
# liveness so a producer that dies without relaying its sentinel fails
# the pass instead of hanging the consumer forever
_RING_POLL_S = 0.5


def _ring_put(q: queue.Queue, stop: threading.Event, item) -> bool:
    """Stop-aware bounded put (chunks, sentinel and errors alike) so an
    abandoned consumer can never wedge the transfer thread — same contract
    as ``AvroChunkSource._put_or_stop``."""
    while not stop.is_set():
        try:
            q.put(item, timeout=0.2)
            return True
        except queue.Full:
            continue
    return False


def iter_device_chunks(chunks, to_device: Callable, depth: Optional[int] = None,
                       stats: Optional[StreamStats] = None):
    """Yield ``(host_chunk, device_batch)`` with the device batches staged
    ``depth`` chunks ahead by a dedicated transfer thread.

    This generalizes the old one-chunk lookahead: the transfer thread pulls
    from the (possibly disk-backed) chunk source and issues the
    budget-accounted uploads, so decode AND transfer of chunks i+1..i+K
    overlap the consumer's compute dispatch of chunk i. Exceptions from
    either the source or the upload are re-raised in the consumer (inside
    its CollectiveGuard, preserving coordinated-abort semantics), and the
    consumer's ambient process context (fault-injection identity, simulated
    transport) is propagated into the transfer thread so per-process fault
    plans still address decode faults deterministically.

    ``depth=0`` is a synchronous single-thread fallback (JAX async dispatch
    still overlaps transfer with compute one chunk at a time)."""
    depth = resolve_prefetch_depth(depth)
    if stats is not None:
        stats.passes += 1
    if depth == 0:
        t_wait = time.perf_counter()
        for chunk in chunks:
            now = time.perf_counter()
            dev = to_device(chunk)
            if stats is not None:
                stats.decode_s += now - t_wait
                stats.transfer_s += time.perf_counter() - now
                stats.chunks += 1
            yield chunk, dev
            t_wait = time.perf_counter()
        return

    q: queue.Queue = queue.Queue(maxsize=depth)
    stop = threading.Event()
    tp = current_transport()
    try:
        fault_proc = tp.process_index()
    except Exception:
        fault_proc = None

    tctx = obs_trace.current_context()  # handed off to the ring thread

    def produce():
        it = iter(chunks)
        ctx = (fault_injection.process_context(fault_proc)
               if fault_proc is not None else contextlib.nullcontext())
        try:
            with use_transport(tp), ctx, obs_trace.use_context(tctx):
                t_wait = time.perf_counter()
                while True:
                    try:
                        chunk = next(it)
                    except StopIteration:
                        break
                    now = time.perf_counter()
                    if stop.is_set():
                        return
                    with obs_trace.span("stream.upload", cat="stream"):
                        dev = to_device(chunk)
                    if stats is not None:
                        stats.decode_s += now - t_wait
                        stats.transfer_s += time.perf_counter() - now
                    if not _ring_put(q, stop, (chunk, dev)):
                        return
                    t_wait = time.perf_counter()
                _ring_put(q, stop, None)  # end-of-pass sentinel
        except BaseException as e:  # surfaced in the consumer
            _ring_put(q, stop, e)
        finally:
            # deterministically close a generator-backed source so ITS
            # producer thread (AvroChunkSource) winds down with this pass
            close = getattr(it, "close", None)
            if close is not None:
                close()

    t = threading.Thread(target=produce, daemon=True,
                         name="stream-transfer")
    t.start()
    try:
        while True:
            t0 = time.perf_counter()
            try:
                item = q.get(timeout=_RING_POLL_S)
            except queue.Empty:
                if t.is_alive():
                    if stats is not None:
                        stats.stall_s += time.perf_counter() - t0
                    continue
                try:
                    # the thread may have parked its last item/sentinel
                    # between our timeout and its exit
                    item = q.get_nowait()
                except queue.Empty:
                    raise RuntimeError(
                        "stream-transfer thread died without delivering "
                        "its end-of-pass sentinel") from None
            if stats is not None:
                stats.stall_s += time.perf_counter() - t0
            if item is None:
                break
            if isinstance(item, BaseException):
                raise item
            if stats is not None:
                stats.chunks += 1
            yield item
    finally:
        stop.set()
        t.join(timeout=30)
        if t.is_alive():
            _log.warning(
                "transfer thread %s still alive 30s after the pass ended "
                "(wedged source or upload); leaking it as a daemon",
                t.name)


@dataclasses.dataclass(frozen=True)
class HostChunk:
    """One fixed-shape chunk resident in host RAM (numpy)."""

    indices: np.ndarray  # [rows, k] int32
    values: Optional[np.ndarray]  # [rows, k]; None = implicit-ones layout
    labels: np.ndarray  # [rows]
    offsets: np.ndarray  # [rows]
    weights: np.ndarray  # [rows]; padding rows have weight 0


def make_host_chunks(
    features,
    labels,
    offsets=None,
    weights=None,
    chunk_rows: int = 1 << 16,
    pad_nnz: Optional[int] = None,
) -> tuple[List[HostChunk], int]:
    """Slice a host dataset into uniform chunks (last chunk padded with
    zero-weight rows so every chunk compiles to the same shapes).

    ``features``: HostSparse-like (``indices``/``values``/``dim``) or dense
    [n, d] numpy. Returns (chunks, dim)."""
    labels = np.asarray(labels)
    n = labels.shape[0]
    if offsets is None:
        offsets = np.zeros(n)
    if weights is None:
        weights = np.ones(n)
    offsets = np.asarray(offsets)
    weights = np.asarray(weights)

    if hasattr(features, "indices"):
        indices = np.asarray(features.indices)
        # implicit-ones layout flows value-free all the way to the device:
        # at streamed scale the halved chunk transfer is the whole point
        values = (None if features.values is None
                  else np.asarray(features.values))
        dim = features.dim
    else:
        dense = np.asarray(features)
        dim = dense.shape[1]
        indices = np.broadcast_to(np.arange(dim, dtype=np.int32),
                                  dense.shape).copy()
        values = dense
    k = indices.shape[1]
    if pad_nnz is not None:
        if pad_nnz < k:
            raise ValueError(f"pad_nnz={pad_nnz} < chunk nnz width {k}")
        if values is None and pad_nnz > k:
            raise ValueError(
                "pad_nnz slot padding is invalid for the implicit-ones "
                "layout (every slot is a real 1.0 feature)")
        pad = pad_nnz - k
        indices = np.pad(indices, ((0, 0), (0, pad)))
        if values is not None:
            values = np.pad(values, ((0, 0), (0, pad)))
        k = pad_nnz

    chunks: List[HostChunk] = []
    for start in range(0, max(n, 1), chunk_rows):
        stop = min(start + chunk_rows, n)
        rows = stop - start
        pad = chunk_rows - rows
        chunks.append(HostChunk(
            indices=np.pad(indices[start:stop], ((0, pad), (0, 0))),
            values=(None if values is None
                    else np.pad(values[start:stop], ((0, pad), (0, 0)))),
            labels=np.pad(labels[start:stop], (0, pad)),
            offsets=np.pad(offsets[start:stop], (0, pad)),
            weights=np.pad(weights[start:stop], (0, pad)),  # pad weight = 0
        ))
    return chunks, dim


def _cross_process_sum(tree, stats: Optional[StreamStats] = None):
    """Sum accumulator pytrees across processes (multi-controller runtime).

    Single-process: identity. Multi-process: each process streams only its
    own row span (``multihost.process_span``), then the per-process partials
    are reduced here — the DCN leg of the reference's ``treeAggregate``
    (SURVEY.md §5.8). Uses allgather+sum of [d]-sized partials, negligible
    next to the per-chunk compute; the time still lands in
    ``StreamStats.comm_s`` so a multi-host stall is attributable."""
    if jax.process_count() == 1:
        return tree
    t0 = time.perf_counter()
    from jax.experimental import multihost_utils

    gathered = multihost_utils.process_allgather(tree)
    out = jax.tree.map(lambda a: jnp.asarray(a).sum(axis=0), gathered)
    if stats is not None:
        stats.comm_s += time.perf_counter() - t0
    return out


def _chunk_to_device(chunk: HostChunk, dim: int, dtype, sharding) -> LabeledBatch:
    # every streamed upload is budget-accounted (utils.transfer_budget):
    # chunk-sized pieces are small, and a session budget catches a
    # misconfigured chunk_rows before it can exhaust the device. The
    # .astype happens first so the charged bytes are the bytes moved.
    def put(a):
        return transfer_budget.device_put(a, sharding, what="stream chunk")
    return LabeledBatch(
        SparseFeatures(put(chunk.indices.astype(np.int32)),
                       (None if chunk.values is None
                        else put(chunk.values.astype(dtype))), dim=dim),
        put(chunk.labels.astype(dtype)),
        put(chunk.offsets.astype(dtype)),
        put(chunk.weights.astype(dtype)),
    )



def _host_tol(tolerance, dtype) -> float:
    """Mirror :func:`optimize.common.converged_check` tolerance semantics
    for the streamed HOST loops: an explicit tol <= 0 disables the
    convergence tests entirely (exact iteration counts — bench determinism),
    while a positive tol is clamped to a few ulps of the working dtype so an
    f64-tuned tolerance still terminates in f32. Round 3 clamped
    ``max(tol, eps)`` unconditionally, silently re-enabling the tests that
    ``tolerance=0`` callers (scripts/bench_streaming.py) rely on being off."""
    t = float(np.asarray(tolerance))
    if t <= 0:
        return 0.0
    return max(t, 4 * float(jnp.finfo(dtype).eps))


def _kahan_add(acc, comp, x):
    """One compensated (Kahan) accumulation step: returns (acc', comp')
    with acc' - comp' == (acc - comp) + x to ~f32-exact (``comp`` holds
    the running EXCESS of ``acc`` over the true sum, so fold with
    ``acc - comp``). Streamed fits sum
    thousands of per-chunk partials — at the 1TB north star (~15k chunks)
    naive f32 accumulation drifts by ~n_chunks * eps (~2e-3 relative on
    biased sums), which this removes without f64 (unavailable on TPU
    without x64). XLA is IEEE-strict by default, so the cancellation
    sequence below is not reassociated away."""
    y = x - comp
    t = acc + y
    comp = (t - acc) - y
    return t, comp


def _shard_width(mesh: Optional[Mesh], axis: str) -> int:
    return 1 if mesh is None else int(mesh.shape[axis])


def _partial_sharding(mesh, axis):
    """Sharding for per-device partial accumulators ([S] / [S, d] arrays
    whose leading axis is the device axis)."""
    return NamedSharding(mesh, P(axis)) if mesh is not None else None


def _sharded_zeros(shape, dtype, mesh, axis):
    z = jnp.zeros(shape, dtype)
    sh = _partial_sharding(mesh, axis)
    return jax.device_put(z, sh) if sh is not None else z


def _shard_map_chunk(fn, mesh, axis, n_batch_args, acc_ndims):
    """Wrap a per-shard chunk kernel in ``shard_map`` with NO collective:
    batch args shard on ``axis`` (rows), accumulators carry a leading
    device axis ([S, ...], sharded on it), ``w``-like leading args
    replicate.

    WHY: jit-over-sharded-inputs lets GSPMD insert an all-reduce into
    every per-chunk program, and the streamed loops dispatch chunks
    asynchronously (host syncs only at pass end). XLA:CPU's in-process
    rendezvous deadlocks when ~64+ collective executions queue unsynced
    (scripts/repro_cpu_collective_deadlock.py — 7 of 8 participants
    arrive, SIGABRT; r4 contingency). Per-device partials make the
    per-chunk program collective-free on EVERY backend; the single
    cross-shard reduction happens once per pass in a reduce kernel whose
    result the host consumes (and therefore syncs) immediately. On real
    meshes this is also strictly less ICI traffic: one [d] all-reduce per
    PASS instead of per chunk.

    ``check_vma=False`` is load-bearing: under vma tracking the AD
    transpose of "replicated w touches sharded rows" auto-inserts the
    gradient's all-reduce inside the kernel (see
    ``data_parallel.distributed_value_and_grad``'s comment), which would
    put the per-chunk collective right back."""
    in_specs = ((P(),)                      # w (or other replicated lead)
                + (P(axis),) * n_batch_args
                + tuple(P(axis, *([None] * (nd - 1)))
                        for nd in acc_ndims))
    out_specs = tuple(P(axis, *([None] * (nd - 1))) for nd in acc_ndims)
    return shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def _rebuild_batch(dim, indices, values, labels, offsets, weights
                   ) -> LabeledBatch:
    """Rebuild the chunk batch from flat leaves inside a kernel.
    Implicit-ones chunks pass ``values=()`` — an EMPTY pytree, part of the
    jit signature, so the two layouts never retrace each other; shard_map
    specs stay simplest over flat array arguments."""
    return LabeledBatch(
        SparseFeatures(indices,
                       None if isinstance(values, tuple) else values,
                       dim=dim),
        labels, offsets, weights)


def _batch_args(dev: LabeledBatch):
    """Flatten a device batch into the kernel's leaf arguments (the
    inverse of :func:`_rebuild_batch`)."""
    vals = dev.features.values
    return (dev.features.indices, () if vals is None else vals,
            dev.labels, dev.offsets, dev.weights)


def _make_kahan_reduce():
    """The once-per-pass cross-shard fold of [S, ...] Kahan partials —
    the ONLY collective the sharded streamed paths ever run."""
    return lambda acc, comp: jnp.sum(acc - comp, axis=0)


def streaming_value_and_grad(
    objective: GLMObjective,
    chunks: Sequence[HostChunk],
    dim: int,
    dtype=jnp.float32,
    mesh: Optional[Mesh] = None,
    axis: str = "data",
    prefetch_depth: Optional[int] = None,
    stats: Optional[StreamStats] = None,
) -> Callable:
    """Returns fg(w, l2) -> (value, grad) computed in ONE streamed pass over
    the chunks: per-chunk partials accumulate on device, the transfer
    thread stages the next ``prefetch_depth`` chunks while the current one
    computes (:func:`iter_device_chunks`). L2 is added once at the end.

    Distributed (``mesh``): the per-chunk kernel is COLLECTIVE-FREE — each
    device accumulates its own Kahan partial under ``shard_map``; one
    reduction per pass folds the [S]/[S, d] partials (see
    ``_shard_map_chunk`` for why this matters on XLA:CPU and saves ICI
    bandwidth on real meshes)."""
    sharding = None
    if mesh is not None:
        sharding = NamedSharding(mesh, P(axis))
    S = _shard_width(mesh, axis)

    # cached per objective: a GAME CD loop re-enters fit_streaming every
    # iteration — a fresh jit here would recompile the chunk kernel each
    # time (same failure mode the fit_distributed runner cache fixes)

    def _make_chunk_fg():
        def chunk_fg(w, indices, values, labels, offsets, weights,
                     f_acc, f_comp, g_acc, g_comp):
            batch = _rebuild_batch(dim, indices, values, labels, offsets,
                                   weights)
            f, g = objective.value_and_grad(w, batch, 0.0)
            f_acc, f_comp = _kahan_add(f_acc, f_comp,
                                       jnp.reshape(f, f_acc.shape))
            g_acc, g_comp = _kahan_add(g_acc, g_comp,
                                       jnp.reshape(g, g_acc.shape))
            return f_acc, f_comp, g_acc, g_comp

        if mesh is None:
            return chunk_fg
        return _shard_map_chunk(chunk_fg, mesh, axis, n_batch_args=5,
                                acc_ndims=(1, 1, 2, 2))

    def _make_reduce():
        fold = _make_kahan_reduce()

        def reduce_fg(f_acc, f_comp, g_acc, g_comp):
            return fold(f_acc, f_comp), fold(g_acc, g_comp)
        return reduce_fg

    # dim is baked into the kernel closure (the batch rebuild), so it must
    # be part of the cache key: same objective at a different width must
    # not reuse a kernel with a stale dim. The Kahan accumulators are
    # DONATED: each chunk's call reuses the previous (loss, grad, comp)
    # buffers in place instead of allocating a fresh [S, d] pair per chunk.
    chunk_fg_k = cached_jit(objective, ("stream_fg", mesh, axis, dim),
                            _make_chunk_fg, donate_argnums=(6, 7, 8, 9))
    reduce_k = cached_jit(objective, ("stream_fg_reduce", mesh, axis, dim),
                          _make_reduce)

    def fg(w, l2=0.0):
        w = jnp.asarray(w, dtype)
        acc = (_sharded_zeros((S,), dtype, mesh, axis),
               _sharded_zeros((S,), dtype, mesh, axis),
               _sharded_zeros((S, dim), dtype, mesh, axis),
               _sharded_zeros((S, dim), dtype, mesh, axis))
        # the whole local pass runs under the health guard: a process that
        # fails mid-stream (bad block, decode error, injected fault) is
        # converted into PeerFailure on EVERY process at the pass boundary
        # instead of wedging its peers inside _cross_process_sum
        with CollectiveGuard("stream.fg"):
            for _hc, dev in iter_device_chunks(
                    chunks,
                    lambda c: _chunk_to_device(c, dim, dtype, sharding),
                    prefetch_depth, stats):
                acc = chunk_fg_k(w, *_batch_args(dev), *acc)
            # ONE cross-shard reduction per pass; its output is consumed by
            # the host right away, so at most one collective is in flight
            f_acc, g_acc = reduce_k(*acc)
        f_acc, g_acc = _cross_process_sum((f_acc, g_acc), stats)
        wr = objective._reg_mask(w)
        l2 = jnp.asarray(l2, dtype)
        return f_acc + 0.5 * l2 * jnp.sum(wr * wr), g_acc + l2 * wr

    return fg


def streaming_hvp(
    objective: GLMObjective,
    chunks: Sequence[HostChunk],
    dim: int,
    dtype=jnp.float32,
    mesh: Optional[Mesh] = None,
    axis: str = "data",
    prefetch_depth: Optional[int] = None,
    stats: Optional[StreamStats] = None,
) -> Callable:
    """Returns hvp(w, v, l2) computed in one streamed pass — the cost model
    of the reference's HessianVectorAggregator treeAggregate per CG step
    (SURVEY.md §4.2), with chunks instead of cluster partitions. Sharded:
    collective-free per-device partials, one reduction per pass
    (``_shard_map_chunk``)."""
    sharding = NamedSharding(mesh, P(axis)) if mesh is not None else None
    S = _shard_width(mesh, axis)

    def _make_chunk_hvp():
        def chunk_hvp(wv, indices, values, labels, offsets, weights,
                      acc, comp):
            w, v = wv
            batch = _rebuild_batch(dim, indices, values, labels, offsets,
                                   weights)
            hv = objective.hvp(w, v, batch, 0.0)
            return _kahan_add(acc, comp, jnp.reshape(hv, acc.shape))

        if mesh is None:
            return chunk_hvp
        return _shard_map_chunk(chunk_hvp, mesh, axis, n_batch_args=5,
                                acc_ndims=(2, 2))

    chunk_hvp_k = cached_jit(objective, ("stream_hvp", mesh, axis, dim),
                             _make_chunk_hvp, donate_argnums=(6, 7))
    reduce_k = cached_jit(objective, ("stream_hvp_reduce", mesh, axis, dim),
                          _make_kahan_reduce)

    def hvp(w, v, l2=0.0):
        w = jnp.asarray(w, dtype)
        v = jnp.asarray(v, dtype)
        acc = _sharded_zeros((S, dim), dtype, mesh, axis)
        comp = _sharded_zeros((S, dim), dtype, mesh, axis)
        with CollectiveGuard("stream.hvp"):  # see streaming_value_and_grad
            for _hc, dev in iter_device_chunks(
                    chunks,
                    lambda c: _chunk_to_device(c, dim, dtype, sharding),
                    prefetch_depth, stats):
                acc, comp = chunk_hvp_k((w, v), *_batch_args(dev), acc,
                                        comp)
            total = reduce_k(acc, comp)
        total = _cross_process_sum(total, stats)
        return total + jnp.asarray(l2, dtype) * objective._reg_mask(v)

    return hvp


def streaming_coefficient_variances(
    objective: GLMObjective,
    chunks: Sequence[HostChunk],
    dim: int,
    w: jax.Array,
    l2=0.0,
    dtype=jnp.float32,
    mesh: Optional[Mesh] = None,
    axis: str = "data",
    prefetch_depth: Optional[int] = None,
    stats: Optional[StreamStats] = None,
) -> jax.Array:
    """Diagonal-inverse-Hessian coefficient variances over a streamed pass
    (the in-memory ``GLMObjective.coefficient_variances``, chunked). The
    data term accumulates per chunk (l2=0 adds nothing); the regularization
    diagonal is added once at the end."""
    diag = streaming_hessian_diagonal(objective, chunks, dim, w, l2,
                                      dtype, mesh, axis, prefetch_depth,
                                      stats)
    return 1.0 / jnp.maximum(diag, jnp.finfo(dtype).tiny)


def streaming_hessian_diagonal(
    objective: GLMObjective,
    chunks: Sequence[HostChunk],
    dim: int,
    w: jax.Array,
    l2=0.0,
    dtype=jnp.float32,
    mesh: Optional[Mesh] = None,
    axis: str = "data",
    prefetch_depth: Optional[int] = None,
    stats: Optional[StreamStats] = None,
) -> jax.Array:
    """Exact Hessian diagonal over one streamed (Kahan-compensated) pass —
    shared by coefficient variances and TRON's Jacobi preconditioner.
    Sharded: collective-free per-device partials (``_shard_map_chunk``)."""
    sharding = NamedSharding(mesh, P(axis)) if mesh is not None else None
    S = _shard_width(mesh, axis)

    def _make_chunk_diag():
        def chunk_diag(w, indices, values, labels, offsets, weights,
                       acc, comp):
            batch = _rebuild_batch(dim, indices, values, labels, offsets,
                                   weights)
            d = objective.diagonal_hessian(w, batch, 0.0)
            return _kahan_add(acc, comp, jnp.reshape(d, acc.shape))

        if mesh is None:
            return chunk_diag
        return _shard_map_chunk(chunk_diag, mesh, axis, n_batch_args=5,
                                acc_ndims=(2, 2))

    chunk_diag_k = cached_jit(objective, ("stream_diag", mesh, axis, dim),
                              _make_chunk_diag, donate_argnums=(6, 7))
    reduce_k = cached_jit(objective, ("stream_diag_reduce", mesh, axis, dim),
                          _make_kahan_reduce)

    w = jnp.asarray(w, dtype)
    acc = _sharded_zeros((S, dim), dtype, mesh, axis)
    comp = _sharded_zeros((S, dim), dtype, mesh, axis)
    with CollectiveGuard("stream.diag"):  # see streaming_value_and_grad
        for _hc, dev in iter_device_chunks(
                chunks, lambda c: _chunk_to_device(c, dim, dtype, sharding),
                prefetch_depth, stats):
            acc, comp = chunk_diag_k(w, *_batch_args(dev), acc, comp)
        total = reduce_k(acc, comp)
    total = _cross_process_sum(total, stats)
    reg = jnp.full((dim,), jnp.asarray(l2, dtype))
    if not objective.regularize_intercept and objective.intercept_index >= 0:
        reg = reg.at[objective.intercept_index].set(0.0)
    return total + reg


def fit_streaming(
    objective: GLMObjective,
    chunks: Sequence[HostChunk],
    dim: int,
    w0: Optional[jax.Array] = None,
    l2=0.0,
    config: OptimizerConfig = OptimizerConfig(),
    dtype=jnp.float32,
    mesh: Optional[Mesh] = None,
    axis: str = "data",
    optimizer: str = "lbfgs",
    l1=0.0,
    progress_callback: Optional[Callable] = None,
    prefetch_depth: Optional[int] = None,
) -> OptimizationResult:
    """Streamed (larger-than-HBM) full-batch fit.

    ``progress_callback(iteration, w)``, when given, fires with the
    0-based loop index and the current point — measurement harnesses use
    it for per-iteration progress logging and host-side checkpoints so a
    stall loses an iteration, not the run (VERDICT r3 #5). The
    L-BFGS/OWL-QN loops fire only on iterations that accepted a step
    (line-search-failure retries are counted in ``iterations`` but fire
    no callback, so indices can skip); TRON fires every outer iteration
    — a rejected trust-region step still paid a full CG pass sequence,
    and ``w`` is simply unchanged.

    ``optimizer``: "lbfgs" (default — margin-space line search: trials
    stream cached margin vectors instead of paying a sparse pass each,
    see ``_fit_streaming_lbfgs_margin``), "lbfgs_blackbox" (one full
    streamed fg pass per Armijo trial — the reference's cost model),
    "tron" (trust-region Newton — each CG step is one streamed HVP
    pass), or "owlqn" (L1; auto-selected when ``l1`` > 0). Only the
    outer control flow runs on host; direction/update vector math stays
    on device. Line search is backtracking Armijo; pairs are stored only
    under a curvature guard, which keeps the inverse-Hessian metric
    positive definite without paying extra full passes for the Wolfe
    curvature condition (a weaker (s,y) filter than the in-memory
    strong-Wolfe optimizer — convergence contract in docs/PERF.md)."""
    if optimizer == "auto":
        # measured default: the margin L-BFGS streams 2 sparse passes per
        # iteration — the fewest of any streamed optimizer
        optimizer = "lbfgs"
    if np.asarray(l1).item() > 0 and optimizer != "owlqn":
        optimizer = "owlqn"
    stats = StreamStats()
    if optimizer == "tron":
        res = _fit_streaming_tron(objective, chunks, dim, w0, l2, config,
                                  dtype, mesh, axis, progress_callback,
                                  prefetch_depth, stats)
        return _finish_stream_result(res, stats, "tron")
    if optimizer == "owlqn":
        res = _fit_streaming_owlqn(objective, chunks, dim, w0, l2, l1,
                                   config, dtype, mesh, axis,
                                   progress_callback, prefetch_depth, stats)
        return _finish_stream_result(res, stats, "owlqn")
    if optimizer == "lbfgs":
        res = _fit_streaming_lbfgs_margin(objective, chunks, dim, w0, l2,
                                          config, dtype, mesh, axis,
                                          progress_callback, prefetch_depth,
                                          stats)
        return _finish_stream_result(res, stats, "lbfgs")
    if optimizer != "lbfgs_blackbox":
        raise ValueError(f"unknown streaming optimizer '{optimizer}'")
    m = config.history
    if w0 is None:
        w0 = jnp.zeros((dim,), dtype)
    w = jnp.asarray(w0, dtype)
    fg = streaming_value_and_grad(objective, chunks, dim, dtype, mesh, axis,
                                  prefetch_depth, stats)

    direction, store_pair = _lbfgs_stream_kernels(objective, mesh, axis, m)

    f, g = fg(w, l2)
    g0_norm = float(jnp.linalg.norm(g))
    s_hist = history_zeros(m, dim, dtype)
    y_hist = history_zeros(m, dim, dtype)
    rho = jnp.zeros((m,), dtype)
    k = 0
    eps = float(jnp.finfo(dtype).eps)
    tol = _host_tol(config.tolerance, dtype)
    loss_hist = np.full((config.max_iters,), np.nan)
    gnorm_hist = np.full((config.max_iters,), np.nan)

    it = 0
    converged = False
    for it in range(config.max_iters):
        p = direction(g, s_hist, y_hist, rho, jnp.asarray(k))
        dg = float(jnp.sum(p * g))
        if dg >= 0:  # degraded metric: steepest descent restart
            p = -g
            dg = -float(jnp.sum(g * g))
        alpha = 1.0 if k > 0 else 1.0 / max(g0_norm, 1.0)
        f_cur = float(f)
        accepted = False
        for _ in range(config.max_line_search_steps):
            w_try = w + alpha * p
            f_try, g_try = fg(w_try, l2)
            if float(f_try) <= f_cur + 1e-4 * alpha * dg and np.isfinite(
                float(f_try)
            ):
                accepted = True
                break
            alpha *= 0.5
        if not accepted:
            # mirror optimize/lbfgs.py: failing AT the optimum is
            # convergence, not a stall — and with a stale f32 metric a
            # history reset + steepest-descent retry often buys more
            # productive iterations before giving up. The attempted
            # iteration is counted and recorded (f unchanged), matching
            # the in-memory loop's unconditional it+1.
            gnorm = float(jnp.linalg.norm(g))
            loss_hist[it] = float(f)
            gnorm_hist[it] = gnorm
            if tol > 0 and gnorm <= tol * max(g0_norm, 1.0):
                converged = True
                it += 1
                break
            if k > 0:
                s_hist = history_zeros(m, dim, dtype)
                y_hist = history_zeros(m, dim, dtype)
                rho = jnp.zeros((m,), dtype)
                k = 0
                continue
            it += 1  # the attempted iteration counts: histories[:iterations]
            break    # must include the record written above

        step = w_try - w
        yv = g_try - g
        sy = float(jnp.sum(step * yv))
        if sy > 1e-10 * max(
            float(jnp.linalg.norm(step)) * float(jnp.linalg.norm(yv)), eps
        ):
            s_hist, y_hist, rho = store_pair(s_hist, y_hist, rho,
                                             jnp.asarray(k), step, yv)
            k += 1
        w, f, g = w_try, f_try, g_try
        gnorm = float(jnp.linalg.norm(g))
        loss_hist[it] = float(f)
        gnorm_hist[it] = gnorm
        if progress_callback is not None:
            progress_callback(it, w)
        rel = abs(f_cur - float(f)) / max(abs(f_cur), 1.0)
        if tol > 0 and (rel <= tol or gnorm <= tol * max(g0_norm, 1.0)):
            converged = True
            it += 1
            break
    else:
        it = config.max_iters

    return _finish_stream_result(OptimizationResult(
        w=w, value=f, grad_norm=jnp.linalg.norm(g),
        iterations=jnp.asarray(it), converged=jnp.asarray(converged),
        loss_history=jnp.asarray(loss_hist),
        grad_norm_history=jnp.asarray(gnorm_hist),
    ), stats, "lbfgs_blackbox")


def _finish_stream_result(res: OptimizationResult, stats: StreamStats,
                          optimizer: str) -> OptimizationResult:
    """Attach the fit-wide pipeline stall accounting to the result and log
    the one-line breakdown measurement harnesses grep for."""
    _log.info(
        "streamed %s fit: %d passes / %d chunk transfers; decode-wait "
        "%.3fs, transfer %.3fs, compute-stall %.3fs",
        optimizer, stats.passes, stats.chunks, stats.decode_s,
        stats.transfer_s, stats.stall_s)
    # one StreamStats per fit, so the totals ARE this fit's delta
    obs_metrics.training_metrics().record_prefetch(
        stall_s=stats.stall_s, decode_s=stats.decode_s,
        transfer_s=stats.transfer_s)
    return res._replace(stream_stats=stats.as_dict())


def _lbfgs_stream_kernels(objective, mesh, axis, m):
    """Jitted direction/store-pair kernels, cached per (objective, m) so a
    GAME CD loop re-entering fit_streaming every iteration reuses the
    compiled executables (the same failure mode the chunk-kernel cache
    exists for)."""
    direction = cached_jit(
        objective, ("stream_dir", mesh, axis, m),
        lambda: functools.partial(two_loop_direction, m=m))

    def _make_store():
        def store_pair(s_hist, y_hist, rho, k, step, y):
            sy = jnp.sum(step * y)
            slot = jnp.mod(k, m)
            return (history_store(s_hist, slot, step),
                    history_store(y_hist, slot, y),
                    rho.at[slot].set(1.0 / sy))
        return store_pair

    store_pair = cached_jit(objective, ("stream_store", mesh, axis, m),
                            _make_store)
    return direction, store_pair


def _fit_streaming_lbfgs_margin(objective, chunks, dim, w0, l2, config,
                                dtype, mesh, axis, progress_callback=None,
                                prefetch_depth=None,
                                stats=None) -> OptimizationResult:
    """Streamed L-BFGS with margin-space line search (the default).

    The black-box streamed loop pays one FULL sparse pass (index gather +
    transpose) per Armijo trial. GLM margins are affine in w (offsets and
    the normalization adjust are the constant/linear parts —
    ``ops/objective.margins``), so this loop instead caches the per-chunk
    margin vectors ``mw`` in HOST RAM and evaluates the backtracking
    ladder in GROUPS of 8 candidate steps per stream of (mw, mp, labels,
    weights) — 16 bytes/row per group against the hundreds of bytes/row
    of a sparse pass per trial; the first group almost always decides, so
    the typical iteration is one gather pass (the direction's margins),
    one margin-only ladder stream (worst case
    ceil(max_line_search_steps/8)), and one
    gather+transpose pass for the accepted point's gradient — the same
    2-sparse-pass cost as the in-memory margin optimizer
    (``optimize/lbfgs_margin.py``), where the black-box loop paid
    ``1 + n_trials`` full passes. The L2 term is closed-form along the ray
    (three O(d) scalars). Accumulations are Kahan-compensated; Armijo
    semantics and the (s, y) curvature guard match the black-box loop.

    Drift consistency: ``mw`` is updated incrementally and in f32 slowly
    drifts from the exact margins of ``w``, so the Armijo test compares
    the trial against ``phi(0)`` — the margin-space value of the CURRENT
    point under the same drift — never against the exact ``f`` from the
    sparse pass (mixing the two reference frames would make the shrinking
    Armijo allowance a coin flip near convergence). Exact (f, g) from the
    accepted-point sparse pass still drive convergence tests and the
    returned histories."""
    m = config.history
    if w0 is None:
        w0 = jnp.zeros((dim,), dtype)
    w = jnp.asarray(w0, dtype)
    sharding = NamedSharding(mesh, P(axis)) if mesh is not None else None
    fg = streaming_value_and_grad(objective, chunks, dim, dtype, mesh, axis,
                                  prefetch_depth, stats)

    margin_k = cached_jit(
        objective, ("stream_margin", mesh, axis),
        lambda: lambda w, batch: objective.margins(w, batch))
    # per-chunk trial: masked margins -> weighted loss partial (Kahan)
    from photon_ml_tpu.ops.losses import apply_weights, mask_margins

    # Ladder GROUP width: per streamed pass, this many candidate steps are
    # evaluated together (G x the pointwise math per chunk — nearly free on
    # device, noticeable on a 1-core CPU host, hence not the full 25-step
    # ladder). Backtracking rarely goes past the first few halvings, so one
    # group usually decides; worst case ceil(max_line_search_steps / G)
    # passes instead of one pass per trial.
    L = min(max(int(config.max_line_search_steps), 1), 8)

    S = _shard_width(mesh, axis)

    def _make_trial():
        def trial(alphas, mw, mp, labels, weights, f_acc, f_comp):
            # DELTA space: per-row loss DIFFERENCES l(mw + a*mp) - l(mw).
            # In f32 a loss total's resolution is eps*|f|, far coarser
            # than late-stage improvements, so Armijo on totals stalls;
            # the difference keeps relative accuracy in the improvement
            # itself (same scheme as the in-memory lbfgs_margin delta
            # path). Also removes the need for a separate phi(0) stream:
            # the trial compares against 0.
            #
            # LADDER: ``alphas`` is the whole [L] backtracking ladder and
            # f_acc/f_comp are [L] Kahan accumulators — the streamed
            # search is transfer-bound, so every candidate step is
            # evaluated in the SAME streamed visit of the chunk (L x the
            # pointwise math, ~free on device) instead of one 16B/row
            # stream per trial.
            mm0 = mask_margins(weights, mw)
            l0 = apply_weights(weights, objective.loss.loss(mm0, labels))

            def per_alpha(a):
                mm1 = mask_margins(weights, mw + a * mp)
                return jnp.sum(apply_weights(
                    weights, objective.loss.loss(mm1, labels)) - l0)

            return _kahan_add(f_acc, f_comp,
                              jnp.reshape(jax.vmap(per_alpha)(alphas),
                                          f_acc.shape))

        if mesh is None:
            return trial
        # collective-free per-device [1, L] partials (_shard_map_chunk:
        # the async ladder loop must queue no rendezvous)
        return _shard_map_chunk(trial, mesh, axis, n_batch_args=4,
                                acc_ndims=(2, 2))

    trial_k = cached_jit(objective,
                         ("stream_trial_delta_ladder", mesh, axis, L),
                         _make_trial, donate_argnums=(5, 6))
    trial_reduce_k = cached_jit(
        objective, ("stream_trial_reduce", mesh, axis, L),
        _make_kahan_reduce)

    def _put(a):
        if not isinstance(a, jax.Array):
            # charge the bytes actually moved (post-cast width); any
            # host array-protocol object counts, not only np.ndarray —
            # same gate as transfer_budget.device_put (ADVICE r4), and
            # the charge doubles as the stall-watchdog liveness signal
            transfer_budget.charge(
                int(np.size(a)) * jnp.dtype(dtype).itemsize,
                "margin trial chunk")
        dev = jnp.asarray(a, dtype)
        return jax.device_put(dev, sharding) if sharding else dev

    # Host scalar cache: per-chunk labels/weights/offsets, captured during
    # the first streamed pass. For in-RAM chunk lists these are references
    # (zero copy); for a disk-backed source (io/stream_source.py) this is
    # the 12B/row cache that makes every margin-ladder trial DECODE-FREE —
    # without it each ladder group would re-decode full chunks from disk
    # just to read two scalar columns, turning the 2-pass/iteration cost
    # model into ~(2 + groups) full decodes. Same order of host state as
    # the mw/mp margin caches below (8B/row).
    n_chunks = len(chunks)
    labels_h = [None] * n_chunks
    weights_h = [None] * n_chunks
    offsets_h = [None] * n_chunks

    def margins_of(vec, out):
        """One streamed gather pass: per-chunk margins of ``vec`` (offsets
        included), stored to host numpy in ``out``. The transfer ring
        stages chunk i+1..i+K while chunk i's margins compute, and the
        device->host fetch of chunk i-1 overlaps chunk i's dispatch."""
        # guarded even though this pass itself has no collective: in SPMD
        # lockstep the peers run this same pass, and a process failing
        # here would otherwise strand them at the NEXT phase's barrier
        # until the watchdog instead of aborting promptly
        with CollectiveGuard("stream.margins"):
            pending = None
            for i, (chunk, dev) in enumerate(iter_device_chunks(
                    chunks,
                    lambda c: _chunk_to_device(c, dim, dtype, sharding),
                    prefetch_depth, stats)):
                if labels_h[i] is None:
                    labels_h[i] = chunk.labels
                    weights_h[i] = chunk.weights
                    offsets_h[i] = chunk.offsets
                res = margin_k(vec, dev)
                if pending is not None:
                    out[pending[0]] = np.asarray(pending[1])
                pending = (i, res)
            if pending is not None:
                out[pending[0]] = np.asarray(pending[1])
        return out

    def phi_delta_ladder(mw_h, mp_h, alphas):
        """[L] data-term deltas f(w + a p) - f(w) for the whole
        backtracking ladder, in ONE margin-only streamed pass over the
        HOST caches — no chunk (re-)decode, no sparse data, and (sharded)
        no per-chunk collective: per-device [S, L] partials reduce once
        at the end, synced by the host fetch below."""
        f_acc = _sharded_zeros((S, L), dtype, mesh, axis)
        f_comp = _sharded_zeros((S, L), dtype, mesh, axis)
        a = jnp.asarray(alphas, dtype)
        with CollectiveGuard("stream.ladder"):  # see streaming_value_and_grad
            for i in range(n_chunks):
                f_acc, f_comp = trial_k(
                    a, _put(mw_h[i]), _put(mp_h[i]),
                    _put(labels_h[i]), _put(weights_h[i]),
                    f_acc, f_comp)
            total = trial_reduce_k(f_acc, f_comp)
        (d,) = _cross_process_sum((total,), stats)
        return np.asarray(d, np.float64)

    direction, store_pair = _lbfgs_stream_kernels(objective, mesh, axis, m)

    f, g = fg(w, l2)
    g0_norm = float(jnp.linalg.norm(g))
    mw_h = margins_of(w, [None] * len(chunks))
    mp_h = [None] * len(chunks)
    s_hist = history_zeros(m, dim, dtype)
    y_hist = history_zeros(m, dim, dtype)
    rho = jnp.zeros((m,), dtype)
    k = 0
    eps = float(jnp.finfo(dtype).eps)
    tol = _host_tol(config.tolerance, dtype)
    loss_hist = np.full((config.max_iters,), np.nan)
    gnorm_hist = np.full((config.max_iters,), np.nan)

    it = 0
    converged = False
    for it in range(config.max_iters):
        p = direction(g, s_hist, y_hist, rho, jnp.asarray(k))
        dg = float(jnp.sum(p * g))
        if dg >= 0:  # degraded metric: steepest descent restart
            p = -g
            dg = -float(jnp.sum(g * g))
        # ONE gather pass: the direction's margins (offsets subtracted:
        # margins() adds them and they are the affine constant)
        mp_h = margins_of(p, mp_h)
        for i in range(n_chunks):
            mp_h[i] = mp_h[i] - np.asarray(offsets_h[i], mp_h[i].dtype)
        # L2 delta along the ray: l2 * (a c1 + a^2/2 c2)
        wr = np.asarray(objective._reg_mask(w), np.float64)
        pr = np.asarray(objective._reg_mask(p), np.float64)
        l2f = float(np.asarray(l2))
        c1, c2 = wr @ pr, pr @ pr

        alpha0 = 1.0 if k > 0 else 1.0 / max(g0_norm, 1.0)
        f_cur = float(f)  # exact value (fg pass) — drives convergence only
        # delta-space Armijo over ladder GROUPS, each group one streamed
        # pass: improvement vs 0, accurate at any |f| (and
        # drift-consistent — both sides live on the cached mw). First
        # (largest) passing alpha == what sequential backtracking would
        # have taken.
        full = alpha0 * 0.5 ** np.arange(config.max_line_search_steps)
        accepted = False
        alpha = 0.0
        for g0 in range(0, len(full), L):
            grp = full[g0:g0 + L]
            if len(grp) < L:  # pad: duplicates of the last alpha are inert
                grp = np.concatenate([grp, np.full(L - len(grp), grp[-1])])
            deltas = (phi_delta_ladder(mw_h, mp_h, grp)
                      + l2f * (grp * c1 + 0.5 * grp * grp * c2))
            armijo = (deltas <= 1e-4 * grp * dg) & np.isfinite(deltas)
            if armijo.any():
                accepted = True
                alpha = float(grp[int(np.argmax(armijo))])
                break
        if not accepted:
            # mirror optimize/lbfgs_margin.py: a search failing AT the
            # optimum is convergence, not a stall; otherwise reset the
            # (stale-in-f32) history and retry once from steepest descent
            # before reporting not-converged. The attempted iteration is
            # counted and recorded (f unchanged), matching the in-memory
            # loop's unconditional it+1.
            gnorm = float(jnp.linalg.norm(g))
            loss_hist[it] = float(f)
            gnorm_hist[it] = gnorm
            if tol > 0 and gnorm <= tol * max(g0_norm, 1.0):
                converged = True
                it += 1
                break
            if k > 0:
                s_hist = history_zeros(m, dim, dtype)
                y_hist = history_zeros(m, dim, dtype)
                rho = jnp.zeros((m,), dtype)
                k = 0
                continue
            it += 1  # the attempted iteration counts: histories[:iterations]
            break    # must include the record written above

        w_try = w + jnp.asarray(alpha, dtype) * p
        # accepted point: ONE gather+transpose pass for the exact (f, g)
        f_try_x, g_try = fg(w_try, l2)
        for i in range(len(chunks)):
            mw_h[i] = mw_h[i] + mw_h[i].dtype.type(alpha) * mp_h[i]
        step = w_try - w
        yv = g_try - g
        sy = float(jnp.sum(step * yv))
        if sy > 1e-10 * max(
            float(jnp.linalg.norm(step)) * float(jnp.linalg.norm(yv)), eps
        ):
            s_hist, y_hist, rho = store_pair(s_hist, y_hist, rho,
                                             jnp.asarray(k), step, yv)
            k += 1
        w, f, g = w_try, f_try_x, g_try
        gnorm = float(jnp.linalg.norm(g))
        loss_hist[it] = float(f)
        gnorm_hist[it] = gnorm
        if progress_callback is not None:
            progress_callback(it, w)
        rel = abs(f_cur - float(f)) / max(abs(f_cur), 1.0)
        if tol > 0 and (rel <= tol or gnorm <= tol * max(g0_norm, 1.0)):
            converged = True
            it += 1
            break
    else:
        it = config.max_iters

    return OptimizationResult(
        w=w, value=f, grad_norm=jnp.linalg.norm(g),
        iterations=jnp.asarray(it), converged=jnp.asarray(converged),
        loss_history=jnp.asarray(loss_hist),
        grad_norm_history=jnp.asarray(gnorm_hist),
    )


# Lin-Moré / LIBLINEAR constants (same as optimize/tron.py)
_ETA0, _ETA1, _ETA2 = 1e-4, 0.25, 0.75
_SIGMA1, _SIGMA2, _SIGMA3 = 0.25, 0.5, 4.0


def _fit_streaming_tron(objective, chunks, dim, w0, l2, config, dtype, mesh,
                        axis, progress_callback=None, prefetch_depth=None,
                        stats=None) -> OptimizationResult:
    """Host-loop TRON mirroring ``optimize.tron``: Steihaug CG inner loop
    where every Hessian-vector product is one streamed pass over the data —
    the reference's one-treeAggregate-per-CG-step cost model (SURVEY.md
    §4.2) with host chunks in place of cluster partitions."""
    if w0 is None:
        w0 = jnp.zeros((dim,), dtype)
    w = jnp.asarray(w0, dtype)
    fg = streaming_value_and_grad(objective, chunks, dim, dtype, mesh, axis,
                                  prefetch_depth, stats)
    hvp = streaming_hvp(objective, chunks, dim, dtype, mesh, axis,
                        prefetch_depth, stats)
    max_cg = max(dim, 20)
    eps = float(jnp.finfo(dtype).eps)

    def cg(wc, g, delta, cg_tol, m_diag):
        """Jacobi-preconditioned Steihaug CG; each hvp call is a full
        streamed pass, so the preconditioner (one extra streamed diag
        pass per OUTER iteration) buys the expensive thing: fewer inner
        passes. Trust region measured in the M-norm (mirrors
        optimize.tron)."""
        minv = 1.0 / m_diag
        mdot = lambda a, b: float(jnp.sum(a * m_diag * b))
        s = jnp.zeros_like(g)
        r = -g
        d = minv * r
        rz = float(jnp.sum(r * d))
        for _ in range(max_cg):
            Hd = hvp(wc, d, l2)
            dHd = float(jnp.sum(d * Hd))
            neg_curv = dHd <= 0
            alpha = rz / (1.0 if neg_curv else dHd)
            outside = np.sqrt(mdot(s + alpha * d, s + alpha * d)) >= delta
            if neg_curv or outside:
                sd = mdot(s, d)
                dd = mdot(d, d)
                ss = mdot(s, s)
                disc = np.sqrt(max(sd * sd + dd * (delta * delta - ss), 0.0))
                tau = (-sd + disc) / max(dd, eps)
                s = s + tau * d
                r = r - tau * Hd
                break
            s = s + alpha * d
            r = r - alpha * Hd
            if float(jnp.linalg.norm(r)) <= cg_tol:
                break
            z = minv * r
            rz_new = float(jnp.sum(r * z))
            d = z + (rz_new / max(rz, eps)) * d
            rz = rz_new
        return s, r

    f, g = fg(w, l2)
    f = float(f)
    g0_norm = float(jnp.linalg.norm(g))
    delta = g0_norm
    tol = _host_tol(config.tolerance, dtype)
    loss_hist = np.full((config.max_iters,), np.nan)
    gnorm_hist = np.full((config.max_iters,), np.nan)
    it = 0
    converged = False
    m_diag = None
    for it in range(config.max_iters):
        gnorm = float(jnp.linalg.norm(g))
        if m_diag is None:  # recomputed only after an ACCEPTED step
            md = streaming_hessian_diagonal(objective, chunks, dim, w, l2,
                                            dtype, mesh, axis,
                                            prefetch_depth, stats)
            # same relative positivity floor as optimize.tron
            m_diag = jnp.maximum(md, eps * jnp.maximum(float(jnp.max(md)),
                                                       1.0))
        step, r = cg(w, g, delta, 0.1 * gnorm, m_diag)
        w_try = w + step
        f_try_j, g_try = fg(w_try, l2)
        f_try = float(f_try_j)
        gs = float(jnp.sum(g * step))
        prered = 0.5 * (float(jnp.sum(step * r)) - gs)
        actred = f - f_try
        # radius lives in the CG's M-norm
        snorm = float(jnp.sqrt(jnp.sum(step * m_diag * step)))

        denom = f_try - f - gs
        alpha = _SIGMA3 if denom <= 0 else max(_SIGMA1, -0.5 * (gs / denom))
        if actred < _ETA0 * prered:
            delta = min(max(alpha, _SIGMA1) * snorm, _SIGMA2 * delta)
        elif actred < _ETA1 * prered:
            delta = max(_SIGMA1 * delta, min(alpha * snorm, _SIGMA2 * delta))
        elif actred < _ETA2 * prered:
            delta = max(_SIGMA1 * delta, min(alpha * snorm, _SIGMA3 * delta))
        else:
            delta = max(delta, min(alpha * snorm, _SIGMA3 * delta))

        accept = actred > _ETA0 * prered
        if accept:
            m_diag = None  # w moved: the cached diagonal is stale
            prev_f = f
            w, f, g = w_try, f_try, g_try
            gnorm = float(jnp.linalg.norm(g))
            rel = abs(prev_f - f) / max(abs(prev_f), 1.0)
            if tol > 0 and (rel <= tol or gnorm <= tol * max(g0_norm, 1.0)):
                converged = True
        loss_hist[it] = f
        gnorm_hist[it] = gnorm
        if progress_callback is not None:
            # TRON fires every OUTER iteration, accepted or not: a
            # rejected step still paid a full Steihaug-CG sequence of
            # streamed passes, and the stall
            # watchdog must see that heartbeat. ``w`` is the current
            # (possibly unmoved) point, so checkpoints stay valid, and
            # TRON's own ``iterations`` counts rejected outer iterations
            # the same way.
            progress_callback(it, w)
        if prered <= eps * max(abs(f), 1.0):  # model predicts no gain left
            converged = True
        if converged or delta < eps * max(float(jnp.linalg.norm(w)), 1.0):
            it += 1
            break
    else:
        it = config.max_iters

    return OptimizationResult(
        w=w, value=jnp.asarray(f, dtype), grad_norm=jnp.linalg.norm(g),
        iterations=jnp.asarray(it), converged=jnp.asarray(converged),
        loss_history=jnp.asarray(loss_hist),
        grad_norm_history=jnp.asarray(gnorm_hist),
    )


def _fit_streaming_owlqn(objective, chunks, dim, w0, l2, l1, config, dtype,
                         mesh, axis, progress_callback=None,
                         prefetch_depth=None, stats=None
                         ) -> OptimizationResult:
    """Host-loop OWL-QN mirroring ``optimize.owlqn`` (Andrew & Gao 2007):
    pseudo-gradient from the streamed smooth gradient, L-BFGS direction on
    device, orthant projection of direction and iterates; every line-search
    evaluation is one streamed pass."""
    from photon_ml_tpu.optimize.owlqn import pseudo_gradient

    m = config.history
    if w0 is None:
        w0 = jnp.zeros((dim,), dtype)
    w = jnp.asarray(w0, dtype)
    fg = streaming_value_and_grad(objective, chunks, dim, dtype, mesh, axis,
                                  prefetch_depth, stats)
    mask = jnp.ones((dim,), dtype)
    if objective.intercept_index >= 0 and not objective.regularize_intercept:
        mask = mask.at[objective.intercept_index].set(0.0)
    lam = jnp.asarray(l1, dtype) * mask

    direction = jax.jit(functools.partial(two_loop_direction, m=m))

    @jax.jit
    def project_direction(p, pg):
        p = jnp.where(p * (-pg) > 0, p, 0.0)
        dg = jnp.sum(p * pg)
        return jnp.where(dg < 0, p, -pg), jnp.minimum(dg, jnp.sum(-pg * pg))

    @jax.jit
    def project_point(w_trial, xi):
        return jnp.where(w_trial * xi > 0, w_trial, 0.0)

    def full_F(f_smooth, w_at):
        return float(f_smooth) + float(jnp.sum(lam * jnp.abs(w_at)))

    f, g = fg(w, l2)
    F = full_F(f, w)
    pg = pseudo_gradient(w, g, lam)
    pg0_norm = float(jnp.linalg.norm(pg))
    eps = float(jnp.finfo(dtype).eps)
    tol = _host_tol(config.tolerance, dtype)
    s_hist = history_zeros(m, dim, dtype)
    y_hist = history_zeros(m, dim, dtype)
    rho = jnp.zeros((m,), dtype)
    k = 0
    loss_hist = np.full((config.max_iters,), np.nan)
    gnorm_hist = np.full((config.max_iters,), np.nan)
    it = 0
    converged = False
    for it in range(config.max_iters):
        pg = pseudo_gradient(w, g, lam)
        p = direction(pg, s_hist, y_hist, rho, jnp.asarray(k))
        p, _ = project_direction(p, pg)
        xi = jnp.where(w != 0, jnp.sign(w), jnp.sign(-pg))
        alpha = 1.0 if k > 0 else 1.0 / max(float(jnp.linalg.norm(pg)), 1.0)
        accepted = False
        for _ in range(config.max_line_search_steps):
            w_try = project_point(w + alpha * p, xi)
            f_try, g_try = fg(w_try, l2)
            F_try = full_F(f_try, w_try)
            dgtest = float(jnp.sum(pg * (w_try - w)))
            if F_try <= F + 1e-4 * dgtest and np.isfinite(F_try):
                accepted = True
                break
            alpha *= 0.5
        if not accepted:
            break
        step = w_try - w
        yv = g_try - g
        sy = float(jnp.sum(step * yv))
        if sy > 1e-10 * max(
            float(jnp.linalg.norm(step)) * float(jnp.linalg.norm(yv)), eps
        ):
            slot = k % m
            s_hist = history_store(s_hist, slot, step)
            y_hist = history_store(y_hist, slot, yv)
            rho = rho.at[slot].set(1.0 / sy)
            k += 1
        F_prev = F
        w, g, F = w_try, g_try, F_try
        pg_norm = float(jnp.linalg.norm(pseudo_gradient(w, g, lam)))
        loss_hist[it] = F
        gnorm_hist[it] = pg_norm
        if progress_callback is not None:
            progress_callback(it, w)
        rel = abs(F_prev - F) / max(abs(F_prev), 1.0)
        if tol > 0 and (rel <= tol or pg_norm <= tol * max(pg0_norm, 1.0)):
            converged = True
            it += 1
            break
    else:
        it = config.max_iters

    final_pg = pseudo_gradient(w, g, lam)
    return OptimizationResult(
        w=w, value=jnp.asarray(F, dtype),
        grad_norm=jnp.linalg.norm(final_pg),
        iterations=jnp.asarray(it), converged=jnp.asarray(converged),
        loss_history=jnp.asarray(loss_hist),
        grad_norm_history=jnp.asarray(gnorm_hist),
    )
