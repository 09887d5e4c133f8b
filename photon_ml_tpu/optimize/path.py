"""Pathwise fixed-effect GLM training with KKT-certified safe screening.

The lambda grid is the last repeated cost in fixed-effect training: the
driver warm-starts coefficients across ``--reg-weights`` but every lambda
still solves over ALL features, although at the sparse (large-lambda) end
of an elastic-net path almost every coordinate of the solution is zero.
Strong-rule screening is the standard fix in distributed CD for
regularized GLMs (arxiv 1611.02101) and the core of Snap ML's
hierarchical solver (arxiv 1803.06333): walking the grid in decreasing
order, a feature whose data-gradient magnitude at the previous lambda's
solution falls below the sequential threshold
(``ops.regularization.screening_threshold``) is frozen at zero, the
restricted problem is solved over the survivors, and a full-gradient KKT
check certifies the screen — violators re-enter and the solve repeats, so
a screened fit matches the unscreened fit within solver tolerance BY
CONSTRUCTION, never by hope. This is the fixed-effect twin of the
random-effect active-set CD (``docs/descent.md``): same frozen-frontier
idea, applied across the regularization path instead of across sweeps.

Cost model per lambda (screen on, no repair round): one restricted solve
over a power-of-two bucket of the candidate width plus exactly ONE full
data pass — the certification gradient, which is then REUSED as the next
lambda's screening gradient. Compare one full-width solve (tens of full
passes) per lambda without screening.

Restriction is an ELL column remap, not a data rebuild: member columns
map through a LUT to ``[0, bucket)`` (intercept pinned to restricted
slot 0 so the restricted objective's static fields never change),
non-member slots keep index 0 with value 0 — the restricted batch has
the same ``[n, k]`` shape with only the static ``dim`` shrunk, and the
restricted margins are addend-for-addend the same sums as the full
margins at the scattered-back point. Widths ride a power-of-two bucket
ladder (``pad_to_bucket``) with ONE restricted objective shared by every
bucket, so the jit ladder stays flat as the active set shrinks: after
warm-up, new lambdas compile nothing.

Both data planes are served: in-memory (``fit_distributed`` on a mesh,
full-gradient passes through one cached ``distributed_value_and_grad``
kernel) and out-of-core (``fit_streaming`` over host chunks, with
``_RestrictedChunks`` remapping lazily per pass and
``streaming_value_and_grad`` for the certification pass) — under the
driver's chunk cache the whole 50-lambda path is ONE decode of the data.

Normalization does NOT compose with screening: normalization arrays are
pytree leaves baked into the cached restricted runners, and the virtual
shift couples every column through the margin adjustment, so a frozen
column would still move the margins. ``PathSolver`` refuses the
combination up front instead of silently mis-screening.
"""

from __future__ import annotations

import dataclasses
import logging
import time
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from photon_ml_tpu.obs import metrics as obs_metrics
from photon_ml_tpu.obs import trace as obs_trace
from photon_ml_tpu.ops.objective import GLMObjective, make_objective
from photon_ml_tpu.ops.regularization import (
    RegularizationContext,
    kkt_slack,
    screening_threshold,
)
from photon_ml_tpu.optimize.common import (
    OptimizationResult,
    OptimizerConfig,
    PathConfig,
)

_log = logging.getLogger("photon_ml_tpu")

__all__ = ["PathSolver", "PathLambdaStats", "next_power_of_two",
           "pad_to_bucket"]


def next_power_of_two(n: int) -> int:
    """Smallest power of two >= n (n >= 1)."""
    n = int(n)
    return 1 if n <= 1 else 1 << (n - 1).bit_length()


def pad_to_bucket(n: int, floor: int = 1) -> int:
    """Power-of-two bucket width for a candidate set of size ``n`` with a
    lower bound of ``floor`` (tiny sets must not mint single-use
    compilations). Registered with photon-check's shape-helper set, so
    shapes routed through here stay on the compiled ladder."""
    return next_power_of_two(max(int(n), int(floor)))


@dataclasses.dataclass
class PathLambdaStats:
    """Per-lambda screening record: what the lambda log line, the
    ``photon_train_path_*`` metrics, ``BENCH_path.json`` and the resume
    fingerprint all read. ``screened_dim`` is the restricted width the
    FINAL solve ran over (the bucket; ``dim`` when the solve fell
    through to full width), so artifacts assert the restricted-problem
    geometry, not just the outcome."""

    lam: float
    lam_l1: float
    lam_l2: float
    dim: int
    candidate_size: int      # candidates entering the first restricted solve
    screened_dim: int        # restricted width of the final (accepted) solve
    features_frozen: int     # dim - final candidate count (0 on full solves)
    kkt_rounds: int          # solve rounds total; 1 = screen held first try
    kkt_violations: int      # violators re-admitted across repair rounds
    solver_iterations: int   # optimizer iterations summed over rounds
    full_grad_passes: int    # full data passes paid for screen init + certs
    fallback_full: bool      # repair budget exhausted -> full-width solve
    screen_rule: str
    certified: bool          # always True on return (full solves trivially)
    solver_tolerance: float
    solve_seconds: float

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)


@dataclasses.dataclass
class _PathState:
    """One warm snapshot per solved lambda: the solution, and (lazily)
    the certified data gradient at it — the next lambda's screening
    input. ``g`` is None when the state was seeded from a resume marker
    or produced by a full-width solve; ``_ensure_grad`` computes it on
    first use, which keeps resumed runs' candidate sets IDENTICAL to
    uninterrupted runs (both screen from the data gradient at the same
    point)."""

    lam: float
    lam_l1: float
    w: np.ndarray
    g: Optional[np.ndarray]


class _RestrictedChunks:
    """Lazy LUT-remapped view of a host chunk sequence: each access
    rebuilds the chunk with member columns remapped into ``[0, bucket)``
    and non-member slots zeroed — same ``[rows, k]`` shapes, so the
    streamed kernels' fixed-shape contract holds per bucket. Implicit-
    ones chunks must materialize values here (the member mask IS the
    value), costing the value plane's transfer back; screening still
    wins because the restricted gradient/margin width shrank."""

    def __init__(self, chunks: Sequence, member: np.ndarray,
                 lut: np.ndarray, value_dtype):
        self._chunks = chunks
        self._member = member
        self._lut = lut
        self._vdtype = value_dtype

    def __len__(self) -> int:
        return len(self._chunks)

    def _remap(self, c):
        from photon_ml_tpu.parallel.streaming import HostChunk

        m = self._member[c.indices]
        idx = np.where(m, self._lut[c.indices], 0)
        ones = np.ones(c.indices.shape, self._vdtype)
        vals = ones if c.values is None else c.values
        return HostChunk(
            indices=np.ascontiguousarray(idx, np.int32),
            values=np.where(m, vals, np.zeros((), self._vdtype)),
            labels=c.labels, offsets=c.offsets, weights=c.weights)

    def __getitem__(self, i):
        return self._remap(self._chunks[i])

    def __iter__(self):
        for c in self._chunks:
            yield self._remap(c)


class PathSolver:
    """Pathwise fixed-effect solver: screen -> restricted solve -> KKT
    certify, one lambda at a time, with warm state shared across calls.

    The caller drives the grid (the driver walks it in decreasing order;
    the tuner calls out of order — any solved neighbor works as a warm/
    screening source because certification is unconditional). Exactly one
    of ``batch`` (in-memory: a LabeledBatch + mesh) or ``chunks`` (+
    ``dim``; out-of-core host chunks, ``mesh`` optional) must be given.

    ``solve(reg_weight)`` returns ``(OptimizationResult, PathLambdaStats)``
    with the result's ``w`` scattered back to full width and
    ``solver_tolerance``/``screened_dim`` attached, so every consumer can
    assert the restricted-problem geometry."""

    def __init__(
        self,
        objective: GLMObjective,
        reg: RegularizationContext,
        *,
        batch=None,
        chunks: Optional[Sequence] = None,
        dim: Optional[int] = None,
        mesh=None,
        axis: str = "data",
        optimizer: str = "lbfgs",
        config: OptimizerConfig = OptimizerConfig(),
        path_config: PathConfig = PathConfig(),
        dtype=jnp.float32,
        sparse_grad: str = "auto",
        precomputed_csc=None,
        prefetch_depth: Optional[int] = None,
        w0=None,
    ):
        if (batch is None) == (chunks is None):
            raise ValueError("pass exactly one of batch= or chunks=")
        if objective.normalization is not None \
                and path_config.screen != "off":
            raise ValueError(
                "screening does not compose with normalization (the "
                "virtual shift couples all columns through the margin "
                "adjustment and the factors bake into cached restricted "
                "runners); fit unnormalized or pass screen='off'")
        self._objective = objective
        self._reg = reg
        self._mesh = mesh
        self._axis = axis
        self._optimizer = optimizer
        self._config = config
        self._pc = path_config
        self._dtype = dtype
        self._sparse_grad = sparse_grad
        self._prefetch_depth = prefetch_depth
        self._streaming = chunks is not None
        self._states: List[_PathState] = []
        self._init_probe = None  # (w_init, g_init, lam1_max) — lazy
        self.total_iterations = 0  # across every solve (tuner accounting)

        # one restricted objective serves EVERY bucket: its static fields
        # (loss, regularize_intercept, intercept slot pinned to 0) do not
        # depend on the bucket width, so the runner/kernel caches keyed on
        # its identity hold one ladder of shape-specialized executables
        self._robj = make_objective(
            objective.loss, None, objective.regularize_intercept,
            0 if objective.intercept_index >= 0 else -1)

        if self._streaming:
            if dim is None:
                raise ValueError("chunks= mode needs dim=")
            self._chunks = chunks
            self._dim = int(dim)
            self._np_dtype = np.dtype(jnp.dtype(dtype).name)
            from photon_ml_tpu.parallel.streaming import (
                streaming_value_and_grad)

            self._stream_fg = streaming_value_and_grad(
                objective, chunks, self._dim, dtype, mesh, axis,
                prefetch_depth)
            self._pcsc = None
        else:
            if mesh is None:
                raise ValueError("batch= mode needs mesh=")
            from photon_ml_tpu.parallel.data_parallel import (
                cached_jit, distributed_value_and_grad, resolve_sparse_grad,
                uses_csc)
            from photon_ml_tpu.parallel.mesh import shard_batch
            from photon_ml_tpu.types import SparseFeatures

            self._batch = batch
            feats = batch.features
            if isinstance(feats, SparseFeatures):
                self._dim = feats.dim
                self._h_indices = np.asarray(feats.indices)
                self._h_values = (None if feats.values is None
                                  else np.asarray(feats.values))
                self._h_dense = None
            else:
                dense = np.asarray(feats)
                self._dim = dense.shape[1]
                self._h_dense = dense
                self._h_indices = self._h_values = None
                # device-resident copy with one trailing all-zero column:
                # restricted batches are built by a jitted device gather
                # (pad slots index the zero column), not a host-side
                # column copy — the host gather+pad dominated the
                # restricted solve cost at bench sizes
                self._d_dense_z = jax.device_put(
                    np.pad(dense, ((0, 0), (0, 1))))
                self._gather_k = cached_jit(
                    self._robj, ("path_gather", mesh, axis),
                    lambda: lambda x, idx: x[:, idx])
            self._h_labels = np.asarray(batch.labels)
            self._h_offsets = np.asarray(batch.offsets)
            self._h_weights = np.asarray(batch.weights)
            self._np_dtype = self._h_labels.dtype
            # the full problem's precomputed CSC serves full-width solves
            # only (restricted geometry differs); it is an error to hold
            # one when the resolved sparse-grad path would not read it
            resolved = resolve_sparse_grad(sparse_grad, feats)
            self._pcsc = precomputed_csc if uses_csc(resolved) else None
            # certification kernel: the batch is sharded ONCE and the fg
            # runner cached on the full objective, so every lambda's full-
            # gradient pass reuses one executable
            self._sbatch = shard_batch(batch, mesh, axis)
            self._full_fg = cached_jit(
                objective, ("path_full_fg", mesh, axis),
                lambda: distributed_value_and_grad(objective, mesh, axis))
        self._zero = jnp.zeros((), self._np_dtype)
        if w0 is not None:
            self._w_init = np.asarray(w0, self._np_dtype)
        else:
            self._w_init = np.zeros((self._dim,), self._np_dtype)
        self._penalized = np.ones((self._dim,), bool)
        if objective.intercept_index >= 0 \
                and not objective.regularize_intercept:
            self._penalized[objective.intercept_index] = False

    # -- full data-gradient pass (screen init + certification) -------------
    def _full_grad(self, w: np.ndarray) -> np.ndarray:
        """Data-only gradient (l2=0) at ``w`` — exactly the quantity both
        the screening rules and the zero-coordinate KKT condition are
        stated in (at a zero coordinate the ridge term contributes
        nothing)."""
        w_dev = jnp.asarray(w, self._np_dtype)
        if self._streaming:
            _f, g = self._stream_fg(w_dev, self._zero)
        else:
            _f, g = self._full_fg(w_dev, self._sbatch, self._zero)
        return np.asarray(g)

    def _ensure_grad(self, state: _PathState) -> int:
        if state.g is not None:
            return 0
        state.g = self._full_grad(state.w)
        return 1

    # -- warm/screening source ----------------------------------------------
    def _warm_source(self, lam: float) -> Optional[_PathState]:
        """Nearest solved lambda ABOVE ``lam`` (the sequential rules'
        assumption); if the caller runs out of order and none exists, the
        largest solved lambda below — over-aggressive screening there is
        repaired by the KKT loop like any other over-screen."""
        above = [s for s in self._states if s.lam >= lam]
        if above:
            return min(above, key=lambda s: s.lam)
        if self._states:
            return max(self._states, key=lambda s: s.lam)
        return None

    def _probe(self):
        """First-lambda screening source: the data gradient at the start
        point, whose max penalized magnitude is lambda_max — the smallest
        L1 weight at which every penalized coordinate is zero. Computed
        once, lazily."""
        if self._init_probe is None:
            g0 = self._full_grad(self._w_init)
            lam1_max = float(np.max(np.abs(g0) * self._penalized))
            self._init_probe = (self._w_init, g0, lam1_max)
        return self._init_probe

    def lambda_max(self) -> float:
        """Max penalized |data gradient| at the start point: the L1
        weight above which the penalized solution is all-zero (grid
        construction helper)."""
        return self._probe()[2]

    def seed_state(self, lam: float, w) -> None:
        """Install a solved lambda's solution without re-solving (lambda-
        granular resume): the gradient is computed lazily on first use,
        so replayed-path candidate sets match the uninterrupted run's."""
        w = np.asarray(w, self._np_dtype)
        self._keep(_PathState(lam=float(lam),
                              lam_l1=self._reg.l1_weight(float(lam)),
                              w=w, g=None))

    def _keep(self, state: _PathState) -> None:
        if self._pc.keep_states:
            self._states.append(state)
        else:
            self._states = [state]

    def reset_states(self) -> None:
        """Drop every warm/screening state and the lambda_max probe but
        KEEP the compiled-kernel ladder (caches key on the objective
        identities, which don't change). A re-walked grid then repeats
        the exact screen/solve trajectory on warm kernels — how the
        bench separates compile time from compute (``bench.py path``)."""
        self._states = []
        self._init_probe = None
        self.total_iterations = 0

    # -- restricted problem construction -------------------------------------
    def _selection(self, member: np.ndarray):
        """(cols, lut) for a member mask, intercept pinned to restricted
        slot 0 so the restricted objective's static intercept index is a
        constant across buckets and rounds."""
        ii = self._objective.intercept_index
        cols = np.flatnonzero(member)
        if ii >= 0:
            cols = np.concatenate(([ii], cols[cols != ii]))
        lut = np.zeros((self._dim,), np.int32)
        lut[cols] = np.arange(cols.shape[0], dtype=np.int32)
        return cols, lut

    def _restrict_batch(self, member, lut, bucket):
        from photon_ml_tpu.types import LabeledBatch, SparseFeatures

        if self._h_dense is not None:
            cols, _ = self._selection(member)
            idx = np.full((bucket,), self._dim, np.int32)
            idx[: cols.shape[0]] = cols
            feats = self._gather_k(self._d_dense_z, jnp.asarray(idx))
        else:
            m = member[self._h_indices]
            idx = np.ascontiguousarray(
                np.where(m, lut[self._h_indices], 0), np.int32)
            ones = np.ones(self._h_indices.shape, self._np_dtype)
            vals = ones if self._h_values is None else self._h_values
            feats = SparseFeatures(
                indices=idx,
                values=np.where(m, vals, np.zeros((), self._np_dtype)),
                dim=bucket)
        return LabeledBatch(feats, self._h_labels, self._h_offsets,
                            self._h_weights)

    # -- solves ---------------------------------------------------------------
    def _resolve_opt(self, lam_l1: float) -> str:
        # the smooth optimizers cannot represent the L1 subgradient;
        # mirror fit_streaming's auto-switch for the in-memory path too
        opt = "lbfgs" if self._optimizer == "auto" else self._optimizer
        return "owlqn" if lam_l1 > 0 else opt

    def _solve_restricted(self, member, lut, bucket, w_warm, lam_l1,
                          lam_l2, run_cfg) -> OptimizationResult:
        cols, _ = self._selection(member)
        w0 = np.zeros((bucket,), self._np_dtype)
        w0[: cols.shape[0]] = w_warm[cols]
        opt = self._resolve_opt(lam_l1)
        if self._streaming:
            from photon_ml_tpu.parallel.streaming import fit_streaming

            rchunks = _RestrictedChunks(self._chunks, member, lut,
                                        self._np_dtype)
            return fit_streaming(
                self._robj, rchunks, bucket, w0, l2=lam_l2, config=run_cfg,
                dtype=self._dtype, mesh=self._mesh, axis=self._axis,
                optimizer=opt, l1=lam_l1,
                prefetch_depth=self._prefetch_depth)
        from photon_ml_tpu.parallel.data_parallel import fit_distributed

        rbatch = self._restrict_batch(member, lut, bucket)
        return fit_distributed(
            self._robj, rbatch, self._mesh, jnp.asarray(w0), l2=lam_l2,
            l1=lam_l1, optimizer=opt, config=run_cfg, axis=self._axis,
            sparse_grad=self._sparse_grad)

    def _solve_full(self, w_warm, lam_l1, lam_l2,
                    run_cfg) -> OptimizationResult:
        w0 = jnp.asarray(w_warm, self._np_dtype)
        opt = self._resolve_opt(lam_l1)
        if self._streaming:
            from photon_ml_tpu.parallel.streaming import fit_streaming

            return fit_streaming(
                self._objective, self._chunks, self._dim, w0, l2=lam_l2,
                config=run_cfg, dtype=self._dtype, mesh=self._mesh,
                axis=self._axis, optimizer=opt, l1=lam_l1,
                prefetch_depth=self._prefetch_depth)
        from photon_ml_tpu.parallel.data_parallel import fit_distributed

        return fit_distributed(
            self._objective, self._batch, self._mesh, w0, l2=lam_l2,
            l1=lam_l1, optimizer=opt, config=run_cfg, axis=self._axis,
            sparse_grad=self._sparse_grad, precomputed_csc=self._pcsc)

    # -- the per-lambda walk --------------------------------------------------
    def solve(self, reg_weight: float, tolerance: Optional[float] = None
              ) -> tuple:
        """Solve one lambda: screen from the warm source's certified
        gradient, solve the restricted problem on the bucket ladder, KKT-
        certify, repair and re-solve on violations (full-width fallback
        after ``max_kkt_rounds``). Returns ``(OptimizationResult,
        PathLambdaStats)``; the result's ``w`` is full-width and carries
        ``solver_tolerance`` and ``screened_dim``."""
        lam = float(reg_weight)
        lam_l1 = self._reg.l1_weight(lam)
        lam_l2 = self._reg.l2_weight(lam)
        tol = self._config.tolerance if tolerance is None else tolerance
        run_cfg = (self._config if tolerance is None
                   else dataclasses.replace(self._config,
                                            tolerance=tolerance))
        t0 = time.perf_counter()
        with obs_trace.span("glm.path_lambda", cat="train", lam=lam,
                            l1=lam_l1, l2=lam_l2,
                            rule=self._pc.screen) as sp:
            res, stats = self._solve_one(lam, lam_l1, lam_l2, run_cfg,
                                         float(tol))
            stats.solve_seconds = time.perf_counter() - t0
            sp.set(candidates=stats.candidate_size,
                   screened_dim=stats.screened_dim,
                   frozen=stats.features_frozen,
                   kkt_rounds=stats.kkt_rounds,
                   kkt_violations=stats.kkt_violations,
                   fallback=stats.fallback_full,
                   iterations=stats.solver_iterations)
        obs_metrics.training_metrics().record_path_lambda(
            frozen=stats.features_frozen, rounds=stats.kkt_rounds,
            violations=stats.kkt_violations,
            full_grad_passes=stats.full_grad_passes,
            fallback=stats.fallback_full)
        self.total_iterations = self.total_iterations \
            + stats.solver_iterations
        _log.info(
            "path lambda=%g rule=%s: candidates=%d/%d screened_dim=%d "
            "frozen=%d kkt_rounds=%d violations=%d iters=%d tol=%g "
            "fallback=%s", lam, stats.screen_rule, stats.candidate_size,
            stats.dim, stats.screened_dim, stats.features_frozen,
            stats.kkt_rounds, stats.kkt_violations,
            stats.solver_iterations, stats.solver_tolerance,
            stats.fallback_full)
        return res, stats

    def _solve_one(self, lam, lam_l1, lam_l2, run_cfg, tol):
        stats = PathLambdaStats(
            lam=lam, lam_l1=lam_l1, lam_l2=lam_l2, dim=self._dim,
            candidate_size=self._dim, screened_dim=self._dim,
            features_frozen=0, kkt_rounds=0, kkt_violations=0,
            solver_iterations=0, full_grad_passes=0, fallback_full=False,
            screen_rule=self._pc.screen, certified=False,
            solver_tolerance=tol, solve_seconds=0.0)

        src = self._warm_source(lam)
        if self._pc.screen == "off" or lam_l1 <= 0:
            # warm-started full-width fit: the pre-path behavior (also
            # the no-L1 case, where nothing is ever exactly zero and
            # there is nothing to screen). Trivially certified: the
            # solver's own convergence test covered every coordinate.
            w_warm = src.w if src is not None else self._w_init
            res = self._solve_full(w_warm, lam_l1, lam_l2, run_cfg)
            stats.kkt_rounds = 1
            stats.solver_iterations = int(res.iterations)
            stats.certified = True
            w_full = np.asarray(res.w)
            self._keep(_PathState(lam, lam_l1, w_full, None))
            return self._finish(res, w_full, self._dim, tol), stats

        if src is not None:
            stats.full_grad_passes = stats.full_grad_passes \
                + self._ensure_grad(src)
            w_prev, g_prev, lam_l1_prev = src.w, src.g, src.lam_l1
        else:
            w_prev, g_prev, lam1_max = self._probe()
            stats.full_grad_passes = stats.full_grad_passes + 1
            lam_l1_prev = max(lam1_max, lam_l1)

        thr = screening_threshold(self._pc.screen, lam_l1,
                                  max(lam_l1_prev, lam_l1),
                                  self._pc.screen_slack)
        member = (np.abs(g_prev) >= thr) | (w_prev != 0) | ~self._penalized
        stats.candidate_size = int(np.count_nonzero(member))

        w_full = np.asarray(w_prev, self._np_dtype).copy()
        res = None
        g_cert: Optional[np.ndarray] = None
        while True:
            stats.kkt_rounds = stats.kkt_rounds + 1
            n_sel = int(np.count_nonzero(member))
            bucket = pad_to_bucket(n_sel, self._pc.min_bucket)
            over_budget = stats.kkt_rounds > self._pc.max_kkt_rounds
            if bucket >= self._dim or over_budget:
                # nothing to gain from restriction (or the repair budget
                # is spent): full-width solve, certified by construction
                stats.fallback_full = over_budget
                res = self._solve_full(w_full, lam_l1, lam_l2, run_cfg)
                stats.solver_iterations = stats.solver_iterations \
                    + int(res.iterations)
                stats.screened_dim = self._dim
                stats.features_frozen = 0
                stats.certified = True
                w_full = np.asarray(res.w)
                g_cert = None  # next lambda recomputes lazily (one pass)
                break
            cols, lut = self._selection(member)
            res = self._solve_restricted(member, lut, bucket, w_full,
                                         lam_l1, lam_l2, run_cfg)
            stats.solver_iterations = stats.solver_iterations \
                + int(res.iterations)
            w_r = np.asarray(res.w)
            w_full = np.zeros((self._dim,), self._np_dtype)
            w_full[cols] = w_r[: cols.shape[0]]
            # certification: ONE full data pass; at screened (zero)
            # coordinates the elastic-net KKT condition is |g_j| <= l1
            g_cert = self._full_grad(w_full)
            stats.full_grad_passes = stats.full_grad_passes + 1
            slack = kkt_slack(lam_l1, self._pc.kkt_tol)
            viol = (~member) & (np.abs(g_cert) > lam_l1 + slack)
            nv = int(np.count_nonzero(viol))
            if nv == 0:
                stats.screened_dim = bucket
                stats.features_frozen = self._dim - n_sel
                stats.certified = True
                break
            stats.kkt_violations = stats.kkt_violations + nv
            member = member | viol

        self._keep(_PathState(lam, lam_l1, w_full, g_cert))
        return self._finish(res, w_full, stats.screened_dim, tol), stats

    def _finish(self, res: OptimizationResult, w_full: np.ndarray,
                screened_dim: int, tol: float) -> OptimizationResult:
        return res._replace(w=jnp.asarray(w_full),
                            solver_tolerance=float(tol),
                            screened_dim=int(screened_dim))

    # -- instrumentation ------------------------------------------------------
    def compiled_kernel_count(self) -> int:
        """Compiled executables across the full objective's cached
        kernels AND the shared restricted objective's bucket ladder — the
        bench's flat-compile gate: after the ladder warms, this number
        must not move."""
        from photon_ml_tpu.parallel.data_parallel import (
            compiled_kernel_count)

        return compiled_kernel_count(self._objective) \
            + compiled_kernel_count(self._robj)
