"""Shared optimizer machinery: config, convergence, state tracking.

Equivalent of the reference's abstract ``optimization.Optimizer`` +
``OptimizationStatesTracker`` (SURVEY.md §3.1; reference mount empty):
convergence on relative-loss change and normalized gradient norm with a max
iteration cap, and a per-iteration (loss, gradient-norm) history. The tracker
here is a pair of fixed-length device arrays filled inside the jitted
``lax.while_loop`` — readable after the fact without host round-trips per
iteration.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
from jax import lax


@dataclasses.dataclass(frozen=True)
class OptimizerConfig:
    """Mirrors the reference's per-coordinate optimizer config surface
    (optimizer type, max iters, tolerance — SURVEY.md §5.6)."""

    max_iters: int = 100
    tolerance: float = 1e-7
    # L-BFGS/OWL-QN history length (Breeze default is 10 ranks).
    history: int = 10
    # line-search evaluation cap per iteration
    max_line_search_steps: int = 25


@dataclasses.dataclass(frozen=True)
class ToleranceSchedule:
    """Inexact-outer-loop solver tolerance schedule (the standard trick in
    distributed block-coordinate methods: early sweeps don't need exact
    inner solves because the other blocks will move anyway — arxiv
    1611.02101 / 1803.06333). ``at(step, final_tol)`` starts at ``start``
    and tightens geometrically by ``decay`` per outer step, clamped from
    below at the caller's final tolerance; once the schedule reaches the
    final tolerance it stays there, so the set of distinct tolerances (and
    therefore of solver compilations keyed on them) is bounded by
    ``log(start/final) / log(1/decay)`` + 1."""

    start: float = 1e-3
    decay: float = 0.1

    def __post_init__(self):
        import math

        if not (math.isfinite(self.start) and self.start > 0):
            raise ValueError(f"schedule start must be finite and > 0, "
                             f"got {self.start}")
        if not (0 < self.decay < 1):
            raise ValueError(f"schedule decay must be in (0, 1), "
                             f"got {self.decay}")

    def at(self, step: int, final_tol: float) -> float:
        if final_tol <= 0:
            # an explicit tol <= 0 disables convergence tests entirely
            # (pinned iteration counts); a schedule must not re-enable them
            return final_tol
        return max(float(final_tol), self.start * self.decay ** max(step, 0))


@dataclasses.dataclass(frozen=True)
class PathConfig:
    """Pathwise fixed-effect solver knobs (``optimize.path.PathSolver``) —
    rides alongside :class:`OptimizerConfig` the way the reference's
    per-coordinate optimizer config rides alongside its training config.

    ``screen``: ``"strong"`` (sequential strong rule — aggressive,
    occasionally over-screens, always KKT-repaired), ``"safe"`` (double
    the strong rule's guard band — keeps marginal features on correlated
    designs, fewer repair rounds), or ``"off"`` (warm-started full-feature
    fits; the pre-path behavior). ``kkt_tol`` is the relative slack on the
    L1 weight in the violation test ``|g_j| > l1 + kkt_tol*max(l1, 1)``
    for screened coordinates. ``max_kkt_rounds`` bounds the
    screen→solve→check repair loop before falling back to a full-feature
    solve (which is trivially certified). ``min_bucket`` floors the
    power-of-two restricted width so tiny candidate sets don't mint
    single-use compilations. ``screen_slack`` inflates the screening
    threshold by ``slack * (l1_prev - l1)`` — 0 is the published rules;
    positive values deliberately over-screen (the KKT-repair adversarial
    tests and aggressiveness tuning use it). ``keep_states`` retains one
    (lambda, w, gradient) snapshot per solved lambda so out-of-order
    solves (the GP tuner) warm-start from the nearest solved neighbor;
    costs 2 * dim * 8 bytes per lambda."""

    screen: str = "strong"
    kkt_tol: float = 1e-6
    max_kkt_rounds: int = 5
    min_bucket: int = 64
    screen_slack: float = 0.0
    keep_states: bool = True

    def __post_init__(self):
        if self.screen not in ("strong", "safe", "off"):
            raise ValueError(f"screen must be strong|safe|off, "
                             f"got {self.screen!r}")
        if not (self.kkt_tol >= 0):
            raise ValueError(f"kkt_tol must be >= 0, got {self.kkt_tol}")
        if self.max_kkt_rounds < 1:
            raise ValueError(f"max_kkt_rounds must be >= 1, "
                             f"got {self.max_kkt_rounds}")
        if self.min_bucket < 1:
            raise ValueError(f"min_bucket must be >= 1, "
                             f"got {self.min_bucket}")


def parse_tolerance_schedule(spec: str) -> "ToleranceSchedule | None":
    """Parse a ``START:DECAY`` CLI spec (e.g. ``1e-3:0.1``) into a
    :class:`ToleranceSchedule`; ``off``/``none`` disable it. Raises
    ``ValueError`` with a usable message on anything malformed."""
    s = spec.strip().lower()
    if s in ("off", "none", ""):
        return None
    parts = s.split(":")
    if len(parts) != 2:
        raise ValueError(
            f"expected START:DECAY (e.g. 1e-3:0.1) or 'off', got {spec!r}")
    try:
        start, decay = float(parts[0]), float(parts[1])
    except ValueError:
        raise ValueError(
            f"expected numeric START:DECAY, got {spec!r}") from None
    return ToleranceSchedule(start, decay)


class OptimizationResult(NamedTuple):
    """Final point + convergence record (OptimizationStatesTracker role)."""

    w: jax.Array
    value: jax.Array
    grad_norm: jax.Array
    iterations: jax.Array  # i32 scalar
    converged: jax.Array  # bool scalar
    loss_history: jax.Array  # [max_iters] padded with NaN past `iterations`
    grad_norm_history: jax.Array  # [max_iters] same padding
    # streamed fits only: host-side pipeline stall accounting for the whole
    # fit (parallel/streaming.StreamStats.as_dict() — decode-wait /
    # transfer / compute-stall seconds, chunk and pass counts). None for
    # in-memory fits; never touched inside jit.
    stream_stats: "dict | None" = None
    # Restricted-problem geometry, attached HOST-SIDE after the solve
    # (never inside jit): the tolerance this fit actually converged
    # against and the width of the problem it was solved over (the
    # screened/bucketed dimension for pathwise fits, the full feature
    # dim otherwise). Logs, BENCH_path.json and the resume marker assert
    # the geometry, not just the outcome.
    solver_tolerance: "float | None" = None
    screened_dim: "int | None" = None
    # Data products the fit ran, counted inside the optimizer's
    # ``while_loop`` (i32 scalars): every ``X v`` (margins of a point or
    # of a direction, the first half of an HVP) and every ``X^T d`` (a
    # data gradient, the second half of an HVP; not the Jacobi diagonal).
    # None for the host-driven streamed fits (``stream_stats`` counts
    # their passes).
    gather_products: "jax.Array | None" = None
    transpose_products: "jax.Array | None" = None
    # OWL-QN only, counted on the device like the products (i32 scalars):
    # the trial points its backtracking searches evaluated over the whole
    # fit, and the coefficients of the final ``w`` that are not exactly
    # zero. None from the other optimizers.
    line_search_trials: "jax.Array | None" = None
    nonzeros: "jax.Array | None" = None
    # TRON only, counted on the device (i32 scalars): the CG steps of all
    # its outer iterations (one HVP each), the iterations whose trial
    # point was refused (``w`` kept, the radius shrunk), the Jacobi
    # diagonals computed (the starting point's and one a step that is
    # accepted and followed by another iteration; 0 without a
    # preconditioner) and the evaluations of a :class:`MarginOracle`'s
    # ``curvature`` (at the same points; 0 without one: ``cg_steps /
    # curvature_passes`` HVPs shared each). None from the other optimizers.
    cg_steps: "jax.Array | None" = None
    rejected_steps: "jax.Array | None" = None
    precond_passes: "jax.Array | None" = None
    curvature_passes: "jax.Array | None" = None
    # OWL-QN and TRON, counted on the device (i32 scalar): the evaluations
    # that read the margins of a point the fit had already evaluated
    # instead of gathering them (a :class:`MarginOracle`'s): OWL-QN's
    # gradient at a search's accepted point, one a pass, and TRON's
    # curvature at an accepted trial point, one a renewal. 0 from either
    # without such an oracle; None from the other optimizers.
    margins_reused: "jax.Array | None" = None


class MarginOracle(NamedTuple):
    """A smooth objective in halves that share the margins ``m = X w`` of
    a point (``parallel.data_parallel.make_csc_path`` builds one over the
    sorted view): ``value(w) -> (f, m)`` gathers them, ``grad(w, m) -> g``
    and ``curvature(m) -> c`` (the linearization an ``hvp(c, v)`` and a
    ``precond(c)`` read) take them instead of gathering them again.
    ``value`` then ``grad`` at one ``w`` is the same arithmetic as the
    objective's ``fun_and_grad(w)``. ``m`` is whatever pytree ``value``
    hands back: ``w`` itself makes ``curvature`` a function of the point."""

    value: Callable
    grad: Callable
    curvature: Callable


def converged_check(f_prev, f, g_norm, g0_norm, tol, f_scale=None):
    """Reference-style stopping rule: relative loss change below tol OR
    gradient norm below tol * max(1, ||g0||). A positive tolerance is
    clamped to a few ulps of the working dtype so a tol tuned for f64
    (e.g. 1e-9) still terminates in f32/bf16 instead of spinning to
    max_iters. An explicit tol <= 0 is honored exactly — it disables both
    tests, pinning the iteration count at max_iters (bench determinism:
    round 2's f32 run silently stopped at 15/20 "pinned" iterations
    because the clamp re-enabled the relative-loss test).

    ``f_scale``: override for the relative-test scale. Delta-space
    callers pass the accurately-summed improvement as ``f_prev=0,
    f=-delta`` (so the difference is exact, not a rounding artifact of
    two large totals) with ``f_scale`` = the current loss value."""
    dtype = jnp.asarray(f).dtype
    eps = jnp.finfo(dtype).eps
    tol = jnp.asarray(tol, dtype)
    tol = jnp.where(tol > 0, jnp.maximum(tol, 4 * eps), tol)
    scale = jnp.abs(f_prev if f_scale is None else f_scale)
    rel_loss = jnp.abs(f_prev - f) <= tol * jnp.maximum(scale, 1.0)
    grad_small = g_norm <= tol * jnp.maximum(g0_norm, 1.0)
    return (tol > 0) & (rel_loss | grad_small)


def grad_converged(g_norm, g0_norm, tol):
    """The gradient-norm half of :func:`converged_check` alone (same tol
    clamping). Used when a failed line search invalidates the relative-
    loss test (f unchanged -> zero delta would pass spuriously) but the
    gradient test remains meaningful — a search that fails AT the optimum
    must still report convergence."""
    dtype = jnp.asarray(g_norm).dtype
    eps = jnp.finfo(dtype).eps
    tol = jnp.asarray(tol, dtype)
    tol = jnp.where(tol > 0, jnp.maximum(tol, 4 * eps), tol)
    return (tol > 0) & (g_norm <= tol * jnp.maximum(g0_norm, 1.0))


def init_history(max_iters: int, dtype) -> tuple[jax.Array, jax.Array]:
    nan = jnp.full((max_iters,), jnp.nan, dtype)
    return nan, nan


def l2_norm(a):
    return jnp.sqrt(jnp.sum(a * a))


# The (s, y) pair history of L-BFGS / OWL-QN: ``m`` slots of ``[d]``
# vectors, each read whole by a dynamic index. It is ONE flat array of
# ``m * stride`` elements, slot ``j`` at ``[j * stride, j * stride + d)``,
# so a slot is contiguous in memory. As ``[m, d]`` the TPU tiles the two
# minor dimensions (8, 128): the slot index lands on the sublanes, a slot
# is one row in eight of every tile, and reading or writing one moves
# eight (PERF.md section 6, PR 35: 0.85 ms a slot read at d = 2^24 where
# a contiguous one rides inside its consumer). The three functions below
# are the only code that knows the layout.

# a 1-D f32 array lies in HBM in tiles of 1024 elements (``T(1024)``): a
# slot that starts on a tile boundary is sliced without a shifting copy
_HISTORY_TILE = 1024


def _history_stride(d: int) -> int:
    """Elements from one slot's start to the next: ``d`` filled up to whole
    tiles where that costs under an eighth of a slot, ``d`` itself below
    (the random effects' vmapped solves hold a history of ``d`` in the
    tens per entity: a tile a slot would be most of their memory)."""
    if d < 8 * _HISTORY_TILE:
        return d
    return -(-d // _HISTORY_TILE) * _HISTORY_TILE


def history_zeros(m: int, d: int, dtype) -> jax.Array:
    """An empty history of ``m`` slots of ``[d]`` vectors."""
    return jnp.zeros((m * _history_stride(d),), dtype)


def history_slot(hist: jax.Array, j, d: int) -> jax.Array:
    """Slot ``j`` of ``hist`` as a ``[d]`` vector."""
    return lax.dynamic_slice(hist, (j * _history_stride(d),), (d,))


def history_store(hist: jax.Array, j, v: jax.Array, store=None) -> jax.Array:
    """``hist`` with ``v`` in slot ``j`` — where ``store`` (a traced bool;
    None: always) says so, else as it was. Only the slot is read and
    written, so inside a loop carry the update is in place."""
    (d,) = v.shape
    if store is not None:
        v = jnp.where(store, v, history_slot(hist, j, d))
    return lax.dynamic_update_slice(hist, v, (j * _history_stride(d),))


def match_vma(x, ref):
    """Give ``x`` the varying-manual-axes (vma) type of ``ref``.

    Inside ``shard_map`` (manual mode), freshly created constants (zeros,
    counters, False flags) are "unvarying" while values derived from sharded
    inputs are "varying over the mesh axis"; ``lax.while_loop`` requires carry
    input/output types to match exactly, so optimizer loop state initialized
    from constants must be cast to the gradient's vma. Outside shard_map this
    is a no-op."""
    vma = frozenset(getattr(jax.typeof(ref), "vma", frozenset()))
    cur = frozenset(getattr(jax.typeof(x), "vma", frozenset()))
    missing = tuple(sorted(vma - cur))
    if missing:
        x = jax.lax.pcast(x, missing, to="varying")
    return x


def match_vma_tree(tree, ref):
    return jax.tree.map(lambda x: match_vma(x, ref), tree)
