"""Data-parallel objective evaluation: the ``DistributedGLMLossFunction``
equivalent (SURVEY.md §3.2/§4.2; reference mount empty).

The reference broadcasts coefficients to executors and tree-aggregates
per-partition (loss, gradient) partials back to the driver each optimizer
iteration. Here the batch lives sharded over the mesh's ``data`` axis, the
coefficient vector is replicated, and a ``shard_map`` computes per-shard
partial sums joined by ``lax.psum`` over ICI — one XLA program, no host in
the loop. The entire optimizer (L-BFGS/TRON/OWL-QN ``while_loop``) jits
*around* this, so a whole fit is a single device computation.
"""

from __future__ import annotations

import functools
import time
from collections import OrderedDict
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P

from photon_ml_tpu.obs import metrics as obs_metrics
from photon_ml_tpu.obs import trace as obs_trace
from photon_ml_tpu.ops.losses import apply_weights, mask_margins
from photon_ml_tpu.ops.objective import GLMObjective
from photon_ml_tpu.optimize import OptimizerConfig, run_optimizer
from photon_ml_tpu.optimize.common import MarginOracle, OptimizationResult
from photon_ml_tpu.parallel.mesh import shard_batch
from photon_ml_tpu.types import (
    LabeledBatch,
    SparseFeatures,
    build_csc_transpose,
    csc_transpose_apply,
    margins as ell_margins,
    transpose_apply,
)


def distributed_value_and_grad(
    objective: GLMObjective, mesh: Mesh, axis: str = "data"
) -> Callable:
    """Returns fg(w, batch, l2) -> (value, grad) with batch rows sharded over
    ``axis``. The L2 term is added once globally (outside the psum), matching
    the single-device objective exactly."""

    @functools.partial(
        shard_map,
        mesh=mesh,
        in_specs=(P(), P(axis), P()),
        out_specs=(P(), P()),
    )
    def shard_fg(w, batch, l2):
        # Per-shard data term only; L2 added globally afterwards. Only the
        # value needs an explicit psum: under shard_map's varying-axis
        # tracking (check_vma), the AD transpose of "replicated w touches
        # sharded batch" inserts the gradient's all-reduce automatically —
        # psumming g again would multiply it by the axis size.
        with jax.named_scope("photon.glm/loss"):
            f, g = objective.value_and_grad(w, batch, 0.0)
        with jax.named_scope("photon.allreduce/value"):
            return lax.psum(f, axis), g

    def fg(w, batch, l2=0.0):
        l2 = jnp.asarray(l2, w.dtype)
        f, g = shard_fg(w, batch, l2)
        return _add_l2(objective, w, l2, f, g)

    return fg


@jax.named_scope("photon.glm/reg")
def _add_l2_value(objective, w, l2, f):
    wr = objective._reg_mask(w)
    return f + 0.5 * l2 * jnp.sum(wr * wr)


@jax.named_scope("photon.glm/reg")
def _add_l2_grad(objective, w, l2, g):
    return g + l2 * objective._reg_mask(w)


def _add_l2(objective, w, l2, f, g):
    return (_add_l2_value(objective, w, l2, f),
            _add_l2_grad(objective, w, l2, g))


def distributed_hvp(objective: GLMObjective, mesh: Mesh, axis: str = "data") -> Callable:
    """Returns hvp(w, v, batch, l2) sharded like distributed_value_and_grad.
    This is what the reference's HessianVectorAggregator treeAggregate does
    per CG step (SURVEY.md §4.2), as one on-device collective."""

    @functools.partial(
        shard_map,
        mesh=mesh,
        in_specs=(P(), P(), P(axis)),
        out_specs=P(),
    )
    def shard_hvp(w, v, batch):
        # Like the gradient, the HVP's all-reduce is inserted by the AD
        # transpose (w and v are replicated, batch varies over `axis`).
        grad_data = lambda x: objective.grad(x, batch, 0.0)
        with jax.named_scope("photon.glm/loss"):
            return jax.jvp(grad_data, (w,), (v,))[1]

    def hvp(w, v, batch, l2=0.0):
        l2 = jnp.asarray(l2, w.dtype)
        hv = shard_hvp(w, v, batch)
        vr = objective._reg_mask(v)
        return hv + l2 * vr

    return hvp


def distributed_diagonal_hessian(objective: GLMObjective, mesh: Mesh,
                                 axis: str = "data") -> Callable:
    """Returns diag(w, batch, l2) -> exact Hessian diagonal, rows sharded
    over ``axis`` — one data pass; feeds TRON's Jacobi preconditioner."""

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(P(), P(axis)),
        out_specs=P(),
    )
    def shard_diag(w, batch):
        d = objective.diagonal_hessian(w, batch, 0.0)
        with jax.named_scope("photon.allreduce/grad"):
            return lax.psum(d, axis)

    def diag(w, batch, l2=0.0):
        return _add_l2_diag(objective, shard_diag(w, batch), l2)

    return diag


@jax.named_scope("photon.glm/reg")
def _add_l2_diag(objective, d, l2):
    """The data term's Hessian diagonal plus the L2 term's (0 at an
    unregularized intercept)."""
    reg = jnp.full_like(d, jnp.asarray(l2, d.dtype))
    if not objective.regularize_intercept and objective.intercept_index >= 0:
        reg = reg.at[objective.intercept_index].set(0.0)
    return d + reg


# Jitted-runner cache: one jit wrapper per (objective, fit configuration),
# so repeated fits — regularization grids, bench warm-up + timed runs,
# calibration sweeps — reuse one compiled executable instead of re-tracing
# and RECOMPILING per call (a fresh ``jax.jit(lambda ...)`` every call made
# the round-2 bench time compile, not compute, and silently broke the
# "l2 is traced so a grid reuses one compilation" contract). Keyed by
# objective identity (objectives hold unhashable arrays) then by the
# hashable fit configuration; jit's own per-wrapper cache handles argument
# shapes/dtypes. The runners' closures strongly reference the objective,
# so entries hold it strongly too (identity stays valid) and growth is
# bounded by LRU eviction — evicting an entry drops its executables and
# its objective together.
_RUNNER_CACHE: "OrderedDict[int, tuple]" = OrderedDict()
_RUNNER_CACHE_MAX = 16


def _runner_cache_for(objective) -> dict:
    oid = id(objective)
    entry = _RUNNER_CACHE.get(oid)
    if entry is not None and entry[0] is objective:
        _RUNNER_CACHE.move_to_end(oid)
        return entry[1]
    runners: dict = {}
    _RUNNER_CACHE[oid] = (objective, runners)
    _RUNNER_CACHE.move_to_end(oid)
    while len(_RUNNER_CACHE) > _RUNNER_CACHE_MAX:
        _RUNNER_CACHE.popitem(last=False)
    return runners


def cached_jit(objective, key, make_fn, **jit_kwargs):
    """Get-or-create a jitted kernel in the objective's runner cache (the
    fit runners and the streaming chunk kernels share one cache policy).
    The program is named ``photon_<key[0]>`` — the key's own first word —
    so the profiler's ``XLA Modules`` line (``jit_photon_fit_tron``) and
    ``compiled_kernel_count`` speak of the same programs.
    ``jit_kwargs`` (e.g. ``donate_argnums``) apply only when the kernel is
    first built, so every caller of one key must pass the same ones."""
    cache = _runner_cache_for(objective)
    fn = cache.get(key)
    if fn is None:
        made = make_fn()
        made.__name__ = made.__qualname__ = f"photon_{key[0]}"
        fn = jax.jit(made, **jit_kwargs)
        cache[key] = fn
    return fn


def compiled_kernel_count(objective) -> int:
    """Total compiled-executable count across the objective's cached
    kernels (bench/test instrumentation: a count that stays flat across
    streamed passes proves the fixed-shape chunk contract held — no chunk
    retraced a kernel)."""
    total = 0
    for entry in _runner_cache_for(objective).values():
        for fn in (entry if isinstance(entry, (tuple, list)) else (entry,)):
            size = getattr(fn, "_cache_size", None)
            if callable(size):
                total += int(size())
    return total


@jax.named_scope("photon.allreduce/value")
def _psum_value(x, axis):
    return lax.psum(x, axis)


@jax.named_scope("photon.allreduce/grad")
def _psum_grad(x, axis):
    return lax.psum(x, axis)


def _eff_coeffs(norm, w):
    """Optimizer-space w -> (raw-space effective w, scalar margin adj)."""
    if norm is None:
        return w, jnp.zeros((), w.dtype)
    return norm.model_coefficients(w)


def _norm_fixed_fs(norm, dtype):
    """Normalization (factors, shifts) with the intercept slot pinned 1/0."""
    f = s = None
    if norm is not None and norm.factors is not None:
        f = norm.factors.astype(dtype)
        if norm.intercept_index >= 0:
            f = f.at[norm.intercept_index].set(1.0)
    if norm is not None and norm.shifts is not None:
        s = norm.shifts.astype(dtype)
        if norm.intercept_index >= 0:
            s = s.at[norm.intercept_index].set(0.0)
    return f, s


def _norm_chain_t(norm, gx, d_sum):
    """Raw-space Xᵀd (plus Σd) -> optimizer-space gradient."""
    if norm is None:
        return gx
    f, s = _norm_fixed_fs(norm, gx.dtype)
    if f is not None:
        gx = gx * f
    if s is not None:
        fs = s if f is None else f * s
        gx = gx - fs * d_sum
    return gx


def _weighted_loss(loss, weights, labels):
    """-> ``m -> Σ wᵢ l(mᵢ)``, the data term as a function of the margins
    (weight-0 rows masked out of the loss and out of its derivative)."""
    return lambda m: jnp.sum(apply_weights(
        weights, loss.loss(mask_margins(weights, m), labels)))


def _csc_apply(sparse_grad: str):
    """-> ``(apply_t, check_vma)`` of a CSC ``sparse_grad``: the function
    ``apply_t(csc, d) = Xᵀd`` and what ``shard_map`` is told around it.
    check_vma is off around the Pallas scan: the interpret-mode kernel body
    can't thread varying-axis types through pallas_call (the reductions
    are explicit psums, so nothing relies on vma-driven transposes)."""
    if sparse_grad == "csc_pallas":
        from photon_ml_tpu.ops.pallas_kernels import csc_transpose_apply_pallas

        return csc_transpose_apply_pallas, False
    return csc_transpose_apply, True


class CSCPath(NamedTuple):
    """What :func:`make_csc_path` returns (signatures in its docstring)."""

    build: Callable
    fg: Callable
    hvp: Callable
    curvature: Callable
    hvp_at: Callable
    diag_at: Callable
    value: Callable
    grad_at: Callable
    d2_at: Callable


def make_csc_path(objective: GLMObjective, mesh: Mesh, axis: str = "data",
                  use_pallas: bool = False) -> CSCPath:
    """Scatter-free sparse gradient path (see ``types.CSCTranspose``).

    ``build(batch)`` sorts each shard's nonzeros by column under
    ``shard_map`` (runs on device, once per jitted fit); ``fg(w, batch, csc,
    l2)`` / ``hvp(w, v, batch, csc, l2)`` evaluate the objective with
    explicit margin-space derivatives — forward is the ELL gather, backward
    is the CSC prefix-sum, reductions are explicit psums. Requires
    SparseFeatures.

    The second-order oracle comes in three pieces besides, so that a caller
    who holds an iterate over many products (TRON's CG solve) evaluates it
    there once: ``curvature(w, batch) -> d2``, the ``[rows]`` vector
    ``wᵢ l''(mᵢ)`` at ``m = X w_eff + offsets`` (one gather; rows stay
    sharded over ``axis``); ``hvp_at(d2, v, batch, csc, l2) = Xᵀ(d2 ⊙ X v)
    + l2 v`` (two gathers, ``X v`` and the transpose's); and ``diag_at(d2,
    csc, l2)``, the Hessian's exact diagonal ``Σᵢ d2ᵢ x'ᵢⱼ² + l2`` as
    transposes of ``d2`` through the sorted view — its values squared; with
    implicit ones the view itself — where
    ``GLMObjective.diagonal_hessian`` scatter-adds (one gather, one prefix
    sum: ``csc_transpose_apply``'s f32 cumsum under ``use_pallas`` too,
    since ``d2`` is all-positive). ``hvp`` is ``hvp_at`` of ``curvature``:
    one body.

    And the objective comes in halves that share a point's margins ``m = X
    w_eff + offsets + adjust`` (rows sharded over ``axis``), so that a caller
    who has evaluated a point reads its margins where it would gather them
    again (an optimizer's :class:`MarginOracle`): ``value(w, batch, l2) ->
    (f, m)`` (one gather), ``grad_at(w, m, batch, csc, l2) -> g`` (``w`` for
    the L2 term; the transpose's gather alone) and ``d2_at(m, batch) -> d2``
    (no gather). ``fg`` is ``grad_at`` of ``value``'s margins and
    ``curvature`` is ``d2_at`` of a point's margins: one body each.

    Normalization composes with the coefficient-space trick: margins use
    ``w_eff = f̃·w`` plus the scalar shift adjustment, and the transposed
    chain rule maps the raw-space contraction back to optimizer space as
    ``g = f̃ ⊙ (Xᵀd) − f̃ s̃ Σd`` (f̃/s̃ have the intercept slot pinned to
    1/0) — both linear, so they commute with the per-shard psum."""
    norm = objective.normalization
    apply_t, check_vma = _csc_apply("csc_pallas" if use_pallas else "csc")

    def _eff(w):
        return _eff_coeffs(norm, w)

    def _chain_t(gx, d_sum):
        return _norm_chain_t(norm, gx, d_sum)

    def build(batch: LabeledBatch):
        feats = batch.features
        if not isinstance(feats, SparseFeatures):
            raise ValueError("CSC path needs SparseFeatures")
        dim = feats.dim

        @functools.partial(
            shard_map, mesh=mesh, in_specs=(P(axis), P(axis)),
            out_specs=P(axis),
        )
        def _build(indices, values):
            # lead with a shard axis so P(axis) concatenation keeps each
            # shard's arrays intact ([n_shards, ...] leaves overall)
            return jax.tree.map(
                lambda a: a[None], build_csc_transpose(indices, values, dim))

        return _build(feats.indices, feats.values)

    def _margins_at(w, batch):
        w_eff, adjust = _eff(w)
        return ell_margins(batch.features, w_eff) + batch.offsets + adjust

    def _per_ex(batch):
        return _weighted_loss(objective.loss, batch.weights, batch.labels)

    def _d2(m, batch):
        with jax.named_scope("photon.glm/loss"):
            return apply_weights(batch.weights,
                                 objective.loss.d2(
                                     mask_margins(batch.weights, m),
                                     batch.labels))

    @functools.partial(
        shard_map, mesh=mesh, in_specs=(P(), P(axis)),
        out_specs=(P(), P(axis)),
    )
    def shard_value(w, batch):
        m = _margins_at(w, batch)
        with jax.named_scope("photon.glm/loss"):
            f = _per_ex(batch)(m)
        return _psum_value(f, axis), m

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(P(axis), P(axis), P(axis)),
        out_specs=P(),
        check_vma=check_vma,
    )
    def shard_grad_at(m, batch, csc_sh):
        with jax.named_scope("photon.glm/loss"):
            d = jax.grad(_per_ex(batch))(m)
        csc = jax.tree.map(lambda a: a[0], csc_sh)
        return _psum_grad(_chain_t(apply_t(csc, d), jnp.sum(d)), axis)

    @functools.partial(
        shard_map, mesh=mesh, in_specs=(P(), P(axis)), out_specs=P(axis),
    )
    def curvature(w, batch):
        return _d2(_margins_at(w, batch), batch)

    d2_at = shard_map(_d2, mesh=mesh, in_specs=(P(axis), P(axis)),
                      out_specs=P(axis))

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(P(axis), P(), P(axis), P(axis)),
        out_specs=P(),
        check_vma=check_vma,
    )
    def shard_hvp_at(d2, v, batch, csc_sh):
        # directional margin: the margin is linear in w, so the same
        # effective-coefficient map applies to v (no offset term)
        v_eff, v_adjust = _eff(v)
        mv = ell_margins(batch.features, v_eff) + v_adjust
        with jax.named_scope("photon.glm/loss"):
            dv = d2 * mv
        csc = jax.tree.map(lambda a: a[0], csc_sh)
        return _psum_grad(_chain_t(apply_t(csc, dv), jnp.sum(dv)), axis)

    @functools.partial(
        shard_map, mesh=mesh, in_specs=(P(axis), P(axis)), out_specs=P(),
    )
    def shard_diag_at(d2, csc_sh):
        # Always the blocked f32 cumsum, whatever ``apply_t`` is: d2 is
        # all-positive, and the Pallas scan's two MXU dots round their
        # inputs (the contributions, then the 128-lane row totals) to
        # bfloat16 on the chip. Sign-mixed d forgives that (row totals
        # stay small); under d2 = exp(m) a column that straddles a lane
        # row is off by 2^-9 of a row total that dwarfs it, diagonals come
        # out negative, and a Poisson fit refuses the steps that follow
        # (PERF.md section 7.10, measured). ~2 ms a call dearer at 40.9M.
        csc = jax.tree.map(lambda a: a[0], csc_sh)
        squares = (csc if csc.values is None  # 1^2 == 1
                   else csc.replace(values=csc.values ** 2))
        diag = csc_transpose_apply(squares, d2)
        # GLMObjective.diagonal_hessian's expansion of the (virtually)
        # normalized square: f² (Σ d2 x² − 2 s Σ d2 x + s² Σ d2)
        f, s = _norm_fixed_fs(norm, diag.dtype)
        if s is not None:
            diag = (diag - 2.0 * s * csc_transpose_apply(csc, d2)
                    + s * s * jnp.sum(d2))
        if f is not None:
            diag = diag * f * f
        return _psum_grad(diag, axis)

    def value(w, batch, l2=0.0):
        f, m = shard_value(w, batch)
        return _add_l2_value(objective, w, jnp.asarray(l2, w.dtype), f), m

    def grad_at(w, m, batch, csc, l2=0.0):
        g = shard_grad_at(m, batch, csc)
        return _add_l2_grad(objective, w, jnp.asarray(l2, w.dtype), g)

    def fg(w, batch, csc, l2=0.0):
        f, m = value(w, batch, l2)
        return f, grad_at(w, m, batch, csc, l2)

    def hvp_at(d2, v, batch, csc, l2=0.0):
        l2 = jnp.asarray(l2, v.dtype)
        hv = shard_hvp_at(d2, v, batch, csc)
        with jax.named_scope("photon.glm/reg"):
            return hv + l2 * objective._reg_mask(v)

    def hvp(w, v, batch, csc, l2=0.0):
        return hvp_at(curvature(w, batch), v, batch, csc, l2)

    def diag_at(d2, csc, l2=0.0):
        return _add_l2_diag(objective, shard_diag_at(d2, csc), l2)

    return CSCPath(build, fg, hvp, curvature, hvp_at, diag_at, value,
                   grad_at, d2_at)


# What "auto" resolves to where it was measured (PERF.md sections 5 and 7):
# on the v5e the CSC view with the Pallas per-tile scan, on a CPU XLA's
# scatter-add.
_SPARSE_GRAD_DEFAULT = {"cpu": "scatter", "tpu": "csc_pallas"}
_CSC_GRADS = ("csc", "csc_pallas")
_SPARSE_GRADS = ("auto", "scatter") + _CSC_GRADS
_sparse_grad_warned: set = set()


def uses_csc(sparse_grad: str) -> bool:
    """Whether a RESOLVED ``sparse_grad`` computes ``Xᵀd`` from the CSC view."""
    return sparse_grad in _CSC_GRADS


def resolve_sparse_grad(sparse_grad: str, features=None) -> str:
    """The one gate of ``sparse_grad``: rejects a name that is not one of
    "auto" | "scatter" | "csc" | "csc_pallas", and resolves "auto" to the
    measured per-platform default. Dense features always resolve "auto" to
    "scatter" (the csc paths are sparse-only; dense X^T d is a plain MXU
    matmul). Unmeasured platforms fall back to "scatter" with a one-line
    log, mirroring ``game.random_effect.resolve_re_optimizer`` — no silent
    cross-platform fallback."""
    if sparse_grad not in _SPARSE_GRADS:
        raise ValueError(f"unknown sparse_grad {sparse_grad!r}; one of "
                         f"{', '.join(map(repr, _SPARSE_GRADS))}")
    if sparse_grad != "auto":
        return sparse_grad
    if features is not None and not isinstance(features, SparseFeatures):
        return "scatter"
    platform = jax.devices()[0].platform
    choice = _SPARSE_GRAD_DEFAULT.get(platform, "scatter")
    if platform not in _SPARSE_GRAD_DEFAULT and platform not in _sparse_grad_warned:
        _sparse_grad_warned.add(platform)
        import logging

        logging.getLogger("photon_ml_tpu").info(
            "sparse_grad='auto' on platform %r -> %r (no benchmark cell has "
            "run there; pass sparse_grad='csc' or 'csc_pallas' to train "
            "through the CSC view)", platform, choice)
    return choice


def build_csc(objective: GLMObjective, batch: LabeledBatch, mesh: Mesh,
              axis: str = "data"):
    """Precompute the column-sorted (CSC) view of a sharded batch ONCE for
    reuse across fits (``fit_distributed(..., precomputed_csc=...)``) —
    regularization grids and repeated fits share one dataset, so the
    O(nnz log nnz) device sort should be paid per dataset, not per fit. The batch is padded/sharded exactly as
    ``fit_distributed`` will pad it, so the views line up."""
    with obs_trace.span("fit.build_csc", cat="train", rows=batch.num_examples,
              dim=batch.dim, chips=mesh.shape[axis]):
        batch = shard_batch(batch, mesh, axis)
        build = cached_jit(
            objective, ("build_csc", mesh, axis),
            lambda: make_csc_path(objective, mesh, axis).build)
        return build(batch)


def make_margin_path(objective: GLMObjective, mesh: Mesh, axis: str = "data",
                     transpose: str = "scatter"):
    """Margin-space primitives for :func:`optimize.lbfgs_margin.lbfgs_margin`.

    Returns ``(init_margin, dir_margin, loss_and_dir, make_data_grad)``:

    * ``init_margin(w, batch)`` — margins of the starting point, offsets and
      normalization adjust included (sharded [n]).
    * ``dir_margin(batch)(p)`` — the linear margin of a direction, no
      offsets (the per-iteration gather pass).
    * ``loss_and_dir(batch)(m, mp)`` — ``(Σ wᵢ l(mᵢ), Σ wᵢ l'(mᵢ) mpᵢ)``
      psummed to global scalars: the O(n) line-search trial evaluation.
    * ``make_data_grad(batch, csc)(m)`` — the data-term gradient from
      margins (the per-iteration transpose pass): XLA scatter-add when
      ``transpose='scatter'``/dense, or the column-sorted scatter-free
      apply when a prebuilt ``csc`` is given; normalization chain rule and
      the psum are applied inside.

    All reductions are explicit psums over ``axis`` so the optimizer runs
    entirely outside ``shard_map`` on replicated [d]-vectors.
    """
    norm = objective.normalization
    loss = objective.loss

    apply_t, check_vma = _csc_apply(transpose)  # read where a csc is given

    @functools.partial(
        shard_map, mesh=mesh, in_specs=(P(), P(axis)), out_specs=P(axis),
    )
    def s_margin(v_eff, feats):
        return ell_margins(feats, v_eff)

    def init_margin(w, batch):
        w_eff, adjust = _eff_coeffs(norm, w)
        return s_margin(w_eff, batch.features) + batch.offsets + adjust

    def dir_margin(batch):
        def f(p):
            p_eff, p_adjust = _eff_coeffs(norm, p)
            return s_margin(p_eff, batch.features) + p_adjust

        return f

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(P(axis), P(axis), P(axis), P(axis)),
        out_specs=(P(), P()),
    )
    def s_loss_and_dir(m, mp, labels, weights):
        per_ex = _weighted_loss(loss, weights, labels)
        with jax.named_scope("photon.glm/loss"):
            f, d1 = jax.value_and_grad(per_ex)(m)
            df = jnp.sum(d1 * mp)
        return _psum_value(f, axis), _psum_value(df, axis)

    def loss_and_dir(batch):
        return lambda m, mp: s_loss_and_dir(m, mp, batch.labels, batch.weights)

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(P(axis), P(axis), P(), P(axis), P(axis)),
        out_specs=(P(), P()),
    )
    def s_delta_and_dir(m, mp, alpha, labels, weights):
        """Line-search evaluation in DELTA space: sums the per-row loss
        DIFFERENCES l(m + a*mp) - l(m), which keeps relative accuracy in
        the delta itself. In f32 the total loss's resolution is eps*|f|
        (~5e-3 at the bench scale) — far coarser than the per-iteration
        improvements near convergence, so Wolfe tests on totals become
        coin flips and the fit stalls (observed: hard stop at 16/20 on
        TPU). The derivative is evaluated at the trial point as usual."""
        per_ex = _weighted_loss(loss, weights, labels)
        with jax.named_scope("photon.glm/loss"):
            mm0 = mask_margins(weights, m)
            m1 = m + alpha * mp
            d1 = jax.grad(per_ex)(m1)
            diffs = apply_weights(
                weights,
                loss.loss(mask_margins(weights, m1), labels)
                - loss.loss(mm0, labels))
            delta, df = jnp.sum(diffs), jnp.sum(d1 * mp)
        return _psum_value(delta, axis), _psum_value(df, axis)

    def delta_and_dir(batch):
        return lambda m, mp, alpha: s_delta_and_dir(
            m, mp, alpha, batch.labels, batch.weights)

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(P(axis), P(axis), P(axis), P(axis)),
        out_specs=P(),
    )
    def s_grad_scatter(m, feats, labels, weights):
        per_ex = _weighted_loss(loss, weights, labels)
        with jax.named_scope("photon.glm/loss"):
            d1 = jax.grad(per_ex)(m)
        g = _norm_chain_t(norm, transpose_apply(feats, d1), jnp.sum(d1))
        return _psum_grad(g, axis)

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(P(axis), P(axis), P(axis), P(axis)),
        out_specs=P(),
        check_vma=check_vma,
    )
    def s_grad_csc(m, labels, weights, csc_sh):
        per_ex = _weighted_loss(loss, weights, labels)
        with jax.named_scope("photon.glm/loss"):
            d1 = jax.grad(per_ex)(m)
        csc = jax.tree.map(lambda a: a[0], csc_sh)
        g = _norm_chain_t(norm, apply_t(csc, d1), jnp.sum(d1))
        return _psum_grad(g, axis)

    def make_data_grad(batch, csc=None):
        if csc is None:
            return lambda m: s_grad_scatter(
                m, batch.features, batch.labels, batch.weights)
        return lambda m: s_grad_csc(
            m, batch.labels, batch.weights, csc)

    return (init_margin, dir_margin, loss_and_dir, make_data_grad,
            delta_and_dir)


def _margin_fit(objective, mesh, axis, config, transpose, precomputed):
    """-> (cache key, maker of the program ``run(w0, b, l2v, csc)``): the
    L-BFGS fit with the margin-space line search, 2 data passes per
    iteration (one gather, one transpose) regardless of line-search
    effort. ``transpose`` is a resolved ``sparse_grad``; the csc ones sort
    the nonzeros once (inside the jit but OUTSIDE the optimizer loop), or
    take a precomputed view."""
    use_csc = uses_csc(transpose)
    key = ("fit_lbfgs_margin", mesh, axis, transpose, config, precomputed)

    def make():
        from photon_ml_tpu.optimize.lbfgs_margin import lbfgs_margin

        (init_margin, dir_margin, loss_and_dir, make_data_grad,
         delta_and_dir) = \
            make_margin_path(objective, mesh, axis, transpose=transpose)
        reg_mask = objective._reg_mask
        build = None
        if use_csc and not precomputed:
            build = make_csc_path(objective, mesh, axis).build

        def run(w0, b, l2v, csc):
            if use_csc and csc is None:
                csc = build(b)
            m0 = init_margin(w0, b)
            return lbfgs_margin(
                dir_margin(b), loss_and_dir(b), make_data_grad(b, csc),
                reg_mask, w0, m0, l2v, config,
                loss_delta_and_dir=delta_and_dir(b),
            )

        return run

    return key, make


def _black_box_fit(objective, mesh, axis, optimizer, config, sparse_grad,
                   precomputed):
    """-> (key, maker) of the fit that hands the optimizer a black-box
    objective: the program ``run(w0, b, l2v, l1v, csc)``. Its one fork is
    where the oracle comes from: autodiff over the sharded objective
    (XLA's scatter-add), or :func:`make_csc_path` — then ONE program sorts
    the shard's nonzeros by column (or takes the view :func:`build_csc`
    made) and runs the whole optimizer loop against the sorted view, so
    the sort amortizes over every iteration (and over every fit when
    precomputed). ``l1v`` is None for every optimizer but OWL-QN, ``csc``
    is None unless precomputed: neither is an operand then."""
    use_csc = uses_csc(sparse_grad)
    key = (f"fit_{optimizer}", mesh, axis, config, sparse_grad, precomputed)

    def make():
        if use_csc:
            path = make_csc_path(objective, mesh, axis,
                                 use_pallas=(sparse_grad == "csc_pallas"))
        else:
            fg = distributed_value_and_grad(objective, mesh, axis)
            hvp = distributed_hvp(objective, mesh, axis)
            diag = distributed_diagonal_hessian(objective, mesh, axis)
        mask_int = (objective.intercept_index
                    if (objective.intercept_index >= 0
                        and not objective.regularize_intercept) else -1)

        def run(w0, b, l2v, l1v, csc):
            # L1 intercept mask (consistent with the L2 mask) is
            # shape-dependent: derive from the traced w0 so the cached
            # runner serves any dimension
            l1_mask = (None if l1v is None or mask_int < 0
                       else jnp.ones_like(w0).at[mask_int].set(0.0))
            # TRON's second-order oracle. The Jacobi preconditioner buys
            # fewer CG passes (each CG step is a full pass) for a diagonal
            # an outer iteration. With the sorted view in hand OWL-QN and
            # TRON evaluate through the halves that share a point's
            # margins: OWL-QN's gradient at a search's accepted point and
            # TRON's curvature d2 at an accepted trial point read the
            # margins that point's value gathered, and TRON carries d2:
            # every HVP of a CG solve and the diagonal (a transpose of d2
            # through the view, no scatter-add) read that one vector.
            # Without a view each recomputes the margins at the w it is
            # handed.
            if use_csc:
                if csc is None:
                    csc = path.build(b)
                fg_at = lambda w: path.fg(w, b, csc, l2v)
                second_order = dict(
                    margins=MarginOracle(
                        value=lambda w: path.value(w, b, l2v),
                        grad=lambda w, m: path.grad_at(w, m, b, csc, l2v),
                        curvature=lambda m: path.d2_at(m, b)),
                    hvp=lambda d2, v: path.hvp_at(d2, v, b, csc, l2v),
                    precond=lambda d2: path.diag_at(d2, csc, l2v))
            else:
                fg_at = lambda w: fg(w, b, l2v)
                second_order = dict(
                    hvp=lambda w, v: hvp(w, v, b, l2v),
                    precond=lambda w: diag(w, b, l2v))
            return run_optimizer(optimizer, fg_at, w0, config, l1=l1v,
                                 l1_mask=l1_mask, **second_order)

        return run

    return key, make


def fit_distributed(
    objective: GLMObjective,
    batch: LabeledBatch,
    mesh: Mesh,
    w0: jax.Array,
    l2=0.0,
    l1=0.0,
    optimizer: str = "lbfgs",
    config: OptimizerConfig = OptimizerConfig(),
    axis: str = "data",
    sparse_grad: str = "auto",
    line_search: str = "margin",
    precomputed_csc=None,
) -> OptimizationResult:
    """Shard the batch over the mesh and run a full jitted fit — the
    ``DistributedOptimizationProblem.run`` equivalent (SURVEY.md §3.2).

    ``sparse_grad``, how ``X^T d`` is computed — anything else raises
    (``resolve_sparse_grad``): "auto" (default: by platform, the CSC view
    with the Pallas scan on a TPU, XLA's scatter-add on a CPU), "scatter"
    (XLA scatter-add via the autodiff transpose), "csc" (scatter-free
    column-sorted gradients — see ``make_csc_path``; sorts once per fit on
    device, or takes ``precomputed_csc``), "csc_pallas" (the same view, its
    multiply and per-tile prefix sums in one Pallas kernel).

    ``line_search``: "margin" (default, L-BFGS only) runs the strong-Wolfe
    search on cached margin vectors — O(n) per trial, two O(nnz) passes per
    iteration total (see ``optimize.lbfgs_margin``); "full" evaluates the
    black-box objective at every trial (the round-2 behavior, kept for
    parity testing and as the TRON/OWL-QN path).

    ``precomputed_csc``: reuse a ``build_csc(batch, mesh)`` result across
    fits on the same dataset (regularization grids) so the per-dataset
    column sort is paid once, not per fit.

    The call returns once the program is dispatched. It leaves a ``fit``
    span (with ``fit.shard_batch`` and ``fit.dispatch`` under it) and one
    record in ``obs.metrics.training_metrics()`` (``record_fit``): the
    result's pass and product counters stay on the device until that
    record is read."""
    t_entry = time.perf_counter()
    sparse_grad = resolve_sparse_grad(sparse_grad, batch.features)
    margin = optimizer == "lbfgs" and line_search == "margin"
    if precomputed_csc is not None and not uses_csc(sparse_grad):
        raise ValueError(
            f"precomputed_csc given but sparse_grad={sparse_grad!r} does "
            "not use it; pass sparse_grad='csc' (or 'csc_pallas')")
    precomputed = precomputed_csc is not None
    if margin:
        key, make = _margin_fit(objective, mesh, axis, config, sparse_grad,
                                precomputed)
        args = (precomputed_csc,)
    else:
        key, make = _black_box_fit(objective, mesh, axis, optimizer, config,
                                   sparse_grad, precomputed)
        # only OWL-QN's program reads l1: no other has the operand
        args = (l1 if optimizer == "owlqn" else None, precomputed_csc)
    compiled = key not in _runner_cache_for(objective)
    with obs_trace.span("fit", cat="train", optimizer=optimizer,
              sparse_grad=sparse_grad, rows=batch.num_examples,
              dim=batch.dim, chips=mesh.shape[axis], compiled=compiled):
        with obs_trace.span("fit.shard_batch", cat="train"):
            batch = shard_batch(batch, mesh, axis)
        run = cached_jit(objective, key, make)
        with obs_trace.span("fit.dispatch", cat="train"):
            res = run(w0, batch, l2, *args)
    # a call traced inside a caller's jit (an export, a compile from shapes)
    # ran no fit: its counters are tracers, which the ring would hold past
    # their trace and fail on 64 fits later
    if not isinstance(res.iterations, jax.core.Tracer):
        obs_metrics.training_metrics().record_fit(
            optimizer=optimizer, sparse_grad=sparse_grad, compiled=compiled,
            dispatch_s=time.perf_counter() - t_entry, result=res)
    return res
