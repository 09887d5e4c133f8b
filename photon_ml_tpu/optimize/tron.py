"""Jitted TRON: trust-region Newton with a conjugate-gradient inner loop.

Equivalent of the reference's own ``optimization.TRON`` implementation (from
LIBLINEAR's algorithm, Lin & Moré — SURVEY.md §3.1; reference mount empty).
The decisive TPU difference (SURVEY.md §4.2): the reference pays one full
cluster ``treeAggregate`` per CG step for each Hessian-vector product; here an
HVP is forward-over-reverse autodiff inside the same XLA program — roughly two
fused gradient passes, with any cross-device reduction riding ICI.

The second-order oracle is evaluated at an iterate ONCE. A caller whose
Hessian is ``X^T diag(d2(w)) X + ...`` hands :func:`tron` its objective in
halves that share a point's margins ``m = X w`` (a
:class:`~photon_ml_tpu.optimize.common.MarginOracle`), whose ``curvature(m)
-> c`` linearizes it there (any pytree: the ``[rows]`` vector ``d2`` for a
GLM), and an ``hvp`` and a ``precond`` that read ``c`` where they would read
``w``: ``c`` is carried in the loop state beside the Jacobi diagonal,
recomputed only where a step is accepted and another CG solve will follow,
from the margins the accepted trial point's evaluation gathered, so every
CG step of a solve and the diagonal share one evaluation of it and none
gathers again. Without the oracle both are handed ``w`` itself and
recompute what they need.
"""

from __future__ import annotations

from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from photon_ml_tpu.optimize.common import (
    MarginOracle,
    OptimizationResult,
    OptimizerConfig,
    converged_check,
    init_history,
    l2_norm,
    match_vma_tree,
)

# Lin-Moré / LIBLINEAR constants
_ETA0, _ETA1, _ETA2 = 1e-4, 0.25, 0.75
_SIGMA1, _SIGMA2, _SIGMA3 = 0.25, 0.5, 4.0


class _CGState(NamedTuple):
    s: jax.Array
    r: jax.Array
    d: jax.Array
    rz: jax.Array  # r . M^{-1} r (== r.r when unpreconditioned)
    i: jax.Array
    done: jax.Array


@jax.named_scope("photon.tron/cg")
def _steihaug_cg(hvp: Callable, g: jax.Array, delta, cg_tol, max_cg: int,
                 m_diag: jax.Array | None = None):
    """Approximately minimize q(s) = g.s + 0.5 s.H.s within a trust region.

    ``m_diag``: optional Jacobi preconditioner, the (positive) diagonal of
    an approximation to H. Each CG step costs one HVP — for the
    distributed/streamed fits that is a FULL pass over the data, so fewer
    CG steps is a direct data-pass saving on badly-scaled problems (sparse
    features with wildly different counts). Preconditioned Steihaug
    measures the trust region in the M-norm (LIBLINEAR's newer TRON does
    the same); with ``m_diag=None`` every M-product degenerates to the
    plain Euclidean form and the iteration is identical to classic
    Steihaug. The residual invariant r == -(g + H s) holds either way, so
    the caller's ``prered`` formula is unchanged."""
    if m_diag is None:
        minv = None
        mdot = lambda a, b: jnp.sum(a * b)
        prec = lambda r: r
    else:
        minv = 1.0 / m_diag
        mdot = lambda a, b: jnp.sum(a * m_diag * b)
        prec = lambda r: minv * r

    def boundary_tau(s, d):
        sd = mdot(s, d)
        dd = mdot(d, d)
        ss = mdot(s, s)
        disc = jnp.sqrt(jnp.maximum(sd * sd + dd * (delta * delta - ss), 0.0))
        return (-sd + disc) / jnp.maximum(dd, jnp.finfo(d.dtype).tiny)

    def body(st: _CGState) -> _CGState:
        Hd = hvp(st.d)
        dHd = jnp.sum(st.d * Hd)
        neg_curv = dHd <= 0
        alpha = st.rz / jnp.where(neg_curv, 1.0, dHd)
        outside = jnp.sqrt(mdot(st.s + alpha * st.d,
                                st.s + alpha * st.d)) >= delta
        hit = neg_curv | outside
        # one uniform update keeps r == -(g + H s) exact even on the
        # boundary step, so the caller can form prered from (s, r) alone
        step = jnp.where(hit, boundary_tau(st.s, st.d), alpha)
        s_new = st.s + step * st.d
        r_new = st.r - step * Hd
        z_new = prec(r_new)
        rz_new = jnp.sum(r_new * z_new)
        beta = rz_new / jnp.maximum(st.rz, jnp.finfo(st.rz.dtype).tiny)
        d_new = z_new + beta * st.d
        done = hit | (l2_norm(r_new) <= cg_tol)
        return _CGState(s_new, r_new, d_new, rz_new, st.i + 1, done)

    def cond(st: _CGState):
        return (~st.done) & (st.i < max_cg)

    r0 = -g
    z0 = prec(r0)
    init = _CGState(jnp.zeros_like(g), r0, z0, jnp.sum(r0 * z0),
                    jnp.asarray(0), jnp.asarray(False))
    st = lax.while_loop(cond, body, match_vma_tree(init, g))
    return st.s, st.r, st.i


class _State(NamedTuple):
    it: jax.Array
    w: jax.Array
    f: jax.Array
    g: jax.Array
    delta: jax.Array
    c: object  # the curvature of the kept w's margins (() without them)
    m_diag: jax.Array  # cached preconditioner diag ([0] when unused)
    converged: jax.Array
    stalled: jax.Array
    loss_hist: jax.Array
    gnorm_hist: jax.Array
    n_products: jax.Array  # i32: evaluations + HVPs (one X v + X^T d each)
    cg_steps: jax.Array  # i32: CG steps (one HVP each) over all iterations
    rejected_steps: jax.Array  # i32: iterations whose trial point was refused
    precond_passes: jax.Array  # i32: Jacobi diagonals computed, m0 included
    curvature_passes: jax.Array  # i32: curvature(m) evaluations, w0's included


def tron(
    fun_and_grad: Callable,
    w0: jax.Array,
    config: OptimizerConfig = OptimizerConfig(),
    hvp: Callable | None = None,
    max_cg_iters: int | None = None,
    precond: Callable | None = None,
    margins: MarginOracle | None = None,
) -> OptimizationResult:
    """Minimize fun(w). ``hvp(w, v)`` defaults to forward-over-reverse autodiff
    of the gradient part of ``fun_and_grad``. ``precond(w)`` optionally
    returns the Hessian diagonal at w (one extra data pass per OUTER
    iteration) for Jacobi-preconditioned CG — fewer inner HVP passes on
    badly-scaled problems. ``margins`` optionally evaluates the same
    objective in halves that share a point's margins ``m``: every
    evaluation then goes through it, and its ``curvature(m) -> c``
    linearizes the objective at an iterate: ``hvp(c, v)`` and
    ``precond(c)`` are then called with it in ``w``'s place (an explicit
    ``hvp`` is required). The curvature and the diagonal are those of the
    kept ``w``: computed at ``w0`` and after a step that is accepted AND
    followed by another iteration, from that trial's margins — a refused
    step keeps them, and the last iterate's, which no CG solve would read,
    are never computed."""
    dtype = w0.dtype
    if margins is not None and hvp is None:
        raise ValueError("the margins' curvature needs the hvp(c, v) that "
                         "reads it")
    if hvp is None:
        grad_only = lambda w: fun_and_grad(w)[1]

        def hvp(w, v):
            return jax.jvp(grad_only, (w,), (v,))[1]

    if margins is None:
        def evaluate(w):  # -> (f, g, margins): none without the oracle
            return (*fun_and_grad(w), None)

        curvature = None
    else:
        def evaluate(w):
            f, mw = margins.value(w)
            return f, margins.grad(w, mw), mw

        curvature = jax.named_scope("photon.tron/curvature")(
            margins.curvature)
    hvp = jax.named_scope("photon.tron/hvp")(hvp)
    trial = jax.named_scope("photon.tron/trial")(evaluate)
    if precond is not None:
        precond = jax.named_scope("photon.tron/precond")(precond)
    second_order = precond is not None or curvature is not None
    max_cg = max_cg_iters if max_cg_iters is not None else max(w0.shape[0], 20)
    f0, g0, m0 = evaluate(w0)
    g0_norm = l2_norm(g0)
    loss_hist, gnorm_hist = init_history(config.max_iters, f0.dtype)

    def _guard(md):
        # positivity guard: the M-norm needs a positive diagonal
        return jnp.maximum(md, jnp.finfo(dtype).eps
                           * jnp.maximum(jnp.max(md), 1.0))

    def _second_order(w, mw):
        """-> (c, m_diag) at ``w`` (of margins ``mw``), each as the state
        holds it."""
        c = curvature(mw) if curvature is not None else ()
        at = w if curvature is None else c
        return c, (_guard(precond(at)) if precond is not None
                   else jnp.zeros((0,), dtype))

    @jax.named_scope("photon.tron/update")
    def body(s: _State) -> _State:
        cg_tol = 0.1 * l2_norm(s.g)
        m_diag = s.m_diag if precond is not None else None
        at = s.w if curvature is None else s.c
        step, r, n_cg = _steihaug_cg(lambda v: hvp(at, v), s.g, s.delta,
                                  cg_tol, max_cg, m_diag=m_diag)
        w_try = s.w + step
        f_try, g_try, m_try = trial(w_try)
        gs = jnp.sum(s.g * step)
        # r == -(g + H step) from CG, so s.H.s = -g.s - r.s and
        # prered = -(g.s + s.H.s/2) = 0.5*(r.s - g.s) — no extra HVP needed
        prered = 0.5 * (jnp.sum(step * r) - gs)
        actred = s.f - f_try
        # the radius lives in the same norm the CG boundary used
        snorm = (l2_norm(step) if m_diag is None
                 else jnp.sqrt(jnp.sum(step * m_diag * step)))

        # Lin-Moré radius update via quadratic interpolation
        denom = f_try - s.f - gs
        alpha = jnp.where(denom <= 0, _SIGMA3, jnp.maximum(_SIGMA1, -0.5 * (gs / jnp.where(denom == 0, 1.0, denom))))
        delta = jnp.where(
            actred < _ETA0 * prered,
            jnp.minimum(jnp.maximum(alpha, _SIGMA1) * snorm, _SIGMA2 * s.delta),
            jnp.where(
                actred < _ETA1 * prered,
                jnp.maximum(_SIGMA1 * s.delta, jnp.minimum(alpha * snorm, _SIGMA2 * s.delta)),
                jnp.where(
                    actred < _ETA2 * prered,
                    jnp.maximum(_SIGMA1 * s.delta, jnp.minimum(alpha * snorm, _SIGMA3 * s.delta)),
                    jnp.maximum(s.delta, jnp.minimum(alpha * snorm, _SIGMA3 * s.delta)),
                ),
            ),
        )
        accept = actred > _ETA0 * prered
        w_new = jnp.where(accept, w_try, s.w)
        f_new = jnp.where(accept, f_try, s.f)
        g_new = jnp.where(accept, g_try, s.g)
        gnorm = l2_norm(g_new)
        conv = accept & converged_check(s.f, f_new, gnorm, g0_norm, config.tolerance)
        # the quadratic model predicting no significant reduction IS
        # convergence (nothing left to gain at this dtype's resolution)
        eps = jnp.finfo(dtype).eps
        conv = conv | (prered <= eps * jnp.maximum(jnp.abs(s.f), 1.0))
        # radius below step resolution at w means further steps can't move w
        stalled = delta < eps * jnp.maximum(l2_norm(w_new), 1.0)
        # the curvature and the diagonal each cost data passes: recompute
        # them only where w moved (a refused step keeps w, so both stay
        # valid) and a CG solve will read them (``cond`` of the next state).
        # A renewal's w_new is w_try: the curvature reads its margins
        renew = accept & ~conv & ~stalled & (s.it + 1 < config.max_iters)
        if second_order:
            c_new, m_new = lax.cond(renew,
                                    lambda: _second_order(w_new, m_try),
                                    lambda: (s.c, s.m_diag))
        else:
            c_new, m_new = s.c, s.m_diag
        renewed = renew.astype(jnp.int32)
        return _State(
            s.it + 1, w_new, f_new, g_new, delta, c_new, m_new, conv, stalled,
            s.loss_hist.at[s.it].set(f_new),
            s.gnorm_hist.at[s.it].set(gnorm),
            # one HVP a CG step and the trial point's (f, g); the Jacobi
            # diagonal and the curvature it shares with the HVPs are passes
            # of their own (``precond_passes``, ``curvature_passes``), not
            # products
            s.n_products + n_cg.astype(jnp.int32) + 1,
            s.cg_steps + n_cg.astype(jnp.int32),
            s.rejected_steps + (~accept).astype(jnp.int32),
            s.precond_passes + (renewed if precond is not None else 0),
            s.curvature_passes + (renewed if curvature is not None else 0),
        )

    def cond(s: _State):
        return (~s.converged) & (~s.stalled) & (s.it < config.max_iters)

    c0, diag0 = _second_order(w0, m0)
    init = _State(
        it=jnp.asarray(0), w=w0, f=f0, g=g0,
        delta=g0_norm, c=c0, m_diag=diag0,
        converged=jnp.asarray(False), stalled=jnp.asarray(False),
        loss_hist=loss_hist, gnorm_hist=gnorm_hist,
        n_products=jnp.asarray(1, jnp.int32),  # (f0, g0)
        cg_steps=jnp.asarray(0, jnp.int32),
        rejected_steps=jnp.asarray(0, jnp.int32),
        precond_passes=jnp.asarray(int(precond is not None), jnp.int32),
        curvature_passes=jnp.asarray(int(curvature is not None), jnp.int32),
    )
    s = lax.while_loop(cond, body, match_vma_tree(init, g0))
    return OptimizationResult(
        w=s.w, value=s.f, grad_norm=l2_norm(s.g), iterations=s.it,
        converged=s.converged, loss_history=s.loss_hist, grad_norm_history=s.gnorm_hist,
        gather_products=s.n_products, transpose_products=s.n_products,
        cg_steps=s.cg_steps, rejected_steps=s.rejected_steps,
        precond_passes=s.precond_passes,
        curvature_passes=s.curvature_passes,
        # every curvature but w0's is an accepted trial point's, read off
        # the margins that trial gathered
        margins_reused=jnp.maximum(s.curvature_passes - 1, 0),
    )
