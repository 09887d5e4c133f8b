"""L-BFGS specialized for linear-margin objectives (GLMs).

The generic :func:`photon_ml_tpu.optimize.lbfgs.lbfgs` treats the objective
as a black box, so every strong-Wolfe trial point costs a full
value-and-gradient pass over the data — for the sparse hot loop that is one
O(nnz) margin gather plus one O(nnz + d) transpose scatter *per line-search
evaluation* (SURVEY.md §4.2; the reference pays the same price as one
cluster ``treeAggregate`` per evaluation).

A GLM's data term factors through the margins, and margins are linear in
the coefficients (normalization's coefficient-space map included —
``ops/normalization.py``):

    m(w + a*p) = m(w) + a * m_dir(p)

so one gather per iteration (the direction's margin) makes every
line-search trial an O(n) pointwise evaluation on cached margin vectors,
and only the *accepted* point pays the transpose for its gradient. Per
iteration the data passes drop from ``2 * (1 + line_search_evals)`` to
exactly 2 (one gather + one transpose), independent of how hard the line
search works. The L2 term is quadratic along the ray and handled in closed
form via three precomputed scalars.

The loop carries the current margins ``mw`` and updates them incrementally
(``mw += a * mp``); the accumulated f32 drift per iteration is O(eps *
|a*mp|), negligible over the tens-of-iterations fits this serves (parity
is asserted against the black-box path in tests/test_parallel.py).
"""

from __future__ import annotations

from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from photon_ml_tpu.optimize.common import (
    OptimizationResult,
    grad_converged,
    OptimizerConfig,
    converged_check,
    history_store,
    history_zeros,
    init_history,
    l2_norm,
    match_vma_tree,
)
from photon_ml_tpu.optimize.lbfgs import two_loop_direction
from photon_ml_tpu.optimize.linesearch import strong_wolfe


class _State(NamedTuple):
    it: jax.Array
    k: jax.Array
    w: jax.Array
    mw: jax.Array  # cached margins of w (incl. offsets + normalization adjust)
    f: jax.Array
    g: jax.Array
    s_hist: jax.Array
    y_hist: jax.Array
    rho: jax.Array
    converged: jax.Array
    stalled: jax.Array
    loss_hist: jax.Array
    gnorm_hist: jax.Array
    n_passes: jax.Array  # i32: gather + transpose pairs (the search adds none)


def lbfgs_margin(
    dir_margin: Callable,  # p [d] -> m_p [n] (linear; no offsets)
    loss_and_dir: Callable,  # (m [n], m_p [n]) -> (sum_i w_i l(m_i),
    #                                               sum_i w_i l'(m_i) m_p_i)
    data_grad: Callable,  # m [n] -> data-term gradient [d] (chain rule incl.)
    reg_mask: Callable,  # w [d] -> w with unpenalized slots zeroed
    w0: jax.Array,
    m0: jax.Array,
    l2,
    config: OptimizerConfig = OptimizerConfig(),
    loss_delta_and_dir: Callable | None = None,
    # (m, m_p, alpha) -> (sum_i w_i (l(m_i + a m_p_i) - l(m_i)),
    #                     sum_i w_i l'(m_i + a m_p_i) m_p_i)
) -> OptimizationResult:
    """Minimize  sum_i w_i l(m_i(w)) + 0.5*l2*||reg_mask(w)||^2  where the
    margin map is affine in w. All data reductions must already be global
    (psummed) inside the supplied callables.

    When ``loss_delta_and_dir`` is given, the line search and the
    relative-loss convergence test run in DELTA space: per-row loss
    differences are summed instead of differencing two rounded totals.
    In f32 a total's resolution is eps*|f|, far coarser than late-stage
    per-iteration improvements, so total-space Wolfe tests stall the fit
    (observed on TPU: hard stop at 16/20 iterations); delta sums keep
    relative accuracy in the improvement itself."""
    m = config.history
    d = w0.shape[0]
    dtype = w0.dtype
    l2 = jnp.asarray(l2, dtype)

    def full_f(mw, w):
        f_data, _ = loss_and_dir(mw, mw)
        wr = reg_mask(w)
        return f_data + 0.5 * l2 * jnp.sum(wr * wr)

    def full_g(mw, w):
        return data_grad(mw) + l2 * reg_mask(w)

    f0 = full_f(m0, w0)
    g0 = full_g(m0, w0)
    g0_norm = l2_norm(g0)
    loss_hist, gnorm_hist = init_history(config.max_iters, f0.dtype)

    @jax.named_scope("photon.lbfgs/update")
    def body(s: _State) -> _State:
        p = two_loop_direction(s.g, s.s_hist, s.y_hist, s.rho, s.k, m)
        dg = jnp.sum(p * s.g)
        p = jnp.where(dg < 0, p, -s.g)

        mp = dir_margin(p)  # the iteration's ONE gather pass
        # L2 along the ray: ||reg(w) + a*reg(p)||^2 = c0 + 2*a*c1 + a^2*c2
        wr, pr = reg_mask(s.w), reg_mask(p)
        c1 = jnp.sum(wr * pr)
        c2 = jnp.sum(pr * pr)

        if loss_delta_and_dir is not None:
            # DELTA space: phi returns f(w + a p) - f(w) via summed
            # per-row differences (accurate at any |f|); strong_wolfe's
            # tests are all translation-invariant, so feeding f0 = 0
            # keeps its semantics exactly
            def phi(alpha):
                delta_data, df_data = loss_delta_and_dir(s.mw, mp, alpha)
                delta = delta_data + l2 * (alpha * c1
                                           + 0.5 * alpha * alpha * c2)
                df = df_data + l2 * (c1 + alpha * c2)
                return delta, df

            ls_f0 = jnp.zeros((), dtype)
        else:
            def phi(alpha):
                """(f(w + a p), f'(a)) as an O(n) pointwise computation;
                the scalar derivative doubles as the 1-d 'gradient' for
                strong_wolfe (direction 1.0: sum(g*p) == the derivative)."""
                f_data, df_data = loss_and_dir(s.mw + alpha * mp, mp)
                f = f_data + 0.5 * l2 * (jnp.sum(wr * wr)
                                         + 2.0 * alpha * c1
                                         + alpha * alpha * c2)
                df = df_data + l2 * (c1 + alpha * c2)
                return f, df

            ls_f0 = s.f

        # phi'(0) == p . g exactly (g is the full gradient incl. the L2
        # term): an O(d) local dot, not another distributed evaluation
        df0 = jnp.sum(p * s.g)
        alpha0 = jnp.where(s.k > 0, 1.0, 1.0 / jnp.maximum(l2_norm(s.g), 1.0))
        ls = strong_wolfe(
            phi, jnp.zeros((), dtype), jnp.ones((), dtype), ls_f0, df0,
            alpha0=alpha0, max_evals=config.max_line_search_steps,
        )
        # in delta space ls.f is the accepted IMPROVEMENT (0 on failure)
        f_new = (s.f + ls.f) if loss_delta_and_dir is not None else ls.f
        w_new = s.w + ls.alpha * p
        mw_new = s.mw + ls.alpha * mp
        g_new = full_g(mw_new, w_new)  # the iteration's ONE transpose pass

        step = ls.alpha * p
        y = g_new - s.g
        sy = jnp.sum(step * y)
        store = ls.ok & (
            sy > 1e-10 * jnp.maximum(l2_norm(step) * l2_norm(y),
                                     jnp.finfo(dtype).tiny)
        )
        slot = jnp.mod(s.k, m)
        s_hist = history_store(s.s_hist, slot, step, store)
        y_hist = history_store(s.y_hist, slot, y, store)
        rho = jnp.where(store,
                        s.rho.at[slot].set(1.0 / jnp.where(sy == 0, 1.0, sy)),
                        s.rho)
        # line-search failure (alpha=0, no step): RESET the history and
        # retry from steepest descent before giving up — in f32 the
        # L-BFGS metric goes stale near convergence and a restart often
        # buys several more productive iterations (observed on TPU:
        # hard stop at iteration 16/20). Stall only if the search failed
        # with an already-empty history (p was -g).
        k_new = jnp.where(store, s.k + 1, jnp.where(ls.ok, s.k, 0))
        stalled = (~ls.ok) & (s.k == 0)
        gnorm = l2_norm(g_new)
        # gate on ls.ok: a failed search leaves f unchanged, and a zero
        # loss-delta would spuriously pass the relative convergence test
        if loss_delta_and_dir is not None:
            # accurate delta: test |improvement| directly against
            # tol * max(|f|, 1) (converged_check would re-difference the
            # rounded totals and lose exactly what delta space preserves)
            full = converged_check(jnp.zeros((), dtype), -ls.f, gnorm,
                                   g0_norm, config.tolerance, f_scale=s.f)
        else:
            full = converged_check(s.f, f_new, gnorm, g0_norm,
                                   config.tolerance)
        # failed search: rel-loss half is invalid (zero delta) but the
        # gradient test must still fire — failing AT the optimum is
        # convergence, not a stall
        conv = jnp.where(ls.ok, full,
                         grad_converged(gnorm, g0_norm, config.tolerance))
        return _State(
            s.it + 1, k_new, w_new, mw_new, f_new, g_new,
            s_hist, y_hist, rho,
            conv, stalled,
            s.loss_hist.at[s.it].set(f_new),
            s.gnorm_hist.at[s.it].set(gnorm),
            s.n_passes + 1,  # dir_margin(p) and full_g(mw_new, w_new)
        )

    def cond(s: _State):
        return (~s.converged) & (~s.stalled) & (s.it < config.max_iters)

    init = _State(
        it=jnp.asarray(0), k=jnp.asarray(0), w=w0, mw=m0, f=f0, g=g0,
        s_hist=history_zeros(m, d, dtype), y_hist=history_zeros(m, d, dtype),
        rho=jnp.zeros((m,), dtype),
        converged=jnp.asarray(False), stalled=jnp.asarray(False),
        loss_hist=loss_hist, gnorm_hist=gnorm_hist,
        n_passes=jnp.asarray(1, jnp.int32),  # the caller's m0, and g0
    )
    s = lax.while_loop(cond, body, match_vma_tree(init, g0))
    return OptimizationResult(
        w=s.w, value=s.f, grad_norm=l2_norm(s.g), iterations=s.it,
        converged=s.converged, loss_history=s.loss_hist,
        grad_norm_history=s.gnorm_hist,
        gather_products=s.n_passes, transpose_products=s.n_passes,
    )
