"""Jitted OWL-QN (Orthant-Wise Limited-memory Quasi-Newton) for L1 /
elastic-net objectives.

Equivalent of the reference's ``optimization.OWLQN`` (which wraps Breeze
OWLQN — SURVEY.md §3.1; reference mount empty). Minimizes
F(w) = f(w) + l1 * ||w * mask||_1 where f is smooth (the elastic net's L2 part
lives inside f, matching the reference's split — SURVEY.md §3.1
regularization row). Standard Andrew & Gao (2007) scheme: pseudo-gradient,
L-BFGS direction from smooth-gradient history, orthant projection of both the
direction and the line-search iterates.

Handed a :class:`~photon_ml_tpu.optimize.common.MarginOracle`, the search's
trials carry the margins ``X w`` their values gathered, and the gradient at
the accepted point reads them: one gather a trial, none more a pass.
"""

from __future__ import annotations

from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax

from photon_ml_tpu.optimize.common import (
    MarginOracle,
    OptimizationResult,
    OptimizerConfig,
    converged_check,
    history_store,
    history_zeros,
    init_history,
    l2_norm,
    match_vma_tree,
)
from photon_ml_tpu.optimize.lbfgs import two_loop_direction
from photon_ml_tpu.optimize.linesearch import backtracking


def pseudo_gradient(w, g, l1):
    """Directional-derivative-minimizing subgradient of f + l1*|w|_1."""
    right = g + l1
    left = g - l1
    at_zero = jnp.where(right < 0, right, jnp.where(left > 0, left, 0.0))
    return jnp.where(w > 0, right, jnp.where(w < 0, left, at_zero))


class _State(NamedTuple):
    it: jax.Array
    k: jax.Array
    w: jax.Array
    F: jax.Array  # full objective incl. L1
    g: jax.Array  # smooth gradient
    m: object  # the margins of w (None without a MarginOracle)
    s_hist: jax.Array
    y_hist: jax.Array
    rho: jax.Array
    converged: jax.Array
    stalled: jax.Array
    loss_hist: jax.Array
    gnorm_hist: jax.Array
    n_trials: jax.Array  # i32: the searches' trial points, each one gather
    n_transpose: jax.Array  # i32: only an accepted point's gradient transposes


def owlqn(
    fun_and_grad: Callable,
    w0: jax.Array,
    l1_weight,
    config: OptimizerConfig = OptimizerConfig(),
    l1_mask: Optional[jax.Array] = None,
    margins: Optional[MarginOracle] = None,
) -> OptimizationResult:
    """Minimize f(w) + l1_weight * ||w * l1_mask||_1; fun_and_grad is the
    smooth part. l1_mask defaults to all-ones (mask the intercept with 0).
    ``margins`` optionally is the same smooth part in halves that share a
    point's margins: every evaluation then goes through it, and the
    gradient at a search's accepted point reads that trial's margins."""
    m = config.history
    d = w0.shape[0]
    dtype = w0.dtype
    mask = jnp.ones((d,), dtype) if l1_mask is None else l1_mask.astype(dtype)
    lam = jnp.asarray(l1_weight, dtype) * mask

    if margins is None:
        def full_value(w):
            f, _ = fun_and_grad(w)
            return f + jnp.sum(lam * jnp.abs(w))

        def grad_at(w, _):
            return fun_and_grad(w)[1]

        f0, g0 = fun_and_grad(w0)
        m0 = None
    else:
        def full_value(w):
            f, mw = margins.value(w)
            return f + jnp.sum(lam * jnp.abs(w)), mw

        grad_at = margins.grad
        f0, m0 = margins.value(w0)
        g0 = margins.grad(w0, m0)
    F0 = f0 + jnp.sum(lam * jnp.abs(w0))
    pg0_norm = l2_norm(pseudo_gradient(w0, g0, lam))
    loss_hist, gnorm_hist = init_history(config.max_iters, F0.dtype)

    def body(s: _State) -> _State:
        with jax.named_scope("photon.owlqn/pseudo_gradient"):
            pg = pseudo_gradient(s.w, s.g, lam)
        p = two_loop_direction(pg, s.s_hist, s.y_hist, s.rho, s.k, m)
        with jax.named_scope("photon.owlqn/direction"):
            # align the direction with -pg (orthant-wise projection of
            # the direction)
            p = jnp.where(p * (-pg) > 0, p, 0.0)
            dg = jnp.sum(p * pg)
            p = jnp.where(dg < 0, p, -pg)
            # orthant choice: sign(w), or sign(-pg) where w == 0
            xi = jnp.where(s.w != 0, jnp.sign(s.w), jnp.sign(-pg))
            alpha0 = jnp.where(s.k > 0, 1.0, 1.0 / jnp.maximum(l2_norm(pg), 1.0))

        def project(w_trial):
            return jnp.where(w_trial * xi > 0, w_trial, 0.0)

        # under photon.owlqn/line_search: projection, the trial's value
        # (and its margins, with the oracle: the accepted point's, or the
        # kept w's where the search failed)
        w_new, F_new, m_new, trials, ok = backtracking(
            full_value, s.w, p, s.F, pg, alpha0=alpha0,
            max_evals=config.max_line_search_steps, project=project,
            aux0=s.m,
        )
        g_new = grad_at(w_new, m_new)
        with jax.named_scope("photon.owlqn/update"):
            step = w_new - s.w
            y = g_new - s.g
            sy = jnp.sum(step * y)
            store = ok & (sy > 1e-10 * jnp.maximum(l2_norm(step) * l2_norm(y), jnp.finfo(dtype).tiny))
            slot = jnp.mod(s.k, m)
            s_hist = history_store(s.s_hist, slot, step, store)
            y_hist = history_store(s.y_hist, slot, y, store)
            rho = jnp.where(store, s.rho.at[slot].set(1.0 / jnp.where(sy == 0, 1.0, sy)), s.rho)
            k_new = jnp.where(store, s.k + 1, s.k)
        with jax.named_scope("photon.owlqn/pseudo_gradient"):
            pg_new_norm = l2_norm(pseudo_gradient(w_new, g_new, lam))
        conv = converged_check(s.F, F_new, pg_new_norm, pg0_norm, config.tolerance)
        return _State(
            s.it + 1, k_new, w_new, F_new, g_new, m_new,
            s_hist, y_hist, rho, conv, ~ok,
            s.loss_hist.at[s.it].set(F_new),
            s.gnorm_hist.at[s.it].set(pg_new_norm),
            # a trial reads the value alone (its gradient is dead code)
            s.n_trials + trials.astype(jnp.int32),
            s.n_transpose + 1,
        )

    def cond(s: _State):
        return (~s.converged) & (~s.stalled) & (s.it < config.max_iters)

    init = _State(
        it=jnp.asarray(0), k=jnp.asarray(0), w=w0, F=F0, g=g0, m=m0,
        s_hist=history_zeros(m, d, dtype), y_hist=history_zeros(m, d, dtype),
        rho=jnp.zeros((m,), dtype),
        converged=jnp.asarray(False), stalled=jnp.asarray(False),
        loss_hist=loss_hist, gnorm_hist=gnorm_hist,
        n_trials=jnp.asarray(0, jnp.int32),
        n_transpose=jnp.asarray(1, jnp.int32),  # (f0, g0)
    )
    s = lax.while_loop(cond, body, match_vma_tree(init, g0))
    with jax.named_scope("photon.owlqn/pseudo_gradient"):
        final_pg = pseudo_gradient(s.w, s.g, lam)
    passes = s.it.astype(jnp.int32)
    # the accepted point's gradient reads its trial's margins, or (without
    # the oracle) gathers them once more
    reused = passes if margins is not None else jnp.zeros_like(passes)
    return OptimizationResult(
        w=s.w, value=s.F, grad_norm=l2_norm(final_pg), iterations=s.it,
        converged=s.converged, loss_history=s.loss_hist, grad_norm_history=s.gnorm_hist,
        gather_products=1 + s.n_trials + passes - reused,  # (f0, g0)'s first
        transpose_products=s.n_transpose,
        line_search_trials=s.n_trials,
        nonzeros=jnp.count_nonzero(s.w).astype(jnp.int32),
        margins_reused=reused,
    )
