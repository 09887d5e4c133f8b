"""Jitted L-BFGS with strong-Wolfe line search.

Equivalent of the reference's ``optimization.LBFGS`` (which wraps Breeze
L-BFGS with a strong-Wolfe search — SURVEY.md §3.1; reference mount empty),
rebuilt as a single ``lax.while_loop`` whose carry holds the circular
(s, y) history, so the whole optimization is one XLA computation: no
per-iteration host round-trip, and under sharded batches the gradient's
all-reduce rides ICI inside the same program (the ``treeAggregate``
replacement, SURVEY.md §4.2).
"""

from __future__ import annotations

from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from photon_ml_tpu.optimize.common import (
    grad_converged,
    OptimizationResult,
    OptimizerConfig,
    converged_check,
    history_slot,
    history_store,
    history_zeros,
    init_history,
    l2_norm,
    match_vma_tree,
)
from photon_ml_tpu.optimize.linesearch import strong_wolfe


class _State(NamedTuple):
    it: jax.Array  # iteration counter
    k: jax.Array  # number of (s,y) pairs ever stored (head of circular buffer)
    w: jax.Array
    f: jax.Array
    g: jax.Array
    s_hist: jax.Array  # m slots of [d] (optimize.common.history_zeros)
    y_hist: jax.Array  # the same
    rho: jax.Array  # [m]
    converged: jax.Array
    stalled: jax.Array
    loss_hist: jax.Array
    gnorm_hist: jax.Array
    n_evals: jax.Array  # i32: fun_and_grad evaluations (one X v + X^T d each)


@jax.named_scope("photon.lbfgs/two_loop")
def two_loop_direction(g, s_hist, y_hist, rho, k, m):
    """Two-loop recursion over a circular buffer; slot (k-1-i) mod m is the
    i-th most recent pair, masked out when i >= min(k, m)."""
    dtype = g.dtype
    (d,) = g.shape
    n_valid = jnp.minimum(k, m)

    def newest_to_oldest(i, carry):
        q, alphas = carry
        j = jnp.mod(k - 1 - i, m)
        valid = i < n_valid
        a = jnp.where(
            valid, rho[j] * jnp.sum(history_slot(s_hist, j, d) * q), 0.0)
        q = q - a * history_slot(y_hist, j, d)
        return q, alphas.at[j].set(a)

    q, alphas = lax.fori_loop(
        0, m, newest_to_oldest, match_vma_tree((g, jnp.zeros((m,), dtype)), g)
    )

    newest = jnp.mod(k - 1, m)
    y_newest = history_slot(y_hist, newest, d)
    sy = jnp.sum(history_slot(s_hist, newest, d) * y_newest)
    yy = jnp.sum(y_newest * y_newest)
    gamma = jnp.where((k > 0) & (yy > 0), sy / jnp.maximum(yy, jnp.finfo(dtype).tiny), 1.0)
    r = gamma * q

    def oldest_to_newest(i, r):
        rank = n_valid - 1 - i  # recency rank, oldest first
        j = jnp.mod(k - 1 - rank, m)
        valid = rank >= 0
        beta = rho[j] * jnp.sum(history_slot(y_hist, j, d) * r)
        upd = history_slot(s_hist, j, d) * (alphas[j] - beta)
        return r + jnp.where(valid, upd, 0.0)

    r = lax.fori_loop(0, m, oldest_to_newest, r)
    return -r


def lbfgs(
    fun_and_grad: Callable,
    w0: jax.Array,
    config: OptimizerConfig = OptimizerConfig(),
) -> OptimizationResult:
    """Minimize fun(w); fun_and_grad(w) -> (f, g). Fully jittable."""
    m = config.history
    d = w0.shape[0]
    dtype = w0.dtype
    f0, g0 = fun_and_grad(w0)
    g0_norm = l2_norm(g0)
    loss_hist, gnorm_hist = init_history(config.max_iters, f0.dtype)

    @jax.named_scope("photon.lbfgs/update")
    def body(s: _State) -> _State:
        p = two_loop_direction(s.g, s.s_hist, s.y_hist, s.rho, s.k, m)
        # ensure descent; fall back to steepest descent if the metric degraded
        dg = jnp.sum(p * s.g)
        p = jnp.where(dg < 0, p, -s.g)
        alpha0 = jnp.where(s.k > 0, 1.0, 1.0 / jnp.maximum(l2_norm(s.g), 1.0))
        ls = strong_wolfe(
            fun_and_grad, s.w, p, s.f, s.g, alpha0=alpha0,
            max_evals=config.max_line_search_steps,
        )
        w_new = s.w + ls.alpha * p
        step = ls.alpha * p
        y = ls.g - s.g
        sy = jnp.sum(step * y)
        store = ls.ok & (
            sy > 1e-10 * jnp.maximum(l2_norm(step) * l2_norm(y), jnp.finfo(dtype).tiny)
        )
        slot = jnp.mod(s.k, m)
        s_hist = history_store(s.s_hist, slot, step, store)
        y_hist = history_store(s.y_hist, slot, y, store)
        rho = jnp.where(store, s.rho.at[slot].set(1.0 / jnp.where(sy == 0, 1.0, sy)), s.rho)
        # on line-search failure: reset the history and retry from
        # steepest descent; stall only if -g itself failed (k == 0).
        # conv is gated on ls.ok — a failed search leaves f unchanged and
        # the zero delta would spuriously pass the relative test
        # (same policy as optimize/lbfgs_margin.py)
        k_new = jnp.where(store, s.k + 1, jnp.where(ls.ok, s.k, 0))
        stalled = (~ls.ok) & (s.k == 0)
        gnorm = l2_norm(ls.g)
        # failed search: only the rel-loss half is invalid (zero delta
        # passes spuriously); the gradient test must still fire — a
        # search failing AT the optimum is convergence, not a stall
        conv = jnp.where(
            ls.ok,
            converged_check(s.f, ls.f, gnorm, g0_norm, config.tolerance),
            grad_converged(gnorm, g0_norm, config.tolerance))
        # A fit that converges on its FIRST iteration was already at its
        # stopping point: the step it just probed buys less than the
        # tolerance by definition, and taking it would make a warm-started
        # re-fit of an already-converged problem drift by one noise-level
        # step per call — coordinate descent re-fits every coordinate
        # every sweep, and that drift kept re-activating the active-set
        # frontier (game/descent.py) and prevented the sweep-level early
        # exit from ever seeing a stationary score vector. Such a re-fit
        # is now an exact no-op: it returns w0 bit-identically. Later
        # iterations keep their converging step (it carries the final
        # refinement of a genuinely-progressing fit), as before.
        take = ~(conv & (s.it == 0))
        w_out = jnp.where(take, w_new, s.w)
        f_out = jnp.where(take, ls.f, s.f)
        g_out = jnp.where(take, ls.g, s.g)
        return _State(
            s.it + 1, k_new, w_out, f_out, g_out,
            s_hist, y_hist, rho,
            conv, stalled,
            s.loss_hist.at[s.it].set(f_out),
            s.gnorm_hist.at[s.it].set(l2_norm(g_out)),
            s.n_evals + ls.n_evals.astype(jnp.int32),
        )

    def cond(s: _State):
        return (~s.converged) & (~s.stalled) & (s.it < config.max_iters)

    init = _State(
        it=jnp.asarray(0), k=jnp.asarray(0), w=w0, f=f0, g=g0,
        s_hist=history_zeros(m, d, dtype), y_hist=history_zeros(m, d, dtype),
        rho=jnp.zeros((m,), dtype),
        converged=jnp.asarray(False), stalled=jnp.asarray(False),
        loss_hist=loss_hist, gnorm_hist=gnorm_hist,
        n_evals=jnp.asarray(1, jnp.int32),  # (f0, g0)
    )
    s = lax.while_loop(cond, body, match_vma_tree(init, g0))
    return OptimizationResult(
        w=s.w, value=s.f, grad_norm=l2_norm(s.g), iterations=s.it,
        converged=s.converged, loss_history=s.loss_hist, grad_norm_history=s.gnorm_hist,
        gather_products=s.n_evals, transpose_products=s.n_evals,
    )
