"""Strong-Wolfe line search, jit-compatible (single ``lax.while_loop``).

Plays the role Breeze's ``StrongWolfeLineSearch`` plays under the reference's
``LBFGS`` (SURVEY.md §3.1; reference mount empty). Standard
bracketing + zoom (Nocedal & Wright alg. 3.5/3.6) expressed as a phase
state-machine so the whole search stays on device; zoom uses safeguarded
quadratic interpolation with bisection fallback.
"""

from __future__ import annotations

from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from photon_ml_tpu.optimize.common import match_vma_tree

_BRACKET, _ZOOM, _DONE = 0, 1, 2


class LineSearchResult(NamedTuple):
    alpha: jax.Array
    f: jax.Array
    g: jax.Array  # gradient at w + alpha * p
    n_evals: jax.Array
    ok: jax.Array  # strong-Wolfe satisfied (else best-effort Armijo point)


class _State(NamedTuple):
    phase: jax.Array
    i: jax.Array
    alpha: jax.Array  # candidate to evaluate next / final
    f: jax.Array
    dg: jax.Array
    g: jax.Array
    a_prev: jax.Array
    f_prev: jax.Array
    dg_prev: jax.Array
    a_lo: jax.Array
    f_lo: jax.Array
    dg_lo: jax.Array
    g_lo: jax.Array
    a_hi: jax.Array
    f_hi: jax.Array
    ok: jax.Array


@jax.named_scope("photon.lbfgs/line_search")
def strong_wolfe(
    fun_and_grad: Callable,
    w: jax.Array,
    p: jax.Array,
    f0: jax.Array,
    g0: jax.Array,
    alpha0=1.0,
    c1: float = 1e-4,
    c2: float = 0.9,
    max_evals: int = 25,
    alpha_max: float = 1e6,
) -> LineSearchResult:
    """Search along p from w. fun_and_grad(w) -> (f, g). Requires p a descent
    direction (dphi0 < 0); otherwise returns alpha=0, ok=False."""
    dtype = f0.dtype
    dphi0 = jnp.sum(g0 * p).astype(dtype)

    def phi(alpha):
        f, g = fun_and_grad(w + alpha * p)
        return f, jnp.sum(g * p), g

    def interp(a_lo, f_lo, dg_lo, a_hi, f_hi):
        # safeguarded quadratic interpolation on [lo, hi]
        denom = 2.0 * (f_hi - f_lo - dg_lo * (a_hi - a_lo))
        quad = a_lo - dg_lo * (a_hi - a_lo) ** 2 / jnp.where(denom == 0, 1.0, denom)
        mid = 0.5 * (a_lo + a_hi)
        lo, hi = jnp.minimum(a_lo, a_hi), jnp.maximum(a_lo, a_hi)
        width = hi - lo
        bad = (
            ~jnp.isfinite(quad)
            | (quad <= lo + 0.1 * width)
            | (quad >= hi - 0.1 * width)
            | (denom == 0)
        )
        return jnp.where(bad, mid, quad)

    def body(s: _State) -> _State:
        f, dg, g = phi(s.alpha)
        armijo_fail = (f > f0 + c1 * s.alpha * dphi0) | ((f >= s.f_prev) & (s.i > 0))
        curvature_ok = jnp.abs(dg) <= -c2 * dphi0

        def bracket_step():
            # cases per Nocedal & Wright alg 3.5
            to_zoom_hi = armijo_fail  # zoom(prev, cur)
            done = (~armijo_fail) & curvature_ok
            to_zoom_lo = (~armijo_fail) & (~curvature_ok) & (dg >= 0)  # zoom(cur, prev)
            next_alpha = jnp.minimum(2.0 * s.alpha, alpha_max)
            phase = jnp.where(done, _DONE, jnp.where(to_zoom_hi | to_zoom_lo, _ZOOM, _BRACKET))
            a_lo = jnp.where(to_zoom_hi, s.a_prev, s.alpha)
            f_lo = jnp.where(to_zoom_hi, s.f_prev, f)
            dg_lo = jnp.where(to_zoom_hi, s.dg_prev, dg)
            g_lo = jnp.where(to_zoom_hi, s.g, g)  # best-known g (approx for prev)
            a_hi = jnp.where(to_zoom_hi, s.alpha, s.a_prev)
            f_hi = jnp.where(to_zoom_hi, f, s.f_prev)
            alpha_next = jnp.where(
                phase == _ZOOM, interp(a_lo, f_lo, dg_lo, a_hi, f_hi),
                jnp.where(done, s.alpha, next_alpha),
            )
            return _State(
                phase, s.i + 1, alpha_next, f, dg, g,
                s.alpha, f, dg,
                a_lo, f_lo, dg_lo, g_lo, a_hi, f_hi,
                ok=done,
            )

        def zoom_step():
            hi_update = (f > f0 + c1 * s.alpha * dphi0) | (f >= s.f_lo)
            done = (~hi_update) & curvature_ok
            flip = (~hi_update) & (~curvature_ok) & (dg * (s.a_hi - s.a_lo) >= 0)
            a_hi = jnp.where(hi_update, s.alpha, jnp.where(flip, s.a_lo, s.a_hi))
            f_hi = jnp.where(hi_update, f, jnp.where(flip, s.f_lo, s.f_hi))
            a_lo = jnp.where(hi_update, s.a_lo, s.alpha)
            f_lo = jnp.where(hi_update, s.f_lo, f)
            dg_lo = jnp.where(hi_update, s.dg_lo, dg)
            g_lo = jax.tree.map(lambda old, new: jnp.where(hi_update, old, new), s.g_lo, g)
            phase = jnp.where(done, _DONE, _ZOOM)
            alpha_next = jnp.where(done, s.alpha, interp(a_lo, f_lo, dg_lo, a_hi, f_hi))
            return _State(
                phase, s.i + 1, alpha_next, f, dg, g,
                s.alpha, f, dg,
                a_lo, f_lo, dg_lo, g_lo, a_hi, f_hi,
                ok=done,
            )

        return lax.cond(s.phase == _BRACKET, bracket_step, zoom_step)

    def cond(s: _State):
        return (s.phase != _DONE) & (s.i < max_evals)

    zero = jnp.zeros((), dtype)
    init = _State(
        phase=jnp.asarray(_BRACKET),
        i=jnp.asarray(0),
        alpha=jnp.asarray(alpha0, dtype),
        f=f0, dg=dphi0, g=g0,
        a_prev=zero, f_prev=f0, dg_prev=dphi0,
        a_lo=zero, f_lo=f0, dg_lo=dphi0, g_lo=g0,
        a_hi=jnp.asarray(alpha_max, dtype), f_hi=f0,
        ok=jnp.asarray(False),
    )
    bad_direction = dphi0 >= 0
    s = lax.while_loop(cond, body, match_vma_tree(init, f0))

    # On exhaustion fall back to the best bracket point (a_lo satisfies Armijo
    # by construction once zoom is entered); if nothing worked, take no step.
    finished = s.phase == _DONE
    alpha = jnp.where(finished, s.alpha, s.a_lo)
    f = jnp.where(finished, s.f, s.f_lo)
    g = jnp.where(finished, s.g, s.g_lo)
    took_step = alpha > 0
    alpha = jnp.where(bad_direction, 0.0, alpha)
    f = jnp.where(bad_direction, f0, f)
    g = jax.tree.map(lambda a, b: jnp.where(bad_direction, a, b), g0, g)
    return LineSearchResult(alpha, f, g, s.i, (finished | took_step) & ~bad_direction)


@jax.named_scope("photon.owlqn/line_search")
def backtracking(
    fun: Callable,
    w: jax.Array,
    p: jax.Array,
    f0: jax.Array,
    pseudo_grad: jax.Array,
    alpha0=1.0,
    c1: float = 1e-4,
    shrink: float = 0.5,
    max_evals: int = 30,
    project: Callable | None = None,
    aux0=None,
):
    """Armijo backtracking with optional orthant projection (OWL-QN style).

    fun(w) -> f, or ``(f, aux)`` where ``aux0`` (the ``aux`` of ``w``) is
    given: the search then carries the last trial's ``aux`` and hands back
    the accepted point's (``aux0`` where none was accepted), so a caller
    reads what the evaluation of ``w_new`` computed without computing it
    again. ``project(w_trial)`` maps the trial point back to the
    feasible orthant before evaluation (identity if None). The sufficient
    decrease test uses the OWL-QN form f_new <= f0 + c1 * pseudo_grad.(w_new - w)
    which reduces to plain Armijo when project is None and pseudo_grad is the
    gradient. Returns (w_new, f_new, aux_new, n_evals, ok); ``aux_new`` is
    None without ``aux0``.
    """
    proj = project if project is not None else (lambda x: x)
    evaluate = fun if aux0 is not None else (lambda x: (fun(x), None))

    def body(s):
        alpha, _, _, _, i, _ = s
        w_new = proj(w + alpha * p)
        f_new, aux = evaluate(w_new)
        ok = f_new <= f0 + c1 * jnp.sum(pseudo_grad * (w_new - w))
        return (jnp.where(ok, alpha, alpha * shrink), w_new, f_new, aux,
                i + 1, ok)

    def cond(s):
        *_, i, ok = s
        return (~ok) & (i < max_evals)

    # the carried aux starts as zeros: ``aux0`` is read again below, so a
    # loop started from it would begin with a copy of it
    _, w_new, f_new, aux_new, i, ok = lax.while_loop(
        cond, body,
        match_vma_tree(
            (jnp.asarray(alpha0, f0.dtype), w, f0,
             jax.tree.map(jnp.zeros_like, aux0), jnp.asarray(0),
             jnp.asarray(False)),
            f0,
        ),
    )
    keep = lambda a, b: jnp.where(ok, b, a)
    w_new = jax.tree.map(keep, w, w_new)
    aux_new = jax.tree.map(keep, aux0, aux_new)
    f_new = jnp.where(ok, f_new, f0)
    return w_new, f_new, aux_new, i, ok
