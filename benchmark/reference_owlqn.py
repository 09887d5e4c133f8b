"""The plain reference of the elastic-net fit: numpy/scipy, float64.

It imports nothing of the program and takes nothing the program made. From
``benchmark/reference.py`` it takes the smooth part (``LogisticL2``: the
logistic loss summed over rows plus ``0.5 * l2 * |w|^2``), the blocked
vector arithmetic (``Workers``) and the two-loop recursion; what it states
itself is the elastic-net objective ``F(w) = f(w) + l1 * |w|_1`` (Zou &
Hastie 2005; upstream Photon ML's ``ELASTIC_NET``: ``l1 = lambda * alpha``,
``l2 = lambda * (1 - alpha)``) and OWL-QN, written from Andrew & Gao,
*Scalable Training of L1-Regularized Log-Linear Models*, ICML 2007:

* the pseudo-gradient (their eq. 4): the one-sided derivative that points
  downhill, nought where ``|g_j| <= l1`` at a zero;
* the direction ``d = -H pg`` by the two-loop recursion over the history of
  the *smooth* gradients (section 3.2: ``y = grad f(x') - grad f(x)``, the
  L1 term adds no curvature), then ``p = pi(d; -pg)``: the components whose
  sign disagrees with ``-pg`` set to nought;
* the orthant ``xi = sign(w)``, ``sign(-pg)`` at a zero, and every trial
  point projected onto it: ``pi(w + alpha p; xi)``;
* backtracking, ``alpha`` halved (their ``beta = 0.5``) until
  ``F(w') <= F(w) + c1 * pg . (w' - w)`` with ``c1 = 1e-4`` (their
  ``gamma``).

Departures from the paper, each one the program's (``optimize/owlqn.py``)
and kept so that the two can be followed step by step:

1. the first trial step is ``1 / max(|pg|, 1)`` until a pair is stored
   (the paper: ``1 / |pg|`` at the first iteration), and 1 afterwards;
2. where ``p . pg >= 0`` after the alignment (every component disagreed,
   or the history went bad) the direction falls back to ``-pg`` (the paper
   needs no fallback: its ``H`` is positive definite by construction);
3. a curvature pair is kept only when the search succeeded and
   ``s . y > 1e-10 * |s| |y|`` (the paper keeps every pair);
4. the search gives up after ``max_line_search_steps`` trials; the fit then
   ends where it stands (``stalled``), with fewer steps than asked.

``rounding`` (on the smooth part, and on the ``w`` whose absolute values are
summed) puts a lower precision in the reference's place: the control.
``l1_in_search=False`` and ``project=False`` plant two faults only this
optimizer can have: the L1 term left out of the value the line search
compares, and the trial points left unprojected.
"""

from __future__ import annotations

import math

import numpy as np

from benchmark.reference import LogisticL2, Workers, _two_loop

C1 = 1e-4  # Andrew & Gao's gamma; Nocedal & Wright's c1
SHRINK = 0.5  # Andrew & Gao's beta
SY_GUARD = 1e-10  # departure 3


class ElasticNet:
    """``F(w) = smooth(w) + l1 * |w|_1``; ``smooth`` is a ``LogisticL2``
    (its ``l2`` is the elastic net's L2 part)."""

    def __init__(self, smooth: LogisticL2, l1: float):
        self.smooth, self.l1 = smooth, float(l1)
        self.par: Workers = smooth.par

    def abs_sum(self, w) -> float:
        par, r = self.par, self.smooth.r
        return math.fsum(par.map(lambda sl: float(np.abs(r(w[sl])).sum()),
                                 par.slices(w.shape[0])))

    def smooth_value(self, w) -> float:
        """``LogisticL2.value_grad``'s value without its ``X^T d``."""
        s = self.smooth
        m = s.margins(w)
        f = s.scale * float(np.sum(np.logaddexp(0.0, m) - s.y * m))
        return f + 0.5 * s.l2 * self.par.dot(w, w)

    def value(self, w) -> float:
        return self.smooth_value(w) + self.l1 * self.abs_sum(w)

    def value_grad(self, w):
        """-> (F(w), gradient of the smooth part)."""
        f, g = self.smooth.value_grad(w)
        return f + self.l1 * self.abs_sum(w), g

    def pseudo_gradient(self, w, g):
        """Andrew & Gao eq. 4, by blocks."""
        par, l1 = self.par, self.l1
        out = np.empty_like(g)

        def one(sl):
            right, left = g[sl] + l1, g[sl] - l1
            at_zero = np.where(right < 0, right,
                               np.where(left > 0, left, 0.0))
            out[sl] = np.where(w[sl] > 0, right,
                               np.where(w[sl] < 0, left, at_zero))

        par.map(one, par.slices(w.shape[0]))
        return out

    def value_pseudo_gradient(self, w):
        """-> (F(w), pseudo-gradient at w)."""
        F, g = self.value_grad(w)
        return F, self.pseudo_gradient(w, g)


def _align(par: Workers, p, pg):
    """``pi(p; -pg)``, in place: the components of ``p`` that do not point
    along ``-pg`` are set to nought."""
    def one(sl):
        p[sl][p[sl] * pg[sl] >= 0] = 0.0
    par.map(one, par.slices(p.shape[0]))
    return p


def _orthant(par: Workers, w, pg):
    """``xi``: ``sign(w)``, and ``sign(-pg)`` where ``w`` is nought."""
    xi = np.empty_like(w)

    def one(sl):
        xi[sl] = np.where(w[sl] != 0, np.sign(w[sl]), np.sign(-pg[sl]))
    par.map(one, par.slices(w.shape[0]))
    return xi


def _trial(par: Workers, w, alpha, p, xi):
    """``pi(w + alpha p; xi)``, or ``w + alpha p`` where ``xi`` is None."""
    out = np.empty_like(w)

    def one(sl):
        t = w[sl] + alpha * p[sl]
        if xi is not None:
            t[t * xi[sl] <= 0] = 0.0
        out[sl] = t
    par.map(one, par.slices(w.shape[0]))
    return out


def owlqn_steps(obj: ElasticNet, w0: np.ndarray, steps: int,
                history: int = 10, max_line_search_steps: int = 25,
                l1_in_search: bool = True, project: bool = True):
    """Follow at most ``steps`` iterations from ``w0`` (fewer where a search
    fails: departure 4). -> (w, [F after each step], [|pseudo-gradient|
    after each step], [trials of each step])."""
    par = obj.par
    w = np.asarray(w0, np.float64).copy()
    F, g = obj.value_grad(w)
    search_value = obj.value if l1_in_search else obj.smooth_value
    if not l1_in_search:
        F = obj.smooth_value(w)
    pairs, values, pgnorms, trials = [], [], [], []
    for _ in range(steps):
        pg = obj.pseudo_gradient(w, g)
        p = _align(par, _two_loop(par, pg, pairs), pg)
        if not par.dot(p, pg) < 0:  # departure 2
            p = par.scale(par.copy(pg), -1.0)
        xi = _orthant(par, w, pg) if project else None
        alpha = 1.0 if pairs else 1.0 / max(par.norm(pg), 1.0)  # departure 1
        ok, n = False, 0
        while not ok and n < max_line_search_steps:
            w_new = _trial(par, w, alpha, p, xi)
            F_new = search_value(w_new)
            step = par.axpy(par.copy(w_new), -1.0, w)
            ok = F_new <= F + C1 * par.dot(pg, step)
            n += 1
            if not ok:
                alpha *= SHRINK
        trials.append(n)
        if not ok:  # departure 4: the state stays, the fit ends
            w_new, F_new, step = w, F, np.zeros_like(w)
        g_new = obj.smooth.value_grad(w_new)[1]
        y = par.axpy(par.copy(g_new), -1.0, g)
        sy = par.dot(step, y)
        if ok and sy > SY_GUARD * max(par.norm(step) * par.norm(y),
                                      np.finfo(np.float64).tiny):
            pairs = (pairs + [(step, y, 1.0 / sy)])[-history:]
        w, F, g = w_new, F_new, g_new
        values.append(F)
        pgnorms.append(par.norm(obj.pseudo_gradient(w, g)))
        if not ok:
            break
    return w, values, pgnorms, trials
