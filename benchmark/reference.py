"""The plain reference of the fixed-effect fits: numpy/scipy, float64.

It imports nothing of the program and takes nothing the program made. It
states the L2 logistic objective over one-hot rows (the copy of
``chip_smoke.glm_reference``, made a class so that the optimizers can call
it), and follows the two published optimizers step by step:

* L-BFGS (Nocedal & Wright alg. 7.4/7.5: two-loop recursion, history ``m``,
  initial scaling ``s.y / y.y``) with a strong-Wolfe search (alg. 3.5/3.6,
  ``c1 = 1e-4``, ``c2 = 0.9``, doubling bracket, zoom by safeguarded
  quadratic interpolation), first trial step ``1 / max(|g|, 1)`` and 1
  afterwards;
* TRON (Lin & More; Lin, Weng & Keerthi 2008: trust-region Newton, Steihaug
  CG to ``|r| <= 0.1 |g|``, Jacobi-preconditioned, LIBLINEAR's constants).

``rounding`` puts a lower precision in the reference's place (the control):
every vector the objective reads or hands back is rounded through it.
``rows`` and ``scale`` plant the faults "part of the batch left out, the mean
taken over the rest" and "the exchange between chips left out".

Every run pays the reference's time after its window, so the arithmetic on
2^24-long vectors and the two sparse products run by blocks on a few
threads (numpy and scipy release the interpreter lock inside them), in place
where they can: a temporary that long costs more in page faults than the
arithmetic that fills it. The sums are the same sums, block by block.
"""

from __future__ import annotations

import math
import os
from concurrent.futures import ThreadPoolExecutor
from typing import Callable, Optional

import numpy as np
import scipy.sparse as sp
from scipy.special import expit



class Workers:
    """Blocked vector arithmetic on a thread pool the caller owns."""

    CHUNK = 1 << 18

    def __init__(self, threads: Optional[int] = None):
        if threads is None:
            threads = max(1, min(16, len(os.sched_getaffinity(0))))
        self.threads = threads
        self.pool = ThreadPoolExecutor(threads)

    def close(self) -> None:
        self.pool.shutdown(wait=True)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    def map(self, fn, items):
        return list(self.pool.map(fn, items))

    def slices(self, n: int):
        return [slice(i, min(i + self.CHUNK, n))
                for i in range(0, n, self.CHUNK)]

    def dot(self, a, b) -> float:
        return math.fsum(self.map(lambda sl: float(a[sl] @ b[sl]),
                                  self.slices(a.shape[0])))

    def dot3(self, a, m, b) -> float:
        return math.fsum(self.map(
            lambda sl: float(np.einsum("i,i,i->", a[sl], m[sl], b[sl])),
            self.slices(a.shape[0])))

    def norm(self, a) -> float:
        return math.sqrt(self.dot(a, a))

    def axpy(self, y, a, x):
        """y += a * x, in place."""
        def one(sl):
            y[sl] += a * x[sl]
        self.map(one, self.slices(y.shape[0]))
        return y

    def scale(self, y, a):
        def one(sl):
            y[sl] *= a
        self.map(one, self.slices(y.shape[0]))
        return y

    def copy(self, x):
        out = np.empty_like(x)

        def one(sl):
            out[sl] = x[sl]
        self.map(one, self.slices(x.shape[0]))
        return out


def bfloat16_rounding(x):
    import ml_dtypes

    return np.asarray(x, np.float32).astype(ml_dtypes.bfloat16).astype(
        np.float64)


class LogisticL2:
    """sum_i log(1 + exp(m_i)) - y_i m_i + 0.5 * l2 * |w|^2 over rows with
    ``k`` implicit ones each; m = X w. ``X`` is kept as blocks of rows and
    ``X^T`` as blocks of columns, one product a block and thread."""

    def __init__(self, indices: np.ndarray, labels: np.ndarray, dim: int,
                 l2: float, workers: Workers, rows: Optional[slice] = None,
                 scale: float = 1.0, rounding: Optional[Callable] = None):
        if rows is not None:
            indices, labels = indices[rows], labels[rows]
        n, k = indices.shape
        self.par = workers
        blocks = max(1, min(workers.threads, n // 1024))
        cuts = [n * b // blocks for b in range(blocks + 1)]

        def rows_block(b):
            lo, hi = cuts[b], cuts[b + 1]
            return sp.csr_matrix(
                (np.ones((hi - lo) * k), indices[lo:hi].reshape(-1),
                 np.arange(0, (hi - lo) * k + 1, k, dtype=np.int64)),
                shape=(hi - lo, dim))

        self.X = workers.map(rows_block, range(blocks))
        XT = sp.csr_matrix(
            (np.ones(n * k), indices.reshape(-1),
             np.arange(0, n * k + 1, k, dtype=np.int64)),
            shape=(n, dim)).T.tocsr()
        ccuts = [dim * b // blocks for b in range(blocks + 1)]
        self.XT = workers.map(lambda b: XT[ccuts[b]:ccuts[b + 1]],
                              range(blocks))
        self.y = np.asarray(labels, np.float64)
        self.l2, self.scale, self.dim = float(l2), float(scale), dim
        self.r = rounding if rounding is not None else (lambda x: x)

    def _x(self, v):
        return np.concatenate(self.par.map(lambda x: x @ v, self.X))

    def _xt(self, d):
        return np.concatenate(self.par.map(lambda xt: xt @ d, self.XT))

    def margins(self, v):
        return self.r(self._x(self.r(v)))

    def value_grad(self, w):
        m = self.margins(w)
        f = self.scale * float(np.sum(np.logaddexp(0.0, m) - self.y * m))
        g = self._xt(self.r(expit(m) - self.y))
        if self.scale != 1.0:
            self.par.scale(g, self.scale)
        self.par.axpy(g, self.l2, w)
        return f + 0.5 * self.l2 * self.par.dot(w, w), self.r(g)

    def hvp(self, w, v):
        s = expit(self.margins(w))
        hv = self._xt(self.r(s * (1.0 - s) * self.margins(v)))
        if self.scale != 1.0:
            self.par.scale(hv, self.scale)
        return self.r(self.par.axpy(hv, self.l2, v))

    def diag_hessian(self, w):
        s = expit(self.margins(w))
        diag = self._xt(self.r(s * (1.0 - s)))
        diag *= self.scale
        diag += self.l2
        return diag


# -- strong-Wolfe line search (Nocedal & Wright alg. 3.5 / 3.6) ------------

def _interpolate(a_lo, f_lo, dg_lo, a_hi, f_hi):
    denom = 2.0 * (f_hi - f_lo - dg_lo * (a_hi - a_lo))
    mid = 0.5 * (a_lo + a_hi)
    if denom == 0:
        return mid
    quad = a_lo - dg_lo * (a_hi - a_lo) ** 2 / denom
    lo, hi = min(a_lo, a_hi), max(a_lo, a_hi)
    width = hi - lo
    if (not math.isfinite(quad) or quad <= lo + 0.1 * width
            or quad >= hi - 0.1 * width):
        return mid
    return quad


def strong_wolfe(phi, f0, dphi0, alpha0, c1=1e-4, c2=0.9, max_evals=25,
                 alpha_max=1e6):
    """phi(alpha) -> (f, f'). -> (alpha, f(alpha), ok)."""
    if dphi0 >= 0:
        return 0.0, f0, False
    zoom = done = False
    alpha, f = alpha0, f0
    a_prev, f_prev, dg_prev = 0.0, f0, dphi0
    a_lo, f_lo, dg_lo, a_hi, f_hi = 0.0, f0, dphi0, alpha_max, f0
    i = 0
    while not done and i < max_evals:
        f, dg = phi(alpha)
        armijo_fail = f > f0 + c1 * alpha * dphi0
        curvature_ok = abs(dg) <= -c2 * dphi0
        if not zoom:
            fail = armijo_fail or (f >= f_prev and i > 0)
            done = (not fail) and curvature_ok
            if fail:  # zoom(previous, current)
                a_lo, f_lo, dg_lo, a_hi, f_hi = a_prev, f_prev, dg_prev, alpha, f
            else:  # zoom(current, previous) where the slope turned up
                a_lo, f_lo, dg_lo, a_hi, f_hi = alpha, f, dg, a_prev, f_prev
            zoom = fail or (not curvature_ok and dg >= 0)
            a_prev, f_prev, dg_prev = alpha, f, dg
            if not done:
                alpha = (_interpolate(a_lo, f_lo, dg_lo, a_hi, f_hi) if zoom
                         else min(2.0 * alpha, alpha_max))
        else:
            hi_update = armijo_fail or f >= f_lo
            done = (not hi_update) and curvature_ok
            if hi_update:
                a_hi, f_hi = alpha, f
            else:
                if not curvature_ok and dg * (a_hi - a_lo) >= 0:
                    a_hi, f_hi = a_lo, f_lo
                a_lo, f_lo, dg_lo = alpha, f, dg
            if not done:
                alpha = _interpolate(a_lo, f_lo, dg_lo, a_hi, f_hi)
        i += 1
    if done:
        return alpha, f, True
    return a_lo, f_lo, a_lo > 0  # best point that met Armijo, if any


# -- L-BFGS ----------------------------------------------------------------

def _two_loop(par: Workers, g, pairs):
    """pairs: [(s, y, rho)], oldest first."""
    q = par.copy(g)
    alphas = []
    for s, y, rho in reversed(pairs):
        a = rho * par.dot(s, q)
        par.axpy(q, -a, y)
        alphas.append(a)
    if pairs:
        s, y, _ = pairs[-1]
        yy = par.dot(y, y)
        if yy > 0:
            par.scale(q, par.dot(s, y) / yy)
    for (s, y, rho), a in zip(pairs, reversed(alphas)):
        par.axpy(q, a - rho * par.dot(y, q), s)
    return par.scale(q, -1.0)


def lbfgs_steps(obj: LogisticL2, w0: np.ndarray, steps: int,
                history: int = 10, max_line_search_steps: int = 25):
    """Follow ``steps`` iterations from ``w0``.
    -> (w, [loss after each step], [|grad| after each step])."""
    par = obj.par
    w = np.asarray(w0, np.float64).copy()
    f, g = obj.value_grad(w)
    pairs, losses, gnorms = [], [], []
    for _ in range(steps):
        p = _two_loop(par, g, pairs)
        if par.dot(p, g) >= 0:
            p = par.scale(par.copy(g), -1.0)
        trial = {}

        def phi(alpha):
            ft, gt = obj.value_grad(par.axpy(par.copy(w), alpha, p))
            trial.clear()  # keep the last trial's gradient only
            trial[alpha] = gt
            return ft, par.dot(gt, p)

        alpha0 = 1.0 if pairs else 1.0 / max(par.norm(g), 1.0)
        alpha, f_new, ok = strong_wolfe(phi, f, par.dot(p, g), alpha0,
                                        max_evals=max_line_search_steps)
        step = par.scale(p, alpha)
        par.axpy(w, 1.0, step)
        g_new = trial[alpha] if alpha in trial else obj.value_grad(w)[1]
        y = par.axpy(par.copy(g_new), -1.0, g)
        sy = par.dot(step, y)
        if ok and sy > 1e-10 * max(par.norm(step) * par.norm(y), 1e-300):
            pairs = (pairs + [(step, y, 1.0 / sy)])[-history:]
        elif not ok:
            pairs = []
        f, g = f_new, g_new
        losses.append(f)
        gnorms.append(par.norm(g))
    return w, losses, gnorms


# -- TRON ------------------------------------------------------------------

_ETA0, _ETA1, _ETA2 = 1e-4, 0.25, 0.75
_SIGMA1, _SIGMA2, _SIGMA3 = 0.25, 0.5, 4.0


def _steihaug_cg(par: Workers, hvp, g, delta, cg_tol, max_cg, m_diag):
    """Minimise g.s + 0.5 s.H.s within |s|_M <= delta. -> (s, r, HVPs)."""
    s = np.zeros_like(g)
    r = par.scale(par.copy(g), -1.0)
    d = r / m_diag
    rz = par.dot(r, d)
    i = 0
    while i < max_cg:
        Hd = hvp(d)
        dHd = par.dot(d, Hd)
        alpha = rz / dHd if dHd > 0 else 0.0
        ss, sd, dd = (par.dot3(s, m_diag, s), par.dot3(s, m_diag, d),
                      par.dot3(d, m_diag, d))
        # |s + alpha d|_M^2, without forming the trial point
        hit = dHd <= 0 or math.sqrt(
            max(ss + 2.0 * alpha * sd + alpha * alpha * dd, 0.0)) >= delta
        if hit:
            disc = math.sqrt(max(sd * sd + dd * (delta * delta - ss), 0.0))
            alpha = (-sd + disc) / max(dd, 1e-300)
        par.axpy(s, alpha, d)
        par.axpy(r, -alpha, Hd)
        i += 1
        if hit or par.norm(r) <= cg_tol:
            break
        z = r / m_diag
        rz_new = par.dot(r, z)
        par.scale(d, rz_new / max(rz, 1e-300))
        par.axpy(d, 1.0, z)
        rz = rz_new
    return s, r, i


def tron_steps(obj: LogisticL2, w0: np.ndarray, steps: int):
    """Follow ``steps`` outer iterations from ``w0``.
    -> (w, [loss after each step], [|grad| after each step], [HVPs])."""
    par = obj.par
    eps = float(np.finfo(np.float32).eps)  # the served dtype's guard

    def guard(md):
        return np.maximum(md, eps * max(float(md.max()), 1.0))

    w = np.asarray(w0, np.float64).copy()
    f, g = obj.value_grad(w)
    delta = par.norm(g)
    m_diag = guard(obj.diag_hessian(w))
    losses, gnorms, hvps = [], [], []
    for _ in range(steps):
        step, r, n_cg = _steihaug_cg(par, lambda v: obj.hvp(w, v), g, delta,
                                     0.1 * par.norm(g), max(w.shape[0], 20),
                                     m_diag)
        w_try = par.axpy(par.copy(step), 1.0, w)
        f_try, g_try = obj.value_grad(w_try)
        gs = par.dot(g, step)
        prered = 0.5 * (par.dot(step, r) - gs)
        actred = f - f_try
        snorm = math.sqrt(par.dot3(step, m_diag, step))
        denom = f_try - f - gs
        alpha = _SIGMA3 if denom <= 0 else max(_SIGMA1, -0.5 * gs / denom)
        if actred < _ETA0 * prered:
            delta = min(max(alpha, _SIGMA1) * snorm, _SIGMA2 * delta)
        elif actred < _ETA1 * prered:
            delta = max(_SIGMA1 * delta, min(alpha * snorm, _SIGMA2 * delta))
        elif actred < _ETA2 * prered:
            delta = max(_SIGMA1 * delta, min(alpha * snorm, _SIGMA3 * delta))
        else:
            delta = max(delta, min(alpha * snorm, _SIGMA3 * delta))
        if actred > _ETA0 * prered:
            w, f, g = w_try, f_try, g_try
            m_diag = guard(obj.diag_hessian(w))
        losses.append(f)
        gnorms.append(par.norm(g))
        hvps.append(n_cg)
    return w, losses, gnorms, hvps
