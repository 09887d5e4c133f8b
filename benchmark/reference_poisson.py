"""The plain reference of the count fits: the L2 Poisson objective with a
log-exposure offset a row, numpy/scipy, float64.

    sum_i exp(m_i) - y_i m_i + 0.5 * l2 * |w|^2,    m = X w + offset

— upstream Photon ML's ``PoissonLossFunction`` (``exp(m) - y m``, the
label-only term ``log(y!)`` dropped) with the record's ``offset`` in the
margin. It imports nothing of the program and takes nothing the program
made. ``benchmark/reference.tron_steps`` follows a fit of it: that function
asks an objective for ``value_grad``, ``hvp``, ``diag_hessian`` and ``par``
alone, so the published TRON is stated once for both losses.

The products ``X v`` and ``X^T d`` over one-hot rows are
``reference.LogisticL2``'s blocks (the class is that one with the three
derivatives of another loss); ``rounding``, ``rows`` and ``scale`` mean what
they mean there: a lower precision in the reference's place (the control),
and the fault "part of the batch left out, the rest counted ``scale``
times".

Departures from the published algorithm, all of them ``tron_steps``' own
and shared with the logistic cell: the trust region is measured in the
norm of the Jacobi diagonal (LIBLINEAR's newer TRON), the diagonal is
floored at float32's epsilon times its largest entry, and CG stops at
``|r| <= 0.1 |g|`` or the boundary. One thing is this loss's: ``exp`` is
not bounded, so a trial point far outside the region where the quadratic
model holds can evaluate to ``inf`` (its gradient then holds ``inf`` and
``nan``). No clamp is put on it: ``tron_steps`` reads ``actred = -inf``,
refuses the step and shrinks the radius, and the trial's gradient is thrown
away. The warnings numpy would print there are silenced, nothing else.
"""

from __future__ import annotations

from typing import Callable, Optional

import numpy as np

from benchmark import reference


class PoissonL2(reference.LogisticL2):
    """``labels`` are counts; ``offsets`` [rows] are added to ``X w``."""

    def __init__(self, indices: np.ndarray, labels: np.ndarray,
                 offsets: np.ndarray, dim: int, l2: float,
                 workers: reference.Workers, rows: Optional[slice] = None,
                 scale: float = 1.0, rounding: Optional[Callable] = None):
        super().__init__(indices, labels, dim, l2, workers, rows=rows,
                         scale=scale, rounding=rounding)
        offsets = np.asarray(offsets, np.float64)
        self.offsets = self.r(offsets if rows is None else offsets[rows])

    def eta(self, w):
        """The linear predictor a row: ``X w + offset``."""
        return self.r(self.margins(w) + self.offsets)

    def d2(self, w):
        """The loss's second derivative a row: exp(m)."""
        with np.errstate(over="ignore"):
            return np.exp(self.eta(w))

    def value_grad(self, w):
        m = self.eta(w)
        with np.errstate(over="ignore", invalid="ignore"):
            mu = np.exp(m)
            f = self.scale * float(np.sum(mu - self.y * m))
            g = self._xt(self.r(mu - self.y))
        if self.scale != 1.0:
            self.par.scale(g, self.scale)
        self.par.axpy(g, self.l2, w)
        return f + 0.5 * self.l2 * self.par.dot(w, w), self.r(g)

    def hvp(self, w, v):
        hv = self._xt(self.r(self.d2(w) * self.margins(v)))
        if self.scale != 1.0:
            self.par.scale(hv, self.scale)
        return self.r(self.par.axpy(hv, self.l2, v))

    def diag_hessian(self, w):
        diag = self._xt(self.r(self.d2(w)))
        diag *= self.scale
        diag += self.l2
        return diag
