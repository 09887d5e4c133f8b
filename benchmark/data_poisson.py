"""The traffic generator of the count fits: ``data.py``'s Criteo-shaped rows
with a count and an exposure a row in the binary label's place.

The feature matrix is ``data.draw_problem``'s, column for column (the same
``data_seed`` gives ``criteo-lr-tron``'s rows), and ``--seed`` draws
``data.draw_layout``'s column bijection and row permutation, so that a seed
changes the layout and not the problem. From a stream of their own, seeded
by ``data_seed``, come

* the planted vector ``w* ~ N(0, w_scale^2)`` a column,
* the log-exposures ``log e_i ~ N(log_exposure_mean, log_exposure_sd^2)``,
* the counts ``y_i ~ Poisson(e_i exp(x_i . w*))``,

which is how a count model takes its exposure: ``log E[y] = log e + x . w``,
the log-exposure handed to the fit as the row's offset
(upstream Photon ML's ``TrainingExampleAvro.offset``). A Poisson fit is
invariant under the layout up to rounding, as a logistic one is. The same
seeds give the same arrays.
"""

from __future__ import annotations

import numpy as np

from benchmark import data

_STREAM = 0x706F6973  # "pois": the counts' stream beside the features'


def draw_counts(cols: np.ndarray, dim: int, data_seed: int, w_scale: float,
                log_exposure_mean: float, log_exposure_sd: float):
    """-> (counts [rows] float64, log-exposures [rows] float64) of the base
    problem's rows ``cols``, before the seed's relabelling."""
    rng = np.random.default_rng([data_seed, _STREAM])
    w_true = rng.normal(size=dim)
    w_true *= w_scale
    log_e = rng.normal(log_exposure_mean, log_exposure_sd, cols.shape[0])
    rate = np.exp(log_e + w_true[cols].sum(axis=1))
    return rng.poisson(rate).astype(np.float64), log_e


def poisson_rows(rows: int, dim: int, k: int, data_seed: int, seed: int,
                 w_scale: float, log_exposure_mean: float,
                 log_exposure_sd: float):
    """-> (indices [rows, k] int32, counts [rows] float64,
    log-exposures [rows] float64)."""
    cols, _ = data.draw_problem(rows, dim, k, data_seed)
    counts, log_e = draw_counts(cols, dim, data_seed, w_scale,
                                log_exposure_mean, log_exposure_sd)
    a, b, perm = data.draw_layout(rows, dim, seed)
    cols *= a
    cols += b
    cols %= dim
    return cols.astype(np.int32)[perm], counts[perm], log_e[perm]
