"""The one traffic generator for fixed-effect fits: Criteo-shaped rows.

``k`` categorical fields a row, hashed into one ``dim`` space, field values
heavy-tailed (log-uniform rank), labels from a planted weight vector: the
draw of ``chip_smoke.glm_rows`` (copied; the original is listed in PERF.md
for a later PR to delete), split in two so that every ``--seed`` gives the
same amount of work:

* the *problem* (which field values meet in which row, the planted vector,
  the labels) is drawn from the configuration's ``data_seed``;
* ``--seed`` draws how it is laid out: a bijection of the hashed column ids
  (``col -> (a*col + b) mod dim``, ``a`` coprime to ``dim``) and a
  permutation of the rows.

A logistic fit is invariant under both up to rounding, so TRON's CG counts
and L-BFGS's line-search evaluations are the same for every seed, while the
memory pattern of every gather, the sort behind the CSC view and the rows
each chip holds all change. The same seed gives the same arrays.
"""

from __future__ import annotations

import math

import numpy as np

_HASH_MULT = 2654435761
_FIELD_MULT = 40503


def draw_problem(rows: int, dim: int, k: int, data_seed: int):
    """-> (columns [rows, k] int64, labels [rows] float64) of the base
    problem, before the seed's relabelling."""
    rng = np.random.default_rng(data_seed)
    w_true = rng.normal(size=dim)
    w_true *= 0.5
    # in place throughout: a fresh [rows, k] temporary costs more in page
    # faults than the arithmetic that fills it
    u = rng.random((rows, k))
    u *= math.log(dim)
    np.exp(u, out=u)
    cols = u.astype(np.int64)  # the value's rank within its field
    cols *= _HASH_MULT
    cols += np.arange(k, dtype=np.int64)[None, :] * _FIELD_MULT
    cols %= dim
    logits = w_true[cols].sum(axis=1)
    labels = (rng.random(rows) < 1.0 / (1.0 + np.exp(-logits)))
    return cols, labels.astype(np.float64)


def draw_layout(rows: int, dim: int, seed: int):
    """-> (a, b, row permutation): the part of the inputs ``--seed`` draws."""
    rng = np.random.default_rng(seed)
    while True:
        a = int(rng.integers(1, dim))
        if math.gcd(a, dim) == 1:
            break
    b = int(rng.integers(0, dim))
    return a, b, rng.permutation(rows)


def criteo_rows(rows: int, dim: int, k: int, data_seed: int, seed: int):
    """-> (indices [rows, k] int32, labels [rows] float64)."""
    cols, labels = draw_problem(rows, dim, k, data_seed)
    a, b, perm = draw_layout(rows, dim, seed)
    cols *= a
    cols += b
    cols %= dim
    return cols.astype(np.int32)[perm], labels[perm]
