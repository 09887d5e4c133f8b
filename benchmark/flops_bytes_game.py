"""The least operations and bytes of one GLMix sweep, from its shapes
alone: what the algorithm needs, nothing an implementation adds.

A sweep is the fixed effect's fit, the two random effects' Newton solves
and three rescorings:

* fixed effect: ``fixed_iterations + 1`` product pairs (``X v`` and
  ``X^T d``: the start's value and gradient, then one evaluation an
  iteration; further line-search trials and the two-loop recursion are the
  optimizer's own), priced as ``benchmark/flops_bytes.py`` prices a pass;
* a Newton iteration of one effect, over its real rows only (padding is
  the implementation's): ``2 D^2`` for a row's share of the Hessian and
  ``4 D`` for its margin and its share of the gradient, ``D^3 / 3`` an
  entity for the Cholesky solve; each row of the effect's table read once —
  its ``slots`` indices and values, its label, weight and offset;
* a rescoring: 2 a nonzero, its index and value read once and a score
  written a row.

Bytes are float32 / int32. Bytes bind everywhere.
"""

from __future__ import annotations

import statistics

from benchmark import flops_bytes


def window_sweeps(run):
    """The program's records of the window's sweeps (the runner took them
    from ``TrainingMetrics.sweep_records``); nothing where the program
    keeps none."""
    sweeps = run.window.get("sweeps")
    return sweeps if sweeps else None


def step_ms(run, kind, field: str = "seconds"):
    """Median over the window's sweeps of ``field`` summed over the steps
    of coordinate type ``kind`` (``None``: all), in milliseconds."""
    sweeps = window_sweeps(run)
    if not sweeps:
        return None
    return statistics.median(
        sum(c[field] for c in s["coordinates"]
            if kind is None or c["type"] == kind) * 1e3 for s in sweeps)


def _effects(s: dict):
    return (("user", s["users"], s["user_dim"], s["user_slots"]),
            ("item", s["items"], s["item_dim"], s["item_slots"]))


def newton_flops(s: dict) -> float:
    total = 0.0
    for _, entities, D, _ in _effects(s):
        total += s["random_iterations"] * (
            s["rows"] * (2.0 * D * D + 4.0 * D) + entities * D ** 3 / 3.0)
    return total


def newton_bytes(s: dict) -> float:
    return sum(s["random_iterations"] * s["rows"] * (slots * 8.0 + 12.0)
               for _, _, _, slots in _effects(s))


def sweep_flops(s: dict) -> float:
    pairs = s["fixed_iterations"] + 1
    rescoring = 2.0 * s["rows"] * (s["fixed_fields"] + s["user_slots"]
                                   + s["item_slots"])
    return (pairs * flops_bytes.pass_flops(s["rows"], s["fixed_fields"])
            + newton_flops(s) + rescoring)


def sweep_bytes(s: dict) -> float:
    pairs = s["fixed_iterations"] + 1
    rescoring = (s["rows"] * 4.0 * s["fixed_fields"]
                 + s["rows"] * 8.0 * (s["user_slots"] + s["item_slots"])
                 + 3 * s["rows"] * 4.0)
    return (pairs * flops_bytes.pass_bytes(s["rows"], s["fixed_fields"])
            + newton_bytes(s) + rescoring)


def least_seconds(flops: float, nbytes: float, peaks: dict) -> float:
    return max(flops / peaks["flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])
