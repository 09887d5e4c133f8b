"""Line-search trials per OWL-QN pass: sum over the window's fits of
``line_search_trials`` (every trial point the backtracking searches
evaluated: one whole ``X v`` each, the black-box search's price) over the
sum of their passes. 1 where no search backtracks. Read from the program's
fit records; nothing where the program keeps no such counter."""

import os

from benchmark import harness

_products = harness.load_module(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "fit_products_per_pass.py"))


def per_pass(run, field):
    """Sum of a fit record's ``field`` over the window's fits, over their
    passes; None where a record lacks it."""
    fits = _products.window_fits(run)
    if not fits:
        return None
    counts = [r.get(field) for r in fits]
    passes = sum(r.get("iterations") or 0 for r in fits)
    if any(c is None for c in counts) or passes <= 0:
        return None
    return sum(counts) / passes


def read(run):
    return per_pass(run, "line_search_trials")
