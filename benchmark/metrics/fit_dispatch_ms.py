"""Median over the window's fits of ``dispatch_s``: the host's seconds inside
``fit_distributed`` from entry to the return of the asynchronous call
(``shard_batch``, the runner cache, the jitted call's dispatch) — the inside
of ``fit_gap_ms``. From the program's ``fit`` records."""

import os
import statistics

from benchmark import harness

_products = harness.load_module(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "fit_products_per_pass.py"))


def read(run):
    fits = _products.window_fits(run)
    if not fits:
        return None
    return statistics.median(r["dispatch_s"] for r in fits) * 1e3
