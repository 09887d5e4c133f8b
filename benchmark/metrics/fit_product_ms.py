"""Median over the window's fits of fit seconds (the piece's ``t1 - t0``, as
``fit_pass_ms`` takes them) over that fit's product pairs
((``gather_products`` + ``transpose_products``) / 2): the time of one
``X v`` + ``X^T d`` pair with everything the optimizer adds spread over it.
Comparable between the L-BFGS and the TRON cell, which ``fit_pass_ms`` is
not. Counters from the program's fit records, seconds from the pieces'
clock."""

import os
import statistics

from benchmark import harness

_products = harness.load_module(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "fit_products_per_pass.py"))


def read(run):
    fits = _products.window_fits(run)
    if not fits:
        return None
    per_pair = []
    for piece, record in zip(run.window["pieces"], fits):
        pairs = _products.product_pairs(record)
        if pairs is None:
            return None
        if pairs > 0:
            per_pair.append((piece["t1"] - piece["t0"]) / pairs * 1e3)
    return statistics.median(per_pair) if per_pair else None
