"""``X v`` products per OWL-QN pass: sum over the window's fits of
``gather_products`` (a gather of rows x k table entries each: every trial of
the line search, the accepted point's gradient, ``(f0, g0)``) over the sum of
their passes — what a margin-space or cheaper line search would cut. Read
from the program's fit records."""

import os

from benchmark import harness

_trials = harness.load_module(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "enet_trials_per_pass.py"))


def read(run):
    return _trials.per_pass(run, "gather_products")
