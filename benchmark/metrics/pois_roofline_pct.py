"""`criteo-poisson-tron.fit`'s least time for the window's outer iterations
over the time taken, computed as ``fit_roofline_pct`` computes it (the
minimal pass of benchmark/flops_bytes.py; bytes bind)."""

import os

from benchmark import harness

read = harness.load_module(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "fit_roofline_pct.py")).read
