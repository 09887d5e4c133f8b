"""`criteo-enet.fit`'s share of the chip's peak FLOP/s, computed as
``fit_mfu_pct`` computes it (the minimal pass of benchmark/flops_bytes.py:
one ``X v`` and one ``X^T d`` x passes per second over the bf16 peak), so
that the elastic-net cell's share compares with the L2 cells': what OWL-QN's
black-box line search and orthant arithmetic add shows as a lower share."""

import os

from benchmark import harness

read = harness.load_module(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "fit_mfu_pct.py")).read
