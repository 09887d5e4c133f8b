"""`criteo-poisson-tron.fit`'s share of the chip's peak FLOP/s, computed as
``fit_mfu_pct`` computes it (the minimal pass of benchmark/flops_bytes.py:
one ``X v`` and one ``X^T d`` x outer iterations per second over the bf16
peak), so that the share compares with ``criteo-lr-tron.fit``'s: the CG
steps, the refused trials and the diagonals of an iteration show as a lower
share. ``exp`` is not counted: the minimal pass is the data products."""

import os

from benchmark import harness

read = harness.load_module(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "fit_mfu_pct.py")).read
