"""Median over the window's runs of the ``cd.finish`` span: the model's
build after the last sweep (a fetch of each bucket's coefficients) and the
history. From the program's run records."""

import statistics

from benchmark import cd_runs


def read(run):
    runs = cd_runs.window_runs(run)
    if not runs:
        return None
    return statistics.median(r["finish_seconds"] * 1e3 for r in runs)
