"""Median over the window's runs of the ``cd.prepare`` span: everything a
``CoordinateDescent.run`` does before its first sweep (states from the
``dataset_cache``, zero score vectors, the offsets' upload). From the
program's run records."""

import statistics

from benchmark import cd_runs


def read(run):
    runs = cd_runs.window_runs(run)
    if not runs:
        return None
    return statistics.median(r["prepare_seconds"] * 1e3 for r in runs)
