"""Median over the window's sweeps of the sweep's seconds less the seconds
its host spent blocked in fetches (``seconds - sync_wait_seconds``): the
host's own time in a sweep, an upper bound on the idle time of the chip the
host causes inside it. From the program's sweep records."""

import statistics

from benchmark import cd_runs


def read(run):
    sweeps = cd_runs.synced_sweeps(run)
    if not sweeps:
        return None
    return statistics.median(
        (s["seconds"] - s["sync_wait_seconds"]) * 1e3 for s in sweeps)
