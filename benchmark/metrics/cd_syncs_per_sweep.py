"""Blocking fetches a sweep, mean over the window's sweeps: each is a
``cd.fetch`` span, a point where the host waits for the chip and the chip
then waits for the host. From the program's sweep records (``syncs``)."""

from benchmark import cd_runs


def read(run):
    sweeps = cd_runs.synced_sweeps(run)
    if not sweeps:
        return None
    return sum(s["syncs"] for s in sweeps) / len(sweeps)
