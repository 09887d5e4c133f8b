"""Megabytes the GAME path uploaded inside a sweep, mean over the window's
sweeps: the program counts them where it moves them. Resident tables leave
the scalars of a step; a run's offsets, uploaded before its first sweep,
are not inside one."""

from benchmark import flops_bytes_game


def read(run):
    sweeps = flops_bytes_game.window_sweeps(run)
    if not sweeps:
        return None
    return sum(s["h2d_bytes"] for s in sweeps) / len(sweeps) / 1e6
