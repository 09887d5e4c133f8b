"""Padded row slots over all row slots the window's random-effect solves
read, both coordinates: what the size buckets cost. From the program's
sweep records (host counts of the resident tables)."""

from benchmark import flops_bytes_game


def read(run):
    sweeps = flops_bytes_game.window_sweeps(run)
    if not sweeps:
        return None
    steps = [c for s in sweeps for c in s["coordinates"]
             if c["type"] == "random"]
    real = sum(c["real_slots"] for c in steps)
    padded = sum(c["padded_slots"] for c in steps)
    return 100.0 * padded / (real + padded) if real + padded else None
