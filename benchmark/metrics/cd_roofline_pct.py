"""The least time the chip could take for the window's sweeps (the larger
of operations over peak FLOP/s and bytes over peak HBM bytes/s for the
minimal sweep of benchmark/flops_bytes_game.py: bytes bind) over the time
taken."""

from benchmark import flops_bytes_game as fb


def read(run):
    if run.peaks is None or run.seconds <= 0 or run.passes <= 0:
        return None
    s = run.shapes
    least = fb.least_seconds(fb.sweep_flops(s), fb.sweep_bytes(s), run.peaks)
    return 100.0 * least * run.passes / run.seconds
