"""The least time the chip could take for the window's Newton solves of
both random effects (real rows only; benchmark/flops_bytes_game.py: bytes
bind) over the seconds of the program's ``re.solve`` spans, which close on
a fetched value. Host seconds around the solves, so the share can only
read low."""

from benchmark import flops_bytes_game as fb


def read(run):
    sweeps = fb.window_sweeps(run)
    if not sweeps or run.peaks is None:
        return None
    taken = sum(c["fit_seconds"] for s in sweeps for c in s["coordinates"]
                if c["type"] == "random")
    if taken <= 0:
        return None
    least = fb.least_seconds(fb.newton_flops(run.shapes),
                             fb.newton_bytes(run.shapes), run.peaks)
    return 100.0 * least * len(sweeps) / taken
