"""The least time the chips could take for the window's passes (the larger
of operations over peak FLOP/s and bytes over peak HBM bytes/s, for the
minimal pass of benchmark/flops_bytes.py: bytes bind) over the time taken."""

from benchmark import flops_bytes


def read(run):
    s = run.shapes
    if run.peaks is None or run.seconds <= 0 or run.passes <= 0:
        return None
    least, _ = flops_bytes.pass_roofline_seconds(s["rows_per_chip"], s["k"],
                                                 run.peaks)
    return 100.0 * least * run.passes / run.seconds
