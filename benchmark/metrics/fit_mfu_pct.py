"""The whole step's share of the chips' peak FLOP/s: the operations the
algorithm needs per pass (benchmark/flops_bytes.py, from shapes) x passes
per second, over chips x the published bf16 peak. About 1e-4 % for a sparse
GLM: printed unrounded, never 0."""

from benchmark import flops_bytes


def read(run):
    s = run.shapes
    if run.peaks is None or run.seconds <= 0 or run.passes <= 0:
        return None
    flops = flops_bytes.pass_flops(s["rows"], s["k"]) * run.passes
    return (100.0 * flops / run.seconds
            / (run.chips * run.peaks["flops_per_s"]))
