"""The whole sweep's share of the chip's peak FLOP/s: the operations a
GLMix sweep needs (benchmark/flops_bytes_game.py, from shapes) x sweeps per
second, over the published bf16 peak. A sparse GLM and 21- to 36-wide
Newton systems: about 1e-3 %, printed unrounded."""

from benchmark import flops_bytes_game


def read(run):
    if run.peaks is None or run.seconds <= 0 or run.passes <= 0:
        return None
    flops = flops_bytes_game.sweep_flops(run.shapes) * run.passes
    return (100.0 * flops / run.seconds
            / (run.chips * run.peaks["flops_per_s"]))
