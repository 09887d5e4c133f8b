"""Median over the window's sweeps of the two random-effect coordinates'
steps together (the ``cd.coordinate`` spans of type random: solve,
rescoring, residual), from the program's sweep records."""

from benchmark import flops_bytes_game


def read(run):
    return flops_bytes_game.step_ms(run, "random")
