"""Median over the window's sweeps of the fixed-effect coordinate's step
(the ``cd.coordinate`` span of type fixed: fit, rescoring, residual), from
the program's sweep records."""

from benchmark import flops_bytes_game


def read(run):
    return flops_bytes_game.step_ms(run, "fixed")
