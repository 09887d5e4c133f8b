"""Median over the window's sweeps of the three rescorings together (the
``fe.rescore`` and ``re.rescore`` spans, each closed by the fetched score
delta), from the program's sweep records. Part of ``cd_fixed_ms`` and
``cd_random_ms``, not beside them."""

from benchmark import flops_bytes_game


def read(run):
    return flops_bytes_game.step_ms(run, None, "rescore_seconds")
