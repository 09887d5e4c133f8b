"""Refused steps per TRON outer iteration: sum over the window's fits of
``rejected_steps`` (iterations whose trial point the trust region refused:
CG and a whole ``(f, g)`` spent, ``w`` kept, the radius shrunk, no diagonal)
over the sum of their iterations. 0 in a logistic fit; what a first radius
fitted to ``exp`` would cut. Read from the program's fit records; nothing
where the program keeps no such counter."""

import os

from benchmark import harness

_per_pass = harness.load_module(os.path.join(
    os.path.dirname(os.path.abspath(__file__)),
    "enet_trials_per_pass.py")).per_pass


def read(run):
    return _per_pass(run, "rejected_steps")
