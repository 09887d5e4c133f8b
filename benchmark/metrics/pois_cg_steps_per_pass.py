"""CG steps per TRON outer iteration: sum over the window's fits of
``cg_steps`` (one Hessian-vector product each, through the whole data) over
the sum of their iterations. What a better preconditioner or a cheaper HVP
would move, told apart: this one counts steps, ``pois_pass_ms`` prices them.
Read from the program's fit records; nothing where the program keeps no
such counter."""

import os

from benchmark import harness

_per_pass = harness.load_module(os.path.join(
    os.path.dirname(os.path.abspath(__file__)),
    "enet_trials_per_pass.py")).per_pass


def read(run):
    return _per_pass(run, "cg_steps")
