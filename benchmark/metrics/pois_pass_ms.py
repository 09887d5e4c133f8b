"""`criteo-poisson-tron.fit`'s median over the window's fits of fit seconds /
outer iterations (host clock around the fetched result), as ``fit_pass_ms``
takes it: a TRON iteration with its CG steps, its trial point and, where the
step was accepted, its Jacobi diagonal."""

import os

from benchmark import harness

read = harness.load_module(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "fit_pass_ms.py")).read
