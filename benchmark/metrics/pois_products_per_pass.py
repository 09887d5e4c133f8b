"""Data products per TRON outer iteration in `criteo-poisson-tron.fit`: pairs
of one ``X v`` and one ``X^T d`` (an HVP a CG step, the trial point's
``(f, g)``, and ``(f0, g0)`` once a fit) over the window's iterations, as
``fit_products_per_pass`` reads the program's fit records."""

import os

from benchmark import harness

read = harness.load_module(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "fit_products_per_pass.py")).read
