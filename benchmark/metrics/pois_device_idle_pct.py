"""`criteo-poisson-tron.fit`'s device idle share: 1 - (union of the device's
op intervals over the traced window), as ``fit_device_idle_pct`` reads
``run.trace``."""

import os

from benchmark import harness

read = harness.load_module(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "fit_device_idle_pct.py")).read
