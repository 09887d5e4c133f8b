"""`criteo-enet.fit`'s median over the window's fits of fit seconds /
passes (host clock around the fetched result), as ``fit_pass_ms`` takes
it: an OWL-QN pass with its trials, its gradient, its two-loop and its
orthant arithmetic."""

import os

from benchmark import harness

read = harness.load_module(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "fit_pass_ms.py")).read
