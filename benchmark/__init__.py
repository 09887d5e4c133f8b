"""The benchmark of photon_ml_tpu: the yardstick later PRs are measured by.

Found by name from ``BENCHMARK.json``: a configuration's file under
``configs/``, a traffic mix's parameters under ``traffic/``, the limits of a
cell's ``correct`` under ``limits/``, a metric's reader under ``metrics/``
and a runner under ``runners/``. A new cell, configuration, runner or metric
is new files and new entries; no file here needs an edit for it.
"""
