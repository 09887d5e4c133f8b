"""Tier-1's hold on the traced run's reduction (``benchmark/trace_reduce``):
a window of the GLMix cell's size has to reduce in seconds, or a
``--trace 1`` run of ``glmix-ml20m.cd-sweep`` runs past the 1,200 s it is
allowed (PR 31's did). A copy of ``benchmark/tests/test_trace_reduce.py::
test_a_glmix_window_reduces_in_a_minute``, which tier-1 does not run."""

import random
import time

from benchmark import trace_reduce as tr


def window_like(n_ops, n_host, n_programs, seconds=20.0):
    """Events of a closed-loop window's sizes: ops back to back with a
    short gap after each, program runs and host spans end to end."""
    rng = random.Random(1)
    span, dt = seconds * 1e9, seconds * 1e9 / n_ops
    return {0: {"ops": [(f"%fusion.{i % 50} = f32[8] fusion(f32[8] %x)",
                         i * dt, i * dt + dt * rng.uniform(0.2, 0.999))
                        for i in range(n_ops)],
                "modules": [(f"jit_prog_{i % 16}(123)", i * span / n_programs,
                             (i + 0.9) * span / n_programs)
                            for i in range(n_programs)]},
            "host": [(f"PjitFunction(x{i})", i * span / n_host,
                      (i + 0.7) * span / n_host) for i in range(n_host)]}


def test_a_glmix_window_reduces_in_a_minute():
    # the window of glmix-ml20m.cd-sweep since the Newton step's LU went
    # (PR 33) holds 652,976 ops, 12,258 host spans and 2,304 program runs:
    # 8 runs where it held 5, ten small fusions a column of every solve
    events = window_like(700_000, 13_000, 2_500)
    t = time.perf_counter()
    s = tr.reduce_events(events)
    assert time.perf_counter() - t < 60
    assert len(s["top_gaps"]) == 10
    assert len(s["piece_gaps_s"]) == 2_500 // 16 - 1 + (2_500 % 16 > 0)
