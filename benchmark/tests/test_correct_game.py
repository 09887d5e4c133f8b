"""``correct`` of the GLMix cell has been shown to fail: the control (the
reference in bfloat16, in the program's place) and each fault a sweep can
have come out not correct, and the sound program comes out correct, at a
size a test run can hold. The faults are planted under the harness, in the
program's own functions, and the rest of a run is driven as it is on the
chip (``--rehearse`` only skips the look for a TPU and shrinks the
sizes). The metric readers are held to a window's records."""

import dataclasses
import io
import json
import os
import time
from contextlib import redirect_stderr, redirect_stdout
from types import SimpleNamespace

import numpy as np
import pytest

from benchmark import data_game, harness, readings_game, reference
from benchmark import flops_bytes_game as fb
from benchmark.runners import game_cd

ROOT = os.path.dirname(harness.BENCH_DIR)
CELL = "glmix-ml20m.cd-sweep"


def drive(seed=7):
    out, err = io.StringIO(), io.StringIO()
    with redirect_stdout(out), redirect_stderr(err):
        rc = harness.run_cell(ROOT, CELL, seed, 0.2, False, True,
                              time.perf_counter())
    assert rc == 0
    result = json.loads(out.getvalue().strip().splitlines()[-1])
    assert "compared train_loss_sweep1_gap" in err.getvalue()
    return result


def plant(monkeypatch, fault):
    import jax.numpy as jnp

    from photon_ml_tpu.game import descent

    real_train = descent.train_random_effect
    if fault == "stale_offsets":
        # the users' scores are left out of the items' offsets
        real = descent._ResidualTotal.excluding

        def excluding(self, name, scores):
            offs = real(self, name, scores)
            return offs - scores["per-user"] if name == "per-item" else offs

        monkeypatch.setattr(descent._ResidualTotal, "excluding", excluding)
    elif fault == "skip_bucket":  # one bucket comes back as it went in
        def train(data, offsets, **kw):
            fit = real_train(data, offsets, **kw)
            if data.effect_name != "per-user":
                return fit
            b = len(fit.coefficients) // 2
            before = (jnp.zeros_like(fit.coefficients[b])
                      if kw.get("w0") is None else kw["w0"][b])
            return dataclasses.replace(fit, coefficients=[
                before if i == b else c
                for i, c in enumerate(fit.coefficients)])

        monkeypatch.setattr(descent, "train_random_effect", train)
    elif fault == "half_rows":  # the heaviest user loses half its rows
        def train(data, offsets, **kw):
            if data.effect_name != "per-user":
                return real_train(data, offsets, **kw)
            rows = [(b.sample_idx >= 0).sum(axis=1) for b in data.buckets]
            at = max(range(len(rows)), key=lambda i: rows[i].max())
            e = int(np.argmax(rows[at]))
            weights = np.array(data.buckets[at].weights, copy=True)
            weights[e, : rows[at][e] // 2] = 0.0
            buckets = list(data.buckets)
            buckets[at] = dataclasses.replace(buckets[at], weights=weights)
            kw["placed"] = None  # the altered table is placed for the call
            return real_train(dataclasses.replace(data, buckets=buckets),
                              offsets, **kw)

        monkeypatch.setattr(descent, "train_random_effect", train)
    else:
        raise AssertionError(fault)


def test_sound_program_is_correct():
    result = drive()
    assert result["correct"] is True, result["compared"]
    assert result["device"]["platform"] == "cpu"
    assert result["metrics"] == {}  # no CPU time under a device metric's name
    assert result["failed"] == 0 and result["attempted"] >= 1
    assert result["compared"]["window_compiles"] == [0, 0]
    assert set(result["run"]["work"]["passes"]) == {2}


@pytest.mark.parametrize("fault", ["stale_offsets", "skip_bucket",
                                   "half_rows"])
def test_fault_is_not_correct(monkeypatch, fault):
    plant(monkeypatch, fault)
    result = drive()
    assert result["correct"] is False
    over = [k for k, (v, lim) in result["compared"].items()
            if lim is None or not v <= lim]
    assert over, result["compared"]


@pytest.fixture(scope="module")
def followed():
    cell = harness.load_cell(ROOT, CELL, rehearse=True)
    runner = game_cd.Runner(cell, 11)
    runner.rows = data_game.glmix_rows(cell.config, 11)
    with reference.Workers(2) as workers:
        ref = runner.reference(workers, 1)
        yield cell, runner, workers, ref, ref.follow(runner.sweeps,
                                                     runner.caps)


def test_control_in_bfloat16_is_not_correct(followed):
    cell, runner, workers, ref, trajectory = followed
    control = runner.reference(workers, 1,
                               rounding=reference.bfloat16_rounding)
    numbers = game_cd.compare(
        readings_game.stand_in(control, runner.sweeps, runner.caps), ref,
        trajectory)
    correct, compared = harness.decide(numbers, cell.limits)
    assert correct is False, compared
    # and the reference in its own place reads nought
    same = game_cd.compare(
        readings_game.stand_in(ref, runner.sweeps, runner.caps), ref,
        trajectory)
    assert harness.decide(same, cell.limits)[0] is True
    assert max(same.values()) == 0.0


@pytest.mark.parametrize("fault", ["stale_offsets", "skip_group",
                                   "half_rows"])
def test_reference_fault_is_not_correct(followed, fault):
    """The same faults planted in the reference: what the limits' upper
    readings are taken from (``benchmark/readings_game.py``)."""
    cell, runner, _, ref, trajectory = followed
    numbers = game_cd.compare(
        readings_game.stand_in(ref, runner.sweeps, runner.caps, fault=fault),
        ref, trajectory)
    assert harness.decide(numbers, cell.limits)[0] is False


# -- the readers -------------------------------------------------------------

SHAPES = {"rows": 1 << 22, "users": 29044, "items": 5721,
          "fixed_fields": 12, "fixed_iterations": 2, "random_iterations": 4,
          "user_dim": 21, "item_dim": 36, "user_slots": 11, "item_slots": 5,
          "sweeps_per_piece": 2}
PEAKS = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _sweep(fixed_s, random_s, h2d=8.0):
    def step(name, kind, seconds, **kw):
        return {"name": name, "type": kind, "seconds": seconds,
                "fit_seconds": 0.75 * seconds,
                "rescore_seconds": 0.25 * seconds, **kw}

    slots = dict(real_slots=1 << 22, padded_slots=1 << 21,
                 entities_solved=100, iterations_sum=400, iterations_max=4)
    return {"iteration": 0, "seconds": fixed_s + 2 * random_s,
            "h2d_bytes": h2d, "d2h_bytes": 64.0, "compiles": 0.0,
            "coordinates": [step("fixed", "fixed", fixed_s),
                            step("per-user", "random", random_s, **slots),
                            step("per-item", "random", random_s, **slots)]}


def _run(sweeps, seconds=10.0):
    return SimpleNamespace(
        window={"pieces": [], "rows": SHAPES["rows"], "sweeps": sweeps},
        seconds=seconds, passes=len(sweeps or ()), chips=1, peaks=PEAKS,
        shapes=SHAPES, trace=None, setup_s=1.0)


def _read(name, run):
    return harness.load_module(os.path.join(
        harness.BENCH_DIR, "metrics", name + ".py")).read(run)


def test_readers_read_the_sweep_records():
    run = _run([_sweep(1.0, 0.5), _sweep(1.2, 0.4), _sweep(1.1, 0.6)])
    assert _read("cd_fixed_ms", run) == pytest.approx(1100.0)
    assert _read("cd_random_ms", run) == pytest.approx(1000.0)
    assert _read("cd_rescore_ms", run) == pytest.approx(500.0)
    assert _read("re_padded_row_pct", run) == pytest.approx(100.0 / 3)
    assert _read("cd_h2d_mb_per_sweep", run) == pytest.approx(8e-6)
    least = fb.least_seconds(fb.newton_flops(SHAPES), fb.newton_bytes(SHAPES),
                             PEAKS)
    assert _read("re_newton_roofline_pct", run) == pytest.approx(
        100.0 * least * 3 / (0.75 * 3.0))
    assert 0 < _read("cd_roofline_pct", run) < 100
    assert 0 < _read("cd_mfu_pct", run) < _read("cd_roofline_pct", run)


def test_readers_find_nothing_without_sweep_records():
    """The parent's program keeps no sweep records: nothing is reported,
    nothing raises."""
    for sweeps in (None, []):
        run = _run(sweeps)
        for name in ("cd_fixed_ms", "cd_random_ms", "cd_rescore_ms",
                     "re_newton_roofline_pct", "re_padded_row_pct",
                     "cd_h2d_mb_per_sweep"):
            assert _read(name, run) is None
    run = _run(None)
    run.window.pop("sweeps")
    assert _read("cd_fixed_ms", run) is None


def test_a_sweep_is_bound_by_bytes_and_counts_what_it_says():
    s = SHAPES
    assert fb.sweep_bytes(s) / PEAKS["hbm_bytes_per_s"] > (
        fb.sweep_flops(s) / PEAKS["flops_per_s"])
    # the fixed effect: three product pairs of 4 flops a nonzero
    fixed = 3 * 4.0 * s["rows"] * 12
    newton = 4 * (s["rows"] * (2 * 21 ** 2 + 4 * 21) + 29044 * 21 ** 3 / 3
                  + s["rows"] * (2 * 36 ** 2 + 4 * 36) + 5721 * 36 ** 3 / 3)
    rescoring = 2.0 * s["rows"] * (12 + 11 + 5)
    assert fb.sweep_flops(s) == pytest.approx(fixed + newton + rescoring)
    assert fb.newton_flops(s) == pytest.approx(newton)
