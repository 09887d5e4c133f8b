"""``correct`` of ``criteo-enet.fit`` has been shown to fail: the control
(the reference in bfloat16, in the program's place), the three faults an
elastic-net fit by OWL-QN can have, planted in the program under the
harness, and a state handed back unchanged all come out not correct, and
the sound program comes out correct, at the rehearsal's size. The limits
they are held to are read at that size, as the cell's own were read at its
size on the chip: four times the largest reading of the sound program over
three seeds (float32 on the CPU against the float64 reference)."""

import importlib
import io
import json
import os
import subprocess
import sys
import time
from contextlib import redirect_stderr, redirect_stdout

import numpy as np
import pytest

from benchmark import data, harness, readings_enet, reference

ROOT = os.path.dirname(harness.BENCH_DIR)
CELL = "criteo-enet.fit"
enet = harness.load_module(os.path.join(harness.BENCH_DIR, "runners",
                                        "glm_fit_enet.py"))


def drive(seed=7):
    out, err = io.StringIO(), io.StringIO()
    with redirect_stdout(out), redirect_stderr(err):
        rc = harness.run_cell(ROOT, CELL, seed, 0.2, False, True,
                              time.perf_counter())
    assert rc == 0
    result = json.loads(out.getvalue().strip().splitlines()[-1])
    assert "compared fit_support_gap" in err.getvalue()
    return result


def numbers_of(result):
    return {k: v for k, (v, _) in result["compared"].items()
            if k != "window_compiles"}


@pytest.fixture(scope="module")
def limits_here():
    sound = [numbers_of(drive(seed)) for seed in (1, 2, 3)]
    return {k: 4.0 * max(s[k] for s in sound) for k in sound[0]}


def plant(monkeypatch, fault):
    from photon_ml_tpu.parallel import data_parallel as dp

    # the module: the package's attribute of that name is the function
    owlqn = importlib.import_module("photon_ml_tpu.optimize.owlqn")

    real_fit, real_search = dp.fit_distributed, owlqn.backtracking

    def fit(objective, batch, mesh, w0, **kw):
        import jax
        import jax.numpy as jnp

        if fault == "unchanged":  # the step hands its state back
            res = real_fit(objective, batch, mesh, w0, **kw)
            f0, g0 = objective.value_and_grad(w0, batch, kw["l2"])
            F0 = f0 + kw["l1"] * jnp.sum(jnp.abs(w0))
            pg0 = jnp.linalg.norm(owlqn.pseudo_gradient(w0, g0, kw["l1"]))
            return res._replace(
                w=w0, value=F0, grad_norm=pg0,
                loss_history=jnp.full_like(res.loss_history, F0),
                grad_norm_history=jnp.full_like(res.grad_norm_history, pg0))
        if fault == "half_batch":  # the other half counted twice
            n = batch.num_examples // 2
            half = jax.tree.map(lambda a: np.asarray(a)[:n], batch)
            half = half.replace(weights=half.weights * 2.0)
            return real_fit(objective, half, mesh, w0, **kw)
        return real_fit(objective, batch, mesh, w0, **kw)

    def search(fun, w, p, f0, pg, **kw):
        import jax.numpy as jnp

        if fault == "no_projection":  # trial points leave the orthant
            return real_search(fun, w, p, f0, pg, **{**kw, "project": None})
        if fault == "no_l1_in_search":  # the search compares the smooth part
            l1 = 1.0  # the configuration's
            return real_search(
                lambda x: fun(x) - l1 * jnp.sum(jnp.abs(x)), w, p,
                f0 - l1 * jnp.sum(jnp.abs(w)), pg, **kw)
        return real_search(fun, w, p, f0, pg, **kw)

    monkeypatch.setattr(dp, "fit_distributed", fit)
    monkeypatch.setattr(owlqn, "backtracking", search)


def test_sound_program_is_correct(limits_here):
    result = drive()
    assert result["correct"] is True, result["compared"]  # the cell's limits
    assert harness.decide(numbers_of(result), limits_here)[0] is True
    assert result["device"]["platform"] == "cpu"
    assert result["metrics"] == {}  # no CPU time under a device metric's name
    assert result["failed"] == 0 and result["attempted"] >= 1
    work = result["run"]["work"]
    assert (work["l1"], work["l2"]) == (1.0, 1.0)
    assert 0 < work["nonzeros_median"] < 1 << 12
    assert work["trials_per_pass_median"] >= 1.0


@pytest.mark.parametrize("fault", ["unchanged", "half_batch",
                                   "no_l1_in_search", "no_projection"])
def test_fault_is_not_correct(monkeypatch, limits_here, fault):
    plant(monkeypatch, fault)
    result = drive()
    correct, compared = harness.decide(numbers_of(result), limits_here)
    assert correct is False
    over = [k for k, (v, lim) in compared.items() if not v <= lim]
    assert over, compared
    if fault == "no_projection":
        assert "fit_support_gap" in over and "fit_nonzero_gap" in over
    if fault == "no_l1_in_search":
        assert "loss_step1_gap" in over
    if fault == "unchanged":
        assert result["correct"] is False  # under the cell's limits too


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_control_in_bfloat16_is_not_correct(limits_here, seed):
    cell = harness.load_cell(ROOT, CELL, rehearse=True)
    runner = enet.Runner(cell, seed)
    runner.indices, runner.labels = data.criteo_rows(
        runner.rows, runner.dim, runner.k, int(cell.config["data_seed"]), seed)
    w0 = runner.start_point(1)
    with reference.Workers(2) as workers:
        obj = runner.reference_objective(workers)
        followed = runner.reference_fit(obj, w0)
        control = runner.reference_objective(
            workers, rounding=reference.bfloat16_rounding)
        numbers = enet.compare(readings_enet.stand_in(runner, control, w0),
                               obj, w0, followed, runner.first_steps)
        # and the reference in its own place reads nought
        same = enet.compare(readings_enet.stand_in(runner, obj, w0), obj, w0,
                            followed, runner.first_steps)
    assert harness.decide(numbers, limits_here)[0] is False, numbers
    assert harness.decide(numbers, cell.limits)[0] is False, numbers
    assert harness.decide(same, limits_here)[0] is True
    assert set(same) == set(cell.limits)  # a limit for every number


def test_rehearsal_from_outside_is_correct():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    done = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed",
         "2147483659", "--seconds", "0.2", "--trace", "1", "--rehearse", "1"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-2000:]
    result = json.loads(done.stdout.strip().splitlines()[-1])
    assert result["correct"] is True and result["failed"] == 0
    assert result["metrics"] == {}
    assert result["compared"]["window_compiles"] == [0, 0]


def test_new_metrics_read_nothing_where_the_program_counts_nothing():
    """A program without OWL-QN's counters (the parent) gives the two
    counter metrics nothing to read: they return None and do not raise."""
    from types import SimpleNamespace

    from photon_ml_tpu.obs.metrics import training_metrics

    readers = {name: harness.load_module(os.path.join(
        harness.BENCH_DIR, "metrics", name + ".py"))
        for name in ("enet_trials_per_pass", "enet_gathers_per_pass")}
    tm = training_metrics()
    old = SimpleNamespace(iterations=10, gather_products=21,
                          transpose_products=11)
    for _ in range(2):  # the parent's record_fit reads these three alone
        with tm._fit_lock:
            tm._fit_ring.append({
                "optimizer": "owlqn", "sparse_grad": "csc", "compiled": False,
                "dispatch_s": 0.0, "iterations": old.iterations,
                "gather_products": old.gather_products,
                "transpose_products": old.transpose_products,
                "counted": True})
    run = SimpleNamespace(window={"pieces": [{}, {}]})
    assert readers["enet_trials_per_pass"].read(run) is None
    assert readers["enet_gathers_per_pass"].read(run) == 2.1
    for rec in list(tm._fit_ring)[-2:]:
        rec.update(line_search_trials=12, nonzeros=5)
    assert readers["enet_trials_per_pass"].read(run) == 1.2
