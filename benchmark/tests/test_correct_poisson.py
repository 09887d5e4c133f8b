"""``correct`` of ``criteo-poisson-tron.fit`` has been shown to fail: the
control (the reference in bfloat16, in the program's place), the faults a
Poisson fit with offsets under TRON can have, planted in the *program* under
the harness (the offsets left out, the logistic loss's second derivative in
the Hessian-vector product and the diagonal, half of the batch, a state
handed back unchanged) all come out not correct, and the sound program comes
out correct, at the rehearsal's size and through the CSC path the chip runs.
The limits they are held to are read at that size, as the cell's own were
read at its size on the chip: four times the largest reading of the sound
program over three seeds (float32 on the CPU against the float64
reference); the two discrete numbers read 0 there, so any other sequence of
accepted steps or count of CG steps is over its limit."""

import dataclasses
import io
import json
import os
import subprocess
import sys
import time
from contextlib import redirect_stderr, redirect_stdout
from types import SimpleNamespace

import numpy as np
import pytest

from benchmark import harness, readings_poisson, reference

ROOT = os.path.dirname(harness.BENCH_DIR)
CELL = "criteo-poisson-tron.fit"
poisson = readings_poisson.poisson
FAULTS = ["unchanged", "no_offsets", "logistic_d2", "half_batch"]


def drive(seed=7):
    out, err = io.StringIO(), io.StringIO()
    with redirect_stdout(out), redirect_stderr(err):
        rc = harness.run_cell(ROOT, CELL, seed, 0.2, False, True,
                              time.perf_counter())
    assert rc == 0
    result = json.loads(out.getvalue().strip().splitlines()[-1])
    assert "compared fit_accepted_gap" in err.getvalue()
    return result


def numbers_of(result):
    return {k: v for k, (v, _) in result["compared"].items()
            if k != "window_compiles"}


@pytest.fixture(scope="module")
def limits_here():
    sound = [numbers_of(drive(seed)) for seed in (1, 2, 3)]
    return {k: 4.0 * max(s[k] for s in sound) for k in sound[0]}


def plant(monkeypatch, fault):
    from photon_ml_tpu.ops import losses
    from photon_ml_tpu.parallel import data_parallel as dp

    real_fit = dp.fit_distributed

    def fit(objective, batch, mesh, w0, **kw):
        import jax
        import jax.numpy as jnp

        if fault == "unchanged":  # the step hands its state back
            res = real_fit(objective, batch, mesh, w0, **kw)
            f0, g0 = objective.value_and_grad(w0, batch, kw["l2"])
            return res._replace(
                w=w0, value=f0, grad_norm=jnp.linalg.norm(g0),
                loss_history=jnp.full_like(res.loss_history, f0),
                grad_norm_history=jnp.full_like(res.grad_norm_history,
                                                jnp.linalg.norm(g0)),
                rejected_steps=res.iterations.astype(jnp.int32))
        if fault == "no_offsets":  # every exposure 1
            batch = batch.replace(offsets=jnp.zeros_like(batch.offsets))
        if fault == "logistic_d2":  # another loss's curvature
            objective = dataclasses.replace(
                objective, loss=dataclasses.replace(
                    objective.loss, d2=losses.LOGISTIC.d2))
        if fault == "half_batch":  # the other half counted twice
            n = batch.num_examples // 2
            half = jax.tree.map(lambda a: np.asarray(a)[:n], batch)
            batch = half.replace(weights=half.weights * 2.0)
            kw = {**kw, "precomputed_csc": None}  # the view of all the rows
        return real_fit(objective, batch, mesh, w0, **kw)

    monkeypatch.setattr(dp, "fit_distributed", fit)


def test_sound_program_is_correct(limits_here):
    result = drive()
    assert result["correct"] is True, result["compared"]  # the cell's limits
    assert harness.decide(numbers_of(result), limits_here)[0] is True
    assert result["device"]["platform"] == "cpu"
    assert result["metrics"] == {}  # no CPU time under a device metric's name
    assert result["failed"] == 0 and result["attempted"] >= 1
    work = result["run"]["work"]
    assert work["sparse_grad"] == "csc"
    cap = harness.load_cell(ROOT, CELL, rehearse=True).config[
        "passes_per_fit"]
    assert set(work["passes"]) == {cap}
    # both branches of the trust region in every fit, the same in each
    assert set(work["rejected_steps"]) == {1}
    assert set(work["precond_passes"]) == {cap}  # w0's, one an accepted step
    assert set(work["cg_steps"]) == {15}  # 3, 2, 2, 5, 3: the reference's


@pytest.mark.parametrize("fault", FAULTS)
def test_fault_is_not_correct(monkeypatch, limits_here, fault):
    plant(monkeypatch, fault)
    result = drive()
    correct, compared = harness.decide(numbers_of(result), limits_here)
    assert correct is False
    over = [k for k, (v, lim) in compared.items() if not v <= lim]
    assert over, compared
    assert result["correct"] is False, result["compared"]  # the cell's too
    if fault == "unchanged":
        assert compared["fit_change_gap"][0] == pytest.approx(1.0)
        assert result["failed"] == result["attempted"]  # no step accepted
    if fault == "logistic_d2":
        # the value and the gradient are sound at any w: the path moves
        assert "fit_cg_gap" in over or "fit_accepted_gap" in over
        assert compared["final_loss_gap"][0] < 1e-5
    if fault in ("no_offsets", "half_batch"):
        assert "final_loss_gap" in over and "grad_step1_gap" in over


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_control_in_bfloat16_is_not_correct(limits_here, seed):
    cell = harness.load_cell(ROOT, CELL, rehearse=True)
    runner = poisson.Runner(cell, seed)
    runner.draw()
    w0 = runner.start_point(1)
    with reference.Workers(2) as workers:
        obj = runner.reference_objective(workers)
        followed = runner.reference_fit(obj, w0)
        control = runner.reference_objective(
            workers, rounding=reference.bfloat16_rounding)
        numbers = poisson.compare(
            readings_poisson.stand_in(runner, control, w0), obj, w0,
            followed, runner.first_steps)
        # and the reference in its own place reads nought
        same = poisson.compare(readings_poisson.stand_in(runner, obj, w0),
                               obj, w0, followed, runner.first_steps)
    assert harness.decide(numbers, limits_here)[0] is False, numbers
    assert harness.decide(numbers, cell.limits)[0] is False, numbers
    assert harness.decide(same, limits_here)[0] is True
    assert set(same) <= set(cell.limits)  # a limit for every number


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


def test_runner_works_on_a_program_without_the_counters(monkeypatch):
    """The parent commit's TRON returns no ``cg_steps``, ``rejected_steps``
    or ``precond_passes``: the runner then reads the CG steps off the product
    counter and the accepted steps off the loss history, and the run is
    correct all the same."""
    from photon_ml_tpu.parallel import data_parallel as dp

    real_fit = dp.fit_distributed
    old = SimpleNamespace  # a result without the three fields

    def fit(*args, **kw):
        res = real_fit(*args, **kw)
        return old(**{k: v for k, v in res._asdict().items()
                      if k not in poisson._COUNTERS})

    monkeypatch.setattr(dp, "fit_distributed", fit)
    result = drive()
    assert result["correct"] is True, result["compared"]
    assert result["compared"]["fit_accepted_gap"][0] == 0.0
    assert result["compared"]["fit_cg_gap"][0] == 0.0
    work = result["run"]["work"]
    assert set(work["cg_steps"]) == {None}
    assert set(work["rejected_steps"]) == {None}
    assert result["failed"] == 0


def test_new_metrics_read_nothing_where_the_program_counts_nothing():
    """A program without TRON's counters (the parent) gives the two counter
    metrics nothing to read: they return None and do not raise; the product
    counter's metric reads as it does in the other TRON cell."""
    from photon_ml_tpu.obs.metrics import training_metrics

    names = ("pois_cg_steps_per_pass", "pois_rejected_steps_per_pass",
             "pois_products_per_pass")
    readers = {name: harness.load_module(os.path.join(
        harness.BENCH_DIR, "metrics", name + ".py")) for name in names}
    tm = training_metrics()
    for _ in range(2):  # the parent's record_fit keeps these alone
        with tm._fit_lock:
            tm._fit_ring.append({
                "optimizer": "tron", "sparse_grad": "csc_pallas",
                "compiled": False, "dispatch_s": 0.0, "iterations": 6,
                "gather_products": 27, "transpose_products": 27,
                "line_search_trials": None, "nonzeros": None,
                "counted": True})
    run = SimpleNamespace(window={"pieces": [{}, {}]})
    assert readers["pois_cg_steps_per_pass"].read(run) is None
    assert readers["pois_rejected_steps_per_pass"].read(run) is None
    assert readers["pois_products_per_pass"].read(run) == 4.5
    for rec in list(tm._fit_ring)[-2:]:
        rec.update(cg_steps=20, rejected_steps=1, precond_passes=6)
    assert readers["pois_cg_steps_per_pass"].read(run) == 20 / 6
    assert readers["pois_rejected_steps_per_pass"].read(run) == 1 / 6
