"""``correct`` has been shown to fail: the control (the reference in
bfloat16, in the program's place) and each fault a fixed-effect fit can
have come out not correct, and the sound program comes out correct, at a
size a test run can hold. The faults are planted under the harness, in the
program's entry, and the rest of a run is driven as it is on the chip
(``--rehearse`` only skips the look for a TPU and shrinks the sizes)."""

import io
import json
import os
import time
from contextlib import redirect_stderr, redirect_stdout

import numpy as np
import pytest

from benchmark import harness, readings, reference
from benchmark.runners import glm_fit

ROOT = os.path.dirname(harness.BENCH_DIR)
CELLS = ["criteo-lr.fit", "criteo-lr-tron.fit", "criteo-lr.fit-x4"]


def drive(workload, seed=7):
    out, err = io.StringIO(), io.StringIO()
    with redirect_stdout(out), redirect_stderr(err):
        rc = harness.run_cell(ROOT, workload, seed, 0.2, False, True,
                              time.perf_counter())
    assert rc == 0
    result = json.loads(out.getvalue().strip().splitlines()[-1])
    assert list(result)[-1] == "compared"
    assert "compared loss_step1_gap" in err.getvalue()
    return result


def plant(monkeypatch, fault):
    from photon_ml_tpu.parallel import data_parallel as dp
    from photon_ml_tpu.parallel.mesh import make_mesh

    real = dp.fit_distributed

    def fit(objective, batch, mesh, w0, **kw):
        import jax
        import jax.numpy as jnp

        if fault == "unchanged":  # the step hands its state back
            res = real(objective, batch, mesh, w0, **kw)
            f0, g0 = objective.value_and_grad(w0, batch, kw["l2"])
            return res._replace(
                w=w0, value=f0, grad_norm=jnp.linalg.norm(g0),
                loss_history=jnp.full_like(res.loss_history, f0),
                grad_norm_history=jnp.full_like(res.grad_norm_history,
                                                jnp.linalg.norm(g0)))
        if fault == "half_batch":  # the mean taken over the other half
            n = batch.num_examples // 2
            half = jax.tree.map(lambda a: np.asarray(a)[:n], batch)
            half = half.replace(weights=half.weights * 2.0)
            return real(objective, half, mesh, w0, **kw)
        if fault == "no_exchange":  # one chip's rows, nothing summed
            n = batch.num_examples // mesh.shape["data"]
            mine = jax.tree.map(lambda a: np.asarray(a)[:n], batch)
            return real(objective, mine, make_mesh({"data": 1}), w0, **kw)
        if fault == "altered":  # the answer altered where it is produced
            res = real(objective, batch, mesh, w0, **kw)
            return res._replace(w=res.w * 1.01)
        raise AssertionError(fault)

    monkeypatch.setattr(dp, "fit_distributed", fit)


@pytest.mark.parametrize("workload", CELLS)
def test_sound_program_is_correct(workload):
    result = drive(workload)
    assert result["correct"] is True, result["compared"]
    assert result["device"]["platform"] == "cpu"
    assert result["metrics"] == {}  # no CPU time under a device metric's name
    assert result["failed"] == 0 and result["attempted"] >= 1


@pytest.mark.parametrize("workload,fault", [
    (w, f) for w in CELLS for f in ("unchanged", "half_batch", "altered")
] + [("criteo-lr.fit-x4", "no_exchange")])
def test_fault_is_not_correct(monkeypatch, workload, fault):
    plant(monkeypatch, fault)
    result = drive(workload)
    assert result["correct"] is False
    over = [k for k, (v, lim) in result["compared"].items()
            if lim is None or not v <= lim]
    assert over, result["compared"]


@pytest.mark.parametrize("workload", CELLS)
@pytest.mark.parametrize("seed", [1, 2, 3])
def test_control_in_bfloat16_is_not_correct(workload, seed):
    cell = harness.load_cell(ROOT, workload, rehearse=True)
    runner = glm_fit.Runner(cell, seed)
    from benchmark import data

    runner.indices, runner.labels = data.criteo_rows(
        runner.rows, runner.dim, runner.k, int(cell.config["data_seed"]), seed)
    w0 = runner.start_point(0)
    with reference.Workers(2) as workers:
        obj = runner.reference_objective(workers)
        followed = runner.reference_fit(obj, w0)
        control = runner.reference_objective(
            workers, rounding=reference.bfloat16_rounding)
        numbers = glm_fit.compare(readings.stand_in(runner, control, w0),
                                  obj, w0, followed, runner.first_steps)
        # and the reference in its own place reads nought
        same = glm_fit.compare(readings.stand_in(runner, obj, w0), obj, w0,
                               followed, runner.first_steps)
    correct, compared = harness.decide(numbers, cell.limits)
    assert correct is False, compared
    assert harness.decide(same, cell.limits)[0] is True
