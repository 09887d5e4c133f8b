"""Poisson regression with an exposure offset under TRON (ISSUE 36): the
plain reference ``benchmark/reference_poisson.PoissonL2`` against central
differences and an outside solver, the program's fit against
``reference.tron_steps`` of it step by step in float64, TRON's three new
counters against the reference's counts, a trial point that overflows
float32, the faults the benchmark plants, and the fit over the sorted view,
whose curvature at an accepted trial point reads that point's margins,
against the results the parent commit gave."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import scipy.optimize

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import (data_poisson, harness, readings_poisson,  # noqa: E402
                       reference, reference_poisson)
from photon_ml_tpu.obs.metrics import training_metrics  # noqa: E402
from photon_ml_tpu.ops.objective import make_objective  # noqa: E402
from photon_ml_tpu.optimize import OptimizerConfig  # noqa: E402
from photon_ml_tpu.parallel import fit_distributed, make_mesh  # noqa: E402
from photon_ml_tpu.types import LabeledBatch, SparseFeatures  # noqa: E402

poisson = readings_poisson.poisson  # the cell's runner module

ROWS, DIM, K = 1 << 12, 1 << 9, 39  # the cell's rehearsal
L2 = 1.0
STEPS = 6
W0 = 1e-8  # the benchmark's fit 1
DRAWN = (0.1, -2.0, 0.75)  # the configuration's w_scale and exposures


def problem(rows=ROWS, dim=DIM, k=K, seed=2147483659, drawn=DRAWN):
    return data_poisson.poisson_rows(rows, dim, k, 20260930, seed, *drawn)


def program_fit(indices, counts, offsets, dim, sparse_grad, chips,
                steps=STEPS, dtype=jnp.float64, w0=W0):
    n = indices.shape[0]
    batch = LabeledBatch(
        SparseFeatures(jnp.asarray(indices), None, dim=dim),
        jnp.asarray(counts, dtype), jnp.asarray(offsets, dtype),
        jnp.ones(n, dtype))
    mesh = make_mesh({"data": chips}, devices=jax.devices()[:chips])
    return fit_distributed(
        make_objective("poisson"), batch, mesh, jnp.full((dim,), w0, dtype),
        l2=L2, optimizer="tron",
        config=OptimizerConfig(max_iters=steps, tolerance=0.0),
        sparse_grad=sparse_grad)


# -- the reference's objective against differences and an outside solver ----
@pytest.fixture(scope="module")
def small():
    rows, dim, k = 512, 96, 6
    indices, counts, offsets = problem(rows, dim, k, seed=11)
    with reference.Workers(1) as workers:
        yield reference_poisson.PoissonL2(indices, counts, offsets, dim, L2,
                                          workers), dim


def test_offsets_are_nonzero_and_counts_are_sparse():
    _, counts, offsets = problem()
    assert np.all(counts >= 0) and np.all(counts == np.round(counts))
    assert 0.7 < np.mean(counts == 0) < 0.9  # four rows in five
    assert 0.1 < counts.mean() < 0.4
    assert abs(offsets.mean() + 2.0) < 0.1 and 0.6 < offsets.std() < 0.9


def test_seed_draws_the_layout_and_not_the_problem():
    a = problem(seed=1)
    b = problem(seed=2)
    assert not np.array_equal(a[0], b[0])
    for x, y in zip(a[1:], b[1:]):  # the same counts and exposures, permuted
        np.testing.assert_array_equal(np.sort(x), np.sort(y))
    again = problem(seed=1)
    for x, y in zip(a, again):
        np.testing.assert_array_equal(x, y)
    # the feature matrix is criteo-lr-tron's
    from benchmark import data

    np.testing.assert_array_equal(
        a[0], data.criteo_rows(ROWS, DIM, K, 20260930, 1)[0])


def test_gradient_hvp_and_diagonal_against_central_differences(small):
    obj, dim = small
    rng = np.random.default_rng(0)
    w, v = rng.normal(size=dim) * 0.1, rng.normal(size=dim)
    h = 1e-5
    _, g = obj.value_grad(w)
    f_plus, g_plus = obj.value_grad(w + h * v)
    f_minus, g_minus = obj.value_grad(w - h * v)
    np.testing.assert_allclose((f_plus - f_minus) / (2 * h), g @ v,
                               rtol=1e-7)
    hv = obj.hvp(w, v)
    np.testing.assert_allclose((g_plus - g_minus) / (2 * h), hv, rtol=1e-6,
                               atol=1e-7)
    # the Jacobi diagonal counts a column once a slot (``LogisticL2``'s, and
    # the program's): the Hessian's own diagonal wherever no row holds a
    # column twice, which at the cell's width is every row but a few
    diag = obj.diag_hessian(w)
    counts = sum(x.toarray() for x in obj.X) if len(obj.X) > 1 else (
        obj.X[0].toarray())
    once = np.flatnonzero((counts <= 1).all(axis=0) & (counts.sum(axis=0) > 0))
    assert once.size >= 3
    for j in once[[0, once.size // 2, -1]]:
        e = np.zeros(dim)
        e[j] = 1.0
        np.testing.assert_allclose(obj.hvp(w, e)[j], diag[j], rtol=1e-12)
    np.testing.assert_allclose(diag, counts.T @ obj.d2(w) + L2, rtol=1e-12)


def test_offsets_enter_every_margin(small):
    obj, dim = small
    w = np.full(dim, 0.01)
    f, g = obj.value_grad(w)
    kept = obj.offsets
    obj.offsets = np.zeros_like(kept)
    try:
        f_none, g_none = obj.value_grad(w)
    finally:
        obj.offsets = kept
    assert abs(f - f_none) > 0.1 * abs(f)
    assert np.linalg.norm(g - g_none) > 0.1 * np.linalg.norm(g)


def test_reference_tron_reaches_the_optimum_of_an_outside_solver(small):
    obj, dim = small
    w, losses, gnorms, _ = reference.tron_steps(obj, np.full(dim, W0), 40)
    out = scipy.optimize.minimize(
        obj.value_grad, np.zeros(dim), jac=True, method="L-BFGS-B",
        options={"maxiter": 5000, "ftol": 1e-15, "gtol": 1e-10})
    np.testing.assert_allclose(losses[-1], out.fun, rtol=1e-10)
    np.testing.assert_allclose(w, out.x, atol=1e-5)
    assert gnorms[-1] < 1e-6
    assert np.all(np.diff(losses) <= 0)  # a refused step keeps the loss


# -- the program against the reference, step by step ------------------------
@pytest.fixture(scope="module")
def followed():
    indices, counts, offsets = problem()
    with reference.Workers(2) as workers:
        obj = reference_poisson.PoissonL2(indices, counts, offsets, DIM, L2,
                                          workers)
        w0 = np.full(DIM, W0)
        yield (indices, counts, offsets, obj.value_grad(w0)[0],
               reference.tron_steps(obj, w0, STEPS))


def refused(losses, f_start, rel=0.0):
    """A refused step hands back the loss before it, to the bit; the
    program's own starting value is the reference's to rounding."""
    before = np.concatenate([[f_start], losses[:-1]])
    return np.abs(np.asarray(losses) - before) <= rel * np.abs(before)


@pytest.mark.parametrize("sparse_grad,chips", [
    ("scatter", 1), ("scatter", 4), ("csc", 1), ("csc", 4)])
def test_program_follows_the_reference_step_by_step(followed, sparse_grad,
                                                    chips):
    indices, counts, offsets, f_start, (w_ref, losses, gnorms, cg) = followed
    res = program_fit(indices, counts, offsets, DIM, sparse_grad, chips)
    assert int(res.iterations) == STEPS == len(losses)
    np.testing.assert_allclose(np.asarray(res.loss_history), losses,
                               rtol=1e-9)
    # a gradient is a sum of terms of the first one's size: by the sixth
    # step its norm is 200 times smaller and holds 1e-9 of that scale
    np.testing.assert_allclose(np.asarray(res.grad_norm_history), gnorms,
                               rtol=1e-9, atol=1e-9 * gnorms[0])
    np.testing.assert_allclose(float(res.value), losses[-1], rtol=1e-9)
    np.testing.assert_allclose(float(res.grad_norm), gnorms[-1], rtol=1e-9,
                               atol=1e-9 * gnorms[0])
    np.testing.assert_allclose(np.asarray(res.w), w_ref, rtol=1e-7,
                               atol=1e-12)
    # the same steps accepted and refused: a refused step hands back the
    # loss before it to the bit, on both sides
    theirs = refused(losses, f_start)
    assert theirs.any() and not theirs.all()  # both branches are held
    np.testing.assert_array_equal(
        refused(np.asarray(res.loss_history), f_start, rel=1e-12), theirs)
    # the counters against the reference's counts; with every step's
    # loss equal to 1e-9, an equal total is an equal count a step
    for counter in (res.cg_steps, res.rejected_steps, res.precond_passes,
                    res.curvature_passes):
        assert counter.dtype == jnp.int32 and counter.shape == ()
    assert int(res.cg_steps) == sum(cg)
    assert int(res.rejected_steps) == int(theirs.sum())
    # w0's diagonal and one a step accepted before the last (ISSUE 37: the
    # last iterate's no CG solve would read); with the sorted view in hand
    # each comes from a curvature vector the solve's HVPs share
    diagonals = 1 + int((~theirs[:-1]).sum())
    curvatures = diagonals if sparse_grad == "csc" else 0
    assert int(res.precond_passes) == diagonals
    assert int(res.curvature_passes) == curvatures
    # every renewal reads the accepted trial's margins; w0's curvature
    # shares (f0, g0)'s
    assert res.margins_reused.dtype == jnp.int32
    assert int(res.margins_reused) == max(curvatures - 1, 0)
    assert int(res.gather_products) == 1 + sum(cg) + STEPS
    assert int(res.transpose_products) == int(res.gather_products)
    record = training_metrics().fit_records()[-1]
    assert record["optimizer"] == "tron"
    assert (record["cg_steps"], record["rejected_steps"],
            record["precond_passes"], record["curvature_passes"]) == (
        sum(cg), int(theirs.sum()), diagonals, curvatures)
    # and the runner's own derivations, for a program without the counters
    assert poisson.accepted_steps(
        np.asarray(res.loss_history), f_start, rel=1e-4) == (~theirs).sum()
    assert poisson.accepted_steps(losses, f_start) == (~theirs).sum()


def test_counters_of_each_step(followed):
    """A fit under ``tolerance=0`` and a cap of ``s`` is the first ``s``
    steps of the longer fit, so the totals' differences are the steps'."""
    indices, counts, offsets, f_start, (_, losses, _, cg) = followed
    fits = [program_fit(indices, counts, offsets, DIM, "csc", 1, steps=s)
            for s in range(1, STEPS + 1)]
    assert np.diff([0] + [int(r.cg_steps) for r in fits]).tolist() == cg
    theirs = refused(losses, f_start)
    assert np.diff([0] + [int(r.rejected_steps) for r in fits]).tolist() == (
        theirs.astype(int).tolist())
    # a cap of s computes w0's diagonal and one for each of the first
    # s - 1 steps that was accepted: the s-th step's has no reader
    for counter in ("precond_passes", "curvature_passes"):
        assert np.diff([1] + [int(getattr(r, counter)) for r in fits]
                       ).tolist() == [0] + (~theirs[:-1]).astype(int).tolist()
    # and every renewal is read off the trial's margins, none at w0
    assert np.diff([0] + [int(r.margins_reused) for r in fits]).tolist() == (
        [0] + (~theirs[:-1]).astype(int).tolist())


def test_other_optimizers_count_none_of_the_three():
    indices, counts, offsets = problem(rows=256, dim=128, k=8)
    n = indices.shape[0]
    batch = LabeledBatch(SparseFeatures(jnp.asarray(indices), None, dim=128),
                         jnp.asarray(counts), jnp.asarray(offsets),
                         jnp.ones(n))
    mesh = make_mesh({"data": 1}, devices=jax.devices()[:1])
    for optimizer, line_search, l1 in (("lbfgs", "margin", 0.0),
                                       ("owlqn", "full", 0.5)):
        res = fit_distributed(
            make_objective("poisson"), batch, mesh, jnp.zeros(128), l2=L2,
            l1=l1, optimizer=optimizer, line_search=line_search,
            config=OptimizerConfig(max_iters=2, tolerance=0.0),
            sparse_grad="scatter")
        record = training_metrics().fit_records()[-1]
        for name in ("cg_steps", "rejected_steps", "precond_passes",
                     "curvature_passes"):
            assert getattr(res, name) is None and record[name] is None
        # OWL-QN counts the margins it reuses (none without the sorted view)
        assert (res.margins_reused is None) == (optimizer == "lbfgs")


def test_tron_without_a_preconditioner_computes_no_diagonal():
    from photon_ml_tpu.optimize import tron

    fg = jax.value_and_grad(lambda w: jnp.sum(jnp.cosh(w - 1.0)))
    res = tron(fg, jnp.zeros(5), OptimizerConfig(max_iters=4, tolerance=0.0))
    assert int(res.precond_passes) == int(res.curvature_passes) == 0
    assert int(res.cg_steps) == int(res.gather_products) - 1 - 4


# -- over the sorted view, the parent's results to the bit -------------------
PARITY = os.path.join(ROOT, "tests", "data", "sorted_view_parity.npz")
PARITY_FIELDS = ("w", "value", "grad_norm", "iterations", "converged",
                 "loss_history", "grad_norm_history", "gather_products",
                 "cg_steps", "rejected_steps", "precond_passes",
                 "curvature_passes")
PARITY_CASES = [(dtype, chips) for dtype in ("float64", "float32")
                for chips in (1, 4)]


def parity_fit(dtype, chips):
    """The fit whose results ``tests/data/sorted_view_parity.npz`` holds as
    the parent commit returned them, when the curvature at an accepted trial
    point gathered that point's margins again."""
    indices, counts, offsets = problem()
    return program_fit(indices, counts, offsets, DIM, "csc", chips,
                       dtype=getattr(jnp, dtype))


@pytest.mark.parametrize("dtype,chips", PARITY_CASES)
def test_sorted_view_fit_keeps_the_parents_bits(dtype, chips):
    """The curvature of a renewal is read off the very margins the trial's
    gather made: the CG and accept sequence, the counts and every number of
    the fit are the parent's, float32 included."""
    res = parity_fit(dtype, chips)
    assert int(res.margins_reused) == int(res.curvature_passes) - 1 >= 1
    with np.load(PARITY) as parent:
        for field in PARITY_FIELDS:
            np.testing.assert_array_equal(
                np.asarray(getattr(res, field)),
                parent[f"tron-{dtype}-{chips}/{field}"], err_msg=field)


# -- a trial point that overflows float32 -----------------------------------
@pytest.mark.parametrize("sparse_grad", ["scatter", "csc"])
def test_overflowing_trial_is_a_refused_step(sparse_grad):
    """Counts in the hundreds make ``|g0|``, LIBLINEAR's first radius, so
    wide that the first trial point's margins pass 88.7: ``exp`` overflows
    float32, the trial's loss is ``inf`` and its gradient holds ``nan``. The
    step must come out refused (``w`` and the loss kept, to the bit), the
    radius smaller (a later trial differs and is accepted), ``w`` finite."""
    rows, dim, k = 256, 64, 4
    indices, _, offsets = problem(rows, dim, k, seed=3)
    counts = np.full(rows, 300.0)
    with reference.Workers(1) as workers:
        obj = reference_poisson.PoissonL2(indices, counts, offsets, dim, L2,
                                          workers)
        f_start = obj.value_grad(np.zeros(dim))[0]
        _, losses, _, _ = reference.tron_steps(obj, np.zeros(dim), 12)
    assert losses[0] == f_start  # the float64 reference refuses it too
    one = program_fit(indices, counts, offsets, dim, sparse_grad, 1, steps=1,
                      dtype=jnp.float32, w0=0.0)
    assert one.w.dtype == jnp.float32
    assert int(one.rejected_steps) == 1 and int(one.precond_passes) == 1
    assert int(one.curvature_passes) == (sparse_grad == "csc")
    np.testing.assert_array_equal(np.asarray(one.w), 0.0)
    np.testing.assert_allclose(float(one.value), f_start, rtol=1e-5)
    # (that the trial really overflowed: the next test)
    longer = program_fit(indices, counts, offsets, dim, sparse_grad, 1,
                         steps=8, dtype=jnp.float32, w0=0.0)
    assert int(longer.iterations) == 8
    history = np.asarray(longer.loss_history)
    assert np.all(np.isfinite(history)) and np.all(np.isfinite(longer.w))
    assert history[0] == np.float32(one.value)
    assert 1 <= int(longer.rejected_steps) < 8
    # the refused steps renewed neither the curvature nor the diagonal
    moved = np.diff(history, prepend=history[0]) != 0  # step 1 was refused
    assert int(longer.rejected_steps) == 8 - moved.sum()
    assert int(longer.precond_passes) == 1 + moved[:-1].sum()
    assert int(longer.curvature_passes) == (
        int(longer.precond_passes) if sparse_grad == "csc" else 0)
    assert float(longer.value) < f_start - 1.0  # accepted steps followed
    assert np.all(np.diff(history) <= 0)
    assert bool(jnp.isfinite(longer.grad_norm))


def test_the_overflow_test_overflows():
    """The problem above, by hand: the first trial's margins pass float32's
    88.7 and its loss is ``inf`` there."""
    rows, dim, k = 256, 64, 4
    indices, _, offsets = problem(rows, dim, k, seed=3)
    counts = np.full(rows, 300.0)
    with reference.Workers(1) as workers:
        obj = reference_poisson.PoissonL2(indices, counts, offsets, dim, L2,
                                          workers)
        w0 = np.zeros(dim)
        f, g = obj.value_grad(w0)
        eps = float(np.finfo(np.float32).eps)
        md = obj.diag_hessian(w0)
        md = np.maximum(md, eps * max(md.max(), 1.0))
        step, _, _ = reference._steihaug_cg(
            workers, lambda v: obj.hvp(w0, v), g, workers.norm(g),
            0.1 * workers.norm(g), 64, md)
        m = obj.eta(w0 + step)
    assert m.max() > 88.8
    with np.errstate(over="ignore"):
        assert np.isinf(np.sum(np.exp(m.astype(np.float32))))


# -- the planted faults, and what the reference imports ---------------------
@pytest.mark.parametrize("fault", ["control_bf16", "fault_no_offsets",
                                   "fault_logistic_d2", "fault_half_batch"])
def test_planted_faults_change_the_fit(fault):
    cell = harness.load_cell(ROOT, "criteo-poisson-tron.fit", rehearse=True)
    runner = poisson.Runner(cell, 5)
    runner.draw()
    assert (runner.rows, runner.dim) == (ROWS, DIM)
    w0 = runner.start_point(1)
    with reference.Workers(2) as workers:
        obj = runner.reference_objective(workers)
        followed = runner.reference_fit(obj, w0)
        bad = readings_poisson.planted(
            runner, workers, {"control", "faults"})[fault]()
        numbers = poisson.compare(
            readings_poisson.stand_in(runner, bad, w0), obj, w0, followed,
            runner.first_steps)
        same = poisson.compare(
            readings_poisson.stand_in(runner, obj, w0), obj, w0, followed,
            runner.first_steps)
    assert set(same) == set(numbers) and max(same.values()) == 0.0
    assert numbers["fit_loss_gap"] > 1e-5 and numbers["fit_change_gap"] > 1e-4
    if fault == "fault_logistic_d2":
        # the value and the gradient are the sound ones: only the path moves
        assert numbers["final_loss_gap"] == numbers["final_grad_gap"] == 0.0
        assert numbers["fit_cg_gap"] > 0.1
    elif fault != "control_bf16":
        assert numbers["final_grad_gap"] > 0.1


def test_reference_imports_nothing_of_the_program():
    import ast

    for name in ("reference_poisson.py", "data_poisson.py", "reference.py",
                 "data.py"):
        with open(os.path.join(ROOT, "benchmark", name)) as f:
            tree = ast.parse(f.read())
        for node in ast.walk(tree):
            names = ([a.name for a in node.names]
                     if isinstance(node, ast.Import)
                     else [node.module or ""]
                     if isinstance(node, ast.ImportFrom) else [])
            assert not any(n.startswith(("photon_ml_tpu", "jax"))
                           for n in names), (name, names)
