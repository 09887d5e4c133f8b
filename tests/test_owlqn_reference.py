"""OWL-QN with an elastic net (ISSUE 34): the program's fit against the
plain reference ``benchmark/reference_owlqn.py`` step by step in float64,
the reference against the KKT conditions and an outside solver, the two
counters the fit now keeps against the reference's counts, and the fit over
the sorted view, whose gradient reads the margins of the trial its search
accepted, against the results the parent commit's black-box form gave."""

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

from benchmark import data, reference, reference_owlqn  # noqa: E402
from photon_ml_tpu.obs.metrics import training_metrics  # noqa: E402
from photon_ml_tpu.ops.objective import make_objective  # noqa: E402
from photon_ml_tpu.optimize import OptimizerConfig  # noqa: E402
from photon_ml_tpu.parallel import fit_distributed, make_mesh  # noqa: E402
from photon_ml_tpu.types import LabeledBatch, SparseFeatures  # noqa: E402

ROWS, DIM, K = 1 << 11, 1 << 12, 39
L1 = L2 = 1.0
STEPS = 10
W0 = 1e-8  # the benchmark's fit 1: every coefficient starts positive


def problem(rows=ROWS, dim=DIM, k=K, seed=2147483659):
    return data.criteo_rows(rows, dim, k, 20260930, seed)


def program_fit(indices, labels, dim, sparse_grad, chips, steps=STEPS,
                l2=L2, dtype=jnp.float64):
    n = indices.shape[0]
    batch = LabeledBatch(SparseFeatures(jnp.asarray(indices), None, dim=dim),
                         jnp.asarray(labels, dtype), jnp.zeros(n, dtype),
                         jnp.ones(n, dtype))
    mesh = make_mesh({"data": chips}, devices=jax.devices()[:chips])
    return fit_distributed(
        make_objective("logistic"), batch, mesh, jnp.full((dim,), W0, dtype),
        l2=l2, l1=L1, optimizer="owlqn",
        config=OptimizerConfig(max_iters=steps, tolerance=0.0),
        sparse_grad=sparse_grad, line_search="full")


@pytest.fixture(scope="module")
def followed():
    indices, labels = problem()
    with reference.Workers(2) as workers:
        obj = reference_owlqn.ElasticNet(
            reference.LogisticL2(indices, labels, DIM, L2, workers), L1)
        yield indices, labels, reference_owlqn.owlqn_steps(
            obj, np.full(DIM, W0), STEPS)


@pytest.mark.parametrize("sparse_grad,chips", [
    ("scatter", 1), ("scatter", 4), ("csc", 1), ("csc", 4)])
def test_program_follows_the_reference_step_by_step(followed, sparse_grad,
                                                    chips):
    indices, labels, (w_ref, values, pgnorms, trials) = followed
    res = program_fit(indices, labels, DIM, sparse_grad, chips)
    assert int(res.iterations) == STEPS == len(values)
    np.testing.assert_allclose(np.asarray(res.loss_history), values,
                               rtol=1e-9)
    np.testing.assert_allclose(np.asarray(res.grad_norm_history), pgnorms,
                               rtol=1e-9)
    np.testing.assert_allclose(float(res.value), values[-1], rtol=1e-9)
    np.testing.assert_allclose(float(res.grad_norm), pgnorms[-1], rtol=1e-9)
    w = np.asarray(res.w)
    # the same support: what a missing or misplaced projection moves first
    np.testing.assert_array_equal(w != 0, w_ref != 0)
    assert 0 < np.count_nonzero(w) < DIM
    np.testing.assert_allclose(w, w_ref, rtol=1e-7, atol=1e-12)
    # the new counters against the reference's counts; with every step's
    # objective equal to 1e-9, an equal total is an equal count a step
    assert res.line_search_trials.dtype == jnp.int32
    assert res.nonzeros.dtype == jnp.int32
    assert int(res.line_search_trials) == sum(trials)
    assert int(res.nonzeros) == np.count_nonzero(w_ref)
    # over the sorted view the accepted point's gradient reads its trial's
    # margins: a gather a trial and (f0, g0)'s; without it one more a pass
    reused = STEPS if sparse_grad == "csc" else 0
    assert res.margins_reused.dtype == jnp.int32
    assert int(res.margins_reused) == reused
    assert int(res.gather_products) == sum(trials) + STEPS + 1 - reused
    assert int(res.transpose_products) == STEPS + 1
    record = training_metrics().fit_records()[-1]
    assert record["optimizer"] == "owlqn"
    assert record["line_search_trials"] == sum(trials)
    assert record["nonzeros"] == np.count_nonzero(w_ref)
    assert record["margins_reused"] == reused


def test_trial_count_of_a_step_that_backtracks():
    """A fit under ``tolerance=0`` and a cap of ``s`` is the first ``s``
    steps of the longer fit, so the totals' differences are the steps'. At
    256 columns and a weak L2 the tenth search halves its step once."""
    dim, l2 = 256, 0.01
    indices, labels = problem(dim=dim)
    with reference.Workers(2) as workers:
        obj = reference_owlqn.ElasticNet(
            reference.LogisticL2(indices, labels, dim, l2, workers), L1)
        _, values, _, trials = reference_owlqn.owlqn_steps(
            obj, np.full(dim, W0), 11)
    assert trials[8:] == [1, 2, 1]
    fits = [program_fit(indices, labels, dim, "csc", 1, steps=s, l2=l2)
            for s in (8, 9, 10, 11)]
    totals = [int(r.line_search_trials) for r in fits]
    assert np.diff(totals).tolist() == trials[8:]
    assert totals[-1] == sum(trials)
    # a weak L2 conditions the problem worse: rounding grows to 3e-9
    np.testing.assert_allclose(np.asarray(fits[-1].loss_history), values,
                               rtol=1e-7)
    # the search that halves its step reuses the margins of the trial it
    # accepts (the second), as every other one does: a gather a trial
    for s, res in zip((8, 9, 10, 11), fits):
        assert int(res.margins_reused) == int(res.iterations) == s
        assert int(res.gather_products) == 1 + int(res.line_search_trials)


# -- over the sorted view, the parent's results to the bit -------------------
PARITY = os.path.join(ROOT, "tests", "data", "sorted_view_parity.npz")
PARITY_FIELDS = ("w", "value", "grad_norm", "iterations", "converged",
                 "loss_history", "grad_norm_history", "line_search_trials",
                 "nonzeros")
PARITY_CASES = [(dtype, chips) for dtype in ("float64", "float32")
                for chips in (1, 4)]


def parity_fit(dtype, chips):
    """The fit whose results ``tests/data/sorted_view_parity.npz`` holds as
    the parent commit returned them, when the gradient at an accepted point
    gathered that point's margins again."""
    indices, labels = problem()
    return program_fit(indices, labels, DIM, "csc", chips,
                       dtype=getattr(jnp, dtype))


@pytest.mark.parametrize("dtype,chips", PARITY_CASES)
def test_sorted_view_fit_keeps_the_parents_bits(dtype, chips):
    """The margins the gradient reads are the array the search's gather
    made: every number of the fit is the parent's, float32 included."""
    res = parity_fit(dtype, chips)
    assert int(res.margins_reused) == STEPS
    with np.load(PARITY) as parent:
        for field in PARITY_FIELDS:
            np.testing.assert_array_equal(
                np.asarray(getattr(res, field)),
                parent[f"owlqn-{dtype}-{chips}/{field}"], err_msg=field)


def test_other_optimizers_count_neither():
    indices, labels = problem(rows=256, dim=128, k=8)
    n = indices.shape[0]
    batch = LabeledBatch(SparseFeatures(jnp.asarray(indices), None, dim=128),
                         jnp.asarray(labels), jnp.zeros(n), jnp.ones(n))
    mesh = make_mesh({"data": 1}, devices=jax.devices()[:1])
    for optimizer, line_search in (("lbfgs", "margin"), ("tron", "full")):
        res = fit_distributed(
            make_objective("logistic"), batch, mesh, jnp.zeros(128), l2=L2,
            optimizer=optimizer, line_search=line_search,
            config=OptimizerConfig(max_iters=2, tolerance=0.0),
            sparse_grad="scatter")
        assert res.line_search_trials is None and res.nonzeros is None
        # TRON counts the margins it reuses (none without the sorted view)
        assert (res.margins_reused is None) == (optimizer == "lbfgs")
        record = training_metrics().fit_records()[-1]
        assert record["line_search_trials"] is None
        assert record["nonzeros"] is None


@pytest.fixture(scope="module")
def small_optimum():
    """The reference run to its end on a small problem: 300 steps."""
    rows, dim, k = 512, 96, 6
    indices, labels = problem(rows, dim, k, seed=11)
    with reference.Workers(1) as workers:
        obj = reference_owlqn.ElasticNet(
            reference.LogisticL2(indices, labels, dim, L2, workers), L1)
        w, values, pgnorms, trials = reference_owlqn.owlqn_steps(
            obj, np.full(dim, W0), 300)
        yield obj, w, values, pgnorms


def test_reference_meets_the_kkt_conditions(small_optimum):
    obj, w, values, pgnorms = small_optimum
    _, g = obj.smooth.value_grad(w)
    zero = w == 0
    assert 0 < zero.sum() < w.shape[0]
    assert np.all(np.abs(g[zero]) <= L1 + 1e-9)
    np.testing.assert_allclose(g[~zero] + L1 * np.sign(w[~zero]), 0.0,
                               atol=1e-7)
    assert pgnorms[-1] < 1e-7
    assert np.all(np.diff(values) <= 0)  # every accepted step descends


def test_reference_agrees_with_an_outside_solver(small_optimum):
    """L-BFGS-B on the split ``w = u - v``, ``u, v >= 0``: a smooth problem
    with bounds, solved by code that shares nothing with the reference."""
    obj, w, values, _ = small_optimum
    dim = w.shape[0]

    def split(uv):
        u, v = uv[:dim], uv[dim:]
        f, g = obj.smooth.value_grad(u - v)
        return f + L1 * uv.sum(), np.concatenate([g + L1, -g + L1])

    out = scipy.optimize.minimize(
        split, np.zeros(2 * dim), jac=True, method="L-BFGS-B",
        bounds=[(0.0, None)] * (2 * dim),
        options={"maxiter": 5000, "ftol": 1e-15, "gtol": 1e-10})
    w_out = out.x[:dim] - out.x[dim:]
    assert values[-1] <= out.fun * (1 + 1e-10)
    np.testing.assert_allclose(values[-1], out.fun, rtol=1e-9)
    np.testing.assert_allclose(w, w_out, atol=1e-5)


@pytest.mark.parametrize("fault", ["l1_in_search", "project"])
def test_planted_faults_change_the_fit(followed, fault):
    """The two faults the benchmark plants are not no-ops at this size."""
    indices, labels, (w_ref, values, _, _) = followed
    with reference.Workers(2) as workers:
        obj = reference_owlqn.ElasticNet(
            reference.LogisticL2(indices, labels, DIM, L2, workers), L1)
        w, reported, _, _ = reference_owlqn.owlqn_steps(
            obj, np.full(DIM, W0), STEPS, **{fault: False})
        if fault == "project":
            assert abs(obj.value(w) - values[-1]) > 1e-6 * values[-1]
            assert np.count_nonzero(w) > np.count_nonzero(w_ref)
        else:  # no search backtracks here: the same path, another value
            np.testing.assert_allclose(
                reported[-1] + L1 * np.abs(w).sum(), obj.value(w))
            assert values[-1] - reported[-1] > 0.1 * values[-1]


def test_reference_imports_nothing_of_the_program():
    import ast

    for name in ("reference_owlqn.py", "reference.py"):
        with open(os.path.join(ROOT, "benchmark", name)) as f:
            tree = ast.parse(f.read())
        for node in ast.walk(tree):
            names = ([a.name for a in node.names]
                     if isinstance(node, ast.Import)
                     else [node.module or ""]
                     if isinstance(node, ast.ImportFrom) else [])
            assert not any(n.startswith(("photon_ml_tpu", "jax"))
                           for n in names), (name, names)
