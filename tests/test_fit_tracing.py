"""The fixed-effect fit's tracing (ISSUE 26): kernel scopes and program
names in the lowered text, product counters against executed evaluations
and against the parent's recorded fits (to rounding), fit records that stay
on the device until read, and spans on the profiler's clock."""

import collections
import glob
import gzip
import json
import os
import re
import subprocess
import sys
import types as pytypes

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import scipy.sparse as sp

from photon_ml_tpu import types
from photon_ml_tpu.obs import metrics as obs_metrics
from photon_ml_tpu.obs import trace as obs_trace
from photon_ml_tpu.ops.objective import make_objective
from photon_ml_tpu.optimize import OptimizerConfig, get_optimizer
from photon_ml_tpu.parallel import data_parallel as dp
from photon_ml_tpu.parallel import fit_distributed, make_mesh
from photon_ml_tpu.parallel.mesh import shard_batch
from photon_ml_tpu.types import (LabeledBatch, SparseFeatures, make_batch,
                                 sparse_from_scipy)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DATA = os.path.join(ROOT, "tests", "data")

# -- counters and parity against the parent ---------------------------------
# (optimizer, line_search, sparse_grad, chips) -> (gather, transpose)
# products of the fit below; tests/data/fit_parity_pr25.npz holds what the
# parent commit (PR 25, no counters, no scopes) returned for the same calls
PARITY_CASES = {
    ("lbfgs", "margin", "scatter", 1): (9, 9),
    ("lbfgs", "margin", "scatter", 4): (9, 9),
    ("lbfgs", "full", "scatter", 1): (9, 9),
    ("lbfgs", "full", "scatter", 4): (9, 9),
    ("owlqn", "full", "scatter", 1): (17, 9),
    ("owlqn", "full", "scatter", 4): (17, 9),
    ("tron", "full", "scatter", 1): (29, 29),
    ("tron", "full", "scatter", 4): (29, 29),
    ("lbfgs", "margin", "csc", 1): (9, 9),
    ("lbfgs", "margin", "csc", 4): (9, 9),
    ("lbfgs", "full", "csc", 1): (9, 9),
    # over the sorted view the accepted point's gradient reads the margins
    # its trial gathered: a gather a trial and (f0, g0)'s
    ("owlqn", "full", "csc", 1): (9, 9),
    ("tron", "full", "csc", 1): (29, 29),
    ("tron", "full", "csc", 4): (29, 29),
}
PARITY_FIELDS = ("w", "value", "grad_norm", "iterations", "converged",
                 "loss_history", "grad_norm_history")


def parity_problem():
    rng = np.random.default_rng(26)
    n, d = 96, 24
    X = rng.normal(size=(n, d)) * (rng.random((n, d)) < 0.3)
    w_true = rng.normal(size=d)
    y = (rng.random(n) < 1 / (1 + np.exp(-X @ w_true))).astype(float)
    batch = make_batch(sparse_from_scipy(sp.csr_matrix(X), dtype=jnp.float64),
                       y, weights=rng.random(n) + 0.5, dtype=jnp.float64)
    return batch, d


def parity_fit(case, objective=None):
    optimizer, line_search, sparse_grad, chips = case
    batch, d = parity_problem()
    mesh = make_mesh({"data": chips}, devices=jax.devices()[:chips])
    return fit_distributed(
        objective or make_objective("logistic"), batch, mesh, jnp.zeros(d),
        l2=0.5,
        l1=0.05 if optimizer == "owlqn" else 0.0, optimizer=optimizer,
        config=OptimizerConfig(max_iters=8, tolerance=1e-7),
        sparse_grad=sparse_grad, line_search=line_search)


@pytest.mark.parametrize("case", list(PARITY_CASES),
                         ids=["-".join(map(str, c)) for c in PARITY_CASES])
def test_fit_counts_products_and_stays_equal_to_parent(case):
    res = parity_fit(case)
    with np.load(os.path.join(DATA, "fit_parity_pr25.npz")) as parent:
        for field in PARITY_FIELDS:
            # to rounding, not to the bit, since PR 35: at d = 24 the CPU
            # compiler unrolls the two-loop's kernels whole, and with a
            # slot sliced out of a flat history it contracts other
            # multiply-adds of them (9 of 14 cases moved: |w| by 4.4e-16
            # at most, the gradient norm by 2.1e-15; TRON's none)
            want = parent["-".join(map(str, case)) + "/" + field]
            np.testing.assert_allclose(
                np.asarray(getattr(res, field)), want,
                rtol=1e-12, atol=1e-14, err_msg=field)
            if case[0] == "tron" and case[2] == "scatter":
                # without a sorted view TRON is handed no curvature and is
                # the parent's to the bit (ISSUE 37)
                np.testing.assert_array_equal(
                    np.asarray(getattr(res, field)), want, err_msg=field)
    assert res.gather_products.dtype == jnp.int32
    assert res.transpose_products.dtype == jnp.int32
    got = (int(res.gather_products), int(res.transpose_products))
    assert got == PARITY_CASES[case]
    if case[1] == "margin":
        # the margin search adds no product: one pair a pass, and (m0, g0)
        assert got == (int(res.iterations) + 1,) * 2


def _counting(fun):
    """``fun`` with a host callback on every *executed* call: the hand
    count the program's counters are held against."""
    calls = collections.Counter()

    def counted(name):
        def wrapper(*args):
            jax.debug.callback(lambda: calls.update([name]))
            return fun[name](*args)
        return wrapper

    return calls, {name: counted(name) for name in fun}


def _rosenbrock_like():
    A = jnp.asarray(np.random.default_rng(3).normal(size=(12, 6)))

    def f(w):
        r = A @ w - jnp.sin(jnp.arange(12.0))
        return jnp.sum(jnp.log1p(r * r)) + 5.0 * jnp.sum(
            (w[1:] - w[:-1] ** 2) ** 2)

    return f, jnp.full((6,), 1.5)


@pytest.mark.parametrize("optimizer", ["lbfgs", "owlqn", "tron"])
def test_counters_equal_executed_evaluations(optimizer):
    f, w0 = _rosenbrock_like()
    fg = jax.value_and_grad(f)
    calls, fun = _counting({
        "fg": fg, "hvp": lambda w, v: jax.jvp(jax.grad(f), (w,), (v,))[1]})
    cfg = OptimizerConfig(max_iters=12, tolerance=1e-9)
    opt = get_optimizer(optimizer)
    if optimizer == "owlqn":
        res = jax.jit(lambda w: opt(fun["fg"], w, 0.01, cfg))(w0)
    elif optimizer == "tron":
        res = jax.jit(lambda w: opt(fun["fg"], w, cfg, hvp=fun["hvp"]))(w0)
    else:
        res = jax.jit(lambda w: opt(fun["fg"], w, cfg))(w0)
    jax.block_until_ready(res)
    jax.effects_barrier()
    gathers, transposes = (int(res.gather_products),
                           int(res.transpose_products))
    # every executed evaluation or HVP gathers margins once
    assert gathers == calls["fg"] + calls["hvp"]
    assert gathers > int(res.iterations) + 1  # searches and CG did work
    if optimizer == "owlqn":
        # a backtracking trial reads the value alone: the transposes are
        # (f0, g0) and one accepted point a pass
        assert transposes == int(res.iterations) + 1
    else:
        assert transposes == gathers


# -- scopes and program names in the lowered text ---------------------------
@pytest.fixture
def vector_gather():
    """The TPU's gather form on the CPU, so its scopes lower here."""
    before = types.gather_mode()
    types.set_gather_mode("vector")
    yield
    types.set_gather_mode(before)


def _criteo_like(n=512, k=39, d=256):
    rng = np.random.default_rng(0)
    idx = rng.integers(0, d, (n, k)).astype(np.int32)
    y = (rng.random(n) < 0.5).astype(np.float32)
    batch = LabeledBatch(SparseFeatures(jnp.asarray(idx), None, dim=d),
                         jnp.asarray(y), jnp.zeros(n, jnp.float32),
                         jnp.ones(n, jnp.float32))
    return batch, d


_KERNEL_SCOPES = [
    "photon.table_gather/rows", "photon.table_gather/select",
    "photon.csc/boundary_combine/lp", "photon.csc/boundary_combine/span",
    "photon.csc/prefix_sum", "photon.csc/build", "photon.glm/loss",
    "photon.allreduce/grad", "photon.allreduce/value"]
_LBFGS_SCOPES = ["photon.lbfgs/two_loop", "photon.lbfgs/line_search",
                 "photon.lbfgs/update"]
# OWL-QN's own (ISSUE 34); its two-loop is L-BFGS's, its body no longer
# borrows `photon.lbfgs/update`
_OWLQN_SCOPES = ["photon.lbfgs/two_loop", "photon.owlqn/pseudo_gradient",
                 "photon.owlqn/direction", "photon.owlqn/line_search",
                 "photon.owlqn/update"]
_TRON_SCOPES = ["photon.tron/cg", "photon.tron/hvp", "photon.tron/precond",
                "photon.tron/trial",  # the trial point's (f, g): ISSUE 36
                "photon.tron/curvature"]  # d2 of an iterate, once: ISSUE 37
# (optimizer, line_search, sparse_grad) -> (program, scopes beside the
# kernels', call sites of X^T d: distinct name stacks of the `lp` gather)
LOWERED = {
    ("lbfgs", "margin", "csc_pallas"):
        ("photon_fit_lbfgs_margin", _LBFGS_SCOPES, 2),  # g0, a pass
    ("lbfgs", "margin", "csc"):
        ("photon_fit_lbfgs_margin", _LBFGS_SCOPES, 2),
    ("lbfgs", "full", "csc_pallas"):
        ("photon_fit_lbfgs", _LBFGS_SCOPES, 2),  # g0, a search's trial
    # g0 and the accepted point, each from margins it holds: a backtracking
    # trial evaluates the value alone
    ("owlqn", "full", "csc_pallas"): ("photon_fit_owlqn", _OWLQN_SCOPES, 2),
    # g0, an HVP, the trial point, and the Jacobi diagonal (a transpose of
    # the curvature through the view) at w0 and after an accepted step
    ("tron", "full", "csc_pallas"): ("photon_fit_tron", _TRON_SCOPES, 5),
}


def _lower_fit(fit, n, d):
    """-> (the fit's lowering on one device, the batch)."""
    optimizer, line_search, sparse_grad = fit
    batch, d = _criteo_like(n=n, d=d)
    mesh = make_mesh({"data": 1}, devices=jax.devices()[:1])
    obj = make_objective("logistic")
    cfg = OptimizerConfig(max_iters=3, tolerance=0.0)
    if line_search == "margin":
        key, make = dp._margin_fit(obj, mesh, "data", cfg, sparse_grad, False)
    else:
        key, make = dp._black_box_fit(obj, mesh, "data", optimizer, cfg,
                                      sparse_grad, False)
    args = (jnp.zeros(d, jnp.float32), shard_batch(batch, mesh), 1.0)
    if line_search != "margin":
        args += (0.1 if optimizer == "owlqn" else None,)
    args += (None,)
    return dp.cached_jit(obj, key, make).lower(*args), batch


# (indices, result type, location) of every gather in a lowering's text
_GATHER = re.compile(
    r'"stablehlo\.gather"\(.*: \(tensor<[^>]*>, '
    r'tensor<(\d+)x1xi\d+>\) -> tensor<([^>]*)> loc\((#loc\d+)\)')


def _combine_gathers(text):
    """{call site: [(indices, result type, name stack below the combine's
    scope)]} of the ``stablehlo.gather``s under the boundary combine."""
    names = dict(re.findall(r'^(#loc\d+) = loc\("([^"]*)"', text, re.M))
    sites = collections.defaultdict(list)
    for indices, result, loc in _GATHER.findall(text):
        site, scope, below = names[loc].partition(
            "photon.csc/boundary_combine/")
        if scope:
            sites[site].append((int(indices), result, below))
    return sites


@pytest.mark.parametrize("fit", list(LOWERED),
                         ids=["-".join(f) for f in LOWERED])
def test_lowered_fit_holds_every_scope_once_per_call_site(fit, vector_gather):
    optimizer, line_search, sparse_grad = fit
    program, optimizer_scopes, transposes = LOWERED[fit]
    d = 256
    # three tiles of the prefix sum
    lowered, batch = _lower_fit(fit, n=2048, d=d)
    text = lowered.as_text(debug_info=True)
    assert f"@jit_{program}" in text
    stacks = set(re.findall(r'loc\("([^"]*photon\.[^"]*)"', text))
    for scope in _KERNEL_SCOPES + optimizer_scopes:
        assert any(f"{scope}/" in s for s in stacks), scope
    lp = {s for s in stacks
          if s.endswith("photon.csc/boundary_combine/lp/gather")}
    assert len(lp) == transposes, sorted(lp)
    # block totals are read where a column spans a block, not per column:
    # at each call site one gather runs over the dim column boundaries
    # after the first (`lp`: words here, dim < 2^14) and every other over
    # the B block boundaries at most
    B = -(-batch.features.indices.size // (256 * 128))  # the smaller tile
    assert 1 < B < d
    sites = _combine_gathers(text)
    assert len(sites) == transposes, sorted(sites)
    for site, gathers in sites.items():
        *short, longest = sorted(gathers)
        assert longest == (d, f"{d}xf32", "lp/gather"), (site, gathers)
        assert short and short[-1][0] <= B, (site, gathers)
    if sparse_grad == "csc_pallas":
        assert any("photon_multiply_prefix_sum" in s for s in stacks)
    if optimizer == "tron":
        # the trial point's products keep the kernels' scopes under the
        # trial's, apart from the CG's HVPs and from (f0, g0)
        trial = {s for s in stacks if "photon.tron/trial/" in s}
        assert any("photon.table_gather/rows" in s for s in trial)
        assert any("photon.csc/boundary_combine/lp" in s for s in trial)
        assert not any("photon.tron/hvp" in s or "photon.tron/cg" in s
                       for s in trial)
        # the second-order oracle at an iterate, once (ISSUE 37): an HVP
        # gathers X v and dv[rows] and not X w; the curvature gathers
        # nothing, at w0 and in the loop: it reads the margins of (f0, g0)
        # and of the accepted trial; the diagonal's one gather is d2[rows]
        names = dict(re.findall(r'^(#loc\d+) = loc\("([^"]*)"', text, re.M))
        takes = [names[loc] for loc in re.findall(
            r'call @_take\w*\(.*loc\((#loc\d+)\)$', text, re.M)]
        assert all(s.endswith("photon.table_gather/rows/jit(_take)")
                   for s in takes), takes
        under = lambda scope: [s for s in takes if f"/{scope}/" in s]
        assert len(under("photon.tron/hvp")) == 2
        assert len(under("photon.tron/curvature")) == 0
        assert len(under("photon.tron/precond")) == 2
        assert len(under("photon.tron/trial")) == 2  # X w, d[rows]
        assert len(takes) == 8  # and (f0, g0)'s two
        # the diagonal's prefix sum is the f32 cumsum, not the Pallas scan
        # (d2 is all-positive: ``make_csc_path``)
        assert not any("photon.tron/precond" in s
                       and "multiply_prefix_sum" in s for s in stacks)
        assert any("photon.tron/hvp" in s
                   and "multiply_prefix_sum" in s for s in stacks)
        # and no scatter-add: the program's scatters write the <= B
        # spanning columns of a boundary combine and a history's one slot
        assert all(s.endswith(("photon.csc/boundary_combine/span/scatter",
                               "photon.tron/update/scatter"))
                   for s in stacks if s.endswith("scatter"))
    if optimizer == "owlqn":
        assert not any("photon.lbfgs/update" in s
                       or "photon.lbfgs/line_search" in s for s in stacks)
        # the trial's product gather keeps the kernel's scope, under the
        # search's
        assert any("photon.owlqn/line_search/" in s
                   and "photon.table_gather/rows" in s for s in stacks)
        # what OWL-QN's counters say: a trial's X^T d does not run
        compiled = set(re.findall(
            r'op_name="([^"]*boundary_combine/lp/gather)"',
            lowered.compile().as_text()))
        assert len(compiled) == 2, sorted(compiled)


def test_lowered_fit_reads_the_column_boundaries_as_rows(vector_gather):
    """From dim = 2^14 on, `lp` takes ``table_gather``'s vector form:
    128-lane rows of the block-local prefixes and a lane select, under
    ``lp/rows`` and ``lp/select``; no gather of single words over the
    column boundaries is left in the fit."""
    d = types._GATHER_MIN_SIZE
    lowered, batch = _lower_fit(("lbfgs", "margin", "csc_pallas"), n=2048,
                                d=d)
    text = lowered.as_text(debug_info=True)
    B = -(-batch.features.indices.size // (256 * 128))
    rows = B * 256  # of the block-local prefixes, seen as [rows, 128]
    names = dict(re.findall(r'^(#loc\d+) = loc\("([^"]*)"', text, re.M))
    # the row gather is `jnp.take`, a function of its own in the text:
    # follow the call under `lp/rows` to the gather in its body
    calls = collections.defaultdict(list)
    for callee, types_, loc in re.findall(
            r'call @(_take\w*)\(.*\) : (\(.*\) -> tensor<[^>]*>) '
            r'loc\((#loc\d+)\)', text):
        site, scope, below = names[loc].partition(
            "photon.csc/boundary_combine/")
        if scope:
            calls[site].append((callee, types_, below))
    assert len(calls) == 2, sorted(calls)  # g0, a pass
    for site, ((callee, types_, below),) in calls.items():
        assert types_ == (f"(tensor<{rows}x128xf32>, tensor<{d}xi32>) -> "
                          f"tensor<{d}x128xf32>"), (site, types_)
        assert below == "lp/rows/jit(_take)", (site, below)
        body = text[text.index(f"func.func private @{callee}("):]
        body = body[:body.index("\n  }")]
        gather, = re.findall(r'"stablehlo\.gather".*', body)
        assert "slice_sizes = array<i64: 1, 128>" in gather
        assert f"tensor<{d}x1xi32>) -> tensor<{d}x128xf32>" in gather
    # what is left under the combine's scope reads <= B words: `span`
    sites = _combine_gathers(text)
    assert sorted(sites) == sorted(calls)
    for site, gathers in sites.items():
        assert gathers and max(gathers)[0] <= B, (site, gathers)
        assert {below for _, _, below in gathers} <= {
            "span/gather", "span/jit(searchsorted)/gather"}, gathers
    stacks = set(re.findall(r'loc\("([^"]*photon\.[^"]*)"', text))
    assert any("photon.csc/boundary_combine/lp/select/" in s for s in stacks)
    # the product gathers keep their scopes, and the combine has none of them
    assert any("photon.table_gather/rows/" in s for s in stacks)
    assert not any("boundary_combine" in s and "photon.table_gather" in
                   s.partition("boundary_combine")[2] for s in stacks)
    # no gather of single floats over dim or dim + 1 indices is left (the
    # binary search that builds `col_starts` reads int32 column ids)
    for indices, result, _ in _GATHER.findall(text):
        assert not (int(indices) in (d, d + 1) and "x128x" not in result
                    and re.search(r"xf\d+$", result)), (indices, result)


def test_scalar_mode_leaves_no_row_gather_in_the_fit():
    """Under ``set_gather_mode("scalar")`` the same fit reads words
    everywhere, `lp` among them."""
    before = types.gather_mode()
    types.set_gather_mode("scalar")
    try:
        d = types._GATHER_MIN_SIZE
        lowered, _ = _lower_fit(("lbfgs", "margin", "csc_pallas"), n=2048,
                                d=d)
        text = lowered.as_text(debug_info=True)
    finally:
        types.set_gather_mode(before)
    assert "stablehlo.gather" in text
    assert "slice_sizes = array<i64: 1, 128>" not in text
    for site, gathers in _combine_gathers(text).items():
        assert max(gathers) == (d, f"{d}xf32", "lp/gather"), (site, gathers)


def test_build_csc_is_one_named_cached_program():
    batch, _ = _criteo_like(n=64, k=4, d=32)
    mesh = make_mesh({"data": 1}, devices=jax.devices()[:1])
    obj = make_objective("logistic")
    dp.build_csc(obj, batch, mesh)
    before = dp.compiled_kernel_count(obj)
    dp.build_csc(obj, batch, mesh)
    assert dp.compiled_kernel_count(obj) == before == 1
    (key, fn), = dp._runner_cache_for(obj).items()
    assert key[0] == "build_csc"
    assert "@jit_photon_build_csc" in fn.lower(
        shard_batch(batch, mesh)).as_text()


# -- fit records -------------------------------------------------------------
class _OnDevice:
    """A scalar that says when it is fetched."""

    fetched = 0

    def __init__(self, value):
        self.value = value

    def __int__(self):
        type(self).fetched += 1
        return self.value


def _result(passes, gathers, transposes, trials=None, nonzeros=None,
            cls=_OnDevice, tron=None, reused=None):
    """``trials`` and ``nonzeros`` are OWL-QN's, ``tron`` = (CG steps,
    refused steps, diagonals, curvatures) TRON's, ``reused`` both's: None
    from the others."""
    cg, refused, diagonals, curvatures = tron or (None,) * 4
    return pytypes.SimpleNamespace(
        iterations=cls(passes), gather_products=cls(gathers),
        transpose_products=cls(transposes),
        line_search_trials=None if trials is None else cls(trials),
        nonzeros=None if nonzeros is None else cls(nonzeros),
        cg_steps=None if cg is None else cls(cg),
        rejected_steps=None if refused is None else cls(refused),
        precond_passes=None if diagonals is None else cls(diagonals),
        curvature_passes=None if curvatures is None else cls(curvatures),
        margins_reused=None if reused is None else cls(reused))


def test_record_fit_fetches_nothing_until_read_and_keeps_64():
    tm = obs_metrics.TrainingMetrics()
    _OnDevice.fetched = 0
    for i in range(tm.FIT_RECORDS):
        tm.record_fit(optimizer="lbfgs", sparse_grad="csc_pallas",
                      compiled=i == 0, dispatch_s=0.002,
                      result=_result(10, 11, 11))
    assert _OnDevice.fetched == 0
    snap = tm.snapshot()
    assert _OnDevice.fetched == 3 * tm.FIT_RECORDS
    assert snap["photon_train_fit_total"] == {"": 64}
    assert snap["photon_train_fit_passes_total"] == {"": 640}
    assert snap["photon_train_fit_products_total"] == {
        'kind="gather"': 704, 'kind="transpose"': 704}
    assert snap["photon_train_fit_dispatch_seconds"][""]["count"] == 64
    tm.snapshot()  # a record is fetched once
    assert _OnDevice.fetched == 3 * tm.FIT_RECORDS
    # the 65th pushes the oldest out of the ring, counted on its way
    tm.record_fit(optimizer="tron", sparse_grad="csc", compiled=False,
                  dispatch_s=0.5, result=_result(1, 6, 6))
    records = tm.fit_records()
    assert len(records) == tm.FIT_RECORDS
    assert records[-1] == {
        "optimizer": "tron", "sparse_grad": "csc", "compiled": False,
        "dispatch_s": 0.5, "iterations": 1, "gather_products": 6,
        "transpose_products": 6, "line_search_trials": None,
        "nonzeros": None, "cg_steps": None, "rejected_steps": None,
        "precond_passes": None, "curvature_passes": None,
        "margins_reused": None}
    assert records[0]["compiled"] is False  # the first record has gone
    assert tm.snapshot()["photon_train_fit_passes_total"] == {"": 641}
    # an OWL-QN fit's record carries its three counters, fetched with the
    # other three when the record is read and not before
    fetched = _OnDevice.fetched
    tm.record_fit(optimizer="owlqn", sparse_grad="csc", compiled=False,
                  dispatch_s=0.1,
                  result=_result(10, 13, 11, 12, 580063, reused=10))
    assert _OnDevice.fetched == fetched
    last = tm.fit_records()[-1]
    assert _OnDevice.fetched == fetched + 6
    assert (last["line_search_trials"], last["nonzeros"]) == (12, 580063)
    assert last["margins_reused"] == 10
    assert (last["iterations"], last["gather_products"]) == (10, 13)
    assert (last["cg_steps"], last["rejected_steps"], last["precond_passes"],
            last["curvature_passes"]) == (None, None, None, None)
    # and a TRON fit's its five, fetched on read and not before
    fetched = _OnDevice.fetched
    tm.record_fit(optimizer="tron", sparse_grad="csc_pallas", compiled=False,
                  dispatch_s=0.1,
                  result=_result(6, 19, 19, tron=(12, 1, 5, 5), reused=4))
    assert _OnDevice.fetched == fetched
    last = tm.fit_records()[-1]
    assert _OnDevice.fetched == fetched + 8
    assert (last["cg_steps"], last["rejected_steps"], last["precond_passes"],
            last["curvature_passes"], last["margins_reused"]) == (
        12, 1, 5, 5, 4)
    assert (last["line_search_trials"], last["nonzeros"]) == (None, None)


def test_fit_distributed_leaves_a_record_without_a_device_fetch():
    tm = obs_metrics.training_metrics()
    case, obj = ("tron", "full", "scatter", 1), make_objective("logistic")
    parity_fit(case, obj)
    assert tm.fit_records()[-1]["compiled"] is True
    before = len(tm.fit_records())
    # the guard bites on a chip; on the CPU a read is no transfer, and
    # the fetch count is held by ``_OnDevice`` in the test above
    with jax.transfer_guard_device_to_host("disallow"):
        res = parity_fit(case, obj)
    rec = tm.fit_records()[-1]
    assert len(tm.fit_records()) == min(before + 1, tm.FIT_RECORDS)
    assert (rec["optimizer"], rec["sparse_grad"], rec["compiled"]) == (
        "tron", "scatter", False)
    assert (rec["iterations"], rec["gather_products"],
            rec["transpose_products"]) == (int(res.iterations), 29, 29)
    assert 0 < rec["dispatch_s"] < 60
    # TRON's own: products = (f0, g0) + an HVP a CG step + a trial a
    # pass; a diagonal at w0 and one a step accepted before the last (here
    # every step is accepted and the last one converges); no curvature
    # without the sorted view
    assert rec["cg_steps"] == 29 - 1 - rec["iterations"]
    assert rec["rejected_steps"] == int(res.rejected_steps) == 0
    assert rec["precond_passes"] == rec["iterations"] > 1
    assert rec["curvature_passes"] == rec["margins_reused"] == 0
    with_view = parity_fit(("tron", "full", "csc", 1), obj)
    assert tm.fit_records()[-1]["curvature_passes"] == int(
        with_view.precond_passes) == rec["iterations"]
    # every curvature but w0's is read off an accepted trial's margins
    assert tm.fit_records()[-1]["margins_reused"] == rec["iterations"] - 1
    for case in (("lbfgs", "margin", "scatter", 1),
                 ("owlqn", "full", "scatter", 1)):
        other = parity_fit(case)
        rec = tm.fit_records()[-1]
        assert rec["optimizer"] == case[0]
        for name in ("cg_steps", "rejected_steps", "precond_passes",
                     "curvature_passes"):
            assert getattr(other, name) is None and rec[name] is None


def test_a_fit_traced_inside_a_jit_leaves_no_record():
    # tests/test_tpu_lowering.py and test_tpu_compile.py trace whole fits
    # from shapes; their counters are tracers, and a record of them failed
    # the fit that pushed it out of the ring, FIT_RECORDS fits later in
    # the same process
    tm = obs_metrics.training_metrics()
    case = ("lbfgs", "margin", "scatter", 1)
    parity_fit(case)
    before = [r["iterations"] for r in tm.fit_records()]
    jax.jit(lambda: parity_fit(case).w).lower()
    assert [r["iterations"] for r in tm.fit_records()] == before
    for _ in range(tm.FIT_RECORDS + 1):
        tm.record_fit(optimizer="lbfgs", sparse_grad="scatter",
                      compiled=False, dispatch_s=0.0,
                      result=pytypes.SimpleNamespace(
                          iterations=1, gather_products=None,
                          transpose_products=None,
                          line_search_trials=None, nonzeros=None))


def test_streamed_results_count_nothing():
    tm = obs_metrics.TrainingMetrics()
    tm.record_fit(optimizer="lbfgs", sparse_grad="scatter", compiled=False,
                  dispatch_s=0.0, result=pytypes.SimpleNamespace(
                      iterations=3, gather_products=None,
                      transpose_products=None,
                      line_search_trials=None, nonzeros=None))
    assert tm.fit_records()[0]["gather_products"] is None
    assert tm.snapshot()["photon_train_fit_passes_total"] == {"": 3}


# the series a fresh TrainingMetrics rendered before the fit's records, in
# their order (less the four per-step series PR 38 removed: the CD step's
# seconds are in the sweep record): the new ones may only follow them
_PARENT_SERIES = [
    "photon_train_chunk_cache_warm_passes_total",
    "photon_train_chunk_cache_cold_passes_total",
    "photon_train_chunk_cache_fallthrough_passes_total",
    "photon_train_prefetch_stall_seconds_total",
    "photon_train_prefetch_decode_seconds_total",
    "photon_train_prefetch_transfer_seconds_total",
    "photon_train_exchange_bytes_sent_total",
    "photon_train_exchange_bytes_gathered_total",
    "photon_train_exchange_rounds_total",
    "photon_train_exchange_seconds_total",
    "photon_train_path_lambdas_total",
    "photon_train_path_features_frozen_total",
    "photon_train_path_kkt_rounds_total",
    "photon_train_path_kkt_violations_total",
    "photon_train_path_full_grad_passes_total",
    "photon_train_path_fallback_total"]


def test_prometheus_text_keeps_its_contract():
    tm = obs_metrics.TrainingMetrics()
    tm.record_fit(optimizer="lbfgs", sparse_grad="csc", compiled=True,
                  dispatch_s=0.0015, result=_result(10, 11, 11, cls=int))
    text = tm.render()
    names = re.findall(r"^# TYPE (\S+) (\S+)$", text, flags=re.M)
    assert [n for n, _ in names] == _PARENT_SERIES + [
        "photon_train_fit_total", "photon_train_fit_passes_total",
        "photon_train_fit_products_total",
        "photon_train_fit_dispatch_seconds",
        # the sweep's series follow the fit's
        "photon_train_sweep_total", "photon_train_sweep_seconds",
        "photon_train_re_entities_solved_total",
        "photon_train_re_newton_iterations_total",
        "photon_train_re_row_slots_total",
        "photon_train_h2d_bytes_total", "photon_train_d2h_bytes_total",
        "photon_train_compiles_total",
        # the GAME path's blocking fetches, cache loads and runs (PR 38)
        "photon_train_syncs_total", "photon_train_sync_wait_seconds_total",
        "photon_train_cache_loads_total", "photon_train_run_total",
        "photon_train_run_seconds"]
    assert dict(names)["photon_train_fit_dispatch_seconds"] == "histogram"
    assert "photon_train_fit_total 1\n" in text
    assert "photon_train_fit_passes_total 10\n" in text
    assert 'photon_train_fit_products_total{kind="gather"} 11\n' in text
    assert 'photon_train_fit_products_total{kind="transpose"} 11\n' in text
    assert 'photon_train_fit_dispatch_seconds_bucket{le="0.005"} 1\n' in text
    assert "photon_train_fit_dispatch_seconds_count 1\n" in text
    # the serving exposition does not know the training series
    assert "photon_train" not in obs_metrics.ServingMetrics().render()


# -- spans on the profiler's clock ------------------------------------------
def _xplane_bytes(trace_dir):
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    assert found, "the profiler wrote no .xplane.pb"
    with open(found[0], "rb") as f:
        return f.read()


def test_span_is_an_annotation_in_a_profiler_session(tmp_path):
    assert obs_trace.active_tracer() is None
    assert obs_trace.span("fit.dispatch") is obs_trace._NULL_SPAN
    with obs_trace.profile(str(tmp_path / "no_tracer")):
        with obs_trace.span("photon-span-alone", cat="train", rows=7) as sp:
            assert sp is not obs_trace._NULL_SPAN
            sp.set(compiled=True)
            jnp.ones(8).block_until_ready()
    assert obs_trace.span("fit.dispatch") is obs_trace._NULL_SPAN
    assert b"photon-span-alone" in _xplane_bytes(str(tmp_path / "no_tracer"))

    obs_trace.start(str(tmp_path / "photon"), export_thread=False)
    try:
        with obs_trace.profile(str(tmp_path / "both")):
            with obs_trace.span("photon-span-both", cat="train"):
                jnp.ones(8).block_until_ready()
    finally:
        obs_trace.stop()
    assert b"photon-span-both" in _xplane_bytes(str(tmp_path / "both"))
    with open(tmp_path / "photon" / "trace-rank0.json") as f:
        events = json.load(f)["traceEvents"]
    assert "photon-span-both" in {e["name"] for e in events}


def test_fit_spans_carry_their_arguments(tmp_path):
    obs_trace.start(str(tmp_path), export_thread=False)
    try:
        parity_fit(("lbfgs", "margin", "csc", 1))
        batch, _ = parity_problem()
        dp.build_csc(make_objective("logistic"), batch,
                     make_mesh({"data": 1}, devices=jax.devices()[:1]))
    finally:
        obs_trace.stop()
    with open(tmp_path / "trace-rank0.json") as f:
        spans = {e["name"]: e for e in json.load(f)["traceEvents"]
                 if e.get("ph") == "X"}
    assert {"fit", "fit.shard_batch", "fit.dispatch",
            "fit.build_csc"} <= set(spans)
    fit = spans["fit"]
    assert {k: fit["args"][k] for k in (
        "optimizer", "sparse_grad", "rows", "dim", "chips")} == {
        "optimizer": "lbfgs", "sparse_grad": "csc", "rows": 96, "dim": 24,
        "chips": 1}
    assert isinstance(fit["args"]["compiled"], bool)
    for child in ("fit.shard_batch", "fit.dispatch"):
        assert spans[child]["args"]["trace_id"] == fit["args"]["trace_id"]
        assert fit["ts"] <= spans[child]["ts"]
        assert (spans[child]["ts"] + spans[child]["dur"]
                <= fit["ts"] + fit["dur"] + 1e-3)


def test_one_function_starts_a_profiler_session():
    """``obs.trace.profile`` is the program's only profiler session, and
    the old entry points are gone."""
    hits = []
    for path in glob.glob(os.path.join(ROOT, "photon_ml_tpu", "**", "*.py"),
                          recursive=True):
        with open(path) as f:
            src = f.read()
        if "profiler.start_trace(" in src or "profiler.trace(" in src:
            hits.append(os.path.relpath(path, ROOT))
        assert ".".join(("utils", "tracing")) not in src, path
    assert hits == [os.path.join("photon_ml_tpu", "obs", "trace.py")]
    assert not os.path.exists(
        os.path.join(ROOT, "photon_ml_tpu", "utils", "tracing.py"))


# -- the benchmark's readers -------------------------------------------------
def _reader(name):
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    from benchmark import harness

    return harness.load_module(os.path.join(
        harness.BENCH_DIR, "metrics", name + ".py"))


def _run(seconds):
    t, pieces = 100.0, []
    for s in seconds:
        pieces.append({"t0": t, "t1": t + s, "passes": 10})
        t += s + 0.004
    return pytypes.SimpleNamespace(window={"pieces": pieces})


def test_readers_on_hand_made_records(monkeypatch):
    tm = obs_metrics.TrainingMetrics()
    monkeypatch.setattr(obs_metrics, "_TRAINING", tm)
    run = _run([7.0, 7.7, 7.0])
    readers = [_reader(n) for n in (
        "fit_products_per_pass", "fit_product_ms", "fit_dispatch_ms")]
    assert [r.read(run) for r in readers] == [None, None, None]  # no record
    # set-up's fit, then the window's three
    for i, dispatch in enumerate([2.5, 0.0012, 0.0014, 0.0013]):
        tm.record_fit(optimizer="lbfgs", sparse_grad="csc_pallas",
                      compiled=i == 0, dispatch_s=dispatch,
                      result=_result(10, 11, 11, cls=int))
    per_pass, product_ms, dispatch_ms = (r.read(run) for r in readers)
    assert per_pass == pytest.approx(1.1)
    assert product_ms == pytest.approx(7000.0 / 11)
    assert dispatch_ms == pytest.approx(1.3)
    # fit_pass_ms is their product
    assert product_ms * per_pass == pytest.approx(
        _reader("fit_pass_ms").read(run))
    # counters the optimizer does not keep: nothing is reported
    tm.record_fit(optimizer="lbfgs", sparse_grad="scatter", compiled=False,
                  dispatch_s=0.001, result=pytypes.SimpleNamespace(
                      iterations=10, gather_products=None,
                      transpose_products=None,
                      line_search_trials=None, nonzeros=None))
    assert readers[0].read(run) is None and readers[1].read(run) is None
    assert readers[2].read(run) == pytest.approx(1.3)


def test_readers_find_nothing_in_a_program_without_records(monkeypatch):
    monkeypatch.setattr(obs_metrics, "training_metrics",
                        lambda: pytypes.SimpleNamespace())  # the parent's
    run = _run([7.0])
    for name in ("fit_products_per_pass", "fit_product_ms",
                 "fit_dispatch_ms"):
        assert _reader(name).read(run) is None


def test_new_metrics_are_declared_for_every_cell():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    # every cell of whole fixed-effect fits that PR 26 found (the GAME
    # cells keep sweep records, not fit records, and have readers of their
    # own; so has the elastic-net cell, below: appending it to these lists
    # would be an edit of what the benchmark has)
    cells = [w["name"] for w in bench["workloads"]
             if w["traffic"] in ("fit", "fit-x4")]
    declared = {m["name"]: m for m in bench["per_layer"]}
    for name, source, layer in (
            ("fit_products_per_pass", "program_counter", "optimize"),
            ("fit_product_ms", "program_counter", "kernels"),
            ("fit_dispatch_ms", "program_span", "parallel/data_parallel")):
        m = declared[name]
        assert (m["source"], m["layer"], m["moves"], m["workloads"]) == (
            source, layer, "train_rows_per_s", cells)
    for name, source, layer in (
            ("enet_mfu_pct", "host_clock", "whole step"),
            ("enet_roofline_pct", "host_clock", "whole step"),
            ("enet_device_idle_pct", "device_trace", "device"),
            ("enet_pass_ms", "host_clock", "optimize"),
            ("enet_trials_per_pass", "program_counter", "optimize"),
            ("enet_gathers_per_pass", "program_counter", "optimize")):
        m = declared[name]
        assert (m["source"], m["layer"], m["moves"], m["workloads"]) == (
            source, layer, "train_rows_per_s", ["criteo-enet.fit"])
    # the Poisson TRON cell's seven, for that cell alone (ISSUE 36)
    pois = {m["name"]: m for m in bench["per_layer"]
            if m["name"].startswith("pois_")}
    assert list(pois) == [
        "pois_mfu_pct", "pois_roofline_pct", "pois_device_idle_pct",
        "pois_pass_ms", "pois_products_per_pass", "pois_cg_steps_per_pass",
        "pois_rejected_steps_per_pass"]
    for name, source, layer in (
            ("pois_mfu_pct", "host_clock", "whole step"),
            ("pois_roofline_pct", "host_clock", "whole step"),
            ("pois_device_idle_pct", "device_trace", "device"),
            ("pois_pass_ms", "host_clock", "optimize"),
            ("pois_products_per_pass", "program_counter", "optimize"),
            ("pois_cg_steps_per_pass", "program_counter", "optimize"),
            ("pois_rejected_steps_per_pass", "program_counter", "optimize")):
        m = pois[name]
        assert (m["source"], m["layer"], m["moves"], m["workloads"]) == (
            source, layer, "train_rows_per_s", ["criteo-poisson-tron.fit"])
        assert os.path.exists(os.path.join(ROOT, "benchmark", "metrics",
                                           name + ".py"))
    # and no list the benchmark had names the new cell
    for m in bench["per_layer"]:
        if not m["name"].startswith("pois_"):
            assert "criteo-poisson-tron.fit" not in m["workloads"], m["name"]


def test_traced_rehearsal_still_ends():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    done = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "criteo-lr-tron.fit", "--seed", "3000000019", "--seconds", "0.2",
         "--trace", "1", "--rehearse", "1"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert done.returncode == 0, done.stderr[-2000:]
    result = json.loads(done.stdout.strip().splitlines()[-1])
    assert result["metrics"] == {}  # a rehearsal prints no metric
    assert result["correct"] is True
    assert result["compared"]["window_compiles"] == [0, 0]


# -- photon-trace kernels on a trace recorded on the chip -------------------
KERNEL_TRACE = os.path.join(DATA, "tiny_fit_scopes.xplane.pb.gz")


def test_kernels_table_of_the_recorded_trace(capsys):
    """Two 3-pass L-BFGS fits at 2^12 rows, 2^14 buckets, recorded on the
    v5e by ``scripts/trace_window.py --keep-trace`` with the scopes in."""
    from photon_ml_tpu.obs import trace_cli, xplane

    table = xplane.kernel_table(KERNEL_TRACE)
    assert table["busy_s"] > 0
    assert table["attributed_share"] > 0.9
    rollup, scopes = table["rollup"], table["scopes"]
    for scope in ("photon.csc/boundary_combine", "photon.table_gather/rows",
                  "photon.table_gather/select", "photon.lbfgs/two_loop",
                  "photon.lbfgs/update", "photon.glm/loss"):
        assert rollup[scope]["device_s"] > 0, scope
    # the one gather over the column boundaries, in the vector form (2^14
    # buckets: 128-lane rows, a lane select, and `lp`'s own index
    # arithmetic and `where`), and the block totals of the spanning
    # columns; "" is the function's own `lp[1:] - lp[:-1]`
    prefix = "photon.csc/boundary_combine"
    combine = {s[len(prefix):].lstrip("/"): row for s, row in scopes.items()
               if s.startswith(prefix)}
    assert sorted(combine) == ["", "lp", "lp/rows", "lp/select", "span"]
    for row in combine.values():
        assert row["executions"] > 0 and row["instructions"]
    lp_s = sum(row["device_s"] for s, row in combine.items()
               if s.startswith("lp"))
    assert combine["lp/rows"]["device_s"] > combine["lp/select"]["device_s"]
    assert combine["lp/rows"]["device_s"] > 10 * combine["lp"]["device_s"]
    assert combine["span"]["device_s"] < 0.5 * lp_s
    # `photon.table_gather/*` counts the product gathers alone: two fits of
    # 4 `X p` and 4 `d[rows]` each; the 4 + 4 row gathers of `lp` are its own
    ops = next(iter(xplane.device_ops(KERNEL_TRACE).values()))
    row_gathers = collections.Counter(
        xplane.scope_of(op["tf_op"]) for op in ops
        if op["name"].startswith("fusion")
        and op["tf_op"].rstrip(":").endswith("/gather")
        and "/rows/" in op["tf_op"])
    assert row_gathers == {"photon.table_gather/rows": 2 * (4 + 4),
                           "photon.csc/boundary_combine/lp/rows": 2 * 4}
    assert sum(r["share"] for r in scopes.values()) == pytest.approx(1.0)
    assert sum(r["device_s"] for r in scopes.values()) == pytest.approx(
        table["busy_s"])
    # every event's program is named after the fit
    programs = {op["tf_op"].split("/")[0] for op in ops if op["tf_op"]}
    assert "jit(photon_fit_lbfgs_margin)" in programs
    assert "jit(run)" not in programs

    assert trace_cli.main(["kernels", KERNEL_TRACE]) == 0
    out = capsys.readouterr().out
    assert "photon.csc/boundary_combine" in out and "% busy" in out
    assert trace_cli.main(["kernels", KERNEL_TRACE, "--json"]) == 0
    assert json.loads(capsys.readouterr().out)["busy_s"] == table["busy_s"]


def test_scope_of_a_name_stack():
    from photon_ml_tpu.obs.xplane import scope_of

    assert scope_of("jit(photon_fit_lbfgs_margin)/while/body/photon.lbfgs/"
                    "update/photon.csc/boundary_combine/lp/gather:") == (
        "photon.csc/boundary_combine/lp")
    assert scope_of("jit(f)/photon.table_gather/while/body/"
                    "photon.table_gather/rows/gather:") == (
        "photon.table_gather/rows")
    assert scope_of("jit(f)/photon.glm/loss/transpose(jvp())/mul:") == (
        "photon.glm/loss")
    assert scope_of("jit(f)/photon.lbfgs/line_search/while/body/cond/"
                    "branch_1_fun/select_n:") == "photon.lbfgs/line_search"
    assert scope_of("jit(f)/while/body/add:") is None
    assert scope_of("") is None


def test_scope_of_the_boundary_gather_in_its_vector_form():
    """`lp` calls ``table_gather``'s body with scopes of its own, so its
    rows and its lane select stay the combine's in the kernels table."""
    from photon_ml_tpu.obs.xplane import scope_of

    lp = ("jit(photon_fit_lbfgs_margin)/while/body/photon.lbfgs/update/"
          "photon.csc/boundary_combine/lp/")
    assert scope_of(lp + "while/body/closed_call/rows/jit(_take)/gather:") == (
        "photon.csc/boundary_combine/lp/rows")
    assert scope_of(lp + "while/body/closed_call/select/reduce_sum:") == (
        "photon.csc/boundary_combine/lp/select")
    assert scope_of(lp + "rows/jit(_take)/gather:") == (
        "photon.csc/boundary_combine/lp/rows")  # one chunk: no lax.map
    assert scope_of(lp + "while:") == "photon.csc/boundary_combine/lp"
    assert scope_of(lp + "jit(_where)/select_n:") == (
        "photon.csc/boundary_combine/lp")
    # the product gather's own stack, for contrast
    assert scope_of("jit(photon_fit_lbfgs_margin)/while/body/"
                    "photon.table_gather/while/body/closed_call/"
                    "photon.table_gather/rows/jit(_take)/gather:") == (
        "photon.table_gather/rows")
    for stack in (lp + "while/body/closed_call/rows/jit(_take)/gather:",
                  lp + "while/body/closed_call/select/reduce_sum:"):
        assert not scope_of(stack).startswith("photon.table_gather")


def test_wire_reader_reads_the_trace_without_names():
    """The benchmark's recorded trace (PR 25, before the scopes): the same
    events, none attributed."""
    from photon_ml_tpu.obs import xplane

    path = os.path.join(ROOT, "benchmark", "testdata",
                        "tiny_fit.xplane.pb.gz")
    ops = xplane.device_ops(path)
    assert list(ops) == ["/device:TPU:0"] and len(ops["/device:TPU:0"]) == 9690
    op = ops["/device:TPU:0"][100]
    assert op["tf_op"].startswith("jit(run)/while/body")
    assert op["end_ps"] > op["start_ps"] and op["bytes_accessed"] > 0
    table = xplane.kernel_table(path)
    assert table["attributed_share"] == 0.0
    assert list(table["scopes"]) == [xplane.UNSCOPED]
    with gzip.open(path) as f:
        assert len(f.read()) > 0
