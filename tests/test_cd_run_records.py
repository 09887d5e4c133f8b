"""What a GLMix run says of its host's time (PR 38): every blocking fetch
is a timed ``cd.fetch`` counted as one sync, a run leaves a record of its
``cd.prepare`` and ``cd.finish`` stages, and the benchmark's five readers
of them.

The runs are the benchmark cell's own (``glmix-ml20m.cd-sweep`` at its
rehearsal's shapes, ``benchmark/runners/game_cd.py``): the set-up run, then
one more over the same ``dataset_cache``.
"""

import os
import sys
from types import SimpleNamespace

import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import harness  # noqa: E402
from photon_ml_tpu.game import random_effect  # noqa: E402
from photon_ml_tpu.obs import metrics as obs_metrics  # noqa: E402
from photon_ml_tpu.obs import trace as obs_trace  # noqa: E402
from photon_ml_tpu.obs import xplane  # noqa: E402

# fetches a sweep at the rehearsal's shapes: the fixed effect's value,
# convergence and iterations and its rescoring's delta; a random effect's
# counts and rescoring's delta, twice; the training loss
SYNCS_A_SWEEP = 9


@pytest.fixture(scope="module")
def two_runs():
    cell = harness.load_cell(ROOT, "glmix-ml20m.cd-sweep", rehearse=True)
    runner = harness.load_module(os.path.join(
        harness.BENCH_DIR, "runners", "game_cd.py")).Runner(cell, 2**31 + 11)
    tm = obs_metrics.training_metrics()
    runner.setup()  # run 0: regroup, placement, compiles
    runner.piece(1)  # run 1, over the same dataset_cache
    model = runner.last[1]
    buckets = sum(len(b.entity_ids) > 0
                  for name in ("per-user", "per-item")
                  for b in model.coordinates[name].buckets)
    sweeps = tm.sweep_records()[-4:]
    return tm.run_records()[-2:], [sweeps[:2], sweeps[2:]], buckets


def test_a_run_leaves_a_record_its_stages_and_sweeps_fit_in(two_runs):
    runs, sweeps, _ = two_runs
    for run, its_sweeps in zip(runs, sweeps):
        assert run["sweeps"] == len(its_sweeps) == 2
        inside = (run["prepare_seconds"] + run["finish_seconds"]
                  + sum(s["seconds"] for s in its_sweeps))
        assert 0 < inside <= run["seconds"]
        assert set(run["prepare"]) == set(run["finish"]) == {
            "h2d_bytes", "d2h_bytes", "compiles", "syncs",
            "sync_wait_seconds", "cache_loads"}


def test_a_sweep_syncs_nine_times_and_the_finish_once_a_bucket(two_runs):
    runs, sweeps, buckets = two_runs
    for run, its_sweeps in zip(runs, sweeps):
        assert run["prepare"]["syncs"] == 0
        # one fetch of coefficients a bucket that has entities
        assert run["finish"]["syncs"] == buckets > 0
        for s in its_sweeps:
            assert s["syncs"] == SYNCS_A_SWEEP
            # the steps' fetches, and the training loss's after them
            assert sum(c["syncs"] for c in s["coordinates"]) + 1 == s["syncs"]
            assert [c["syncs"] for c in s["coordinates"]] == [4, 2, 2]


def test_no_wait_outlasts_what_waited(two_runs):
    runs, sweeps, _ = two_runs
    for run, its_sweeps in zip(runs, sweeps):
        for stage in ("prepare", "finish"):
            assert 0 <= run[stage]["sync_wait_seconds"] <= (
                run[stage + "_seconds"])
        for s in its_sweeps:
            assert 0 < s["sync_wait_seconds"] <= s["seconds"]
            for c in s["coordinates"]:
                assert 0 < c["sync_wait_seconds"] <= c["seconds"]


def test_the_second_run_compiles_and_loads_nothing(two_runs):
    runs, sweeps, _ = two_runs
    for stage in (runs[1]["prepare"], runs[1]["finish"], *sweeps[1]):
        assert stage["compiles"] == 0 and stage["cache_loads"] == 0


# -- fetch ------------------------------------------------------------------
def test_fetch_with_tracing_off_is_the_null_span_and_its_counts(monkeypatch):
    assert obs_trace.active_tracer() is None and not obs_trace._profiling()
    opened = []
    real = obs_trace.span

    def spy(name, cat="app", **args):
        sp = real(name, cat, **args)
        opened.append((name, cat, args, sp))
        return sp

    monkeypatch.setattr(obs_trace, "span", spy)
    tm = obs_metrics.training_metrics()
    a = jnp.arange(4, dtype=jnp.float32).block_until_ready()
    before = tm.transfer_counts()
    got = random_effect.fetch(a, "train_loss")
    moved = tm.transfer_counts().since(before)
    np.testing.assert_array_equal(got, [0, 1, 2, 3])
    assert opened == [("cd.fetch", "train", {"what": "train_loss"},
                       obs_trace._NULL_SPAN)]
    assert moved["syncs"] == 1 and moved["d2h_bytes"] == 16
    assert 0 <= moved["sync_wait_seconds"] < 1
    assert moved["h2d_bytes"] == moved["compiles"] == 0
    # a host array is no sync: no span, nothing counted
    opened.clear()
    before = tm.transfer_counts()
    random_effect.fetch(np.ones(3), "model")
    assert opened == []
    assert tm.transfer_counts() == before


def test_fetch_is_a_span_on_the_profilers_clock(tmp_path):
    a = jnp.arange(8.0).block_until_ready()
    with obs_trace.profile(str(tmp_path)):
        with obs_trace.span("cd.finish", cat="train"):
            random_effect.fetch(a, "model")
    spans = {name: (s0, s1)
             for name, s0, s1 in xplane.host_spans(str(tmp_path))}
    assert {"cd.finish", "cd.fetch"} <= set(spans)
    (f0, f1), (s0, s1) = spans["cd.fetch"], spans["cd.finish"]
    assert s0 <= f0 < f1 <= s1


# -- the benchmark's readers ------------------------------------------------
READERS = ["cd_syncs_per_sweep", "cd_sweep_host_ms", "cd_prepare_ms",
           "cd_finish_ms", "cd_setup_prepare_s"]


def _read(name, run):
    return harness.load_module(os.path.join(
        harness.BENCH_DIR, "metrics", name + ".py")).read(run)


def _sweep(seconds, waited, syncs=9):
    return {"seconds": seconds, "sync_wait_seconds": waited, "syncs": syncs,
            "coordinates": []}


def _run_record(prepare, finish):
    stage = {"h2d_bytes": 0.0, "d2h_bytes": 0.0, "compiles": 0.0,
             "syncs": 0.0, "sync_wait_seconds": 0.0, "cache_loads": 0.0}
    return {"seconds": 1.0, "prepare_seconds": prepare,
            "finish_seconds": finish, "sweeps": 2, "prepare": stage,
            "finish": stage}


def _bench_run(sweeps, pieces=2):
    return SimpleNamespace(window={"pieces": [{}] * pieces, "rows": 8,
                                   "sweeps": sweeps})


@pytest.mark.parametrize("name", READERS)
def test_a_reader_finds_nothing_without_records(name, monkeypatch):
    """The parent's program: sweep records without ``syncs``, no run
    records; and a program that keeps no ``run_records`` at all."""
    monkeypatch.setattr(obs_metrics, "training_metrics",
                        obs_metrics.TrainingMetrics)
    old_sweep = {"seconds": 1.0, "coordinates": [], "h2d_bytes": 0.0}
    assert _read(name, _bench_run([old_sweep] * 4)) is None
    assert _read(name, _bench_run(None)) is None
    monkeypatch.setattr(obs_metrics, "training_metrics", SimpleNamespace)
    assert _read(name, _bench_run([old_sweep] * 4)) is None


@pytest.mark.parametrize("name,want", [
    ("cd_syncs_per_sweep", (9 + 9 + 10 + 9) / 4),
    ("cd_sweep_host_ms", 42.5),  # of 40, 45, 35, 50 ms
    ("cd_prepare_ms", 15.0),  # of the window's two runs: 10 and 20 ms
    ("cd_finish_ms", 35.0),  # 30 and 40 ms
    ("cd_setup_prepare_s", 7.5),  # the run before the window's
])
def test_a_reader_reads_hand_made_records(name, want, monkeypatch):
    tm = obs_metrics.TrainingMetrics()
    for rec in (_run_record(99.0, 99.0), _run_record(7.5, 0.5),
                _run_record(0.010, 0.030), _run_record(0.020, 0.040)):
        tm.record_run(rec)
    monkeypatch.setattr(obs_metrics, "training_metrics", lambda: tm)
    sweeps = [_sweep(1.0, 0.960), _sweep(1.0, 0.955),
              _sweep(1.0, 0.965, syncs=10), _sweep(1.0, 0.950)]
    assert _read(name, _bench_run(sweeps)) == pytest.approx(want)
