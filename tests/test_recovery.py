"""Fail-recover: elastic in-job recovery for entity-sharded GAME
training, plus the satellites that ride with it.

Covers, per the acceptance contract:

* the shared :class:`Backoff` schedule (jitter + deadline) and its
  adoption by ``retry_transient``;
* failure classification (``rollback`` / ``rank_loss`` / ``fatal``) and
  ``recovery_supported`` probing;
* in-job ROLLBACK and RANK-LOSS recovery of a sharded coordinate-descent
  run with **f64 bit parity** against an uninterrupted reference —
  including shrinking all the way to a single survivor — and the bounded
  escalation when the failure budget is exhausted;
* the crash-schedule chaos sweep: a drop-kill armed at EVERY registered
  fault-injection site, asserting clean coordinated abort or bit-parity
  recovery, never a hang;
* durable commits (``io/durable.py``): fsync-the-file-and-parent
  discipline and the ``durable.commit`` crash window leaving the
  destination untouched (registry ``LATEST`` included);
* respawn-with-backoff supervision (``run_supervised_processes``) and
  ``retry_collective``;
* the driver surface: ``--max-rank-failures`` / ``--recovery-snapshot-
  every`` wiring and a 4-rank ``photon-game-train --entity-shards 4``
  run that loses a rank mid-sweep and still produces the bit-identical
  model;
* the serving satellites: the registry watcher's consecutive-failure
  error backoff and the front door's real circuit breaker
  (open -> half-open probe -> readmit, ``photon_fd_backend_state``).
"""

import asyncio
import json
import os
import time

import numpy as np
import pytest

from photon_ml_tpu.parallel import fault_injection as fi
from photon_ml_tpu.parallel import resilience
from photon_ml_tpu.parallel.recovery import (
    FATAL,
    RANK_LOSS,
    ROLLBACK,
    RecoveryManager,
    classify_failure,
    recovery_supported,
    retry_collective,
)
from photon_ml_tpu.parallel.resilience import (
    CODE_DATA,
    CODE_DEVICE_LOSS,
    CODE_ERROR,
    Backoff,
    PeerFailure,
    WatchdogTimeout,
    retry_transient,
)
from photon_ml_tpu.testing import (
    Dropped,
    run_simulated_processes,
    run_supervised_processes,
)
from tests.test_entity_shard import _configs, _make_dataset


@pytest.fixture(autouse=True)
def _clear_faults():
    fi.clear()
    yield
    fi.clear()


@pytest.fixture(autouse=True)
def _short_barrier(monkeypatch):
    # a dead peer must fail its survivors' barriers quickly: no recovery
    # test is allowed to ride the 600 s production watchdog
    monkeypatch.setenv("PHOTON_ML_TPU_BARRIER_TIMEOUT_S", "30")


# -- Backoff: the one shared delay policy -----------------------------------
class _Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


class _MaxJitterRng:
    def uniform(self, lo, hi):
        return hi


def test_backoff_schedule_clamps_and_resets():
    clock = _Clock()
    b = Backoff(base_s=1.0, factor=2.0, max_s=5.0, jitter=0.0, clock=clock)
    assert [b.next_delay() for _ in range(5)] == [1.0, 2.0, 4.0, 5.0, 5.0]
    assert b.attempts == 5
    b.reset()
    assert b.attempts == 0 and b.next_delay() == 1.0


def test_backoff_jitter_is_a_fraction_and_deadline_expires():
    clock = _Clock()
    b = Backoff(base_s=2.0, factor=2.0, max_s=60.0, jitter=0.25,
                deadline_s=10.0, rng=_MaxJitterRng(), clock=clock)
    assert b.next_delay() == pytest.approx(2.0 * 1.25)
    assert not b.expired() and b.remaining() == pytest.approx(10.0)
    clock.t = 10.0
    assert b.expired() and b.remaining() == 0.0
    b.reset()  # the deadline window restarts at reset
    assert not b.expired() and b.remaining() == pytest.approx(10.0)


def test_retry_transient_jittered_delays():
    sleeps, calls = [], []

    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise RuntimeError("transient")
        return "ok"

    out = retry_transient(flaky, attempts=3, backoff_s=1.0,
                          backoff_factor=2.0, jitter=0.5,
                          rng=_MaxJitterRng(), sleep=sleeps.append)
    assert out == "ok" and len(calls) == 3
    assert sleeps == [pytest.approx(1.5), pytest.approx(3.0)]


def test_retry_transient_deadline_abandons_the_next_sleep():
    clock = _Clock()
    sleeps, calls = [], []

    def always():
        calls.append(1)
        raise RuntimeError("still down")

    with pytest.raises(RuntimeError, match="still down"):
        retry_transient(always, attempts=5, backoff_s=2.0,
                        jitter=0.0, deadline_s=1.0, clock=clock,
                        sleep=sleeps.append)
    # the first retry's 2 s sleep would overrun the 1 s deadline: the
    # last real error escalates instead of sleeping through it
    assert len(calls) == 1 and sleeps == []


# -- failure classification -------------------------------------------------
def test_classify_failure_classes():
    assert classify_failure(
        WatchdogTimeout("gone", tag="t", failed={2: CODE_ERROR})) == RANK_LOSS
    assert classify_failure(
        PeerFailure("x", tag="t", failed={1: CODE_ERROR})) == ROLLBACK
    assert classify_failure(
        PeerFailure("x", tag="t", failed={1: CODE_DEVICE_LOSS})) == FATAL
    assert classify_failure(
        PeerFailure("x", tag="t", failed={1: CODE_DATA})) == FATAL
    assert classify_failure(ValueError("bad rows")) == FATAL


def test_recovery_supported_probes_the_transport():
    class NoRecover:
        def process_count(self):
            return 4

    class CanRecover(NoRecover):
        def recover(self, payload, timeout):  # pragma: no cover - probe
            raise NotImplementedError

    assert recovery_supported() is True  # single process: trivially yes
    assert recovery_supported(NoRecover()) is False
    assert recovery_supported(CanRecover()) is True


# -- in-job recovery: bit parity against the uninterrupted run --------------
N_SWEEPS = 4


@pytest.fixture(scope="module")
def reference_fit():
    """Uninterrupted single-host reference: the trajectory every
    recovered run must reproduce BIT-EXACTLY."""
    import jax.numpy as jnp

    from photon_ml_tpu.game.descent import CoordinateDescent
    from tests.test_entity_shard import _coeff_map

    ds, val = _make_dataset(with_val=True)
    model, history = CoordinateDescent(
        _configs(), task="logistic", n_iterations=N_SWEEPS,
        dtype=jnp.float64, evaluators=["auc"]).run(ds, validation=val)
    return ds, val, model, history, _coeff_map(model)


def _assert_bit_parity(model, history, reference_fit):
    from tests.test_entity_shard import _coeff_map

    _ds, _val, m_ref, h_ref, ref = reference_fit
    got = _coeff_map(model)
    assert max(float(np.max(np.abs(got[k] - ref[k]))) for k in ref) == 0.0
    fixed = np.asarray(model.coordinates["fixed"].model.coefficients.means)
    fixed_ref = np.asarray(
        m_ref.coordinates["fixed"].model.coefficients.means)
    assert float(np.max(np.abs(fixed - fixed_ref))) == 0.0
    if history is not None:
        aucs = [r["auc"] for r in history if "auc" in r]
        assert aucs == [r["auc"] for r in h_ref if "auc" in r]


def _sharded_fit(ds, val, rank, n, recovery):
    import jax.numpy as jnp

    from photon_ml_tpu.game.descent import CoordinateDescent
    from photon_ml_tpu.parallel.entity_shard import EntityShardSpec

    cd = CoordinateDescent(
        _configs(), task="logistic", n_iterations=N_SWEEPS,
        dtype=jnp.float64, evaluators=["auc"] if val is not None else (),
        entity_shard=EntityShardSpec(n, rank), recovery=recovery)
    model, history = cd.run(ds, validation=val)
    return model, history, recovery.as_dict()


def test_rank_loss_recovery_bit_parity_4_ranks(reference_fit, tmp_path):
    """The tentpole: rank 2 drop-killed mid-sweep; the three survivors
    reform onto a 3-shard owner map, redistribute its entities from the
    last committed snapshot, and finish with coefficients AND the AUC
    history bit-identical to the uninterrupted run."""
    ds, val, _m, _h, _ref = reference_fit

    def fn(rank):
        rec = RecoveryManager(str(tmp_path / "rec"), max_rank_failures=1,
                              backoff_s=0.01, jitter=0.0)
        return _sharded_fit(ds, val, rank, 4, rec)

    # cd.step fires once per (sweep, coordinate): occurrence 5 dies in
    # sweep 2's random-effect step, after sweep 2's snapshot committed
    fi.install(fi.crash_schedule((2, "cd.step", 5)))
    outs = run_simulated_processes(4, fn, join_timeout=600)
    assert isinstance(outs[2], (BaseException, Dropped))
    for r in (0, 1, 3):
        assert not isinstance(outs[r], (BaseException, Dropped)), (
            f"rank {r}: {outs[r]!r}")
        model, history, stats = outs[r]
        _assert_bit_parity(model, history, reference_fit)
        assert stats["recoveries"] == 1
        assert stats["rank_failures"] == 1 and stats["rollbacks"] == 0
        assert stats["members"] == [0, 1, 3]
        assert stats["recovery_seconds"] > 0.0


def test_rollback_recovery_bit_parity(reference_fit, tmp_path):
    """A transient raise (all ranks still alive) rolls back to the last
    committed sweep and retries on the SAME membership — bit parity."""
    ds, _val, _m, _h, _ref = reference_fit

    def fn(rank):
        rec = RecoveryManager(str(tmp_path / "rec"), max_rank_failures=0,
                              backoff_s=0.01, jitter=0.0)
        return _sharded_fit(ds, None, rank, 2, rec)

    fi.install(fi.crash_schedule((1, "entity_shard.exchange", 2),
                                 kind="raise"))
    outs = run_simulated_processes(2, fn, join_timeout=600)
    for r, o in enumerate(outs):
        assert not isinstance(o, (BaseException, Dropped)), f"rank {r}: {o!r}"
        model, _history, stats = o
        _assert_bit_parity(model, None, reference_fit)
        assert stats["rollbacks"] == 1 and stats["rank_failures"] == 0


def test_recovery_shrinks_to_single_survivor(reference_fit, tmp_path):
    """2 ranks, one killed: the lone survivor absorbs the whole entity
    table (the 1-shard owner map IS the single-process layout) and still
    lands on the reference coefficients."""
    ds, _val, _m, _h, _ref = reference_fit

    def fn(rank):
        rec = RecoveryManager(str(tmp_path / "rec"), max_rank_failures=1,
                              backoff_s=0.01, jitter=0.0)
        return _sharded_fit(ds, None, rank, 2, rec)

    fi.install(fi.crash_schedule((1, "cd.step", 3)))
    outs = run_simulated_processes(2, fn, join_timeout=600)
    assert isinstance(outs[1], (BaseException, Dropped))
    assert not isinstance(outs[0], (BaseException, Dropped)), repr(outs[0])
    model, _history, stats = outs[0]
    _assert_bit_parity(model, None, reference_fit)
    assert stats["members"] == [0] and stats["rank_failures"] == 1


def test_device_loss_stays_fatal_coordinated_abort(reference_fit, tmp_path):
    """Device loss is NOT recoverable in-job: every rank must take the
    coordinated-abort path (the drivers' exit-75/resume contract), and
    no recovery may be attempted."""
    ds, _val, _m, _h, _ref = reference_fit

    def fn(rank):
        rec = RecoveryManager(str(tmp_path / "rec"), max_rank_failures=1,
                              backoff_s=0.01, jitter=0.0)
        return _sharded_fit(ds, None, rank, 2, rec)

    fi.install([fi.Fault(site="cd.step", process=1, at=2,
                         kind="device_loss")])
    outs = run_simulated_processes(2, fn, join_timeout=600)
    assert all(isinstance(o, BaseException) for o in outs), outs
    assert isinstance(outs[0], PeerFailure) and outs[0].device_loss


def test_rank_failure_budget_bounds_escalation(reference_fit, tmp_path):
    """Losing MORE ranks than --max-rank-failures allows must escalate
    loudly on every survivor, not recover past the operator's budget."""
    ds, _val, _m, _h, _ref = reference_fit

    def fn(rank):
        import jax.numpy as jnp

        from photon_ml_tpu.game.descent import CoordinateDescent
        from photon_ml_tpu.parallel.entity_shard import EntityShardSpec

        rec = RecoveryManager(str(tmp_path / "rec"), max_rank_failures=1,
                              backoff_s=0.01, jitter=0.0)
        cd = CoordinateDescent(
            _configs(), task="logistic", n_iterations=6,
            dtype=jnp.float64, entity_shard=EntityShardSpec(4, rank),
            recovery=rec)
        return cd.run(ds)

    # rank 2 dies in sweep 1; after the rollback-and-reform, rank 3's
    # occurrence counter keeps advancing and kills it a few sweeps later
    # — the second loss exceeds max_rank_failures=1
    fi.install(fi.crash_schedule((2, "cd.step", 3), (3, "cd.step", 9)))
    outs = run_simulated_processes(4, fn, join_timeout=600)
    assert isinstance(outs[2], (BaseException, Dropped))
    assert isinstance(outs[3], (BaseException, Dropped))
    for r in (0, 1):
        assert isinstance(outs[r], PeerFailure), f"rank {r}: {outs[r]!r}"


# -- chaos harness: a kill armed at EVERY registered fault site -------------
# Every production fault-injection site, by literal name (the photon-check
# --fault-sites audit requires each to appear in a tier-1 test). Split by
# reachability from the in-memory 2-rank sharded fit: HOT sites fire on
# that path and each gets its own kill run; INERT sites (streaming, chunk
# cache, model/registry saves, the GLM grid, real rendezvous) cannot fire
# there, so all of them are armed together in one run per victim — one
# fit proves the whole armed plan is inert AND that arming it perturbs
# nothing (bit parity).
HOT_FAULT_SITES = [
    "cd.step",
    "entity_shard.exchange",
    "durable.commit",
    "transport.allgather",
    "recovery.commit",
]
INERT_FAULT_SITES = [
    "cd.score_gather",
    "multihost.init",
    "glm.lambda",
    "registry.publish_prepared",
    "registry.published",
    "chunk_cache.spill",
    "chunk_cache.commit",
    "model_io.save_coordinate",
    "model_io.save_metadata",
    "stream.chunk",
    "stream.block_payload",
]
ALL_FAULT_SITES = HOT_FAULT_SITES + INERT_FAULT_SITES


def _chaos_run(site_kills, victim, reference_fit, tmp_path, site_label):
    """One 2-rank sharded fit with a drop-kill plan armed. Contract: the
    run either completes CLEAN on every rank with bit parity (no armed
    site fires on this path, or recovery absorbed the loss), or the
    victim is dead and every other rank either recovered to parity or
    raised a coordinated abort — and nothing ever hangs (the 30 s
    watchdog plus the join timeout bound every wait)."""
    ds, _val, _m, _h, _ref = reference_fit

    def fn(rank):
        rec = RecoveryManager(str(tmp_path / "rec"), max_rank_failures=1,
                              backoff_s=0.01, jitter=0.0)
        return _sharded_fit(ds, None, rank, 2, rec)

    fi.install(fi.crash_schedule(*site_kills))
    outs = run_simulated_processes(2, fn, join_timeout=300)
    for r, o in enumerate(outs):
        if isinstance(o, Dropped):
            assert r == victim, (
                f"rank {r} dropped but the kill was armed on {victim} "
                f"at {site_label!r} — a survivor hung or died silently")
        elif isinstance(o, BaseException):
            # coordinated abort: a classified, raised failure — never a
            # hang; anything non-PeerFailure must be the victim's own
            assert isinstance(o, PeerFailure) or r == victim, (
                f"rank {r}: {o!r}")
        else:
            model, _history, _stats = o
            _assert_bit_parity(model, None, reference_fit)
    return outs


@pytest.mark.parametrize("victim", [0, 1])
@pytest.mark.parametrize("site", HOT_FAULT_SITES)
def test_chaos_crash_schedule_hot_sites(site, victim, reference_fit,
                                        tmp_path):
    """Drop-kill each rank at the first firing of every site on the
    sharded-fit path; these kills actually land, so each case must end
    in recovery-to-parity or a coordinated abort."""
    _chaos_run([(victim, site, 0)], victim, reference_fit, tmp_path, site)


@pytest.mark.parametrize("victim", [0, 1])
def test_chaos_crash_schedule_inert_sites_stay_clean(victim, reference_fit,
                                                     tmp_path):
    """Arm a kill for the victim at EVERY off-path site at once: none
    can fire during an in-memory fit, so every rank must complete clean
    with bit parity — a site that starts firing on this path shows up
    here as a kill and moves to HOT_FAULT_SITES."""
    kills = [(victim, site, 0) for site in INERT_FAULT_SITES]
    outs = _chaos_run(kills, victim, reference_fit, tmp_path,
                      "|".join(INERT_FAULT_SITES))
    assert not any(isinstance(o, (BaseException, Dropped)) for o in outs), (
        f"an 'inert' site fired during the fit: {outs!r}")


# -- durable commits --------------------------------------------------------
def test_durable_replace_fsyncs_file_and_parent(tmp_path, monkeypatch):
    from photon_ml_tpu.io import durable

    synced = []
    real_fsync = os.fsync
    monkeypatch.setattr(os, "fsync", lambda fd: (synced.append(fd),
                                                 real_fsync(fd))[1])
    tmp = tmp_path / "marker.tmp"
    dst = tmp_path / "marker.json"
    tmp.write_text("{}")
    durable.durable_replace(str(tmp), str(dst))
    assert dst.read_text() == "{}" and not tmp.exists()
    # one fsync for the temp file's content, one for the parent dir
    assert len(synced) >= 2


def test_durable_commit_crash_window_leaves_dst_untouched(tmp_path):
    from photon_ml_tpu.io.durable import durable_replace

    dst = tmp_path / "LATEST"
    dst.write_text("old")
    tmp = tmp_path / "LATEST.tmp"
    tmp.write_text("new")
    fi.install([fi.Fault(site="durable.commit")])
    with pytest.raises(fi.InjectedFault):
        durable_replace(str(tmp), str(dst))
    fi.clear()
    # the crash window is BEFORE the rename: the old commit survives and
    # the staged content is still there for inspection, never half-applied
    assert dst.read_text() == "old" and tmp.read_text() == "new"


def test_registry_set_latest_survives_commit_crash(saved_game_model,
                                                   tmp_path):
    from photon_ml_tpu.registry import ModelRegistry

    model_dir, _bundle = saved_game_model
    reg = ModelRegistry(str(tmp_path / "reg"))
    v1 = reg.publish(model_dir, set_latest=True)
    v2 = reg.publish(model_dir)
    fi.install([fi.Fault(site="durable.commit")])
    with pytest.raises(fi.InjectedFault):
        reg.set_latest(v2)
    fi.clear()
    assert reg.read_latest() == v1  # the promotion never half-landed
    reg.set_latest(v2)
    assert reg.read_latest() == v2


# -- supervision + collective retry ----------------------------------------
def test_run_supervised_processes_respawns_with_backoff():
    sleeps = []

    def fn(rank, attempt):
        if attempt == 0 and rank == 1:
            raise RuntimeError("first attempt dies")
        return attempt

    outs, attempts = run_supervised_processes(
        2, fn, max_restarts=2, backoff_s=0.01, jitter=0.0,
        sleep=sleeps.append)
    assert outs == [1, 1] and attempts == 2
    assert sleeps == [pytest.approx(0.01)]


def test_run_supervised_processes_gives_up_after_budget():
    def fn(rank):
        raise RuntimeError("always down")

    outs, attempts = run_supervised_processes(
        2, fn, max_restarts=1, backoff_s=0.0, jitter=0.0,
        sleep=lambda s: None)
    assert attempts == 2  # initial try + one restart, then surrender
    assert all(isinstance(o, RuntimeError) for o in outs)


def test_retry_collective_retries_rollback_class_once():
    calls = {}

    def fn(rank):
        def body():
            calls[rank] = calls.get(rank, 0) + 1
            if calls[rank] == 1:
                raise PeerFailure("transient exchange", tag="t",
                                  failed={rank: CODE_ERROR})
            return rank

        return retry_collective(body, max_retries=1, backoff_s=0.01,
                                jitter=0.0, tag="test.retry")

    outs = run_simulated_processes(2, fn, join_timeout=120)
    assert outs == [0, 1]
    assert calls == {0: 2, 1: 2}


def test_retry_collective_escalates_fatal_immediately():
    calls = []

    def body():
        calls.append(1)
        raise PeerFailure("device gone", tag="t",
                          failed={0: CODE_DEVICE_LOSS})

    with pytest.raises(PeerFailure):
        retry_collective(body, max_retries=3, backoff_s=0.0)
    assert len(calls) == 1  # fatal: no retry, no barrier


# -- driver surface ---------------------------------------------------------
def test_driver_recovery_flags_defaults_and_validation():
    from photon_ml_tpu.cli.game_training_driver import build_arg_parser
    from photon_ml_tpu.cli.glm_driver import build_arg_parser as glm_parser

    args = build_arg_parser().parse_args(
        ["--train-data", "x", "--output-dir", "y", "--coordinates", "z"])
    assert args.max_rank_failures == 0  # recovery is strictly opt-in
    assert args.recovery_snapshot_every == 1
    args = build_arg_parser().parse_args(
        ["--train-data", "x", "--output-dir", "y", "--coordinates", "z",
         "--max-rank-failures", "2", "--recovery-snapshot-every", "3"])
    assert args.max_rank_failures == 2
    assert args.recovery_snapshot_every == 3
    g = glm_parser().parse_args(
        ["--train-data", "x", "--output-dir", "y"])
    assert g.max_rank_failures == 0
    with pytest.raises(SystemExit):
        build_arg_parser().parse_args(
            ["--train-data", "x", "--output-dir", "y", "--coordinates",
             "z", "--recovery-snapshot-every", "0"])


@pytest.mark.slow
def test_game_driver_entity_sharded_recovery(tmp_path):
    # slow-marked for the tier-1 wall-clock budget: the same 4-rank
    # kill -> 3-survivor bit-parity contract is gated on every push by
    # the ci_lint exit-13 leg (scripts/chaos_smoke.py)
    """The acceptance run: ``photon-game-train --entity-shards 4
    --max-rank-failures 1`` on 4 simulated processes, one killed
    mid-sweep — the job finishes in-job and the saved model is
    bit-identical to an uninterrupted 4-shard run."""
    from photon_ml_tpu.cli.game_training_driver import main as train_main
    from photon_ml_tpu.io.model_io import load_game_model
    from photon_ml_tpu.testing import (
        synthetic_game_data,
        write_game_avro_fixture,
    )

    data = synthetic_game_data({"userId": 8}, seed=4)
    train = str(tmp_path / "train.avro")
    write_game_avro_fixture(train, data,
                            rows=np.arange(len(data.labels)))
    coords = json.dumps([
        {"name": "fixed", "coordinate_type": "fixed",
         "feature_shard": "global", "reg_type": "l2", "reg_weight": 0.5,
         "tolerance": 1e-10, "max_iters": 25},
        {"name": "per-user", "coordinate_type": "random",
         "feature_shard": "entity", "entity_column": "userId",
         "reg_type": "l2", "reg_weight": 1.0, "max_iters": 15,
         # lbfgs: bit-invariant to the survivor layout's bucket widths
         "optimizer": "lbfgs", "tolerance": 1e-9},
    ])
    shards = json.dumps({"global": ["g"], "entity": ["u"]})

    def argv(out):
        return [
            "--train-data", train, "--output-dir", str(out),
            "--task", "logistic_regression", "--coordinates", coords,
            "--feature-shards", shards, "--n-iterations", "3",
            "--dtype", "float64", "--entity-shards", "4",
            "--max-rank-failures", "1",
        ]

    def run(out):
        return run_simulated_processes(
            4, lambda rank: train_main(argv(out)), join_timeout=600)

    clean = run(tmp_path / "clean")
    assert all(rc == 0 for rc in clean), clean
    fi.install(fi.crash_schedule((2, "cd.step", 3)))
    crashed = run(tmp_path / "crashed")
    fi.clear()
    assert isinstance(crashed[2], (BaseException, Dropped))
    for r in (0, 1, 3):
        assert crashed[r] == 0, f"rank {r}: {crashed[r]!r}"

    ref = load_game_model(str(tmp_path / "clean" / "best"))
    got = load_game_model(str(tmp_path / "crashed" / "best"))
    np.testing.assert_array_equal(
        np.asarray(ref.coordinates["fixed"].model.coefficients.means),
        np.asarray(got.coordinates["fixed"].model.coefficients.means))
    # the survivor layout re-buckets entities (3-shard owner map), so
    # compare entity -> (feature index, coefficient) maps, not bucket order
    def coeff_map(model):
        out = {}
        for b in model.coordinates["per-user"].buckets:
            C = np.asarray(b.coefficients)
            proj = (np.asarray(b.projection)
                    if getattr(b, "projection", None) is not None else None)
            for r, eid in enumerate(b.entity_ids):
                if proj is not None:
                    valid = proj[r] >= 0
                    out[str(eid)] = sorted(zip(proj[r][valid].tolist(),
                                               C[r][valid].tolist()))
                else:
                    out[str(eid)] = list(enumerate(C[r].tolist()))
        return out

    ref_map, got_map = coeff_map(ref), coeff_map(got)
    assert sorted(ref_map) == sorted(got_map)
    for eid in ref_map:
        assert ref_map[eid] == got_map[eid], f"entity {eid} diverged"
    events = [json.loads(line)["event"] for line in
              (tmp_path / "crashed" / "photon.log.jsonl")
              .read_text().splitlines()]
    assert "in_job_recovery" in events


# -- serving satellites: watcher backoff + circuit breaker ------------------
class _FlakyRegistry:
    def __init__(self):
        self.fail = True

    def read_latest(self):
        if self.fail:
            raise RuntimeError("registry down")
        return None


class _StubSession:
    active_version = None


def test_watcher_error_backoff_escalates_and_resets():
    from photon_ml_tpu.serve.watcher import RegistryWatcher

    reg = _FlakyRegistry()
    w = RegistryWatcher(reg, _StubSession(), interval_s=10.0, jitter_s=0.0,
                        error_backoff_max_s=80.0)

    class _ZeroRng:
        def uniform(self, lo, hi):
            return 0.0

    rng = _ZeroRng()
    assert w._next_delay(rng) == 10.0  # healthy: the plain interval

    def tick():
        before = w.errors
        w.check_once()
        w._observe(before)
        return w._next_delay(rng)

    # consecutive failures: 2x, 4x, 8x the interval (within jitter),
    # capped at error_backoff_max_s
    d1, d2, d3 = tick(), tick(), tick()
    assert 20.0 <= d1 <= 22.0
    assert 40.0 <= d2 <= 44.0
    assert 80.0 <= d3 <= 88.0
    reg.fail = False  # first clean poll resets the schedule
    assert tick() == 10.0
    assert w.errors == 3


def test_backend_breaker_opens_after_consecutive_failures():
    from photon_ml_tpu.serve.aserver import _Backend

    b = _Backend("127.0.0.1", 9, cooldown_s=0.1)
    now = time.monotonic()
    b.record_failure(3, now)
    b.record_failure(3, now)
    assert b.state == "closed" and b.opened == 0  # 2 < threshold
    b.record_success()
    assert b.fails == 0  # any success resets the consecutive count
    for _ in range(3):
        b.record_failure(3, now)
    assert b.state == "open" and b.opened == 1
    assert b.next_probe_at > now
    # a failed half-open probe reopens with an escalated cool-down
    b.state = "half_open"
    b.record_failure(3, now)
    assert b.state == "open" and b.opened == 2
    b.record_success()
    assert b.state == "closed" and b.fails == 0


def test_front_door_half_open_probe_readmits_and_metrics_gauge():
    from photon_ml_tpu.serve.aserver import AsyncFrontDoor

    door = AsyncFrontDoor(["127.0.0.1:1"], retry_backend_s=0.01,
                          breaker_threshold=2)
    b = door._backends[0]
    healthy = {"v": False}

    async def fake_exchange(backend, raw):
        if not healthy["v"]:
            raise ConnectionError("still down")
        return b"HTTP/1.1 200 OK\r\nContent-Length: 0\r\n\r\n"

    door._backend_exchange = fake_exchange

    async def run():
        now = time.monotonic()
        b.record_failure(door.breaker_threshold, now)
        b.record_failure(door.breaker_threshold, now)
        assert b.state == "open"
        # first probe fails: back to open, escalated cool-down
        b.next_probe_at = 0.0
        door._maybe_probe(b, time.monotonic())
        assert b.state == "half_open" and b.probe_inflight
        await asyncio.sleep(0.01)
        assert b.state == "open" and door.readmitted == 0
        # replica recovers: the next probe readmits it
        healthy["v"] = True
        b.next_probe_at = 0.0
        door._maybe_probe(b, time.monotonic())
        await asyncio.sleep(0.01)
        assert b.state == "closed" and door.readmitted == 1
        # breaker state is exported for operators
        b.state = "open"
        b.next_probe_at = time.monotonic() + 999.0
        text = await door._fd_metrics()
        return text

    text = asyncio.run(run())
    assert "photon_fd_backend_state" in text
    assert 'photon_fd_backend_state{backend="127.0.0.1:1"} 2' in text
    assert "photon_fd_readmitted_total 1" in text
    stats = door.stats()
    assert stats["readmitted"] == 1
    assert stats["backends"][0]["state"] == "open"
    assert stats["backends"][0]["down"] is True


def test_front_door_sync_pick_never_flips_half_open_without_a_loop():
    """_maybe_probe from a no-loop context must leave the breaker open
    (probing requires the event loop) — the backend stays ejected rather
    than getting stuck half-open with no probe in flight."""
    from photon_ml_tpu.serve.aserver import AsyncFrontDoor

    door = AsyncFrontDoor(["127.0.0.1:1"], retry_backend_s=0.01,
                          breaker_threshold=1)
    b = door._backends[0]
    b.record_failure(1, time.monotonic())
    assert b.state == "open"
    b.next_probe_at = 0.0
    door._maybe_probe(b, time.monotonic())  # sync caller: no running loop
    assert b.state == "open" and not b.probe_inflight
