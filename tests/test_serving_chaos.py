"""Serving-tier chaos: store-fault storms served degraded instead of
5xx, paged-install failures, corrupt-registry stale-model serving, a
replica kill absorbed by the front door's breaker + retry, hedging
against a slow replica, and a slow real-socket soak with armed latency
faults. Fault sites exercised here: ``store.load``, ``paged.install``,
``registry.read``, ``fd.proxy``."""

import asyncio
import json
import threading
import time

import numpy as np
import pytest

from photon_ml_tpu.parallel import fault_injection
from photon_ml_tpu.parallel.fault_injection import Fault
from tests.conftest import serving_rows


@pytest.fixture(autouse=True)
def _clean_faults():
    fault_injection.clear()
    yield
    fault_injection.clear()


def _session(model_dir, **kw):
    from photon_ml_tpu.serve import ScoringSession

    kw.setdefault("dtype", "float64")
    kw.setdefault("max_batch", 16)
    kw.setdefault("coeff_cache_entries", 32)
    return ScoringSession(model_dir, **kw)


# -- degradation ladder under store faults ----------------------------------

class TestStoreFaultStorm:
    def test_cold_faults_degrade_instead_of_raising(self, saved_game_model):
        """100% store.load failures: a ctx-carrying batch with cold
        entities serves at level 1 (resident/fixed-only for the cold
        rows) instead of surfacing the store exception."""
        from photon_ml_tpu.serve import ScoreContext

        model_dir, bundle = saved_game_model
        session = _session(model_dir, warmup=False)
        try:
            rows = serving_rows(bundle, list(range(6)))
            fault_injection.install([
                Fault("store.load", kind="raise", at=-1,
                      message="storm: store down")])
            ctx = ScoreContext()
            got = session.score_rows(rows, ctx=ctx)
            assert got.shape == (6,)
            assert np.all(np.isfinite(got))
            assert ctx.degraded >= 1
            assert "store_fault" in ctx.reasons
            # a ctx-LESS caller keeps the pre-existing contract: the
            # store failure surfaces (no silent fidelity loss without
            # an opted-in ladder)
            from photon_ml_tpu.parallel.fault_injection import InjectedFault

            fresh = _session(model_dir, warmup=False)
            try:
                with pytest.raises(InjectedFault):
                    fresh.score_rows(serving_rows(bundle, list(range(6))))
            finally:
                fresh.close()
        finally:
            session.close()

    def test_paged_install_failure_degrades(self, saved_game_model):
        """The install half of a cold fault failing (device hiccup) is
        the same brownout: serve resident-only, never 5xx."""
        from photon_ml_tpu.serve import ScoreContext

        model_dir, bundle = saved_game_model
        session = _session(model_dir, warmup=False)
        try:
            fault_injection.install([
                Fault("paged.install", kind="raise", at=-1,
                      message="install failed")])
            ctx = ScoreContext()
            got = session.score_rows(serving_rows(bundle, list(range(4))),
                                     ctx=ctx)
            assert got.shape == (4,)
            assert ctx.degraded >= 1
            assert "store_fault" in ctx.reasons
        finally:
            session.close()

    def test_storm_at_overload_full_availability_zero_5xx(
            self, saved_game_model):
        """The acceptance gate: 100% store.load faults under a 2x
        max_batch concurrent burst -> every response is a 200 served at
        degraded level 1-2 (reported in the body AND the metrics);
        nothing becomes a 5xx."""
        from photon_ml_tpu.serve import (
            MicroBatcher,
            ScoringService,
        )

        model_dir, bundle = saved_game_model
        session = _session(model_dir, warmup=False, max_batch=8)
        batcher = MicroBatcher(session.score_rows, max_batch=8,
                               max_delay_ms=2.0, max_queue=256,
                               metrics=session.metrics)
        svc = ScoringService(session, batcher)
        try:
            fault_injection.install([
                Fault("store.load", kind="raise", at=-1,
                      message="storm")])
            n_requests = 16  # 2x the batch capacity, concurrently
            results = [None] * n_requests

            def fire(i):
                results[i] = svc.handle_score(
                    {"rows": serving_rows(bundle, [i % 12, (i + 1) % 12])})

            threads = [threading.Thread(target=fire, args=(i,))
                       for i in range(n_requests)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(30.0)
            statuses = [r[0] for r in results]
            assert all(s == 200 for s in statuses), statuses
            assert all(r[1]["degraded"] in (1, 2) for r in results), (
                [r[1].get("degraded") for r in results])
            snap = svc.metrics.snapshot()
            assert snap["degraded_total"] >= n_requests
            assert 'photon_serve_degraded_total{level="1"}' in \
                svc.metrics.render()
        finally:
            svc.close()

    def test_faults_off_no_degradation_and_bitwise_parity(
            self, saved_game_model):
        """With no faults armed and ample budget, the ladder is inert:
        degraded stays 0 and a ctx-carrying score is BITWISE identical
        to the ctx-less path (one margin path, no fidelity drift)."""
        from photon_ml_tpu.serve import ScoreContext

        model_dir, bundle = saved_game_model
        session = _session(model_dir)
        try:
            rows = serving_rows(bundle, list(range(10)))
            baseline = session.score_rows(rows)
            ctx = ScoreContext(deadline_at=time.monotonic() + 60.0)
            got = session.score_rows(rows, ctx=ctx)
            assert ctx.degraded == 0
            assert ctx.reasons == []
            assert np.array_equal(np.asarray(got), np.asarray(baseline))
            assert session.metrics.snapshot()["degraded_total"] == 0
        finally:
            session.close()

    def test_tight_budget_skips_cold_fault(self, saved_game_model):
        """Once the fault-cost EWMA is primed (a slow store), a batch
        whose remaining budget cannot cover another fault degrades to
        resident-only instead of blocking on the store."""
        from photon_ml_tpu.serve import ScoreContext

        model_dir, bundle = saved_game_model
        session = _session(model_dir, warmup=False)
        try:
            # prime the measured fault cost: one slow (delayed) cold load
            fault_injection.install([
                Fault("store.load", kind="delay", delay_s=0.2, at=-1)])
            ctx0 = ScoreContext()
            session.score_rows(serving_rows(bundle, [0, 1]), ctx=ctx0)
            assert session._fault_ewma_s is not None
            assert session._fault_ewma_s >= 0.15
            fault_injection.clear()
            # 50ms of budget left < ~200ms measured fault cost: the cold
            # entities are NOT faulted; the batch reports level 1 "budget"
            ctx = ScoreContext(deadline_at=time.monotonic() + 0.05)
            got = session.score_rows(serving_rows(bundle, [4, 5, 6]),
                                     ctx=ctx)
            assert got.shape == (3,)
            assert ctx.degraded == 1
            assert "budget" in ctx.reasons
        finally:
            session.close()


# -- stale-model serving on registry failure --------------------------------

class TestCorruptRegistry:
    def test_registry_fault_pins_live_model_and_raises_staleness(self):
        from photon_ml_tpu.obs.metrics import ServingMetrics
        from photon_ml_tpu.serve import RegistryWatcher

        class _Sess:
            active_version = "v000001"
            metrics = ServingMetrics()
            swaps = 0

            def swap(self, source, version=None):
                self.swaps += 1

        class _Reg:
            def read_latest(self):
                return "v000002"

            def open_version(self, v):
                return f"/models/{v}"

        sess = _Sess()
        watcher = RegistryWatcher(_Reg(), sess, interval_s=0.01)
        fault_injection.install([
            Fault("registry.read", kind="raise", at=-1,
                  message="corrupt LATEST")])
        watcher.last_success_at = time.monotonic() - 5.0
        assert watcher.check_once() is None
        assert watcher.errors == 1
        assert sess.swaps == 0, "a failing registry must not touch state"
        assert watcher.staleness_s >= 5.0
        snap = sess.metrics.snapshot()
        assert snap["model_staleness_s"] >= 5.0
        assert "photon_serve_model_staleness_seconds" in \
            sess.metrics.render()
        # registry heals: the next poll swaps and staleness resets
        fault_injection.clear()
        assert watcher.check_once() == "v000002"
        assert sess.swaps == 1
        assert watcher.staleness_s < 1.0
        assert sess.metrics.snapshot()["model_staleness_s"] == 0.0

    def test_up_to_date_poll_counts_as_fresh(self):
        from photon_ml_tpu.serve import RegistryWatcher

        class _Sess:
            active_version = "v000001"

        class _Reg:
            def read_latest(self):
                return "v000001"

        watcher = RegistryWatcher(_Reg(), _Sess(), interval_s=0.01)
        watcher.last_success_at = time.monotonic() - 9.0
        assert watcher.check_once() is None
        assert watcher.staleness_s < 1.0


# -- front door: kill, breaker, hedged retry --------------------------------

async def _score_via_door(door, rows, deadline_ms=None):
    reader, writer = await asyncio.open_connection(door.host, door.port)
    body = json.dumps({"rows": rows}).encode()
    hdr = ("" if deadline_ms is None
           else f"X-Deadline-Ms: {deadline_ms}\r\n")
    writer.write((f"POST /score HTTP/1.1\r\nHost: t\r\n"
                  f"Content-Type: application/json\r\n{hdr}"
                  f"Content-Length: {len(body)}\r\n\r\n").encode() + body)
    await writer.drain()
    head = await reader.readuntil(b"\r\n\r\n")
    status = int(head.split(b" ", 2)[1])
    length = 0
    for line in head.split(b"\r\n"):
        if line.lower().startswith(b"content-length:"):
            length = int(line.split(b":")[1])
    payload = await reader.readexactly(length) if length else b""
    writer.close()
    return status, json.loads(payload) if payload else None


class TestFrontDoorChaos:
    def test_replica_kill_mid_burst_zero_errors(self, saved_game_model):
        """Kill one of two replicas mid-burst: its breaker opens, every
        affected request is retried onto the survivor, and the client
        sees ZERO non-200s."""
        from photon_ml_tpu.serve import (
            AsyncFrontDoor,
            AsyncScoringServer,
            MicroBatcher,
            ScoringService,
        )

        model_dir, bundle = saved_game_model

        def make_service():
            session = _session(model_dir, max_batch=8)
            batcher = MicroBatcher(session.score_rows, max_batch=8,
                                   max_delay_ms=1.0,
                                   metrics=session.metrics)
            return ScoringService(session, batcher)

        svc_a, svc_b = make_service(), make_service()

        async def scenario():
            srv_a = await AsyncScoringServer(svc_a).start()
            srv_b = await AsyncScoringServer(svc_b).start()
            door = await AsyncFrontDoor(
                [f"127.0.0.1:{srv_a.port}", f"127.0.0.1:{srv_b.port}"],
                breaker_threshold=1, retry_backend_s=60.0).start()
            rows = serving_rows(bundle, [0, 1])
            statuses = []
            for i in range(12):
                if i == 4:
                    # abrupt kill: stop accepting AND sever live
                    # connections (no drain — this is a crash, not a
                    # rolling restart)
                    srv_a._server.close()
                    for task in list(srv_a._conns):
                        task.cancel()
                status, body = await _score_via_door(door, rows)
                statuses.append(status)
                if status == 200:
                    assert len(body["scores"]) == 2
            assert statuses == [200] * 12, statuses
            stats = door.stats()
            assert stats["unavailable"] == 0
            dead = [b for b in stats["backends"] if b["state"] == "open"]
            assert len(dead) == 1, stats["backends"]
            assert stats["retried"] >= 1
            await door.aclose()
            await srv_b.aclose()
            try:
                await srv_a.aclose(drain_timeout_s=0.1)
            except Exception:
                pass

        try:
            asyncio.run(scenario())
        finally:
            svc_a.close()
            svc_b.close()

    def test_hedge_duplicates_to_second_replica_and_wins(self):
        """A backend running past its own observed p99 gets its request
        duplicated onto a second replica; the fast answer wins, the slow
        loser is cancelled WITHOUT tripping its breaker."""
        from photon_ml_tpu.serve import AsyncFrontDoor

        async def scenario():
            async def backend(delay_s, reader, writer):
                try:
                    while True:
                        head = await reader.readuntil(b"\r\n\r\n")
                        length = 0
                        for line in head.split(b"\r\n"):
                            if line.lower().startswith(b"content-length:"):
                                length = int(line.split(b":")[1])
                        if length:
                            await reader.readexactly(length)
                        await asyncio.sleep(delay_s)
                        body = (b'{"scores": [0.0], "degraded": 0, '
                                b'"from": "' + str(delay_s).encode()
                                + b'"}')
                        writer.write(
                            b"HTTP/1.1 200 OK\r\nContent-Type: application"
                            b"/json\r\nContent-Length: "
                            + str(len(body)).encode() + b"\r\n\r\n" + body)
                        await writer.drain()
                except (asyncio.IncompleteReadError, ConnectionError,
                        asyncio.CancelledError):
                    pass
                finally:
                    # since Python 3.12 Server.wait_closed() waits for
                    # every connection's transport to be closed
                    writer.close()

            import functools
            slow = await asyncio.start_server(
                functools.partial(backend, 1.0), "127.0.0.1", 0)
            fast = await asyncio.start_server(
                functools.partial(backend, 0.0), "127.0.0.1", 0)
            slow_port = slow.sockets[0].getsockname()[1]
            fast_port = fast.sockets[0].getsockname()[1]
            door = await AsyncFrontDoor(
                [f"127.0.0.1:{slow_port}", f"127.0.0.1:{fast_port}"],
                policy="round_robin", hedge_enabled=True,
                hedge_min_s=0.05, hedge_min_samples=5).start()
            slow_b = door._backends[0]
            # prime the slow backend's latency history: its p99 says
            # ~10ms, so a 1s exchange is a tail worth hedging
            for _ in range(10):
                slow_b.note_latency(10.0)
            # force the pick onto the slow backend (round-robin tie on
            # inflight otherwise makes the test order-dependent)
            t0 = time.monotonic()
            request = (b"POST /score HTTP/1.1\r\nHost: t\r\n"
                       b"Content-Length: 2\r\n"
                       b"Connection: keep-alive\r\n\r\n{}")
            data, hedge_won = await door._hedged_exchange(
                slow_b, request, "/score", set())
            elapsed = time.monotonic() - t0
            assert data is not None and b" 200 " in data
            assert hedge_won, "the duplicate's response did not win"
            assert b'"from": "0.0"' in data, "fast replica did not win"
            assert elapsed < 0.8, f"hedge never fired ({elapsed:.2f}s)"
            assert door.hedged == 1
            assert door.hedge_wins == 1
            # the cancelled slow loser is NOT a failure: breaker closed
            assert slow_b.state == "closed"
            assert slow_b.fails == 0
            await door.aclose()
            for s in (slow, fast):
                s.close()
                await s.wait_closed()

        asyncio.run(scenario())

    def test_expired_deadline_rejected_at_the_door(self):
        """X-Deadline-Ms <= 0 is shed by the front door itself — before
        any backend connection is touched."""
        from photon_ml_tpu.serve import AsyncFrontDoor

        async def scenario():
            door = await AsyncFrontDoor(["127.0.0.1:1"]).start()
            status, body = await _score_via_door(
                door, [{"features": []}], deadline_ms=0)
            assert status == 429
            assert body["cause"] == "deadline"
            assert door.deadline_rejects == 1
            assert door.proxied == 0
            text = await door._fd_metrics()
            assert "photon_fd_deadline_rejects_total 1" in text
            assert "photon_fd_hedged_total 0" in text
            await door.aclose()

        asyncio.run(scenario())

    def test_deadline_header_forwarded_to_replica(self, saved_game_model):
        """A positive budget rides the proxied request as X-Deadline-Ms;
        an ample one scores normally end to end."""
        from photon_ml_tpu.serve import (
            AsyncFrontDoor,
            AsyncScoringServer,
            MicroBatcher,
            ScoringService,
        )

        model_dir, bundle = saved_game_model
        session = _session(model_dir, max_batch=8)
        batcher = MicroBatcher(session.score_rows, max_batch=8,
                               max_delay_ms=1.0, metrics=session.metrics)
        svc = ScoringService(session, batcher)

        async def scenario():
            srv = await AsyncScoringServer(svc).start()
            door = await AsyncFrontDoor(
                [f"127.0.0.1:{srv.port}"]).start()
            status, body = await _score_via_door(
                door, serving_rows(bundle, [0, 1]), deadline_ms=30_000)
            assert status == 200
            assert body["degraded"] == 0
            await door.aclose()
            await srv.aclose()

        try:
            asyncio.run(scenario())
        finally:
            svc.close()


@pytest.mark.slow
class TestDelayFaultSoak:
    def test_soak_with_armed_proxy_delay_faults(self, saved_game_model):
        """Real-socket soak with kind="delay" faults armed at fd.proxy:
        every exchange eats injected latency, yet availability stays
        100% and nothing trips a breaker (a slow fleet is not a dead
        fleet)."""
        from photon_ml_tpu.serve import (
            AsyncFrontDoor,
            AsyncScoringServer,
            MicroBatcher,
            ScoringService,
        )

        model_dir, bundle = saved_game_model

        def make_service():
            session = _session(model_dir, max_batch=8)
            batcher = MicroBatcher(session.score_rows, max_batch=8,
                                   max_delay_ms=1.0,
                                   metrics=session.metrics)
            return ScoringService(session, batcher)

        svc_a, svc_b = make_service(), make_service()
        fault_injection.install([
            Fault("fd.proxy", kind="delay", delay_s=0.02, at=-1)])

        async def scenario():
            srv_a = await AsyncScoringServer(svc_a).start()
            srv_b = await AsyncScoringServer(svc_b).start()
            door = await AsyncFrontDoor(
                [f"127.0.0.1:{srv_a.port}", f"127.0.0.1:{srv_b.port}"],
                hedge_enabled=True, hedge_min_s=0.05,
                hedge_min_samples=10).start()
            statuses = []
            for i in range(40):
                status, body = await _score_via_door(
                    door, serving_rows(bundle, [i % 12]),
                    deadline_ms=30_000)
                statuses.append(status)
            assert statuses == [200] * 40, statuses
            stats = door.stats()
            assert stats["unavailable"] == 0
            assert all(b["state"] == "closed"
                       for b in stats["backends"]), stats["backends"]
            await door.aclose()
            await srv_a.aclose()
            await srv_b.aclose()

        try:
            asyncio.run(scenario())
        finally:
            fault_injection.clear()
            svc_a.close()
            svc_b.close()
