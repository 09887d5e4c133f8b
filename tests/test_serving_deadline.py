"""Deadline propagation + brownout: the X-Deadline-Ms budget riding
submit -> queue -> execute with stage-labelled drops at the cheapest
point, the measured retry_after_s drain estimate, the brownout
controller's hysteresis, and the warming /healthz contract the front
door's half-open probe keys on."""

import threading
import time

import numpy as np
import pytest

from tests.conftest import serving_rows


# -- header parsing ---------------------------------------------------------

class TestDeadlineHeader:
    def test_parse_valid_blank_and_missing(self):
        from photon_ml_tpu.serve import ScoringService

        assert ScoringService.parse_deadline_ms("250") == 250.0
        assert ScoringService.parse_deadline_ms(" 12.5 ") == 12.5
        assert ScoringService.parse_deadline_ms(None) is None
        assert ScoringService.parse_deadline_ms("") is None
        assert ScoringService.parse_deadline_ms("   ") is None

    def test_garbled_header_raises(self):
        from photon_ml_tpu.serve import ScoringService

        with pytest.raises(ValueError, match="X-Deadline-Ms"):
            ScoringService.parse_deadline_ms("soon")

    def test_header_wins_over_service_default(self):
        from photon_ml_tpu.serve import MicroBatcher, ScoringService

        batcher = MicroBatcher(lambda rows, pc: np.zeros(len(rows)),
                               max_batch=4)
        try:
            svc = ScoringService.__new__(ScoringService)
            svc.default_deadline_ms = 500.0
            assert ScoringService.deadline_s(svc, 250.0) == 0.25
            assert ScoringService.deadline_s(svc, None) == 0.5
            svc.default_deadline_ms = None
            assert ScoringService.deadline_s(svc, None) is None
        finally:
            batcher.close()


# -- stage-labelled drops ---------------------------------------------------

class _Metrics:
    """Counting stub for the shed/deadline-drop/degraded surface."""

    def __init__(self):
        self.sheds = []
        self.drops = []
        self.degraded = []

    def record_shed(self, cause="queue_full"):
        self.sheds.append(cause)

    def record_deadline_drop(self, stage):
        self.drops.append(stage)

    def record_degraded(self, level, n=1):
        self.degraded.append((level, n))

    def set_queue_depth(self, depth):
        pass

    def record_batch(self, rows, cap, ms):
        pass

    def record_request(self, rows, ms, queue_wait_ms=0.0, compute_ms=0.0):
        pass

    def record_error(self):
        pass


class TestStageLabelledDrops:
    def test_admission_drop_spends_nothing(self):
        """A request arriving with its budget already gone is shed at
        submit — stage "admission", before it ever holds a queue slot."""
        from photon_ml_tpu.serve import MicroBatcher, QueueFullError

        scored = []
        metrics = _Metrics()
        batcher = MicroBatcher(
            lambda rows, pc: scored.append(len(rows)) or np.zeros(len(rows)),
            max_batch=4, max_delay_ms=1.0, metrics=metrics)
        try:
            with pytest.raises(QueueFullError) as ei:
                batcher.submit([{"features": []}], deadline_s=0.0)
            assert ei.value.cause == "deadline"
            assert metrics.drops == ["admission"]
            assert metrics.sheds == ["deadline"]
            assert scored == []  # nothing reached the score_fn
        finally:
            batcher.close()

    def test_expired_in_queue_drops_before_device_compute(self):
        """The acceptance gate: a request whose budget expires while it
        waits behind a slow batch is dropped at the queue/pre_compute
        stage — its rows NEVER reach the scoring function."""
        from photon_ml_tpu.serve import MicroBatcher, QueueFullError

        seen_rows = []
        release = threading.Event()

        def slow_score(rows, pc):
            seen_rows.append([r["tag"] for r in rows])
            release.wait(5.0)
            return np.zeros(len(rows))

        metrics = _Metrics()
        batcher = MicroBatcher(slow_score, max_batch=1, max_delay_ms=1.0,
                               max_queue=8, metrics=metrics)
        try:
            first = batcher.submit([{"tag": "head", "features": []}])
            # wait until the worker is INSIDE the slow head-of-line batch
            deadline = time.monotonic() + 5.0
            while not seen_rows and time.monotonic() < deadline:
                time.sleep(0.005)
            assert seen_rows, "worker never picked up the head batch"
            doomed = batcher.submit([{"tag": "doomed", "features": []}],
                                    deadline_s=0.05)
            time.sleep(0.1)  # budget expires while queued
            release.set()
            with pytest.raises(QueueFullError) as ei:
                doomed.result(5.0)
            assert ei.value.cause == "deadline"
            first.result(5.0)
            assert all("doomed" not in tags for tags in seen_rows), (
                "an expired request was scored anyway")
            assert metrics.drops, "no stage-labelled drop recorded"
            assert set(metrics.drops) <= {"queue", "pre_compute"}
        finally:
            release.set()
            batcher.close()

    def test_deadline_shed_maps_to_429(self, saved_game_model):
        """End to end through the service: deadline drops surface as a
        429 shed with cause=deadline — never a 5xx."""
        from photon_ml_tpu.serve import (
            MicroBatcher,
            ScoringService,
            ScoringSession,
        )

        model_dir, bundle = saved_game_model
        session = ScoringSession(model_dir, dtype="float64", max_batch=8,
                                 warmup=False)
        batcher = MicroBatcher(session.score_rows, max_batch=8,
                               max_delay_ms=1.0, metrics=session.metrics)
        svc = ScoringService(session, batcher)
        try:
            status, body = svc.handle_score(
                {"rows": serving_rows(bundle, [0])}, deadline_ms=0.0)
            assert status == 429
            assert body["shed"] is True
            assert body["cause"] == "deadline"
            assert session.metrics.snapshot()[
                "deadline_drops_admission"] == 1
            # an ample budget scores normally, not degraded
            status, body = svc.handle_score(
                {"rows": serving_rows(bundle, [0])}, deadline_ms=30_000.0)
            assert status == 200
            assert body["degraded"] == 0
        finally:
            svc.close()


# -- measured retry_after ---------------------------------------------------

class TestRetryAfterEwma:
    def test_static_fallback_before_first_batch(self):
        from photon_ml_tpu.serve import MicroBatcher

        batcher = MicroBatcher(lambda rows, pc: np.zeros(len(rows)),
                               max_batch=8, max_delay_ms=10.0)
        try:
            # no batch has completed: the old static floor remains
            assert batcher.retry_after_s == pytest.approx(0.010)
        finally:
            batcher.close()

    def test_hint_tracks_measured_service_time(self):
        """After real batches the hint is backlog / measured drain rate,
        not queue_depth x batching-deadline: a slow score_fn must raise
        it far beyond the static estimate."""
        from photon_ml_tpu.serve import MicroBatcher

        def slow(rows, pc):
            time.sleep(0.05)
            return np.zeros(len(rows))

        batcher = MicroBatcher(slow, max_batch=1, max_delay_ms=1.0,
                               max_queue=64)
        try:
            for _ in range(4):
                batcher.score([{"features": []}], timeout=5.0)
            assert batcher._svc_ewma_s is not None
            assert batcher._svc_ewma_s >= 0.04
            assert batcher._rpb_ewma == pytest.approx(1.0)
            # simulate a backlog of 10: the hint must say ~10 batches of
            # ~50ms, not 10 * 1ms
            depth = 10
            hint = (depth / max(batcher._rpb_ewma, 1.0)) * batcher._svc_ewma_s
            assert hint > 0.4
        finally:
            batcher.close()


# -- ScoreContext threading -------------------------------------------------

class TestScoreContext:
    def test_remaining_budget(self):
        from photon_ml_tpu.serve import ScoreContext

        assert ScoreContext().remaining_s() is None
        ctx = ScoreContext(deadline_at=time.monotonic() + 1.0)
        assert 0.9 < ctx.remaining_s() <= 1.0

    def test_brownout_floor_seeds_degraded(self):
        from photon_ml_tpu.serve import ScoreContext

        ctx = ScoreContext(level=2)
        assert ctx.degraded == 2
        assert ctx.reasons == ["brownout"]

    def test_batcher_threads_ctx_into_ctx_aware_score_fn(self):
        """A score_fn with a ``ctx`` parameter receives the batch's
        ScoreContext (tightest member deadline + brownout floor); the
        session's escalation lands back on every request and in the
        degraded metric."""
        from photon_ml_tpu.serve import BrownoutController, MicroBatcher

        seen_ctx = []

        def score(rows, pc, ctx=None):
            seen_ctx.append(ctx)
            ctx.degraded = max(ctx.degraded, 1)
            ctx.reasons.append("store_fault")
            return np.zeros(len(rows))

        brown = BrownoutController()
        metrics = _Metrics()
        batcher = MicroBatcher(score, max_batch=4, max_delay_ms=1.0,
                               metrics=metrics, brownout=brown)
        try:
            req = batcher.submit([{"features": []}], deadline_s=10.0)
            req.result(5.0)
            assert len(seen_ctx) == 1 and seen_ctx[0] is not None
            assert seen_ctx[0].deadline_at is not None
            assert req.degraded == 1
            assert metrics.degraded == [(1, 1)]
        finally:
            batcher.close()

    def test_ctxless_score_fn_keeps_working(self):
        """Plain two-arg score functions (every pre-existing caller and
        test fake) never see a ctx kwarg."""
        from photon_ml_tpu.serve import MicroBatcher

        batcher = MicroBatcher(lambda rows, pc: np.zeros(len(rows)),
                               max_batch=4, max_delay_ms=1.0)
        try:
            req = batcher.submit([{"features": []}], deadline_s=10.0)
            assert list(req.result(5.0)) == [0.0]
            assert req.degraded == 0
        finally:
            batcher.close()


# -- brownout controller ----------------------------------------------------

class TestBrownoutController:
    def _controller(self, **kw):
        from photon_ml_tpu.serve import BrownoutController

        clock = {"now": 0.0}
        kw.setdefault("enter_ms", {1: 50.0, 2: 200.0})
        kw.setdefault("alpha", 1.0)  # EWMA == last sample: direct control
        kw.setdefault("min_dwell_s", 2.0)
        ctl = BrownoutController(time_fn=lambda: clock["now"], **kw)
        return ctl, clock

    def test_escalation_is_immediate(self):
        ctl, _ = self._controller()
        assert ctl.note_queue_wait(10.0) == 0
        assert ctl.note_queue_wait(80.0) == 1
        assert ctl.note_queue_wait(500.0) == 2
        assert ctl.transitions == 2

    def test_deescalation_waits_out_dwell_and_hysteresis(self):
        ctl, clock = self._controller()
        ctl.note_queue_wait(80.0)
        assert ctl.level == 1
        # EWMA back inside the hysteresis band (>= exit_ratio * 50): hold
        assert ctl.note_queue_wait(30.0) == 1
        # clearly below the band but dwell not served yet: still hold
        assert ctl.note_queue_wait(5.0) == 1
        clock["now"] = 3.0
        assert ctl.note_queue_wait(5.0) == 0

    def test_level_change_fires_metrics_after_lock(self):
        from photon_ml_tpu.serve import BrownoutController

        levels = []

        class _M:
            def set_brownout_level(self, level):
                levels.append(level)

        ctl = BrownoutController(alpha=1.0, metrics=_M())
        ctl.note_queue_wait(500.0)
        assert levels == [2]

    def test_invalid_exit_ratio_rejected(self):
        from photon_ml_tpu.serve import BrownoutController

        with pytest.raises(ValueError):
            BrownoutController(exit_ratio=1.5)


# -- warming healthz + half-open hold ---------------------------------------

class TestWarmingProbe:
    def test_healthz_reports_warming_until_installs_drain(
            self, saved_game_model):
        """/healthz stays HTTP 200 while prewarm installs drain, but the
        body says "warming" — liveness and readiness in one response."""
        from photon_ml_tpu.serve import (
            MicroBatcher,
            ScoringService,
            ScoringSession,
        )

        model_dir, bundle = saved_game_model
        session = ScoringSession(model_dir, dtype="float64", max_batch=8,
                                 warmup=False)
        batcher = MicroBatcher(session.score_rows, max_batch=8,
                               metrics=session.metrics)
        svc = ScoringService(session, batcher)
        try:
            status, body = svc.handle_healthz()
            assert status == 200
            assert body["status"] == "ok"
            assert not session.warming
            # a swap queues background page installs: warming until the
            # installer drains them
            session.swap(model_dir, version="v-rewarm")
            status, body = svc.handle_healthz()
            assert status == 200
            if session.warming:
                assert body["status"] == "warming"
            session.drain_installs(10.0)
            status, body = svc.handle_healthz()
            assert body["status"] == "ok"
            assert not session.warming
        finally:
            svc.close()

    def test_front_door_holds_half_open_on_warming(self):
        """A probe answering 200 {"status": "warming"} keeps the backend
        OUT of rotation (half-open hold, no failure/backoff escalation);
        "ok" readmits it."""
        import asyncio

        from photon_ml_tpu.serve import AsyncFrontDoor

        async def scenario():
            answers = {"status": "warming"}

            async def fake_backend(reader, writer):
                try:
                    while True:
                        head = await reader.readuntil(b"\r\n\r\n")
                        if b"content-length" in head.lower():
                            length = int(
                                [ln.split(b":")[1] for ln in
                                 head.split(b"\r\n")
                                 if ln.lower().startswith(
                                     b"content-length")][0])
                            if length:
                                await reader.readexactly(length)
                        import json as _json
                        body = _json.dumps(answers).encode()
                        writer.write(
                            b"HTTP/1.1 200 OK\r\nContent-Type: "
                            b"application/json\r\nContent-Length: "
                            + str(len(body)).encode() + b"\r\n\r\n" + body)
                        await writer.drain()
                except (asyncio.IncompleteReadError, ConnectionError):
                    pass
                finally:
                    # since Python 3.12 Server.wait_closed() waits for
                    # every connection's transport to be closed
                    writer.close()

            server = await asyncio.start_server(fake_backend,
                                                "127.0.0.1", 0)
            port = server.sockets[0].getsockname()[1]
            door = AsyncFrontDoor([f"127.0.0.1:{port}"],
                                  retry_backend_s=0.05)
            backend = door._backends[0]
            backend.state = "open"
            backend.next_probe_at = 0.0
            door._maybe_probe(backend, time.monotonic())
            for _ in range(100):
                if not backend.probe_inflight:
                    break
                await asyncio.sleep(0.01)
            assert backend.state == "half_open"
            assert door.warming_holds == 1
            assert door.readmitted == 0
            assert backend.next_probe_at > time.monotonic() - 0.05
            # installer drained: the next probe readmits
            answers["status"] = "ok"
            backend.next_probe_at = 0.0
            door._maybe_probe(backend, time.monotonic())
            for _ in range(100):
                if not backend.probe_inflight:
                    break
                await asyncio.sleep(0.01)
            assert backend.state == "closed"
            assert door.readmitted == 1
            await door.aclose()
            server.close()
            await server.wait_closed()

        asyncio.run(scenario())
