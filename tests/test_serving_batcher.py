"""MicroBatcher: coalescing, deadlines, bounded-queue load shedding (no
hangs), stuck-batch watchdog, and result slicing. All tests drive fake
score functions — no model, no device."""

import threading
import time

import numpy as np
import pytest


def _echo_score(rows, per_coordinate=False):
    scores = np.asarray([float(r["v"]) for r in rows])
    if per_coordinate:
        return scores, {"fixed": scores * 2}
    return scores


def _rows(*vals):
    return [{"v": v} for v in vals]


def test_coalesces_requests_into_batches():
    from photon_ml_tpu.serve import MicroBatcher

    batches = []
    gate = threading.Event()

    def score(rows, per_coordinate=False):
        gate.wait(5.0)
        batches.append(len(rows))
        return _echo_score(rows)

    b = MicroBatcher(score, max_batch=8, max_delay_ms=50.0, max_queue=64)
    try:
        pending = [b.submit(_rows(float(i))) for i in range(8)]
        gate.set()  # all 8 one-row requests admitted before scoring runs
        results = [p.result(10.0) for p in pending]
        assert [r[0] for r in results] == [float(i) for i in range(8)]
        # the first batch may dispatch with however many had arrived when
        # the worker woke, but far fewer executions than requests
        assert sum(batches) == 8
        assert len(batches) < 8
        assert max(batches) <= 8
    finally:
        b.close()


def test_deadline_dispatches_partial_batch():
    from photon_ml_tpu.serve import MicroBatcher

    b = MicroBatcher(_echo_score, max_batch=64, max_delay_ms=20.0,
                     max_queue=8)
    try:
        t0 = time.monotonic()
        out = b.score(_rows(3.0), timeout=10.0)
        elapsed = time.monotonic() - t0
        assert out[0] == 3.0
        assert elapsed < 5.0  # deadline fired; nothing waited for 64 rows
    finally:
        b.close()


def test_queue_full_sheds_immediately():
    from photon_ml_tpu.serve import MicroBatcher, QueueFullError

    release = threading.Event()

    def blocked(rows, per_coordinate=False):
        release.wait(10.0)
        return _echo_score(rows)

    b = MicroBatcher(blocked, max_batch=1, max_delay_ms=1.0, max_queue=2)
    try:
        first = b.submit(_rows(1.0))  # worker takes it, blocks in score
        time.sleep(0.05)
        held = [b.submit(_rows(2.0)), b.submit(_rows(3.0))]  # fills queue
        t0 = time.monotonic()
        with pytest.raises(QueueFullError, match="shed"):
            b.submit(_rows(4.0))
        assert time.monotonic() - t0 < 1.0  # shed, not queued/blocked
        release.set()
        assert first.result(10.0)[0] == 1.0
        assert [h.result(10.0)[0] for h in held] == [2.0, 3.0]
    finally:
        release.set()
        b.close()


def test_shed_is_counted():
    from photon_ml_tpu.serve import MicroBatcher, QueueFullError
    from photon_ml_tpu.serve.metrics import ServingMetrics

    release = threading.Event()
    metrics = ServingMetrics()

    def blocked(rows, per_coordinate=False):
        release.wait(10.0)
        return _echo_score(rows)

    b = MicroBatcher(blocked, max_batch=1, max_delay_ms=1.0, max_queue=1,
                     metrics=metrics)
    try:
        b.submit(_rows(1.0))
        time.sleep(0.05)
        b.submit(_rows(2.0))
        with pytest.raises(QueueFullError):
            b.submit(_rows(3.0))
        assert metrics.snapshot()["shed_total"] == 1
    finally:
        release.set()
        b.close()


def test_watchdog_fails_stuck_batch_and_worker_survives():
    from photon_ml_tpu.serve import BatchWatchdogTimeout, MicroBatcher
    from photon_ml_tpu.parallel.resilience import WatchdogTimeout

    hang = threading.Event()
    calls = []

    def sometimes_stuck(rows, per_coordinate=False):
        calls.append(len(rows))
        if rows[0]["v"] == -1.0:
            hang.wait(30.0)  # simulated wedged execution
        return _echo_score(rows)

    b = MicroBatcher(sometimes_stuck, max_batch=4, max_delay_ms=1.0,
                     max_queue=8, watchdog_s=0.2)
    try:
        stuck = b.submit(_rows(-1.0))
        with pytest.raises(BatchWatchdogTimeout, match="watchdog"):
            stuck.result(10.0)
        assert isinstance(stuck._error, WatchdogTimeout)  # PR-1 failure class
        # the worker abandoned the wedged execution and keeps serving
        assert b.score(_rows(5.0), timeout=10.0)[0] == 5.0
    finally:
        hang.set()
        b.close()


def test_multi_row_requests_slice_in_order():
    from photon_ml_tpu.serve import MicroBatcher

    b = MicroBatcher(_echo_score, max_batch=8, max_delay_ms=20.0,
                     max_queue=16)
    try:
        p1 = b.submit(_rows(1.0, 2.0, 3.0))
        p2 = b.submit(_rows(10.0), per_coordinate=True)
        p3 = b.submit(_rows(20.0, 30.0))
        assert list(p1.result(10.0)) == [1.0, 2.0, 3.0]
        scores, parts = p2.result(10.0)
        assert list(scores) == [10.0]
        assert list(parts["fixed"]) == [20.0]
        assert list(p3.result(10.0)) == [20.0, 30.0]
    finally:
        b.close()


def test_oversized_and_empty_requests_rejected():
    from photon_ml_tpu.serve import MicroBatcher

    b = MicroBatcher(_echo_score, max_batch=2, max_delay_ms=1.0,
                     max_queue=4)
    try:
        with pytest.raises(ValueError, match="max_batch"):
            b.submit(_rows(1.0, 2.0, 3.0))
        with pytest.raises(ValueError, match="empty"):
            b.submit([])
        # a request that would overflow the current batch is carried to
        # the next execution, not dropped
        p1 = b.submit(_rows(1.0))
        p2 = b.submit(_rows(2.0, 3.0))
        assert list(p1.result(10.0)) == [1.0]
        assert list(p2.result(10.0)) == [2.0, 3.0]
    finally:
        b.close()


def test_scoring_error_propagates_to_all_requests_of_batch():
    from photon_ml_tpu.serve import MicroBatcher

    def boom(rows, per_coordinate=False):
        raise RuntimeError("synthetic scoring failure")

    b = MicroBatcher(boom, max_batch=4, max_delay_ms=20.0, max_queue=8)
    try:
        p1 = b.submit(_rows(1.0))
        p2 = b.submit(_rows(2.0))
        for p in (p1, p2):
            with pytest.raises(RuntimeError, match="synthetic"):
                p.result(10.0)
    finally:
        b.close()


def test_close_rejects_new_submissions():
    from photon_ml_tpu.serve import MicroBatcher

    b = MicroBatcher(_echo_score, max_batch=2, max_delay_ms=1.0,
                     max_queue=4)
    b.close()
    with pytest.raises(RuntimeError, match="closed"):
        b.submit(_rows(1.0))


def test_queue_full_carries_retry_after_hint():
    """429s must tell the client HOW LONG to back off: retry_after_s
    derives from queue depth x the batching deadline and is floored at
    one deadline."""
    from photon_ml_tpu.serve import MicroBatcher, QueueFullError

    gate = threading.Event()

    def slow(rows, per_coordinate=False):
        gate.wait(5.0)
        return _echo_score(rows)

    b = MicroBatcher(slow, max_batch=2, max_delay_ms=10.0, max_queue=2)
    try:
        for i in range(3):  # worker holds one, queue holds two
            b.submit(_rows(float(i)))
            time.sleep(0.02 if i == 0 else 0.0)
        with pytest.raises(QueueFullError) as exc:
            b.submit(_rows(9.0))
        assert exc.value.cause == "queue_full"
        assert exc.value.retry_after_s >= b.max_delay_s
    finally:
        gate.set()
        b.close()


def test_deadline_shed_splits_metrics_by_cause():
    """Requests whose deadline expires while queued are shed by the
    worker with cause='deadline'; the metrics split the two shed causes
    and shed_total stays their sum."""
    from photon_ml_tpu.serve import (
        MicroBatcher,
        QueueFullError,
        ServingMetrics,
    )

    metrics = ServingMetrics()
    release = threading.Event()

    def slow(rows, per_coordinate=False):
        release.wait(5.0)
        return _echo_score(rows)

    b = MicroBatcher(slow, max_batch=1, max_delay_ms=1.0, max_queue=8,
                     request_deadline_s=0.05, metrics=metrics)
    try:
        first = b.submit(_rows(1.0))   # occupies the worker
        stale = b.submit(_rows(2.0))   # waits past its deadline
        time.sleep(0.15)
        release.set()
        assert first.result(5.0)[0] == 1.0
        with pytest.raises(QueueFullError) as exc:
            stale.result(5.0)
        assert exc.value.cause == "deadline"
        assert exc.value.retry_after_s > 0
        snap = metrics.snapshot()
        assert snap["shed_deadline_total"] == 1
        assert snap["shed_total"] == (snap["shed_queue_full_total"]
                                      + snap["shed_deadline_total"])
    finally:
        release.set()
        b.close()


def test_request_latency_splits_into_queue_wait_and_compute():
    """The queue-wait / device-compute histograms must account for the
    request latency: a stalled batch shows up as queue wait for the
    request behind it and as compute for its own batch."""
    from photon_ml_tpu.serve import MicroBatcher, ServingMetrics

    metrics = ServingMetrics()

    def slow(rows, per_coordinate=False):
        time.sleep(0.03)
        return _echo_score(rows)

    b = MicroBatcher(slow, max_batch=1, max_delay_ms=1.0, max_queue=8,
                     metrics=metrics)
    try:
        pending = [b.submit(_rows(float(i))) for i in range(3)]
        for p in pending:
            p.result(10.0)
        snap = metrics.snapshot()
        # batch 3 waited behind ~2 executions of ~30ms each
        assert snap["queue_wait_p99_ms"] >= 30.0
        assert snap["compute_p50_ms"] >= 25.0
        assert metrics.queue_wait_ms.total == 3
        assert metrics.compute_ms.total == 3
        rendered = metrics.render()
        assert "photon_serve_queue_wait_ms_bucket" in rendered
        assert "photon_serve_compute_ms_bucket" in rendered
    finally:
        b.close()


def test_done_callback_fires_on_resolution_any_order():
    """add_done_callback is the asyncio bridge: it must fire exactly
    once whether registered before or after the request resolves."""
    from photon_ml_tpu.serve import MicroBatcher

    b = MicroBatcher(_echo_score, max_batch=4, max_delay_ms=1.0)
    try:
        fired = []
        req = b.submit(_rows(1.0))
        req.add_done_callback(lambda r: fired.append(r.result(0)[0]))
        req.result(5.0)
        deadline = time.monotonic() + 5.0
        while not fired and time.monotonic() < deadline:
            time.sleep(0.001)
        assert fired == [1.0]
        # late registration: resolved request -> immediate callback
        req.add_done_callback(lambda r: fired.append("late"))
        assert fired == [1.0, "late"]
        assert req.error is None
    finally:
        b.close()


def test_close_joins_worker_without_thread_leak():
    """close() must actually reap the worker (bounded join, PT403's
    runtime discipline) — verified by the thread-leak sanitizer."""
    from photon_ml_tpu.analysis.sanitizers import ThreadLeakSanitizer
    from photon_ml_tpu.serve import MicroBatcher

    with ThreadLeakSanitizer():
        b = MicroBatcher(_echo_score, max_batch=8, max_delay_ms=10.0,
                         max_queue=8)
        assert b.score(_rows(1.0), timeout=10.0)[0] == 1.0
        b.close()
        assert not b._worker.is_alive()
        assert b.join_timeouts == 0
        b.close()  # idempotent


def test_close_idle_worker_wakes_from_bounded_poll():
    """A worker that never saw a request parks in the bounded idle
    poll; close() must still reap it promptly via the stop event."""
    from photon_ml_tpu.serve import MicroBatcher

    b = MicroBatcher(_echo_score, max_batch=8, max_delay_ms=10.0)
    t0 = time.monotonic()
    b.close()
    assert not b._worker.is_alive()
    assert time.monotonic() - t0 < 5.0


def test_close_times_out_on_wedged_scoring_and_warns(caplog):
    """A wedged scoring execution must not wedge close(): the bounded
    join expires, the leak is counted and logged (the
    producer_join_timeouts idiom), and the request still resolves when
    the execution finally returns."""
    import logging

    from photon_ml_tpu.serve import MicroBatcher

    release = threading.Event()

    def wedged(rows, per_coordinate=False):
        release.wait(30.0)
        return _echo_score(rows)

    b = MicroBatcher(wedged, max_batch=8, max_delay_ms=1.0, max_queue=8)
    req = b.submit(_rows(2.0))
    deadline = time.monotonic() + 5.0
    while b.queue_depth and time.monotonic() < deadline:
        time.sleep(0.005)  # worker picked it up and is inside wedged()
    with caplog.at_level(logging.WARNING,
                         logger="photon_ml_tpu.serve.batcher"):
        b.close(drain_timeout_s=0.1)
    assert b.join_timeouts == 1
    assert any("still alive" in r.getMessage() for r in caplog.records)
    release.set()
    assert req.result(10.0)[0] == 2.0
    b._worker.join(10.0)
    assert not b._worker.is_alive()
