"""obs/metrics.py: the unified registry, exposition-format correctness,
and the byte-compatibility contract of the serve/metrics.py re-export.

The serving half of the refactor is gated by a GOLDEN fixture
(``analysis_fixtures/serve_metrics_golden.txt``): one fixed exercise
sequence over :class:`ServingMetrics` must render byte-identically to
the text the pre-refactor ``serve/metrics.py`` produced — scrape
configs and recording rules parse these exact bytes, so "semantically
equal" is not good enough.
"""

import os
import threading

import pytest

from photon_ml_tpu.obs.metrics import (
    DEFAULT_SECONDS_BUCKETS,
    Histogram,
    MetricsRegistry,
    ServingMetrics,
    TrainingMetrics,
    escape_label_value,
)

GOLDEN = os.path.join(os.path.dirname(__file__), "analysis_fixtures",
                      "serve_metrics_golden.txt")


def exercise(m: ServingMetrics) -> None:
    """The fixed sequence the golden fixture was rendered from. Any
    edit here must regenerate the fixture (and justify why the bytes
    changed)."""
    m.record_request(8, 3.2, queue_wait_ms=0.4, compute_ms=2.5)
    m.record_request(64, 120.0, queue_wait_ms=30.0, compute_ms=80.0)
    m.record_request(1, 0.2)
    m.record_shed()
    m.record_shed(cause="deadline")
    m.record_error()
    m.record_batch(64, 64, 9.5)
    m.record_batch(8, 64, 1.25)
    m.set_queue_depth(3)
    m.record_compile(hit=False)
    m.record_compile(hit=True)
    m.record_compile(hit=True)
    m.record_coeff(hits=10, misses=2, evictions=1)
    m.record_paged(installs=4, page_evictions=1, faults=2)
    m.set_active_version("v000001")
    m.record_swap('v0002"w\\x', 12.5)
    m.record_gate(True)
    m.record_gate(False)
    m.record_degraded(1)
    m.record_degraded(2, n=3)
    m.record_degraded(0)  # no-op: level 0 is "not degraded"
    m.record_deadline_drop("admission")
    m.record_deadline_drop("queue")
    m.record_deadline_drop("queue")
    m.record_deadline_drop("pre_compute")
    m.set_brownout_level(1)
    m.set_model_staleness(42.5)
    # entity-affinity membership series (PR 15): the fixture was
    # regenerated when these were appended — an append-only byte change,
    # every pre-existing series renders identically
    m.set_membership_epoch(3)
    m.record_membership(prefetch_entities=5, prefetch_bytes=640,
                        non_owned_skips=2, evictions=7)


class TestServingParity:
    def test_obs_render_matches_golden_bytes(self):
        m = ServingMetrics()
        exercise(m)
        with open(GOLDEN, encoding="utf-8") as f:
            assert m.render() == f.read()

    def test_serve_shim_is_the_same_class(self):
        # serve/metrics.py is a pure re-export: anything importing the
        # old path gets the SAME objects, not lookalikes
        from photon_ml_tpu.serve import metrics as serve_metrics

        assert serve_metrics.ServingMetrics is ServingMetrics
        assert serve_metrics.Histogram is Histogram

    def test_serve_shim_render_matches_golden_bytes(self):
        from photon_ml_tpu.serve.metrics import ServingMetrics as Shim

        m = Shim()
        exercise(m)
        with open(GOLDEN, encoding="utf-8") as f:
            assert m.render() == f.read()


class TestEscaping:
    @pytest.mark.parametrize("raw,expected", [
        ('plain', 'plain'),
        ('with"quote', 'with\\"quote'),
        ('back\\slash', 'back\\\\slash'),
        ('line\nbreak', 'line\\nbreak'),
        # backslash escapes first, so an escaped quote stays parseable
        ('\\"', '\\\\\\"'),
    ])
    def test_escape_label_value(self, raw, expected):
        assert escape_label_value(raw) == expected

    def test_escaped_value_renders_into_valid_series(self):
        reg = MetricsRegistry()
        c = reg.counter("t_total", "help")
        c.inc(v='a"b\\c\nd')
        out = reg.render()
        assert 't_total{v="a\\"b\\\\c\\nd"} 1' in out


class TestHistogramContract:
    def test_inf_bucket_equals_count(self):
        h = Histogram([1.0, 10.0])
        for v in (0.5, 5.0, 50.0, 500.0):
            h.observe(v)
        out = []
        h.render("m", out)
        text = "\n".join(out)
        assert 'm_bucket{le="+Inf"} 4' in text
        assert "m_count 4" in text

    def test_le_cumulativity(self):
        h = Histogram(list(DEFAULT_SECONDS_BUCKETS))
        import random

        rng = random.Random(7)
        for _ in range(500):
            h.observe(rng.uniform(0.0, 1000.0))
        out = []
        h.render("m", out)
        counts = [int(line.rsplit(" ", 1)[1]) for line in out
                  if "_bucket{" in line]
        assert counts == sorted(counts), "le buckets must be cumulative"
        assert counts[-1] == 500  # +Inf holds every observation

    def test_boundary_lands_in_le_bucket(self):
        # le is <=: an observation exactly on a bound counts in it
        # (integral bounds render without a trailing .0, like Prometheus
        # client_python)
        h = Histogram([1.0, 2.0])
        h.observe(1.0)
        out = []
        h.render("m", out)
        text = "\n".join(out)
        assert 'm_bucket{le="1"} 1' in text


class TestRegistry:
    def test_kind_conflict_raises(self):
        reg = MetricsRegistry()
        reg.counter("x_total", "help")
        with pytest.raises(ValueError):
            reg.gauge("x_total", "help")

    def test_get_or_create_returns_same_series(self):
        reg = MetricsRegistry()
        assert reg.counter("y_total", "h") is reg.counter("y_total", "h")

    def test_render_orders_by_registration(self):
        reg = MetricsRegistry()
        reg.counter("b_total", "h").inc()
        reg.gauge("a_gauge", "h").set(1)
        out = reg.render()
        assert out.index("b_total") < out.index("a_gauge")

    def test_labeled_series_first_seen_order_is_stable(self):
        # exposition order within a family is first-seen (documented on
        # _Series) — deterministic, so scrape diffs stay readable
        reg = MetricsRegistry()
        c = reg.counter("z_total", "h")
        c.inc(k="b")
        c.inc(k="a")
        c.inc(k="b")
        out = reg.render()
        assert 'z_total{k="b"} 2' in out
        assert 'z_total{k="a"} 1' in out
        assert out.index('k="b"') < out.index('k="a"')

    def test_thread_safe_increments(self):
        reg = MetricsRegistry()
        c = reg.counter("n_total", "h")

        def work():
            for _ in range(1000):
                reg.inc("n_total")  # registry-level inc holds the lock

        threads = [threading.Thread(target=work) for _ in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert c.get() == 4000


class TestTrainingMetrics:
    def test_record_run_and_render(self):
        tm = TrainingMetrics()
        stage = {"h2d_bytes": 0.0, "d2h_bytes": 8.0, "compiles": 0.0,
                 "syncs": 1.0, "sync_wait_seconds": 0.01,
                 "cache_loads": 0.0}
        for seconds in (1.5, 2.5):
            tm.record_run({"seconds": seconds, "prepare_seconds": 0.25,
                           "finish_seconds": 0.125, "sweeps": 2,
                           "prepare": stage, "finish": stage})
        runs = tm.run_records()
        assert [r["seconds"] for r in runs] == [1.5, 2.5]
        assert runs[0]["prepare"]["syncs"] == 1.0
        out = tm.render()
        assert "photon_train_run_total 2\n" in out
        assert 'photon_train_run_seconds_count{stage="run"} 2' in out
        assert 'photon_train_run_seconds_sum{stage="prepare"} 0.5' in out
        seconds = tm.snapshot()["photon_train_run_seconds"]
        assert set(seconds) == {'stage="run"', 'stage="prepare"',
                                'stage="finish"'}
        # a ring of RUN_RECORDS, oldest first
        for i in range(TrainingMetrics.RUN_RECORDS):
            tm.record_run({**runs[0], "seconds": float(i)})
        assert len(tm.run_records()) == TrainingMetrics.RUN_RECORDS
        assert tm.run_records()[0]["seconds"] == 0.0

    def test_chunk_cache_and_prefetch_and_exchange(self):
        tm = TrainingMetrics()
        tm.record_chunk_cache_pass("warm")
        tm.record_chunk_cache_pass("warm")
        tm.record_chunk_cache_pass("cold")
        tm.record_prefetch(stall_s=0.1, decode_s=0.5, transfer_s=0.2)
        tm.record_exchange(1024, 4096, 0.01)
        out = tm.render()
        assert "photon_train_chunk_cache_warm_passes_total 2" in out
        assert "photon_train_chunk_cache_cold_passes_total 1" in out
        assert "photon_train_prefetch_stall_seconds_total 0.1" in out
        assert "photon_train_exchange_bytes_sent_total 1024" in out
        assert "photon_train_exchange_bytes_gathered_total 4096" in out

    def test_singleton(self):
        from photon_ml_tpu.obs.metrics import training_metrics

        assert training_metrics() is training_metrics()
