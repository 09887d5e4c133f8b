"""Compiles seen by ``jax.monitoring`` (copied from ``chip_smoke.py``)."""

from __future__ import annotations


class CompileMeter:
    """Seconds spent in XLA compiles (or fetching them from the persistent
    cache), their number, and the cache's hits."""

    def __init__(self):
        import jax.monitoring as mon

        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        mon.register_event_duration_secs_listener(self._duration)
        mon.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event.endswith("backend_compile_duration"):
            self.seconds += secs
            self.compiles += 1

    def _event(self, event, **_):
        if event.endswith("compilation_cache/cache_hits"):
            self.cache_hits += 1

    def snapshot(self):
        return self.seconds, self.compiles, self.cache_hits
