"""Counts XLA compilations through JAX's monitoring events."""
from __future__ import annotations


class CompileCounter:
    """Backend compiles, and how many of them the persistent cache answered.

    `requests` counts every backend compile request, `cache_hits` those the
    persistent cache served, so `requests - cache_hits` programs were really
    compiled; `seconds` sums the backend compile durations.
    """

    BACKEND = "/jax/core/compile/backend_compile_duration"
    CACHE_HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        import jax

        self.requests = self.cache_hits = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_time)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_time(self, event, duration, **_):
        if event == self.BACKEND:
            self.requests += 1
            self.seconds += duration

    def _on_event(self, event, **_):
        if event == self.CACHE_HIT:
            self.cache_hits += 1

    def mark(self) -> tuple:
        return self.requests, self.cache_hits, self.seconds

    def since(self, mark: tuple) -> dict:
        requests = self.requests - mark[0]
        hits = self.cache_hits - mark[1]
        return {"compiled": requests - hits, "loaded": hits,
                "seconds": self.seconds - mark[2]}
