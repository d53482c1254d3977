"""Compile requests, persistent-cache hits and compile seconds, from the
``jax.monitoring`` channels. A request that was not a cache hit reached
the backend compiler."""

from __future__ import annotations

_REQUEST = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"


class CompileListener:
    def __init__(self):
        self.requests = 0
        self.cache_hits = 0
        self.seconds = 0.0

    def install(self) -> "CompileListener":
        from jax import monitoring

        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)
        return self

    def _duration(self, event, duration_secs, **kw):
        if event == _REQUEST:
            self.requests += 1
            self.seconds += float(duration_secs)

    def _event(self, event, **kw):
        if event == _CACHE_HIT:
            self.cache_hits += 1

    def snapshot(self) -> dict:
        return {"requests": self.requests, "cache_hits": self.cache_hits,
                "backend_compiles": self.requests - self.cache_hits,
                "seconds": self.seconds}


def delta(after: dict, before: dict) -> dict:
    return {k: after[k] - before[k] for k in after}
