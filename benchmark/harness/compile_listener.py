"""What jax's own monitoring says about compilation in this process
(the listener chip_smoke.py proved on the v5e in PR 21, copied so that
no program PR can move it): compile requests that consulted the
persistent cache, how many it answered, and the seconds the backend
spent compiling or loading."""

from __future__ import annotations


class CompileCounters:
    def __init__(self):
        import jax

        self.requests = self.hits = 0
        self.backend_compile_s = 0.0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.backend_compile_s += duration

    @property
    def compiled(self) -> int:
        """Programs the backend compiled because the cache had none."""
        return self.requests - self.hits

    def snapshot(self) -> tuple[int, int]:
        return self.requests, self.hits
