"""Seconds spent compiling, and persistent-cache hits, from JAX's own
monitoring events.  The benchmark reads it around set-up and around the
measured window, where no compile should happen."""
from __future__ import annotations


class CompileClock:
    def __init__(self):
        import jax
        self.compile_s = 0.0
        self.compiles = 0
        self.cache_hits = 0

        def on_duration(event, secs, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.compile_s += secs
                self.compiles += 1

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def snapshot(self):
        return {"compile_s": self.compile_s, "compiles": self.compiles,
                "cache_hits": self.cache_hits}
