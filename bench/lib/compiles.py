"""XLA compiles and persistent-cache hits, from JAX's monitoring events
(copied from ``chip_smoke.py``'s ``CompileCounter``)."""
from __future__ import annotations


class CompileCounter:
    def __init__(self):
        from jax import monitoring

        self.compiles = 0
        self.cache_hits = 0

        def on_duration(event, duration, **kw):
            if event == "/jax/core/compile/backend_compile_duration":
                self.compiles += 1

        def on_event(event, **kw):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        monitoring.register_event_duration_secs_listener(on_duration)
        monitoring.register_event_listener(on_event)
