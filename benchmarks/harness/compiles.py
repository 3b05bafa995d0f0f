"""Count compilations and split set-up time, from JAX's own monitoring
events. A backend-compile event fires once per new executable, whether
XLA compiled it or the persistent cache supplied it; inside a measured
window the count has to stay at zero."""

from __future__ import annotations

TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"


class CompileLedger:
    """One per process: JAX has no call to unregister a listener."""

    def __init__(self):
        self.seconds = {TRACE: 0.0, LOWER: 0.0, BACKEND: 0.0}
        self.programs = 0
        self.cache_hits = 0

    def install(self) -> "CompileLedger":
        from jax import monitoring

        def on_duration(name, dur, **kw):
            if name in self.seconds:
                self.seconds[name] += dur
                if name == BACKEND:
                    self.programs += 1

        def on_event(name, **kw):
            if name == CACHE_HIT:
                self.cache_hits += 1

        monitoring.register_event_duration_secs_listener(on_duration)
        monitoring.register_event_listener(on_event)
        return self

    def snapshot(self) -> dict:
        return {
            "trace_s": self.seconds[TRACE],
            "lower_s": self.seconds[LOWER],
            "backend_compile_or_cache_load_s": self.seconds[BACKEND],
            "programs": self.programs,
            "cache_hits": self.cache_hits,
        }
