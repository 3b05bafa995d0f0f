#!/usr/bin/env python
"""What one always-on span costs the host, with no profiler session
open: ``python scripts/span_cost.py`` prints one JSON line.

The tracer's ring level (ddp_tpu/obs/tracer.py) is on in every run, so
its cost is part of every step. Budget: 3 us a span on the chip's host.
Times ``with tracer.span(name): pass`` (two clock reads, one profiler
annotation the runtime drops, one ring append) and the retroactive
``complete``, best of a few rounds, on whatever host this runs on; a
host number, whatever device JAX finds. Beside them, off the hot path:
what a kept record costs (``phase_complete``) and what one call of the
compile listener costs (a compile event's callback, which keeps one),
each over a store with room.
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(n: int = 200_000, rounds: int = 5) -> dict:
    import jax

    from ddp_tpu.obs import tracer as tracer_mod
    from ddp_tpu.obs.tracer import KEPT_RECORDS, Tracer

    tracer = Tracer()
    span_ns, complete_ns, phase_ns, listener_ns = [], [], [], []
    for _ in range(rounds):
        t = time.perf_counter()
        for _ in range(n):
            with tracer.span("serve.step", nums=(8, 8, 3)):
                pass
        span_ns.append((time.perf_counter() - t) / n * 1e9)
        t = time.perf_counter()
        for _ in range(n):
            tracer.complete("server.request", 0.0, 1.0, nums=(1, 0.1, 0.2))
        complete_ns.append((time.perf_counter() - t) / n * 1e9)
        kept = Tracer()
        t = time.perf_counter()
        for _ in range(KEPT_RECORDS):
            kept.phase_complete("compile.trace", 0.0, 1.0, nums=("f",))
        phase_ns.append((time.perf_counter() - t) / KEPT_RECORDS * 1e9)
        # the listener keeps in the process-global tracer
        tracer_mod._GLOBAL, was = Tracer(), tracer_mod._GLOBAL
        on_duration = tracer_mod._COMPILES.on_duration
        event = "/jax/core/compile/jaxpr_to_mlir_module_duration"
        t = time.perf_counter()
        for _ in range(KEPT_RECORDS):
            on_duration(event, 1e-3, fun_name="jit(f)")
        listener_ns.append((time.perf_counter() - t) / KEPT_RECORDS * 1e9)
        tracer_mod._GLOBAL = was
    return {
        "span_ns_best": min(span_ns), "span_ns_rounds": span_ns,
        "complete_ns_best": min(complete_ns),
        "phase_complete_ns_best": min(phase_ns),
        "compile_listener_ns_best": min(listener_ns),
        "platform": jax.devices()[0].platform,
        "cpu_count": os.cpu_count(),
    }


if __name__ == "__main__":
    print(json.dumps(main()))
