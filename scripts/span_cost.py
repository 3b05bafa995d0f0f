#!/usr/bin/env python
"""What one always-on span costs the host, with no profiler session
open: ``python scripts/span_cost.py`` prints one JSON line.

The tracer's ring level (ddp_tpu/obs/tracer.py) is on in every run, so
its cost is part of every step. Budget: 3 us a span on the chip's host.
Times ``with tracer.span(name): pass`` (two clock reads, one profiler
annotation the runtime drops, one ring append) and the retroactive
``complete``, best of a few rounds, on whatever host this runs on; a
host number, whatever device JAX finds.
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(n: int = 200_000, rounds: int = 5) -> dict:
    import jax

    from ddp_tpu.obs.tracer import Tracer

    tracer = Tracer()
    span_ns, complete_ns = [], []
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
    return {
        "span_ns_best": min(span_ns), "span_ns_rounds": span_ns,
        "complete_ns_best": min(complete_ns),
        "platform": jax.devices()[0].platform,
        "cpu_count": os.cpu_count(),
    }


if __name__ == "__main__":
    print(json.dumps(main()))
