"""Host spans recorded from the benchmark's own files, around the calls
into each layer. Kept in memory; with tracing on they are also written
into the profiler's trace (``jax.profiler.TraceAnnotation``) so that
idle gaps on the device can be named by what the host was doing."""

from __future__ import annotations

import contextlib
import time
from collections import defaultdict


class Spans:
    def __init__(self):
        self.enabled = False  # host timing of spans (traced runs only)
        self.annotate = False  # also write into the profiler's trace
        self.total_s: dict[str, float] = defaultdict(float)
        self.count: dict[str, int] = defaultdict(int)

    def reset(self) -> None:
        self.total_s.clear()
        self.count.clear()

    @contextlib.contextmanager
    def span(self, name: str):
        if not self.enabled:
            yield
            return
        ann = None
        if self.annotate:
            import jax

            ann = jax.profiler.TraceAnnotation(name)
            ann.__enter__()
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.total_s[name] += time.perf_counter() - t0
            self.count[name] += 1
            if ann is not None:
                ann.__exit__(None, None, None)
