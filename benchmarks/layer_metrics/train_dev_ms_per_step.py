"""Device-busy milliseconds per train step, from the profiler's trace:
the union of operation intervals over the traced steps, averaged over
the chips."""

from benchmarks.harness import trace as btrace

NAME = "train_dev_ms_per_step"
UNIT = "ms"
LAYER = "Train step"
MOVES = "train_tokens_per_s_per_chip"
SOURCE = "device_trace"


def read(run):
    steps = run.counters.get("traced_steps")
    if run.trace is None or not steps:
        return None
    return btrace.busy(run.trace)["busy_s"] / steps * 1e3
