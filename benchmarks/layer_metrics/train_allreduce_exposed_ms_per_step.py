"""Gradient all-reduce that nothing hides: collective device time during
which no compute operation runs on the same chip, per step, averaged
over the chips. A run on one chip has no collective and reports
nothing."""

from benchmarks.harness import trace as btrace

NAME = "train_allreduce_exposed_ms_per_step"
UNIT = "ms"
LAYER = "Collectives"
MOVES = "train_tokens_per_s_per_chip"
SOURCE = "device_trace"


def read(run):
    steps = run.counters.get("traced_steps")
    if run.trace is None or not steps:
        return None
    if not any(btrace.is_collective(e) for e in run.trace.device_ops):
        return None
    return btrace.exposed_collective_s(run.trace) / steps * 1e3
