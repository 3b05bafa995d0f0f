"""Share of the first device's idle seconds, in gaps of 20 us or more,
that a span of the program covers (the program's annotations in the
profiler's own file, named by ``trace.idle_gaps``'s rule): how much of
the idle time the program can put a name to. The ``#`` line lists the
seconds by span."""

from benchmarks.harness import program_spans as ps
from benchmarks.harness.result import emit

NAME = "serve_idle_attributed_pct"
UNIT = "%"
LAYER = "Device"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"


def read(run):
    if "slots" not in run.counters:
        return None
    gaps = ps.idle_gaps(run)
    if gaps is None:
        return None
    long_gaps = {k: v for k, v in gaps.items() if k != "under_20us"}
    total = sum(long_gaps.values())
    if total <= 0:
        return None
    emit("idle_by_program_span", gaps)
    return (total - long_gaps.get("unspanned", 0.0)) / total * 100.0
