"""The longest single span of the program in the untraced blocks. In a
quiet run it is a loader fetch or a dispatch of a few milliseconds;
with a host stall in the window it is the stall, and the ``#`` line
names the span and lists count, total and longest per name: what says
where."""

from benchmarks.harness import program_spans as ps
from benchmarks.harness.result import emit

NAME = "train_host_max_span_ms"
UNIT = "ms"
LAYER = "Trainer loop"
MOVES = "train_tokens_per_s_per_chip"
SOURCE = "program_span"


def read(run):
    if "timed_steps" not in run.counters:
        return None
    ring = ps.ring()
    spans = ps.inside(ring, ps.blocks_of(run, False))
    if not spans:
        return None
    top = max(spans, key=lambda e: e[2])
    traced = ps.inside(ring, ps.blocks_of(run, True))
    emit("program_span", {
        "metric": NAME, "untraced_blocks": top[2] * 1e3,
        "span": top[0], "at_s": top[1] - run.blocks[0].start,
        "traced_blocks": max(e[2] for e in traced) * 1e3
        if traced else None,
        "by_name": ps.by_name(spans),
    })
    return top[2] * 1e3
