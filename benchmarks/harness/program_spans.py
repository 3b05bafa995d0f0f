"""The program's own spans, for the per-layer metrics that read them.

The program records host spans into a process-global, always-on ring
(``ddp_tpu.obs.tracer.get_tracer().ring()``: ``(name, t0, dur, parent,
nums)`` with ``t0`` on ``time.perf_counter``, the clock ``Block.start``
and ``Block.end`` are on) and, while the profiler runs, into the
profiler's own trace. The drivers free the program before any reader
runs, so a reader reaches the spans only through that global.

Readers report a host number from the UNTRACED blocks (the measured
window) and print the traced blocks' value beside it on a ``#`` line:
the difference is what the profiler costs the host, in every traced
run, for nothing.

A program without the ring, or without a span (the parent of the PR
that brought them), gives nothing here and no reader raises.
"""

from __future__ import annotations

import copy
import os
import statistics
import sys

from benchmarks.harness import trace as btrace
from benchmarks.harness.result import emit

# Names the program gives its spans (obs/tracer.SPAN_NUMS).
PREFIXES = ("serve.", "server.", "data.", "train.")
# The program's own table of wait spans, as it stood when this was
# written: host time spent waiting for the device.
WAIT = "serve.sample"


def ring() -> list:
    """The program's always-on spans, oldest first; [] where the
    program has none."""
    try:
        from ddp_tpu.obs.tracer import get_tracer

        return [e for e in get_tracer().ring()
                if e[0].startswith(PREFIXES)]
    except (ImportError, AttributeError):
        return []


def blocks_of(run, traced: bool) -> list:
    return [b for b in run.blocks if b.traced == traced]


def inside(spans: list, blocks: list) -> list:
    """Spans that lie wholly inside one of the blocks."""
    out = []
    for e in spans:
        t0, t1 = e[1], e[1] + e[2]
        if any(b.start <= t0 and t1 <= b.end for b in blocks):
            out.append(e)
    return out


def ending_in(spans: list, blocks: list) -> list:
    """Spans that END between the first block's start and the last
    block's end: a request is counted where it is handed back."""
    if not blocks:
        return []
    lo, hi = blocks[0].start, blocks[-1].end
    return [e for e in spans if lo <= e[1] + e[2] <= hi]


def both(name: str, value_of) -> float | None:
    """``value_of(traced: bool)`` over the untraced blocks is the
    metric; the same over the traced blocks is printed beside it."""
    value = value_of(False)
    if value is not None:
        emit("program_span", {"metric": name, "untraced_blocks": value,
                              "traced_blocks": value_of(True)})
    return value


def per_step_ms(run, span_name: str, traced: bool) -> float | None:
    """Total milliseconds of one span name over the blocks' steps."""
    blocks = blocks_of(run, traced)
    steps = sum(b.steps for b in blocks)
    spans = [e for e in inside(ring(), blocks) if e[0] == span_name]
    if not steps or not spans:
        return None
    return sum(e[2] for e in spans) / steps * 1e3


def request_ms_p50(run, field: int, traced: bool) -> float | None:
    """Median, over requests handed back in the blocks, of one number
    of ``server.request`` (1 ``lock_wait_s``, 2 ``pickup_s``), in ms."""
    done = [e for e in ending_in(ring(), blocks_of(run, traced))
            if e[0] == "server.request" and len(e[4]) > field]
    if not done:
        return None
    return statistics.median(e[4][field] for e in done) * 1e3


def by_name(spans: list) -> dict:
    """name -> {count, total_ms, max_ms}, longest total first."""
    acc: dict = {}
    for name, _, dur, _, _ in spans:
        a = acc.setdefault(name, {"count": 0, "total_ms": 0.0,
                                  "max_ms": 0.0})
        a["count"] += 1
        a["total_ms"] += dur * 1e3
        a["max_ms"] = max(a["max_ms"], dur * 1e3)
    return dict(sorted(acc.items(), key=lambda kv: -kv[1]["total_ms"]))


# ---- the program's names in the device trace ---------------------------


def kernel_ms_per_step(run, names: tuple) -> float | None:
    """Device milliseconds a traced step spends in the Pallas kernels
    the program named ``names`` (``pallas_call(name=...)``: the trace's
    ``custom-call:<name>``), averaged over the chips. None where no
    such kernel ran."""
    steps = run.counters.get("traced_steps")
    if run.trace is None or not steps:
        return None
    classes = {"custom-call:" + n for n in names}
    s = btrace.seconds_where(
        run.trace,
        lambda e: btrace.is_kernel(e) and btrace.op_class(e) in classes,
    )
    return s / steps * 1e3 if s > 0 else None


# ---- the program's annotations in the profiler's own file ---------------


def out_dir(run) -> str:
    """The run's directory, resolved as ``run.py`` resolves it:
    ``--out`` if the command line has it, else
    ``chiprun_out/bench/<cell>`` under the checkout."""
    argv = sys.argv
    for i, a in enumerate(argv):
        if a == "--out" and i + 1 < len(argv):
            return argv[i + 1]
        if a.startswith("--out="):
            return a[len("--out="):]
    return os.path.join(run.cell.root, "chiprun_out", "bench",
                        run.cell.name)


def annotated(run) -> list:
    """Host events of the run's ``.xplane.pb`` that carry the program's
    prefixes, as ``(name, start_ns, dur_ns)`` on the clock the device
    events are on, shortest first (so that of two spans covering a gap
    equally the inner one names it). [] where there is no trace or no
    such event."""
    import jax

    try:
        path = btrace.find_xplane(os.path.join(out_dir(run), "trace"))
    except FileNotFoundError:
        return []
    pd = jax.profiler.ProfileData.from_file(path)
    spans = []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(PREFIXES):
                    spans.append((ev.name, int(ev.start_ns),
                                  int(ev.duration_ns)))
    return sorted(spans, key=lambda s: s[2])


def idle_gaps(run) -> dict | None:
    """``trace.idle_gaps`` with the program's annotations as the host
    spans: idle seconds of the first device by the program span that
    covers most of each gap. The rule that names a gap stays the one
    the result line's ``breakdown`` uses.

    The profiler keeps an annotation only if it both began and ended
    inside the session, so the span in flight when the session opened
    and the one in flight when it closed are missing from the file
    (a 106 ms engine step each). The window is therefore cut to where
    the annotations are whole: first one's start to last one's end."""
    if run.trace is None:
        return None
    spans = annotated(run)
    if not spans:
        return None
    tr = copy.copy(run.trace)
    tr.host_spans = spans
    lo, hi = tr.window_ns
    tr.window_ns = (max(lo, min(s[1] for s in spans)),
                    min(hi, max(s[1] + s[2] for s in spans)))
    return btrace.idle_gaps(tr)
