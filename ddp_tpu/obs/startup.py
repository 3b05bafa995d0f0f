"""What a process did before its first productive step, in one line.

Reads the tracer's KEPT records (``Tracer.startup()``: the phases under
``startup.`` and one ``compile.*`` record per JAX compile event, by
program name; obs/tracer.py) and sums them up for an operator:
``train.py`` logs the line when the first step has run,
``scripts/serve.py`` prints it when the socket listens.

Phases and compile records nest (a module imports another, an inner
``jit`` is traced inside an outer one), so every total here is the
UNION of the intervals, never their sum.
"""

from __future__ import annotations

import time

from ddp_tpu.obs.tracer import Tracer

COMPILE_KINDS = ("compile.trace", "compile.lower", "compile.backend")


def union_s(records: list) -> float:
    """Seconds covered by at least one of the records' intervals."""
    total, end = 0.0, float("-inf")
    for t0, dur in sorted((e[1], e[2]) for e in records):
        t1 = t0 + dur
        if t1 > end:
            total += t1 - max(t0, end)
            end = t1
    return total


def programs(records: list) -> dict:
    """fun_name -> {trace_s, lower_s, backend_s, compiles, cache_hits}
    over the ``compile.*`` records, the dearest program first. One name
    may compile more than once (a chunk program per width): summed."""
    out: dict = {}
    for name, _, dur, _, nums in records:
        if name not in COMPILE_KINDS or not nums:
            continue
        p = out.setdefault(nums[0], {
            "trace_s": 0.0, "lower_s": 0.0, "backend_s": 0.0,
            "compiles": 0, "cache_hits": 0,
        })
        p[name.split(".")[1] + "_s"] += dur
        if name == "compile.backend":
            p["compiles"] += 1
            p["cache_hits"] += int(nums[1]) if len(nums) > 1 else 0
    return dict(sorted(
        out.items(),
        key=lambda kv: -(kv[1]["trace_s"] + kv[1]["lower_s"]
                         + kv[1]["backend_s"]),
    ))


def startup_line(tracer: Tracer) -> str:
    """Total (first kept record to now), imports, state, warm-up, and
    the three dearest programs with their trace / lower / backend
    seconds and cache hits over executables."""
    records = tracer.startup()
    if not records:
        return "start-up: nothing recorded"

    def of(name):
        return union_s([e for e in records if e[0] == name])

    progs = programs(records)
    compiles = sum(p["compiles"] for p in progs.values())
    hits = sum(p["cache_hits"] for p in progs.values())
    dearest = ", ".join(
        f"{name} {p['trace_s']:.2f}/{p['lower_s']:.2f}/{p['backend_s']:.2f}"
        for name, p in list(progs.items())[:3]
    )
    return (
        f"start-up {time.perf_counter() - records[0][1]:.1f}s: "
        f"imports {of('startup.import'):.1f}s, "
        f"state {of('startup.state'):.1f}s, "
        f"warm-up {of('startup.warmup'):.1f}s; "
        f"compiles trace {of('compile.trace'):.1f}s "
        f"lower {of('compile.lower'):.1f}s "
        f"backend {of('compile.backend'):.1f}s, "
        f"{hits}/{compiles} cache hits; "
        f"dearest (trace/lower/backend s): {dearest or 'none'}"
    )
