"""What the readers of ``setup_s``'s layers share: the program's own
account of its start.

The program keeps a record of each phase between the process's start
and its first productive step, and of every JAX compile event by the
program's name, in a store that outlives its ring
(``ddp_tpu.obs.tracer.get_tracer().startup()``: ``(name, t0, dur,
parent, nums)`` with ``t0`` on ``time.perf_counter``, the clock
``Block.start`` is on; ``obs/tracer.SPAN_NUMS`` says what ``nums``
hold). A reader takes the records that END before the run's first
block opens: what came later is the window's, not set-up's.

Records nest (a module imports another; an inner ``jit`` is traced
inside an outer one), so a total is the UNION of the intervals, never
their sum. The ``# setup`` line's ``trace_s`` is a sum.

A program without the store (the parent of the PR that brought it), or
with nothing in it, gives None here: never 0, and no reader raises.
"""

from __future__ import annotations

from benchmarks.harness.result import emit

UNIT = "s"
LAYER = "CLI / launcher, runtime"
MOVES = "setup_s"
SOURCE = "program_span"

COMPILE = ("compile.trace", "compile.lower", "compile.backend")


def _tracer():
    try:
        from ddp_tpu.obs.tracer import get_tracer

        return get_tracer()
    except ImportError:
        return None


def window_open(run) -> float | None:
    """Where the run's first block, traced or not, opens."""
    return run.blocks[0].start if run.blocks else None


def kept(run) -> list:
    """The program's kept records that end before the window opens,
    oldest first; [] where the program keeps none."""
    tracer, opens = _tracer(), window_open(run)
    if opens is None or not hasattr(tracer, "startup"):
        return []
    return [e for e in tracer.startup() if e[1] + e[2] <= opens]


def named(run, name: str) -> list:
    return [e for e in kept(run) if e[0] == name]


def union(intervals: list) -> float:
    """Seconds covered by at least one of the ``(start, end)`` pairs."""
    total, reach = 0.0, float("-inf")
    for start, end in sorted(intervals):
        if end > reach:
            total += end - max(start, reach)
            reach = end
    return total


def union_s(records: list) -> float | None:
    """Seconds at least one of the records covers; None for none."""
    if not records:
        return None
    return union([(e[1], e[1] + e[2]) for e in records])


def dearest(seconds: dict, n: int = 3) -> dict:
    return dict(sorted(seconds.items(), key=lambda kv: -kv[1])[:n])


def programs(run) -> dict:
    """fun_name -> trace_s, lower_s, backend_s, compiles, cache_hits
    over the compile records, the dearest program first. A name that
    compiles more than once (a chunk program per width) is summed."""
    out: dict = {}
    for name, _, dur, _, nums in kept(run):
        if name not in COMPILE or not nums:
            continue
        p = out.setdefault(str(nums[0]), {
            "trace_s": 0.0, "lower_s": 0.0, "backend_s": 0.0,
            "compiles": 0, "cache_hits": 0})
        p[name.split(".")[1] + "_s"] += dur
        if name == "compile.backend":
            p["compiles"] += 1
            p["cache_hits"] += int(nums[1]) if len(nums) > 1 else 0
    cost = lambda p: p["trace_s"] + p["lower_s"] + p["backend_s"]  # noqa: E731
    return dict(sorted(out.items(), key=lambda kv: -cost(kv[1])))


def spanned_share(run) -> float | None:
    """The share of ``[window open - setup_s, window open]`` that at
    least one of the program's records covers, kept store and ring."""
    tracer, opens = _tracer(), window_open(run)
    setup_s = run.end_to_end.get("setup_s")
    if opens is None or not setup_s or not hasattr(tracer, "startup"):
        return None
    kept_records = tracer.startup()
    if not kept_records:
        return None
    lo = opens - setup_s
    cut = [(max(lo, e[1]), min(opens, e[1] + e[2]))
           for e in kept_records + tracer.ring()]
    return union([(a, b) for a, b in cut if b > a]) / setup_s


def refused() -> int | None:
    """Records the program's store was too full to keep: over 0, the
    start is not all there and the store wants widening."""
    return getattr(_tracer(), "startup_refused", None)


def say(metric: str, value, detail: dict):
    """The reader's ``#`` line beside its number."""
    if value is not None:
        emit("setup_span", {"metric": metric, "value": value, **detail})
    return value
