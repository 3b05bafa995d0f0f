"""Device milliseconds of one execution of the decode program, from the
profiler's trace. The engine's programs share one name, so the decode
program is found by what it holds: the executions inside which the
flash-decode Pallas kernel ran (prefill chunks hold none)."""

from benchmarks.harness import trace as btrace

NAME = "serve_decode_dev_ms_per_step"
UNIT = "ms"
LAYER = "Decode and prefill programs"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"


def read(run):
    if run.trace is None or "slots" not in run.counters:
        return None
    mods = btrace.modules_with(run.trace, btrace.is_kernel)
    if not mods:
        return None
    return sum(e[6] for e in mods) / len(mods) / 1e6
