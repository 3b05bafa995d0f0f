"""Device milliseconds of one execution of a prefill program, from the
profiler's trace: the executions named ``serve_prefill_*`` (first chunk
and continuation), mean. Nothing where the programs carry no names of
their own."""

from benchmarks.harness import trace as btrace

NAME = "serve_prefill_dev_ms_per_chunk"
UNIT = "ms"
LAYER = "Decode and prefill programs"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"


def read(run):
    if run.trace is None:
        return None
    mods = btrace.modules(run.trace, r"^jit_serve_prefill_")
    if not mods:
        return None
    return sum(e[6] for e in mods) / len(mods) / 1e6
