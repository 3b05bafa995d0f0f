"""Device milliseconds of one execution of a prefill program of the
block-diffusion model (64 prompt tokens of one lane under the
block-causal mask, through every expert they reach): the executions
named ``jit_serve_prefill_*`` in the profiler's trace, mean. The plain
cell's ``serve_prefill_dev_ms_per_chunk`` under this cell's own name,
so that the two programs' numbers never share a series."""

from benchmarks.harness import trace as btrace

NAME = "serve_bd_prefill_dev_ms_per_chunk"
UNIT = "ms"
LAYER = "Decode and prefill programs"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"


def read(run):
    if run.trace is None or "block_slots" not in run.counters:
        return None
    mods = btrace.modules(run.trace, r"^jit_serve_prefill_")
    if not mods:
        return None
    return sum(e[6] for e in mods) / len(mods) / 1e6
