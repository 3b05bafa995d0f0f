"""Device milliseconds of one execution of the block-step program
(forward over every lane's block, choice, unmasking, commit): the
executions named ``jit_serve_block_step`` in the profiler's trace."""

from benchmarks.harness import trace as btrace

NAME = "serve_bd_step_dev_ms"
UNIT = "ms"
LAYER = "Decode and prefill programs"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"


def read(run):
    if run.trace is None:
        return None
    mods = btrace.modules(run.trace, "serve_block_step")
    if not mods:
        return None
    return sum(e[6] for e in mods) / len(mods) / 1e6
