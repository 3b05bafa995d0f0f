"""Device milliseconds of one execution of a prefill program of the
hybrid model (up to 256 prompt tokens of one lane through the chunked
scan and the lane's attention, state carried from the chunk before):
the executions named ``jit_serve_prefill_first`` and
``jit_serve_prefill_chunk`` in the profiler's trace, mean."""

from benchmarks.layer_metrics import _hy_common as hy

NAME = "serve_hy_prefill_dev_ms_per_chunk"
UNIT = "ms"
LAYER = "Decode and prefill programs"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"


def read(run):
    return hy.module_ms(run, r"^jit_serve_prefill_")
