"""Device milliseconds of one execution of a GLM-5 prefill program (up
to 2,048 prompt positions of one lane through 7 layers: index scores
against the lane's rows below the chunk's end, the threshold of each
query's 2,048 best, expanded attention under that mask by blocks of
keys, the experts over 2,048 tokens): the executions named
``jit_serve_prefill_first`` and ``jit_serve_prefill_chunk`` in the
profiler's trace, mean."""

from benchmarks.layer_metrics import _gd_common as gd

NAME = "serve_gd_prefill_dev_ms_per_chunk"
UNIT = "ms"
LAYER = "Decode and prefill programs"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"


def read(run):
    return gd.module_ms(run, r"^jit_serve_prefill_")
