"""Device milliseconds of one execution of the hybrid model's decode
program (every live lane one token: 36 state updates, 4 banded
attentions, the weights once): the executions named
``jit_serve_decode`` in the profiler's trace, mean."""

from benchmarks.layer_metrics import _hy_common as hy

NAME = "serve_hy_decode_dev_ms_per_step"
UNIT = "ms"
LAYER = "Decode and prefill programs"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"


def read(run):
    return hy.module_ms(run, r"^jit_serve_decode")
