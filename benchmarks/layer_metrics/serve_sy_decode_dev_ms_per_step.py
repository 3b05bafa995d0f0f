"""Device milliseconds of one execution of the SambaY decode program
(every live lane one token through all 32 layers: 9 state updates, 8
ring and 8 shared-row attentions, the weights once): the executions
named ``jit_serve_decode`` in the profiler's trace, mean."""

from benchmarks.layer_metrics import _sy_common as sy

NAME = "serve_sy_decode_dev_ms_per_step"
UNIT = "ms"
LAYER = "Decode and prefill programs"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"


def read(run):
    return sy.module_ms(run, r"^jit_serve_decode")
