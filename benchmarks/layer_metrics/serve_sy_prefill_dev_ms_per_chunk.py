"""Device milliseconds of one execution of a prefill program of the
SambaY model (up to 512 prompt tokens of one lane through the
self-decoder: 9 selective scans, 8 window attentions over ring and
chunk, the full layer over the lane; the cross-decoder at one position
where the chunk samples): the executions named
``jit_serve_prefill_first`` and ``jit_serve_prefill_chunk`` in the
profiler's trace, mean."""

from benchmarks.layer_metrics import _sy_common as sy

NAME = "serve_sy_prefill_dev_ms_per_chunk"
UNIT = "ms"
LAYER = "Decode and prefill programs"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"


def read(run):
    return sy.module_ms(run, r"^jit_serve_prefill_")
