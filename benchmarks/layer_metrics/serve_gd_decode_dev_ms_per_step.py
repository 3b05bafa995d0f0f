"""Device milliseconds of one execution of the GLM-5 decode program (16
lanes one token through 7 layers: the projections, the index scores of
every stored row of a lane, the top-2,048, the gathered latent rows
under absorbed attention, the dense or the shared and held experts,
the head): the executions named ``jit_serve_decode`` in the profiler's
trace, mean."""

from benchmarks.layer_metrics import _gd_common as gd

NAME = "serve_gd_decode_dev_ms_per_step"
UNIT = "ms"
LAYER = "Decode and prefill programs"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"


def read(run):
    return gd.module_ms(run, r"^jit_serve_decode")
