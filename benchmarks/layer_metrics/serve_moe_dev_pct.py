"""The routed experts' share of device busy time: the device seconds of
the two grouped-matmul kernels (``moe_grouped_gate_up``,
``moe_grouped_down``) over all operations'. The sort, the gathers into
and out of the padded layout and the router are XLA's and not in it."""

from benchmarks.layer_metrics import _bd_common as bd

NAME = "serve_moe_dev_pct"
UNIT = "%"
LAYER = "Kernels"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"


def read(run):
    return bd.share_of_busy(run, bd.GROUPED)
