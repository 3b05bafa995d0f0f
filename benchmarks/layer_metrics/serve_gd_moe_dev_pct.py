"""The grouped expert kernels' share of device busy time in the GLM-5
cell: ``moe_grouped_gate_up`` (a column block of the expert width at a
time at these widths) and ``moe_grouped_down`` over the pairs routed to
the 16 held experts, over everything that ran on the device in the
traced window. The router, the sort into tiles and the shared expert
are XLA's and are not in it."""

from benchmarks.layer_metrics import _gd_common as gd

NAME = "serve_gd_moe_dev_pct"
UNIT = "%"
LAYER = "Kernels"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"


def read(run):
    return gd.share_of_busy(run, gd.GROUPED)
