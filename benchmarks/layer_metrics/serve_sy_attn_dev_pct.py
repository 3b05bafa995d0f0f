"""The decode attention kernel's share of device busy time in the
SambaY cell: ``flash_decode`` over the 8 window layers' rings and, 8
times a step, over the full layer's shared rows; four 128-lane queries
a kv pair (the two maps of two head pairs)."""

from benchmarks.layer_metrics import _sy_common as sy

NAME = "serve_sy_attn_dev_pct"
UNIT = "%"
LAYER = "Kernels"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"


def read(run):
    return sy.share_of_busy(run, sy.DECODE)
