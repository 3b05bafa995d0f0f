"""The decode attention kernel's share of device busy time in the
hybrid cell: ``flash_decode`` over the 4 attention layers' rows, 4
queries a kv head of 64, two kv heads to a 128-lane group of the
stored rows."""

from benchmarks.layer_metrics import _hy_common as hy

NAME = "serve_hy_attn_pct"
UNIT = "%"
LAYER = "Kernels"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"


def read(run):
    return hy.share_of_busy(run, hy.DECODE)
