"""The decode kernel's share of device busy time in the block-diffusion
cell: ``flash_decode`` with a block's queries folded into its group
dimension (32 rows a kv head), on the stored cache."""

from benchmarks.layer_metrics import _bd_common as bd

NAME = "serve_bd_attn_pct"
UNIT = "%"
LAYER = "Kernels"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"


def read(run):
    return bd.share_of_busy(run, bd.DECODE)
