"""The prefill scan's share of device busy time in the SambaY cell:
the ``selective_scan`` Pallas calls (one a Mamba layer a prefill chunk;
XLA runs each fused with the slice of its padded output, under the
call's name) over everything that ran on the device in the traced window."""

from benchmarks.layer_metrics import _sy_common as sy

NAME = "serve_sy_scan_dev_pct"
UNIT = "%"
LAYER = "Kernels"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"


def read(run):
    return sy.share_of_busy(run, sy.SCAN)
