"""The state-update kernel's share of device busy time: the
``ssm_state_update`` Pallas calls (one a Mamba layer a decode step)
over everything that ran on the device in the traced window."""

from benchmarks.layer_metrics import _hy_common as hy

NAME = "serve_ssm_update_dev_pct"
UNIT = "%"
LAYER = "Kernels"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"


def read(run):
    return hy.share_of_busy(run, hy.UPDATE)
