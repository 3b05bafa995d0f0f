"""Latent attention's share of device busy time in the GLM-5 cell: the
operations XLA compiled from the program's ``mla_decode`` scope (the
gather of the selected latent rows, the absorbed scores, softmax and
value product of a decode step) and its ``mla_prefill`` scope (a
chunk's key and value expansion, masked scores and online softmax by
blocks of keys; the scopes of the selection inside it are counted
there, not here), over everything that ran on the device in the traced
window."""

from benchmarks.layer_metrics import _gd_common as gd

NAME = "serve_gd_attn_dev_pct"
UNIT = "%"
LAYER = "Kernels"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"


def read(run):
    return gd.scope_share_of_busy(run, ("mla_decode", "mla_prefill"))
