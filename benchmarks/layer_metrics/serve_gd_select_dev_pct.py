"""The selection's share of device busy time in the GLM-5 cell: the
operations XLA compiled from the program's ``dsa_index`` scope (the
indexer's dots, ReLU and weighted sum over a lane's stored rows, in a
decode step and in a chunk) and its ``dsa_select`` scope (a decode
step's top-2,048, a chunk's radix select of each query's threshold),
over everything that ran on the device in the traced window. The work
that exists only because attention is sparse."""

from benchmarks.layer_metrics import _gd_common as gd

NAME = "serve_gd_select_dev_pct"
UNIT = "%"
LAYER = "Kernels"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"


def read(run):
    return gd.scope_share_of_busy(run, ("dsa_index", "dsa_select"))
