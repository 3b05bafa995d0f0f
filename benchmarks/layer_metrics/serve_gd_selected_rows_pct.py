"""Of the stored rows the indexer scored in the timed window, the share
attention then read: the engine's ``dsa_rows_selected_total`` over its
``dsa_rows_scored_total`` (a query at position t scores t + 1 rows a
layer and attends min(t + 1, 2,048)). 100 would mean no query had more
than 2,048 rows to choose from: the selection would not be live."""

from benchmarks.layer_metrics import _gd_common as gd

NAME = "serve_gd_selected_rows_pct"
UNIT = "%"
LAYER = "Serve engine"
MOVES = "serve_tokens_per_s"
SOURCE = "program_counter"


def read(run):
    return gd.ratio_pct(run, "dsa_rows_selected_total",
                        "dsa_rows_scored_total")
