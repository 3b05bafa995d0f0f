"""Of the (token, chosen expert) pairs the routed layers made in the
timed window, the share whose expert this chip holds: the engine's
``moe_pairs_held_total`` over its ``moe_pairs_routed_total``. 16 of 256
experts held and a router that favours none: about 6.25."""

from benchmarks.layer_metrics import _gd_common as gd

NAME = "serve_gd_pairs_held_pct"
UNIT = "%"
LAYER = "Expert routing"
MOVES = "serve_tokens_per_s"
SOURCE = "program_counter"


def read(run):
    return gd.ratio_pct(run, "moe_pairs_held_total",
                        "moe_pairs_routed_total")
