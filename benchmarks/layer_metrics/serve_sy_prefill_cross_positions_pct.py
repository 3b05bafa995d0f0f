"""Prompt positions that went through the cross-decoder, as a share of
those that went through the self-decoder, over the timed window: the
engine's ``prefill_cross_positions_total`` over
``prefill_self_positions_total``. About 0.1 where prefill stops at the
shared K/V layer (one position a request); 100 where every layer runs
at every position."""

from benchmarks.layer_metrics import _sy_common as sy

NAME = "serve_sy_prefill_cross_positions_pct"
UNIT = "%"
LAYER = "Serve engine"
MOVES = "serve_tokens_per_s"
SOURCE = "program_counter"


def read(run):
    d = sy.delta(run, "timed")
    if not d or not d.get("prefill_self_positions_total"):
        return None
    return (d.get("prefill_cross_positions_total", 0)
            / d["prefill_self_positions_total"] * 100.0)
