"""Forwards a committed token costs: the engine's lane-forwards of
generating lanes over the tokens it committed, in the timed window
(counters ``block_forwards_total`` / ``tokens_committed_total``). Under
the static schedule the lengths fix it: a block of B costs
``denoise_steps`` + 1 forwards, a first block that a prompt's last r
tokens open costs B - r + 1, and the surplus of a last block is run but
not counted as tokens. Prompts of 126 and answers of 256 at B = 4, 4
steps: (64 x 5 + 3) / 256 = 1.26."""

from benchmarks.layer_metrics import _bd_common as bd

NAME = "serve_bd_forwards_per_token"
UNIT = "forwards/token"
LAYER = "Serve engine"
MOVES = "serve_tokens_per_s"
SOURCE = "program_counter"


def read(run):
    d = bd.delta(run, "timed")
    if not d or not d.get("tokens_committed_total"):
        return None
    return d["block_forwards_total"] / d["tokens_committed_total"]
