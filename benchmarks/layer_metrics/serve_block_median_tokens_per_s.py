"""The median of the ~2 s blocks' token rates: the engine's pace in a
typical block. It swings with how many blocks hold prefill and leaves
out what stalls cost, so it is a diagnostic beside the end-to-end rate
(all tokens over all time), not a replacement."""

NAME = "serve_block_median_tokens_per_s"
UNIT = "tokens/s"
LAYER = "Serve engine"
MOVES = "serve_tokens_per_s"
SOURCE = "program_counter"


def read(run):
    if "slots" not in run.counters:
        return None
    return run.window.get("median_block_rate")
