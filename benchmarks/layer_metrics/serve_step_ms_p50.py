"""Wall milliseconds per engine step: each block's seconds over the
steps the engine counted in it, median over the window's blocks."""

import statistics

NAME = "serve_step_ms_p50"
UNIT = "ms"
LAYER = "Serve engine"
MOVES = "serve_tokens_per_s"
SOURCE = "program_counter"


def read(run):
    per = [b.seconds / b.steps * 1e3 for b in run.blocks
           if not b.traced and b.steps and "active" in b.extra]
    return statistics.median(per) if per else None
