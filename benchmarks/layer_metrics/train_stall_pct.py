"""Share of the window's wall time beyond its blocks at the median
block's pace: what host stalls cost. The end-to-end rate, all work over
all time, pays it; the block median beside it does not."""

NAME = "train_stall_pct"
UNIT = "%"
LAYER = "Trainer loop"
MOVES = "train_tokens_per_s_per_chip"
SOURCE = "host_clock"


def read(run):
    if "stall_share" not in run.window or "timed_steps" not in run.counters:
        return None
    return run.window["stall_share"] * 100.0
