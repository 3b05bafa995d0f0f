"""The median of the fenced blocks' rates: the pace of the train step
with no host stall in it. A steadier statistic beside the end-to-end
rate (all work over all time): where the two part, ``train_stall_pct``
says by how much and the block series in the run's file says where."""

NAME = "train_block_median_tokens_per_s_per_chip"
UNIT = "tokens/s/chip"
LAYER = "Train step"
MOVES = "train_tokens_per_s_per_chip"
SOURCE = "host_clock"


def read(run):
    if "timed_steps" not in run.counters:
        return None
    return run.window.get("median_block_rate")
