"""Model FLOP/s utilization: the operations forward and backward NEED
per token (``flops.py``; causal attention halved, no recomputation)
times the tokens a chip trains per second at the median block's pace
(the train step's own rate; a host stall is the Trainer loop's and is
in ``train_stall_pct``), over the chip's published bf16 peak
(``peaks.py``)."""

from benchmarks.harness import flops, peaks

NAME = "train_mfu_pct"
UNIT = "%"
LAYER = "Train step"
MOVES = "train_tokens_per_s_per_chip"
SOURCE = "host_clock"


def read(run):
    rate = run.window.get("median_block_rate")
    sizes = run.counters.get("sizes")
    if rate is None or not sizes or "timed_steps" not in run.counters:
        return None
    peak = peaks.peak_for(run.device["kind"]).bf16_flops_per_s
    return flops.lm_train_flops_per_token(**sizes) * rate / peak * 100.0
