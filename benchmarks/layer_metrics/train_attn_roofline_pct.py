"""The flash kernels' share of their roofline (compute-bound at these
shapes): the causal attention work a step needs, forward and backward,
over the chip's bf16 peak, over the device time of the Pallas kernels in the
traced steps (a train step holds no other: flash forward, dq, dkv). The backward kernels recompute the
scores; that is not counted as work."""

from benchmarks.harness import flops, peaks
from benchmarks.harness import trace as btrace

NAME = "train_attn_roofline_pct"
UNIT = "%"
LAYER = "Kernels"
MOVES = "train_tokens_per_s_per_chip"
SOURCE = "device_trace"


def read(run):
    steps = run.counters.get("traced_steps")
    sizes = run.counters.get("sizes")
    if run.trace is None or not steps or not sizes:
        return None
    kernel_s = btrace.seconds_where(run.trace, btrace.is_kernel) / steps
    if kernel_s <= 0:
        return None
    need = flops.attention_train_flops_per_token(
        seq_len=sizes["seq_len"], d_model=sizes["d_model"],
        depth=sizes["depth"],
    ) * run.counters["tokens_per_step_per_chip"]
    peak = peaks.peak_for(run.device["kind"]).bf16_flops_per_s
    return need / peak / kernel_s * 100.0
