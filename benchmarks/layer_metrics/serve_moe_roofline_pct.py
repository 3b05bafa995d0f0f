"""The grouped expert kernels' share of their roofline: the least time
the chip could take for what an expert-layer call had to do — the
larger of its bytes over the HBM's peak and its operations over the
bf16 peak (``harness/sdar_flops.py``, from the engine's routed counts:
rows routed, and experts a row reached, whose matrices cross once a
call) — over the device time the two kernels take a call. Both are
means over calls: the counts over the engine's expert-layer calls while
the traced blocks were sampled, the time over the kernel's executions
in the trace (the profiler's session is longer than the sampling and
the two cannot be cut to one interval; a step's and a prefill chunk's
calls mix alike in both). At a few rows an expert the kernels are
bandwidth-bound: the experts' matrices are most of the model."""

from benchmarks.harness import peaks, sdar_flops
from benchmarks.layer_metrics import _bd_common as bd

NAME = "serve_moe_roofline_pct"
UNIT = "%"
LAYER = "Kernels"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"


def read(run):
    d = bd.delta(run, "traced")
    spent = bd.kernel_seconds(run, bd.GROUPED)
    sizes = run.counters.get("sizes")
    if not d or spent is None or not d.get("moe_layer_calls_total"):
        return None
    ran = bd.kernel_events(run, bd.GROUPED[1])
    if not ran:
        return None
    peak = peaks.peak_for(run.device["kind"])
    dm, f = sizes["d_model"], sizes["moe_intermediate"]
    calls = d["moe_layer_calls_total"]
    rows = d["moe_tokens_routed_total"] / calls
    hit = d["moe_experts_hit_total"] / calls
    least = max(
        sdar_flops.moe_kernels_bytes(rows, hit, dm, f) / peak.hbm_bytes_per_s,
        sdar_flops.moe_kernels_flops(rows, dm, f) / peak.bf16_flops_per_s,
    )
    return least / (spent / ran) * 100.0
