"""The state-update kernel's share of its roofline: the bytes one call
of ``ssm_state_update`` NEEDS (``harness/granite_flops.py``: each LIVE
lane's state of one layer once in and once out, and its vectors), over
the HBM's peak, over the device time the kernel takes a call. The
kernel is bytes-bound by far (5 operations a state element of 8 bytes
moved). Both are means over calls: the live lanes a decode step from
the engine's ``ssm_lane_updates_total`` over the ``serve.decode`` spans
of the traced blocks, the time over the kernel's executions in the
trace. An idle lane's state counts for nothing: a kernel that read it
would fall below its share."""

from benchmarks.harness import granite_flops, peaks
from benchmarks.layer_metrics import _hy_common as hy

NAME = "serve_ssm_update_roofline_pct"
UNIT = "%"
LAYER = "Kernels"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"


def read(run):
    if not hy.is_hybrid(run):
        return None
    d = hy.delta(run, "traced")
    spent = hy.kernel_seconds(run, hy.UPDATE)
    steps = len(hy.traced_spans(run, "serve.decode"))
    if not d or spent is None or not steps:
        return None
    ran = hy.kernel_events(run, hy.UPDATE[0])
    if not ran or not d.get("ssm_lane_updates_total"):
        return None
    sizes = run.counters["sizes"]
    live = d["ssm_lane_updates_total"] / steps
    peak = peaks.peak_for(run.device["kind"])
    least = max(
        granite_flops.ssm_update_bytes(sizes, live) / peak.hbm_bytes_per_s,
        granite_flops.ssm_update_flops(sizes, live) / peak.bf16_flops_per_s,
    )
    return least / (spent / ran) * 100.0
