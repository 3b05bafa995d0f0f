"""The per-element state-update kernel's share of its roofline: the
bytes one call of ``selective_state_update`` NEEDS
(``harness/sambay_flops.py``: each LIVE lane's state of one layer once
in and once out, its vectors, ``A`` once), over the HBM's peak, over
the device time the kernel takes a call. The live lanes a step are the
engine's ``ssm_lane_updates_total`` over the ``serve.decode`` spans of
the traced blocks. An idle lane's state counts for nothing."""

from benchmarks.harness import sambay_flops as sf
from benchmarks.layer_metrics import _sy_common as sy

NAME = "serve_sy_ssm_update_roofline_pct"
UNIT = "%"
LAYER = "Kernels"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"


def read(run):
    if not sy.is_sambay(run):
        return None
    d = sy.delta(run, "traced")
    steps = len(sy.traced_spans(run, "serve.decode"))
    if not d or not steps or not d.get("ssm_lane_updates_total"):
        return None
    sizes = run.counters["sizes"]
    live = d["ssm_lane_updates_total"] / steps
    least = sy.least_seconds(run, sf.ssm_update_bytes(sizes, live),
                             sf.ssm_update_flops(sizes, live))
    return sy.kernel_roofline_pct(run, sy.UPDATE, least)
