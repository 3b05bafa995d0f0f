"""The prefill scan kernel's share of its roofline: the larger of the
bytes one call of ``selective_scan`` NEEDS over the HBM's peak and its
operations over the bf16 peak (``harness/sambay_flops.py``: a chunk's
REAL positions, from the engine's ``ssm_prefill_tokens_total`` over the
``serve.prefill_chunk`` spans of the traced blocks), over the device
time the kernel takes a call. The kernel's arithmetic is the VPU's and
the EUP's (an exponential a state element), not the MXU's, so a share
of the bf16 peak is out of reach by construction: what it reads is
mostly the bytes."""

from benchmarks.harness import sambay_flops as sf
from benchmarks.layer_metrics import _sy_common as sy

NAME = "serve_sy_scan_roofline_pct"
UNIT = "%"
LAYER = "Kernels"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"


def read(run):
    if not sy.is_sambay(run):
        return None
    d = sy.delta(run, "traced")
    chunks = len(sy.traced_spans(run, "serve.prefill_chunk"))
    if not d or not chunks or not d.get("ssm_prefill_tokens_total"):
        return None
    sizes = run.counters["sizes"]
    tokens = d["ssm_prefill_tokens_total"] / chunks
    least = sy.least_seconds(run, sf.scan_bytes(sizes, tokens),
                             sf.scan_flops(sizes, tokens))
    return sy.kernel_roofline_pct(run, sy.SCAN, least)
