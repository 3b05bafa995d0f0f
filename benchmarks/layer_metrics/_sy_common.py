"""What the SambaY cell's readers share: the difference of the engine's
lane counters over a window (``run.counters`` holds a (before, after)
pair for the timed and for the traced window), the program's own
``serve.decode`` and ``serve.prefill_chunk`` spans inside the traced
blocks, and the device seconds of Pallas kernels by the name the
program gave them (the block-diffusion readers' arithmetic, imported).
Everything returns None where the program has no such counter, span or
kernel, as a program from before this model has not."""

import re

from benchmarks.harness import peaks
from benchmarks.harness import trace as btrace
from benchmarks.layer_metrics import _bd_common as bd
from benchmarks.layer_metrics import _hy_common as hy

UPDATE = ("selective_state_update",)
SCAN = ("selective_scan",)
DECODE = ("flash_decode",)
kernel_events = bd.kernel_events
traced_spans = hy.traced_spans


def _named(names: tuple):
    """Device operations by the name the program gave a kernel: a
    Pallas call as it stands, or the fusion XLA wrapped around it (the
    scan's call runs fused with the slice of its padded output and
    shows as ``fusion`` ``kCustom`` under the call's own name)."""
    return lambda e: e[1] == "ops" and re.sub(r"(\.\d+)+$", "", e[2]) in names


def named_seconds(run, names: tuple) -> float | None:
    if run.trace is None:
        return None
    s = btrace.seconds_where(run.trace, _named(names))
    return s if s > 0 else None


def named_events(run, names: tuple) -> int:
    """How many such operations ran on the first device inside the
    traced window."""
    tr = run.trace
    devs = tr.devices() if tr is not None else []
    if not devs:
        return 0
    lo, hi = tr.window_ns
    pick = _named(names)
    return sum(1 for e in tr.device_ops if e[0] == devs[0] and pick(e)
               and lo <= e[5] and e[5] + e[6] <= hi)


def is_sambay(run) -> bool:
    return "sambay_slots" in run.counters


def delta(run, window: str) -> dict | None:
    pair = run.counters.get(f"sambay_counts_{window}")
    if not pair:
        return None
    before, after = pair
    return {k: after[k] - before[k] for k in after if k.endswith("_total")}


def share_of_busy(run, names: tuple) -> float | None:
    if not is_sambay(run):
        return None
    s = named_seconds(run, names)
    if s is None:
        return None
    busy = btrace.busy(run.trace)["busy_s"]
    return s / busy * 100.0 if busy > 0 else None


def module_ms(run, pattern: str) -> float | None:
    """Mean device milliseconds of the program executions whose name
    matches, in the traced window."""
    if run.trace is None or not is_sambay(run):
        return None
    mods = btrace.modules(run.trace, pattern)
    if not mods:
        return None
    return sum(e[6] for e in mods) / len(mods) / 1e6


def least_seconds(run, nbytes: float, flops: float) -> float:
    """The least time the chip could take: the larger of the bytes over
    the HBM's peak and the operations over the bf16 peak."""
    peak = peaks.peak_for(run.device["kind"])
    return max(nbytes / peak.hbm_bytes_per_s, flops / peak.bf16_flops_per_s)


def kernel_roofline_pct(run, names: tuple, least_per_call) -> float | None:
    """A kernel's share of its roofline: ``least_per_call`` seconds a
    call over the device time the kernel takes a call (means over the
    traced window)."""
    if not is_sambay(run):
        return None
    spent = named_seconds(run, names)
    ran = named_events(run, names)
    if spent is None or not ran or least_per_call is None:
        return None
    return least_per_call / (spent / ran) * 100.0
