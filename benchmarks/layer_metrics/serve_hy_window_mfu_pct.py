"""The whole traced window's share of its roofline in the hybrid cell:
for every decode step and every prefill chunk the program ran, the
least time the chip could take for what that execution had to do (the
larger of its needed bytes over the HBM's peak and its needed
operations over the bf16 peak, ``harness/granite_flops.py``), summed,
over the device's busy time in the window.

What an execution had to do comes from the program's own spans inside
the traced blocks: a ``serve.decode`` span gives its live lanes and the
K/V rows they attended (weights once, live state once in and once out,
rows attended only); a ``serve.prefill_chunk`` span gives its start,
and the real positions a chunk held are the engine's
``ssm_prefill_tokens_total`` over the chunks (padding is never
counted). Spans that lie inside the blocks are a subset of what the
device ran in the window, and means of a convex bound underestimate
it, so the share cannot pass 100 unless the counts are wrong."""

from benchmarks.harness import granite_flops as gf
from benchmarks.harness import peaks
from benchmarks.harness import trace as btrace
from benchmarks.layer_metrics import _hy_common as hy

NAME = "serve_hy_window_mfu_pct"
UNIT = "%"
LAYER = "Decode and prefill programs"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"


def read(run):
    if run.trace is None or not hy.is_hybrid(run):
        return None
    d = hy.delta(run, "traced")
    decodes = hy.traced_spans(run, "serve.decode")
    chunks = hy.traced_spans(run, "serve.prefill_chunk")
    busy = btrace.busy(run.trace)["busy_s"]
    if not d or not decodes or busy <= 0:
        return None
    sizes = run.counters["sizes"]
    peak = peaks.peak_for(run.device["kind"])

    def least(nbytes, flops):
        return max(nbytes / peak.hbm_bytes_per_s,
                   flops / peak.bf16_flops_per_s)

    total = 0.0
    for e in decodes:
        lanes, rows = e[4][0], e[4][1]
        total += least(
            gf.decode_step_bytes(sizes, live_lanes=lanes, rows_attended=rows),
            gf.decode_step_flops(sizes, live_lanes=lanes, rows_attended=rows),
        )
    if chunks:
        tokens = d.get("ssm_prefill_tokens_total", 0) / len(chunks)
        for e in chunks:
            start = e[4][2]
            total += least(
                gf.prefill_chunk_bytes(sizes, tokens=tokens, start=start),
                gf.prefill_chunk_flops(sizes, tokens=tokens, start=start),
            )
    return total / busy * 100.0
