"""The whole traced window's share of its roofline in the GLM-5 cell:
for every decode step and every prefill chunk the program ran, the
least time the chip could take for what that execution had to do (the
larger of its needed bytes over the HBM's peak and its needed
operations over the bf16 peak, ``harness/glm_dsa_flops.py``), summed,
over the device's busy time in the window.

What an execution had to do comes from the program's own spans inside
the traced blocks: a ``serve.decode_selected`` record (one a decode
step) gives the rows the indexer scored, the rows attention read and
the LIVE lanes (weights once, of the held experts those the live lanes
are expected to hit; rows as counted); a ``serve.chunk_selected``
record (one a prefill chunk) gives the same two counts over the
chunk's REAL positions, how many those are, where the chunk starts and
whether it sampled (padding is never counted).
Attention is counted SPARSE: ``min(t + 1, 2,048)`` rows a query. A
program that walks a lane's unselected rows under a mask pays for it
here. Spans that lie inside the blocks are a subset of what the device
ran in the window, so the share cannot pass 100 unless the counts are
wrong."""

from benchmarks.harness import glm_dsa_flops as gf
from benchmarks.harness import trace as btrace
from benchmarks.layer_metrics import _gd_common as gd

NAME = "serve_gd_window_mfu_pct"
UNIT = "%"
LAYER = "Decode and prefill programs"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"


def read(run):
    if run.trace is None or not gd.is_glm(run):
        return None
    decodes = gd.traced_spans(run, "serve.decode_selected")
    chunks = gd.traced_spans(run, "serve.chunk_selected")
    busy = btrace.busy(run.trace)["busy_s"]
    if not decodes or busy <= 0:
        return None
    sizes = run.counters["sizes"]
    total = 0.0
    for e in decodes:
        scored, selected, live = e[4]
        work = dict(live_lanes=live, rows_scored=scored,
                    rows_selected=selected)
        total += gd.least_seconds(run, gf.decode_step_bytes(sizes, **work),
                                  gf.decode_step_flops(sizes, **work))
    for e in chunks:
        scored, selected, tokens, start, final = e[4]
        final = bool(final)
        total += gd.least_seconds(
            run,
            gf.prefill_chunk_bytes(sizes, tokens=tokens, start=start,
                                   final=final),
            gf.prefill_chunk_flops(sizes, tokens=tokens, start=start,
                                   final=final, rows_scored=scored,
                                   rows_selected=selected))
    return total / busy * 100.0
