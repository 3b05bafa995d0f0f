"""The whole traced window's share of its roofline in the SambaY cell:
for every decode step and every prefill chunk the program ran, the
least time the chip could take for what that execution had to do (the
larger of its needed bytes over the HBM's peak and its needed
operations over the bf16 peak, ``harness/sambay_flops.py``), summed,
over the device's busy time in the window.

What an execution had to do comes from the program's own spans inside
the traced blocks: a ``serve.decode_rows`` record (one a decode step)
gives the ring and shared rows the step attended and its LIVE lanes
(weights once, live state once in and once out, rows attended only); a ``serve.prefill_chunk`` span gives
its start and whether it sampled (then, and only then, the
cross-decoder's weights and the head count), and the real positions a
chunk held are the engine's ``prefill_self_positions_total`` over the
chunks (padding is never counted). Spans that lie inside the blocks are
a subset of what the device ran in the window, so the share cannot pass
100 unless the counts are wrong."""

from benchmarks.harness import sambay_flops as sf
from benchmarks.harness import trace as btrace
from benchmarks.layer_metrics import _sy_common as sy

NAME = "serve_sy_window_mfu_pct"
UNIT = "%"
LAYER = "Decode and prefill programs"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"


def read(run):
    if run.trace is None or not sy.is_sambay(run):
        return None
    d = sy.delta(run, "traced")
    decodes = sy.traced_spans(run, "serve.decode_rows")
    chunks = sy.traced_spans(run, "serve.prefill_chunk")
    busy = btrace.busy(run.trace)["busy_s"]
    if not d or not decodes or busy <= 0:
        return None
    sizes = run.counters["sizes"]
    total = 0.0
    for e in decodes:
        ring_rows, shared_rows, live_lanes = e[4]
        work = dict(live_lanes=live_lanes, ring_rows=ring_rows,
                    shared_rows=shared_rows)
        total += sy.least_seconds(run, sf.decode_step_bytes(sizes, **work),
                                  sf.decode_step_flops(sizes, **work))
    if chunks:
        tokens = d.get("prefill_self_positions_total", 0) / len(chunks)
        for e in chunks:
            work = dict(tokens=tokens, start=e[4][2], final=bool(e[4][4]))
            total += sy.least_seconds(
                run, sf.prefill_chunk_bytes(sizes, **work),
                sf.prefill_chunk_flops(sizes, **work))
    return total / busy * 100.0
