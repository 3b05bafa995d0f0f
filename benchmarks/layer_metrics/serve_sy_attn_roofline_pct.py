"""The decode attention kernel's share of its roofline in the SambaY
cell: the bytes its calls NEED (``harness/sambay_flops.py``: each
attended row once a reading layer, from the ring and shared rows the
program's ``serve.decode_rows`` records of the traced blocks carry, one
a decode step), over the
HBM's peak, over the kernel's device time, both a call. Bytes-bound by
far (24 operations a byte at most, against the chip's 240). A kernel
that read a lane's unattended rows, or an idle lane's, would fall below
its share."""

from benchmarks.harness import sambay_flops as sf
from benchmarks.layer_metrics import _sy_common as sy

NAME = "serve_sy_attn_roofline_pct"
UNIT = "%"
LAYER = "Kernels"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"


def read(run):
    if not sy.is_sambay(run):
        return None
    decodes = sy.traced_spans(run, "serve.decode_rows")
    ran = sy.kernel_events(run, sy.DECODE[0])
    if not decodes or not ran:
        return None
    sizes = run.counters["sizes"]
    n = sf.counts(sizes)
    rows = sum(e[4][0] + e[4][1] for e in decodes)
    # calls the records account for: one a reading layer a step
    calls = len(decodes) * (n["window"] + 1 + n["cross"])
    least = sy.least_seconds(
        run, sf.decode_attn_bytes(sizes, rows) / calls,
        rows * sf.attn_row_flops(sizes) / calls)
    return sy.kernel_roofline_pct(run, sy.DECODE, least)
