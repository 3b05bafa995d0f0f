"""The flash-decode kernel's share of device busy time: the Pallas
kernels' device seconds over all operations' (a serving step holds no
other Pallas kernel at decode widths). Its roofline share in bytes
needs the tokens each step attends, which no counter gives yet."""

from benchmarks.harness import trace as btrace

NAME = "serve_decode_attn_pct"
UNIT = "%"
LAYER = "Kernels"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"


def read(run):
    if run.trace is None or "slots" not in run.counters:
        return None
    busy = btrace.busy(run.trace)["busy_s"]
    if busy <= 0:
        return None
    return btrace.seconds_where(run.trace, btrace.is_kernel) / busy * 100.0
