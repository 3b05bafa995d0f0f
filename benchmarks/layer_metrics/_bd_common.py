"""What the block-diffusion cell's readers share: the difference of the
engine's ``block_stats()`` counters over a window (``run.counters``
holds a (before, after) pair for the timed and for the traced window),
and the device seconds of Pallas kernels by the name the program gave
them. Everything returns None where the program has no such counter or
kernel, as a program from before this model has not."""

from benchmarks.harness import trace as btrace

GROUPED = ("moe_grouped_gate_up", "moe_grouped_down")
DECODE = ("flash_decode",)


def delta(run, window: str) -> dict | None:
    pair = run.counters.get(f"block_counts_{window}")
    if not pair:
        return None
    before, after = pair
    return {k: after[k] - before[k] for k in after
            if isinstance(after[k], (int, float))
            and not isinstance(after[k], bool)}


def _picks(names: tuple):
    classes = {"custom-call:" + n for n in names}
    return lambda e: btrace.is_kernel(e) and btrace.op_class(e) in classes


def kernel_seconds(run, names: tuple) -> float | None:
    if run.trace is None:
        return None
    s = btrace.seconds_where(run.trace, _picks(names))
    return s if s > 0 else None


def kernel_events(run, name: str) -> int:
    """How many times the kernel ``name`` ran on the first device
    inside the traced window."""
    tr = run.trace
    devs = tr.devices() if tr is not None else []
    if not devs:
        return 0
    lo, hi = tr.window_ns
    pick = _picks((name,))
    return sum(
        1 for e in tr.device_ops
        if e[1] == "ops" and e[0] == devs[0] and pick(e)
        and lo <= e[5] and e[5] + e[6] <= hi
    )


def share_of_busy(run, names: tuple) -> float | None:
    s = kernel_seconds(run, names)
    if s is None or "block_slots" not in run.counters:
        return None
    busy = btrace.busy(run.trace)["busy_s"]
    return s / busy * 100.0 if busy > 0 else None
