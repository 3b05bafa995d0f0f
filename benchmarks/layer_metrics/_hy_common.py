"""What the hybrid (state-space and attention) cell's readers share: the
difference of the engine's recurrent-lane counters over a window
(``run.counters`` holds a (before, after) pair for the timed and for
the traced window), the program's own ``serve.decode`` and
``serve.prefill_chunk`` spans inside the traced blocks, and the device
seconds of Pallas kernels by the name the program gave them (the
block-diffusion readers' arithmetic, imported). Everything returns None
where the program has no such counter, span or kernel, as a program
from before this model has not."""

from benchmarks.harness import program_spans as ps
from benchmarks.harness import trace as btrace
from benchmarks.layer_metrics import _bd_common as bd

UPDATE = ("ssm_state_update",)
DECODE = ("flash_decode",)
kernel_seconds = bd.kernel_seconds
kernel_events = bd.kernel_events


def is_hybrid(run) -> bool:
    return "hybrid_slots" in run.counters


def delta(run, window: str) -> dict | None:
    pair = run.counters.get(f"hybrid_counts_{window}")
    if not pair:
        return None
    before, after = pair
    return {k: after[k] - before[k] for k in after if k.endswith("_total")}


def traced_spans(run, name: str) -> list:
    """The program's spans ``name`` that lie inside a traced block."""
    return [e for e in ps.inside(ps.ring(), ps.blocks_of(run, True))
            if e[0] == name]


def share_of_busy(run, names: tuple) -> float | None:
    if not is_hybrid(run):
        return None
    s = kernel_seconds(run, names)
    if s is None:
        return None
    busy = btrace.busy(run.trace)["busy_s"]
    return s / busy * 100.0 if busy > 0 else None


def module_ms(run, pattern: str) -> float | None:
    """Mean device milliseconds of the program executions whose name
    matches, in the traced window."""
    if run.trace is None or not is_hybrid(run):
        return None
    mods = btrace.modules(run.trace, pattern)
    if not mods:
        return None
    return sum(e[6] for e in mods) / len(mods) / 1e6
