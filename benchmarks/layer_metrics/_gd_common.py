"""What the GLM-5 cell's readers share: the difference of the engine's
``latent_stats()`` counters over a window (``run.counters`` holds a
(before, after) pair for the timed and for the traced window), the
program's own ``serve.decode``, ``serve.decode_selected`` and
``serve.prefill_chunk`` spans inside the traced blocks, the device
seconds of Pallas kernels by the name the program gave them (the
block-diffusion readers' arithmetic, imported), and the device seconds
of the operations XLA compiled from one of the program's NAMED SCOPES
(``dsa_index``, ``dsa_select``, ``mla_decode``, ``mla_prefill``,
``moe_share``): the profiler names a device operation by its HLO
instruction and keeps no name stack with it (my chip run, PR 43: an
event's stats are its offset and duration), so the driver asks the
program for its compiled text (``ServeEngine.program_hlo()``), whose
instructions carry the ``op_name`` they came from, and
:func:`scope_table` joins the two by instruction name inside each
program execution. Everything returns None where the program has no
such counter, span, kernel or text, as a program from before this model
has not."""

import bisect
import re

from benchmarks.harness import trace as btrace
from benchmarks.layer_metrics import _bd_common as bd
from benchmarks.layer_metrics import _hy_common as hy
from benchmarks.layer_metrics import _sy_common as sy

GROUPED = bd.GROUPED
SCOPES = ("dsa_index", "dsa_select", "mla_decode", "mla_prefill",
          "moe_share")
_SCOPE = re.compile(r"/(" + "|".join(SCOPES) + r")(?=/)")
kernel_seconds = bd.kernel_seconds
kernel_events = bd.kernel_events
traced_spans = hy.traced_spans
least_seconds = sy.least_seconds


def is_glm(run) -> bool:
    return "glm_dsa_slots" in run.counters


def delta(run, window: str) -> dict | None:
    pair = run.counters.get(f"glm_dsa_counts_{window}")
    if not pair:
        return None
    before, after = pair
    return {k: after[k] - before[k] for k in after if k.endswith("_total")}


def ratio_pct(run, over: str, under: str) -> float | None:
    """One counter's growth over another's, in the timed window."""
    d = delta(run, "timed") if is_glm(run) else None
    if not d or not d.get(under):
        return None
    return d[over] / d[under] * 100.0


def innermost_scope(text: str) -> str | None:
    """The last of the program's scopes in a JAX name stack
    (``jit(serve_decode)/.../mla_prefill/dsa_index/dot_general``)."""
    found = _SCOPE.findall(text)
    return found[-1] if found else None


_INST = re.compile(
    r'^\s*(?:ROOT )?%?([\w.\-]+) = .*?metadata=\{[^}]*?op_name="([^"]*)"',
    re.M)


def scope_map(hlo_text: str) -> dict[str, str]:
    """Instruction name -> innermost scope, for the instructions of a
    compiled program whose ``op_name`` lies under one of the program's
    scopes (a fusion carries its root's)."""
    out = {}
    for inst, op_name in _INST.findall(hlo_text):
        scope = innermost_scope(op_name + "/")
        if scope:
            out[inst] = scope
    return out


def scope_maps(program_hlo: dict) -> dict[str, dict]:
    """``ServeEngine.program_hlo()`` -> the scope map of each program
    by the name the profiler gives its executions (``jit_serve_decode``,
    ...). The widths of a chunk program share a name: the widest one's
    map wins where they name an instruction differently (it takes most
    of the time), the others fill what it lacks."""
    def width(name):
        return int(name.partition(":")[2] or 0)

    out: dict[str, dict] = {}
    for name in sorted(program_hlo, key=width):
        module = "jit_" + name.partition(":")[0]
        out.setdefault(module, {}).update(scope_map(program_hlo[name]))
    return out


def scope_table(run) -> dict | None:
    """Device seconds by innermost scope, of the first device's
    operations inside the traced window: each operation is looked up,
    by its instruction name, in the scope map of the program execution
    it ran inside. None where the run carries no maps (a program
    without ``program_hlo``)."""
    maps = run.counters.get("scope_maps") if is_glm(run) else None
    if run.trace is None or not maps:
        return None
    tr = run.trace
    mods = sorted(btrace.modules(tr), key=lambda e: e[5])
    starts = [e[5] for e in mods]
    devs = tr.devices()
    lo, hi = tr.window_ns
    table: dict[str, float] = {}
    for e in tr.device_ops:
        if (e[1] != "ops" or e[0] != devs[0] or e[3] in btrace._CONTAINER
                or e[5] < lo or e[5] + e[6] > hi):
            continue
        i = bisect.bisect_right(starts, e[5]) - 1
        if i < 0 or e[5] >= mods[i][5] + mods[i][6]:
            continue
        scope = maps.get(mods[i][2], {}).get(e[2].lstrip("%"))
        if scope:
            table[scope] = table.get(scope, 0.0) + e[6] / 1e9
    return table or None


def scope_share_of_busy(run, scopes: tuple) -> float | None:
    table = scope_table(run)
    if not table:
        return None
    s = sum(table.get(k, 0.0) for k in scopes)
    busy = btrace.busy(run.trace)["busy_s"]
    return s / busy * 100.0 if s > 0 and busy > 0 else None


def share_of_busy(run, names: tuple) -> float | None:
    if not is_glm(run):
        return None
    s = kernel_seconds(run, names)
    if s is None:
        return None
    busy = btrace.busy(run.trace)["busy_s"]
    return s / busy * 100.0 if busy > 0 else None


def module_ms(run, pattern: str) -> float | None:
    """Mean device milliseconds of the program executions whose name
    matches, in the traced window."""
    if run.trace is None or not is_glm(run):
        return None
    mods = btrace.modules(run.trace, pattern)
    if not mods:
        return None
    return sum(e[6] for e in mods) / len(mods) / 1e6
