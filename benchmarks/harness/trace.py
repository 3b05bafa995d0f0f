"""From a profiler trace to numbers: the reduction every PR shares.

``record`` wraps a few seconds of a steady window in the JAX profiler;
``load`` turns the ``.xplane.pb`` it wrote into a small plain structure
(``Trace``: device operations and the benchmark's host spans on one
clock); the rest reduce that structure: device busy time as the union
of operation intervals, time by operation class, the time of named
kernels, collective time that no compute hides, and idle gaps named by
what the host was doing. A recorded ``Trace`` (JSON) is kept with the
tests, so the reduction is checked without a chip.
"""

from __future__ import annotations

import bisect
import contextlib
import glob
import json
import os
import re
from dataclasses import dataclass, field

# An event on a device: (device, line, inst, opcode, detail, start_ns,
# dur_ns). ``line`` is "ops" (the profiler's ``XLA Ops``: what ran),
# "async" (``Async XLA Ops``: start-to-done spans of asynchronous copies
# and collectives) or "module" (``XLA Modules``: one event per program
# execution). On the ops lines the profiler names an event by its HLO
# text; ``parse_hlo`` keeps the instruction's name, its opcode and, as
# ``detail``, a fusion's kind or a custom call's target.
DeviceOp = tuple
# A host span: (name, start_ns, dur_ns).
HostSpan = tuple

LINES = {"XLA Ops": "ops", "Async XLA Ops": "async", "XLA Modules": "module"}
SPAN_PREFIX = "bench."
MIN_GAP_NS = 20_000  # shorter idle gaps are pooled as "under_20us"
PALLAS_TARGET = "tpu_custom_call"

_COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
)
_CONTAINER = ("while", "conditional", "call")
_HLO = re.compile(r"^%?(?P<inst>[^\s=]+) = ")


def parse_hlo(text: str) -> tuple[str, str, str]:
    """HLO text of one instruction -> (inst, opcode, detail)."""
    m = _HLO.match(text)
    if not m:
        return text.split("(")[0].strip(), "", ""
    inst, rest = m.group("inst"), text[m.end():]
    if rest.startswith("("):  # a tuple shape: skip to its closing paren
        depth = 0
        for i, ch in enumerate(rest):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                rest = rest[i + 1:]
                break
    else:
        rest = rest[rest.find(" "):] if " " in rest else ""
    opcode = rest.strip().split("(")[0].strip()
    detail = ""
    if opcode == "fusion":
        k = re.search(r"kind=(k\w+)", text)
        detail = k.group(1) if k else ""
    elif opcode == "custom-call":
        k = re.search(r'custom_call_target="([^"]+)"', text)
        detail = k.group(1) if k else ""
    return inst, opcode, detail


@dataclass
class Trace:
    device_ops: list = field(default_factory=list)
    host_spans: list = field(default_factory=list)
    window_ns: tuple = (0, 0)  # [start, end] of the traced window

    def to_json(self) -> dict:
        return {
            "device_ops": [list(e) for e in self.device_ops],
            "host_spans": [list(e) for e in self.host_spans],
            "window_ns": list(self.window_ns),
        }

    @classmethod
    def from_json(cls, d: dict) -> "Trace":
        return cls(
            device_ops=[tuple(e) for e in d["device_ops"]],
            host_spans=[tuple(e) for e in d["host_spans"]],
            window_ns=tuple(d["window_ns"]),
        )

    def devices(self) -> list[str]:
        return sorted({e[0] for e in self.device_ops})


@contextlib.contextmanager
def record(log_dir: str, spans=None):
    """Profile the body. Host tracing is kept to the benchmark's own
    annotations (no Python tracer): the trace is of the device."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    if spans is not None:
        spans.annotate = True
    try:
        with jax.profiler.TraceAnnotation(SPAN_PREFIX + "window"):
            yield
    finally:
        if spans is not None:
            spans.annotate = False
        jax.profiler.stop_trace()


def find_xplane(log_dir: str) -> str:
    files = sorted(glob.glob(
        os.path.join(log_dir, "plugins", "profile", "*", "*.xplane.pb")
    ))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return files[-1]


def _stats(ev) -> dict:
    try:
        return {k: v for k, v in ev.stats}
    except Exception:  # noqa: BLE001 — a stat that will not decode
        return {}


def load(log_dir: str) -> Trace:
    """``.xplane.pb`` -> ``Trace``. Device planes are ``/device:TPU:N``
    and their operations the line ``XLA Ops``; host spans are the
    ``bench.*`` annotations on any host thread."""
    import jax

    pd = jax.profiler.ProfileData.from_file(find_xplane(log_dir))
    tr = Trace()
    for plane in pd.planes:
        is_dev = plane.name.startswith("/device:TPU:")
        for line in plane.lines:
            if is_dev and line.name in LINES:
                tag = LINES[line.name]
                for ev in line.events:
                    if tag == "module":
                        inst, opcode, detail = ev.name.split("(")[0], "", ""
                    else:
                        inst, opcode, detail = parse_hlo(ev.name)
                    tr.device_ops.append((
                        plane.name, tag, inst, opcode, detail,
                        int(ev.start_ns), int(ev.duration_ns),
                    ))
            elif not is_dev:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        tr.host_spans.append((
                            ev.name, int(ev.start_ns), int(ev.duration_ns)
                        ))
    win = [s for s in tr.host_spans if s[0] == SPAN_PREFIX + "window"]
    if win:
        tr.window_ns = (win[0][1], win[0][1] + win[0][2])
    elif tr.device_ops:
        tr.window_ns = (
            min(e[5] for e in tr.device_ops),
            max(e[5] + e[6] for e in tr.device_ops),
        )
    tr.host_spans = [s for s in tr.host_spans
                     if s[0] != SPAN_PREFIX + "window"]
    return tr


def schema(log_dir: str, per_line: int = 6) -> dict:
    """What a trace holds, for a human: planes, lines, sample events
    with every stat. Look at one by hand before trusting ``load``."""
    import jax

    pd = jax.profiler.ProfileData.from_file(find_xplane(log_dir))
    out = {}
    for plane in pd.planes:
        lines = {}
        for line in plane.lines:
            evs = list(line.events)
            lines[line.name] = {
                "events": len(evs),
                "sample": [
                    {"name": e.name, "start_ns": e.start_ns,
                     "dur_ns": e.duration_ns,
                     "stats": {k: str(v)[:200] for k, v in _stats(e).items()}}
                    for e in evs[:per_line]
                ],
            }
        out[plane.name] = lines
    return out


# ---- reduction ----------------------------------------------------------


def _union(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    out: list[tuple[int, int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _length(intervals) -> int:
    return sum(e - s for s, e in intervals)


def _clip(ops, window):
    lo, hi = window
    for e in ops:
        s, t = max(e[5], lo), min(e[5] + e[6], hi)
        if t > s:
            yield e, s, t


def _base(inst: str) -> str:
    return re.sub(r"(\.\d+|\.remat\d*|\.clone\d*)+$", "", inst) or inst


def op_class(e: DeviceOp) -> str:
    """A stable name for a kind of device operation, ``<class>:<op>``:
    ``matmul:fusion`` (an output fusion: a matmul with what was fused
    into it), ``custom-call:attn`` (a Pallas kernel, named by the module
    it serves), ``collective:all-reduce``, ``fusion:loop:fusion``,
    ``copy:copy-done``."""
    _, _, inst, opcode, detail = e[:5]
    base = _base(inst)
    m = _COLLECTIVE.match(opcode)
    if m:
        return "collective:" + m.group(1)
    if opcode == "custom-call":
        return "custom-call:" + (base if detail == PALLAS_TARGET else detail)
    if opcode == "fusion":
        if detail == "kOutput":
            return "matmul:" + base
        return f"fusion:{detail[1:].lower() or 'other'}:{base}"
    if opcode.startswith(("copy", "slice-start", "slice-done")):
        return "copy:" + opcode
    return "op:" + (opcode or base)


def is_kernel(e: DeviceOp) -> bool:
    """A Pallas (Mosaic) kernel."""
    return e[3] == "custom-call" and e[4] == PALLAS_TARGET


def is_collective(e: DeviceOp, which: str = "") -> bool:
    m = _COLLECTIVE.match(e[3])
    return bool(m) and (not which or m.group(1) in which.split("|"))


def _ran(tr: Trace, dev: str | None = None):
    """Events of the ops line (what occupied the device), containers
    left out: their bodies are there themselves."""
    for e in tr.device_ops:
        if e[1] == "ops" and e[3] not in _CONTAINER and (
            dev is None or e[0] == dev
        ):
            yield e


def busy(tr: Trace) -> dict:
    """Seconds in which an operation ran, per device and averaged, and
    the traced window's length."""
    per_dev = {}
    for dev in tr.devices():
        iv = [(s, t) for _, s, t in _clip(_ran(tr, dev), tr.window_ns)]
        per_dev[dev] = _length(_union(iv)) / 1e9
    window_s = (tr.window_ns[1] - tr.window_ns[0]) / 1e9
    n = max(1, len(per_dev))
    return {
        "busy_s": sum(per_dev.values()) / n,
        "window_s": window_s,
        "per_device_busy_s": per_dev,
    }


def seconds_by_class(tr: Trace) -> dict[str, float]:
    """Device seconds by operation class, averaged over devices."""
    acc: dict[str, float] = {}
    n = max(1, len(tr.devices()))
    for e, s, t in _clip(_ran(tr), tr.window_ns):
        c = op_class(e)
        acc[c] = acc.get(c, 0.0) + (t - s) / 1e9 / n
    return dict(sorted(acc.items(), key=lambda kv: -kv[1]))


def seconds_where(tr: Trace, pred) -> float:
    """Device seconds (mean over devices) of the operations that
    ``pred`` picks: how a metric finds its kernel."""
    n = max(1, len(tr.devices()))
    return sum(
        t - s for e, s, t in _clip(_ran(tr), tr.window_ns) if pred(e)
    ) / 1e9 / n


def modules(tr: Trace, pattern: str = "") -> list[DeviceOp]:
    """Program executions on the first device whose name matches."""
    devs = tr.devices()
    rx = re.compile(pattern)
    return [
        e for e in tr.device_ops
        if e[1] == "module" and e[0] == devs[0] and rx.search(e[2])
        and e[5] >= tr.window_ns[0] and e[5] + e[6] <= tr.window_ns[1]
    ] if devs else []


def modules_with(tr: Trace, pred) -> list[DeviceOp]:
    """Program executions on the first device inside which an operation
    that ``pred`` picks ran: how a metric finds the program that holds
    its kernel where programs share a name."""
    devs = tr.devices()
    if not devs:
        return []
    marks = sorted(e[5] for e in _ran(tr, devs[0]) if pred(e))
    out = []
    for m in modules(tr):
        i = bisect.bisect_left(marks, m[5])
        if i < len(marks) and marks[i] < m[5] + m[6]:
            out.append(m)
    return out


def exposed_collective_s(tr: Trace, which: str = "") -> float:
    """Collective seconds during which no compute operation ran on the
    same device, averaged over devices: what overlap failed to hide.
    A collective's interval is its operation's on the ops line or, for
    an asynchronous one, start to done on the async line."""
    tot = 0
    devs = tr.devices()
    for dev in devs:
        evs = [e for e in tr.device_ops if e[0] == dev]
        coll = _union([(s, t) for _, s, t in _clip(
            (e for e in evs if e[1] in ("ops", "async")
             and is_collective(e, which)), tr.window_ns)])
        comp = _union([(s, t) for _, s, t in _clip(
            (e for e in _ran(tr, dev) if not is_collective(e)
             and not e[3].endswith(("-start", "-done"))), tr.window_ns)])
        hidden, j = 0, 0
        for s, t in coll:
            while j < len(comp) and comp[j][1] <= s:
                j += 1
            k = j
            while k < len(comp) and comp[k][0] < t:
                hidden += min(t, comp[k][1]) - max(s, comp[k][0])
                k += 1
        tot += _length(coll) - hidden
    return tot / 1e9 / max(1, len(devs))


def idle_gaps(tr: Trace) -> dict[str, float]:
    """Idle seconds of the first device, by what the host was doing in
    each gap: the benchmark's span that covers most of it,
    ``unspanned`` where none does, ``under_20us`` for the short ones."""
    devs = tr.devices()
    if not devs:
        return {}
    lo, hi = tr.window_ns
    iv = _union([(s, t) for _, s, t in _clip(
        _ran(tr, devs[0]), tr.window_ns)])
    gaps, prev = [], lo
    for s, t in iv:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, t)
    if hi > prev:
        gaps.append((prev, hi))
    acc: dict[str, float] = {}
    for s, t in gaps:
        if t - s < MIN_GAP_NS:
            name = "under_20us"
        else:
            name, cover = "unspanned", 0
            for n, hs, hd in tr.host_spans:
                c = min(t, hs + hd) - max(s, hs)
                if c > cover:
                    name, cover = n, c
        acc[name] = acc.get(name, 0.0) + (t - s) / 1e9
    return dict(sorted(acc.items(), key=lambda kv: -kv[1]))


def breakdown(tr: Trace, top: int = 10) -> dict:
    return {
        "device_ops": [[k, v] for k, v in
                       list(seconds_by_class(tr).items())[:top]],
        "idle_gaps": [[k, v] for k, v in list(idle_gaps(tr).items())[:top]],
    }


def save(tr: Trace, path: str) -> None:
    with open(path, "w") as f:
        json.dump(tr.to_json(), f)
