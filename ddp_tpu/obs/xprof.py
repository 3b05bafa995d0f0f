"""Compiled-program observability: what did XLA actually build?

Everything else in ``ddp_tpu.obs`` measures from the host side (wall
clocks, loss reads) or computes by hand (analytic FLOPs estimators,
the zero strategy's ring-model ``comm_bytes``). The compiler already
knows the ground truth: ``jit(f).lower(args).compile()`` exposes
``cost_analysis()`` (XLA-counted FLOPs, bytes accessed) and
``memory_analysis()`` (argument/output/temp/generated-code bytes), the
optimized HLO names every collective with its payload shape, and TPU
devices expose live ``memory_stats()``. This module surfaces all of
it:

- :class:`Xprof` — instruments a jitted callable so the compile the
  hot path was going to pay anyway happens in OUR hands: the wrapper
  owns the signature→executable cache (ahead-of-time
  ``lower().compile()``, then dispatch through the compiled object —
  bit-identical results, ONE compile per signature, donation
  preserved), and each compile is recorded as a ledger entry carrying
  the function label, arg-shape signature, compile wall-time,
  XLA-measured FLOPs/bytes-accessed, the full memory breakdown, and
  the per-kind collective payload parsed from the optimized HLO.
  Recompiles become attributable events: label + shape-diff vs the
  previous signature + compile seconds (obs/steptime.py attaches them
  to the step that paid).
- :class:`DeviceMemorySampler` — per-step device-memory high-water /
  headroom: ``device.memory_stats()`` where the runtime provides it
  (TPU), live-buffer accounting over ``jax.live_arrays()`` elsewhere
  (the ``parallel/zero.opt_bytes_per_device`` convention — per-shard
  bytes on each device, max over devices).
- cross-checks — :func:`ring_collective_traffic` converts HLO payload
  shapes into the same ring model ``zero_comm_bytes`` prices, so the
  hand ledger is validated against the compiled program
  (:meth:`Xprof.comm_check`); the ledger's XLA-counted ``flops``
  validate the analytic MFU estimators (tests/test_xprof.py pins
  per-family tolerance bands).

Disabled mode is FREE, the tracer's discipline: ``instrument`` returns
the caller's function object unchanged (not a wrapper), the sampler
returns ``{}``, and nothing imports beyond this module's top level —
pinned by tests.

Instrumentation is a diagnosis mode like ``--trace_dir``: dispatching
through ``Compiled`` objects skips jit's C++ fast path, so expect a
few extra microseconds of host overhead per step while ``--xprof`` is
on.
"""

from __future__ import annotations

import re
import threading
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Callable, Optional

# Bytes per element for the HLO shape grammar (f32[8,28]{1,0} etc).
_HLO_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

# `%name = SHAPES op-name(...)` in optimized HLO. SHAPES is one shape
# or a tuple of them. Async collectives appear as a `-start`/`-done`
# pair: the `-start` result is a TUPLE that aliases the operand
# buffer(s) alongside the destination (counting it would overstate
# the payload ~2x), while the `-done` result is exactly the
# collective's result — so `-done` is counted and `-start` skipped
# (sync ops, with no suffix, count their own result).
_COLLECTIVE_OPS = (
    "all-reduce", "reduce-scatter", "all-gather", "all-to-all",
    "collective-permute",
)
# The shapes group must admit TPU post-optimization layouts — tiling
# and memory-space annotations like f32[1024,8]{1,0:T(8,128)} or
# f32[512]{0:S(1)} carry uppercase letters and parens (a char class
# without them silently parses ZERO collectives on exactly the
# backend this exists for). The lazy match stays anchored by the
# literal op-name keyword, so widening it cannot over-consume.
_COLLECTIVE_RE = re.compile(
    r"=\s*(\(?[a-zA-Z0-9\[\]{},():*\s]+?\)?)\s+("
    + "|".join(_COLLECTIVE_OPS)
    + r")(-start|-done)?\("
)
# The `%name` defining the instruction, scanned BACKWARD from a
# collective match: async `-done` ops reference their `-start` by this
# name, which is how the done's bytes re-join the start's groups.
_DEF_NAME_RE = re.compile(r"%([\w.\-]+)\s*$")
_OPERAND_NAME_RE = re.compile(r"%([\w.\-]+)")
_SHAPE_RE = re.compile(r"(pred|[suf]\d+|bf16|c\d+)\[([0-9,]*)\]")
# XLA annotates long tuples with position comments (`/*index=5*/`),
# inside result shapes and operand lists alike; they carry nothing
# the parser reads and their `/`, `=` would stop the shapes group.
_HLO_COMMENT_RE = re.compile(r"/\*.*?\*/")
# The two HLO spellings of group membership: explicit nested braces
# (`replica_groups={{0,1},{2,3}}`) and the iota/v2 form
# (`replica_groups=[2,2]<=[4]` — reshape iota(4) to [2,2], each row a
# group — optionally with a transpose, `<=[2,2]T(1,0)`).
_GROUPS_EXPLICIT_RE = re.compile(r"replica_groups=\{(\{[0-9,{}]*\})\}")
_GROUPS_IOTA_RE = re.compile(
    r"replica_groups=\[([0-9,]+)\]<=\[([0-9,]+)\](?:T\(([0-9,]+)\))?"
)


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _HLO_DTYPE_BYTES.get(dtype, 4)


def _shapes_bytes(shapes: str, *, arrays_only: bool = False) -> int:
    """Bytes of every shape in an HLO result (one shape or a tuple);
    ``arrays_only`` leaves the scalars out."""
    return sum(
        _shape_bytes(dt, dims) for dt, dims in _SHAPE_RE.findall(shapes)
        if dims or not arrays_only
    )


def _parse_replica_groups(line: str) -> Optional[list[list[int]]]:
    """The collective's replica groups from its HLO line, or None when
    the op carries none (= one group of the whole world)."""
    m = _GROUPS_IOTA_RE.search(line)
    if m:
        import numpy as np

        gshape = [int(x) for x in m.group(1).split(",")]
        rshape = [int(x) for x in m.group(2).split(",")]
        ids = np.arange(int(np.prod(rshape))).reshape(rshape)
        if m.group(3):
            perm = [int(x) for x in m.group(3).split(",")]
            ids = ids.transpose(perm)
        return [
            [int(v) for v in row]
            for row in ids.reshape(-1).reshape(gshape)
        ]
    m = _GROUPS_EXPLICIT_RE.search(line)
    if m:
        groups = []
        for grp in re.findall(r"\{([0-9,\s]*)\}", m.group(1)):
            ids = [int(v) for v in grp.split(",") if v.strip()]
            if ids:
                groups.append(ids)
        return groups or None
    return None


def parse_hlo_collectives(hlo_text: str) -> dict[str, dict]:
    """Optimized-HLO text → per-kind ``{count, result_bytes, ops}``.

    ``result_bytes`` sums each collective's RESULT shape(s): the full
    array for all-reduce/all-gather, the 1/N shard for reduce-scatter
    — :func:`ring_collective_traffic` converts to wire traffic.

    ``ops`` lists each instance as ``{result_bytes, groups}`` where
    ``groups`` is the parsed ``replica_groups`` membership (None = the
    whole world in one group). SUBGROUP collectives — the hierarchical
    zero step's within-slice scatter and cross-slice shard exchange —
    ring-model over their own group size, and the membership is what
    :func:`hlo_axis_traffic` attributes to ICI vs DCN. Async pairs:
    the ``-start`` op carries the attributes but its tuple result
    aliases the operand, so the groups are recorded at ``-start``
    keyed by its instruction NAME and the bytes counted at the
    ``-done`` that references that name as its operand — an overlapped
    schedule may retire dones out of start order, so FIFO pairing
    would cross-wire groups (positional fallback only when the
    operand reference is unresolvable).
    """
    out: dict[str, dict] = {}
    pending: dict[str, dict] = {}
    hlo_text = _HLO_COMMENT_RE.sub("", hlo_text)
    for m in _COLLECTIVE_RE.finditer(hlo_text):
        shapes, op, suffix = m.group(1), m.group(2), m.group(3)
        bol = hlo_text.rfind("\n", 0, m.start()) + 1
        eol = hlo_text.find("\n", m.end())
        line = hlo_text[m.end() : eol if eol >= 0 else len(hlo_text)]
        if suffix == "-start":
            named = _DEF_NAME_RE.search(hlo_text[bol : m.start()].rstrip())
            key = named.group(1) if named else f"?{len(pending)}"
            pending.setdefault(op, {})[key] = _parse_replica_groups(line)
            continue  # its tuple aliases the operand; `-done` counts
        if suffix == "-done":
            queued = pending.get(op, {})
            ref = _OPERAND_NAME_RE.search(line)
            if ref is not None and ref.group(1) in queued:
                groups = queued.pop(ref.group(1))
            elif queued:  # unresolvable reference: oldest pending
                groups = queued.pop(next(iter(queued)))
            else:
                groups = _parse_replica_groups(line)
        else:
            groups = _parse_replica_groups(line)
        total = _shapes_bytes(shapes)
        ent = out.setdefault(
            op, {"count": 0, "result_bytes": 0, "ops": []}
        )
        ent["count"] += 1
        ent["result_bytes"] += total
        ent["ops"].append({"result_bytes": total, "groups": groups})
    return out


# ---- where the all-reduces stand in the scheduled program -------------

_INSTR_RE = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=\s*")
_CALLS_RE = re.compile(r"calls=%([\w.\-]+)")
_COMPUTE_OPCODES = frozenset({"fusion", "custom-call", "convolution", "dot"})
# A fused computation that holds a piece of an asynchronous collective
# on a TPU: the start and done fusions wrap these custom calls; with
# several steps the fusions between them are ``async_collective_fusion``
# computations that carry compute and a step of the collective.
_ASYNC_START, _ASYNC_DONE = "AsyncCollectiveStart", "AsyncCollectiveDone"


def _closing_paren(s: str, start: int) -> int:
    """Index of the parenthesis that closes the one opened just before
    ``s[start]`` (``len(s)`` if none does)."""
    depth = 1
    for i in range(start, len(s)):
        depth += (s[i] == "(") - (s[i] == ")")
        if depth == 0:
            return i
    return len(s)


def _split_instruction(line: str):
    """One line of HLO text -> (name, shapes, opcode, rest after the
    opcode's opening parenthesis), or None for anything else."""
    m = _INSTR_RE.match(line)
    if not m:
        return None
    rest = line[m.end():]
    if rest.startswith("("):  # a tuple shape: to its closing paren
        i = _closing_paren(rest, 1)
        shapes, rest = rest[: i + 1], rest[i + 1:].lstrip()
    else:
        shapes, _, rest = rest.partition(" ")
    opcode, _, args = rest.partition("(")
    return m.group(1), shapes, opcode.strip(), args


def collective_schedule(hlo_text: str) -> dict:
    """The all-reduces of a compiled program, by where its SCHEDULED
    entry computation puts them.

    ``compiled.as_text()`` of an executable is scheduled: the order of
    the entry computation's instructions is the order the core issues
    them in. A plain ``all-reduce`` there is synchronous — the core
    waits for it wherever it stands. An asynchronous one is a pair:
    ``all-reduce-start`` / ``-done``, or on a TPU a ``fusion`` wrapping
    the custom calls ``AsyncCollectiveStart`` / ``AsyncCollectiveDone``
    (named ``async-collective-start`` / ``-done``), with the compute
    scheduled between the two running under it.

    Returns positions (indices into the entry computation), not times:

    - ``reduces``: one dict per all-reduce that carries an array (a
      gradient reduce; a scalar ``psum`` of the loss is listed under
      ``other``): ``name``, ``kind`` (``all-reduce`` |
      ``all-reduce-start`` | ``async-collective-start``), ``start``,
      ``done`` (= ``start`` when synchronous), ``bytes`` (the reduced
      arrays), ``compute_between`` (fusions, kernels and matmuls
      between start and done) and ``backward_between`` (those of them
      that belong to the backward pass: ``transpose(`` in their
      ``op_name``; the TPU compiler defers weight-gradient matmuls
      past the last attention kernel to pair them with the reduces);
    - ``first_backward`` / ``last_backward``: positions of the first
      and last Pallas kernel of the backward pass (a ``tpu_custom_call``
      whose ``op_name`` holds ``transpose(``), None where there is none
      (a CPU compile interprets the kernels);
    - ``summary``: ``reduces``, ``bytes``, ``asynchronous`` with
      ``asynchronous_bytes``, ``start_in_backward`` (start before the
      last backward kernel), ``under_backward`` (asynchronous with
      backward-pass compute between start and done) with
      ``under_backward_bytes``.
    """
    text = _HLO_COMMENT_RE.sub("", hlo_text)
    # computations that hold a piece of an asynchronous collective
    comp_kind: dict[str, str] = {}
    comp_reduce_bytes: dict[str, int] = {}
    entry: list[str] = []
    cur, in_entry = None, False
    for line in text.split("\n"):
        if line.startswith("ENTRY "):
            in_entry, cur = True, None
            continue
        if line.startswith("}"):
            in_entry, cur = False, None
            continue
        if in_entry:
            entry.append(line)
            continue
        if line.startswith("%") and line.rstrip().endswith("{"):
            cur = line[1:].split(" ", 1)[0]
            continue
        if cur is None:
            continue
        if _ASYNC_START in line:
            comp_kind[cur] = "start"
        elif _ASYNC_DONE in line:
            comp_kind[cur] = "done"
        parts = _split_instruction(line)
        if parts and parts[2] == "all-reduce":
            comp_reduce_bytes[cur] = comp_reduce_bytes.get(cur, 0) + (
                _shapes_bytes(parts[1], arrays_only=True)
            )
            comp_kind.setdefault(cur, "step")

    instrs = []  # (name, shapes, opcode, operands, line)
    index: dict[str, int] = {}
    for line in entry:
        parts = _split_instruction(line)
        if parts is None:
            continue
        name, shapes, opcode, args = parts
        operands = _OPERAND_NAME_RE.findall(args[: _closing_paren(args, 0)])
        index[name] = len(instrs)
        instrs.append((name, shapes, opcode, operands, line))

    def async_piece(i: int) -> Optional[str]:
        name, _, opcode, _, line = instrs[i]
        if opcode == "all-reduce-start":
            return "start"
        if opcode == "all-reduce-done":
            return "done"
        if opcode != "fusion":
            return None
        m = _CALLS_RE.search(line)
        return comp_kind.get(m.group(1)) if m else None

    def start_of(i: int, seen: set) -> Optional[int]:
        """The start an asynchronous done (or step) hangs from."""
        for op in instrs[i][3]:
            j = index.get(op)
            while j is not None and instrs[j][2] in (
                "get-tuple-element", "bitcast"
            ):
                j = index.get(instrs[j][3][0]) if instrs[j][3] else None
            if j is None or j in seen:
                continue
            seen.add(j)
            piece = async_piece(j)
            if piece == "start":
                return j
            if piece in ("step", "done"):
                k = start_of(j, seen)
                if k is not None:
                    return k
        return None

    backward = [
        i for i, (_, _, opcode, _, line) in enumerate(instrs)
        if opcode == "custom-call" and "tpu_custom_call" in line
        and "transpose(" in line
    ]
    last_bwd = backward[-1] if backward else None

    reduces, other, starts = [], [], {}
    for i, (name, shapes, opcode, operands, line) in enumerate(instrs):
        piece = async_piece(i)
        if opcode == "all-reduce":
            groups = _parse_replica_groups(line)
            if groups is not None and max(map(len, groups)) <= 1:
                continue  # over one device (XLA:CPU keeps these): a copy
            nbytes = _shapes_bytes(shapes, arrays_only=True)
            rec = {"name": name, "kind": "all-reduce", "start": i,
                   "done": i, "bytes": nbytes, "compute_between": 0}
            (reduces if nbytes else other).append(rec)
        elif piece == "start":
            if opcode == "fusion":
                comp = _CALLS_RE.search(line).group(1)
                nbytes = comp_reduce_bytes.get(comp, 0)
                kind = "async-collective-start"
            else:
                # the start's tuple aliases its operand beside the result
                nbytes = _shapes_bytes(shapes, arrays_only=True) // 2
                kind = "all-reduce-start"
            rec = {"name": name, "kind": kind, "start": i, "done": i,
                   "bytes": nbytes, "compute_between": 0}
            starts[i] = rec
            (reduces if nbytes else other).append(rec)
        elif piece == "done":
            j = start_of(i, set())
            if j in starts:
                starts[j]["done"] = i
    for rec in reduces:
        between = [
            instrs[k][4] for k in range(rec["start"] + 1, rec["done"])
            if instrs[k][2] in _COMPUTE_OPCODES
            and async_piece(k) not in ("start", "done")
        ]
        rec["compute_between"] = len(between)
        rec["backward_between"] = sum("transpose(" in l for l in between)
    over = [r for r in reduces if r["backward_between"]]
    return {
        "instructions": len(instrs),
        "first_backward": backward[0] if backward else None,
        "last_backward": last_bwd,
        "reduces": reduces,
        "other": other,
        "summary": {
            "reduces": len(reduces),
            "bytes": sum(r["bytes"] for r in reduces),
            "asynchronous": sum(r["done"] > r["start"] for r in reduces),
            "asynchronous_bytes": sum(
                r["bytes"] for r in reduces if r["done"] > r["start"]
            ),
            "start_in_backward": sum(
                last_bwd is not None and r["start"] < last_bwd
                for r in reduces
            ),
            "under_backward": len(over),
            "under_backward_bytes": sum(r["bytes"] for r in over),
        },
    }


def _op_ring_bytes(op: str, result_bytes: int, group: int) -> int:
    """One collective instance → per-replica ring traffic over its own
    group: all-reduce 2·(g−1)/g of the full bytes, all-gather (g−1)/g
    of its (full) result, reduce-scatter (g−1)·its (shard) result,
    permute one hop."""
    if group <= 1:
        return 0
    frac = (group - 1) / group
    if op == "all-reduce":
        return int(2 * frac * result_bytes)
    if op == "reduce-scatter":
        return int((group - 1) * result_bytes)
    if op == "collective-permute":
        return int(result_bytes)
    return int(frac * result_bytes)  # all-gather / all-to-all


def ring_collective_traffic(
    collectives: dict[str, dict], world: int
) -> dict[str, int]:
    """HLO result bytes → per-replica ring traffic, the model
    ``parallel/zero.zero_comm_bytes`` prices. Subgroup-aware: an op
    whose ``replica_groups`` name a smaller group ring-models over
    THAT size (groups absent = one ring over ``world``), so the
    hierarchical step's within-slice and cross-slice collectives each
    price over their own fabric's group.
    """
    traffic = {}
    for op, key in (
        ("all-reduce", "all_reduce"),
        ("all-gather", "all_gather"),
        ("reduce-scatter", "reduce_scatter"),
        ("collective-permute", "collective_permute"),
        ("all-to-all", "all_to_all"),
    ):
        ent = collectives.get(op, {})
        ops = ent.get("ops")
        if ops is None:
            # Pre-extension dict (stored records): aggregate math.
            ops = [
                {"result_bytes": ent.get("result_bytes", 0), "groups": None}
            ] if ent else []
        traffic[key] = sum(
            _op_ring_bytes(
                op,
                o["result_bytes"],
                len(o["groups"][0]) if o.get("groups") else world,
            )
            for o in ops
        )
    traffic["total"] = sum(traffic.values())
    return traffic


def hlo_axis_traffic(
    collectives: dict[str, dict], *, slice_size: int, world: int
) -> dict[str, dict[str, int]]:
    """Ring traffic split by fabric: ``ici`` (every group stays inside
    one slice block) vs ``dcn`` (any group spans slices).

    Replica ids group into contiguous per-slice blocks of
    ``slice_size`` because the mesh's ``dcn`` axis is OUTERMOST
    (runtime/mesh.py ``slice_block_size``) — so id//slice_size is the
    slice, and a group with members in two slices rides the slow
    fabric. Ops without groups span the world: dcn iff
    ``world > slice_size``.
    """
    out = {
        "ici": {"total": 0}, "dcn": {"total": 0},
    }
    for op, key in (
        ("all-reduce", "all_reduce"),
        ("all-gather", "all_gather"),
        ("reduce-scatter", "reduce_scatter"),
        ("collective-permute", "collective_permute"),
        ("all-to-all", "all_to_all"),
    ):
        for axis in out:
            out[axis].setdefault(key, 0)
        for o in collectives.get(op, {}).get("ops", []):
            groups = o.get("groups")
            if groups:
                g = len(groups[0])
                crossing = any(
                    len({i // max(1, slice_size) for i in grp}) > 1
                    for grp in groups
                )
            else:
                g = world
                crossing = world > slice_size
            b = _op_ring_bytes(op, o["result_bytes"], g)
            axis = "dcn" if crossing else "ici"
            out[axis][key] += b
            out[axis]["total"] += b
    return out


def _leaf_sig(leaf) -> str:
    dtype = getattr(leaf, "dtype", None)
    shape = getattr(leaf, "shape", None)
    if dtype is None or shape is None:
        return type(leaf).__name__
    short = (
        str(dtype)
        .replace("bfloat", "bf").replace("float", "f")
        .replace("uint", "u").replace("int", "i")
        .replace("bool", "pred").replace("complex", "c")
    )
    return f"{short}[{','.join(str(d) for d in shape)}]"


def shape_signature(args: tuple) -> str:
    """Human-readable arg-shape signature: ``f32[8,28,28,1]|i32[8]``
    over the FLATTENED leaves (pytree args summarize as leaf count +
    total elements — a 50-leaf param tree must not make the ledger
    unreadable)."""
    import jax

    parts = []
    for a in args:
        leaves = jax.tree_util.tree_leaves(a)
        if len(leaves) == 1:
            parts.append(_leaf_sig(leaves[0]))
        else:
            elems = sum(
                int(getattr(l, "size", 0) or 0) for l in leaves
            )
            parts.append(f"tree({len(leaves)} leaves, {elems} elems)")
    return "|".join(parts)


def shape_diff(old: str, new: str) -> str:
    """Positional diff of two signatures: ``arg2: i32[8]->i32[16]``."""
    olds, news = old.split("|"), new.split("|")
    diffs = [
        f"arg{i}: {o}->{n}"
        for i, (o, n) in enumerate(zip(olds, news))
        if o != n
    ]
    if len(olds) != len(news):
        diffs.append(f"arity: {len(olds)}->{len(news)}")
    return "; ".join(diffs) or "(identical signature)"


@dataclass
class ProgramProfile:
    """One compiled executable's ledger entry (JSON-ready via
    :meth:`record`)."""

    label: str
    signature: str
    compile_time_s: float
    # The part of compile_time_s spent tracing and lowering (Python,
    # never cached); the rest is XLA's compile — what a persistent
    # compile-cache hit removes. None for observe-only entries.
    lower_time_s: Optional[float] = None
    flops: Optional[float] = None
    bytes_accessed: Optional[float] = None
    memory: dict = field(default_factory=dict)
    collectives: dict = field(default_factory=dict)
    shape_diff: Optional[str] = None  # vs the label's previous compile
    fallback: bool = False  # observe-only (no AOT introspection)
    calls: int = 0
    # Host-side facts XLA can't see (e.g. the effective Pallas block_k
    # after divisor fallback), attached via :meth:`Xprof.annotate`.
    notes: dict = field(default_factory=dict)

    def record(self) -> dict:
        out = {
            "label": self.label,
            "signature": self.signature,
            "compile_time_s": round(self.compile_time_s, 4),
            "calls": self.calls,
        }
        if self.lower_time_s is not None:
            out["lower_time_s"] = round(self.lower_time_s, 4)
        if self.notes:
            out["notes"] = dict(self.notes)
        if self.flops is not None:
            out["flops"] = self.flops
        if self.bytes_accessed is not None:
            out["bytes_accessed"] = self.bytes_accessed
        if self.memory:
            out["memory"] = dict(self.memory)
        if self.collectives:
            out["collectives"] = dict(self.collectives)
        if self.shape_diff:
            out["shape_diff"] = self.shape_diff
        if self.fallback:
            out["fallback"] = True
        return out


def _introspect(compiled) -> tuple[Optional[float], Optional[float], dict]:
    """(flops, bytes_accessed, memory breakdown) from a Compiled.

    Both analyses answer on the CPU and on the TPU (PR 21's chip
    runs); a field a backend leaves out stays None/absent."""
    ca = compiled.cost_analysis() or {}
    flops = ca.get("flops")
    bytes_accessed = ca.get("bytes accessed")
    memory: dict = {}
    ma = compiled.memory_analysis()
    if ma is not None:
        for key in (
            "argument_size_in_bytes",
            "output_size_in_bytes",
            "temp_size_in_bytes",
            "alias_size_in_bytes",
            "generated_code_size_in_bytes",
        ):
            v = getattr(ma, key, None)
            if v is not None:
                memory[key.replace("_size_in_bytes", "_bytes")] = int(v)
    return (
        float(flops) if flops is not None else None,
        float(bytes_accessed) if bytes_accessed is not None else None,
        memory,
    )


def call_signature(args: tuple):
    """What ``jit`` keys its cache on, for a wrapper that owns the
    signature -> executable cache: the FLATTENED avals (shape, dtype,
    weak type, sharding) under the arguments' tree. ``_Instrumented``
    pays it on every call (0.2 ms for a train state of 300 leaves), so
    the dtype goes in as it is: its ``str`` alone cost four times the
    rest."""
    import jax

    leaves, treedef = jax.tree_util.tree_flatten(args)
    return (
        treedef,
        tuple(
            (
                getattr(l, "shape", None),
                getattr(l, "dtype", type(l)),
                bool(getattr(l, "weak_type", False)),
                getattr(l, "sharding", None),
            )
            for l in leaves
        ),
    )


class _Instrumented:
    """The enabled-mode wrapper: owns the signature→executable cache.

    Dispatch path: key the FLATTENED avals (shape/dtype/weak_type/
    sharding — exactly what jit's cache keys, so a weak-type or
    sharding change recompiles here too, attributed instead of
    silent); on miss, ``lower().compile()`` under a timer, introspect,
    ledger, then call the compiled object. ``_cache_size()`` mirrors
    jit's for the serve engine's static-shape pins.

    Callables without ``.lower`` (epoch-runner closures) fall back to
    observe-only: the first call per signature is timed as a whole
    (compile + run — flagged ``fallback`` in the ledger, honest about
    what was measurable) and later calls pass straight through.
    """

    def __init__(self, xprof: "Xprof", fn: Callable, label: str):
        self._xprof = xprof
        self._fn = fn
        self.label = label
        self._compiled: dict = {}
        self._aot = hasattr(fn, "lower")

    def _key(self, args: tuple):
        return call_signature(args)

    def _cache_size(self) -> int:
        return len(self._compiled)

    def __getattr__(self, name):
        # Delegate everything the wrapper doesn't own (e.g. an epoch
        # runner's steps_per_epoch attribute). Deliberately NOT
        # ``lower``: re-instrumenting a wrapper must not build a
        # second AOT layer.
        if name == "lower":
            raise AttributeError(name)
        return getattr(self._fn, name)

    def __call__(self, *args):
        key = self._key(args)
        hit = self._compiled.get(key)
        if hit is not None:
            hit[1].calls += 1
            return hit[0](*args) if self._aot else self._fn(*args)
        if not self._aot:
            t0 = time.perf_counter()
            out = self._fn(*args)
            profile = self._xprof._record_compile(
                self, args, time.perf_counter() - t0, compiled=None
            )
            self._compiled[key] = (None, profile)
            return out
        t0 = time.perf_counter()
        lowered = self._fn.lower(*args)
        lower_s = time.perf_counter() - t0
        compiled = lowered.compile()
        dt = time.perf_counter() - t0
        profile = self._xprof._record_compile(
            self, args, dt, compiled=compiled, lower_s=lower_s
        )
        self._compiled[key] = (compiled, profile)
        return compiled(*args)


class Xprof:
    """Compile ledger + recompile event stream for instrumented
    programs.

    ``enabled=False`` (the default everywhere) is free:
    ``instrument`` hands back the caller's function object itself —
    not a wrapper — so the disabled hot path is the uninstrumented
    hot path, byte for byte (pinned by tests).
    """

    MAX_EVENTS = 1024
    MAX_LEDGER = 512

    def __init__(self, *, enabled: bool = False):
        self.enabled = bool(enabled)
        self._lock = threading.Lock()
        # Append-only, one entry per COMPILE: two compiles can share a
        # shape signature (the dispatch cache also keys weak_type and
        # sharding), and a keyed ledger would overwrite the first —
        # dropping its compile seconds and making the exported
        # compile_seconds_total counter go backwards. Bounded like the
        # event deque: a recompile storm — the exact pathology this
        # diagnoses — must not grow the process (or every flight-
        # recorder dump / /stats payload that embeds the ledger)
        # without limit, so old entries are evicted while the
        # program_count / compile-seconds counters below stay monotone
        # accumulators that survive eviction.
        self._ledger: deque[ProgramProfile] = deque(maxlen=self.MAX_LEDGER)
        self._program_count = 0
        self._total_compile_s = 0.0
        self._total_lower_s = 0.0
        # Last signature per label, for shape_diff on recompile.
        self._last_sig: dict[str, str] = {}
        # Per-label annotation dicts (see :meth:`annotate`).
        self._notes: dict[str, dict] = {}
        self._events: deque = deque(maxlen=self.MAX_EVENTS)
        self.event_seq = 0

    # ---- instrumentation --------------------------------------------

    def instrument(self, fn: Callable, label: str) -> Callable:
        """Wrap ``fn`` (ideally a jit wrapper) for ledgered compiles;
        identity when disabled."""
        if not self.enabled:
            return fn
        return _Instrumented(self, fn, label)

    def annotate(self, label: str, **fields) -> None:
        """Attach host-known facts to a label's ledger entries.

        XLA's introspection can't see decisions made before lowering —
        the effective Pallas ``block_k`` after divisor fallback, a
        host-resolved bucket width, a dtype chosen by a knob. Callers
        record them here; the fields ride every subsequent (and, for
        robustness, every already-ledgered) compile record under a
        ``notes`` key, so the ledger's reader sees one surface. No-op
        when disabled — the free-when-disabled contract holds.
        """
        if not self.enabled:
            return
        with self._lock:
            merged = dict(self._notes.get(label, {}))
            merged.update(fields)
            self._notes[label] = merged
            for p in self._ledger:
                if p.label == label:
                    p.notes.update(fields)

    def _record_compile(
        self, inst: _Instrumented, args: tuple, dt: float, *, compiled,
        lower_s: Optional[float] = None,
    ) -> ProgramProfile:
        sig = shape_signature(args)
        if compiled is not None:
            flops, bytes_accessed, memory = _introspect(compiled)
            collectives = parse_hlo_collectives(compiled.as_text())
        else:
            flops = bytes_accessed = None
            memory, collectives = {}, {}
        with self._lock:
            prev = self._last_sig.get(inst.label)
            profile = ProgramProfile(
                label=inst.label,
                signature=sig,
                compile_time_s=dt,
                lower_time_s=lower_s,
                flops=flops,
                bytes_accessed=bytes_accessed,
                memory=memory,
                collectives=collectives,
                shape_diff=shape_diff(prev, sig) if prev is not None else None,
                fallback=compiled is None,
                calls=1,
                notes=dict(self._notes.get(inst.label, {})),
            )
            self._ledger.append(profile)
            self._program_count += 1
            self._total_compile_s += dt
            self._total_lower_s += lower_s or 0.0
            self._last_sig[inst.label] = sig
            self.event_seq += 1
            self._events.append((self.event_seq, profile.record()))
        return profile

    # ---- reading the ledger -----------------------------------------

    def events_after(self, seq: int) -> tuple[int, list[dict]]:
        """Compile events with sequence > ``seq`` → (new cursor,
        events). The attribution/metrics readers each keep their own
        cursor, so neither consumes the other's view."""
        with self._lock:
            out = [dict(ev) for s, ev in self._events if s > seq]
            return self.event_seq, out

    def ledger_records(self) -> list[dict]:
        """JSON-ready ledger (flight-recorder dumps, bench records) —
        the most recent ``MAX_LEDGER`` compiles."""
        with self._lock:
            return [p.record() for p in self._ledger]

    @property
    def program_count(self) -> int:
        with self._lock:
            return self._program_count

    @property
    def total_compile_s(self) -> float:
        with self._lock:
            return self._total_compile_s

    @property
    def total_lower_s(self) -> float:
        """Seconds of ``total_compile_s`` spent tracing and lowering;
        the remainder is XLA's compile (or its persistent-cache load)."""
        with self._lock:
            return self._total_lower_s

    def label_collectives(self, label: str) -> Optional[dict]:
        """Raw parsed collectives of the label's most recent AOT
        compile (the per-axis attribution input), or None."""
        with self._lock:
            for p in reversed(self._ledger):
                if p.label == label and not p.fallback:
                    return p.collectives
        return None

    def collective_traffic(
        self, label: str, world: int
    ) -> Optional[dict[str, int]]:
        """Ring-model per-replica traffic of the label's most recent
        compile, or None when nothing compiled (or no collectives)."""
        coll = self.label_collectives(label)
        return (
            ring_collective_traffic(coll, world)
            if coll is not None
            else None
        )

    def comm_check(
        self,
        label: str,
        expected_total: int,
        world: int,
        *,
        tolerance: float = 0.05,
        expected_by_axis: Optional[dict] = None,
        slice_size: Optional[int] = None,
    ) -> Optional[dict]:
        """Hand-ledger vs HLO: does ``expected_total`` (e.g. the zero
        strategy's ``zero_comm_bytes`` estimate) match the compiled
        program's ring traffic within ``tolerance``? None until the
        label compiles; otherwise a JSON-ready verdict.

        ``expected_by_axis`` + ``slice_size`` extend the verdict per
        fabric (the hierarchical zero claim): each axis's analytic
        total (``zero_comm_bytes``'s ``by_axis[...]["total"]``) is
        checked against the replica-group-attributed HLO traffic
        (:func:`hlo_axis_traffic`) under the same tolerance, and the
        overall ``within_tolerance`` requires every axis to hold."""
        measured = self.collective_traffic(label, world)
        if measured is None:
            return None
        ratio = (
            measured["total"] / expected_total if expected_total else None
        )
        if expected_total:
            within = ratio is not None and abs(ratio - 1.0) <= tolerance
        else:
            # Expected zero (world 1, or a collective-free strategy):
            # the check passes iff the program is indeed collective-
            # free — a nonzero measurement against a zero estimate is
            # exactly the drift this exists to catch.
            within = measured["total"] == 0
        out = {
            "label": label,
            "expected_comm_bytes": int(expected_total),
            "measured_comm_bytes": measured["total"],
            "measured_by_kind": {
                k: v for k, v in measured.items() if k != "total" and v
            },
            "ratio": round(ratio, 4) if ratio is not None else None,
            "within_tolerance": within,
        }
        if expected_by_axis is not None and slice_size:
            coll = self.label_collectives(label)
            split = hlo_axis_traffic(
                coll or {}, slice_size=slice_size, world=world
            )
            by_axis = {}
            for axis, exp in expected_by_axis.items():
                exp_total = int(
                    exp["total"] if isinstance(exp, dict) else exp
                )
                got = split.get(axis, {}).get("total", 0)
                aratio = got / exp_total if exp_total else None
                # A small ABSOLUTE slack on top of the ratio band: the
                # scalar loss/accuracy/norm reductions (a few 4-byte
                # all-reduces) ride whichever fabric their pmean spans
                # and are not part of the analytic shard-payload model
                # — at real bucket sizes they are noise, but against a
                # small per-axis expectation they would fail the pure
                # ratio test spuriously.
                awithin = (
                    abs(aratio - 1.0) <= tolerance
                    or abs(got - exp_total) <= 64
                    if aratio is not None
                    else got <= 64
                )
                by_axis[axis] = {
                    "expected_comm_bytes": exp_total,
                    "measured_comm_bytes": int(got),
                    "ratio": (
                        round(aratio, 4) if aratio is not None else None
                    ),
                    "within_tolerance": awithin,
                }
                out["within_tolerance"] = (
                    out["within_tolerance"] and awithin
                )
            out["by_axis"] = by_axis
        return out


# ---- device memory: high-water and headroom ---------------------------


def max_device_buffer_bytes(arrays) -> int:
    """Max over local devices of the bytes the given jax.Arrays' live
    shards actually hold there (per-shard accounting over the real
    shardings: replicated arrays count in full on every device,
    sharded arrays 1/N). THE one definition of this convention —
    ``parallel/zero.opt_bytes_per_device`` (the bench's opt-memory
    ratio) and the sampler's live-buffer fallback both call it, so
    the two can never drift. Deleted/donated arrays are skipped."""
    per: dict[Any, int] = {}
    for arr in arrays:
        try:
            shards = arr.addressable_shards
        except Exception:  # noqa: BLE001 — deleted/donated arrays
            continue
        for s in shards:
            n = 1
            for d in s.data.shape:
                n *= int(d)
            per[s.device] = per.get(s.device, 0) + n * arr.dtype.itemsize
    return max(per.values(), default=0)


class DeviceMemorySampler:
    """Per-step HBM high-water/headroom, host-side and sync-free.

    TPU runtimes expose ``device.memory_stats()`` (bytes_in_use /
    peak_bytes_in_use / bytes_limit); backends without it (CPU) fall
    back to live-buffer accounting — per-device bytes of every
    ``jax.live_arrays()`` shard, the ``opt_bytes_per_device``
    convention — with the high-water tracked across samples by this
    object (and no limit, so headroom is honestly absent rather than
    invented). ``enabled=False`` samples nothing and returns ``{}``.
    """

    def __init__(self, *, enabled: bool = False, devices=None):
        self.enabled = bool(enabled)
        self._devices = devices
        self._high_water = 0
        self._source: Optional[str] = None

    def _live_buffer_bytes(self) -> int:
        import jax

        return max_device_buffer_bytes(jax.live_arrays())

    def sample(self) -> dict:
        """One sample → ``{hbm_used_bytes, hbm_high_water_bytes,
        hbm_limit_bytes?, hbm_headroom_frac?, hbm_source}`` (max over
        local devices), plus ``hbm_peak_bytes_by_device`` where the
        runtime reports ``memory_stats`` — a replica that holds
        nothing, or one that holds everyone's batch, shows there and
        not in a max. ``{}`` when disabled."""
        if not self.enabled:
            return {}
        import jax

        devices = self._devices if self._devices is not None else jax.local_devices()
        used = peak = limit = None
        by_device = []
        for d in devices:
            stats = d.memory_stats()  # None on backends without stats
            if not stats:
                continue
            u = int(stats.get("bytes_in_use", 0))
            p = int(stats.get("peak_bytes_in_use", u))
            by_device.append(p)
            lim = stats.get("bytes_limit")
            used = u if used is None else max(used, u)
            peak = p if peak is None else max(peak, p)
            if lim:
                limit = int(lim) if limit is None else max(limit, int(lim))
        if used is not None:
            self._source = "memory_stats"
            self._high_water = max(self._high_water, peak or used)
        else:
            self._source = "live_buffers"
            used = self._live_buffer_bytes()
            self._high_water = max(self._high_water, used)
        out = {
            "hbm_used_bytes": int(used),
            "hbm_high_water_bytes": int(self._high_water),
            "hbm_source": self._source,
        }
        if by_device:
            out["hbm_peak_bytes_by_device"] = by_device
        if limit:
            out["hbm_limit_bytes"] = int(limit)
            out["hbm_headroom_frac"] = round(
                max(0.0, 1.0 - self._high_water / limit), 6
            )
        return out

    @property
    def high_water_bytes(self) -> int:
        return self._high_water
