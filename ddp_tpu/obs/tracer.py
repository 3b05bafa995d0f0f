"""In-process span tracer: an always-on ring, the profiler's trace, and
Perfetto/Chrome ``trace_event`` JSON.

``jax.profiler`` answers "which kernel is slow" but says nothing about
the *host* side — input wait, scheduler stalls, lock waits, checkpoint
flushes. This tracer is the complement, at two levels:

1. **The ring, always on.** ``span()`` and ``complete()`` append
   ``(name, t0, dur, parent, nums)`` to a bounded ring (a
   ``deque(maxlen=ring_events)``) whether or not anything was switched
   on: two ``perf_counter`` reads, one ``jax.profiler.TraceAnnotation``
   (which the runtime drops while no profiler session is open) and one
   ``deque.append``. No args dict, no lock beyond the deque's own, no
   summaries, no export, and NEVER a device sync: a host span records
   host time. It is what a benchmark's reader takes after the program
   is gone (``get_tracer().ring()``) and what an operator finds after
   a stall nobody planned to trace (the ``/statusz`` tail). While a
   profiler session is open the same spans land in its ``.xplane.pb``
   on the clock the device events use, so an idle gap on the device
   can be put down to the span that covers it.
2. **``enabled``** (``--trace_dir``, ``DDP_TPU_TRACE_DIR``) adds what
   an export needs: the args dict, the thread id, a ``StatSummary``
   per span name (capped at ``MAX_SUMMARY_NAMES``), instants, counter
   tracks, the per-request async spans, and the crash-safe Perfetto
   export (temp file + ``os.replace``; the launcher path registers an
   atexit export so a watchdog abort still leaves a trace).
3. **Phases, kept.** ``phase()`` and ``phase_complete()`` record what
   a process does ONCE, between its start and its first productive
   step (imports, state, warm-up, every compile by program name): the
   same tuple, the same annotation, appended to the ring AND to a
   bounded store of its own (``KEPT_RECORDS``) that the hot path never
   writes, so the start is still there after an hour of serving
   (``startup()``). Once full the store keeps what it has, the start
   being what it is for, and counts what it refuses. ``span()`` and
   ``complete()`` know nothing of it.

``t0`` is a ``time.perf_counter`` stamp; ``parent`` is the ``t0`` of the
span that caused this one (None at the top); ``nums`` is a small tuple
of numbers whose meaning ``SPAN_NUMS`` gives per span name. Exported
timestamps are Unix-epoch microseconds (``perf_counter`` deltas pinned
to ``time.time`` at construction) so per-rank traces from different
processes merge onto one timeline (scripts/trace_merge.py).
"""

from __future__ import annotations

import contextlib
import json
import os
import threading
import time
from typing import Any, Optional

from jax import monitoring

# The profiler's own annotation: written into the ``.xplane.pb`` while a
# session is open, dropped by the runtime otherwise.
from jax.profiler import TraceAnnotation

from ddp_tpu.utils.metrics import StatSummary

# Env vars the launcher/child processes use to switch tracing on
# without plumbing flags through every worker signature.
TRACE_DIR_ENV = "DDP_TPU_TRACE_DIR"
RING_EVENTS_ENV = "DDP_TPU_TRACE_RING_EVENTS"

DEFAULT_RING_EVENTS = 65536
MAX_SUMMARY_NAMES = 256
# Room for a start: JAX reports a trace of EVERY function it traces
# (``jnp``'s own are ``jit``s too, once a layer a program), so a cell
# of the benchmark keeps 6,700-18,100 records by its first step
# (PERF.md section 5): under 6 MB when full.
KEPT_RECORDS = 32768
# A ``compile.*`` record briefer than this is counted, not listed, in
# the ``/statusz`` view (``startup()`` returns every one).
BRIEF_COMPILE_S = 1e-3

# Canonical per-rank trace filename (the launcher writes one per rank;
# trace_merge globs this pattern).
RANK_TRACE_FILENAME = "trace_rank{rank}.trace.json"


# What each span's ``nums`` hold, in order. The one table the program's
# call sites, the Perfetto export and the benchmark's readers share.
SPAN_NUMS: dict[str, tuple[str, ...]] = {
    # serve/engine.py — children carry the ``t0`` of their serve.step
    "serve.step": ("tokens", "active", "queue_depth"),
    "serve.retire": ("finished",),
    "serve.admit": ("admitted", "chunks"),
    "serve.prefill_chunk": ("rid", "slot", "start", "width", "final"),
    "serve.decode": ("lanes", "rows_attended"),
    # where a lane holds K/V of more than one kind (models/sambay.py),
    # where its ``serve.decode`` ends, of no duration and under the
    # same parent: ring rows and shared rows that step read, summed
    # over the reading layers, and the live lanes among its ``lanes``
    "serve.decode_rows": ("ring_rows", "shared_rows", "live_lanes"),
    "serve.spec_verify": ("lanes", "drafted", "accepted"),
    # the dispatch of one forward over every lane's block; unmasked and
    # committed are the last FETCHED report's (a step or two behind)
    "serve.block_step": ("lanes", "unmasked", "committed"),
    "serve.sample": ("tokens",),
    # serve/server.py — one event per request, at hand-back
    "server.request": ("rid", "lock_wait_s", "pickup_s", "poll_wait_s"),
    # data/loader.py, train/trainer.py
    "data.next_batch": ("rows",),
    "train.dispatch": (),
    # parallel/ddp.py — one per compile of the train step (lower +
    # compile seconds): the gradient all-reduces of the compiled step,
    # how many are asynchronous start/done pairs, how many start before
    # the last backward kernel, how many have backward-pass compute
    # between start and done, and the bytes of the first two
    "train.compile": ("reduces", "asynchronous", "start_in_backward",
                      "under_backward", "reduce_bytes",
                      "asynchronous_bytes"),
    # ops/flash.py — one per traced ``pallas_call`` of a flash training
    # kernel (trace time, zero duration; a compiled step leaves none):
    # the grid steps a (batch·head) visits, how many of them run the
    # masked program, how many are dead (visited, nothing computed),
    # and where its operands lie: ``projection`` (the fused qkv matmul's
    # output, read and written in place), ``heads_last`` ([B, T, H·D])
    # or ``transposed`` ([B·H, T, D] copies: heads narrower than 128
    # lanes); since PR 39 the kernel's ``form`` (``grid``: a grid step
    # a block pair; ``resident``: the backward's ONE kernel, a head's
    # operands in VMEM and the pairs walked inside), the grid steps a
    # (batch·head) takes, the matmuls a pair costs in this kernel (2
    # forward; backward 5 resident, 3 + 4 over the grid form's two) and
    # the VMEM bytes reckoned for it (0: the compiler's default limit).
    # ``kernel``, ``operand_dtype``, ``operand_layout`` and ``form``
    # are names, not numbers
    "flash.plan": ("kernel", "block_q", "block_k", "visited", "diagonal",
                   "dead", "operand_dtype", "operand_layout", "form",
                   "grid_steps", "matmuls_per_pair", "vmem_bytes"),
    # models/lm.py — one per traced call of the train step's next-token
    # loss (``_make_sharded_token_metrics``; trace time, zero duration):
    # the form (``fused``: head and loss ONE operation over bf16
    # operands, ``ops/lm_head.head_loss``; ``plain``: cross-entropy and
    # arg-max over the logits the head returned; a name), the rows and
    # the vocabulary of a shard, the vocabulary as the operation's
    # matmuls see it (padded to 128 when fused) and the rows it takes at
    # a time (all of them: nothing yet holds a block)
    "lm.head_plan": ("form", "rows", "vocab", "padded_vocab", "block_rows"),
    # models/lm.py — one per traced call of the train step's forward
    # (``make_lm_train_step``; trace time, zero duration): the form of
    # the dense blocks' ``ln2`` output (``held``: an array of the
    # program, written once and read by ``mlp1``'s forward and by its
    # weight-gradient matmul; ``plain``: re-derived inside each
    # consumer's fusion, as ``ln1``'s is in either form), why
    # (``one_device`` | ``data_parallel`` | ``sharded`` | ``remat``:
    # ``parallel/ddp.norm_plan``, from the mesh), the LayerNorms held
    # and their bytes a traced call.
    # ``form`` and ``reason`` are names
    "lm.norm_plan": ("form", "reason", "norms_held", "bytes_held"),
    # ops/ssm.py — one per traced ``pallas_call`` of the state update
    # (``ssm_state_update``, ``selective_state_update``) or of the
    # prefill scan (``selective_scan``), at trace time, zero duration:
    # lanes and heads of a lane a grid step holds (Mamba-1 has no
    # heads: channels). ``kernel`` and ``state_dtype`` are names
    "ssm.plan": ("kernel", "lanes_per_tile", "heads_per_tile",
                 "state_dtype"),
    # ops/decode.py — one per traced ``flash_decode`` call over rows
    # stored with heads packed on lanes (``_packed_call``), at trace
    # time, zero duration: the kernel's form (``walk``: a name; the
    # kernel copies a lane's live rows itself), grid steps a lane, rows
    # an absorb takes and rows a copy holds, copies in flight a stream,
    # the stored row's width and the lane's length, and whether a
    # lane's last copy is cut to its live rows' tiles or fetched in
    # whole absorbs (0: ``ops.decode.fetched_rows``)
    "decode.plan": ("form", "steps_per_lane", "absorb_rows", "copy_rows",
                    "copies_in_flight", "row_width", "lane_rows",
                    "last_copy_cut"),
    # models/glm_dsa.py — one per traced program of the block (trace
    # time, zero duration): which program (``prefill_first`` |
    # ``prefill_chunk`` | ``decode``: a name), its queries, the stored
    # rows a query may score, how many it attends, the layers, the keys
    # a chunk takes at a time (0: a decode step scores a lane in one),
    # the widths of a position's latent and indexer rows, and (PR 46)
    # the form its attention took (``kernel``: ops/latent_prefill.py |
    # ``walk``: the ``jnp`` walk | ``gather``: a decode step; a name)
    # with the queries the kernel scores at a time (0: no kernel)
    "dsa.plan": ("program", "queries", "keys", "top_k", "layers",
                 "key_block", "latent_row", "index_row", "attn_form",
                 "query_tile"),
    # where a lane's keys are selected (models/glm_dsa.py), where its
    # ``serve.decode`` ends, of no duration and under the same parent:
    # rows the indexer scored and rows attention read that step, summed
    # over the live lanes and the layers, and the live lanes
    "serve.decode_selected": ("rows_scored", "rows_selected", "live_lanes"),
    # ... and where its ``serve.prefill_chunk`` ends: the same two
    # counts over the chunk's REAL positions and the layers, how many
    # those are, where the chunk starts and whether it samples
    "serve.chunk_selected": ("rows_scored", "rows_selected", "tokens",
                             "start", "final"),
    # ---- the process's start: phases, kept past the ring ------------
    # (``Tracer.phase`` / ``phase_complete``; docs/OBSERVABILITY.md
    # "Start-up"). Names are names, not numbers.
    # The whole import of one of the program's heavy modules (a stamp
    # at its first line, the record at its last) or of a third-party
    # package, around the import statement in the program's module
    # that first needs it. They nest: take the UNION of intervals
    "startup.import": ("module",),
    # train/trainer.py ``Trainer.__init__`` (kind ``trainer``) and
    # serve/engine.py ``ServeEngine.__init__`` (kind ``engine``);
    # the phases below it carry its ``t0`` as ``parent``
    "startup.state": ("kind",),
    "startup.model_init": (),  # step builders and the initial state
    # ``Trainer.train()``, no parent: the checkpoint restored, or not
    "startup.checkpoint": (),
    "startup.lane_cache": (),  # the engine's lanes, allocated
    # scripts/serve.py: parameters restored or initialised
    "startup.weights": (),
    # serve/engine.py ``warmup()``: one ``warmup_program`` per call it
    # makes (``parent`` the warm-up's ``t0``; ``width`` 0 for a program
    # without one), then the wait for the device
    "startup.warmup": ("programs",),
    "startup.warmup_program": ("program", "width"),
    "startup.warmup_wait": (),
    # One record per JAX compile event, by the program's name, from the
    # one listener below (``t0`` is the event's end less its duration).
    # They carry no parent: start-up is one thread, and a reader gives
    # each to the innermost kept phase whose interval contains it. An
    # inner ``jit`` traced inside an outer one leaves a record inside
    # the outer's: take the UNION of intervals, never the sum.
    # ``fun_name`` is the name the program gave ``jax.jit`` (the
    # ``jit(...)`` JAX wraps it in for lowering and the backend taken
    # off); ``cache_hit`` is 1 where the persistent cache supplied the
    # executable
    "compile.trace": ("fun_name",),
    "compile.lower": ("fun_name",),
    "compile.backend": ("fun_name", "cache_hit"),
}
# Spans in which the host WAITS for the device (the blocking token
# fetch): host time, but not host work.
WAIT_SPANS = frozenset({"serve.sample"})


class _Span:
    """A live span: lands in the ring (and the profiler's trace) on
    ``__exit__``. ``t0`` is valid from ``__enter__`` on and is what a
    child passes as ``parent``; ``nums`` may be set inside the body."""

    __slots__ = ("_tracer", "name", "args", "parent", "nums", "t0", "_ann")

    def __init__(self, tracer, name, args, parent, nums):
        self._tracer = tracer
        self.name = name
        self.args = args
        self.parent = parent
        self.nums = nums
        self.t0 = 0.0
        self._ann = None

    def __enter__(self) -> "_Span":
        self._ann = ann = TraceAnnotation(self.name)
        ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        t0 = self.t0
        dur = time.perf_counter() - t0
        self._ann.__exit__(None, None, None)
        tracer = self._tracer
        tracer._ring.append((self.name, t0, dur, self.parent, self.nums))
        if tracer.enabled:
            tracer._record(
                "X", self.name, t0, dur,
                self.args or _nums_args(self.name, self.parent, self.nums),
            )
        return False


class _Phase(_Span):
    """A live phase: a span that is also kept (``Tracer.phase``)."""

    __slots__ = ()

    def __exit__(self, *exc) -> bool:
        t0 = self.t0
        dur = time.perf_counter() - t0
        self._ann.__exit__(None, None, None)
        self._tracer.phase_complete(
            self.name, t0, dur, self.args,
            parent=self.parent, nums=self.nums,
        )
        return False


class _Kept:
    """The records a process keeps of its start. Bounded: once full it
    keeps what it has and counts what it refuses."""

    __slots__ = ("records", "refused", "lock")

    def __init__(self):
        self.records: list[tuple] = []
        self.refused = 0
        self.lock = threading.Lock()  # compiles run on any thread


def _nums_args(name: str, parent, nums: tuple) -> Optional[dict]:
    fields = SPAN_NUMS.get(name, ())
    args = {
        (fields[i] if i < len(fields) else f"n{i}"): v
        for i, v in enumerate(nums)
    }
    if parent is not None:
        args["parent_t0"] = parent
    return args or None


class Tracer:
    """Thread-safe bounded span/instant recorder.

    Spans always reach the ring. ``enabled=False`` (the default) makes
    everything else — args, summaries, instants, counters, async
    spans — a constant-cost no-op. ``process_id`` becomes the Chrome
    ``pid`` so merged multi-rank traces show one track group per rank.
    """

    def __init__(
        self,
        *,
        enabled: bool = False,
        ring_events: int = DEFAULT_RING_EVENTS,
        process_id: int = 0,
        kept_with: Optional["Tracer"] = None,
    ):
        from collections import deque

        self.enabled = bool(enabled)
        self.process_id = int(process_id)
        self.ring_events = max(1, int(ring_events))
        # The always-on level: (name, t0, dur, parent, nums).
        self._ring: Any = deque(maxlen=self.ring_events)
        # The enabled level: full Perfetto records.
        self._events: Any = deque(maxlen=self.ring_events)
        # The kept level. A process starts once: a tracer that replaces
        # the process-global one, or stands beside it (``--trace_dir``,
        # scripts/serve.py), keeps with it (``kept_with``), so that
        # ``startup()`` reads the same from either.
        self._kept = kept_with._kept if kept_with is not None else _Kept()
        self._lock = threading.Lock()
        self._summaries: dict[str, StatSummary] = {}
        self._dropped = 0
        # perf_counter→unix pin: exported ts are absolute µs, so traces
        # from different ranks/processes align on one timeline.
        self._unix_base = time.time() - time.perf_counter()

    # ---- recording --------------------------------------------------

    def span(
        self,
        name: str,
        args: Optional[dict] = None,
        *,
        parent: Optional[float] = None,
        nums: tuple = (),
    ) -> _Span:
        """Context manager timing one span, always recorded.
        ``parent`` is the causing span's ``t0``; ``nums`` the numbers
        ``SPAN_NUMS`` names. An exported event's Perfetto ``args`` pane
        shows ``args`` (a plain dict) where given, else ``nums`` under
        their names."""
        return _Span(self, name, args, parent, nums)

    def instant(self, name: str, args: Optional[dict] = None) -> None:
        """A zero-duration marker event."""
        if not self.enabled:
            return
        now = time.perf_counter()
        self._record("i", name, now, 0.0, args)

    def counter(self, name: str, values: dict) -> None:
        """A Perfetto counter sample (``ph: "C"``): ``values`` maps
        series name → number and renders as a counter track (the HBM
        used/high-water track rides this). Free when disabled, like
        every recording path."""
        if not self.enabled:
            return
        self._record("C", name, time.perf_counter(), 0.0, values)

    def complete(
        self,
        name: str,
        start_perf: float,
        dur_s: float,
        args: Optional[dict] = None,
        *,
        parent: Optional[float] = None,
        nums: tuple = (),
    ) -> None:
        """Record a span retroactively from stamps already in hand
        (``start_perf`` from ``time.perf_counter``): measure first,
        record after. Reaches the ring like ``span()`` but not the
        profiler's trace, which takes no event after the fact."""
        dur_s = max(0.0, dur_s)
        self._ring.append((name, start_perf, dur_s, parent, nums))
        if self.enabled:
            self._record(
                "X", name, start_perf, dur_s,
                args or _nums_args(name, parent, nums),
            )

    def phase(
        self,
        name: str,
        args: Optional[dict] = None,
        *,
        parent: Optional[float] = None,
        nums: tuple = (),
    ) -> _Phase:
        """``span()`` for what the process does once on its way to its
        first productive step: recorded like a span and also KEPT
        (``startup()``). Not for a hot path: a kept record costs a
        lock."""
        return _Phase(self, name, args, parent, nums)

    def phase_complete(
        self,
        name: str,
        start_perf: float,
        dur_s: float,
        args: Optional[dict] = None,
        *,
        parent: Optional[float] = None,
        nums: tuple = (),
    ) -> None:
        """``complete()`` for a phase: into the ring, and kept."""
        self.complete(name, start_perf, dur_s, args, parent=parent,
                      nums=nums)
        kept = self._kept
        with kept.lock:
            if len(kept.records) < KEPT_RECORDS:
                kept.records.append(
                    (name, start_perf, max(0.0, dur_s), parent, nums)
                )
            else:
                kept.refused += 1

    def async_complete(
        self,
        name: str,
        start_perf: float,
        dur_s: float,
        aid: str,
        args: Optional[dict] = None,
        *,
        cat: str = "request",
    ) -> None:
        """A nestable async span (Perfetto ph ``b``/``e``) recorded
        retroactively. ``aid`` is the async id — events sharing
        (cat, id) land on one async track, which is how per-request
        lifecycle spans group across engine steps (obs/reqtrace.py).
        Free when disabled, like every recording path."""
        if not self.enabled:
            return
        self._record("b", name, start_perf, 0.0, args, aid=aid, cat=cat)
        self._record(
            "e", name, start_perf + max(0.0, dur_s), 0.0, None,
            aid=aid, cat=cat,
        )

    def async_instant(
        self,
        name: str,
        t_perf: float,
        aid: str,
        args: Optional[dict] = None,
        *,
        cat: str = "request",
    ) -> None:
        """A nestable async instant (ph ``n``) at an explicit stamp."""
        if not self.enabled:
            return
        self._record("n", name, t_perf, 0.0, args, aid=aid, cat=cat)

    def _record(
        self, ph: str, name: str, t0: float, dur_s: float,
        args: Optional[dict],
        aid: Optional[str] = None,
        cat: Optional[str] = None,
    ) -> None:
        tid = threading.get_ident()
        with self._lock:
            if len(self._events) == self.ring_events:
                self._dropped += 1
            self._events.append((ph, name, t0, dur_s, tid, args, aid, cat))
            if ph == "X":
                summ = self._summaries.get(name)
                if summ is None:
                    if len(self._summaries) >= MAX_SUMMARY_NAMES:
                        return
                    summ = self._summaries[name] = StatSummary()
                summ.add(dur_s)

    # ---- export -----------------------------------------------------

    def ring(self) -> list[tuple]:
        """The always-on spans, oldest first: ``(name, t0, dur, parent,
        nums)`` with ``t0`` on ``time.perf_counter``."""
        while True:
            try:
                return list(self._ring)
            except RuntimeError:  # appended to while copied: again
                continue

    def startup(self) -> list[tuple]:
        """The kept records, oldest first (a phase before what it
        contains): the ring's tuples, still here when the ring has
        long turned over."""
        with self._kept.lock:
            records = list(self._kept.records)
        return sorted(records, key=lambda e: (e[1], -e[2]))

    @property
    def startup_refused(self) -> int:
        """Records a full store refused: over 0, widen it."""
        return self._kept.refused

    def startup_snapshot(self) -> dict:
        """JSON-ready view of the kept records for ``/statusz``: name,
        seconds since the first record, seconds, ``nums`` under their
        ``SPAN_NUMS`` names. JAX reports a trace of every ``jnp``
        function a program calls, thousands of records of microseconds
        each: the view lists the ``compile.*`` records of
        ``BRIEF_COMPILE_S`` or more and counts the rest (``startup()`` has them all). Also
        how many records a full store refused."""
        records = self.startup()
        first = records[0][1] if records else 0.0
        listed, brief, brief_total = [], 0, 0.0
        for name, t0, dur, _, nums in records:
            if dur < BRIEF_COMPILE_S and name.startswith("compile."):
                brief, brief_total = brief + 1, brief_total + dur
                continue
            listed.append(
                {"name": name, "at_s": round(t0 - first, 6),
                 "seconds": round(dur, 6),
                 **(_nums_args(name, None, nums) or {})}
            )
        return {
            "records": listed,
            "brief_compile_records": brief,
            "brief_compile_seconds": round(brief_total, 6),
            "refused": self.startup_refused,
        }

    def _event_dicts(self, limit: Optional[int] = None) -> list[dict]:
        if self.enabled:
            with self._lock:
                raw = list(self._events)
        else:
            raw = self.ring()
        if limit is not None:
            raw = raw[-limit:]
        if not self.enabled:
            # Nothing was switched on: the ring is the trace. No thread
            # ids at this level; nums ride as args under their names.
            raw = [
                ("X", name, t0, dur, 0, _nums_args(name, parent, nums),
                 None, None)
                for name, t0, dur, parent, nums in raw
            ]
        out = []
        for ph, name, t0, dur_s, tid, args, aid, cat in raw:
            ev: dict[str, Any] = {
                "ph": ph,
                "name": name,
                "ts": round((self._unix_base + t0) * 1e6, 3),
                "pid": self.process_id,
                "tid": tid,
            }
            if ph == "X":
                ev["dur"] = round(dur_s * 1e6, 3)
            if ph == "i":
                ev["s"] = "t"  # thread-scoped instant
            if ph in ("b", "e", "n"):
                # Nestable async events: matched per (pid, cat, id) —
                # the per-request lifecycle tracks (obs/reqtrace.py).
                ev["cat"] = cat or "request"
                ev["id"] = aid
            if args:
                ev["args"] = args
            out.append(ev)
        return out

    def summaries(self) -> dict[str, dict]:
        """Per-span-name duration snapshots (seconds)."""
        with self._lock:
            names = list(self._summaries.items())
        return {n: s.snapshot(ndigits=6) for n, s in names}

    def summary_states(self) -> dict[str, dict]:
        """Mergeable per-name StatSummary states (trace_merge input)."""
        with self._lock:
            names = list(self._summaries.items())
        return {n: s.to_state() for n, s in names}

    def snapshot(self, *, limit: Optional[int] = 512) -> dict:
        """Live, JSON-ready view for the server's /statusz route."""
        return {
            "enabled": self.enabled,
            "traceEvents": self._event_dicts(limit),
            "dropped_events": self._dropped,
            "span_summaries": self.summaries(),
        }

    def trace_document(self) -> dict:
        """The full exportable Chrome/Perfetto trace object."""
        meta = [
            {
                "ph": "M",
                "name": "process_name",
                "pid": self.process_id,
                "tid": 0,
                "args": {"name": f"ddp_tpu rank {self.process_id}"},
            }
        ]
        return {
            "traceEvents": meta + self._event_dicts(),
            "displayTimeUnit": "ms",
            "ddp_tpu": {
                "rank": self.process_id,
                "dropped_events": self._dropped,
                "span_summaries": self.summary_states(),
            },
        }

    def export(self, path: str) -> str:
        """Crash-safe write of the trace document to ``path``."""
        path = os.path.abspath(path)
        os.makedirs(os.path.dirname(path), exist_ok=True)
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(self.trace_document(), f)
        os.replace(tmp, path)
        return path

    def export_to_dir(self, trace_dir: str) -> str:
        return self.export(
            os.path.join(
                trace_dir,
                RANK_TRACE_FILENAME.format(rank=self.process_id),
            )
        )


# ---- process-global tracer (launcher / env wiring) -------------------

_GLOBAL = Tracer()
_GLOBAL_LOCK = threading.Lock()


class _CompileListener:
    """The program's ONE listener on JAX's monitoring events, installed
    where the process-global tracer is made (JAX has no public call to
    take a listener off, and a second would see every compile twice). For
    each of the three duration events of a compile it keeps a record in
    the process-global tracer, under the program's name: ``t0`` is now
    less the duration (the event fires at the end of what it times).
    Nothing compiles in steady state, so a steady step never gets
    here."""

    EVENTS = {
        "/jax/core/compile/jaxpr_trace_duration": "compile.trace",
        "/jax/core/compile/jaxpr_to_mlir_module_duration": "compile.lower",
        "/jax/core/compile/backend_compile_duration": "compile.backend",
    }
    CACHE_HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        # Executables built or loaded, process-wide: what
        # ``obs/steptime.CompileCounter`` reports.
        self.count = 0
        self._lock = threading.Lock()
        # The persistent cache reports a hit INSIDE the backend event
        # it serves, on the thread that compiles.
        self._hit = threading.local()

    def install(self) -> "_CompileListener":
        monitoring.register_event_duration_secs_listener(self.on_duration)
        monitoring.register_event_listener(self.on_event)
        return self

    def on_event(self, event: str, **kw) -> None:
        if event == self.CACHE_HIT:
            self._hit.seen = True

    def on_duration(self, event: str, duration: float, **kw) -> None:
        name = self.EVENTS.get(event)
        if name is None:
            return
        # Lowering and the backend are told ``jit(<name>)``, tracing the
        # bare name: one program, one name.
        fun = str(kw.get("fun_name", ""))
        if fun.startswith("jit(") and fun.endswith(")"):
            fun = fun[4:-1]
        nums: tuple = (fun,)
        if name == "compile.backend":
            with self._lock:
                self.count += 1
            nums += (int(getattr(self._hit, "seen", False)),)
            self._hit.seen = False
        _GLOBAL.phase_complete(
            name, time.perf_counter() - duration, duration, nums=nums
        )


_COMPILES = _CompileListener().install()


def compile_count() -> int:
    """Executables built or loaded in this process so far (one
    ``backend_compile`` event each, whoever owned the compile)."""
    return _COMPILES.count


def get_tracer() -> Tracer:
    """The process-global tracer: what ``ServeEngine``, ``LMServer``,
    ``ShardedLoader`` and ``Trainer`` record into unless handed
    another. Its ring is always on; ``enabled`` only once someone
    installs an enabled one."""
    return _GLOBAL


@contextlib.contextmanager
def importing(module: str):
    """Around the import statement of a third-party package that costs
    over a quarter of a second, in the program's module that first
    needs it: ``with importing("optax"): import optax``."""
    t0 = time.perf_counter()
    try:
        yield
    finally:
        imported(module, t0)


def imported(module: str, t0: float) -> None:
    """Keep the ``startup.import`` record of ``module``, whose import
    began at ``t0`` and ends here: called at the last line of the
    program's heavy modules with a stamp from their first
    (docs/OBSERVABILITY.md "Start-up")."""
    _GLOBAL.phase_complete(
        "startup.import", t0, time.perf_counter() - t0, nums=(module,)
    )


def install_from_env(
    process_id: int = 0, *, register_atexit: bool = True
) -> Tracer:
    """Install an enabled global tracer iff ``DDP_TPU_TRACE_DIR`` is
    set.

    Called by runtime/launch.py in every spawned child so worker
    functions get per-rank trace files without new plumbing. The
    atexit export makes the trace survive crashes and watchdog aborts
    (``os._exit`` skips atexit — the watchdog dumps stacks instead;
    everything softer than that still exports).
    """
    global _GLOBAL
    trace_dir = os.environ.get(TRACE_DIR_ENV)
    if not trace_dir:
        return _GLOBAL
    ring = int(os.environ.get(RING_EVENTS_ENV, DEFAULT_RING_EVENTS))
    with _GLOBAL_LOCK:
        tracer = Tracer(
            enabled=True, ring_events=ring, process_id=process_id,
            kept_with=_GLOBAL,
        )
        _GLOBAL = tracer
    if register_atexit:
        import atexit

        atexit.register(_export_quietly, tracer, trace_dir)
    return tracer


def _export_quietly(tracer: Tracer, trace_dir: str) -> None:
    try:
        tracer.export_to_dir(trace_dir)
    except OSError:
        pass  # interpreter teardown: never turn exit into a traceback


# ---- schema validation (shared by tests and trace_merge) -------------


def validate_trace_file(path: str) -> dict:
    """Load ``path`` and check the Chrome ``trace_event`` essentials.

    Raises ``ValueError`` with a precise reason on any violation —
    this is what the smoke tier runs against an emitted trace so an
    exporter regression fails tier-1 fast, and what trace_merge runs
    on every input before merging. Returns the parsed document.
    """
    with open(path) as f:
        try:
            doc = json.load(f)
        except json.JSONDecodeError as e:
            raise ValueError(f"{path}: not valid JSON: {e}") from e
    if not isinstance(doc, dict):
        raise ValueError(f"{path}: top level must be an object")
    events = doc.get("traceEvents")
    if not isinstance(events, list):
        raise ValueError(f"{path}: traceEvents must be a list")
    for i, ev in enumerate(events):
        if not isinstance(ev, dict):
            raise ValueError(f"{path}: event {i} is not an object")
        ph = ev.get("ph")
        if not isinstance(ph, str) or not ph:
            raise ValueError(f"{path}: event {i} missing ph")
        if not isinstance(ev.get("name"), str):
            raise ValueError(f"{path}: event {i} missing name")
        if ph == "M":
            continue  # metadata events carry no timestamp
        if not isinstance(ev.get("ts"), (int, float)):
            raise ValueError(f"{path}: event {i} missing numeric ts")
        if not isinstance(ev.get("pid"), int) or not isinstance(
            ev.get("tid"), int
        ):
            raise ValueError(f"{path}: event {i} missing pid/tid")
        if ph == "X":
            dur = ev.get("dur")
            if not isinstance(dur, (int, float)) or dur < 0:
                raise ValueError(
                    f"{path}: complete event {i} needs dur >= 0"
                )
        if ph in ("b", "e", "n"):
            # Nestable async events (the per-request lifecycle spans):
            # Perfetto matches them per (pid, cat, id) — both fields
            # are load-bearing, so their absence is a schema error.
            if not isinstance(ev.get("id"), (str, int)):
                raise ValueError(
                    f"{path}: async event {i} missing id"
                )
            if not isinstance(ev.get("cat"), str):
                raise ValueError(
                    f"{path}: async event {i} missing cat"
                )
    return doc
