"""Step-time attribution: where did this training step's wall time go?

A JAX training step has three host-observable segments:

- **input wait** — the time ``next()`` on the loader blocks before the
  batch exists on the host (data pipeline stall);
- **dispatch** — the time the (async) step call takes to RETURN: under
  normal operation this is trace/lowering-cache lookup plus enqueue
  (sub-ms); a recompile or a full device pipeline shows up here;
- **device compute** — ``block_until_ready`` on a step output after
  dispatch returns: the device-side cost of the step (plus any queue
  ahead of it).

Attribution deliberately synchronizes every step (the bounded-inflight
overlap the trainer normally runs is what it measures AWAY), so it is
a diagnosis mode, not the default — enabled by ``--trace_dir`` and
costing nothing when off (``StepAttributor(enabled=False)`` hands back
the caller's iterator unchanged and ``on_step`` returns immediately).

Recompiles are counted process-wide via ``jax.monitoring`` compile
events (one ``backend_compile`` per executable built or loaded: the
tracer's one listener, ``obs/tracer.compile_count``), not per-function
``_cache_size()`` probes: the trainer's step may be a lambda over a
jitted inner function, and a *process-level* counter also catches
compiles hiding in eval, checkpoint restore, or a library call — if
the count moved during a step, that step paid for a compile, whoever
owned it.
"""

from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Any, Iterable, Iterator, Optional

from ddp_tpu.obs.tracer import Tracer, compile_count, get_tracer


class CompileCounter:
    """Process-wide XLA compile counter: a view of the program's one
    ``jax.monitoring`` listener (``obs/tracer.py``, installed with the
    process-global tracer and always on), which also keeps a record of
    every compile by program name. One increment per *compilation*;
    ``install()`` remains for the callers that asked for the listener
    when it was lazy, and has nothing left to do.
    """

    @classmethod
    def install(cls) -> None:
        pass

    @classmethod
    def installed(cls) -> bool:
        return True

    @classmethod
    def count(cls) -> int:
        return compile_count()


@dataclass
class StepTiming:
    """One step's attribution (seconds; recompiles is a count).

    ``compiles`` names the culprits when the step paid for one and an
    :class:`~ddp_tpu.obs.xprof.Xprof` instruments the hot path: one
    dict per compile with the responsible label, arg-shape signature,
    shape-diff vs the label's previous compile, and compile seconds —
    the recompile-storm sentry's count, upgraded to an attribution.
    None when nothing compiled (or nothing was instrumented).
    """

    input_wait_s: float
    dispatch_s: float
    compute_s: float
    recompiles: int
    compiles: Optional[list] = None

    @property
    def wall_s(self) -> float:
        return self.input_wait_s + self.dispatch_s + self.compute_s


@dataclass
class EpochAttribution:
    """Sums over one epoch of attributed steps."""

    steps: int = 0
    input_wait_s: float = 0.0
    dispatch_s: float = 0.0
    compute_s: float = 0.0
    recompiles: int = 0

    def add(self, t: StepTiming) -> None:
        self.steps += 1
        self.input_wait_s += t.input_wait_s
        self.dispatch_s += t.dispatch_s
        self.compute_s += t.compute_s
        self.recompiles += t.recompiles


class StepAttributor:
    """Per-step input-wait / dispatch / compute / recompile splitter.

    Usage (the trainer's host loop)::

        for batch in attr.batches(loader.epoch(e)):
            state, metrics = train_step(state, ...)
            timing = attr.on_step(metrics.loss)  # None when disabled

    ``batches`` times the gap between iterations (input wait);
    ``on_step`` times dispatch-return vs block_until_ready and reads
    the compile-counter delta. This is the ``--trace_dir`` diagnosis
    mode: it syncs on every step by design, which no span does. The
    device wait also lands in ``tracer`` as ``step.compute``; input
    wait and dispatch are the loader's and the trainer's own spans.
    """

    def __init__(
        self,
        *,
        enabled: bool = False,
        tracer: Optional[Tracer] = None,
        xprof=None,
    ):
        self.enabled = bool(enabled)
        self.tracer = tracer if tracer is not None else get_tracer()
        # Compile attribution (obs/xprof.py): when the hot path is
        # instrumented, a step whose compile counter moved also gets
        # the ledger events that landed during it — label, shape-diff,
        # compile seconds. None/disabled adds nothing.
        self.xprof = xprof if (xprof is not None and xprof.enabled) else None
        self.epoch_totals = EpochAttribution()
        self._input_wait = 0.0
        self._fetch_end = 0.0
        self._compiles_at_fetch = 0
        self._xprof_seq_at_fetch = 0

    def batches(self, iterable: Iterable) -> Iterator:
        """Wrap a batch iterator, timing each ``next()``.

        Disabled mode returns ``iter(iterable)`` itself — no wrapper
        generator, no per-item overhead (pinned by tests).
        """
        if not self.enabled:
            return iter(iterable)
        return self._timed_iter(iterable)

    def _timed_iter(self, iterable: Iterable) -> Iterator:
        it = iter(iterable)
        while True:
            t0 = time.perf_counter()
            try:
                batch = next(it)
            except StopIteration:
                return
            self._fetch_end = time.perf_counter()
            self._input_wait = self._fetch_end - t0
            self._compiles_at_fetch = CompileCounter.count()
            if self.xprof is not None:
                self._xprof_seq_at_fetch = self.xprof.event_seq
            yield batch

    def on_step(self, sync_ref: Any) -> Optional[StepTiming]:
        """Call right after the step call returns; blocks on
        ``sync_ref`` to split dispatch from device compute."""
        if not self.enabled:
            return None
        import jax

        dispatched = time.perf_counter()
        jax.block_until_ready(sync_ref)
        done = time.perf_counter()
        timing = StepTiming(
            input_wait_s=self._input_wait,
            dispatch_s=dispatched - self._fetch_end,
            compute_s=done - dispatched,
            recompiles=CompileCounter.count() - self._compiles_at_fetch,
        )
        if timing.recompiles and self.xprof is not None:
            # The ledger events that landed during this step ARE the
            # culprits — the process counter says a compile happened,
            # the ledger says which label and what shape changed.
            self._xprof_seq_at_fetch, events = self.xprof.events_after(
                self._xprof_seq_at_fetch
            )
            timing.compiles = events or None
        self.epoch_totals.add(timing)
        tr = self.tracer
        if tr.enabled:
            # The wait for the device, a retroactive span (its stamps
            # are in hand). Input wait and dispatch are spanned where
            # they happen — the loader's ``data.next_batch``, the
            # trainer's ``train.dispatch`` — and not a second time here.
            compute_args = None
            if timing.recompiles:
                compute_args = {"recompiles": timing.recompiles}
                if timing.compiles:
                    compute_args["compiled"] = [
                        f"{e.get('label')} ({e.get('compile_time_s')}s)"
                        for e in timing.compiles
                    ]
            tr.complete(
                "step.compute", dispatched, timing.compute_s, compute_args,
            )
        # Prime for a loop body that never re-enters the iterator
        # (last batch): keep fetch_end monotone.
        self._fetch_end = done
        self._input_wait = 0.0
        self._compiles_at_fetch = CompileCounter.count()
        if self.xprof is not None:
            self._xprof_seq_at_fetch = self.xprof.event_seq
        return timing

    def finish_epoch(self) -> EpochAttribution:
        """Return and reset the epoch accumulator."""
        totals = self.epoch_totals
        self.epoch_totals = EpochAttribution()
        return totals


def dispatch_compute_split(run, *args) -> tuple[Any, float, float, int]:
    """Time one whole-epoch dispatch (the ``--fast_epoch`` path).

    Returns ``(result, dispatch_s, compute_s, recompiles)`` where
    ``result`` is whatever ``run(*args)`` returned, dispatch is the
    call-return time and compute the ``block_until_ready`` tail on its
    outputs. Per-epoch granularity is all the host can see of a
    compiled epoch — the scan body is one XLA program.
    """
    import jax

    c0 = CompileCounter.count()
    t0 = time.perf_counter()
    result = run(*args)
    t1 = time.perf_counter()
    jax.block_until_ready(result)
    t2 = time.perf_counter()
    return result, t1 - t0, t2 - t1, CompileCounter.count() - c0
