"""Run health: in-graph gradient statistics and NaN/Inf provenance.

The trainer's only divergence signals used to be a single global
``grad_norm`` scalar and a dead ``np.isfinite`` gate on the FINAL
loss — a NaN born in one layer at step 400 surfaced hours later as a
useless end-of-run number. This module makes health a first-class,
per-layer observable:

- :func:`health_stats` is a jit-fused pass over the (grads, params,
  updates) trees computing per-layer-group L2 norms, max-abs,
  non-finite element counts, and the update/param ratio — all as
  ``[G]`` arrays where ``G`` is the number of layer groups, so the
  device→host cost is a few tiny vectors, never a tree of scalars.
- :class:`HealthMonitor` retires those vectors ONE STEP BEHIND the
  dispatch (the serve engine's device-resident pattern): reading step
  N's stats blocks only until step N finished, which it has by the
  time step N+1 is dispatched — no host sync beyond the existing
  one-step-behind metrics fetch.
- **NaN provenance**: the first step whose stats show a non-finite
  gradient (or loss) is recorded with the first offending layer-group
  path, so a dead run names its layer and step instead of a final NaN.
- :func:`inject_nan` is the fault-injection hook (tests and game-day
  drills): poison one layer group's gradients at one step, inside the
  compiled graph, and assert the provenance names it.

Disabled mode is pinned free, like the tracer: ``health=False`` step
builders trace the identical graph (the health pass is a Python-level
branch at trace time), and a disabled monitor returns one cached empty
tuple per call — no jit cache entries, no growing allocations
(tests/test_health.py).

Layer grouping: a leaf's group label is the first two components of
its parameter path (``block1/attn``, ``front/embed``,
``Conv_0/kernel``) — deterministic, sorted, identical between the
traced pass and the host-side :func:`group_layout` the trainer uses to
decode the ``[G]`` vectors. "First offending layer" means first in
this sorted order among the groups that went non-finite at the
earliest bad step.
"""

from __future__ import annotations

import math
import time
from typing import Any, NamedTuple, Optional

import numpy as np

# Cached empty result for the disabled monitor (same object every
# call — the allocation-free pin).
_NO_EVENTS: tuple = ()


class NonFiniteLossError(RuntimeError):
    """The run's final loss is non-finite.

    Raised by the trainer's end-of-run gate instead of silently
    writing a degraded final record. Carries the flight-recorder dump
    path (post-mortem) and, when health stats were on, the first
    offending (layer, step) the monitor attributed.
    """

    def __init__(
        self,
        loss: float,
        *,
        dump_path: Optional[str] = None,
        first_nonfinite: Optional[tuple] = None,
    ):
        where = (
            f"; first non-finite gradient at layer "
            f"{first_nonfinite[0]!r} step {first_nonfinite[1]}"
            if first_nonfinite
            else ""
        )
        post = f"; flight recorder dump: {dump_path}" if dump_path else ""
        super().__init__(
            f"final loss is non-finite ({loss!r}){where}{post} — the "
            "run diverged; see docs/OBSERVABILITY.md §Run health"
        )
        self.loss = loss
        self.dump_path = dump_path
        self.first_nonfinite = first_nonfinite


class HealthHaltError(RuntimeError):
    """``--health_action halt``: an anomaly detector fired."""

    def __init__(self, events: list, *, dump_path: Optional[str] = None):
        names = ", ".join(sorted({e.get("detector", "?") for e in events}))
        post = f"; flight recorder dump: {dump_path}" if dump_path else ""
        super().__init__(
            f"health sentry halt: {names} at step "
            f"{events[0].get('step')}{post}"
        )
        self.events = events
        self.dump_path = dump_path


class HealthStats(NamedTuple):
    """Per-layer-group stats, each ``[G]`` in ``group_layout`` order.

    Norms are NaN-propagating on purpose (a NaN group norm IS the
    signal); ``grad_nonfinite`` counts non-finite elements exactly.
    """

    grad_norm: Any  # [G] f32 — L2 norm of the group's gradients
    grad_maxabs: Any  # [G] f32 — max |g| in the group
    grad_nonfinite: Any  # [G] int32 — non-finite element count
    param_norm: Any  # [G] f32
    update_norm: Any  # [G] f32
    update_ratio: Any  # [G] f32 — ||update|| / (||param|| + eps)


def _key_str(k) -> str:
    """One path component → plain string (DictKey/GetAttrKey/…)."""
    for attr in ("key", "name", "idx"):
        v = getattr(k, attr, None)
        if v is not None:
            return str(v)
    return str(k)


def leaf_labels(tree) -> list[str]:
    """Per-leaf group label, in ``jax.tree.leaves`` order."""
    import jax

    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return [
        "/".join(_key_str(k) for k in path[:2]) or "<root>"
        for path, _ in flat
    ]


def group_layout(tree) -> tuple[tuple[str, ...], list[int]]:
    """→ (sorted group paths, per-leaf group index).

    The single source of truth for the ``[G]`` vector layout: the
    traced :func:`health_stats` and the host-side decoder both call
    this, so the index→path mapping cannot drift.
    """
    labels = leaf_labels(tree)
    paths = tuple(sorted(set(labels)))
    idx = {p: i for i, p in enumerate(paths)}
    return paths, [idx[l] for l in labels]


def health_stats(grads, params, updates) -> HealthStats:
    """The jit-fused health pass (call inside a train step).

    Per-leaf partial reductions followed by segment-reductions into
    ``[G]`` — O(leaves) tiny ops that XLA fuses into the step; the
    only new outputs are six ``[G]`` vectors.
    """
    import jax
    import jax.numpy as jnp

    paths, gidx = group_layout(grads)
    G = len(paths)
    # Device-resident segment ids (self-lint DDP002): the layout is
    # trace-time static either way, but a host-numpy constant inside
    # the traced stats pass materializes on host first — jnp pins it
    # directly as an on-device constant.
    seg = jnp.asarray(gidx, jnp.int32)

    def seg_sqnorm(tree):
        parts = jnp.stack(
            [
                jnp.sum(jnp.square(l.astype(jnp.float32)))
                for l in jax.tree.leaves(tree)
            ]
        )
        return jnp.sqrt(jax.ops.segment_sum(parts, seg, num_segments=G))

    g_leaves = jax.tree.leaves(grads)
    maxabs = jax.ops.segment_max(
        jnp.stack(
            [jnp.max(jnp.abs(l.astype(jnp.float32))) for l in g_leaves]
        ),
        seg,
        num_segments=G,
    )
    nonfinite = jax.ops.segment_sum(
        jnp.stack(
            [
                (jnp.int32(l.size) - jnp.isfinite(l).sum().astype(jnp.int32))
                for l in g_leaves
            ]
        ),
        seg,
        num_segments=G,
    )
    gnorm = seg_sqnorm(grads)
    pnorm = seg_sqnorm(params)
    unorm = seg_sqnorm(updates)
    return HealthStats(
        grad_norm=gnorm,
        grad_maxabs=maxabs,
        grad_nonfinite=nonfinite,
        param_norm=pnorm,
        update_norm=unorm,
        update_ratio=unorm / (pnorm + 1e-12),
    )


# ---- fault injection -------------------------------------------------


def parse_inject(spec: Optional[str]) -> Optional[tuple[str, int]]:
    """``"layer/group@step"`` → ``(label, step)``; None passes through."""
    if not spec:
        return None
    label, sep, step = spec.rpartition("@")
    if not sep or not label:
        raise ValueError(
            f"--health_inject_nan wants 'layer/group@step', got {spec!r}"
        )
    return label, int(step)


def inject_nan(grads, step, spec: tuple[str, int]):
    """Poison one layer group's gradients at one step, in-graph.

    Adds a step-gated NaN to every leaf of group ``spec[0]`` when
    ``step == spec[1]`` (broadcast: the whole leaf goes NaN, exactly
    like a real overflow would propagate) and +0.0 otherwise — same
    graph shape at every step, so no recompilation per step. Unknown
    labels fail at TRACE time, naming the valid groups.
    """
    import jax
    import jax.numpy as jnp

    label, at_step = spec
    labels = leaf_labels(grads)
    if label not in labels:
        raise ValueError(
            f"health_inject_nan: no layer group {label!r}; groups are "
            f"{sorted(set(labels))}"
        )
    flat, treedef = jax.tree_util.tree_flatten(grads)
    out = []
    for leaf, lbl in zip(flat, labels):
        if lbl == label:
            poison = jnp.where(
                step == at_step, jnp.float32(jnp.nan), jnp.float32(0.0)
            ).astype(leaf.dtype)
            leaf = leaf + poison
        out.append(leaf)
    return jax.tree_util.tree_unflatten(treedef, out)


# ---- host-side monitor -----------------------------------------------


class HealthMonitor:
    """One-step-behind retirement of the step's health vectors.

    ``on_step(step_no, metrics)`` enqueues the just-dispatched step's
    ``(loss, health)`` refs and ingests the PREVIOUS step's — reading
    values that are already (or nearly) computed, so the monitor never
    stalls the dispatch pipeline by more than the one-step lag. Call
    ``drain()`` at epoch end to ingest the final pending step.

    Events (provenance + sentry detections) are returned to the caller
    (the trainer applies the configured action) and simultaneously
    written to the metrics JSONL (kind ``"health"``), the trace ring
    (instant events), and the flight recorder.
    """

    def __init__(
        self,
        *,
        enabled: bool = False,
        paths: tuple[str, ...] = (),
        sentry=None,
        metrics=None,
        tracer=None,
        recorder=None,
    ):
        self.enabled = bool(enabled)
        self.paths = tuple(paths)
        self.sentry = sentry
        self.metrics = metrics
        self.tracer = tracer
        self.recorder = recorder
        self._pending: Optional[tuple] = None
        self._last_t: Optional[float] = None
        # (layer label | None, step) of the FIRST non-finite observation.
        self.first_nonfinite: Optional[tuple[Optional[str], int]] = None
        self.events_total: dict[str, int] = {}
        self.last_loss: Optional[float] = None
        self.last_grad_norm: Optional[float] = None
        if self.enabled:
            from ddp_tpu.obs.steptime import CompileCounter

            self._compiles = CompileCounter.count
            self._c_prev = self._compiles()

    def on_step(self, step_no: int, metrics) -> tuple | list:
        """Enqueue this step, ingest the previous one → its events."""
        if not self.enabled:
            return _NO_EVENTS
        now = time.perf_counter()
        dt = None if self._last_t is None else now - self._last_t
        self._last_t = now
        c = self._compiles()
        recompiles, self._c_prev = c - self._c_prev, c
        prev = self._pending
        self._pending = (
            step_no,
            metrics.loss,
            getattr(metrics, "health", None),
            dt,
            recompiles,
        )
        if prev is None:
            return _NO_EVENTS
        return self._ingest(*prev)

    def drain(self) -> tuple | list:
        """Ingest the final pending step (epoch/run end)."""
        if not self.enabled:
            return _NO_EVENTS
        # Reset the interval clock: the gap to the next epoch's first
        # step spans eval + checkpoint + epoch bookkeeping, which must
        # never reach the straggler detector as a step time.
        self._last_t = None
        if self._pending is None:
            return _NO_EVENTS
        prev, self._pending = self._pending, None
        return self._ingest(*prev)

    def _ingest(self, step_no, loss_ref, stats_ref, dt, recompiles):
        loss = float(np.asarray(loss_ref))
        self.last_loss = loss
        events: list[dict] = []
        grad_norm = None
        bad = np.array([], dtype=np.int64)
        if stats_ref is not None:
            nonfinite = np.asarray(stats_ref.grad_nonfinite)
            gnorms = np.asarray(stats_ref.grad_norm, dtype=np.float64)
            # Global norm from the group norms (NaN-propagating).
            grad_norm = float(np.sqrt(np.sum(np.square(gnorms))))
            self.last_grad_norm = grad_norm
            bad = np.flatnonzero(nonfinite > 0)
        if (len(bad) or not math.isfinite(loss)) and (
            self.first_nonfinite is None
        ):
            layer = self.paths[int(bad[0])] if len(bad) else None
            self.first_nonfinite = (layer, step_no)
            events.append(
                {
                    "detector": "nonfinite",
                    "step": step_no,
                    "layer": layer,
                    "layers": [self.paths[int(i)] for i in bad],
                    "loss": loss,
                }
            )
        if self.sentry is not None:
            events.extend(
                self.sentry.observe(
                    step_no,
                    loss=loss,
                    grad_norm=grad_norm,
                    step_time_s=dt,
                    recompiles=recompiles,
                )
            )
        for ev in events:
            d = ev.get("detector", "?")
            self.events_total[d] = self.events_total.get(d, 0) + 1
            if self.metrics is not None:
                self.metrics.write("health", **ev)
            if self.tracer is not None:
                self.tracer.instant(f"health.{d}", dict(ev))
            if self.recorder is not None:
                self.recorder.record("health", **ev)
        return events

    def snapshot(self) -> dict:
        """JSON-ready summary (the /metricsz train exposition input)."""
        out: dict[str, Any] = {"events": dict(self.events_total)}
        if self.first_nonfinite is not None:
            out["nonfinite_layer"] = self.first_nonfinite[0]
            out["nonfinite_step"] = self.first_nonfinite[1]
        if self.last_loss is not None:
            out["loss"] = self.last_loss
        if self.last_grad_norm is not None:
            out["grad_norm"] = self.last_grad_norm
        return out
