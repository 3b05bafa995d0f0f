"""MPMD pipeline runtime: one process per stage, p2p activations.

Every pipeline schedule in this repo so far (``parallel/pipeline.py``,
``one_f1b.py``, ``interleaved.py``, pipe_vit) is in-graph SPMD: all S
stages live in ONE XLA program, every host compiles the whole model,
and a stage re-placement means recompiling the world — which is why
PR 8's ``--elastic`` had to reject the entire pipe family. This module
is the MPMD alternative (PAPERS.md #2, ROADMAP item 2): each stage is
a separate OS process that compiles ONLY its 1/S of the model, and
activations / activation-cotangents cross stage boundaries as
point-to-point messages (``runtime/p2p.py`` — the DPKV wire
discipline applied to activation tensors) instead of in-graph
collectives.

Topology (2-stage; docs/COMPOSITIONS.md has the full diagram)::

    supervisor (no JAX) ── control TCP, JSON-lines ──┐
        │ spawn/classify-exit/backoff                │
        ├── stage 0 process: embed+stage0  ═ p2p ═ stage 1 process:
        │       fwd/bwd/update jits            stage1+LN+tied head
        └── metrics JSONL (shared, line-append atomic)

Per step, every stage walks its own column of the SAME
``schedule_1f1b`` timetable the in-graph schedule uses, so microbatch
``i``'s forward on stage k overlaps microbatch ``i-1``'s backward on
stage k+1 — the schedule is identical, only the transport changed.
The math is parity-pinned against ``make_pipe_lm_1f1b_train_step``:
strided microbatch split, loss inside the last stage, grads summed
over microbatches then divided by ``B*(T-1)``, tied-embed lookup+head
grads combined, per-leaf optimizer update, ``global_norm`` over the
full divided grad tree (assembled across stages via one sync
relay per step).

What restarts vs what recompiles: a SIGKILLed stage is respawned by
the supervisor (``classify_exit`` + backoff, ``runtime/launch.py``
machinery with PR 13's ReplicaManager as the topology template),
restores its OWN stage-sliced checkpoint (plain npz + the
``train/checkpoint.py`` manifest discipline), and recompiles only its
1/S; surviving stages roll back to the common resume step from their
own checkpoints WITHOUT recompiling (their jit caches live on), and
the replayed microbatches are regenerated deterministically from
(seed, step). Elasticity is the same mechanism: shrink/grow is a
supervisor re-placement decision (respawn with a new stage partition),
not a recompile-the-world event — which is what lifts the pipe-family
``--elastic`` rejection for the MPMD path.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import logging
import multiprocessing
import os
import queue
import shutil
import socket
import threading
import time
from typing import Dict, Optional

import numpy as np

from ddp_tpu.obs.reqtrace import (
    STEP_CAT,
    derive_span_id,
    derive_trace_id,
    encode_trace_context,
    format_trace_id,
    parse_trace_context,
)
from ddp_tpu.runtime import p2p
from ddp_tpu.runtime.chaos import ChaosEngine, stage_events
from ddp_tpu.runtime.launch import classify_exit
from ddp_tpu.utils.metrics import MetricsWriter

logger = logging.getLogger("ddp_tpu")

# NOTE: no jax / pipeline_lm imports at module top. multiprocessing
# 'spawn' children import this module while unpickling the stage
# entrypoint, BEFORE ``_stage_entry`` can pin JAX_PLATFORMS/XLA_FLAGS
# — every accelerator-touching import stays inside functions (the
# runtime/launch.py lazy-import idiom).


@dataclasses.dataclass(frozen=True)
class MPMDConfig:
    """One MPMD pipeline run: model shape + schedule + supervision.

    ``optimizer`` must be a PER-LEAF transformation (sgd/adam/adamw):
    each stage updates only its partition, so a cross-leaf global
    statistic (e.g. global-norm clipping) would need another sync
    round — rejected rather than silently wrong.
    """

    vocab_size: int = 64
    seq_len: int = 16
    d_model: int = 32
    num_heads: int = 4
    mlp_ratio: int = 4
    num_stages: int = 2
    depth_per_stage: int = 1
    num_microbatches: int = 4
    label_smoothing: float = 0.0
    batch_size: int = 8
    steps: int = 8
    seed: int = 0
    optimizer: str = "sgd"
    lr: float = 0.1
    grad_accum_steps: int = 1
    ckpt_every: int = 1
    keep_ckpts: int = 5
    max_restarts: int = 2
    restart_backoff_s: float = 0.05
    chaos: str = ""
    io_timeout_s: float = 180.0

    def __post_init__(self):
        if self.num_stages < 2:
            raise ValueError("MPMD needs >= 2 stages (else just jit)")
        if self.batch_size % self.num_microbatches:
            raise ValueError(
                f"batch_size {self.batch_size} not divisible by "
                f"num_microbatches {self.num_microbatches}"
            )
        if self.optimizer not in ("sgd", "adam", "adamw"):
            raise ValueError(
                f"optimizer {self.optimizer!r} not per-leaf — MPMD "
                "supports sgd/adam/adamw"
            )
        if self.grad_accum_steps < 1 or self.steps < 1:
            raise ValueError("steps and grad_accum_steps must be >= 1")

    def to_json(self) -> str:
        return json.dumps(dataclasses.asdict(self))

    @classmethod
    def from_json(cls, s: str) -> "MPMDConfig":
        return cls(**json.loads(s))


def batch_for_step(
    cfg: MPMDConfig, step: int, accum: int = 0
) -> np.ndarray:
    """The [B, T] int32 token batch for (step, accum chunk) — a pure
    function of the config seed, so a restarted stage replays the
    exact bytes the dead incarnation saw without any data-log."""
    rng = np.random.default_rng([cfg.seed, step, accum])
    return rng.integers(
        0, cfg.vocab_size, (cfg.batch_size, cfg.seq_len)
    ).astype(np.int32)


def _pipe_cfg(cfg: MPMDConfig):
    from ddp_tpu.models.pipeline_lm import PipeLMConfig

    return PipeLMConfig(
        vocab_size=cfg.vocab_size,
        seq_len=cfg.seq_len,
        d_model=cfg.d_model,
        num_heads=cfg.num_heads,
        mlp_ratio=cfg.mlp_ratio,
        num_stages=cfg.num_stages,
        depth_per_stage=cfg.depth_per_stage,
        num_microbatches=cfg.num_microbatches,
        label_smoothing=cfg.label_smoothing,
    )


def _make_optimizer(name: str, lr: float):
    import optax

    return {
        "sgd": optax.sgd,
        "adam": optax.adam,
        "adamw": optax.adamw,
    }[name](lr)


def stage_param_slice(cfg: MPMDConfig, k: int) -> dict:
    """Stage k's parameter partition, derived from the SAME seeded
    full init every stage runs (init is cheap at these scales and
    needs no cross-process handshake; only the slice is KEPT).

    Keys: ``stage`` everywhere; ``front`` (embed + pos) on stage 0;
    ``back`` (final LN) plus an ``embed`` BUFFER (the tied-head mirror
    of front.embed — refreshed from stage 0 each step via sync_down,
    never updated locally) on the last stage.
    """
    import jax

    from ddp_tpu.models.pipeline_lm import init_pipe_lm

    params = init_pipe_lm(_pipe_cfg(cfg), seed=cfg.seed)
    part = {"stage": jax.tree.map(lambda p: p[k], params.stages)}
    if k == 0:
        part["front"] = dict(params.front)
    if k == cfg.num_stages - 1:
        part["back"] = {"ln": params.back["ln"]}
        part["embed"] = params.front["embed"]
    return part


def _trained(part: dict) -> dict:
    """The optimizer-visible subtree: everything but the last stage's
    ``embed`` mirror (stage 0 owns the canonical tied embedding)."""
    return {k: v for k, v in part.items() if k != "embed"}


class _StagePrograms:
    """Stage k's jitted programs — the ONLY XLA this process compiles.

    Bodies are pure jnp; every host sync (np.asarray on activations,
    float() on scalars) happens in the runner's host loop between
    calls. All programs go through the xprof AOT instrumentation so
    the per-stage ledger records compile seconds for the SPMD-control
    comparison.
    """

    def __init__(self, cfg: MPMDConfig, k: int, xprof):
        import jax
        import jax.numpy as jnp
        import optax

        from ddp_tpu.models.pipeline_lm import (
            _first_fn,
            _loss_fn_factory,
            _make_last_fn,
            _stage_module,
        )

        pcfg = _pipe_cfg(cfg)
        S = cfg.num_stages
        stage = _stage_module(pcfg)
        last_fn = _make_last_fn(pcfg)
        loss_fn = _loss_fn_factory(pcfg)
        self.opt = _make_optimizer(cfg.optimizer, cfg.lr)
        opt = self.opt

        def stage_fn(sp, x):
            return stage.apply({"params": sp}, x)

        if k == 0:

            def fwd0(sp, fp, tok_mb):
                return stage_fn(sp, _first_fn(fp, tok_mb))

            def bwd0(sp, fp, tok_mb, cot):
                def f(sp_, fp_):
                    return stage_fn(sp_, _first_fn(fp_, tok_mb))

                _, vjp = jax.vjp(f, sp, fp)
                return vjp(cot)  # (g_stage, g_front)

            self.fwd = xprof.instrument(jax.jit(fwd0), f"stage{k}_fwd")
            self.bwd = xprof.instrument(jax.jit(bwd0), f"stage{k}_bwd")
        elif k < S - 1:

            def bwd_mid(sp, x, cot):
                _, vjp = jax.vjp(stage_fn, sp, x)
                return vjp(cot)  # (g_stage, g_x)

            self.fwd = xprof.instrument(
                jax.jit(stage_fn), f"stage{k}_fwd"
            )
            self.bwd = xprof.instrument(
                jax.jit(bwd_mid), f"stage{k}_bwd"
            )
        else:
            # Last stage: the loss lives INSIDE the backward (same as
            # the in-graph 1F1B kernels — the forward slot only
            # stashes the inbound activation, the vjp recomputes the
            # stage and differentiates stage∘head∘loss in one pass).
            def bwd_last(sp, lp, x, tok_mb):
                def f(sp_, lp_, x_):
                    logits = last_fn(lp_, stage_fn(sp_, x_))
                    return loss_fn(logits, tok_mb)

                (loss, correct), vjp = jax.vjp(f, sp, lp, x)
                gs, gl, gx = vjp(
                    (jnp.ones((), jnp.float32), jnp.zeros((), jnp.float32))
                )
                return loss, correct, gs, gl, gx

            self.fwd = None
            self.bwd = xprof.instrument(
                jax.jit(bwd_last), f"stage{k}_bwd"
            )

        def update(params, opt_state, grads, denom):
            # Parity contract with _apply_update: grads are SUMS over
            # microbatches (and accum chunks); divide once by denom =
            # A*B*(T-1), then per-leaf update. ``sq`` is this
            # partition's share of ||grads/denom||^2 — summed across
            # stages it reproduces the SPMD step's global_norm exactly
            # (the combined tied-embed grad is counted once, on
            # stage 0's side).
            grads = jax.tree.map(
                lambda g: g.astype(jnp.float32) / denom, grads
            )
            sq = optax.global_norm(grads) ** 2
            updates, opt_state = opt.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
            return params, opt_state, sq

        self.update = xprof.instrument(
            jax.jit(update), f"stage{k}_update"
        )


# --------------------------------------------------------------------
# Stage-sliced checkpoints: plain npz per stage + the manifest
# discipline from train/checkpoint.py. ``epoch_<N>`` holds the state
# a run needs to START step N (epoch_0 = the seeded init).
# --------------------------------------------------------------------


def _stage_ckpt_root(workdir: str, k: int) -> str:
    return os.path.join(workdir, "ck", f"stage{k}")


def _save_stage_ckpt(
    workdir: str, k: int, step: int, state_tree, keep: int
) -> None:
    import jax

    from ddp_tpu.train.checkpoint import write_manifest

    root = _stage_ckpt_root(workdir, k)
    step_dir = os.path.join(root, f"epoch_{step}")
    os.makedirs(step_dir, exist_ok=True)
    leaves = jax.tree.leaves(state_tree)
    payload = {
        f"leaf_{i}": np.asarray(leaf) for i, leaf in enumerate(leaves)
    }
    tmp = os.path.join(step_dir, f".state.npz.tmp.{os.getpid()}")
    with open(tmp, "wb") as f:
        np.savez(f, **payload)
    os.replace(tmp, os.path.join(step_dir, "state.npz"))
    write_manifest(root, step)
    # prune: keep the newest ``keep`` epochs (rollback after a peer
    # death needs a few steps of history, not all of it)
    kept = sorted(_stage_ckpt_steps(workdir, k), reverse=True)[keep:]
    for old in kept:
        shutil.rmtree(
            os.path.join(root, f"epoch_{old}"), ignore_errors=True
        )
        try:
            os.remove(os.path.join(root, f"epoch_{old}.manifest.json"))
        except OSError:
            pass


def _stage_ckpt_steps(workdir: str, k: int) -> list[int]:
    root = _stage_ckpt_root(workdir, k)
    try:
        names = os.listdir(root)
    except OSError:
        return []
    steps = []
    for name in names:
        if name.startswith("epoch_") and "." not in name:
            try:
                steps.append(int(name[len("epoch_"):]))
            except ValueError:
                continue
    return sorted(steps)


def _load_stage_ckpt(workdir: str, k: int, step: int, template):
    """One specific epoch -> state tree, or None when missing/torn
    (manifest problems or an unreadable npz both disqualify)."""
    import jax

    from ddp_tpu.train.checkpoint import verify_manifest

    root = _stage_ckpt_root(workdir, k)
    problems = verify_manifest(root, step)
    if problems:
        logger.warning(
            "mpmd stage %d: checkpoint epoch_%d fails its manifest "
            "(%s) — skipping", k, step, "; ".join(problems)
        )
        return None
    path = os.path.join(root, f"epoch_{step}", "state.npz")
    treedef = jax.tree.structure(template)
    n = treedef.num_leaves
    try:
        with np.load(path) as data:
            # commit to device arrays: np leaves carry a different
            # jit-cache signature, and a surviving stage that rolls
            # back must NOT recompile (that's the MPMD selling point)
            leaves = [
                jax.numpy.asarray(data[f"leaf_{i}"]) for i in range(n)
            ]
    except (OSError, KeyError, ValueError) as e:
        logger.warning(
            "mpmd stage %d: checkpoint epoch_%d unreadable (%s) — "
            "skipping", k, step, e
        )
        return None
    return jax.tree.unflatten(treedef, leaves)


def _restore_latest(workdir: str, k: int, template):
    """Newest intact checkpoint -> (step, state) — or (0, None)."""
    for step in sorted(_stage_ckpt_steps(workdir, k), reverse=True):
        state = _load_stage_ckpt(workdir, k, step, template)
        if state is not None:
            return step, state
    return 0, None


# --------------------------------------------------------------------
# Stage runner (child process)
# --------------------------------------------------------------------


class _Halt(Exception):
    """Supervisor ordered this generation to stop (or a peer died):
    unwind the step loop, ack, and wait for reconfiguration."""


class _Ctrl:
    """The stage side of the supervisor's JSON-lines control link.

    A reader thread turns inbound commands into a queue and raises
    the abort flag on halt/shutdown so p2p recvs blocked mid-step
    unwind promptly. Supervisor EOF means the whole run is dead —
    the stage exits rather than orphan itself.
    """

    def __init__(self, port: int):
        self._sock = socket.create_connection(
            ("127.0.0.1", port), timeout=60
        )
        self._file = self._sock.makefile("rwb")
        self._lock = threading.Lock()
        self.abort = threading.Event()
        self.cmds: "queue.Queue[dict]" = queue.Queue()
        threading.Thread(
            target=self._read_loop, name="mpmd-ctrl", daemon=True
        ).start()

    def _read_loop(self) -> None:
        try:
            for line in self._file:
                try:
                    obj = json.loads(line)
                except ValueError:
                    continue
                if obj.get("cmd") in ("halt", "shutdown"):
                    self.abort.set()
                self.cmds.put(obj)
        except OSError:
            pass
        self.abort.set()
        self.cmds.put({"cmd": "shutdown", "reason": "supervisor gone"})

    def send(self, obj: dict) -> None:
        with self._lock:
            self._file.write(json.dumps(obj).encode() + b"\n")
            self._file.flush()

    def next_cmd(self, timeout: Optional[float] = None) -> dict:
        return self.cmds.get(timeout=timeout)


class StageRunner:
    """One pipeline stage: compiles 1/S of the model, walks its
    column of the 1F1B timetable, speaks p2p to its neighbors and
    JSON-lines to the supervisor."""

    def __init__(
        self,
        cfg: MPMDConfig,
        k: int,
        workdir: str,
        metrics_path: Optional[str],
        ctrl_port: int,
    ):
        self.cfg = cfg
        self.k = k
        self.workdir = workdir
        self.metrics_path = metrics_path
        self.ctrl_port = ctrl_port
        self.up: Optional[p2p.Channel] = None
        self.down: Optional[p2p.Channel] = None
        self._p2p_wait = 0.0
        # Per-step fleet-trace state (PR 19): stage 0 mints one trace
        # context per optimizer step and every ACT send carries it in
        # the wire ``meta``; downstream stages adopt from their first
        # recv of the step, so all S stages' spans share one async
        # track. (tid, own span, parent span) or None (not adopted /
        # tracing off — the None path sends byte-identical frames).
        self._step_trace: Optional[tuple] = None
        self._trace_seed = 0
        self._ext: Dict[str, tuple] = {}  # phase extents for spans

    # ---- plumbing ----------------------------------------------------

    def _mark(self, key: str, t0: float, t1: float) -> None:
        e = self._ext.get(key)
        self._ext[key] = (
            (min(t0, e[0]), max(t1, e[1])) if e else (t0, t1)
        )

    def _trace_meta(self) -> Optional[dict]:
        """The ACT sends' wire ``meta`` — the step's context line when
        this stage holds one, else None (``meta=None`` serializes as
        the empty dict every untraced frame already carries)."""
        st = self._step_trace
        if st is None or not self.tracer.enabled:
            return None
        return {"trace": encode_trace_context(st[0], st[1], st[2])}

    def _recv(self, ch: p2p.Channel, kind: str, step: int, mb: int):
        t0 = time.perf_counter()
        with self.tracer.span(
            "pipeline.p2p_wait",
            {"stage": self.k, "kind": kind, "step": step, "mb": mb},
        ):
            msg = ch.recv(
                kind, step, mb,
                abort=self.ctrl.abort,
                timeout=self.cfg.io_timeout_s,
            )
        t1 = time.perf_counter()
        self._p2p_wait += t1 - t0
        self._mark("p2p_wait", t0, t1)
        if self.tracer.enabled and self._step_trace is None:
            # Adopt the upstream step context: our own span is salted
            # with the stage index, the sender's span becomes parent.
            ctx = parse_trace_context(
                (getattr(msg, "meta", None) or {}).get("trace")
            )
            if ctx is not None:
                self._step_trace = (
                    ctx[0], derive_span_id(ctx[0], self.k), ctx[1]
                )
        return msg

    def _open_links(self, endpoints: Dict[str, int], gen: int) -> None:
        """Accept the upstream dial first, then dial downstream: the
        chain establishes 0→1→…→S-1 without a thundering herd. Every
        connection opens with a hello carrying (generation, stage) so
        stale dials from a dead generation are rejected, not consumed.
        """
        cfg = self.cfg
        if self.k > 0:
            deadline = time.monotonic() + cfg.io_timeout_s
            while True:
                conn = self.listener.accept(
                    abort=self.ctrl.abort,
                    timeout=max(0.1, deadline - time.monotonic()),
                )
                ch = p2p.Channel(conn)
                try:
                    hello = ch.recv(
                        p2p.KIND_HELLO, 0, p2p.NO_MICROBATCH,
                        abort=self.ctrl.abort, timeout=10.0,
                    )
                except (p2p.P2PWireError, p2p.PeerGone):
                    ch.close()
                    continue
                if (
                    hello.meta.get("generation") == gen
                    and hello.meta.get("stage") == self.k - 1
                ):
                    self.up = ch
                    break
                ch.close()  # stale backlog from an old generation
        if self.k < cfg.num_stages - 1:
            port = int(endpoints[str(self.k + 1)])
            self.down = p2p.Channel(
                p2p.dial(
                    "127.0.0.1", port,
                    abort=self.ctrl.abort, timeout=cfg.io_timeout_s,
                )
            )
            self.down.send(
                p2p.KIND_HELLO, 0, p2p.NO_MICROBATCH, {},
                meta={"generation": gen, "stage": self.k},
            )

    def _close_links(self) -> None:
        for ch in (self.up, self.down):
            if ch is not None:
                ch.close()
        self.up = self.down = None

    # ---- one optimizer step ------------------------------------------

    def _run_step(self, step: int) -> None:
        import jax
        import jax.numpy as jnp

        cfg = self.cfg
        k = self.k
        S, M, A = cfg.num_stages, cfg.num_microbatches, cfg.grad_accum_steps
        first, last = k == 0, k == S - 1
        sched = self.sched
        tr = self.tracer
        t_start = time.perf_counter()
        self._p2p_wait = 0.0
        self._ext = {}
        if tr.enabled and first:
            tid = derive_trace_id(self._trace_seed, step)
            self._step_trace = (tid, derive_span_id(tid, 0), 0)
        elif not first:
            self._step_trace = None  # adopted from the first recv
        fwd_s = bwd_s = upd_s = 0.0
        acc: dict = {}
        loss_sum = 0.0
        correct = 0.0

        def add(key, tree):
            acc[key] = (
                tree if key not in acc
                else jax.tree.map(jnp.add, acc[key], tree)
            )

        for a in range(A):
            # distinct wire step id per accum chunk: the out-of-order
            # guard stays exact even when (step, mb) repeats
            ws = step * A + a
            tokens = batch_for_step(cfg, step, a)
            mbs = [
                np.ascontiguousarray(tokens[m::M]) for m in range(M)
            ]
            stash: Dict[int, np.ndarray] = {}
            for t in range(sched.n_slots):
                op = int(sched.op[t, k])
                m = int(sched.mb[t, k])
                if op == _FWD:
                    if first:
                        with tr.span(
                            "pipeline.fwd",
                            {"stage": k, "step": step, "mb": m},
                        ):
                            t0 = time.perf_counter()
                            y = np.asarray(
                                self.progs.fwd(
                                    self.part["stage"],
                                    self.part["front"],
                                    mbs[m],
                                )
                            )
                            t1 = time.perf_counter()
                            fwd_s += t1 - t0
                            self._mark("fwd", t0, t1)
                        self.down.send(
                            p2p.KIND_ACT, ws, m, {"x": y},
                            meta=self._trace_meta(),
                        )
                    elif not last:
                        x = self._recv(
                            self.up, p2p.KIND_ACT, ws, m
                        ).arrays["x"]
                        stash[m] = x
                        with tr.span(
                            "pipeline.fwd",
                            {"stage": k, "step": step, "mb": m},
                        ):
                            t0 = time.perf_counter()
                            y = np.asarray(
                                self.progs.fwd(self.part["stage"], x)
                            )
                            t1 = time.perf_counter()
                            fwd_s += t1 - t0
                            self._mark("fwd", t0, t1)
                        self.down.send(
                            p2p.KIND_ACT, ws, m, {"x": y},
                            meta=self._trace_meta(),
                        )
                    else:
                        # last stage's forward slot is recv+stash only
                        # — the bwd vjp recomputes the stage with the
                        # loss attached (in-graph-1F1B parity)
                        stash[m] = self._recv(
                            self.up, p2p.KIND_ACT, ws, m
                        ).arrays["x"]
                elif op == _BWD:
                    if last:
                        x = stash.pop(m)
                        lp = {
                            "ln": self.part["back"]["ln"],
                            "embed": self.part["embed"],
                        }
                        with tr.span(
                            "pipeline.bwd",
                            {"stage": k, "step": step, "mb": m},
                        ):
                            t0 = time.perf_counter()
                            loss, corr, gs, gl, gx = self.progs.bwd(
                                self.part["stage"], lp, x, mbs[m]
                            )
                            gx = np.asarray(gx)
                            t1 = time.perf_counter()
                            bwd_s += t1 - t0
                            self._mark("bwd", t0, t1)
                        self.up.send(p2p.KIND_COT, ws, m, {"g": gx})
                        loss_sum += float(loss)
                        correct += float(corr)
                        add("stage", gs)
                        add("ln", gl["ln"])
                        add("embed", gl["embed"])
                    elif first:
                        g = self._recv(
                            self.down, p2p.KIND_COT, ws, m
                        ).arrays["g"]
                        with tr.span(
                            "pipeline.bwd",
                            {"stage": k, "step": step, "mb": m},
                        ):
                            t0 = time.perf_counter()
                            gs, gf = self.progs.bwd(
                                self.part["stage"],
                                self.part["front"],
                                mbs[m],
                                g,
                            )
                            jax.block_until_ready(gs)
                            t1 = time.perf_counter()
                            bwd_s += t1 - t0
                            self._mark("bwd", t0, t1)
                        add("stage", gs)
                        add("front", gf)
                    else:
                        g = self._recv(
                            self.down, p2p.KIND_COT, ws, m
                        ).arrays["g"]
                        x = stash.pop(m)
                        with tr.span(
                            "pipeline.bwd",
                            {"stage": k, "step": step, "mb": m},
                        ):
                            t0 = time.perf_counter()
                            gs, gx = self.progs.bwd(
                                self.part["stage"], x, g
                            )
                            gx = np.asarray(gx)
                            t1 = time.perf_counter()
                            bwd_s += t1 - t0
                            self._mark("bwd", t0, t1)
                        self.up.send(p2p.KIND_COT, ws, m, {"g": gx})
                        add("stage", gs)
            if stash:
                raise RuntimeError(
                    f"stage {k}: {len(stash)} unconsumed activations"
                )

        # ---- end-of-step sync relay + update -------------------------
        # sync_up (last → 0): step scalars + tied-embed head grad +
        # accumulated grad-norm share. sync_down (0 → last): the
        # UPDATED embedding (the mirror is replaced, not re-derived —
        # no drift) + the total grad norm.
        denom = np.float32(
            A * cfg.batch_size * (cfg.seq_len - 1)
        )
        f32 = lambda v: np.asarray(v, np.float32)  # noqa: E731
        t0 = time.perf_counter()
        if last:
            grads = {"stage": acc["stage"], "back": {"ln": acc["ln"]}}
            trained = _trained(self.part)
            new_p, self.opt_state, sq = self.progs.update(
                trained, self.opt_state, grads, denom
            )
            sq = float(sq)
            upd_s += time.perf_counter() - t0
            self.up.send(
                p2p.KIND_SYNC_UP, step, p2p.NO_MICROBATCH,
                {
                    "loss_sum": f32(loss_sum),
                    "correct": f32(correct),
                    "sq": f32(sq),
                    "embed_grad": np.asarray(acc["embed"]),
                },
            )
            msg = self._recv(
                self.up, p2p.KIND_SYNC_DOWN, step, p2p.NO_MICROBATCH
            )
            self.part = dict(new_p)
            # commit to a device array: a raw wire ndarray has a
            # different jit-cache signature (sharding=None) and would
            # recompile bwd every generation of the mirror
            self.part["embed"] = jnp.asarray(msg.arrays["embed"])
            grad_norm = float(msg.arrays["grad_norm"])
        elif first:
            msg = self._recv(
                self.down, p2p.KIND_SYNC_UP, step, p2p.NO_MICROBATCH
            )
            loss_sum = float(msg.arrays["loss_sum"])
            correct = float(msg.arrays["correct"])
            gf = dict(acc["front"])
            # tied embedding: lookup grad (here) + head grad (last
            # stage) — combined ONCE, on the canonical copy
            gf["embed"] = acc["front"]["embed"] + msg.arrays[
                "embed_grad"
            ]
            grads = {"stage": acc["stage"], "front": gf}
            t0 = time.perf_counter()
            self.part, self.opt_state, sq = self.progs.update(
                self.part, self.opt_state, grads, denom
            )
            sq = float(sq)
            upd_s += time.perf_counter() - t0
            grad_norm = float(
                np.sqrt(sq + float(msg.arrays["sq"]))
            )
            self.down.send(
                p2p.KIND_SYNC_DOWN, step, p2p.NO_MICROBATCH,
                {
                    "embed": np.asarray(self.part["front"]["embed"]),
                    "grad_norm": f32(grad_norm),
                },
            )
        else:
            up_msg = self._recv(
                self.down, p2p.KIND_SYNC_UP, step, p2p.NO_MICROBATCH
            )
            loss_sum = float(up_msg.arrays["loss_sum"])
            correct = float(up_msg.arrays["correct"])
            grads = {"stage": acc["stage"]}
            t0 = time.perf_counter()
            self.part, self.opt_state, sq = self.progs.update(
                self.part, self.opt_state, grads, denom
            )
            sq = float(sq)
            upd_s += time.perf_counter() - t0
            self.up.send(
                p2p.KIND_SYNC_UP, step, p2p.NO_MICROBATCH,
                {
                    "loss_sum": up_msg.arrays["loss_sum"],
                    "correct": up_msg.arrays["correct"],
                    "sq": f32(float(up_msg.arrays["sq"]) + sq),
                    "embed_grad": up_msg.arrays["embed_grad"],
                },
            )
            down_msg = self._recv(
                self.up, p2p.KIND_SYNC_DOWN, step, p2p.NO_MICROBATCH
            )
            self.down.send(
                p2p.KIND_SYNC_DOWN, step, p2p.NO_MICROBATCH,
                dict(down_msg.arrays),
            )
            grad_norm = float(down_msg.arrays["grad_norm"])

        wall = time.perf_counter() - t_start
        loss = loss_sum / float(denom)
        accuracy = correct / float(denom)
        self.last_metrics = {
            "loss": loss, "accuracy": accuracy, "grad_norm": grad_norm
        }
        self.mw.write(
            "step",
            step=step,
            stage=k,
            loss=loss,
            accuracy=accuracy,
            grad_norm=grad_norm,
            wall_s=round(wall, 6),
            fwd_s=round(fwd_s, 6),
            bwd_s=round(bwd_s, 6),
            update_s=round(upd_s, 6),
            p2p_wait_s=round(self._p2p_wait, 6),
            bubble_s=round(max(0.0, wall - fwd_s - bwd_s - upd_s), 6),
        )
        self._emit_step_trace(step, t_start, wall, fwd_s, bwd_s, upd_s)

    def _emit_step_trace(
        self,
        step: int,
        t_start: float,
        wall: float,
        fwd_s: float,
        bwd_s: float,
        upd_s: float,
    ) -> None:
        """The step's async spans on its fleet trace id (cat="step"):
        the MPMD analogue of the serve-side request timeline. One
        umbrella ``mpmd.step`` span per stage plus fwd/bwd/p2p-wait
        phase spans (extent = first..last occurrence of the phase,
        ``busy_s`` = the actual compute inside it) and a bubble
        instant. Non-zero ``parent`` names the upstream stage's span —
        how the merged trace hangs stage k's work off stage k-1's.
        Skipped entirely when this stage never adopted a context
        (tracing off, or a pre-trace upstream peer)."""
        tr = self.tracer
        st = self._step_trace
        if not tr.enabled or st is None:
            return
        aid = format_trace_id(st[0])
        base = {
            "stage": self.k,
            "step": step,
            "span": f"{st[1]:016x}",
            **({"parent": f"{st[2]:016x}"} if st[2] else {}),
        }
        tr.async_complete(
            "mpmd.step", t_start, wall, aid,
            {
                **base,
                "fwd_s": round(fwd_s, 6),
                "bwd_s": round(bwd_s, 6),
                "update_s": round(upd_s, 6),
                "p2p_wait_s": round(self._p2p_wait, 6),
            },
            cat=STEP_CAT,
        )
        for name, key, busy in (
            ("mpmd.fwd", "fwd", fwd_s),
            ("mpmd.bwd", "bwd", bwd_s),
            ("mpmd.p2p_wait", "p2p_wait", self._p2p_wait),
        ):
            ext = self._ext.get(key)
            if ext is not None:
                tr.async_complete(
                    name, ext[0], ext[1] - ext[0], aid,
                    {**base, "busy_s": round(busy, 6)},
                    cat=STEP_CAT,
                )
        tr.async_instant(
            "mpmd.bubble", t_start + wall, aid,
            {
                **base,
                "seconds": round(
                    max(0.0, wall - fwd_s - bwd_s - upd_s), 6
                ),
            },
            cat=STEP_CAT,
        )

    # ---- lifecycle ---------------------------------------------------

    def _state_tree(self):
        return (self.part, self.opt_state)

    def run(self) -> None:
        import jax  # env pinned by _stage_entry before this import

        from ddp_tpu.obs.tracer import get_tracer, install_from_env
        from ddp_tpu.obs.xprof import Xprof
        from ddp_tpu.parallel.one_f1b import BWD, FWD, schedule_1f1b

        global _FWD, _BWD
        _FWD, _BWD = FWD, BWD
        cfg = self.cfg
        k = self.k
        install_from_env(process_id=k)
        self.tracer = get_tracer()
        # Stage 0's per-step trace-id space: urandom-seeded so two
        # runs (or a restarted stage 0) never collide in a merged doc.
        self._trace_seed = int.from_bytes(os.urandom(8), "little")
        self.mw = MetricsWriter(self.metrics_path)
        self.sched = schedule_1f1b(cfg.num_stages, cfg.num_microbatches)
        self.xprof = Xprof(enabled=True)
        self.progs = _StagePrograms(cfg, k, self.xprof)
        self.part = stage_param_slice(cfg, k)
        self.opt_state = self.progs.opt.init(_trained(self.part))
        self.last_metrics = {}
        self.chaos = ChaosEngine(
            stage_events(cfg.chaos),
            stage=k,
            ledger_path=os.path.join(
                self.workdir, f"chaos_stage{k}.json"
            ),
        )

        committed, restored = _restore_latest(
            self.workdir, k, self._state_tree()
        )
        if restored is not None:
            self.part, self.opt_state = restored
            logger.info(
                "mpmd stage %d: restored checkpoint epoch_%d",
                k, committed,
            )
        else:
            committed = 0
            _save_stage_ckpt(
                self.workdir, k, 0, self._state_tree(), cfg.keep_ckpts
            )

        self.listener = p2p.Listener() if k > 0 else None
        self.ctrl = _Ctrl(self.ctrl_port)
        self.ctrl.send(
            {
                "hello": True,
                "stage": k,
                "pid": os.getpid(),
                "p2p_port": self.listener.port if self.listener else 0,
                "committed": committed,
            }
        )

        while True:
            cmd = self.ctrl.next_cmd()
            if cmd.get("cmd") == "shutdown":
                break
            if cmd.get("cmd") == "halt":
                # halt while idle (e.g. between configure rounds)
                self.ctrl.abort.clear()
                self.ctrl.send({"halted": k, "committed": committed})
                continue
            if cmd.get("cmd") != "configure":
                continue
            gen = int(cmd["generation"])
            resume = int(cmd["resume_step"])
            self.ctrl.abort.clear()
            if resume != committed:
                state = _load_stage_ckpt(
                    self.workdir, k, resume, self._state_tree()
                )
                if state is None:
                    raise RuntimeError(
                        f"stage {k}: told to resume at step {resume} "
                        "but no intact checkpoint for it"
                    )
                self.part, self.opt_state = state
                committed = resume
                logger.info(
                    "mpmd stage %d: rolled back to step %d (gen %d)",
                    k, resume, gen,
                )
            try:
                if committed < cfg.steps:
                    self._open_links(cmd["endpoints"], gen)
                    for step in range(committed, cfg.steps):
                        self.chaos.on_step(step)
                        self._run_step(step)
                        committed = step + 1
                        if (
                            committed % cfg.ckpt_every == 0
                            or committed == cfg.steps
                        ):
                            _save_stage_ckpt(
                                self.workdir, k, committed,
                                self._state_tree(), cfg.keep_ckpts,
                            )
                self._close_links()
                self._finish(k, committed)
                break
            except (p2p.Aborted, p2p.PeerGone) as e:
                logger.warning(
                    "mpmd stage %d: generation %d interrupted at "
                    "step %d (%s)", k, gen, committed, e,
                )
                self._close_links()
                # the supervisor owns what happens next: wait for its
                # halt (may already be queued), ack with the durable
                # step, then loop back for the next configure
                try:
                    while True:
                        nxt = self.ctrl.next_cmd(
                            timeout=cfg.io_timeout_s
                        )
                        if nxt.get("cmd") == "halt":
                            self.ctrl.abort.clear()
                            self.ctrl.send(
                                {"halted": k, "committed": committed}
                            )
                            break
                        if nxt.get("cmd") == "shutdown":
                            return
                except queue.Empty:
                    return
        self.mw.close()

    def _finish(self, k: int, committed: int) -> None:
        compile_s = self.xprof.total_compile_s
        programs = self.xprof.program_count
        ledger = {
            "stage": k,
            "compiled_programs": programs,
            "compile_s": compile_s,
            "records": self.xprof.ledger_records(),
        }
        with open(
            os.path.join(self.workdir, f"stage{k}_xprof.json"), "w"
        ) as f:
            json.dump(ledger, f, indent=1)
        self.mw.write(
            "mpmd_xprof",
            stage=k,
            compiled_programs=programs,
            compile_s=round(compile_s, 6),
        )
        final = dict(self.last_metrics)
        final.update(
            stage=k,
            steps=committed,
            compiled_programs=programs,
            compile_s=compile_s,
        )
        if k == 0:
            final["schedule_bubble_fraction"] = float(
                self.sched.bubble_fraction()
            )
        self.mw.flush()
        self.ctrl.send({"done": k, "final": final})


_FWD, _BWD = 1, 2  # rebound from one_f1b inside run() (lazy import)


def _stage_entry(
    cfg_json: str,
    stage: int,
    ctrl_port: int,
    workdir: str,
    metrics_path: Optional[str],
) -> None:
    """multiprocessing-spawn target: pin the JAX env BEFORE first jax
    use — each stage owns exactly one (CPU) device and must never
    touch a compilation cache another process is writing."""
    os.environ["JAX_PLATFORMS"] = (
        os.environ.get("JAX_PLATFORMS") or "cpu"
    )
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
    import jax

    jax.config.update("jax_platforms", os.environ["JAX_PLATFORMS"])
    jax.config.update("jax_enable_compilation_cache", False)
    logging.basicConfig(level=logging.INFO)
    cfg = MPMDConfig.from_json(cfg_json)
    StageRunner(cfg, stage, workdir, metrics_path, ctrl_port).run()


# --------------------------------------------------------------------
# Supervisor (parent process — deliberately JAX-free)
# --------------------------------------------------------------------


class _Writer:
    def __init__(self, f):
        self._f = f
        self._lock = threading.Lock()

    def send(self, obj: dict) -> bool:
        try:
            with self._lock:
                self._f.write(json.dumps(obj).encode() + b"\n")
                self._f.flush()
            return True
        except OSError:
            return False


class _CtrlServer:
    """Supervisor side of the control plane: accepts stage
    connections, funnels every inbound JSON line into one queue."""

    def __init__(self):
        self._srv = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self._srv.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._srv.bind(("127.0.0.1", 0))
        self._srv.listen(16)
        self._srv.settimeout(0.2)
        self.port = int(self._srv.getsockname()[1])
        self.events: "queue.Queue[tuple[dict, _Writer]]" = queue.Queue()
        self._closed = False
        threading.Thread(
            target=self._accept_loop, name="mpmd-accept", daemon=True
        ).start()

    def _accept_loop(self) -> None:
        while not self._closed:
            try:
                sock, _ = self._srv.accept()
            except socket.timeout:
                continue
            except OSError:
                return
            f = sock.makefile("rwb")
            writer = _Writer(f)
            threading.Thread(
                target=self._read_loop,
                args=(f, writer),
                daemon=True,
            ).start()

    def _read_loop(self, f, writer: "_Writer") -> None:
        try:
            for line in f:
                try:
                    obj = json.loads(line)
                except ValueError:
                    continue
                self.events.put((obj, writer))
        except OSError:
            pass

    def close(self) -> None:
        self._closed = True
        try:
            self._srv.close()
        except OSError:
            pass


class PipelineSupervisor:
    """Spawns one process per stage, wires the control plane, and
    owns every placement decision: initial configure, classified-exit
    restarts with backoff, and the common-resume-step rollback that
    keeps a restarted stage and its survivors on one timeline."""

    def __init__(
        self,
        cfg: MPMDConfig,
        workdir: str,
        metrics_path: Optional[str] = None,
    ):
        self.cfg = cfg
        self.workdir = workdir
        self.metrics_path = metrics_path
        os.makedirs(workdir, exist_ok=True)

    def _spawn(self, ctx, k: int, ctrl_port: int):
        proc = ctx.Process(
            target=_stage_entry,
            args=(
                self.cfg.to_json(), k, ctrl_port,
                self.workdir, self.metrics_path,
            ),
            name=f"mpmd-stage{k}",
        )
        proc.start()
        return proc

    def run(self, *, timeout_s: float = 600.0) -> dict:
        cfg = self.cfg
        S = cfg.num_stages
        t_begin = time.monotonic()
        t_end = t_begin + timeout_s
        ctx = multiprocessing.get_context("spawn")
        ctrl = _CtrlServer()
        mw = MetricsWriter(self.metrics_path)
        procs: Dict[int, object] = {}
        writers: Dict[int, _Writer] = {}
        info: Dict[int, dict] = {}
        done: Dict[int, dict] = {}
        restarts = 0
        restart_counts: Dict[int, int] = {}
        restart_log: list[dict] = []
        generation = 0

        def remaining() -> float:
            left = t_end - time.monotonic()
            if left <= 0:
                raise TimeoutError(
                    f"mpmd run exceeded {timeout_s:.0f}s"
                )
            return left

        def await_hellos(expect: set[int]) -> None:
            pending = set(expect)
            while pending:
                try:
                    obj, w = ctrl.events.get(
                        timeout=min(1.0, remaining())
                    )
                except queue.Empty:
                    for k in list(pending):
                        code = procs[k].exitcode
                        if code is not None:
                            raise RuntimeError(
                                f"stage {k} died before hello "
                                f"({classify_exit(code)})"
                            )
                    continue
                if obj.get("hello"):
                    k = int(obj["stage"])
                    writers[k] = w
                    info[k] = {
                        "p2p_port": int(obj["p2p_port"]),
                        "committed": int(obj["committed"]),
                    }
                    pending.discard(k)

        def configure(targets, resume: int, gen: int) -> None:
            endpoints = {
                str(k): info[k]["p2p_port"] for k in range(S)
            }
            for k in targets:
                writers[k].send(
                    {
                        "cmd": "configure",
                        "generation": gen,
                        "resume_step": resume,
                        "endpoints": endpoints,
                    }
                )

        def restart_round(dead: list[int]) -> None:
            nonlocal generation, restarts
            reasons = {}
            for k in dead:
                code = procs[k].exitcode
                reasons[k] = classify_exit(code)
                restarts += 1
                restart_counts[k] = restart_counts.get(k, 0) + 1
                if restarts > cfg.max_restarts:
                    raise RuntimeError(
                        f"stage {k} exceeded the restart budget "
                        f"({cfg.max_restarts}): {reasons[k]}"
                    )
            survivors = [
                k for k in range(S) if k not in dead and k not in done
            ]
            for k in survivors:
                if not writers[k].send(
                    {"cmd": "halt", "generation": generation}
                ):
                    dead.append(k)  # died while we were looking away
                    survivors.remove(k)
            acks: Dict[int, int] = {}
            while len(acks) < len(survivors):
                try:
                    obj, _ = ctrl.events.get(
                        timeout=min(1.0, remaining())
                    )
                except queue.Empty:
                    for k in list(survivors):
                        if procs[k].exitcode is not None:
                            survivors.remove(k)
                            dead.append(k)
                            restarts += 1
                            reasons[k] = classify_exit(
                                procs[k].exitcode
                            )
                            if restarts > cfg.max_restarts:
                                raise RuntimeError(
                                    "restart budget exhausted during "
                                    "halt collection"
                                )
                    continue
                if "halted" in obj:
                    acks[int(obj["halted"])] = int(obj["committed"])
            for k in dead:
                procs[k].join(timeout=10)
                delay = cfg.restart_backoff_s * (
                    2 ** (restart_counts.get(k, 1) - 1)
                )
                time.sleep(min(delay, 5.0))
                procs[k] = self._spawn(ctx, k, ctrl.port)
            await_hellos(set(dead))
            candidates = [acks[k] for k in acks] + [
                info[k]["committed"] for k in dead
            ]
            resume = min(candidates)
            if done and resume < cfg.steps:
                raise RuntimeError(
                    "a stage died after peers completed the run — "
                    f"cannot replay step {resume} without them"
                )
            generation += 1
            for k, c in acks.items():
                info[k]["committed"] = c
            configure(sorted(set(dead) | set(acks)), resume, generation)
            for k in dead:
                restart_log.append(
                    {
                        "stage": k,
                        "exit": reasons[k],
                        "resume_step": resume,
                        "generation": generation,
                    }
                )
                mw.write(
                    "mpmd_restart",
                    stage=k,
                    exit_reason=reasons[k],
                    resume_step=resume,
                    generation=generation,
                )

        try:
            for k in range(S):
                procs[k] = self._spawn(ctx, k, ctrl.port)
            await_hellos(set(range(S)))
            resume = min(info[k]["committed"] for k in range(S))
            generation = 1
            mw.write(
                "mpmd_run_start",
                stages=S,
                steps=cfg.steps,
                resume_step=resume,
                microbatches=cfg.num_microbatches,
                grad_accum_steps=cfg.grad_accum_steps,
            )
            configure(range(S), resume, generation)
            while len(done) < S:
                try:
                    obj, _ = ctrl.events.get(
                        timeout=min(0.25, remaining())
                    )
                except queue.Empty:
                    obj = None
                if obj and "done" in obj:
                    done[int(obj["done"])] = dict(obj.get("final", {}))
                dead = [
                    k for k, p in procs.items()
                    if p.exitcode is not None and k not in done
                ]
                if dead:
                    restart_round(dead)
            wall = time.monotonic() - t_begin
            final0 = done.get(0, {})
            mw.write(
                "mpmd_run",
                stages=S,
                steps=cfg.steps,
                restarts=restarts,
                wall_s=round(wall, 3),
                loss=final0.get("loss"),
                schedule_bubble_fraction=final0.get(
                    "schedule_bubble_fraction"
                ),
            )
            for k in range(S):
                if writers.get(k):
                    writers[k].send({"cmd": "shutdown"})
            return {
                "stages": S,
                "steps": cfg.steps,
                "restarts": restarts,
                "restart_log": restart_log,
                "wall_s": wall,
                "final": {str(k): done[k] for k in sorted(done)},
                "loss": final0.get("loss"),
                "accuracy": final0.get("accuracy"),
                "grad_norm": final0.get("grad_norm"),
                "schedule_bubble_fraction": final0.get(
                    "schedule_bubble_fraction"
                ),
                "workdir": self.workdir,
            }
        finally:
            for proc in procs.values():
                if proc.is_alive():
                    proc.terminate()
            for proc in procs.values():
                proc.join(timeout=10)
                if proc.is_alive():
                    proc.kill()
                    proc.join(timeout=5)
            ctrl.close()
            mw.close()


def train_mpmd(
    cfg: MPMDConfig,
    workdir: str,
    metrics_path: Optional[str] = None,
    *,
    timeout_s: float = 600.0,
) -> dict:
    """Run one MPMD pipeline training job to completion (restarts
    included) and return the supervisor's summary."""
    return PipelineSupervisor(cfg, workdir, metrics_path).run(
        timeout_s=timeout_s
    )


# --------------------------------------------------------------------
# SPMD control: the single-program in-graph 1F1B baseline the MPMD
# runtime is parity-pinned against (same seeds, same batches).
# --------------------------------------------------------------------


def run_spmd_control(cfg: MPMDConfig) -> dict:
    """In-graph 1F1B on ``num_stages`` devices of THIS process —
    loss/accuracy/grad-norm trajectory + the single-program compile
    cost the per-stage ledgers are compared against."""
    if cfg.grad_accum_steps != 1:
        raise ValueError("the SPMD control runs accum=1 only")
    import jax
    import jax.numpy as jnp

    from ddp_tpu.models.pipeline_lm import (
        create_pipe_lm_state,
        make_pipe_lm_1f1b_train_step,
    )
    from ddp_tpu.obs.xprof import Xprof
    from ddp_tpu.runtime.mesh import MeshSpec, make_mesh

    S = cfg.num_stages
    devices = jax.devices()
    if len(devices) < S:
        raise RuntimeError(
            f"SPMD control needs {S} devices, have {len(devices)} "
            "(set --xla_force_host_platform_device_count)"
        )
    mesh = make_mesh(
        MeshSpec(data=1, pipe=S), devices=devices[:S]
    )
    pcfg = _pipe_cfg(cfg)
    opt = _make_optimizer(cfg.optimizer, cfg.lr)
    state = create_pipe_lm_state(pcfg, opt, mesh, seed=cfg.seed)
    step_fn = make_pipe_lm_1f1b_train_step(
        pcfg, opt, mesh, donate=False
    )
    xprof = Xprof(enabled=True)
    fn = xprof.instrument(step_fn, "spmd_1f1b")
    losses, accs, gns, times = [], [], [], []
    for step in range(cfg.steps):
        tokens = jnp.asarray(batch_for_step(cfg, step, 0))
        t0 = time.perf_counter()
        state, metrics = fn(state, tokens)
        losses.append(float(metrics.loss))
        times.append(time.perf_counter() - t0)
        accs.append(float(metrics.accuracy))
        gns.append(float(metrics.grad_norm))
    return {
        "losses": losses,
        "accuracies": accs,
        "grad_norms": gns,
        "step_s": times,
        "compiled_programs": xprof.program_count,
        "compile_s": xprof.total_compile_s,
    }


# --------------------------------------------------------------------
# CLI: supervisor mode by default; --control runs the SPMD baseline
# in-process.
# --------------------------------------------------------------------


def _add_cfg_args(ap: argparse.ArgumentParser) -> None:
    d = MPMDConfig()
    ap.add_argument("--vocab_size", type=int, default=d.vocab_size)
    ap.add_argument("--seq_len", type=int, default=d.seq_len)
    ap.add_argument("--d_model", type=int, default=d.d_model)
    ap.add_argument("--num_heads", type=int, default=d.num_heads)
    ap.add_argument("--mlp_ratio", type=int, default=d.mlp_ratio)
    ap.add_argument("--stages", type=int, default=d.num_stages)
    ap.add_argument(
        "--depth_per_stage", type=int, default=d.depth_per_stage
    )
    ap.add_argument(
        "--microbatches", type=int, default=d.num_microbatches
    )
    ap.add_argument("--batch_size", type=int, default=d.batch_size)
    ap.add_argument("--steps", type=int, default=d.steps)
    ap.add_argument("--seed", type=int, default=d.seed)
    ap.add_argument("--optimizer", default=d.optimizer)
    ap.add_argument("--lr", type=float, default=d.lr)
    ap.add_argument(
        "--grad_accum_steps", type=int, default=d.grad_accum_steps
    )
    ap.add_argument("--ckpt_every", type=int, default=d.ckpt_every)
    ap.add_argument(
        "--max_restarts", type=int, default=d.max_restarts
    )
    ap.add_argument("--chaos", default="")


def _cfg_from_args(args) -> MPMDConfig:
    return MPMDConfig(
        vocab_size=args.vocab_size,
        seq_len=args.seq_len,
        d_model=args.d_model,
        num_heads=args.num_heads,
        mlp_ratio=args.mlp_ratio,
        num_stages=args.stages,
        depth_per_stage=args.depth_per_stage,
        num_microbatches=args.microbatches,
        batch_size=args.batch_size,
        steps=args.steps,
        seed=args.seed,
        optimizer=args.optimizer,
        lr=args.lr,
        grad_accum_steps=args.grad_accum_steps,
        ckpt_every=args.ckpt_every,
        max_restarts=args.max_restarts,
        chaos=args.chaos,
    )


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m ddp_tpu.parallel.mpmd",
        description="MPMD pipeline runtime (one process per stage)",
    )
    _add_cfg_args(ap)
    ap.add_argument(
        "--workdir", default="/tmp/ddp_tpu_mpmd",
        help="checkpoints + ledgers + chaos state",
    )
    ap.add_argument("--metrics_file", default=None)
    ap.add_argument(
        "--timeout_s", type=float, default=600.0,
        help="supervisor wall-clock budget",
    )
    ap.add_argument(
        "--control", action="store_true",
        help="run the in-graph SPMD 1F1B baseline instead (needs "
        "--stages emulated devices in THIS process)",
    )
    ap.add_argument(
        "--json", default=None, metavar="PATH",
        help="also write the result object to PATH",
    )
    args = ap.parse_args(argv)
    logging.basicConfig(level=logging.INFO)
    cfg = _cfg_from_args(args)
    if args.control:
        result = run_spmd_control(cfg)
    else:
        result = train_mpmd(
            cfg,
            args.workdir,
            args.metrics_file,
            timeout_s=args.timeout_s,
        )
    out = json.dumps(result)
    print(out)
    if args.json:
        with open(args.json, "w") as f:
            f.write(out + "\n")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
