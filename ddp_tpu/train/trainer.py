"""Training orchestration — the ``ddp_train`` body (train_ddp.py:17-212).

Same observable flow as the reference's only framework function:
setup → model → data → optimizer → auto-resume → epoch/batch loop with
process-0 loss logging every ``log_interval`` batches → per-epoch
checkpoint → cleanup. Plus what the reference lacks but its north star
requires: a test-split eval loop (accuracy) and step/throughput metrics.

Architectural difference, on purpose: the reference's hot loop crosses
Python→C++ per op and syncs on a collective each backward; here the
whole step (forward, backward, all-reduce, update) is one compiled XLA
program, and the Python loop just feeds it batches and reads metrics.
"""

from __future__ import annotations

import time

_IMPORT_T0 = time.perf_counter()  # → ``startup.import``, at the last line

import dataclasses
import logging
import os
from collections import deque
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from ddp_tpu.data.loader import ShardedLoader
from ddp_tpu.data.registry import load_dataset
from ddp_tpu.models import get_model
from ddp_tpu.obs.goodput import (
    GoodputAccountant,
    mfu as _mfu,
    peak_flops_per_chip,
    train_flops_per_example,
)
from ddp_tpu.obs.health import (
    HealthHaltError,
    HealthMonitor,
    NonFiniteLossError,
    group_layout,
    parse_inject,
)
from ddp_tpu.obs.recorder import FlightRecorder, snapshot_env
from ddp_tpu.obs.sentry import AnomalySentry, SentryConfig
from ddp_tpu.obs.startup import startup_line
from ddp_tpu.obs.steptime import StepAttributor, dispatch_compute_split
from ddp_tpu.obs.tracer import Tracer, get_tracer, imported
from ddp_tpu.obs.xprof import DeviceMemorySampler, Xprof
from ddp_tpu.parallel.ddp import (
    create_train_state,
    make_eval_step,
    make_train_step,
    replicate_state,
)
from ddp_tpu.runtime import consensus, dist
from ddp_tpu.runtime.chaos import ChaosEngine
from ddp_tpu.runtime.mesh import MeshSpec, data_axes, make_mesh
from ddp_tpu.train.checkpoint import CheckpointManager
from ddp_tpu.train.config import TrainConfig
from ddp_tpu.utils.logging import setup_logging
from ddp_tpu.utils.metrics import MetricsWriter
from ddp_tpu.utils.watchdog import StepWatchdog

logger = logging.getLogger("ddp_tpu")


def _ctor_accepts(model_name: str, kwarg: str) -> bool:
    """Does the registry model's constructor take ``kwarg``?

    Signature inspection (explicit parameter or **kwargs) — a
    capability check, not exception-message sniffing, so a genuine
    TypeError from construction is never misread as "drop the kwarg".
    """
    import inspect

    from ddp_tpu.models import _REGISTRY

    ctor = _REGISTRY.get(model_name)
    if ctor is None:
        return False
    try:
        params = inspect.signature(ctor).parameters
    except (TypeError, ValueError):
        return False
    return kwarg in params or any(
        p.kind is inspect.Parameter.VAR_KEYWORD for p in params.values()
    )



def _check_ulysses_heads(num_heads: int, mesh_model: int, mesh_seq: int):
    """Ulysses re-shards each model member's LOCAL heads
    (num_heads/mesh_model) over ``seq`` — one definition for the seq
    AND pipe families so the rule cannot drift; fail at construction,
    not at first trace (parallel/ring.py)."""
    local_heads = num_heads // max(1, mesh_model)
    if local_heads % max(1, mesh_seq):
        raise ValueError(
            f"ulysses shards attention heads: {local_heads} heads per "
            f"model shard ({num_heads} total / --mesh_model "
            f"{mesh_model}) not divisible by --mesh_seq {mesh_seq}"
        )

def _check_tp_dims(config: TrainConfig) -> None:
    """Megatron TP divisibility rules, shared by the seq family and
    the whole pipe family (LM and ViT — one definition, none may
    drift): attention heads and the 4×d_model MLP hidden dim split
    over ``model``. (The ViT's mlp_dim is embed_dim × mlp_ratio,
    which coincides with 4×d_model because the trainer pins
    mlp_ratio=4; a configurable ratio must update this rule.)"""
    d_model = config.model_dim or 64
    if config.num_heads % config.mesh_model:
        raise ValueError(
            f"tensor parallelism splits attention heads: "
            f"--num_heads {config.num_heads} not divisible by "
            f"--mesh_model {config.mesh_model}"
        )
    if (d_model * 4) % config.mesh_model:
        raise ValueError(
            f"tensor parallelism splits the MLP hidden dim: "
            f"{d_model * 4} (4 × --model_dim) not divisible "
            f"by --mesh_model {config.mesh_model}"
        )


@dataclasses.dataclass
class EpochStats:
    epoch: int
    mean_loss: float
    seconds: float
    images_per_sec: float


class Trainer:
    def __init__(self, config: TrainConfig, ctx: dist.DistContext | None = None):
        # ``startup.state`` and the phase under it (obs/tracer.py) are
        # kept from stamps, after the fact: the tracer they go to is
        # made below, once the process knows its rank.
        t_init = time.perf_counter()
        self.config = config
        self.ctx = ctx or dist.setup(
            coordinator_address=config.coordinator_address,
            num_processes=config.num_processes,
            process_id=config.process_id,
            backend=config.backend,
            emulate_devices=config.emulate_devices,
        )
        setup_logging(self.ctx.process_id)
        # Observability (ddp_tpu.obs), set up first so dataset staging
        # and step-builder work below can be spanned. The tracer is the
        # process-global one, whose ring is always on (the loader's
        # ``data.next_batch`` and the step's ``train.dispatch`` land
        # there in every run); --trace_dir swaps in an ENABLED one of
        # this trainer's own (args, summaries, the Perfetto export)
        # and gates the per-step attribution.
        self.tracer = (
            Tracer(
                enabled=True,
                ring_events=config.trace_ring_events,
                process_id=self.ctx.process_id,
                kept_with=get_tracer(),
            )
            if config.trace_dir
            else get_tracer()
        )
        # Compiled-program introspection (--xprof, obs/xprof.py): the
        # hot-path jit programs are instrumented below (per family, at
        # the site where the raw jit object is in hand) so every
        # compile lands in a ledger with XLA-measured FLOPs/memory/
        # collectives, recompiles carry culprits, and the step/epoch
        # records gain the device-memory high-water. Disabled,
        # instrument() is the identity and the sampler returns {} —
        # pinned free like the tracer.
        if config.xprof and config.fast_epoch:
            raise ValueError(
                "--xprof instruments the per-step hot path, but "
                "--fast_epoch runs a whole epoch as ONE dispatch "
                "(dispatch_compute_split already reports its compile "
                "count) — drop one of the two"
            )
        self._xprof = Xprof(enabled=config.xprof)
        self._hbm = DeviceMemorySampler(enabled=config.xprof)
        self._xprof_cursor = 0
        self._comm_checked = False
        self._attr = StepAttributor(
            enabled=bool(config.trace_dir), tracer=self.tracer,
            xprof=self._xprof,
        )
        # Run health (obs/health.py): the in-graph stats pass rides the
        # step builders; the monitor/sentry are constructed after the
        # metrics writer below. Validated here so a bad combination
        # fails before any device work.
        self._health_inject = parse_inject(config.health_inject_nan)
        if self._health_inject is not None and not config.health:
            raise ValueError("--health_inject_nan requires --health")
        if config.health and config.fast_epoch:
            raise ValueError(
                "--health retires per-step gradient stats, but "
                "--fast_epoch runs a whole epoch as ONE dispatch with "
                "no per-step host visibility — drop one of the two"
            )
        if config.health and config.model == "pipe_vit":
            raise ValueError(
                "--health needs a step that computes gradient stats; "
                "the pipe_vit step does not (it reports no grad_norm "
                "either) — use pipe_lm or a non-pipe model"
            )
        # Multi-process --health_action checkpoint|halt: sentry events
        # come from HOST-local signals (wall-clock deltas, the process
        # compile counter), so one rank can see an anomaly its peers
        # don't — but ckpt.save is collective and a one-rank halt
        # leaves peers blocked in the next step's collective. Events
        # are therefore DEFERRED to the next agreement point (the same
        # deterministic cadence the preemption flag uses), where one
        # allgather (runtime/consensus.agree_any) turns "any rank saw
        # it" into "every rank acts together" — the PR-4 restriction,
        # lifted. Deferred events ride these queues:
        self._pending_halt: list[dict] = []
        self._pending_rescue: list[dict] = []
        # Keyword bundle for the step builders that support the fused
        # health pass; {} leaves unsupported builders' graphs untouched.
        hkw = (
            dict(health=True, health_inject=self._health_inject)
            if config.health
            else {}
        )

        devices = jax.devices()
        if config.num_devices > 0:
            devices = devices[: config.num_devices]
        # Sequence family: token-sharded models over the seq axis
        # (ring/Ulysses attention) with their own step/eval builders —
        # the long-context classifier and the causal LM.
        self.lm_mode = config.model == "causal_lm"
        if config.moe_experts and not (
            self.lm_mode or config.model == "pipe_lm"
        ):
            raise ValueError(
                "--moe_experts routes the causal LM's MLPs: use "
                "--model causal_lm or pipe_lm (images have "
                "--model vit_moe_tiny)"
            )
        if config.moe_experts and config.moe_every < 1:
            raise ValueError(
                f"--moe_every must be >= 1, got {config.moe_every}"
            )
        if (
            config.moe_experts
            and config.model == "pipe_lm"
            and (config.model_depth or 1) % config.moe_every
        ):
            # One stacked stage tree feeds one shard_map trace, so
            # every chunk must have the SAME routed-block positions;
            # the global every-k pattern is chunk-periodic iff k
            # divides the per-stage depth. Flat models with k not
            # dividing D (e.g. depth 6 = 2 stages x 3, moe_every 2)
            # need per-chunk param-tree structures, which stacked
            # SPMD stages cannot express — use --model causal_lm for
            # those, or pick k | model_depth (any k, including odd
            # depths: --model_depth 3 --moe_every 3, or 1).
            raise ValueError(
                "the pipelined MoE-LM needs --moe_every "
                f"({config.moe_every}) to divide --model_depth "
                f"({config.model_depth or 1}): stages must be "
                "structure-uniform for parameter stacking (the flat "
                "--model causal_lm expresses any pattern)"
            )
        self.seq_mode = config.model == "long_context" or self.lm_mode
        if config.mesh_seq > 1 and not (
            self.seq_mode or config.model == "pipe_lm"
        ):
            raise ValueError(
                "--mesh_seq shards tokens, which only the sequence "
                "models have: use --model long_context, causal_lm, or "
                "pipe_lm (PP×SP)"
            )
        # Pipeline family: the whole model rides the pipe axis under
        # GPipe / 1F1B / interleaved — the ViT (models/pipeline_vit.py)
        # and, since round 4, the causal LM (models/pipeline_lm.py,
        # which additionally composes with Megatron TP over ``model``:
        # the PP×TP layout).
        self.pipe_lm_mode = config.model == "pipe_lm"
        self.pipe_mode = config.model == "pipe_vit" or self.pipe_lm_mode
        if config.mesh_pipe > 1 and not self.pipe_mode:
            raise ValueError(
                "--mesh_pipe cuts a model into stages, which only the "
                "pipeline family has: use --model pipe_vit or pipe_lm"
            )
        if self.pipe_mode and config.mesh_pipe < 2:
            raise ValueError(
                f"--model {config.model} needs --mesh_pipe >= 2 (a "
                "1-stage pipeline is the plain step — drop the flag)"
            )
        if self.pipe_mode and (
            (config.mesh_expert > 1 and not self.pipe_lm_mode)
            or (config.mesh_seq > 1 and not self.pipe_lm_mode)
            or config.zero1
            or config.grad_accum_steps > 1
            # augment is image-family: the pipelined ViT takes it
            # (applied to the global batch before microbatching);
            # token data has nothing to crop.
            or (
                self.pipe_lm_mode
                and config.augment not in (None, "none")
            )
        ):
            raise ValueError(
                f"--model {config.model} composes with the data axis, "
                "fsdp (ZeRO-sharded stage params), tp (--mesh_model, "
                "PP×TP)"
                + (", expert (--mesh_expert, PP×EP), seq "
                   "(--mesh_seq, PP×SP — ulysses under 1f1b/"
                   "interleaved, ring under gpipe)"
                   if self.pipe_lm_mode else ", augment")
                + ", --fast_epoch, bf16, remat, label smoothing, EMA "
                "and LR schedules — not "
                + ("" if self.pipe_lm_mode else "expert/seq/")
                + "zero1, accumulation (use --num_microbatches)"
                + (", or augment" if self.pipe_lm_mode else "")
            )
        if self.pipe_mode and config.mesh_model > 1:
            _check_tp_dims(config)
        if (self.seq_mode or self.pipe_mode) and (
            config.num_heads < 1
            or (config.model_dim or 64) % config.num_heads
        ):
            # One guard for both spec-driven families (the registry
            # models fix their own head counts).
            raise ValueError(
                f"--num_heads {config.num_heads} must be >= 1 and "
                f"divide --model_dim {config.model_dim or 64}"
            )
        if config.num_kv_heads:
            if not (
                (self.seq_mode and config.model == "causal_lm")
                or self.pipe_lm_mode
            ):
                raise ValueError(
                    "--num_kv_heads (grouped-query attention) shrinks "
                    "the causal LM's generation KV cache: use --model "
                    "causal_lm or pipe_lm (or drop the flag)"
                )
            if (
                config.num_kv_heads < 1
                or config.num_heads % config.num_kv_heads
            ):
                raise ValueError(
                    f"--num_kv_heads {config.num_kv_heads} must be >= 1 "
                    f"and divide --num_heads {config.num_heads}"
                )
            if (
                config.mesh_model > 1
                and config.num_kv_heads % config.mesh_model
            ):
                raise ValueError(
                    "GQA under TP shards whole kv groups: "
                    f"--num_kv_heads {config.num_kv_heads} not "
                    f"divisible by --mesh_model {config.mesh_model}"
                )
        if self.pipe_mode and config.num_microbatches < 1:
            raise ValueError(
                f"--num_microbatches must be >= 1, got "
                f"{config.num_microbatches}"
            )
        if config.virtual_stages < 1:
            raise ValueError(
                f"--virtual_stages must be >= 1, got {config.virtual_stages}"
            )
        if config.virtual_stages > 1 and not self.pipe_mode:
            raise ValueError(
                "--virtual_stages cuts a pipelined model into chunks: "
                "use --model pipe_vit or pipe_lm (with --mesh_pipe "
                "and --pipe_schedule interleaved)"
            )
        if config.virtual_stages > 1 and config.pipe_schedule != "interleaved":
            raise ValueError(
                "--virtual_stages places multiple model chunks per "
                "device, which only the interleaved schedule streams: "
                "add --pipe_schedule interleaved"
            )
        if self.pipe_mode and config.num_microbatches % config.mesh_pipe:
            raise ValueError(
                f"--num_microbatches {config.num_microbatches} must be "
                f"a multiple of --mesh_pipe {config.mesh_pipe} (the "
                "sharded stream rests microbatch m on device m mod S)"
            )
        # Any non-data axis > 1 switches to the GSPMD step — tensor/
        # fsdp/expert sharding by annotation (parallel/spmd.py). A pure
        # data mesh keeps the explicit shard_map DDP step.
        self.use_spmd = (
            config.mesh_model > 1
            or config.mesh_fsdp > 1
            or config.mesh_expert > 1
            or config.zero1  # opt-state sharding rides the GSPMD step
        )
        # ZeRO-style weight-update sharding (--parallel zero,
        # parallel/zero.py): reduce-scatter grads, 1/N sharded
        # optimizer update, all-gather params. Validated here, before
        # any device or dataset work, so a bad combination fails with
        # the flags named.
        # Two-level pod geometry (--mesh_dcn, runtime/mesh.py): the
        # slice axis is a replica axis of the explicit shard_map
        # families — the DDP image step (flat reduction spans it) and
        # the zero step (which goes HIERARCHICAL over it). The
        # annotation-driven/pipelined/sequence paths have not earned
        # the axis yet; reject with the flags named.
        if config.mesh_dcn < 1:
            raise ValueError(
                f"--mesh_dcn must be >= 1, got {config.mesh_dcn}"
            )
        if config.mesh_dcn > 1 and (
            self.use_spmd
            or self.pipe_mode
            or self.seq_mode
            or config.fast_epoch
        ):
            raise ValueError(
                "--mesh_dcn slices the replica axes of the explicit "
                "shard_map families: the DDP image path and --parallel "
                "zero (hierarchical collectives). Drop the slice axis "
                "or the GSPMD/pipe/seq/fast_epoch flags"
            )
        self.zero_mode = config.parallel == "zero"
        # Global-norm clipping under zero is applied IN-STEP from the
        # scattered shards (psum of per-shard squared sums); the
        # optimizer is then built without the chained optax clip.
        self._zero_clip = 0.0
        if self.zero_mode:
            from ddp_tpu.train.optim import check_zero_compatible

            if config.zero1 or config.mesh_fsdp > 1 or config.mesh_expert > 1:
                raise ValueError(
                    "--parallel zero shards the update over the data "
                    "axis; fsdp/expert meshes (and --zero1) already "
                    "shard optimizer state their own way — fsdp IS "
                    "ZeRO-3 — drop the axes/flag or --parallel"
                )
            if (
                config.mesh_model > 1 or config.mesh_seq > 1
            ) and not self.lm_mode:
                raise ValueError(
                    "--parallel zero composes with model/seq axes on "
                    "--model causal_lm only (the GSPMD expression "
                    "shards buckets over data and replicates them over "
                    "the model axes); this model keeps the data axis "
                    "only"
                )
            if config.mesh_pipe > 1:
                raise ValueError(
                    "--parallel zero composes with the data axis only "
                    "(the sharded update scatters over it); drop "
                    "--mesh_pipe or --parallel"
                )
            if self.pipe_mode or (self.seq_mode and not self.lm_mode):
                raise ValueError(
                    f"--parallel zero covers the DDP image family and "
                    f"--model causal_lm; {config.model!r} keeps its "
                    "own update path"
                )
            if config.fast_epoch:
                raise ValueError(
                    "--fast_epoch scans the plain DDP step; the zero "
                    "strategy has its own hot loop — drop one"
                )
            if config.health:
                raise ValueError(
                    "--health groups gradient stats by layer path, but "
                    "--parallel zero only materializes 1/N FLAT "
                    "gradient shards (the reduced full-gradient tree "
                    "never exists) — drop one"
                )
            check_zero_compatible(
                config.optimizer,
                grad_clip_norm=config.grad_clip_norm,
                ema_decay=config.ema_decay,
            )
            self._zero_clip = config.grad_clip_norm
            if config.zero_bucket_mb <= 0:
                raise ValueError(
                    f"--zero_bucket_mb must be > 0, got "
                    f"{config.zero_bucket_mb}"
                )
        self._zero_layout = None
        # Per-step collective-payload estimate (parallel/zero.py): set
        # on the strategies whose comm story the bench compares (plain
        # DDP and zero); None elsewhere omits the metrics field. The
        # by-axis split is present exactly when the step is
        # hierarchical (dcn > 1) — flat streams keep their schema.
        self._comm_bytes: int | None = None
        self._comm_by_axis: dict | None = None
        # The once-per-run xprof cross-check compares _comm_bytes to
        # the WHOLE program's collectives — only honest when the
        # estimate covers them all. The zero×model/seq composition's
        # program also carries TP/SP activation collectives the
        # update-payload estimate deliberately omits, so the check is
        # disabled there (the estimate still stamps records).
        self._comm_check_enabled = True
        from ddp_tpu.data.augment import get_augmentation

        self.dataset = config.dataset
        if self.dataset == "auto":
            self.dataset = (
                "synthetic_seq"
                if self.seq_mode or self.pipe_lm_mode
                else "mnist"
            )
        # Round 1 walled the sequence family off from everything but
        # data+seq (VERDICT.md weak #4); round 2 lifted fsdp
        # (parallel/seq_fsdp.py), accumulation, and label smoothing;
        # round 3 lifts tensor parallelism (parallel/tp.py — Megatron
        # column/row inside the shard_map step, composing with seq and
        # fsdp) and expert parallelism for the MoE-LM (models/moe.py
        # MoEMLP all-to-all dispatch over the ``expert`` axis); round 4
        # lifts --fast_epoch for the causal LM (train/fast.py
        # make_lm_epoch_runner — the compiled-epoch dispatch over the
        # same raw step). What remains out: zero1 (subsumed by fsdp,
        # which shards moments too), the image-only augment pipeline,
        # and fast_epoch for the long-context classifier.
        if self.seq_mode and (
            config.zero1
            or (config.fast_epoch and not self.lm_mode)
            or get_augmentation(config.augment) is not None
        ):
            raise ValueError(
                f"--model {config.model} composes with data/seq/fsdp/"
                "model/expert mesh axes, accumulation, label smoothing "
                "and bf16 — but not zero1 (use --mesh_fsdp), augment"
                + (
                    ""
                    if self.lm_mode
                    else ", or --fast_epoch (causal_lm only)"
                )
            )
        if (self.seq_mode or self.pipe_lm_mode) and config.mesh_expert > 1:
            if not config.moe_experts:
                raise ValueError(
                    "--mesh_expert shards MoE expert weights: give the "
                    "LM experts with --moe_experts N (or drop the axis)"
                )
            if config.moe_experts % config.mesh_expert:
                raise ValueError(
                    f"--moe_experts {config.moe_experts} not divisible "
                    f"by --mesh_expert {config.mesh_expert}"
                )
        if self.seq_mode and config.mesh_model > 1:
            # TP×MoE composes since round 5 (the Megatron-MoE layout):
            # attention heads shard over ``model`` in routed blocks
            # too, the expert MLPs stay replicated across ``model``
            # (experts shard over --mesh_expert — EP owns the MoE
            # sharding story).
            _check_tp_dims(config)
        mesh_spec = MeshSpec(
            data=-1,
            pipe=config.mesh_pipe,
            model=config.mesh_model,
            fsdp=config.mesh_fsdp,
            expert=config.mesh_expert,
            seq=config.mesh_seq,
            dcn=config.mesh_dcn,
        )
        if config.elastic:
            # Elastic world resize (docs/ROBUSTNESS.md): this process
            # may be a relaunch of a differently-sized world. The mesh
            # is re-derived from the LIVE device count (the fixed axes
            # are the sharding contract and must still tile it), and
            # the per-shard batch below absorbs the change so the
            # recorded global batch — what a step MEANS — survives.
            if self.pipe_mode:
                raise ValueError(
                    "--elastic excludes the in-graph pipeline family: "
                    "stage params rest per-device, so a resize would "
                    "need stage re-placement, not a reshard. For an "
                    "elastic pipeline use the MPMD runtime (python -m "
                    "ddp_tpu.parallel.mpmd) — one process per stage, "
                    "per-stage restart and checkpoint-sliced resume — "
                    "or drop --elastic"
                )
            from ddp_tpu.runtime.mesh import live_world_spec

            mesh_spec = live_world_spec(mesh_spec, len(devices))
        self.mesh = make_mesh(mesh_spec, devices=devices)
        self.data_shards = int(
            np.prod([self.mesh.shape[a] for a in data_axes(self.mesh)])
        )
        # With accumulation the loader delivers k microbatches' worth at
        # once; the step splits them and applies one update.
        self.per_shard_batch = config.batch_size
        self.global_batch_size = (
            self.per_shard_batch * self.data_shards * config.grad_accum_steps
        )
        if config.elastic:
            # Honor the run's recorded global-batch contract: flags are
            # per-shard, so at a resized world the natural product above
            # would change the global batch — and with it the meaning
            # of the checkpointed step counter, the LR schedule, and
            # the mid-epoch resume markers. The sampler's shard math
            # makes the rescale exact (same sample windows per step at
            # any divisor world — data/sampler.py).
            from ddp_tpu.data.sampler import rescale_per_shard_batch
            from ddp_tpu.train.checkpoint import load_elastic_contract

            contract = load_elastic_contract(config.checkpoint_dir)
            recorded = int(contract.get("global_batch_size") or 0)
            if recorded and recorded != self.global_batch_size:
                self.per_shard_batch = rescale_per_shard_batch(
                    recorded,
                    self.data_shards,
                    grad_accum_steps=config.grad_accum_steps,
                )
                logger.warning(
                    "Elastic resize: preserving recorded global batch "
                    "%d over %d data shard(s) — per-shard batch %d -> "
                    "%d",
                    recorded,
                    self.data_shards,
                    config.batch_size,
                    self.per_shard_batch,
                )
                self.global_batch_size = recorded

        from ddp_tpu.data.registry import NUM_CLASSES
        from ddp_tpu.train.optim import make_optimizer

        if self.seq_mode:
            if config.seq_len % max(1, config.mesh_seq):
                raise ValueError(
                    f"--seq_len {config.seq_len} not divisible by "
                    f"--mesh_seq {config.mesh_seq}"
                )
            if self.lm_mode:
                from ddp_tpu.models.lm import LMSpec

                self.seq_spec = LMSpec(
                    vocab_size=config.vocab_size,
                    total_len=config.seq_len,
                    d_model=config.model_dim or 64,
                    depth=config.model_depth or 2,
                    num_heads=config.num_heads,
                    strategy=config.seq_strategy,
                    remat=config.remat,
                    num_experts=config.moe_experts,
                    moe_every=config.moe_every,
                    moe_top_k=config.moe_top_k,
                    moe_normalize_gates=config.moe_normalize_gates,
                    num_kv_heads=config.num_kv_heads,
                )
            else:
                from ddp_tpu.models.seq_transformer import (
                    SeqTransformerSpec,
                )

                self.seq_spec = SeqTransformerSpec(
                    num_classes=config.num_classes or 10,
                    total_len=config.seq_len,
                    d_in=config.seq_dim,
                    d_model=config.model_dim or 64,
                    depth=config.model_depth or 2,
                    num_heads=config.num_heads,
                    strategy=config.seq_strategy,
                    remat=config.remat,
                )
            if config.seq_strategy == "ulysses":
                _check_ulysses_heads(
                    self.seq_spec.num_heads, config.mesh_model,
                    config.mesh_seq,
                )
            self.model = None  # spec-driven; no registry module
        elif self.pipe_mode:
            # Spec built after the data split is known (patch size
            # follows the image side); no registry module.
            self.model = None
        else:
            model_kw = {}
            if config.model_depth is not None:
                model_kw["depth"] = config.model_depth
            if config.remat:
                model_kw["remat"] = True
            if self.use_spmd and _ctor_accepts(
                config.model, "attention_fn"
            ):
                # The GSPMD step partitions by annotation; a compiled
                # Mosaic custom call (the flash default on TPU) has no
                # partitioning rule there, unlike the shard_map paths
                # (DDP/seq/pipe) where Pallas is first-class. Route
                # attention through a shard_map ISLAND instead
                # (ops/attention.py gspmd_flash_attention): batch over
                # the data axes, heads over model — which resolves to
                # plain dense XLA below FLASH_MIN_LEN keys (all the
                # image family today, T≤197, where one fused einsum
                # chain wins) and to the Pallas kernel above it, so a
                # long-sequence GSPMD model keeps the kernel. On CPU
                # both branches are the dense path, unchanged.
                from ddp_tpu.ops.attention import gspmd_flash_attention

                model_kw["attention_fn"] = gspmd_flash_attention(self.mesh)
            n_classes = config.num_classes or NUM_CLASSES.get(self.dataset, 10)
            try:
                self.model = get_model(
                    config.model, num_classes=n_classes, **model_kw
                )
            except TypeError as e:
                if config.remat and "remat" in str(e):
                    raise ValueError(
                        f"--remat is not supported by model {config.model!r} "
                        "(no block stack to rematerialize)"
                    ) from e
                raise
        milestones = tuple(
            int(m) for m in config.lr_milestones.split(",") if m.strip()
        )
        self._opt_kwargs = dict(
            lr=config.lr,
            momentum=config.momentum,
            weight_decay=config.weight_decay,
            warmup_steps=config.warmup_steps,
            decay_steps=config.decay_steps,
            # In zero mode the clip moves into the sharded step (a
            # chained optax clip would read PER-SHARD norms there).
            grad_clip_norm=(
                0.0 if self.zero_mode else config.grad_clip_norm
            ),
            ema_decay=config.ema_decay,
            lr_milestones=milestones,
            lr_decay_factor=config.lr_decay_factor,
        )
        self.optimizer = make_optimizer(config.optimizer, **self._opt_kwargs)
        from ddp_tpu.train.optim import make_schedule

        # The schedule alone, for logging the current lr per step —
        # derived from the SAME kwargs the optimizer was built with so
        # the logged lr can't drift from the trained one.
        self._lr_schedule = make_schedule(
            self._opt_kwargs["lr"],
            **{
                k: self._opt_kwargs[k]
                for k in (
                    "warmup_steps", "decay_steps",
                    "lr_milestones", "lr_decay_factor",
                )
            },
        )

        token_mode = self.lm_mode or self.pipe_lm_mode
        if self.seq_mode or self.pipe_lm_mode:
            if self.dataset == "text":
                # Real data for the LM: a corpus file — raw bytes at
                # --vocab_size <= 256, BPE subwords above (the trained
                # tokenizer persists next to the checkpoints: it is
                # part of the model, and generation needs it to decode).
                if not token_mode:
                    raise ValueError(
                        "--dataset text is causal-LM data (bytes, no "
                        "class labels): use --model causal_lm or pipe_lm"
                    )
                if not config.text_file:
                    raise ValueError("--dataset text needs --text_file PATH")
                from ddp_tpu.data.text import load_text_corpus

                train_split, test_split = load_text_corpus(
                    config.text_file, config.seq_len,
                    vocab_size=config.vocab_size,
                    tokenizer_path=os.path.join(
                        config.checkpoint_dir, "tokenizer.json"
                    ),
                )
            elif self.dataset != "synthetic_seq":
                raise ValueError(
                    f"--model {config.model} trains on sequences, not "
                    f"{self.dataset!r}: use --dataset synthetic_seq, "
                    "--dataset text (or leave --dataset unset)"
                )
            else:
                from ddp_tpu.data import sequences
                from ddp_tpu.data.mnist import Split

                n = config.synthetic_size or 2048

                def seq_split(count, seed):
                    if token_mode:
                        toks = sequences.synthetic_tokens(
                            count, total_len=config.seq_len,
                            vocab_size=config.vocab_size, seed=seed,
                        )
                        # labels unused: targets are the shifted tokens
                        return Split(toks, np.zeros(count, np.int32))
                    return sequences.synthetic(
                        count, total_len=config.seq_len, d_in=config.seq_dim,
                        num_classes=self.seq_spec.num_classes, seed=seed,
                    )

                train_split = seq_split(n, config.seed)
                test_split = seq_split(max(1, n // 6), config.seed + 1)
        else:
            train_split, test_split = load_dataset(
                self.dataset,
                config.data_root,
                allow_synthetic=config.synthetic_data,
                synthetic_size=config.synthetic_size,
            )
        self.train_split, self.test_split = train_split, test_split
        self.loader = ShardedLoader(
            train_split.images,
            train_split.labels,
            self.mesh,
            self.global_batch_size,
            shuffle=config.shuffle,
            seed=config.seed,
            # The fast path never drains the loader, and the seq path
            # feeds float sequences the byte-pipeline can't serve —
            # don't spin up (or warn about) a pool that can't be used.
            num_workers=0
            if (config.fast_epoch or self.seq_mode or self.pipe_lm_mode)
            else config.num_workers,
            tracer=self.tracer,
        )
        # ``startup.model_init``: step builders and the initial state,
        # whichever family.
        t = time.perf_counter()

        compute_dtype = jnp.bfloat16 if config.compute_dtype == "bfloat16" else jnp.float32
        augment_fn = get_augmentation(config.augment)
        sample = jnp.zeros(
            (1, *train_split.images.shape[1:]), jnp.float32
        )
        if self.seq_mode:
            from ddp_tpu.parallel.ddp import TrainState

            if self.lm_mode:
                from ddp_tpu.models.lm import (
                    create_lm_train_state,
                    init_lm,
                    make_lm_eval_step,
                    make_lm_train_step,
                )

                if self.zero_mode:
                    # The causal LM rides the IN-GRAPH GSPMD zero
                    # expression (parallel/zero.py zero_gspmd_update):
                    # the bucket layout is built from abstract shapes
                    # so no replicated moment tree ever materializes.
                    # model/seq axes compose here — buckets shard over
                    # data and replicate over them (check_zero_mesh
                    # allow_model_axes).
                    from ddp_tpu.parallel.zero import (
                        build_layout,
                        check_zero_mesh,
                        zero_comm_bytes,
                    )

                    check_zero_mesh(self.mesh, allow_model_axes=True)
                    seq_spec = self.seq_spec
                    self._zero_layout = build_layout(
                        jax.eval_shape(
                            lambda: init_lm(seq_spec, seed=config.seed)
                        ),
                        int(self.mesh.shape["data"]),
                        bucket_mb=config.zero_bucket_mb,
                    )
                    self._comm_bytes = zero_comm_bytes(
                        self._zero_layout,
                        int(self.mesh.shape["data"]),
                        grad_accum_steps=config.grad_accum_steps,
                        gspmd=True,
                        gather_dtype=config.zero_gather_dtype,
                    )["total"]
                    if config.mesh_model > 1 or config.mesh_seq > 1:
                        # TP/SP activation collectives are in the
                        # program but not the update-payload estimate
                        # — a ratio check would alarm spuriously.
                        self._comm_check_enabled = False
                # Instrumented HERE (not on the label-dropping lambda
                # below): only the raw jit object can lower for the
                # xprof compile ledger.
                lm_step = self._xprof.instrument(
                    make_lm_train_step(
                        self.seq_spec, self.optimizer, self.mesh,
                        compute_dtype=compute_dtype,
                        grad_accum_steps=config.grad_accum_steps,
                        label_smoothing=config.label_smoothing,
                        zero_layout=self._zero_layout,
                        zero_gather_dtype=(
                            config.zero_gather_dtype
                            if self.zero_mode
                            else None
                        ),
                        zero_grad_clip_norm=self._zero_clip,
                        **hkw,
                    ),
                    "train_step",
                )
                # labels ride the loader but the LM has no use for
                # them — targets are the shifted tokens.
                self.train_step = lambda s, toks, lbls: lm_step(s, toks)
                self.eval_step = make_lm_eval_step(
                    self.seq_spec, self.mesh, compute_dtype=compute_dtype,
                )
                st = create_lm_train_state(
                    self.seq_spec, self.optimizer, self.mesh,
                    seed=config.seed,
                    zero_layout=self._zero_layout,
                    zero_gather_dtype=(
                        config.zero_gather_dtype if self.zero_mode else None
                    ),
                )
            else:
                from ddp_tpu.models.seq_transformer import (
                    create_seq_train_state,
                    make_seq_parallel_eval_step,
                    make_seq_parallel_train_step,
                )

                self.train_step = make_seq_parallel_train_step(
                    self.seq_spec, self.optimizer, self.mesh,
                    compute_dtype=compute_dtype,
                    grad_accum_steps=config.grad_accum_steps,
                    label_smoothing=config.label_smoothing,
                    **hkw,
                )
                self.eval_step = make_seq_parallel_eval_step(
                    self.seq_spec, self.mesh, compute_dtype=compute_dtype,
                )
                st = create_seq_train_state(
                    self.seq_spec, self.optimizer, self.mesh,
                    seed=config.seed,
                )
            # The trainer's state type (checkpoint schema parity);
            # model_state stays {} — the model is stateless. Replicate
            # EVERY leaf (incl. the step scalar) over the mesh so
            # restored checkpoints come back with uniform shardings —
            # unless fsdp/tp sharded the params at rest, in which case
            # those placements ARE the contract and must survive.
            st_tr = TrainState(
                step=st.step, params=st.params,
                opt_state=st.opt_state, model_state={},
            )
            self.state = (
                st_tr
                if config.mesh_fsdp > 1
                or config.mesh_model > 1
                or config.mesh_expert > 1
                # zero: the data-sharded flat moments ARE the contract
                # — a blanket replicate would silently undo the win.
                or self.zero_mode
                else replicate_state(st_tr, self.mesh)
            )
        elif self.pipe_lm_mode:
            from ddp_tpu.models.pipeline_lm import (
                PipeLMConfig,
                PipeLMState,
                create_pipe_lm_state,
                make_pipe_lm_1f1b_train_step,
                make_pipe_lm_eval_step,
                make_pipe_lm_interleaved_train_step,
                make_pipe_lm_train_step,
            )
            from ddp_tpu.parallel.ddp import TrainState
            from ddp_tpu.parallel.pipeline import bubble_fraction

            self._check_pipe_batch(config)
            interleaved = config.pipe_schedule == "interleaved"
            if config.mesh_seq > 1:
                if config.seq_len % config.mesh_seq:
                    raise ValueError(
                        f"--seq_len {config.seq_len} not divisible by "
                        f"--mesh_seq {config.mesh_seq}"
                    )
                if (
                    config.pipe_schedule != "gpipe"
                    and config.seq_strategy == "ring"
                ):
                    raise ValueError(
                        "PP×SP under the hand-scheduled schedules "
                        "(1f1b/interleaved) needs --seq_strategy "
                        "ulysses: ring's ppermute hops have no replica "
                        "groups and the schedules' fwd/bwd branches "
                        "diverge across pipe stages "
                        "(models/pipeline_lm.py has the full story); "
                        "ring works under --pipe_schedule gpipe"
                    )
                if config.seq_strategy == "ulysses":
                    _check_ulysses_heads(
                        config.num_heads, config.mesh_model,
                        config.mesh_seq,
                    )
            self.pipe_cfg = PipeLMConfig(
                vocab_size=config.vocab_size,
                seq_len=config.seq_len,
                d_model=config.model_dim or 64,
                num_heads=config.num_heads,
                num_stages=config.mesh_pipe,
                depth_per_stage=config.model_depth or 1,
                num_microbatches=config.num_microbatches,
                remat=config.remat,
                virtual_stages=config.virtual_stages,
                label_smoothing=config.label_smoothing,
                tp_size=config.mesh_model,
                num_kv_heads=config.num_kv_heads,
                num_experts=config.moe_experts,
                moe_every=config.moe_every,
                moe_top_k=config.moe_top_k,
                moe_normalize_gates=config.moe_normalize_gates,
                ep_size=config.mesh_expert,
                sp_size=config.mesh_seq,
                sp_strategy=config.seq_strategy,
            )
            if config.moe_experts:
                logger.info(
                    "Pipelined MoE: %d experts every %d-th block; the "
                    "GShard load-balance aux loss is not collected on "
                    "the pipe path (routing + capacity dropping still "
                    "apply) — use --model causal_lm for the full aux "
                    "objective",
                    config.moe_experts,
                    config.moe_every,
                )
            logger.info(
                "Pipeline LM: %d stages × %d virtual × %d blocks, %d "
                "microbatches, %s schedule, tp=%d, bubble fraction %.3f",
                self.pipe_cfg.num_stages,
                self.pipe_cfg.virtual_stages,
                self.pipe_cfg.depth_per_stage,
                self.pipe_cfg.num_microbatches,
                config.pipe_schedule,
                self.pipe_cfg.tp_size,
                bubble_fraction(
                    self.pipe_cfg.num_stages,
                    self.pipe_cfg.num_microbatches
                    * self.pipe_cfg.virtual_stages,
                ),
            )
            make_step = {
                "1f1b": make_pipe_lm_1f1b_train_step,
                "interleaved": make_pipe_lm_interleaved_train_step,
            }.get(config.pipe_schedule, make_pipe_lm_train_step)
            # Instrumented on the raw jit object (the state-converting
            # wrapper below cannot lower).
            pipe_step = self._xprof.instrument(
                make_step(
                    self.pipe_cfg, self.optimizer, self.mesh,
                    compute_dtype=compute_dtype,
                    **hkw,
                ),
                "train_step",
            )

            def step(ts, tokens, labels):
                del labels  # targets are the shifted tokens
                ps, metrics = pipe_step(
                    PipeLMState(ts.step, ts.params, ts.opt_state), tokens
                )
                return (
                    ts._replace(
                        step=ps.step, params=ps.params,
                        opt_state=ps.opt_state,
                    ),
                    metrics,
                )

            self.train_step = step
            self.eval_step = make_pipe_lm_eval_step(
                self.pipe_cfg, self.mesh, compute_dtype=compute_dtype
            )
            st = create_pipe_lm_state(
                self.pipe_cfg, self.optimizer, self.mesh,
                seed=config.seed, interleaved=interleaved,
            )
            # Stage params rest sharded over pipe (and model/fsdp when
            # composed) — those placements are the contract.
            self.state = TrainState(
                step=st.step,
                params=st.params,
                opt_state=st.opt_state,
                model_state={},
            )
        elif self.pipe_mode:
            from ddp_tpu.models.pipeline_vit import (
                PipeViTConfig,
                PipeViTState,
                create_pipe_vit_state,
                create_pipe_vit_state_interleaved,
                make_pipe_vit_1f1b_train_step,
                make_pipe_vit_apply,
                make_pipe_vit_interleaved_train_step,
                make_pipe_vit_train_step,
                sequential_apply_interleaved,
            )
            import optax

            from ddp_tpu.parallel.common import _preprocess
            from ddp_tpu.parallel.ddp import TrainState
            from ddp_tpu.parallel.pipeline import bubble_fraction

            self._check_pipe_batch(config)
            H = int(train_split.images.shape[1])
            pipe_heads = config.num_heads  # validated in __init__ above
            interleaved = config.pipe_schedule == "interleaved"
            self.pipe_cfg = PipeViTConfig(
                num_classes=config.num_classes
                or NUM_CLASSES.get(self.dataset, 10),
                patch_size=7 if H % 7 == 0 else 4,
                embed_dim=config.model_dim or 64,
                num_heads=pipe_heads,
                num_stages=config.mesh_pipe,
                depth_per_stage=config.model_depth or 1,
                num_microbatches=config.num_microbatches,
                remat=config.remat,
                virtual_stages=config.virtual_stages,
                tp_size=config.mesh_model,
            )
            if interleaved:
                from ddp_tpu.parallel.interleaved import schedule_interleaved

                sched = schedule_interleaved(
                    self.pipe_cfg.num_stages,
                    self.pipe_cfg.num_microbatches,
                    self.pipe_cfg.virtual_stages,
                )
                logger.info(
                    "Pipeline: %d stages × %d virtual × %d blocks, %d "
                    "microbatches, interleaved schedule, bubble "
                    "fraction %.3f (plain 1F1B: %.3f)",
                    self.pipe_cfg.num_stages,
                    self.pipe_cfg.virtual_stages,
                    self.pipe_cfg.depth_per_stage,
                    self.pipe_cfg.num_microbatches,
                    sched.bubble_fraction(),
                    bubble_fraction(
                        self.pipe_cfg.num_stages,
                        self.pipe_cfg.num_microbatches,
                    ),
                )
            else:
                logger.info(
                    "Pipeline: %d stages × %d blocks, %d microbatches, "
                    "%s schedule, bubble fraction %.3f",
                    self.pipe_cfg.num_stages, self.pipe_cfg.depth_per_stage,
                    self.pipe_cfg.num_microbatches, config.pipe_schedule,
                    bubble_fraction(
                        self.pipe_cfg.num_stages,
                        self.pipe_cfg.num_microbatches,
                    ),
                )
            make_step = {
                "1f1b": make_pipe_vit_1f1b_train_step,
                "interleaved": make_pipe_vit_interleaved_train_step,
            }.get(config.pipe_schedule, make_pipe_vit_train_step)
            # Instrumented on the raw jit object (the state-converting
            # wrapper below cannot lower).
            pipe_step = self._xprof.instrument(
                make_step(
                    self.pipe_cfg, self.optimizer, self.mesh,
                    compute_dtype=compute_dtype,
                    label_smoothing=config.label_smoothing,
                    augment_fn=augment_fn, seed=config.seed,
                ),
                "train_step",
            )

            def step(ts, images, labels):
                ps, metrics = pipe_step(
                    PipeViTState(ts.step, ts.params, ts.opt_state),
                    images, labels,
                )
                return (
                    ts._replace(
                        step=ps.step, params=ps.params,
                        opt_state=ps.opt_state,
                    ),
                    metrics,
                )

            self.train_step = step
            if interleaved:
                # Eval rides the dense forward over the [v, S] chunk
                # layout — XLA gathers each chunk's weights as it
                # goes; eval is off the step's critical path.
                pipe_cfg = self.pipe_cfg
                apply_fn = jax.jit(
                    lambda p, x: sequential_apply_interleaved(pipe_cfg, p, x)
                )
            else:
                apply_fn = jax.jit(make_pipe_vit_apply(self.pipe_cfg, self.mesh))

            def eval_step(params, model_state, images, labels, weights):
                del model_state
                logits = apply_fn(
                    params, _preprocess(images, compute_dtype)
                ).astype(jnp.float32)
                loss = optax.softmax_cross_entropy_with_integer_labels(
                    logits, labels
                )
                correct = ((jnp.argmax(logits, -1) == labels) * weights).sum()
                return correct, (loss * weights).sum()

            self.eval_step = jax.jit(eval_step)
            make_state = (
                create_pipe_vit_state_interleaved
                if interleaved
                else create_pipe_vit_state
            )
            st = make_state(
                self.pipe_cfg, self.optimizer, sample, self.mesh,
                seed=config.seed,
            )
            # Stage params rest sharded over pipe — those placements
            # are the contract (like fsdp above); don't replicate.
            self.state = TrainState(
                step=st.step,
                params=st.params,
                opt_state=st.opt_state,
                model_state={},
            )
        elif self.use_spmd:
            from ddp_tpu.parallel.spmd import (
                create_spmd_state,
                make_spmd_eval_step,
                make_spmd_train_step,
            )

            self.train_step = make_spmd_train_step(
                self.model, self.optimizer, self.mesh,
                compute_dtype=compute_dtype, seed=config.seed,
                grad_accum_steps=config.grad_accum_steps,
                augment_fn=augment_fn,
                label_smoothing=config.label_smoothing,
                zero1=config.zero1,
                **hkw,
            )
            self.eval_step = make_spmd_eval_step(
                self.model, self.mesh, compute_dtype=compute_dtype
            )
            self.state = create_spmd_state(
                self.model, self.optimizer, sample, self.mesh,
                seed=config.seed,
                zero1=config.zero1,
            )
        elif self.zero_mode:
            # The explicit-collective (shard_map) zero step: bucketed
            # psum_scatter / 1/N update / all_gather in place of the
            # DDP pmean — parity-pinned against make_train_step.
            from ddp_tpu.parallel.zero import (
                create_zero_state,
                make_zero_train_step,
                zero_comm_bytes,
            )

            self.state, self._zero_layout = create_zero_state(
                self.model, self.optimizer, sample, self.mesh,
                seed=config.seed, bucket_mb=config.zero_bucket_mb,
                gather_dtype=config.zero_gather_dtype,
            )
            self.train_step = make_zero_train_step(
                self.model, self.optimizer, self.mesh, self._zero_layout,
                compute_dtype=compute_dtype, seed=config.seed,
                grad_accum_steps=config.grad_accum_steps,
                augment_fn=augment_fn,
                label_smoothing=config.label_smoothing,
                gather_dtype=config.zero_gather_dtype,
                grad_clip_norm=self._zero_clip,
            )
            self.eval_step = make_eval_step(
                self.model, self.mesh, compute_dtype=compute_dtype
            )
            cb = zero_comm_bytes(
                self._zero_layout,
                int(self.mesh.shape["data"]),
                grad_accum_steps=config.grad_accum_steps,
                dcn=config.mesh_dcn,
                gather_dtype=config.zero_gather_dtype,
            )
            self._comm_bytes = cb["total"]
            self._comm_by_axis = cb.get("by_axis")
        else:
            self.train_step = make_train_step(
                self.model, self.optimizer, self.mesh,
                compute_dtype=compute_dtype, seed=config.seed,
                grad_accum_steps=config.grad_accum_steps,
                augment_fn=augment_fn,
                label_smoothing=config.label_smoothing,
                **hkw,
            )
            self.eval_step = make_eval_step(
                self.model, self.mesh, compute_dtype=compute_dtype
            )
            state = create_train_state(
                self.model, self.optimizer, sample, seed=config.seed
            )
            self.state = replicate_state(state, self.mesh)
            # The comm story the zero bench compares against: the full
            # fp32 gradient ring all-reduce, every step.
            from ddp_tpu.parallel.zero import ddp_comm_bytes

            self._comm_bytes = ddp_comm_bytes(
                self.state.params, self.data_shards
            )["total"]
        # Families whose train/eval steps are the raw jit objects get
        # instrumented here in one place (the seq classifier, GSPMD,
        # zero, and plain-DDP steps; the lm/pipe branches wrapped
        # their inner jits above — their outer state adapters cannot
        # lower). Identity when --xprof is off.
        if self._xprof.enabled:
            if hasattr(self.train_step, "lower"):
                self.train_step = self._xprof.instrument(
                    self.train_step, "train_step"
                )
            if hasattr(self.eval_step, "lower"):
                self.eval_step = self._xprof.instrument(
                    self.eval_step, "eval_step"
                )
        self.tracer.phase_complete(
            "startup.model_init", t, time.perf_counter() - t, parent=t_init
        )
        # ``train.dispatch``: the host's share of one step — the call
        # of the jitted step, whatever family built it. Wrapped once,
        # here, where ``train_step`` is final; never a sync. The first
        # call traces, lowers and compiles the step: that one record is
        # also kept, with the process's start.
        step, tracer = self.train_step, self.tracer
        record = tracer.phase

        def dispatch(*args, **kwargs):
            nonlocal record
            with record("train.dispatch"):
                record = tracer.span
                return step(*args, **kwargs)

        self.train_step = dispatch
        self.fast_runner = None
        if config.fast_epoch:
            if not (self.lm_mode or self.pipe_mode) and (
                self.use_spmd or config.grad_accum_steps > 1
            ):
                raise ValueError(
                    "--fast_epoch supports the pure-DDP step without "
                    "gradient accumulation (or the causal LM / "
                    "pipeline families)"
                )
            if not config.shuffle:
                raise ValueError(
                    "--fast_epoch always reshuffles per epoch "
                    "(on-device permutation); drop --no_shuffle"
                )
            if config.watchdog_timeout > 0:
                raise ValueError(
                    "--fast_epoch runs a whole epoch as one dispatch "
                    "with no per-step progress beats, so a step-scale "
                    "--watchdog_timeout would kill healthy runs; drop "
                    "one of the two flags"
                )
            from ddp_tpu.train.fast import (
                device_put_dataset,
                device_put_replicated,
                make_epoch_runner,
                make_lm_epoch_runner,
                make_pipe_lm_epoch_runner,
                make_pipe_vit_epoch_runner,
            )

            if self.pipe_lm_mode:
                # Round-5 wall lift: the pipelined LM rides the
                # compiled-epoch dispatch like the flat LM — the raw
                # pipe step (any schedule) scanned on device.
                from ddp_tpu.models.pipeline_lm import PipeLMState

                dev_tokens = device_put_replicated(
                    train_split.images, self.mesh,  # tokens ride .images
                    tracer=self.tracer,
                )
                runner = make_pipe_lm_epoch_runner(
                    self.pipe_cfg, self.optimizer, self.mesh,
                    dev_tokens, self.global_batch_size,
                    schedule=config.pipe_schedule,
                    compute_dtype=compute_dtype, seed=config.seed,
                )
                self.fast_runner = self._wrap_pipe_runner(
                    runner, PipeLMState
                )
            elif self.pipe_mode:
                from ddp_tpu.models.pipeline_vit import PipeViTState

                dev_images, dev_labels = device_put_dataset(
                    train_split.images, train_split.labels, self.mesh,
                    tracer=self.tracer,
                )
                runner = make_pipe_vit_epoch_runner(
                    self.pipe_cfg, self.optimizer, self.mesh,
                    dev_images, dev_labels, self.global_batch_size,
                    schedule=config.pipe_schedule,
                    compute_dtype=compute_dtype, seed=config.seed,
                    augment_fn=augment_fn,
                    label_smoothing=config.label_smoothing,
                )
                self.fast_runner = self._wrap_pipe_runner(
                    runner, PipeViTState
                )
            elif self.lm_mode:
                dev_tokens = device_put_replicated(
                    train_split.images, self.mesh,  # tokens ride .images
                    tracer=self.tracer,
                )
                self.fast_runner = make_lm_epoch_runner(
                    self.seq_spec, self.optimizer, self.mesh,
                    dev_tokens, self.global_batch_size,
                    compute_dtype=compute_dtype, seed=config.seed,
                    grad_accum_steps=config.grad_accum_steps,
                    label_smoothing=config.label_smoothing,
                )
            else:
                # Full arrays on device: the runner permutes all n
                # images per epoch and drops a DIFFERENT tail of the
                # permutation each time (make_epoch_runner), matching
                # the step path's coverage — a static [:usable]
                # truncation would exclude the same images every epoch.
                dev_images, dev_labels = device_put_dataset(
                    train_split.images, train_split.labels, self.mesh,
                    tracer=self.tracer,
                )
                self.fast_runner = make_epoch_runner(
                    self.model, self.optimizer, self.mesh,
                    dev_images, dev_labels, self.global_batch_size,
                    compute_dtype=compute_dtype, seed=config.seed,
                    augment_fn=augment_fn,
                    label_smoothing=config.label_smoothing,
                )
        if config.keep_best and config.eval_every != 1:
            raise ValueError(
                "--keep_best ranks checkpoints by eval accuracy, so "
                "every epoch needs one: set --eval_every 1"
            )
        if config.keep_best and config.max_checkpoints is None:
            raise ValueError(
                "--keep_best retains the --max_checkpoints best epochs; "
                "without --max_checkpoints it would keep everything — "
                "set --max_checkpoints N (or drop --keep_best)"
            )
        # World-shape-agnostic restore hook: the zero strategy's flat
        # bucket shapes are world-dependent (padded to the replica
        # count), so an elastic resize must RE-BUCKET them on restore —
        # everything else reshards by Orbax templating. None for every
        # other strategy (restore behaves exactly as before).
        self._opt_reshape = None
        if self.zero_mode:
            from ddp_tpu.parallel.zero import ZeroElasticReshaper

            self._opt_reshape = ZeroElasticReshaper(
                self.optimizer, self._zero_layout, self.mesh,
                gather_dtype=config.zero_gather_dtype,
            )
        self.ckpt = CheckpointManager(
            config.checkpoint_dir,
            max_to_keep=config.max_checkpoints,
            keep_best_metric="accuracy" if config.keep_best else None,
        )
        self.metrics_writer = MetricsWriter(
            config.metrics_file, enabled=self.ctx.is_main
        )
        # Goodput accounting is always on — one tiny sidecar next to
        # the checkpoints, loaded/written only during train().
        self._goodput = GoodputAccountant(
            os.path.join(config.checkpoint_dir, "goodput.json"),
            enabled=self.ctx.is_main,
        )
        # Analytic train-FLOPs per example (None for unknown models —
        # MFU is then absent, never silently zero) against the mesh's
        # aggregate peak (None off-TPU: a CPU has no peak, so no MFU).
        self._flops_per_example = self._estimate_flops_per_example()
        chip_peak = peak_flops_per_chip(devices[0])
        self._peak_flops = (
            chip_peak * self.mesh.devices.size if chip_peak else None
        )
        # Runtime sanitizer (--sanitize, runtime/sanitize.py): the
        # transfer guard arms around the hot loop in _train_epoch
        # (deliberate syncs run in allow() windows); disabled it is a
        # nullcontext, pinned free like the tracer. The watchdog half
        # rides the existing StepWatchdog: with no explicit
        # --watchdog_timeout, --sanitize arms it at --sanitize_timeout
        # with the desync-diagnosing abort. Not under --fast_epoch —
        # one dispatch per epoch has no per-step beats (the same
        # reason an explicit step-scale timeout is rejected there).
        from ddp_tpu.runtime.sanitize import Sanitizer, desync_abort

        self._sanitizer = Sanitizer(config.sanitize)
        self._wd_dump_reason = "watchdog_timeout"
        wd_timeout = config.watchdog_timeout
        wd_kwargs = {}
        if (
            config.sanitize
            and wd_timeout <= 0
            and config.sanitize_timeout > 0
            and not config.fast_epoch
        ):
            wd_timeout = config.sanitize_timeout
            wd_kwargs["on_timeout"] = desync_abort(self.ctx.num_processes)
            self._wd_dump_reason = "suspected_desync"
        # Constructed here, armed in train() (start/stop bracket the run).
        self._watchdog = StepWatchdog(wd_timeout, **wd_kwargs)
        # Deterministic fault injection (--chaos, runtime/chaos.py):
        # each rank arms its share of the plan; the per-rank ledger
        # next to the checkpoints makes every event once-only across
        # restarts, so a relaunch loop recovers instead of re-dying.
        self._chaos = ChaosEngine(
            config.chaos,
            rank=self.ctx.process_id,
            ledger_path=os.path.join(
                config.checkpoint_dir,
                f"chaos_ledger.rank{self.ctx.process_id}.json",
            ),
            seed=config.seed,
        )
        if self._chaos.has_step_events() and config.fast_epoch:
            raise ValueError(
                "--chaos step-triggered events need the per-step loop, "
                "but --fast_epoch runs a whole epoch as ONE dispatch — "
                "use epoch-triggered events (…@epochN) or drop "
                "--fast_epoch"
            )
        # Flight recorder: host-dict ring next to the checkpoints, one
        # file per rank; the directory is only created on dump (a
        # Trainer that never trains must not create checkpoint_dir).
        self._recorder = FlightRecorder(
            config.checkpoint_dir,
            rank=self.ctx.process_id,
            capacity=config.flight_records,
        )
        if self._xprof.enabled:
            # OOM forensics: the dump collects the compile ledger and
            # a FRESH memory sample at dump time (a provider, not a
            # snapshot) — what was compiled, how big, and how full the
            # device was when the run died.
            self._recorder.set_provider(
                "xprof",
                lambda: {
                    "compile_ledger": self._xprof.ledger_records(),
                    "memory": self._hbm.sample(),
                },
            )
        # Anomaly sentry + one-step-behind health monitor. The group-
        # path layout comes from the SAME group_layout the in-graph
        # pass uses, so the [G] vectors decode without drift.
        self._sentry = (
            AnomalySentry(
                SentryConfig(
                    window=config.health_window,
                    min_steps=max(2, min(8, config.health_window // 2)),
                    cooldown=config.health_window,
                )
            )
            if config.health
            else None
        )
        self._health = HealthMonitor(
            enabled=config.health,
            paths=group_layout(self.state.params)[0]
            if config.health
            else (),
            sentry=self._sentry,
            metrics=self.metrics_writer,
            tracer=self.tracer,
            recorder=self._recorder,
        )
        self._last_health_ckpt: int | None = None
        # Epoch tag held by a rescue save from THIS run: the boundary
        # save must then force-overwrite, or the completed epoch's
        # state (and its keep_best metric) would silently stay the
        # stale mid-epoch rescue until epoch+1 commits.
        self._rescued_epoch: int | None = None
        # Live Prometheus exposition (--metrics_port): one daemon
        # thread serving /metricsz from the snapshot dict the loop
        # updates at the log cadence. Stopped in close().
        self._prom_state: dict[str, Any] = {}
        self._metrics_port = None
        if config.metrics_port is not None and self.ctx.is_main:
            from ddp_tpu.obs.promtext import MetricsPort, render_train

            self._metrics_port = MetricsPort(
                lambda: render_train(self._prom_snapshot()),
                port=config.metrics_port,
            ).start()
            logger.info(
                "Prometheus exposition at %s/metricsz",
                self._metrics_port.url,
            )
        self._raw_eval_count = 0  # companion raw evals under EMA
        self._preempt_requested = False
        self.history: list[EpochStats] = []
        self._startup_logged = False
        self.tracer.phase_complete(
            "startup.state", t_init, time.perf_counter() - t_init,
            nums=("trainer",),
        )

    # ---- the reference's epoch/batch loop (train_ddp.py:192-209) ----

    @staticmethod
    def _wrap_pipe_runner(runner, state_cls):
        """Adapt a pipe-family epoch runner (PipeLMState/PipeViTState)
        to the trainer's TrainState — the same conversion the per-step
        wrappers do; NamedTuple construction shares buffers, so
        donation still applies."""

        def wrapped(ts, epoch):
            ps, metrics = runner(
                state_cls(ts.step, ts.params, ts.opt_state), epoch
            )
            return (
                ts._replace(
                    step=ps.step, params=ps.params, opt_state=ps.opt_state
                ),
                metrics,
            )

        wrapped.steps_per_epoch = runner.steps_per_epoch
        return wrapped

    def _check_pipe_batch(self, config: TrainConfig) -> None:
        """Microbatch divisibility guards shared by both pipe families."""
        if self.global_batch_size % config.num_microbatches:
            raise ValueError(
                f"global batch {self.global_batch_size} (batch_size "
                f"× data shards) not divisible by "
                f"--num_microbatches {config.num_microbatches}"
            )
        mb_size = self.global_batch_size // config.num_microbatches
        if mb_size % self.data_shards:
            raise ValueError(
                f"microbatch size {mb_size} (global batch "
                f"{self.global_batch_size} / {config.num_microbatches} "
                f"microbatches) not divisible by {self.data_shards} "
                "data shards — each microbatch shards over the data "
                "axis"
            )

    def _estimate_flops_per_example(self) -> float | None:
        """Analytic train FLOPs per example for MFU (obs/goodput.py).

        "Example" matches the throughput unit the trainer already
        reports: an image for the image family, a whole sequence for
        the token/sequence families. None when no estimator exists —
        the metrics stream then omits ``mfu`` rather than lying.
        """
        from ddp_tpu.obs.goodput import (
            lm_train_flops_per_sequence,
            seq_classifier_train_flops,
            vit_train_flops,
        )

        cfg = self.config
        if self.lm_mode:
            return lm_train_flops_per_sequence(self.seq_spec)
        if self.seq_mode:
            return seq_classifier_train_flops(self.seq_spec)
        if self.pipe_lm_mode:
            pc = self.pipe_cfg
            total_depth = (
                pc.num_stages * pc.depth_per_stage * pc.virtual_stages
            )
            from ddp_tpu.models.lm import LMSpec

            return lm_train_flops_per_sequence(
                LMSpec(
                    vocab_size=pc.vocab_size,
                    total_len=pc.seq_len,
                    d_model=pc.d_model,
                    depth=total_depth,
                    num_heads=pc.num_heads,
                    num_experts=pc.num_experts,
                    moe_every=pc.moe_every,
                    moe_top_k=pc.moe_top_k,
                    num_kv_heads=pc.num_kv_heads,
                )
            )
        if self.pipe_mode:
            pc = self.pipe_cfg
            return vit_train_flops(
                tuple(self.train_split.images.shape[1:]),
                pc.num_classes,
                patch_size=pc.patch_size,
                embed_dim=pc.embed_dim,
                depth=pc.num_stages * pc.depth_per_stage * pc.virtual_stages,
                num_heads=pc.num_heads,
            )
        from ddp_tpu.data.registry import NUM_CLASSES

        return train_flops_per_example(
            cfg.model,
            image_shape=tuple(self.train_split.images.shape[1:]),
            num_classes=cfg.num_classes or NUM_CLASSES.get(self.dataset, 10),
            depth=cfg.model_depth,
        )

    def _attention_info(self) -> dict | None:
        """The attention the token/sequence families' step was built
        with (``ops.attention.use_flash`` decides from the platform
        and the key count one call sees); None for the registry image
        models, whose token count the trainer does not know."""
        if not (self.seq_mode or self.pipe_lm_mode):
            return None
        from ddp_tpu.ops.attention import describe_attention

        cfg = self.config
        keys = cfg.seq_len
        if cfg.mesh_seq > 1 and cfg.seq_strategy == "ring":
            keys //= cfg.mesh_seq  # one ring hop attends one block
        return describe_attention(keys)

    def _step_obs_fields(self, timing) -> dict:
        """JSONL fields for one attributed step ({} when attribution
        is off — the step record's schema only widens under
        --trace_dir)."""
        if timing is None:
            return {}
        fields = {
            "input_wait_s": round(timing.input_wait_s, 6),
            "dispatch_s": round(timing.dispatch_s, 6),
            "compute_s": round(timing.compute_s, 6),
            "recompiles": timing.recompiles,
        }
        wall = timing.wall_s
        m = _mfu(
            self.global_batch_size / wall if wall > 0 else 0.0,
            self._flops_per_example,
            self._peak_flops,
        )
        if m is not None:
            fields["mfu"] = round(m, 6)
        return fields

    def _xprof_step_fields(self) -> dict:
        """Log-cadence xprof work: sample device memory (step-record
        fields + Perfetto counter track), drain fresh compile events
        into the metrics stream/flight recorder, and run the one-time
        comm-bytes cross-check. {} when --xprof is off — the step
        record's schema only widens under the flag (the disabled-mode
        byte-identity pin).
        """
        if not self._xprof.enabled:
            return {}
        mem = self._hbm.sample()
        fields = {
            k: mem[k]
            for k in (
                "hbm_used_bytes", "hbm_high_water_bytes",
                "hbm_headroom_frac",
            )
            if k in mem
        }
        if self.tracer.enabled and mem:
            self.tracer.counter(
                "hbm",
                {
                    "used_bytes": mem["hbm_used_bytes"],
                    "high_water_bytes": mem["hbm_high_water_bytes"],
                },
            )
        self._xprof_cursor, events = self._xprof.events_after(
            self._xprof_cursor
        )
        for ev in events:
            rec = {
                k: ev[k]
                for k in (
                    "label", "signature", "shape_diff",
                    "compile_time_s", "lower_time_s", "flops", "memory",
                )
                if ev.get(k) is not None
            }
            if ev.get("collectives"):
                # Per-kind totals of what the compiled program moves
                # (the per-instance list stays in the ledger).
                rec["collectives"] = {
                    op: {"count": c["count"], "result_bytes": c["result_bytes"]}
                    for op, c in ev["collectives"].items()
                }
            self.metrics_writer.write("compile", **rec)
            self._recorder.record("compile", **rec)
        # Hand-ledger vs compiled-program collectives, once per run:
        # the ddp/zero strategies price their per-step payload
        # analytically (parallel/zero.py); the first compiled
        # train_step says what XLA actually emits. World 1 has no
        # collectives to check.
        if (
            self._comm_bytes is not None
            and self._comm_check_enabled
            and not self._comm_checked
            and self.data_shards >= 2
        ):
            from ddp_tpu.runtime.mesh import slice_block_size

            check = self._xprof.comm_check(
                "train_step", self._comm_bytes, self.data_shards,
                # Hierarchical steps additionally pin each fabric:
                # HLO collectives attribute to ici/dcn by their
                # replica-group membership (obs/xprof.py).
                expected_by_axis=self._comm_by_axis,
                slice_size=(
                    slice_block_size(self.mesh)
                    if self._comm_by_axis is not None
                    else None
                ),
            )
            if check is not None:
                self._comm_checked = True
                self.metrics_writer.write("xprof_check", **check)
                if check["within_tolerance"]:
                    logger.info(
                        "xprof comm check: analytic %d bytes vs HLO %d "
                        "(ratio %s) — within tolerance",
                        check["expected_comm_bytes"],
                        check["measured_comm_bytes"],
                        check["ratio"],
                    )
                else:
                    logger.warning(
                        "xprof comm check FAILED: analytic %d bytes vs "
                        "HLO-derived %d (ratio %s) — the comm_bytes "
                        "estimate drifted from the compiled program",
                        check["expected_comm_bytes"],
                        check["measured_comm_bytes"],
                        check["ratio"],
                    )
        self._prom_state["compile_programs"] = self._xprof.program_count
        self._prom_state["compile_seconds_total"] = round(
            self._xprof.total_compile_s, 4
        )
        self._prom_state.update(fields)
        return fields

    def _prom_snapshot(self) -> dict:
        """Live dict for the /metricsz train exposition (promtext)."""
        snap = dict(self._prom_state)
        if self._health.enabled:
            h = self._health.snapshot()
            snap.setdefault("loss", h.get("loss"))
            snap.setdefault("grad_norm", h.get("grad_norm"))
            snap["health_events"] = h.get("events")
            if "nonfinite_layer" in h or "nonfinite_step" in h:
                snap["nonfinite_layer"] = h.get("nonfinite_layer")
                snap["nonfinite_step"] = h.get("nonfinite_step")
        if self._sentry is not None:
            snap["step_time"] = self._sentry.snapshot()["step_time_s"]
        gp = self._goodput.snapshot()
        if gp:
            snap["goodput"] = gp.get("goodput")
        # Stamped at run_start; a snapshot scraped before train()
        # simply renders no build_info gauge (absent ≠ zero).
        bi = getattr(self, "_build_info", None)
        if bi:
            snap["build_info"] = bi
        return snap

    def _on_health_events(
        self, events, *, epoch: int, ran: int
    ) -> None:
        """Apply --health_action to a batch of sentry/provenance
        events. ``ran`` = batches completed within this epoch (the
        mid-epoch checkpoint position, host-known — no sync).

        Single process acts immediately. Multi-process DEFERS: the
        events are rank-local but halt/checkpoint are collective, so
        they queue for the next agreement point (``_sync_flags`` at
        the log cadence / epoch boundary), where every rank adopts the
        OR and enters the collective action together.
        """
        for ev in events:
            logger.warning(
                "health[%s] at step %s: %s",
                ev.get("detector"),
                ev.get("step"),
                {k: v for k, v in ev.items() if k not in ("detector", "step")},
            )
        action = self.config.health_action
        if action != "warn" and self.ctx.num_processes > 1:
            if action == "halt":
                self._pending_halt.extend(events)
            else:  # checkpoint: nonfinite states are never rescuable
                self._pending_rescue.extend(
                    e for e in events if e.get("detector") != "nonfinite"
                )
            return
        if action == "halt":
            dump = self._recorder.dump("health_halt")
            raise HealthHaltError(list(events), dump_path=dump)
        if action == "checkpoint":
            # Never "rescue" a non-finite state: by the time the
            # provenance event is ingested (one step behind) the
            # params already took NaN updates — overwrite-saving them
            # would shadow the last GOOD checkpoint and auto-resume
            # would restore straight into the divergence. Sentry
            # anomalies (spike/explosion/straggler/recompiles) are
            # still-finite states worth pinning; nonfinite is not.
            rescuable = [
                e for e in events if e.get("detector") != "nonfinite"
            ]
            if not rescuable:
                return
            # At most one rescue checkpoint per sentry window: a storm
            # of events must not turn into a storm of checkpoint I/O.
            step = int(rescuable[-1].get("step", 0))
            if (
                self._last_health_ckpt is not None
                and step - self._last_health_ckpt
                < self.config.health_window
            ):
                return
            self._last_health_ckpt = step
            self._rescued_epoch = epoch
            spe = self.loader.steps_per_epoch()
            self.ckpt.save(
                epoch, self.state, overwrite=True, steps_per_epoch=spe,
                mid_batch=ran if 0 < ran < spe else 0,
            )
            # Block until committed: the async save must not still be
            # writing this epoch tag when the epoch-boundary save (or
            # a second rescue) touches it — and a rescue checkpoint
            # that a crash can outrun would be no rescue at all.
            self.ckpt.wait()
            logger.warning(
                "health: checkpoint-and-continue saved epoch %d at "
                "batch %d (step %d)", epoch, ran, step,
            )

    def _install_preemption_handler(self):
        """SIGTERM → finish the in-flight step, checkpoint, exit clean.

        Preemptible/spot TPU VMs get SIGTERM before reclaim; the
        reference would lose the whole epoch (it has no handler —
        SURVEY.md §5 failure detection). Returns the previous handler
        (restored after training); no-op off the main thread.
        """
        import signal
        import threading

        if threading.current_thread() is not threading.main_thread():
            return (False, None)

        def _on_term(signum, frame):
            logger.warning(
                "SIGTERM received — will checkpoint at the next step "
                "boundary and exit"
            )
            self._preempt_requested = True
            # Dump NOW, not at the checkpoint boundary: preemption
            # grace windows are short, and a second SIGKILL-style
            # reclaim must still find the post-mortem on disk. The
            # boundary checkpoint then supersedes nothing — the dump
            # is evidence, not state.
            self._recorder.record("signal", signal="SIGTERM")
            self._recorder.dump("sigterm")

        try:
            return (True, signal.signal(signal.SIGTERM, _on_term))
        except ValueError:  # non-main interpreter contexts
            return (False, None)

    def _sync_flags(self, host_step: int) -> tuple[bool, bool, bool]:
        """ONE allgather carrying the three rank-local escalations →
        world-agreed (preempt, halt, rescue). A collective call: every
        rank must reach it at the same deterministic point (the log
        cadence in the step loop, and each epoch boundary). The rescue
        flag already folds in this rank's throttle window so an agreed
        rescue is performed by every rank unconditionally — any
        post-agreement local filtering would desynchronize the
        collective save.
        """
        rescue_ok = (
            self._last_health_ckpt is None
            or host_step - self._last_health_ckpt
            >= self.config.health_window
        )
        pre, halt, rescue = consensus.agree_any(
            [
                self._preempt_requested,
                bool(self._pending_halt),
                bool(self._pending_rescue) and rescue_ok,
            ],
            num_processes=self.ctx.num_processes,
        )
        if pre:
            self._preempt_requested = True
        return pre, halt, rescue

    def _act_on_agreed(
        self, halt: bool, rescue: bool, *, epoch: int, ran: int,
        host_step: int,
    ) -> None:
        """Perform the world-agreed health action on THIS rank.

        Every rank calls this after ``_sync_flags`` said halt/rescue,
        with identical (epoch, ran, host_step) — ranks whose own
        sentry saw nothing still participate (their event list is a
        ``peer`` placeholder): the save is collective and the halt
        must take every rank down together, not strand survivors in
        the next step's collective.
        """
        if halt:
            events = self._pending_halt or [
                {"detector": "peer", "step": host_step}
            ]
            self._pending_halt = []
            dump = self._recorder.dump("health_halt")
            raise HealthHaltError(list(events), dump_path=dump)
        if rescue:
            self._pending_rescue = []
            self._last_health_ckpt = host_step
            self._rescued_epoch = epoch
            spe = self.loader.steps_per_epoch()
            self.ckpt.save(
                epoch, self.state, overwrite=True, steps_per_epoch=spe,
                mid_batch=ran if 0 < ran < spe else 0,
            )
            self.ckpt.wait()
            logger.warning(
                "health: world-agreed checkpoint-and-continue saved "
                "epoch %d at batch %d (step %d)", epoch, ran, host_step,
            )

    def _fresh_opt_state(self, params):
        """A from-scratch optimizer state in the LIVE layout: the zero
        strategy's flat data-sharded buckets, or plain ``init`` —
        ``--reset_opt_state`` under ``--parallel zero`` must not graft
        a tree-shaped state onto a bucket-sharded step."""
        if self.zero_mode:
            from ddp_tpu.parallel.zero import create_zero_opt_state

            return create_zero_opt_state(
                params, self.optimizer, self.mesh, self._zero_layout,
                gather_dtype=self.config.zero_gather_dtype,
            )
        return self.optimizer.init(params)

    def _restore_or_init(self):
        """Auto-resume, tolerant of --ema_decay being turned ON since
        the checkpoint was written (or a torch-imported checkpoint):
        restore the EMA-less optimizer layout and graft a fresh EMA
        initialized from the restored params. Other optimizer-config
        changes can't be reconciled — fail with the flags named instead
        of Orbax's raw pytree-mismatch error.
        """
        from ddp_tpu.train.optim import EmaState, ema_params, make_optimizer

        def prune_rewound_branch(epoch):
            # Rewind is a branch: the discarded later epochs must not
            # remain discoverable as "latest" (a crash would
            # auto-resume the branch the user just backed out of).
            stale = self.ckpt.delete_after(epoch)
            if stale:
                logger.warning(
                    "Rewind to epoch %d: deleted the abandoned "
                    "branch's checkpoints %s", epoch, stale,
                )

        def do_restore(state):
            if self.config.resume_epoch is not None:
                restored, epoch = self.ckpt.restore(
                    state, self.config.resume_epoch,
                    opt_reshape=self._opt_reshape,
                )
                prune_rewound_branch(epoch)
                logger.info("Resumed from requested epoch %d", epoch)
                return restored, epoch + 1
            return self.ckpt.restore_or_init(
                state, opt_reshape=self._opt_reshape
            )

        if self.config.reset_opt_state:
            # Weights only; the optimizer (schedules, moments, step
            # counter, EMA) starts fresh — the explicit recipe-change
            # path, layout-independent by construction. No
            # latest_epoch() pre-check: in multi-process runs a rank
            # short-circuiting on a racing view of the directory would
            # skip the verification barrier inside the restore (the
            # restore_or_init pairing rule) — absence surfaces as
            # FileNotFoundError on every rank consistently instead.
            try:
                params, model_state, epoch = (
                    self.ckpt.restore_for_inference(
                        self.config.resume_epoch
                    )
                )
            except FileNotFoundError:
                return self.state, 0
            if self.config.resume_epoch is not None:
                prune_rewound_branch(epoch)
            # A mid-epoch preemption artifact (mid_batch > 0) tags an
            # UNFINISHED epoch; promoting it to "completed" silently
            # skips its remaining batches. The normal restore path
            # re-enters the epoch — with a fresh optimizer that replay
            # bookkeeping doesn't apply, so warn instead.
            try:
                mid = int(
                    self.ckpt.read_partial(epoch, ("mid_batch",)).get(
                        "mid_batch", 0
                    )
                )
            except Exception:  # legacy checkpoint without the key
                mid = 0
            if mid > 0:
                logger.warning(
                    "--reset_opt_state restored a mid-epoch artifact "
                    "(epoch %d stopped at batch %d); its remaining "
                    "batches are skipped and training continues at "
                    "epoch %d", epoch, mid, epoch + 1,
                )
            # Adopt the live state's shardings (replicated or GSPMD
            # rule layout), then rebuild optimizer state from the
            # restored params so e.g. the EMA starts from them.
            params = jax.tree.map(
                lambda tpl, arr: jax.device_put(arr, tpl.sharding),
                self.state.params,
                params,
            )
            if model_state:
                model_state = jax.tree.map(
                    lambda tpl, arr: jax.device_put(arr, tpl.sharding),
                    self.state.model_state,
                    model_state,
                )
            else:
                model_state = self.state.model_state
            logger.warning(
                "Restored epoch %d weights with a FRESH optimizer "
                "state (--reset_opt_state)", epoch,
            )
            return (
                self.state._replace(
                    params=params,
                    model_state=model_state,
                    opt_state=self._fresh_opt_state(params),
                ),
                epoch + 1,
            )

        try:
            return do_restore(self.state)
        except (ValueError, KeyError) as e:
            if self.config.ema_decay:
                tx_noema = make_optimizer(
                    self.config.optimizer,
                    **dict(self._opt_kwargs, ema_decay=0.0),
                )
                alt = self.state._replace(
                    opt_state=tx_noema.init(self.state.params)
                )
                try:
                    restored, start_epoch = do_restore(alt)
                except (ValueError, KeyError):
                    restored = None
                if restored is not None and ema_params(restored.opt_state) is None:
                    logger.info(
                        "Checkpoint has no EMA (written without "
                        "--ema_decay) — initializing the EMA from the "
                        "restored params"
                    )
                    ema = EmaState(
                        ema=jax.tree.map(
                            lambda p: jnp.array(p, copy=True), restored.params
                        )
                    )
                    return (
                        restored._replace(
                            opt_state=(restored.opt_state, ema)
                        ),
                        start_epoch,
                    )
            raise RuntimeError(
                "Checkpoint optimizer state does not match the current "
                "optimizer config — changed --optimizer / --momentum / "
                "--ema_decay / --grad_clip_norm or a schedule flag "
                "(--warmup_steps / --decay_steps / --lr_milestones; "
                "schedules add a step-count state) since it was "
                "written? Re-run with --reset_opt_state to keep the "
                "weights and start the optimizer fresh, or point "
                "--checkpoint_dir elsewhere."
            ) from e

    def train(self) -> dict[str, Any]:
        cfg = self.config
        if self.lm_mode and self.ctx.is_main:
            # Architecture sidecar for inference tooling: the fields
            # the checkpoint shapes cannot carry (num_heads, MoE
            # routing, strategy) persist beside the epochs, like the
            # tokenizer does. Written here, not at construction — a
            # Trainer that never trains must not create checkpoint_dir.
            from ddp_tpu.train.checkpoint import save_lm_spec

            save_lm_spec(cfg.checkpoint_dir, self.seq_spec)
        if cfg.elastic and self.ctx.is_main:
            # Record the run's global-batch contract ONCE (first
            # generation); relaunched generations read it in __init__
            # and rescale their per-shard batch to honor it.
            from ddp_tpu.train.checkpoint import save_elastic_contract

            save_elastic_contract(
                cfg.checkpoint_dir,
                global_batch_size=self.global_batch_size,
                world_size=self.ctx.num_processes,
            )
        # Process-start chaos (ckpt_corrupt) fires BEFORE discovery so
        # the integrity/quarantine fallback below is what it drills.
        self._chaos.on_start(cfg.checkpoint_dir)
        with self.tracer.phase("startup.checkpoint"):
            self.state, start_epoch = self._restore_or_init()
        # Integrity fallbacks during discovery (train/checkpoint.py):
        # a corrupt latest was quarantined and an earlier epoch
        # restored. Surface each as a metrics record + flight-recorder
        # event so triage (scripts/health_report.py) sees WHAT state
        # the run actually resumed from.
        resumed = start_epoch - 1 if start_epoch > 0 else None
        for q in self.ckpt.quarantined:
            self.metrics_writer.write(
                "fallback",
                epoch=q["epoch"],
                resumed_epoch=resumed,
                quarantined_path=q["path"],
                problems=q["problems"][:8],
            )
            self._recorder.record(
                "ckpt_fallback",
                epoch=q["epoch"],
                resumed_epoch=resumed,
                problems=q["problems"][:8],
            )
        # Restart-aware goodput: the sidecar (if any) carries the
        # first launch's clock and prior productive seconds, so a
        # preempt/resume cycle accumulates instead of resetting — and
        # the live world size, so a relaunch whose world CHANGED is
        # attributed as resize downtime, not restart downtime. The
        # "world" here is the DATA-PARALLEL world (device shards, not
        # process count): it is what the shard math, the zero bucket
        # layout and the batch rescale actually key on, and it moves
        # for both resize kinds — lost hosts (spawn workers) and lost
        # local devices (--emulate_devices drills).
        self._goodput.start_run(world_size=self.data_shards)
        # Durable immediately: a generation killed before its first
        # epoch boundary must still leave its world size (and launch
        # clock) on disk, or the NEXT generation's restart/resize
        # downtime attribution would skip a boundary.
        self._goodput.flush()
        # Flight-recorder context: what a post-mortem needs but no
        # step record carries — config, env, mesh, rank.
        self._recorder.set_context(
            config=dataclasses.asdict(cfg),
            env=snapshot_env(),
            mesh={a: int(self.mesh.shape[a]) for a in self.mesh.axis_names},
            rank=self.ctx.process_id,
            num_processes=self.ctx.num_processes,
        )
        # Old-world → new-world transition, from the goodput sidecar's
        # recorded world (None on the first generation). Rides both the
        # flight recorder AND the metrics stream: the run_start metrics
        # record is the triage anchor (scripts/health_report.py world
        # trajectory; the elastic drill pins). Written from straight-
        # line code exactly once per train() call — one generation, one
        # record carrying the restart count (pinned by test_metrics and
        # the elastic drills).
        world_fields = {
            "world_size": self.ctx.num_processes,
            "data_shards": self.data_shards,
        }
        if self._goodput.prev_world is not None:
            world_fields["prev_data_shards"] = self._goodput.prev_world
        # Build provenance on the generation anchor (ISSUE 11): the
        # same version/jax/backend/platform block bench records carry,
        # so a resumed run that crossed an image upgrade — or a fleet
        # member running skewed code — is visible from the stream
        # alone. Matching ddp_tpu_build_info gauge on /metricsz.
        from ddp_tpu.obs.recorder import build_info

        self._build_info = build_info()
        # What was built from what the platform offers — the attention
        # implementation and where compiled programs persist — is
        # chosen from observation, so the generation anchor says it.
        # (enable_compile_cache is idempotent — setup() already ran it —
        # and returns the directory in effect.)
        built_fields = {"compile_cache": dist.enable_compile_cache()}
        attention = self._attention_info()
        if attention:
            built_fields["attention"] = attention
        self._recorder.record(
            "run_start", start_epoch=start_epoch,
            restarts=self._goodput.restarts,
            build_info=self._build_info, **world_fields,
        )
        self.metrics_writer.write(
            "run_start",
            start_epoch=start_epoch,
            restarts=self._goodput.restarts,
            global_batch_size=self.global_batch_size,
            build_info=self._build_info,
            **world_fields,
            **built_fields,
        )
        # Mid-epoch preemption saves are tagged with their (incomplete)
        # epoch and record how many batches ran as an explicit
        # mid_batch marker; resume re-enters that epoch at that batch.
        start_batch = 0
        spe = self.loader.steps_per_epoch()
        mid = self.ckpt.last_restored_mid_batch
        if self.fast_runner is None and mid:
            # Explicit mid-epoch marker (recorded at save time) — never
            # derived from step-counter arithmetic, which an imported
            # foreign checkpoint's step offset or a changed config
            # would silently corrupt. Only trust the position when the
            # checkpoint was written under the SAME steps-per-epoch.
            tag = start_epoch - 1
            if self.ckpt.last_restored_spe == spe and 0 < mid < spe:
                start_epoch = tag
                start_batch = mid
                logger.info(
                    "Resuming mid-epoch: epoch %d, batch %d (step %d)",
                    start_epoch,
                    start_batch,
                    int(self.state.step),
                )
            else:
                logger.warning(
                    "Checkpoint was preempted at batch %d under %s "
                    "steps/epoch; current config has %d — resuming at "
                    "epoch granularity",
                    mid,
                    self.ckpt.last_restored_spe,
                    spe,
                )
        if start_epoch >= cfg.epochs:
            logger.info(
                "Checkpoint epoch %d ≥ requested epochs %d — nothing to do",
                start_epoch - 1,
                cfg.epochs,
            )
        profiling = False
        if cfg.profile_dir and self.ctx.is_main:
            jax.profiler.start_trace(cfg.profile_dir)
            profiling = True
        self._watchdog.start()
        # Watchdog forensics: a hang must leave the same post-mortem
        # artifacts as a crash. os._exit(124) skips every finally, so
        # the dump/export run from the abort path itself.
        wd_forensic = None
        if self._recorder.enabled or self.tracer.enabled:
            from ddp_tpu.utils.watchdog import register_forensics

            def wd_forensic():
                self._recorder.dump(self._wd_dump_reason)
                self._export_trace()

            register_forensics(wd_forensic)
        self._preempt_requested = False
        handler_installed, prev_handler = self._install_preemption_handler()
        preempted = False
        last_eval: tuple[float, float] | None = None
        try:
            try:
                for epoch in range(start_epoch, cfg.epochs):
                    skip = start_batch if epoch == start_epoch else 0
                    epoch_start_step = int(self.state.step)
                    with self.tracer.span("epoch", {"epoch": epoch}):
                        stats = self._train_epoch(epoch, skip)
                    # Agreement at the epoch boundary: a SIGTERM that
                    # landed after the last in-loop cadence check —
                    # or a health event the monitor drained at the
                    # epoch tail — must still stop every host on the
                    # same side of the epoch, or survivors would
                    # block in the next epoch's first collective.
                    if self.ctx.num_processes > 1:
                        boundary_step = int(self.state.step)
                        pre, halt, rescue = self._sync_flags(
                            boundary_step
                        )
                        if halt or rescue:
                            ran = boundary_step - epoch_start_step + skip
                            self._act_on_agreed(
                                halt, rescue, epoch=epoch, ran=ran,
                                host_step=boundary_step,
                            )
                    else:
                        pre = self._preempt_requested
                    if pre:
                        # Mid-epoch state, tagged with the incomplete
                        # epoch; overwrite any older preemption save.
                        # No metrics on purpose: metric-less saves are
                        # always preserved under keep_best (a ranked
                        # sentinel would be garbage-collected as worst
                        # and the preemption state lost).
                        # Position within the epoch measured relative
                        # to this epoch's entry step (absolute step
                        # values carry import/config offsets); >= spe
                        # means the epoch actually completed before the
                        # boundary-preemption landed → mid_batch 0.
                        ran = int(self.state.step) - epoch_start_step + skip
                        self.ckpt.save(
                            epoch, self.state, overwrite=True,
                            steps_per_epoch=spe,
                            mid_batch=ran if 0 < ran < spe else 0,
                        )
                        logger.warning(
                            "Preempted during epoch %d at step %d — "
                            "checkpointed; re-run to resume",
                            epoch,
                            int(self.state.step),
                        )
                        preempted = True
                        break
                    self.history.append(stats)
                    do_eval = bool(
                        cfg.eval_every and (epoch + 1) % cfg.eval_every == 0
                    )
                    # keep_best needs the metric AT save time, so eval
                    # runs first only there; otherwise save first — a
                    # failure during a long eval must not lose the
                    # fully-trained epoch.
                    if cfg.keep_best and do_eval:
                        last_eval = self.evaluate()
                        metrics = {"accuracy": last_eval[0]}
                    else:
                        last_eval, metrics = None, None
                    # overwrite=False: if a mid-epoch preemption
                    # artifact holds this tag, keep it (redo-on-crash)
                    # rather than opening a delete-before-commit window;
                    # a later epoch's save supersedes it. If this was
                    # the LAST epoch, supersede explicitly below.
                    with self.tracer.span("checkpoint.save", {"epoch": epoch}):
                        saved = self.ckpt.save(
                            epoch, self.state, steps_per_epoch=spe,
                            metrics=metrics,
                        )
                    if not saved and (
                        epoch == cfg.epochs - 1
                        or self._rescued_epoch == epoch
                    ):
                        # The tag is held by the LAST epoch's earlier
                        # artifact, or by THIS run's mid-epoch rescue
                        # save — both must be superseded by the
                        # completed-epoch state (with its keep_best
                        # metric). Prior-run preemption artifacts keep
                        # the redo-on-crash semantics above.
                        self.ckpt.save(
                            epoch, self.state, overwrite=True,
                            steps_per_epoch=spe, metrics=metrics,
                        )
                    if do_eval and last_eval is None:
                        last_eval = self.evaluate()
                    if last_eval is not None:
                        logger.info(
                            "Epoch %d eval: accuracy %.4f loss %.4f",
                            epoch,
                            *last_eval,
                        )
                        # Eval accuracy joins the live exposition
                        # (render_train's ddp_tpu_train_accuracy).
                        self._prom_state["accuracy"] = last_eval[0]
            finally:
                if profiling:
                    jax.profiler.stop_trace()
                self.ckpt.wait()
            if preempted:
                return {
                    "epochs_run": len(self.history),
                    "preempted": True,
                    "final_accuracy": None,
                    "final_loss": None,
                    "history": [dataclasses.asdict(h) for h in self.history],
                }
            # Reuse the last per-epoch eval rather than re-running it.
            # Still inside the watchdog window: a hang in the final
            # eval collective or checkpoint flush must crash, not stall.
            final_acc, final_loss = last_eval or self.evaluate()
        except BaseException as e:
            # Post-mortem on ANY exit-by-exception. Errors that
            # already dumped (HealthHaltError, NonFiniteLossError)
            # carry their path — don't overwrite their reason.
            if getattr(e, "dump_path", None) is None:
                self._recorder.record(
                    "exception",
                    type=type(e).__name__,
                    message=str(e)[:500],
                )
                self._recorder.dump(f"exception:{type(e).__name__}")
            raise
        finally:
            if wd_forensic is not None:
                from ddp_tpu.utils.watchdog import unregister_forensics

                unregister_forensics(wd_forensic)
            self._watchdog.stop()
            if handler_installed:
                import signal

                # prev None means a non-Python (C-installed) handler we
                # cannot reinstate — SIG_DFL beats leaving ours bound
                # to this finished Trainer.
                signal.signal(
                    signal.SIGTERM,
                    prev_handler if prev_handler is not None else signal.SIG_DFL,
                )
            self._goodput.flush()
            self._export_trace()
        logger.info("Final test accuracy %.4f (loss %.4f)", final_acc, final_loss)
        self._prom_state["accuracy"] = final_acc
        gp = self._goodput.snapshot()
        self.metrics_writer.write(
            "final", accuracy=final_acc, loss=final_loss,
            epochs_run=len(self.history),
            **({"goodput": gp} if gp else {}),
            # The LM community's headline eval number; loss is the
            # mean next-token cross-entropy, so this is exp(loss).
            **(
                {"perplexity": round(float(np.exp(final_loss)), 4)}
                if (self.lm_mode or self.pipe_lm_mode)
                and np.isfinite(final_loss)
                and np.isfinite(np.exp(final_loss))
                else {}
            ),
        )
        # The end-of-run finiteness gate: a diverged run must FAIL
        # with its provenance (layer/step when health was on) and the
        # flight-recorder dump path — not end 0 with a silently
        # degraded final record. The record above is still written
        # (loss serializes as null) so the stream shows the death.
        # The empty-test-split degenerate case (evaluate() returns
        # nan by construction) is not a divergence.
        if not np.isfinite(final_loss) and len(self.test_split[0]) > 0:
            self.metrics_writer.flush()
            dump = self._recorder.dump("nonfinite_final_loss")
            raise NonFiniteLossError(
                float(final_loss),
                dump_path=dump,
                first_nonfinite=self._health.first_nonfinite,
            )
        return {
            "epochs_run": len(self.history),
            "final_accuracy": final_acc,
            "final_loss": final_loss,
            "history": [dataclasses.asdict(h) for h in self.history],
        }

    def _export_trace(self) -> None:
        """Per-rank crash-safe trace export (every rank writes its own
        file; scripts/trace_merge.py joins them on one timeline)."""
        if not (self.tracer.enabled and self.config.trace_dir):
            return
        try:
            path = self.tracer.export_to_dir(self.config.trace_dir)
        except OSError as e:
            logger.warning("trace export failed: %s", e)
            return
        logger.info("Wrote span trace to %s", path)

    # How far the host may run ahead of the devices. Unbounded async
    # dispatch deadlocks the emulated-CPU collective rendezvous when the
    # cores are oversubscribed, and on real chips just buffers garbage;
    # a small window keeps dispatch overlapped with compute.
    MAX_INFLIGHT_STEPS = 8

    def _train_epoch(self, epoch: int, skip_batches: int = 0) -> EpochStats:
        # Epoch-triggered chaos (…@epochN) fires on BOTH paths; step
        # triggers need the per-step loop (guarded at construction).
        self._chaos.on_epoch(epoch)
        if self.fast_runner is not None:
            # The fast path has no mid-epoch granularity (one dispatch
            # per epoch); preemption is honored between epochs.
            return self._train_epoch_fast(epoch)
        cfg = self.config
        from ddp_tpu.train.optim import lr_at

        logger.info("Starting epoch %d", epoch)  # train_ddp.py:194 parity
        t0 = time.perf_counter()
        # Host-side step numbering: the k-th dispatch of this epoch
        # sees step0 + k in-graph. One sync at epoch entry; the loop
        # itself never reads the device step counter.
        step0 = int(self.state.step)
        losses = []
        last_metrics = None
        n_batches = 0
        inflight: deque = deque()
        # Attribution (--trace_dir) times each loader fetch and splits
        # dispatch-return from block_until_ready; disabled, batches()
        # hands back the raw iterator and on_step returns immediately.
        attr = self._attr
        # --sanitize: the guard makes any IMPLICIT transfer in this
        # loop raise at the offending call (runtime/sanitize.py — the
        # dynamic half of lint rule DDP002). The loop's DELIBERATE
        # syncs each run in an allow() window below: the log-cadence
        # reads, the one-step-behind health retire, the consensus
        # gather. Disabled, both are nullcontexts.
        with self._sanitizer.guard():
            for batch_idx, batch in enumerate(
                attr.batches(self.loader.epoch(epoch, skip_batches)),
                start=skip_batches,
            ):
                # Chaos trigger point (--chaos): "step N" fires before
                # the dispatch that would run global step N — kills/
                # SIGTERMs land here, input stalls sleep here (the
                # straggler sentry and goodput accounting see them
                # like real ones).
                self._chaos.on_step(step0 + n_batches)
                self.state, metrics = self.train_step(
                    self.state, batch.images, batch.labels
                )
                timing = attr.on_step(metrics.loss)
                host_step = step0 + n_batches  # this dispatch's step
                self._recorder.record(
                    "step", epoch=epoch, batch=batch_idx, step=host_step
                )
                if self._health.enabled:
                    # Retires the PREVIOUS step's [G] health vectors
                    # (one step behind the dispatch — the only added
                    # sync, hence the allow window) and runs the
                    # sentry; events apply --health_action.
                    with self._sanitizer.allow():
                        events = self._health.on_step(host_step, metrics)
                        if events:
                            self._on_health_events(
                                events, epoch=epoch, ran=batch_idx + 1
                            )
                last_metrics = metrics
                n_batches += 1
                inflight.append(metrics.loss)
                if len(inflight) > self.MAX_INFLIGHT_STEPS:
                    jax.block_until_ready(inflight.popleft())
                # Progress beat AFTER the bounded sync above: a hung
                # collective stalls that block_until_ready, beats
                # stop, and the watchdog converts the hang into a
                # crash.
                self._watchdog.beat()
                if self.ctx.num_processes == 1:
                    if self._preempt_requested:
                        break  # caller checkpoints the mid-epoch state
                elif batch_idx % cfg.log_interval == 0:
                    # Multi-host: breaking on the local flag alone
                    # would leave peers blocked in the next step's
                    # collective. ONE agreement gather at this
                    # deterministic cadence carries the preemption
                    # flag AND the deferred health escalations
                    # (_on_health_events), so every process halts /
                    # checkpoints / exits at the SAME batch.
                    with self._sanitizer.allow():
                        pre, halt, rescue = self._sync_flags(host_step)
                        if halt or rescue:
                            self._act_on_agreed(
                                halt, rescue, epoch=epoch,
                                ran=batch_idx + 1, host_step=host_step,
                            )
                    if pre:
                        break
                if batch_idx % cfg.log_interval == 0:
                    # train_ddp.py:201-202 parity: rank-0 loss print.
                    # .item() syncs, so only at the log cadence — the
                    # allow window marks it deliberate under
                    # --sanitize.
                    with self._sanitizer.allow():
                        loss = float(metrics.loss)
                        losses.append(loss)
                        step_now = int(self.state.step)
                        logger.info(
                            "Epoch %d Batch %d Loss %.4f",
                            epoch, batch_idx, loss,
                        )
                        self._log_startup()
                        gn = (
                            {}
                            if metrics.grad_norm is None
                            else {
                                "grad_norm": round(
                                    float(metrics.grad_norm), 6
                                )
                            }
                        )
                        lr_now = round(
                            lr_at(self._lr_schedule, max(0, step_now - 1)),
                            8,
                        )
                        obs_fields = self._step_obs_fields(timing)
                        # Device-memory sample + compile-event drain
                        # (host-side reads, no device sync — inside
                        # the window only because metrics/recorder
                        # writes belong with the other log-cadence
                        # bookkeeping). {} when --xprof is off.
                        xprof_fields = self._xprof_step_fields()
                    self.metrics_writer.write(
                        "step",
                        epoch=epoch,
                        batch=batch_idx,
                        step=step_now,
                        loss=loss,
                        lr=lr_now,
                        **gn,
                        **obs_fields,
                        **xprof_fields,
                        # Analytic per-step collective payload
                        # (parallel/zero.py estimates — static per
                        # strategy, no sync): present on the ddp/zero
                        # paths so the sharded update's comm story is
                        # auditable next to the step times. The
                        # hierarchical step splits it per fabric.
                        **(
                            {"comm_bytes": self._comm_bytes}
                            if self._comm_bytes is not None
                            else {}
                        ),
                        **(
                            {
                                "comm_bytes_ici": self._comm_by_axis[
                                    "ici"
                                ]["total"],
                                "comm_bytes_dcn": self._comm_by_axis[
                                    "dcn"
                                ]["total"],
                            }
                            if self._comm_by_axis is not None
                            else {}
                        ),
                    )
                    self._recorder.record(
                        "log", step=step_now, epoch=epoch,
                        batch=batch_idx, loss=loss, **gn,
                    )
                    # Live exposition state (--metrics_port /metricsz).
                    self._prom_state.update(
                        step=step_now, epoch=epoch, loss=loss, lr=lr_now,
                        **gn,
                    )
                    if "mfu" in obs_fields:
                        self._prom_state["mfu"] = obs_fields["mfu"]
        if last_metrics is not None:
            jax.block_until_ready(last_metrics.loss)
        # The monitor still owes the LAST step's ingestion (it runs
        # one behind); provenance for a final-step NaN lands here.
        tail_events = self._health.drain()
        if tail_events:
            self._on_health_events(
                tail_events, epoch=epoch, ran=n_batches + skip_batches
            )
        seconds = time.perf_counter() - t0
        return self._finish_epoch(epoch, losses, n_batches, seconds)

    def _log_startup(self) -> None:
        """ONE line on what the process did before its first step
        (obs/startup.py), once that step has run: at the first loss
        read, or, where an epoch is one dispatch, at its end."""
        if not self._startup_logged:
            self._startup_logged = True
            logger.info("%s", startup_line(self.tracer))

    def _finish_epoch(
        self,
        epoch: int,
        losses: list,
        n_batches: int,
        seconds: float,
        obs_extra: dict | None = None,
    ) -> EpochStats:
        self._log_startup()
        """Shared epoch-summary contract for the step and fast paths."""
        images = n_batches * self.global_batch_size
        stats = EpochStats(
            epoch=epoch,
            mean_loss=float(np.mean(losses)) if losses else float("nan"),
            seconds=seconds,
            images_per_sec=images / seconds if seconds else 0.0,
        )
        logger.info(
            "Epoch %d done: %d batches in %.2fs (%.0f images/sec global)",
            epoch,
            n_batches,
            seconds,
            stats.images_per_sec,
        )
        extra = dict(obs_extra or {})
        if self.seq_mode:
            # For sequence models the sample rate is sequences/sec;
            # tokens/sec is the number the field actually compares.
            extra["tokens_per_sec"] = round(
                stats.images_per_sec * self.config.seq_len, 1
            )
        # Attribution totals from the step loop (empty on the fast
        # path, which passes its own obs_extra; empty when disabled).
        totals = self._attr.finish_epoch()
        if totals.steps:
            extra.update(
                input_wait_s=round(totals.input_wait_s, 4),
                dispatch_s=round(totals.dispatch_s, 4),
                compute_s=round(totals.compute_s, 4),
                recompiles=totals.recompiles,
            )
        # MFU needs only the epoch rate + the analytic estimate —
        # reported whenever the model has an estimator, traced or not.
        epoch_mfu = _mfu(
            stats.images_per_sec, self._flops_per_example, self._peak_flops
        )
        if epoch_mfu is not None:
            extra["mfu"] = round(epoch_mfu, 6)
        # Goodput accrues per epoch and flushes per epoch: a kill
        # between epochs loses at most one epoch of accounting.
        self._goodput.add_productive(seconds)
        self._goodput.flush()
        gp = self._goodput.snapshot()
        if gp:
            extra["goodput"] = gp["goodput"]
        if self._health.enabled:
            # Cumulative sentry/provenance event count: a triage pass
            # over epoch records sees WHERE anomalies clustered.
            extra["health_events"] = int(
                sum(self._health.events_total.values())
            )
        if self._comm_bytes is not None:
            extra["comm_bytes"] = self._comm_bytes
        if self._comm_by_axis is not None:
            extra["comm_bytes_ici"] = self._comm_by_axis["ici"]["total"]
            extra["comm_bytes_dcn"] = self._comm_by_axis["dcn"]["total"]
        if self._xprof.enabled:
            # Epoch-boundary memory sample + compile totals (the drain
            # inside also flushes compiles paid outside the log
            # cadence — eval, restore — to the metrics stream).
            xf = self._xprof_step_fields()
            for k in ("hbm_high_water_bytes", "hbm_headroom_frac"):
                if k in xf:
                    extra[k] = xf[k]
            # Per replica, not the max: one that holds nothing (or
            # everyone's share) must show. Epoch records only.
            by_device = self._hbm.sample().get("hbm_peak_bytes_by_device")
            if by_device:
                extra["hbm_peak_bytes_by_device"] = by_device
            extra["compile_s"] = round(self._xprof.total_compile_s, 4)
            extra["lower_s"] = round(self._xprof.total_lower_s, 4)
            extra["compiled_programs"] = self._xprof.program_count
        self.metrics_writer.write(
            "epoch",
            epoch=epoch,
            batches=n_batches,
            seconds=round(seconds, 3),
            images_per_sec=round(stats.images_per_sec, 1),
            mean_loss=stats.mean_loss,
            **extra,
        )
        self._recorder.record(
            "epoch", epoch=epoch, batches=n_batches,
            seconds=round(seconds, 3), mean_loss=stats.mean_loss,
        )
        self._prom_state["epoch"] = epoch
        self._prom_state["images_per_sec"] = round(stats.images_per_sec, 1)
        if epoch_mfu is not None:
            self._prom_state["mfu"] = round(epoch_mfu, 6)
        if totals.steps:
            self._prom_state["recompiles"] = (
                self._prom_state.get("recompiles", 0) + totals.recompiles
            )
        return stats

    def _train_epoch_fast(self, epoch: int) -> EpochStats:
        """One dispatch for the whole epoch (train/fast.py).

        Per-step losses come back as one stacked array; the reference's
        every-``log_interval`` loss lines are printed from it after the
        device sync, so observable output matches the step path.
        """
        cfg = self.config
        logger.info("Starting epoch %d (compiled fast path)", epoch)
        obs_extra = None
        t0 = time.perf_counter()
        # --sanitize: the epoch dispatch is the whole hot loop here —
        # the guard proves it transfer-free; the stacked per-step
        # losses are read AFTER it (outside the guard), where host
        # reads belong.
        if self._attr.enabled:
            # Per-EPOCH attribution — the whole epoch is one dispatch,
            # so dispatch-return vs block_until_ready is all the host
            # can observe of it (steptime.dispatch_compute_split).
            with self._sanitizer.guard():
                (self.state, metrics), disp_s, comp_s, recompiles = (
                    dispatch_compute_split(
                        self.fast_runner, self.state, epoch
                    )
                )
            self.tracer.complete("epoch.dispatch", t0, disp_s)
            self.tracer.complete(
                "epoch.compute", t0 + disp_s, comp_s,
                {"recompiles": recompiles} if recompiles else None,
            )
            obs_extra = {
                "dispatch_s": round(disp_s, 4),
                "compute_s": round(comp_s, 4),
                "recompiles": recompiles,
            }
        else:
            with self._sanitizer.guard():
                self.state, metrics = self.fast_runner(self.state, epoch)
        losses_all = np.asarray(metrics.loss)
        gnorms_all = (
            None if metrics.grad_norm is None else np.asarray(metrics.grad_norm)
        )
        seconds = time.perf_counter() - t0
        n_batches = len(losses_all)
        end_step = int(self.state.step)  # one sync, outside the loop
        losses = []
        from ddp_tpu.train.optim import lr_at

        for batch_idx in range(0, n_batches, cfg.log_interval):
            loss = float(losses_all[batch_idx])
            losses.append(loss)
            step_no = end_step - n_batches + batch_idx + 1
            logger.info("Epoch %d Batch %d Loss %.4f", epoch, batch_idx, loss)
            gn = (
                {}
                if gnorms_all is None
                else {"grad_norm": round(float(gnorms_all[batch_idx]), 6)}
            )
            self.metrics_writer.write(
                "step", epoch=epoch, batch=batch_idx,
                step=step_no,
                loss=loss,
                lr=round(lr_at(self._lr_schedule, max(0, step_no - 1)), 8),
                **gn,
            )
        return self._finish_epoch(
            epoch, losses, n_batches, seconds, obs_extra
        )

    # ---- eval (absent in the reference; required by the north star) ----

    def evaluate(self, *, use_ema: bool | None = None) -> tuple[float, float]:
        """Full test-split accuracy/loss, batched over the mesh.

        The split is padded with wraparound to a global-batch multiple;
        padding carries weight 0 so the totals are exact. In multi-host
        runs each process feeds its contiguous slice of the padded
        split. With ``--ema_decay`` the averaged parameters are
        evaluated (the point of keeping them) and the raw-weights
        accuracy is logged alongside — early in a run the EMA lags far
        behind and a single number would read as a regression.
        """
        eval_params = self.state.params
        if use_ema is None:
            use_ema = bool(self.config.ema_decay)
        if use_ema:
            from ddp_tpu.train.optim import ema_params

            averaged = ema_params(self.state.opt_state)
            if averaged is None:
                logger.warning(
                    "evaluate(use_ema=True) but no EMA state exists "
                    "(--ema_decay off?) — evaluating RAW weights"
                )
            else:
                eval_params = averaged
                # Companion raw-weights eval for the first couple of
                # evals only: that's when the EMA lags enough to read
                # as a regression, and a full second test-split pass
                # per epoch forever is not worth one log line.
                if self._raw_eval_count < 2:
                    self._raw_eval_count += 1
                    raw_acc, raw_loss = self.evaluate(use_ema=False)
                    logger.info(
                        "Eval with raw (non-EMA) weights: accuracy "
                        "%.4f loss %.4f", raw_acc, raw_loss,
                    )
        images, labels = self.test_split
        # Accumulation exists to keep the per-forward footprint at
        # batch_size×shards — eval must not undo that by running one
        # k×-sized forward. per_shard_batch (not config.batch_size):
        # an elastic resize rescaled it to preserve the global batch.
        bs = self.per_shard_batch * self.data_shards
        n = len(images)
        if n == 0:
            return float("nan"), float("nan")
        padded = -(-n // bs) * bs
        weights = np.ones(padded, np.float32)
        weights[n:] = 0.0
        idx = np.arange(padded) % n
        procs, pid = jax.process_count(), jax.process_index()
        if bs % procs:
            # Mirror the loader's guard (data/loader.py): a silent
            # floor-divide here would evaluate a truncated split.
            raise ValueError(
                f"eval batch {bs} (batch_size × data shards) not "
                f"divisible by {procs} processes"
            )
        local = bs // procs
        correct_total, loss_total = 0.0, 0.0
        for b in range(padded // bs):
            lo = b * bs + pid * local
            sel = idx[lo : lo + local]
            img_np, lbl_np, w_np = images[sel], labels[sel], weights[lo : lo + local]
            if procs == 1:
                put = lambda a, s: jax.device_put(a, s)
            else:
                put = lambda a, s: jax.make_array_from_process_local_data(s, a)
            c, l = self.eval_step(
                eval_params,
                self.state.model_state,
                put(img_np, self.loader._img_sharding),
                put(lbl_np, self.loader._lbl_sharding),
                put(w_np, self.loader._lbl_sharding),
            )
            correct_total += float(c)
            loss_total += float(l)
            # Eval progress counts as progress — a slow (healthy) eval
            # must not trip the hang detector.
            self._watchdog.beat()
        return correct_total / n, loss_total / n

    def close(self) -> None:
        self.loader.close()
        self.ckpt.close()
        self.metrics_writer.close()
        if self._metrics_port is not None:
            self._metrics_port.stop()
            self._metrics_port = None


imported(__name__, _IMPORT_T0)
