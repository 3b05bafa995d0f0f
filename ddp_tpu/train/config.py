"""Run configuration — the reference's CLI surface plus its hard-codes.

Parity: ``--epochs`` (default 10) and ``--batch_size`` (default 32,
per data shard) match train_ddp.py:216-218 exactly. Everything the
reference hard-codes becomes a named field with the reference value as
default: lr=0.01 (train_ddp.py:41), checkpoint dir ``./checkpoints``
(train_ddp.py:53), data root ``./data`` (data.py:11), log interval 100
(train_ddp.py:201). The ``--world_size`` flag README.md:72 advertises
but never implements exists here as ``--num_devices`` (how many devices
to use; -1 = all).
"""

from __future__ import annotations

import argparse
import dataclasses


@dataclasses.dataclass
class TrainConfig:
    # Reference CLI (train_ddp.py:216-218)
    epochs: int = 10
    batch_size: int = 32  # per data-parallel shard, like per-rank bs=32

    # Reference hard-codes, surfaced
    lr: float = 0.01  # train_ddp.py:41
    momentum: float = 0.0  # SGD(lr=0.01) → momentum 0
    checkpoint_dir: str = "./checkpoints"  # train_ddp.py:53
    data_root: str = "./data"  # data.py:11
    log_interval: int = 100  # train_ddp.py:201
    seed: int = 0
    shuffle: bool = True  # data.py:18
    num_workers: int = 2  # data.py:22 — native C++ prefetch pool size

    # Framework knobs (no reference analogue)
    model: str = "simple_cnn"
    model_depth: int | None = None  # None = family default (e.g. ViT 12)
    # Width for the sequence family (long_context/causal_lm d_model);
    # registry models fix their widths per family name.
    model_dim: int | None = None
    # Attention heads for the spec-driven families (seq + pipe);
    # registry models fix theirs. head_dim = model_dim / num_heads —
    # 128-wide heads match the MXU's width.
    num_heads: int = 4
    # Grouped-query attention for the causal LM: kv heads < num_heads
    # shrink the generation KV cache (and its decode bandwidth) by
    # the group factor. 0 = plain MHA.
    num_kv_heads: int = 0
    augment: str | None = None  # data/augment.py: "crop_flip" | "flip"
    # "auto" resolves per model family: mnist normally, synthetic_seq
    # for --model long_context. An explicit image dataset with the
    # long-context model is an error, not a silent substitution.
    dataset: str = "auto"
    num_classes: int | None = None  # None = infer from dataset
    optimizer: str = "sgd"  # sgd | adam | adamw
    weight_decay: float = 0.0
    warmup_steps: int = 0
    decay_steps: int = 0  # >0 enables cosine decay to this many steps
    grad_clip_norm: float = 0.0
    # Staircase decay: lr ×= lr_decay_factor at each step milestone
    # (e.g. "3000,6000"); mutually exclusive with decay_steps (cosine).
    lr_milestones: str = ""
    lr_decay_factor: float = 0.1
    label_smoothing: float = 0.0  # soft targets (1-α)·one_hot + α/K
    # >0: track an EMA of params in opt_state and evaluate with it —
    # the standard ViT/ResNet eval-quality lever; checkpoints carry it.
    ema_decay: float = 0.0
    grad_accum_steps: int = 1  # microbatches accumulated per update
    backend: str | None = None  # None = auto (tpu if present else cpu)
    num_devices: int = -1  # devices on the data axis; -1 = all
    # Mesh geometry past pure DDP (runtime/mesh.py axis vocabulary).
    # Any axis > 1 switches the trainer to the GSPMD step
    # (parallel/spmd.py): tensor / ZeRO-style / expert parallelism.
    mesh_model: int = 1  # tensor parallelism
    # Pipeline parallelism (--model pipe_vit): stages over the pipe
    # axis; microbatches stream through (parallel/pipeline.py), with
    # --pipe_schedule picking differentiable GPipe, hand-scheduled
    # 1F1B (parallel/one_f1b.py — O(S) activation stash), or
    # interleaved 1F1B (parallel/interleaved.py — --virtual_stages v
    # model chunks per device, bubble (S−1)/(v·M+S−1)).
    mesh_pipe: int = 1
    pipe_schedule: str = "gpipe"  # gpipe | 1f1b | interleaved
    virtual_stages: int = 1  # interleaved only: chunks per device
    num_microbatches: int = 4
    mesh_fsdp: int = 1  # parameter+optimizer sharding
    mesh_expert: int = 1  # MoE expert parallelism
    # Sequence/context parallelism: tokens shard over the seq axis
    # (ring or Ulysses attention). For the sequence models —
    # --model long_context (classifier) or causal_lm (decoder LM) —
    # on the synthetic_seq dataset.
    mesh_seq: int = 1
    # Two-level pod geometry: number of SLICES on the mesh's outermost
    # dcn axis (runtime/mesh.py). Slices are joined by the slow
    # inter-slice fabric; the hierarchical zero step reduce-scatters
    # within a slice over ICI and exchanges only 1/N shards across
    # slices over DCN. On CPU, --spawn P --emulate_devices K emulates
    # P slices of K chips (process boundaries = the slow fabric).
    mesh_dcn: int = 1
    seq_len: int = 2048  # total sequence length (long_context/causal_lm)
    seq_dim: int = 16  # input feature channels per token
    seq_strategy: str = "ring"  # ring | ulysses
    vocab_size: int = 256  # causal_lm token vocabulary
    # >0: causal_lm/pipe_lm route every --moe_every-th block's MLP
    # through this many experts (GShard top-k routing).
    moe_experts: int = 0
    # Which blocks route: block i (1-based) hosts experts iff
    # i % moe_every == 0. 1 = every block (fully-routed). The pipe
    # family needs moe_every to divide --model_depth (stages must be
    # structure-uniform for parameter stacking — models/pipeline_lm.py).
    moe_every: int = 2
    # Routing config for those MoE blocks: experts per token, and
    # whether the surviving top-k gates renormalize to sum to 1.
    # Recorded in the lm_spec.json checkpoint sidecar so the decode /
    # serving path reproduces the training routing.
    moe_top_k: int = 2
    moe_normalize_gates: bool = True
    # Real LM data: a file read as raw bytes (--dataset text),
    # chunked into seq_len sequences (data/text.py). No tokenizer dep.
    text_file: str | None = None
    zero1: bool = False  # shard optimizer state over data (ZeRO stage 1)
    # Weight-update strategy on the data-parallel path. "auto" keeps
    # the mesh-derived choice (shard_map DDP / GSPMD). "zero" is the
    # ZeRO-style sharded update (parallel/zero.py): reduce-scatter
    # grads in size-targeted buckets, run the optimizer on 1/N flat
    # shards (moments REST sharded — Adam memory divides by the
    # replica count), all-gather params. Covers the DDP image family
    # (explicit shard_map collectives) and the causal LM (in-graph
    # GSPMD expression); parity-pinned against the replicated update.
    parallel: str = "auto"  # auto | zero
    # Bucket size target for the zero reduce-scatters (the knob DDP's
    # C++ reducer calls bucket_cap_mb): smaller buckets give the
    # scheduler more collectives to overlap with backward compute,
    # larger ones amortize per-collective latency.
    zero_bucket_mb: float = 4.0
    # Wire dtype of the zero parameter all-gather. "fp32" (default) is
    # bit-identical to the pre-flag path. "bf16" halves the dominant
    # all-gather bytes (PAPERS.md #3's headline win): the optimizer
    # math and the fp32 MASTER shards (kept in opt_state, sharded like
    # the moments) stay full precision — only the forward sees
    # bf16-rounded params, so rounding never compounds across steps.
    zero_gather_dtype: str = "fp32"  # fp32 | bf16
    # Rematerialize block activations in the backward (jax.checkpoint):
    # HBM for FLOPs. Supported by the block-structured families
    # (resnet*, vit*, vit_moe*); simple_cnn has no block stack to remat.
    remat: bool = False
    emulate_devices: int | None = None  # N virtual CPU devices (dev box)
    compute_dtype: str = "float32"  # "bfloat16" for mixed precision
    eval_every: int = 1  # epochs between test-split evals (0 = only final)
    # Compiled-epoch fast path (train/fast.py): dataset device-resident,
    # on-device shuffle, lax.scan over the epoch — one dispatch/epoch.
    # Single-process, pure-DDP, no grad accumulation.
    fast_epoch: bool = False
    max_checkpoints: int | None = None  # None = keep all, like the reference
    # Resume from a specific saved epoch instead of the latest —
    # rewind-and-retrain (e.g. after a bad LR change). The abandoned
    # branch's LATER checkpoints are deleted on restore, so a crash
    # mid-rewind can never auto-resume the discarded branch.
    resume_epoch: int | None = None
    # Restore ONLY params/model_state and start the optimizer (and its
    # schedules/step counter) fresh. The escape hatch for changing the
    # recipe mid-run — a checkpoint's optimizer state is unusable under
    # a different --optimizer/schedule layout.
    reset_opt_state: bool = False
    # Retain the max_checkpoints BEST-accuracy epochs instead of the
    # most recent (requires eval_every=1 so every save has a metric).
    keep_best: bool = False
    synthetic_data: bool = False  # offline fallback dataset
    synthetic_size: int | None = None
    profile_dir: str | None = None  # jax.profiler trace output
    metrics_file: str | None = None  # JSONL metrics from process 0
    # Host-level observability (ddp_tpu.obs): per-rank span traces
    # (Perfetto trace_event JSON), per-step input-wait/dispatch/compute
    # attribution in the metrics stream, and MFU per step. Off (None)
    # by default — disabled mode is pinned free (tests/test_obs.py).
    # Attribution synchronizes each step, so expect the bounded-
    # inflight overlap to disappear while it is on: a diagnosis mode.
    trace_dir: str | None = None
    # Bounded trace memory: the ring keeps the LAST this-many events.
    trace_ring_events: int = 65536
    # Compiled-program introspection (ddp_tpu.obs.xprof): instrument
    # the hot-path jit programs so every compile is ledgered (label,
    # arg-shape signature, compile wall-time, XLA-measured FLOPs/
    # bytes, memory breakdown, HLO collective payloads), recompiles
    # get culprits instead of counts, and step/epoch records carry
    # the device-memory high-water/headroom. A diagnosis mode like
    # --trace_dir; off (default) is pinned free.
    xprof: bool = False
    # Abort the process when no step completes for this many seconds
    # (0 = off). Converts a hung collective into a crash the launcher
    # detects, so restart+resume can recover. Set generously above the
    # first-step compile time.
    watchdog_timeout: float = 0.0
    # Run health (ddp_tpu.obs.health): fuse per-layer-group gradient
    # stats (norms, max-abs, non-finite counts, update/param ratio)
    # into the train step, retire them one step behind the dispatch,
    # attribute the FIRST non-finite gradient to its layer path and
    # step, and run the anomaly sentry (loss spike / grad explosion /
    # straggler / recompile storm) over the per-step records. Off by
    # default; disabled mode is pinned free (tests/test_health.py).
    health: bool = False
    # What an anomaly-sentry event does: log loudly ("warn"), save an
    # overwrite mid-epoch checkpoint and keep going ("checkpoint"), or
    # raise HealthHaltError after dumping the flight recorder ("halt").
    health_action: str = "warn"
    # Sentry rolling-baseline window (steps).
    health_window: int = 32
    # Fault injection for drills and tests: poison one layer group's
    # gradients with NaN at one step, INSIDE the compiled graph —
    # "layer/group@step", e.g. "block1/attn@3". Requires --health.
    health_inject_nan: str | None = None
    # Flight recorder (ddp_tpu.obs.recorder): ring of the last N step
    # records + config/env/mesh context, dumped crash-safely (per
    # rank, next to the checkpoints) on exception, SIGTERM, the
    # non-finite final-loss gate, and watchdog kill. 0 disables.
    flight_records: int = 256
    # Serve the live train counters as Prometheus text at
    # http://127.0.0.1:PORT/metricsz (obs/promtext.py). None = off;
    # 0 binds an ephemeral port (logged at startup).
    metrics_port: int | None = None
    # Deterministic fault injection (runtime/chaos.py): a comma-
    # separated schedule of kills / SIGTERMs / input stalls /
    # checkpoint corruption at exact steps/epochs, e.g.
    # "kill:rank1@step20,stall:input@step5:2.5s,ckpt_corrupt:latest".
    # Every event fires ONCE across restarts (per-rank ledger next to
    # the checkpoints) — see docs/ROBUSTNESS.md for the grammar.
    chaos: str | None = None
    # Runtime sanitizer (runtime/sanitize.py): arm
    # jax.transfer_guard("disallow") around the train hot loop so any
    # IMPLICIT host<->device transfer raises at the offending call
    # (the dynamic half of scripts/lint.py's DDP002), and arm the
    # step watchdog at --sanitize_timeout with a desync-diagnosing
    # abort when --watchdog_timeout is unset. A diagnosis mode, like
    # --trace_dir.
    sanitize: bool = False
    # Desync-watchdog timeout under --sanitize (seconds; only applies
    # when --watchdog_timeout is 0). Must clear the first-step
    # compile. 0 disables the watchdog half.
    sanitize_timeout: float = 300.0
    # Restart-with-resume under --spawn: when a rank dies, the
    # launcher reaps the whole world and relaunches it (fresh
    # coordinator, exponential backoff) up to this many times; each
    # generation auto-resumes from the latest checkpoint and counts
    # as a restart in goodput.json. 0 = fail fast (the old behavior).
    max_restarts: int = 0
    # Base seconds for the launcher's exponential restart backoff
    # (backoff = restart_backoff * 2^i, capped at 30 s).
    restart_backoff: float = 1.0
    # Elastic world resize (docs/ROBUSTNESS.md "Elastic world resize").
    # Supervisor side (--spawn): a rank that exits with the SHRINK code
    # is permanently gone — relaunch the world one smaller (down to
    # --min_world) instead of failing; GROW relaunches one larger.
    # Worker side (any launch mode): re-derive the mesh from the LIVE
    # device count, preserve the recorded global batch by rescaling the
    # per-shard batch (elastic.json contract), and restore checkpoints
    # world-shape-agnostically (reshard on load; zero re-buckets).
    # Pipeline models are excluded for now (stage placement is
    # per-device; MPMD is its own roadmap item).
    elastic: bool = False
    # Smallest world an elastic supervisor may shrink to; shrinking
    # below raises instead of silently degrading further.
    min_world: int = 1

    # Multi-process / multi-host (reference: spawn at train_ddp.py:222-224
    # + env:// rendezvous at utils.py:7-11)
    spawn: int = 1  # >1: fork N local jax.distributed processes
    coordinator_address: str | None = None  # host:port, MASTER_ADDR role
    num_processes: int | None = None
    process_id: int | None = None

    @classmethod
    def parser(cls) -> argparse.ArgumentParser:
        p = argparse.ArgumentParser(description="TPU-native DDP trainer")
        p.add_argument("--epochs", type=int, default=cls.epochs)
        p.add_argument("--batch_size", type=int, default=cls.batch_size)
        p.add_argument("--lr", type=float, default=cls.lr)
        p.add_argument("--momentum", type=float, default=cls.momentum)
        p.add_argument("--checkpoint_dir", default=cls.checkpoint_dir)
        p.add_argument("--data_root", default=cls.data_root)
        p.add_argument("--log_interval", type=int, default=cls.log_interval)
        p.add_argument("--seed", type=int, default=cls.seed)
        p.add_argument("--no_shuffle", action="store_true")
        p.add_argument("--num_workers", type=int, default=cls.num_workers)
        p.add_argument("--model", default=cls.model)
        p.add_argument("--model_depth", type=int, default=None)
        p.add_argument("--model_dim", type=int, default=None)
        p.add_argument("--num_heads", type=int, default=cls.num_heads)
        p.add_argument(
            "--num_kv_heads", type=int, default=cls.num_kv_heads
        )
        p.add_argument(
            "--augment", default=None, choices=("none", "crop_flip", "flip")
        )
        p.add_argument("--dataset", default=cls.dataset)
        p.add_argument("--num_classes", type=int, default=None)
        p.add_argument(
            "--optimizer", default=cls.optimizer, choices=("sgd", "adam", "adamw")
        )
        p.add_argument("--weight_decay", type=float, default=cls.weight_decay)
        p.add_argument("--warmup_steps", type=int, default=cls.warmup_steps)
        p.add_argument("--decay_steps", type=int, default=cls.decay_steps)
        p.add_argument("--grad_clip_norm", type=float, default=cls.grad_clip_norm)
        p.add_argument("--lr_milestones", default=cls.lr_milestones)
        p.add_argument(
            "--lr_decay_factor", type=float, default=cls.lr_decay_factor
        )
        p.add_argument(
            "--label_smoothing", type=float, default=cls.label_smoothing
        )
        p.add_argument("--ema_decay", type=float, default=cls.ema_decay)
        p.add_argument(
            "--grad_accum_steps", type=int, default=cls.grad_accum_steps
        )
        p.add_argument("--backend", default=None, choices=(None, "tpu", "cpu"))
        p.add_argument("--num_devices", type=int, default=cls.num_devices)
        p.add_argument("--mesh_model", type=int, default=cls.mesh_model)
        p.add_argument("--mesh_pipe", type=int, default=cls.mesh_pipe)
        p.add_argument(
            "--pipe_schedule", default=cls.pipe_schedule,
            choices=("gpipe", "1f1b", "interleaved"),
        )
        p.add_argument(
            "--virtual_stages", type=int, default=cls.virtual_stages
        )
        p.add_argument(
            "--num_microbatches", type=int, default=cls.num_microbatches
        )
        p.add_argument("--mesh_fsdp", type=int, default=cls.mesh_fsdp)
        p.add_argument("--mesh_expert", type=int, default=cls.mesh_expert)
        p.add_argument("--mesh_seq", type=int, default=cls.mesh_seq)
        p.add_argument("--seq_len", type=int, default=cls.seq_len)
        p.add_argument("--seq_dim", type=int, default=cls.seq_dim)
        p.add_argument(
            "--seq_strategy", default=cls.seq_strategy,
            choices=("ring", "ulysses"),
        )
        p.add_argument("--vocab_size", type=int, default=cls.vocab_size)
        p.add_argument("--moe_experts", type=int, default=cls.moe_experts)
        p.add_argument(
            "--moe_every", type=int, default=cls.moe_every,
            help="route every k-th block's MLP (1 = all blocks)",
        )
        p.add_argument(
            "--moe_top_k", type=int, default=cls.moe_top_k,
            help="experts each token visits (GShard top-k routing)",
        )
        p.add_argument(
            "--moe_raw_gates", action="store_true",
            help="combine experts with raw top-k gate values instead "
            "of renormalizing them to sum to 1",
        )
        p.add_argument(
            "--text_file", default=cls.text_file,
            help="byte-level corpus for --dataset text (causal_lm)",
        )
        p.add_argument("--zero1", action="store_true")
        p.add_argument(
            "--parallel", default=cls.parallel, choices=("auto", "zero"),
            help="weight-update strategy: zero = ZeRO-style sharded "
            "update (reduce-scatter grads, 1/N optimizer shards, "
            "all-gather params — parallel/zero.py)",
        )
        p.add_argument(
            "--zero_bucket_mb", type=float, default=cls.zero_bucket_mb,
            help="gradient bucket size target for --parallel zero "
            "(MB; smaller = more overlap-schedulable collectives)",
        )
        p.add_argument(
            "--zero_gather_dtype", default=cls.zero_gather_dtype,
            choices=("fp32", "bf16"),
            help="wire dtype of the zero param all-gather: bf16 halves "
            "the dominant collective while fp32 master shards keep the "
            "update exact (fp32 = bit-identical default)",
        )
        p.add_argument(
            "--mesh_dcn", type=int, default=cls.mesh_dcn,
            help="pod slices on the outermost dcn axis: the zero step "
            "goes hierarchical (reduce-scatter within a slice over "
            "ICI, exchange 1/N shards across slices over DCN)",
        )
        p.add_argument("--remat", action="store_true")
        p.add_argument("--emulate_devices", type=int, default=None)
        p.add_argument(
            "--compute_dtype", default=cls.compute_dtype,
            choices=("float32", "bfloat16"),
        )
        p.add_argument("--eval_every", type=int, default=cls.eval_every)
        p.add_argument("--fast_epoch", action="store_true")
        p.add_argument("--max_checkpoints", type=int, default=None)
        p.add_argument("--resume_epoch", type=int, default=None)
        p.add_argument("--reset_opt_state", action="store_true")
        p.add_argument("--keep_best", action="store_true")
        p.add_argument("--synthetic_data", action="store_true")
        p.add_argument("--synthetic_size", type=int, default=None)
        p.add_argument("--profile_dir", default=None)
        p.add_argument("--metrics_file", default=None)
        p.add_argument(
            "--trace_dir", default=None,
            help="emit per-rank Perfetto span traces + step-time "
            "attribution + MFU (ddp_tpu.obs; see docs/OBSERVABILITY.md)",
        )
        p.add_argument(
            "--trace_ring_events", type=int, default=cls.trace_ring_events,
        )
        p.add_argument(
            "--xprof", action="store_true",
            help="compiled-program introspection: per-executable "
            "compile ledger (XLA FLOPs/memory/collectives), recompile "
            "culprits, HBM high-water in step/epoch records "
            "(ddp_tpu.obs.xprof; see docs/OBSERVABILITY.md)",
        )
        p.add_argument(
            "--watchdog_timeout", type=float, default=cls.watchdog_timeout
        )
        p.add_argument(
            "--health", action="store_true",
            help="per-layer gradient health stats + NaN provenance + "
            "anomaly sentry (ddp_tpu.obs.health; see "
            "docs/OBSERVABILITY.md)",
        )
        p.add_argument(
            "--health_action", default=cls.health_action,
            choices=("warn", "checkpoint", "halt"),
            help="what an anomaly event does: log / overwrite-"
            "checkpoint and continue / halt with HealthHaltError",
        )
        p.add_argument(
            "--health_window", type=int, default=cls.health_window,
        )
        p.add_argument(
            "--health_inject_nan", default=None, metavar="LAYER@STEP",
            help="fault injection: NaN one layer group's grads at one "
            "step (drills/tests; requires --health)",
        )
        p.add_argument(
            "--flight_records", type=int, default=cls.flight_records,
            help="flight-recorder ring size (last N step records "
            "dumped on crash/SIGTERM/watchdog kill; 0 = off)",
        )
        p.add_argument(
            "--metrics_port", type=int, default=None,
            help="serve live train counters as Prometheus text at "
            "/metricsz on this port (0 = ephemeral)",
        )
        p.add_argument(
            "--chaos", default=None, metavar="SPEC",
            help="deterministic fault injection, e.g. "
            "'kill:rank1@step20,sigterm:rank0@epoch1,"
            "stall:input@step5:2.5s,ckpt_corrupt:latest' "
            "(docs/ROBUSTNESS.md; events fire once across restarts)",
        )
        p.add_argument(
            "--sanitize", action="store_true",
            help="arm jax.transfer_guard('disallow') around the hot "
            "loop (implicit host transfers raise) plus the desync "
            "watchdog — the runtime half of scripts/lint.py "
            "(docs/ANALYSIS.md)",
        )
        p.add_argument(
            "--sanitize_timeout", type=float,
            default=cls.sanitize_timeout,
            help="desync-watchdog seconds under --sanitize (when "
            "--watchdog_timeout is unset; 0 = guard only)",
        )
        p.add_argument(
            "--max_restarts", type=int, default=cls.max_restarts,
            help="with --spawn: relaunch the whole world from the "
            "latest checkpoint up to N times after a rank dies",
        )
        p.add_argument(
            "--restart_backoff", type=float,
            default=cls.restart_backoff,
            help="base seconds for the exponential restart backoff",
        )
        p.add_argument(
            "--elastic", action="store_true",
            help="survive world RESIZE, not just restart: with --spawn "
            "the supervisor relaunches with however many workers "
            "remain (scale-down) or are restored (scale-up); workers "
            "re-derive the mesh from the live world, preserve the "
            "recorded global batch, and reshard/re-bucket checkpoints "
            "on restore (docs/ROBUSTNESS.md)",
        )
        p.add_argument(
            "--min_world", type=int, default=cls.min_world,
            help="with --elastic: smallest world the supervisor may "
            "shrink to (shrinking below fails the run)",
        )
        # Discovery: print the registries and exit (handled in train.py
        # before config construction).
        p.add_argument("--list_models", action="store_true")
        p.add_argument("--list_datasets", action="store_true")
        p.add_argument("--spawn", type=int, default=cls.spawn)
        p.add_argument("--coordinator_address", default=None)
        p.add_argument("--num_processes", type=int, default=None)
        p.add_argument("--process_id", type=int, default=None)
        return p

    @classmethod
    def from_namespace(cls, ns) -> "TrainConfig":
        kwargs = dict(vars(ns))
        kwargs["shuffle"] = not kwargs.pop("no_shuffle")
        kwargs["moe_normalize_gates"] = not kwargs.pop("moe_raw_gates")
        # action flags, not config state (handled by train.py)
        kwargs.pop("list_models", None)
        kwargs.pop("list_datasets", None)
        return cls(**kwargs)

    @classmethod
    def from_args(cls, argv=None) -> "TrainConfig":
        return cls.from_namespace(cls.parser().parse_args(argv))
