#!/usr/bin/env python
"""CLI entry point — parity with ``python train_ddp.py --epochs N --batch_size B``.

The reference's launcher (train_ddp.py:215-224) parses two flags and
spawns world_size=2 processes. Here there is nothing to spawn on a
single host: one process drives every local TPU chip SPMD, and
multi-host runs start one process per host (each calling this same
script) with ``jax.distributed`` rendezvous — see ddp_tpu.runtime.dist.

Quickstart (the reference's README.md:59-74 flow, torch-free):

    python train.py --epochs 3 --batch_size 64            # real data
    python train.py --epochs 3 --batch_size 64 \
        --emulate_devices 2 --synthetic_data              # dev box, offline

Re-running resumes from the latest checkpoint in ./checkpoints.
"""

import sys

from ddp_tpu.runtime import dist
from ddp_tpu.train.config import TrainConfig
from ddp_tpu.train.trainer import Trainer


def _run(config: TrainConfig, ctx=None) -> int:
    trainer = Trainer(config, ctx=ctx)
    try:
        summary = trainer.train()
    finally:
        trainer.close()
        dist.cleanup()
    acc = summary.get("final_accuracy")
    if acc is not None and trainer.ctx.is_main:
        print(f"final_accuracy={acc:.4f}")
    return 0


def _spawned_worker(rank: int, world_size: int, argv) -> None:
    """Per-rank body under ``--spawn`` (the reference's ``ddp_train``).

    The launcher already brought up ``jax.distributed`` for this
    process, so the trainer reuses that context.
    """
    config = TrainConfig.from_args(argv)
    _run(config, ctx=dist.current())


def main(argv=None) -> int:
    args = sys.argv[1:] if argv is None else list(argv)
    # Parse once: the namespace drives both the action flags (robust
    # to argparse prefix abbreviation) and the config.
    ns = TrainConfig.parser().parse_args(args)
    if ns.list_models:
        from ddp_tpu.models import available

        # Registry models plus the spec-driven sequence family the
        # trainer accepts without a registry entry.
        seq_family = [
            "causal_lm (sequence: --mesh_seq/--seq_len/--vocab_size)",
            "long_context (sequence: --mesh_seq/--seq_len/--seq_dim)",
            "pipe_vit (pipeline: --mesh_pipe/--pipe_schedule/"
            "--num_microbatches)",
        ]
        print("\n".join(sorted(available() + seq_family)))
        return 0
    if ns.list_datasets:
        from ddp_tpu.data.registry import NUM_CLASSES

        rows = [f"{k} ({v} classes)" for k, v in NUM_CLASSES.items()]
        rows.append("synthetic_seq (sequence models only)")
        rows.append("text (causal_lm byte corpus: --text_file PATH)")
        print("\n".join(sorted(rows)))
        return 0
    config = TrainConfig.from_namespace(ns)
    if config.max_restarts and config.spawn <= 1:
        raise ValueError(
            "--max_restarts is the --spawn launcher's restart loop "
            "(runtime/launch.py); a single-process run restarts by "
            "re-invoking train.py — auto-resume does the rest"
        )
    if config.min_world != TrainConfig.min_world and not config.elastic:
        raise ValueError(
            "--min_world bounds --elastic's scale-down; add --elastic "
            "(or drop --min_world)"
        )
    if config.elastic and config.spawn > 1 and not (
        1 <= config.min_world <= config.spawn
    ):
        raise ValueError(
            f"--min_world {config.min_world} must be in "
            f"[1, --spawn {config.spawn}]"
        )
    if config.spawn > 1:
        # Reference parity: torch.multiprocessing.spawn(ddp_train,
        # nprocs=world_size) at train_ddp.py:222-224. Each rank gets
        # --emulate_devices CPU devices (default 1, like one GPU/rank).
        if config.backend == "tpu":
            raise ValueError(
                "--spawn emulates multi-host on CPU; it cannot combine "
                "with --backend tpu (one process drives all local chips)"
            )
        from ddp_tpu.runtime.launch import spawn

        spawn(
            _spawned_worker,
            config.spawn,
            (args,),
            devices_per_process=config.emulate_devices or 1,
            timeout=None,  # a training run may legitimately take hours
            # Restart-with-resume: a dead rank reaps the world and
            # relaunches it; every rank auto-resumes from the latest
            # checkpoint and goodput.json counts the restart.
            max_restarts=config.max_restarts,
            restart_backoff=config.restart_backoff,
            # Elastic: a rank that exits SHRINK is permanently gone —
            # relaunch smaller (down to --min_world) instead of failing;
            # GROW relaunches larger. Workers reshard on resume.
            elastic=config.elastic,
            min_world=config.min_world,
        )
        return 0
    return _run(config)


if __name__ == "__main__":
    sys.exit(main())
