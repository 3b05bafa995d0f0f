#!/usr/bin/env python
"""Migrate a reference ``epoch_N.pt`` into this framework's checkpoints.

A reference user mid-run has ``./checkpoints/epoch_N.pt`` files
(train_ddp.py:204-209). This converts the newest (or a named) one into
an Orbax checkpoint in the same directory convention, so

    python scripts/import_torch_checkpoint.py --pt checkpoints_torch/epoch_1.pt
    python train.py --epochs 10

resumes at epoch N+1 with the imported weights — switching frameworks
without losing training progress. The optimizer starts fresh (the
reference's momentum-less SGD carries no state to migrate, and the
reference itself never restored it — train_ddp.py:88, SURVEY.md §2a #8).

The reverse direction lives in ``ddp_tpu.interop.export_torch_checkpoint``.
"""

from __future__ import annotations

import argparse
import os
import sys

# Runnable as `python scripts/import_torch_checkpoint.py` from a repo
# checkout without installation.
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--pt", required=True, help="reference .pt checkpoint file")
    p.add_argument("--checkpoint_dir", default="./checkpoints")
    p.add_argument("--optimizer", default="sgd", choices=("sgd", "adam", "adamw"))
    p.add_argument("--lr", type=float, default=0.01)
    p.add_argument("--momentum", type=float, default=0.0)
    args = p.parse_args()

    import jax
    import jax.numpy as jnp

    from ddp_tpu.interop import import_torch_checkpoint
    from ddp_tpu.models import get_model
    from ddp_tpu.parallel.ddp import create_train_state
    from ddp_tpu.train.checkpoint import CheckpointManager
    from ddp_tpu.train.optim import make_optimizer

    params, epoch = import_torch_checkpoint(args.pt)

    model = get_model("simple_cnn")
    tx = make_optimizer(args.optimizer, lr=args.lr, momentum=args.momentum)
    state = create_train_state(model, tx, jnp.zeros((1, 28, 28, 1)), seed=0)
    # Shape-check the import against a fresh init before overwriting.
    for want, got in zip(
        jax.tree.leaves(state.params), jax.tree.leaves(params)
    ):
        if want.shape != jnp.asarray(got).shape:
            raise ValueError(
                f"shape mismatch: expected {want.shape}, got "
                f"{jnp.asarray(got).shape}"
            )
    state = state._replace(
        params=jax.tree.map(jnp.asarray, params),
        opt_state=tx.init(params),
    )

    mgr = CheckpointManager(args.checkpoint_dir, async_save=False)
    saved = mgr.save(epoch, state)
    mgr.close()
    if not saved:
        raise SystemExit(
            f"epoch {epoch} already exists in {args.checkpoint_dir} — "
            "refusing to overwrite"
        )
    print(
        f"Imported {args.pt} (epoch {epoch}) → {args.checkpoint_dir}; "
        f"train.py will resume at epoch {epoch + 1}"
    )


if __name__ == "__main__":
    main()
