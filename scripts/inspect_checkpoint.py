#!/usr/bin/env python
"""Inspect a checkpoint directory: epochs, shapes, sizes, resume state.

Operations tool for the checkpoint layout this framework writes
(train/checkpoint.py). No model or optimizer construction — everything
comes from checkpoint metadata:

    python scripts/inspect_checkpoint.py                    # summary
    python scripts/inspect_checkpoint.py --epoch 3 --tree   # per-leaf

Prints one JSON line per epoch: tag, parameter count/bytes, optimizer
state bytes, step counter, steps-per-epoch it was written under, and
whether it is a mid-epoch preemption artifact (mid_batch > 0).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _tree_stats(meta) -> tuple[int, int]:
    """(leaf element count, bytes) for a metadata subtree."""
    import jax
    import numpy as np

    count = size = 0
    for leaf in jax.tree.leaves(meta):
        n = int(np.prod(leaf.shape)) if leaf.shape else 1
        count += n
        size += n * np.dtype(leaf.dtype).itemsize
    return count, size


def main() -> None:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--checkpoint_dir", default="./checkpoints")
    p.add_argument("--epoch", type=int, default=None, help="only this tag")
    p.add_argument(
        "--tree", action="store_true",
        help="also print every param leaf: path, shape, dtype",
    )
    args = p.parse_args()

    from ddp_tpu.train.checkpoint import CheckpointManager

    mgr = CheckpointManager(args.checkpoint_dir)
    epochs = mgr.all_epochs()
    if not epochs:
        raise SystemExit(f"no checkpoints in {args.checkpoint_dir}")
    latest = epochs[-1]
    if args.epoch is not None:
        if args.epoch not in epochs:
            raise SystemExit(f"epoch {args.epoch} not in {epochs}")
        epochs = [args.epoch]

    for e in epochs:
        meta = mgr.metadata(e)
        n_params, params_bytes = _tree_stats(meta.get("params", {}))
        _, opt_bytes = _tree_stats(meta.get("opt_state", {}))
        _, ms_bytes = _tree_stats(meta.get("model_state", {}))
        record = {
            "epoch": e,
            "params": n_params,
            "params_bytes": params_bytes,
            "opt_state_bytes": opt_bytes,
            "model_state_bytes": ms_bytes,
            "latest": e == latest,
        }
        # Scalars (step/spe/mid_batch) need a real read; metadata has
        # shapes only.
        try:
            got = mgr.read_partial(e, ("step", "spe", "mid_batch"))
            record["step"] = int(got.get("step", 0))
            record["steps_per_epoch"] = int(got.get("spe", 0)) or None
            mid = int(got.get("mid_batch", 0))
            record["mid_epoch_preemption_artifact"] = mid > 0
            if mid:
                record["mid_batch"] = mid
        except Exception as err:  # metadata-only fallback
            record["scalar_read_error"] = str(err)[:120]
        print(json.dumps(record))
        if args.tree:
            import jax.tree_util as jtu

            for path, leaf in jtu.tree_flatten_with_path(
                meta.get("params", {})
            )[0]:
                name = "/".join(
                    getattr(k, "key", str(k)) for k in path
                )
                print(f"  {name}  {tuple(leaf.shape)}  {leaf.dtype}")
    mgr.close()


if __name__ == "__main__":
    main()
