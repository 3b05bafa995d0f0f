#!/usr/bin/env python
"""Checkpoint averaging ("model soup"): merge epochs into one model.

Uniformly averages the parameters of several saved epochs — the
classic cheap ensemble that often beats the best single checkpoint —
and writes the result back as a new checkpoint:

    python scripts/soup.py --epochs 5,7,9 --out_epoch 100
    python scripts/predict.py --epoch 100 --dataset mnist

The soup's optimizer state is FRESH (averaged moments are
meaningless); continue training from it with ``--resume_epoch 100
--reset_opt_state`` if desired. Non-float leaves (e.g. BatchNorm
counts) are taken from the first listed epoch; float model_state
(BatchNorm moments) averages like params.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--checkpoint_dir", default="./checkpoints")
    p.add_argument(
        "--epochs", required=True,
        help="comma-separated saved epoch tags to average",
    )
    p.add_argument(
        "--out_epoch", type=int, required=True,
        help="epoch tag to save the soup under (must not exist)",
    )
    p.add_argument("--model", default="simple_cnn")
    p.add_argument("--model_depth", type=int, default=None)
    p.add_argument("--num_classes", type=int, default=10)
    p.add_argument(
        "--input_shape", default="28,28,1", help="H,W,C of one example"
    )
    args = p.parse_args()
    tags = sorted({int(e) for e in args.epochs.split(",") if e.strip()})
    if len(tags) < 2:
        p.error("need at least two distinct epochs to average")

    import jax
    import jax.numpy as jnp
    import numpy as np

    from ddp_tpu.models import get_model
    from ddp_tpu.parallel.ddp import create_train_state
    from ddp_tpu.train.checkpoint import CheckpointManager
    from ddp_tpu.train.optim import make_optimizer

    mgr = CheckpointManager(args.checkpoint_dir)
    existing = mgr.all_epochs()
    if args.out_epoch in existing:
        mgr.close()
        raise SystemExit(
            f"epoch {args.out_epoch} already exists — pick another tag"
        )
    latest = max(existing, default=None)
    loaded = [mgr.restore_for_inference(e) for e in tags]

    def avg_leaf(*ls):
        """Uniform mean in float64, cast back; non-floats from ls[0]."""
        if not np.issubdtype(ls[0].dtype, np.floating):
            return ls[0]
        mean = sum(np.asarray(l, np.float64) for l in ls) / len(ls)
        return jnp.asarray(mean, dtype=ls[0].dtype)

    # Every ingredient must share a tree structure before averaging —
    # mixing a legacy checkpoint (empty model_state) with a newer one
    # would otherwise surface as an opaque tree-map error.
    for label, trees in (
        ("params", [p_ for p_, _, _ in loaded]),
        ("model_state", [ms for _, ms, _ in loaded]),
    ):
        structs = [jax.tree_util.tree_structure(t) for t in trees]
        bad = [e for e, st in zip(tags, structs) if st != structs[0]]
        if bad:
            mgr.close()
            raise SystemExit(
                f"{label} tree structure differs between epoch {tags[0]} "
                f"and epoch(s) {bad} — these checkpoints cannot be souped "
                f"together (legacy vs current format?)"
            )

    params = jax.tree.map(avg_leaf, *[p_ for p_, _, _ in loaded])
    model_state = jax.tree.map(avg_leaf, *[ms for _, ms, _ in loaded])

    model_kw = {}
    if args.model_depth is not None:
        model_kw["depth"] = args.model_depth
    model = get_model(args.model, num_classes=args.num_classes, **model_kw)
    shape = tuple(int(s) for s in args.input_shape.split(","))
    tx = make_optimizer("sgd", lr=0.01)
    state = create_train_state(
        model, tx, jnp.zeros((1, *shape)), seed=0
    )
    # Sanity: the averaged tree must match this model's structure.
    if jax.tree_util.tree_structure(state.params) != jax.tree_util.tree_structure(params):
        raise SystemExit(
            "averaged params do not match the model structure — check "
            "--model/--model_depth/--num_classes"
        )
    state = state._replace(
        params=params,
        model_state=model_state if model_state else state.model_state,
        opt_state=tx.init(params),
    )
    saved = mgr.save(args.out_epoch, state)
    mgr.close()
    if not saved:
        raise SystemExit(
            f"epoch {args.out_epoch} already exists — pick another tag"
        )
    if latest is not None and args.out_epoch > latest:
        print(
            f"WARNING: epoch {args.out_epoch} is now the directory's "
            f"latest — train.py auto-resume will pick the SOUP (fresh "
            f"sgd optimizer state; other configs need "
            f"--reset_opt_state). Use a tag below {latest} to avoid "
            "this, or delete the soup before resuming.",
            file=sys.stderr,
        )
    print(
        json.dumps(
            {"soup_of": tags, "out_epoch": args.out_epoch,
             "checkpoint_dir": os.path.abspath(args.checkpoint_dir)}
        )
    )


if __name__ == "__main__":
    main()
