#!/usr/bin/env python
"""Autotuner CLI: close the measure→tune→load loop (docs/TUNING.md).

    # tune the serve scheduler for a checkpoint, cache beside it
    python scripts/autotune.py --checkpoint_dir ./checkpoints

    # no checkpoint needed: tune a demo model, γ included
    python scripts/autotune.py --init_demo --gammas 0,2 --sites serve

    # zero knobs for a causal_lm training shape
    python scripts/autotune.py --init_demo --sites zero --world 8

Per site: enumerate the knob grid (validity = the engine's own
construction rules), prune dominated candidates on XLA-counted
FLOPs/bytes/HBM via the xprof compile ledger (pruned fraction
reported), measure the survivors with the bench harness (step p50/p99,
transfer guard armed, token identity asserted against the default),
and persist the winner to ``tuning_cache.json`` beside the checkpoint
dir — which ``train.py`` / ``scripts/serve.py`` / ``scripts/fleet.py``
load by default (``--tuned auto``; explicit flags always win).

Prints one JSON report line per site. A warm cache is a pure hit:
``cache_hit: true, measured: 0`` (re-tune with ``--force``).

TPU runbook: the first on-chip session runs this against the
production checkpoint — see docs/TUNING.md.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import types

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import ddp_tpu  # noqa: F401,E402  (JAX_PLATFORMS pin before backend init)


def _int_grid(text: str) -> tuple[int, ...]:
    return tuple(int(t) for t in text.split(",") if t.strip() != "")


def main() -> None:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--checkpoint_dir", default="./checkpoints")
    p.add_argument(
        "--tuned", default="auto", metavar="auto|PATH",
        help="cache location: 'auto' = tuning_cache.json beside "
        "--checkpoint_dir; a path writes there instead",
    )
    p.add_argument(
        "--sites", default="serve",
        help="comma-separated: serve, zero",
    )
    p.add_argument(
        "--force", action="store_true",
        help="re-tune even when the cache already has a winner",
    )
    p.add_argument("--slots", type=int, default=4)
    p.add_argument("--prefill_len", type=int, default=None)
    p.add_argument(
        "--gammas", default="0", metavar="0,2,4",
        help="spec-token grid for the serve site (>0 needs a draft: "
        "--draft_checkpoint_dir, or --init_demo which synthesizes "
        "one)",
    )
    p.add_argument(
        "--page_sizes", default="0", metavar="0,16",
        help="paged-KV grid for the serve site (0 = fixed-lane)",
    )
    p.add_argument(
        "--max_measure", type=int, default=4,
        help="wall-clock budget: measure at most this many survivors "
        "(deferrals are reported, never silent)",
    )
    p.add_argument("--epoch", type=int, default=None)
    p.add_argument("--num_heads", type=int, default=4)
    p.add_argument("--draft_checkpoint_dir", default=None)
    p.add_argument(
        "--init_demo", action="store_true",
        help="tune a freshly initialized tiny LM (no checkpoint)",
    )
    p.add_argument("--vocab_size", type=int, default=256)
    p.add_argument("--seq_len", type=int, default=128)
    # zero-site shape (the trainer's cache key fields):
    p.add_argument("--world", type=int, default=None)
    p.add_argument("--mesh_dcn", type=int, default=1)
    p.add_argument("--train_model", default="causal_lm")
    p.add_argument("--train_model_dim", type=int, default=None)
    p.add_argument("--train_model_depth", type=int, default=None)
    args = p.parse_args()

    import jax

    from ddp_tpu.models.lm import LMSpec, init_lm
    from ddp_tpu.tune import (
        TuningCache,
        default_cache_path,
        train_signature,
        tune_serve,
        tune_zero,
    )

    sites = [s.strip() for s in args.sites.split(",") if s.strip()]
    bad = [s for s in sites if s not in ("serve", "zero")]
    if bad:
        raise SystemExit(f"unknown site(s) {bad}; pick from serve, zero")

    if args.init_demo:
        spec = LMSpec(
            vocab_size=args.vocab_size, total_len=args.seq_len,
            num_heads=args.num_heads,
        )
        params = init_lm(spec, seed=0)
    else:
        from ddp_tpu.train.checkpoint import (
            CheckpointManager,
            derive_spec_with_sidecar,
        )

        mgr = CheckpointManager(args.checkpoint_dir)
        params, _, _ = mgr.restore_for_inference(args.epoch)
        mgr.close()
        try:
            spec = derive_spec_with_sidecar(
                args.checkpoint_dir, params,
                num_heads_fallback=args.num_heads,
            )
        except ValueError as e:
            raise SystemExit(f"checkpoint in {args.checkpoint_dir}: {e}")

    gammas = _int_grid(args.gammas)
    draft_spec = draft_params = None
    if any(g > 0 for g in gammas):
        if args.draft_checkpoint_dir:
            from ddp_tpu.train.checkpoint import (
                CheckpointManager,
                derive_spec_with_sidecar,
            )

            dmgr = CheckpointManager(args.draft_checkpoint_dir)
            draft_params, _, _ = dmgr.restore_for_inference(None)
            dmgr.close()
            draft_spec = derive_spec_with_sidecar(
                args.draft_checkpoint_dir, draft_params,
                num_heads_fallback=args.num_heads,
            )
        elif args.init_demo:
            draft_spec = spec._replace(
                d_model=max(16, spec.d_model // 2),
                depth=max(1, spec.depth // 2),
            )
            draft_params = init_lm(draft_spec, seed=1)
        else:
            raise SystemExit(
                "--gammas > 0 needs --draft_checkpoint_dir (or "
                "--init_demo, which synthesizes a draft)"
            )

    path = (
        default_cache_path(args.checkpoint_dir)
        if args.tuned == "auto"
        else args.tuned
    )
    cache = TuningCache(path)

    for site in sites:
        if site == "serve":
            rep = tune_serve(
                spec,
                params,
                cache=cache,
                slots=args.slots,
                prefill_len=args.prefill_len,
                draft_spec=draft_spec,
                draft_params=draft_params,
                spec_tokens_grid=gammas,
                page_sizes=_int_grid(args.page_sizes),
                max_measure=args.max_measure,
                force=args.force,
            )
        else:
            world = args.world or len(jax.devices())
            # The trainer keys the zero site by its config's shape
            # fields — mirror them so train.py --tuned auto hits.
            shape = types.SimpleNamespace(
                model=args.train_model,
                model_dim=args.train_model_dim,
                model_depth=args.train_model_depth,
                num_heads=args.num_heads,
                seq_len=args.seq_len,
                vocab_size=args.vocab_size,
            )
            rep = tune_zero(
                params,
                world,
                cache=cache,
                model_sig=train_signature(shape),
                dcn=args.mesh_dcn,
                force=args.force,
            )
        rep["cache_path"] = path
        print(json.dumps(rep, default=str), flush=True)


if __name__ == "__main__":
    main()
