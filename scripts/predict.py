#!/usr/bin/env python
"""Inference CLI: classify with a trained checkpoint, no trainer needed.

The reference ends at training (no eval, no inference — SURVEY.md §5);
this closes the deployment half of the loop:

    python scripts/predict.py --model simple_cnn --dataset mnist
    python scripts/predict.py --model resnet18 --dataset cifar10 \
        --images batch.npy --out preds.npy

Restores the latest (or ``--epoch N``) checkpoint template-free — the
checkpoint's own metadata supplies the tree, so the optimizer that
produced it is irrelevant. With ``--dataset``, runs the test split and
prints accuracy as one JSON line; with ``--images`` (a .npy of NHWC
uint8/float), writes predicted classes to ``--out`` (.npy) and prints a
summary line.
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
    p.add_argument("--epoch", type=int, default=None, help="default: latest")
    p.add_argument("--model", default="simple_cnn")
    p.add_argument("--model_depth", type=int, default=None)
    p.add_argument("--num_classes", type=int, default=None)
    p.add_argument("--dataset", default=None, help="evaluate its test split")
    p.add_argument("--data_root", default="./data")
    p.add_argument("--synthetic_data", action="store_true")
    p.add_argument("--images", default=None, help=".npy of NHWC images")
    p.add_argument("--out", default=None, help=".npy for predicted classes")
    p.add_argument("--batch_size", type=int, default=256)
    p.add_argument(
        "--compute_dtype", default="float32", choices=("float32", "bfloat16")
    )
    # Causal-LM generation (--model causal_lm): KV-cache decode.
    p.add_argument("--prompt", default=None, help="text prompt (byte tokens)")
    p.add_argument(
        "--prompt_tokens", default=None, help="comma-separated token ids"
    )
    p.add_argument("--max_new_tokens", type=int, default=64)
    p.add_argument("--temperature", type=float, default=0.0)
    p.add_argument(
        "--top_k", type=int, default=0,
        help="sample only among the k highest-probability tokens (0 = off)",
    )
    p.add_argument(
        "--top_p", type=float, default=1.0,
        help="nucleus sampling: smallest token set with mass >= p (1 = off)",
    )
    p.add_argument(
        "--beam_width", type=int, default=1,
        help="beam-search decode width (>1 implies deterministic "
        "search; mutually exclusive with sampling flags)",
    )
    p.add_argument("--gen_seed", type=int, default=0)
    # Architecture is derived from the checkpoint's param shapes; only
    # the head count (invisible in shapes) is a flag.
    p.add_argument("--num_heads", type=int, default=4)
    args = p.parse_args()
    if args.model == "causal_lm":
        if (args.prompt is None) == (args.prompt_tokens is None):
            p.error(
                "--model causal_lm needs exactly one of "
                "--prompt / --prompt_tokens"
            )
        # Mirror generate()'s validation as clean CLI errors instead
        # of ValueError tracebacks.
        if not 0.0 < args.top_p <= 1.0:
            p.error(f"--top_p must be in (0, 1], got {args.top_p}")
        if args.top_k < 0:
            p.error(f"--top_k must be >= 0, got {args.top_k}")
        if args.temperature <= 0.0 and (args.top_k or args.top_p < 1.0):
            p.error(
                "--top_k/--top_p only apply when sampling: set "
                "--temperature > 0 (greedy decoding ignores them)"
            )
        if args.beam_width < 1:
            p.error(f"--beam_width must be >= 1, got {args.beam_width}")
        if args.beam_width > 1 and (
            args.temperature > 0.0 or args.top_k or args.top_p < 1.0
        ):
            p.error(
                "--beam_width > 1 is a deterministic search; drop "
                "--temperature/--top_k/--top_p"
            )
        _generate_lm(args)
        return
    if (args.dataset is None) == (args.images is None):
        p.error("exactly one of --dataset / --images is required")

    import jax
    import jax.numpy as jnp
    import numpy as np

    from ddp_tpu.data.registry import NUM_CLASSES, load_split
    from ddp_tpu.models import get_model
    from ddp_tpu.parallel.common import _preprocess, _train_kwarg
    from ddp_tpu.train.checkpoint import CheckpointManager

    mgr = CheckpointManager(args.checkpoint_dir)
    params, model_state, epoch = mgr.restore_for_inference(args.epoch)
    mgr.close()

    num_classes = args.num_classes or NUM_CLASSES.get(args.dataset or "", 10)
    model_kw = {}
    if args.model_depth is not None:
        model_kw["depth"] = args.model_depth
    model = get_model(args.model, num_classes=num_classes, **model_kw)
    compute_dtype = (
        jnp.bfloat16 if args.compute_dtype == "bfloat16" else jnp.float32
    )
    train_kw = _train_kwarg(model, False)
    if compute_dtype != jnp.float32:
        # Cast once on the host, not inside the jitted per-batch call.
        params = jax.tree.map(lambda v: v.astype(compute_dtype), params)

    @jax.jit
    def forward(images):
        x = _preprocess(images, compute_dtype)
        logits = model.apply({"params": params, **model_state}, x, **train_kw)
        return jnp.argmax(logits.astype(jnp.float32), -1)

    def predict_all(images):
        if len(images) == 0:
            return np.zeros((0,), np.int32)
        preds = []
        for i in range(0, len(images), args.batch_size):
            chunk = np.asarray(images[i : i + args.batch_size])
            n = len(chunk)
            # Pad the tail so one compiled shape serves every batch.
            if n < args.batch_size:
                chunk = np.concatenate(
                    [chunk, chunk[:1].repeat(args.batch_size - n, 0)]
                )
            preds.append(np.asarray(forward(jnp.asarray(chunk)))[:n])
        return np.concatenate(preds)

    if args.dataset:
        test = load_split(
            args.dataset, args.data_root, "test",
            allow_synthetic=args.synthetic_data,
        )
        preds = predict_all(test.images)
        acc = float((preds == test.labels).mean())
        print(
            json.dumps(
                {
                    "epoch": epoch,
                    "dataset": args.dataset,
                    "n": int(len(test.labels)),
                    "accuracy": round(acc, 4),
                }
            )
        )
    else:
        images = np.load(args.images)
        if images.ndim == 3:  # single image → batch of one
            images = images[None]
        preds = predict_all(images)
        if args.out:
            np.save(args.out, preds)
        print(
            json.dumps(
                {
                    "epoch": epoch,
                    "n": int(len(preds)),
                    "out": args.out,
                    "predictions": preds[:16].tolist(),
                }
            )
        )


def _generate_lm(args) -> None:
    """Restore a causal-LM checkpoint and decode from it (KV cache).

    vocab_size, total_len, d_model and depth are DERIVED from the
    restored parameter shapes (embed [V, d], pos_embed [1, L, d],
    blockN count) — trusting CLI flags here would silently clamp
    positions past the real table (JAX OOB-slice semantics) and emit
    garbage. Only --num_heads (not recoverable from shapes) comes from
    the flag, validated against d_model. With a byte vocabulary
    (≥256), --prompt text is encoded as raw bytes and the continuation
    decoded back to text.
    """
    import jax.numpy as jnp
    import numpy as np

    from ddp_tpu.models.generate import generate
    from ddp_tpu.train.checkpoint import (
        CheckpointManager,
        derive_spec_with_sidecar,
    )

    mgr = CheckpointManager(args.checkpoint_dir)
    params, _, epoch = mgr.restore_for_inference(args.epoch)
    mgr.close()
    # A tokenizer saved next to the checkpoints (BPE training runs,
    # data/text.py) is part of the model: prompts encode through it
    # and continuations decode back to text. Absent file = byte vocab.
    tokenizer = None
    tok_path = os.path.join(args.checkpoint_dir, "tokenizer.json")
    if os.path.exists(tok_path):
        from ddp_tpu.data.bpe import BPETokenizer

        tokenizer = BPETokenizer.load(tok_path)
    # MoE checkpoints decode too (round 5): generate.py routes each
    # block by the presence of "moe" in its param tree. The lm_spec
    # sidecar the trainer writes beside the epochs supplies the fields
    # shapes cannot carry (num_heads, MoE routing config); CLI
    # --num_heads remains the fallback for sidecar-less checkpoints.
    try:
        spec = derive_spec_with_sidecar(
            args.checkpoint_dir, params, num_heads_fallback=args.num_heads
        )
    except ValueError as e:
        raise SystemExit(
            f"checkpoint in {args.checkpoint_dir}: {e}"
        )

    if args.prompt_tokens is not None:
        toks = [int(t) for t in args.prompt_tokens.split(",") if t.strip()]
    elif tokenizer is not None:
        toks = tokenizer.encode(args.prompt).tolist()
        if tokenizer.vocab_size > spec.vocab_size:
            raise SystemExit(
                f"tokenizer at {tok_path} has {tokenizer.vocab_size} "
                f"ids but the checkpoint embeds {spec.vocab_size}"
            )
    else:
        toks = list(args.prompt.encode("utf-8"))
        bad = [t for t in toks if t >= spec.vocab_size]
        if bad:
            raise SystemExit(
                f"--prompt bytes {sorted(set(bad))} exceed vocab_size "
                f"{spec.vocab_size}; use --prompt_tokens"
            )
    prompt = jnp.asarray([toks], jnp.int32)
    if args.beam_width > 1:
        from ddp_tpu.models.generate import beam_search

        beams, scores = beam_search(
            spec,
            params,
            prompt,
            max_new_tokens=args.max_new_tokens,
            beam_width=args.beam_width,
        )
        out = np.asarray(beams)[0, 0]  # best beam
        extra = {
            "beam_width": args.beam_width,
            "beam_scores": [round(float(s), 4) for s in np.asarray(scores)[0]],
        }
    else:
        out = np.asarray(
            generate(
                spec,
                params,
                prompt,
                max_new_tokens=args.max_new_tokens,
                temperature=args.temperature,
                top_k=args.top_k,
                top_p=args.top_p,
                seed=args.gen_seed,
            )
        )[0]
        extra = {
            "temperature": args.temperature,
            "top_k": args.top_k,
            "top_p": args.top_p,
        }
    new = out[len(toks):]
    record = {
        "epoch": epoch,
        "prompt_tokens": toks,
        "tokens": new.tolist(),
        **extra,
    }
    if tokenizer is not None:
        record["text"] = tokenizer.decode(new)
    elif spec.vocab_size >= 256 and max(new.tolist(), default=0) < 256:
        record["text"] = bytes(int(t) for t in new).decode(
            "utf-8", errors="replace"
        )
    print(json.dumps(record))


if __name__ == "__main__":
    main()
