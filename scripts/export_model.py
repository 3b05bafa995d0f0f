#!/usr/bin/env python
"""AOT-export a trained model as a serialized StableHLO artifact.

The TPU-idiomatic deployment story: weights are BAKED into a
`jax.export` artifact (StableHLO bytecode + calling convention), so
serving needs neither this framework nor the model definition — just
jax on the target platform:

    python scripts/export_model.py --model simple_cnn \
        --batch_size 64 --out model.stablehlo
    # elsewhere:
    #   from jax import export
    #   fn = export.deserialize(open("model.stablehlo","rb").read())
    #   logits = fn.call(images_uint8_nhwc)

The exported function is the full inference path: uint8 NHWC in,
/255 preprocessing, fp32 logits out. The reference has no deployment
path at all (training-only, SURVEY.md §5).
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
    p.add_argument("--epoch", type=int, default=None)
    p.add_argument("--model", default="simple_cnn")
    p.add_argument("--model_depth", type=int, default=None)
    p.add_argument("--num_classes", type=int, default=10)
    p.add_argument("--batch_size", type=int, default=64)
    p.add_argument(
        "--input_shape", default="28,28,1",
        help="H,W,C of one example (uint8 NHWC)",
    )
    p.add_argument("--out", default="model.stablehlo")
    p.add_argument(
        "--check", action="store_true",
        help="deserialize the artifact and compare against live apply",
    )
    args = p.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import export as jexport

    from ddp_tpu.models import get_model
    from ddp_tpu.parallel.common import _preprocess, _train_kwarg
    from ddp_tpu.train.checkpoint import CheckpointManager

    mgr = CheckpointManager(args.checkpoint_dir)
    params, model_state, epoch = mgr.restore_for_inference(args.epoch)
    mgr.close()

    model_kw = {}
    if args.model_depth is not None:
        model_kw["depth"] = args.model_depth
    model = get_model(args.model, num_classes=args.num_classes, **model_kw)
    train_kw = _train_kwarg(model, False)

    def forward(images):
        x = _preprocess(images, jnp.float32)
        return model.apply({"params": params, **model_state}, x, **train_kw)

    shape = tuple(int(s) for s in args.input_shape.split(","))
    spec = jax.ShapeDtypeStruct((args.batch_size, *shape), jnp.uint8)
    exported = jexport.export(jax.jit(forward))(spec)
    data = exported.serialize()
    with open(args.out, "wb") as f:
        f.write(data)

    summary = {
        "out": args.out,
        "bytes": len(data),
        "epoch": epoch,
        "input": [args.batch_size, *shape],
        "platforms": list(exported.platforms),
    }
    if args.check:
        rng = np.random.default_rng(0)
        sample = rng.integers(
            0, 256, size=(args.batch_size, *shape), dtype=np.uint8
        )
        reloaded = jexport.deserialize(open(args.out, "rb").read())
        got = np.asarray(reloaded.call(jnp.asarray(sample)))
        want = np.asarray(forward(jnp.asarray(sample)))
        np.testing.assert_allclose(got, want, atol=1e-5)
        summary["check"] = "ok"
    print(json.dumps(summary))


if __name__ == "__main__":
    main()
