#!/usr/bin/env python3
"""Where the collectives of the LM train step stand in its compiled,
scheduled program — for a TPU that is described, not attached.

The TPU's compiler is installed on the CPU machines, so the step is
compiled here for a described ``v5e:2x2`` at a cell's own size (about
half a minute, no chip time) and the entry computation of
``compiled.as_text()`` — which is scheduled — is read: every gradient
reduce's position, bytes, whether it is a plain ``all-reduce`` (the
TensorCore waits for it wherever it stands) or an asynchronous
start/done pair, and what compute stands between the pair, against the
positions of the backward kernels (``flash_dkv``; with ``flash_dq`` where
a head does not fit the VMEM).

    JAX_PLATFORMS=cpu python scripts/show_collectives.py            # cgpt1.3b-train-ddp4's step
    JAX_PLATFORMS=cpu python scripts/show_collectives.py --plain    # without ddp.overlap_compile_options
    JAX_PLATFORMS=cpu python scripts/show_collectives.py --d_model 1024 --depth 4 --data 4
    ... --option xla_tpu_foo=true      # one more compile option, to try it

Nothing runs; a position is not a time. ``tests/test_tpu_compile.py``
imports ``compile_lm_step`` and ``schedule``; the reader itself
(``collective_schedule``) lives in ``ddp_tpu/obs/xprof.py`` because the
program reads its own compiled step with it, once per compile
(``ddp.jit_train_step``'s ``train.compile`` record).
"""

from __future__ import annotations

import argparse
import os
import sys
from unittest import mock

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def describe_topology(name: str = "v5e:2x2"):
    """The described chips. Call it from a script's main or a test's
    fixture, never at import: one process at a time holds the TPU's
    library."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    return topologies.get_topology_desc(platform="tpu", topology_name=name)


def compile_lm_step(
    devices,
    *,
    mesh_axes: dict,
    d_model: int = 2048,
    depth: int = 8,
    num_heads: int = 16,
    vocab_size: int = 50257,
    seq_len: int = 2048,
    rows_per_chip: int = 4,
    compute_dtype: str = "bfloat16",
    lr: float = 1e-4,
    overlap: bool = True,
    extra_options: dict | None = None,
):
    """The LM train step, as ``Trainer`` builds it, compiled for
    ``devices`` (described ones) on ``make_mesh(mesh_axes)``.

    ``overlap=True`` is the program's own jit (``ddp.jit_train_step``,
    seeing a TPU backend, so with ``ddp.overlap_compile_options`` of
    the mesh); ``False`` is the same step under a bare ``jax.jit``.
    ``extra_options`` are compile options added on top, for trying one.
    Returns ``jax.stages.Compiled``.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ddp_tpu.models import lm
    from ddp_tpu.runtime.mesh import make_mesh
    from ddp_tpu.train.optim import make_optimizer

    mesh = make_mesh(dict(mesh_axes), devices=devices)
    spec = lm.LMSpec(
        vocab_size=vocab_size, total_len=seq_len, d_model=d_model,
        depth=depth, num_heads=num_heads,
    )
    tx = make_optimizer("adam", lr=lr)
    rep = NamedSharding(mesh, P())

    def abstract(tree):
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=rep),
            tree,
        )

    params = jax.eval_shape(lambda: lm.init_lm(spec))
    state = lm.LMTrainState(
        step=jax.ShapeDtypeStruct((), jnp.int32, sharding=rep),
        params=abstract(params),
        opt_state=abstract(jax.eval_shape(tx.init, params)),
    )
    batch_axes = ("data", "fsdp", "expert")
    world = 1
    for a in batch_axes:
        world *= mesh.shape[a]
    tokens = jax.ShapeDtypeStruct(
        (rows_per_chip * world, seq_len), jnp.int32,
        sharding=NamedSharding(mesh, P(batch_axes, "seq")),
    )
    # The kernels and the program's choice of compile options ask the
    # backend: answer for the described chip while the step is built,
    # traced and lowered.
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        step = lm.make_lm_train_step(
            spec, tx, mesh, compute_dtype=jnp.dtype(compute_dtype).type,
            jit=overlap,
        )
        if not overlap:
            step = jax.jit(step, donate_argnums=(0,))
        lowered = step.lower(state, tokens)
    return lowered.compile(compiler_options=extra_options or None)


def schedule(compiled) -> dict:
    """``obs/xprof.collective_schedule`` of a compiled step."""
    from ddp_tpu.obs.xprof import collective_schedule

    return collective_schedule(compiled.as_text())


def show(sched: dict, out=sys.stdout) -> None:
    w = out.write
    w(f"entry computation: {sched['instructions']} instructions; "
      f"first backward kernel at {sched['first_backward']}, "
      f"last at {sched['last_backward']}\n")
    w(f"{'start':>6} {'done':>6} {'MB':>8}  {'kind':<22} "
      f"{'compute (backward) between':>26}  name\n")
    for r in sched["reduces"]:
        where = (
            "in backward"
            if r["start"] < (sched["last_backward"] or 0)
            else "after backward"
        )
        w(f"{r['start']:>6} {r['done']:>6} {r['bytes'] / 1e6:>8.1f}  "
          f"{r['kind']:<22} "
          f"{r['compute_between']:>20} ({r['backward_between']:>3})  "
          f"{r['name']}  ({where})\n")
    s = sched["summary"]
    w(f"gradient reduces {s['reduces']} ({s['bytes'] / 1e9:.3f} GB), "
      f"asynchronous {s['asynchronous']} "
      f"({s['asynchronous_bytes'] / 1e9:.3f} GB), "
      f"start before the last backward kernel "
      f"{s['start_in_backward']}, "
      f"asynchronous with backward compute between start and done "
      f"{s['under_backward']} ({s['under_backward_bytes'] / 1e9:.3f} GB)\n")
    if sched["other"]:
        w("other collectives: " + ", ".join(
            f"{o['name']}@{o['start']}" for o in sched["other"]) + "\n")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--topology", default="v5e:2x2")
    ap.add_argument("--data", type=int, default=4)
    ap.add_argument("--mesh", default="",
                    help="other axes, e.g. seq=2,model=2 (data follows)")
    ap.add_argument("--d_model", type=int, default=2048)
    ap.add_argument("--depth", type=int, default=8)
    ap.add_argument("--num_heads", type=int, default=16)
    ap.add_argument("--vocab_size", type=int, default=50257)
    ap.add_argument("--seq_len", type=int, default=2048)
    ap.add_argument("--rows_per_chip", type=int, default=4)
    ap.add_argument("--plain", action="store_true",
                    help="compile without ddp.overlap_compile_options")
    ap.add_argument("--option", action="append", default=[],
                    metavar="NAME=VALUE", help="one more compile option")
    ap.add_argument("--dump", default="",
                    help="write the compiled module's text here")
    args = ap.parse_args(argv)

    axes = {"data": args.data}
    for kv in filter(None, args.mesh.split(",")):
        k, v = kv.split("=")
        axes[k] = int(v)
    n = 1
    for v in axes.values():
        n *= v
    topo = describe_topology(args.topology)
    compiled = compile_lm_step(
        topo.devices[:n], mesh_axes=axes, d_model=args.d_model,
        depth=args.depth, num_heads=args.num_heads,
        vocab_size=args.vocab_size, seq_len=args.seq_len,
        rows_per_chip=args.rows_per_chip,
        overlap=not args.plain,
        extra_options=dict(o.split("=", 1) for o in args.option),
    )
    if args.dump:
        with open(args.dump, "w") as f:
            f.write(compiled.as_text())
    show(schedule(compiled))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
