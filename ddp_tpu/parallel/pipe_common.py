"""Shared stage-parameter sharding for the pipeline model families.

Every pipelined model (ViT — models/pipeline_vit.py; causal LM —
models/pipeline_lm.py) stacks its uniform stage bodies on a leading
stage dim sharded over ``pipe`` and optionally ZeRO-shards each stage's
leaves over ``fsdp``. The spec computation and the gather/scatter pair
that moves params between their resting layout and the stage program
are family-independent and live here so a fix in one family cannot
miss the other.
"""

from __future__ import annotations

import jax
from jax import lax
from jax.sharding import PartitionSpec as P

FSDP_MIN_SIZE = 2**12  # leaves smaller than this stay replicated


def pipe_batch_axes(mesh) -> tuple:
    """Axes the pipe family shards its batch over. ``expert`` is a
    batch axis exactly as in the flat EP family (runtime/mesh.py
    ``data_axes``): each expert-group member routes its own token
    shard and the all-to-all carries dispatched slots to the expert's
    owner (PP×EP, round 5). ``seq`` composes with pipe too (PP×SP,
    round 5) but shards TOKENS, not batch rows, so it is deliberately
    not a batch axis here — models/pipeline_lm.py puts it on the
    stream spec's trailing token dim and reduces param grads over it
    explicitly."""
    return tuple(
        a for a in ("data", "fsdp", "expert") if mesh.shape.get(a, 1) > 1
    )


def split_microbatch_stream(x, num_microbatches: int, num_stages: int):
    """[B, …] → the [M//S, S, mb, …] pipeline stream, STRIDED.

    Microbatch m takes rows ``m::M`` (the grad-accum idiom,
    models/lm.py): ``stream[r, s, i] = x[i·M + r·S + s]``. The loader
    delivers batches sharded on dim 0 over the batch axes, and the
    trainer's microbatch guard (``mb % data_shards == 0``) makes each
    member's contiguous row block whole i-groups — so the reshape
    below is sharding-LOCAL and the transpose a free tiling
    permutation; the shard_map boundary then only SLICES the
    replicated stream dim onto ``pipe``. A contiguous split would
    demand a dim0-batch → (None, pipe, batch) resharding that XLA's
    SPMD partitioner can only express by involuntary full
    rematerialization (XLA warned of it on the %reshape until round
    5 interleaved the split). One definition for both pipe families
    so the stream, label, and output orderings cannot drift."""
    import jax.numpy as jnp

    B, M, S = x.shape[0], num_microbatches, num_stages
    if B % M:
        raise ValueError(f"batch {B} not divisible by {M} microbatches")
    if M % S:
        raise ValueError(
            f"{M} microbatches not divisible by {S} pipeline stages "
            "(the sharded stream rests microbatch m on device m mod S)"
        )
    x = x.reshape(B // M, M // S, S, *x.shape[1:])
    return jnp.transpose(x, (1, 2, 0) + tuple(range(3, x.ndim)))


def split_microbatch_labels(y, num_microbatches: int):
    """[B, …] → [M, mb, …] with the SAME strided row assignment as
    ``split_microbatch_stream``: ``labels[m, i] = y[i·M + m]``."""
    import jax.numpy as jnp

    B, M = y.shape[0], num_microbatches
    if B % M:
        raise ValueError(f"batch {B} not divisible by {M} microbatches")
    return jnp.moveaxis(y.reshape(B // M, M, *y.shape[1:]), 1, 0)


def merge_microbatch_stream(out):
    """Invert ``split_microbatch_stream``: [R, S, mb, …] → [B, …]
    (out[r, s, i] is row i·M + r·S + s)."""
    import jax.numpy as jnp

    B = out.shape[0] * out.shape[1] * out.shape[2]
    return jnp.moveaxis(out, 2, 0).reshape(B, *out.shape[3:])


def stage_specs(stages, mesh, *, lead: int):
    """Per-leaf PartitionSpec for the stacked stage tree.

    ``lead`` leading dims carry the stage placement (1 for the plain
    [S, …] layout on ``pipe``; 2 for the interleaved [v, S, …] layout
    as P(None, pipe)). With an ``fsdp`` mesh axis, each big-enough
    leaf additionally shards its first evenly-dividing trailing dim —
    ZeRO-style: params and optimizer state REST sharded across the
    batch replicas, and the step all-gathers them transiently
    (``gather_stages``)."""
    fsdp = mesh.shape.get("fsdp", 1)
    lead_axes = ("pipe",) if lead == 1 else (None, "pipe")

    def spec_for(p):
        if fsdp <= 1 or p.size < FSDP_MIN_SIZE:
            return P(*lead_axes)
        spec = list(lead_axes) + [None] * (p.ndim - lead)
        for i in range(lead, p.ndim):
            if p.shape[i] % fsdp == 0:
                spec[i] = "fsdp"
                break
        return P(*spec)

    return jax.tree.map(spec_for, stages)


def stage_specs_megatron(
    stages, mesh, *, lead: int, tp_size: int, ep_size: int = 1
):
    """``stage_specs`` plus Megatron TP dims over ``model`` and MoE
    expert dims over ``expert``.

    With ``tp_size <= 1`` and ``ep_size <= 1`` this IS ``stage_specs``.
    With TP, the block kernels/biases follow parallel/tp.py's suffix
    rules shifted by the ``lead`` stacked dims — column kernels shard
    their output dim, row kernels their input dim, column biases their
    only dim — and ``fsdp``, when present, rides the kernels' *other*
    dim where it divides (the composition seq_param_specs builds).
    With EP (PP×EP, round 5), MoE expert weights take their leading
    per-stage dim (the expert index) on ``expert`` — the same rule as
    seq_param_specs' ``_EXPERT_LEAVES``, shifted by ``lead`` — with
    ``fsdp`` on the next dim where it divides; the router stays with
    the base rule (identical routing on every member). Leaves no rule
    names (LayerNorms) keep the base pipe/fsdp spec.
    """
    base = stage_specs(stages, mesh, lead=lead)
    if tp_size <= 1 and ep_size <= 1:
        return base

    from ddp_tpu.parallel.seq_fsdp import fsdp_size
    from ddp_tpu.parallel.tp import (
        _COLUMN_BIASES,
        _COLUMN_KERNELS,
        _EXPERT_LEAVES,
        _ROW_KERNELS,
        _check_divides,
        _path_str,
    )

    n = fsdp_size(mesh)
    lead_axes = ("pipe",) if lead == 1 else (None, "pipe")

    def with_model(path, p, s):
        suffix = _path_str(path)
        shape = p.shape[lead:]  # per-stage (global, pre-TP/EP) shape
        if ep_size > 1 and suffix.endswith(_EXPERT_LEAVES):
            _check_divides(suffix, shape[0], ep_size)
            # wi [E, d, mlp] / wo [E, mlp, d]: fsdp rides dim 1 where
            # it divides; biases [E, 1, f] shard the expert dim only.
            if (
                n > 1 and len(shape) > 1 and shape[1] > 1
                and shape[1] % n == 0
            ):
                return P(*lead_axes, "expert", "fsdp")
            return P(*lead_axes, "expert")
        if tp_size > 1:
            if suffix.endswith(_COLUMN_KERNELS):
                _check_divides(suffix, shape[1], tp_size)
                d0 = "fsdp" if n > 1 and shape[0] % n == 0 else None
                return P(*lead_axes, d0, "model")
            if suffix.endswith(_COLUMN_BIASES):
                _check_divides(suffix, shape[0], tp_size)
                return P(*lead_axes, "model")
            if suffix.endswith(_ROW_KERNELS):
                _check_divides(suffix, shape[0], tp_size)
                d1 = "fsdp" if n > 1 and shape[1] % n == 0 else None
                return P(*lead_axes, "model", d1)
        return s

    return jax.tree_util.tree_map_with_path(with_model, stages, base)


def gather_stages(sp, specs):
    """all_gather the fsdp-sharded stage leaves INSIDE the island.

    Under AD (the GPipe path) the transpose of this all_gather is a
    psum_scatter over ``fsdp`` — ZeRO's gradient reduce-scatter falls
    out of the schedule for free; the hand-scheduled paths apply the
    matching ``scatter_stage_grads`` explicitly."""

    def g(p, s):
        for i, ax in enumerate(s):
            if ax == "fsdp":
                return lax.all_gather(p, "fsdp", axis=i, tiled=True)
        return p

    return jax.tree.map(g, sp, specs)


def scatter_stage_grads(gs, specs):
    """Reduce stage grads over ``fsdp``: sum + re-shard for leaves
    that rest sharded (psum_scatter), plain psum for the rest —
    exactly the transpose of ``gather_stages`` plus the batch-axis
    reduction every grad needs (fsdp members see different data)."""

    def s(g, spec):
        for i, ax in enumerate(spec):
            if ax == "fsdp":
                return lax.psum_scatter(
                    g, "fsdp", scatter_dimension=i, tiled=True
                )
        return lax.psum(g, "fsdp")

    return jax.tree.map(s, gs, specs)
