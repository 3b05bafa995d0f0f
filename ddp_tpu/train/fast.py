"""Compiled-epoch fast path: the whole training epoch as ONE XLA program.

The reference's hot loop (train_ddp.py:195-202) crosses Python→C++ per
op and per batch; the host-loader path here (train.trainer) already
compiles each *step*, but for small models the per-step dispatch from a
single Python thread is still the ceiling. This module removes the host
from the loop entirely, which is what the ≥50k images/sec/chip target
requires (SURVEY.md §7 "hard parts"):

- the dataset lives on device, uint8, replicated (MNIST: 47 MB — HBM
  noise);
- the per-epoch shuffle (DistributedSampler ``set_epoch`` semantics:
  seed=epoch permutation, pad-to-multiple) is computed on device;
- ``lax.scan`` drives the per-shard DDP step over all batches, each
  device gathering its stripe of each global batch;
- one dispatch per epoch, one device sync at the end.

Semantics match the step-at-a-time path: same sampler contract (keyed
permutation, per-device stripes, final partial batch dropped — see
ShardedLoader.steps_per_epoch), same DDP all-reduce, same SGD update —
pinned by tests/test_fast.py comparing the two paths batch-for-batch.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ddp_tpu.parallel.ddp import (
    StepMetrics,
    TrainState,
    _world,
    make_per_shard_step,
)
from ddp_tpu.runtime.mesh import data_axes


def device_put_replicated(array, mesh: Mesh, tracer=None):
    """Stage one array on device, replicated across the mesh.

    Multi-process meshes can't ``device_put`` onto non-addressable
    devices; there every process supplies the SAME full array (dataset
    loading is deterministic) and
    ``make_array_from_process_local_data`` assembles the replicated
    global — which is also the runner's correctness precondition: the
    per-epoch permutation is computed from the same key on every
    device, so identical staging ⇒ identical batches.

    ``tracer`` (ddp_tpu.obs) spans the staging: for large datasets
    this host→HBM copy is the fast path's one up-front cost, and it
    belongs on the same timeline as the epochs it amortizes into. The
    span is the host's share (the enqueue): tracing never syncs.
    """
    from ddp_tpu.obs.tracer import get_tracer

    rep = NamedSharding(mesh, P())
    with (tracer or get_tracer()).span(
        "fast.stage_dataset", {"bytes": int(array.nbytes)}
    ):
        if jax.process_count() == 1:
            staged = jax.device_put(jnp.asarray(array), rep)
        else:
            import numpy as np

            staged = jax.make_array_from_process_local_data(
                rep, np.asarray(array)
            )
        return staged


def device_put_dataset(images, labels, mesh: Mesh, tracer=None):
    """Stage the full (images, labels) dataset replicated on device."""
    return (
        device_put_replicated(images, mesh, tracer),
        device_put_replicated(labels, mesh, tracer),
    )


def make_epoch_runner(
    model,
    optimizer,
    mesh: Mesh,
    images: jax.Array,
    labels: jax.Array,
    global_batch_size: int,
    *,
    compute_dtype=jnp.float32,
    seed: int = 0,
    donate: bool = True,
    augment_fn=None,
    label_smoothing: float = 0.0,
) -> Callable[[TrainState, jax.Array], tuple[TrainState, StepMetrics]]:
    """Build ``run(state, epoch) -> (state, stacked per-step metrics)``.

    ``images``/``labels`` must be device-resident and replicated (see
    ``device_put_dataset``). Batches-per-epoch is static:
    ``num_examples // global_batch_size`` (final partial batch dropped,
    matching ShardedLoader).
    """
    axes = data_axes(mesh)
    shards = _world(mesh, axes)
    if global_batch_size % shards:
        raise ValueError(
            f"global batch {global_batch_size} not divisible by {shards} shards"
        )
    local_bs = global_batch_size // shards
    n = images.shape[0]
    steps = n // global_batch_size
    if steps == 0:
        raise ValueError(
            f"dataset of {n} examples yields zero batches of {global_batch_size}"
        )
    per_shard_step = make_per_shard_step(
        model, optimizer, axes, shards, compute_dtype=compute_dtype, seed=seed,
        augment_fn=augment_fn, label_smoothing=label_smoothing,
    )

    def per_device_epoch(state: TrainState, epoch, imgs, lbls):
        # Same-keyed permutation on every device — identical plan, no
        # communication. ShardSampler semantics: seed+epoch keying.
        perm = jax.random.permutation(jax.random.key(seed + epoch), n)
        # This device's stripe: shard s takes rows [b*G + s*local, ...)
        # of the permuted order for batch b.
        offset = _linear_shard_index(axes) * local_bs

        def body(state, t):
            idx = lax.dynamic_slice(perm, (t * global_batch_size + offset,), (local_bs,))
            batch_img = jnp.take(imgs, idx, axis=0)
            batch_lbl = jnp.take(lbls, idx, axis=0)
            return per_shard_step(state, batch_img, batch_lbl)

        return lax.scan(body, state, jnp.arange(steps))

    sharded = jax.shard_map(
        per_device_epoch,
        mesh=mesh,
        in_specs=(P(), P(), P(), P()),
        out_specs=(P(), P()),
        check_vma=False,
    )

    def run(state: TrainState, epoch) -> tuple[TrainState, StepMetrics]:
        return jitted(state, jnp.asarray(epoch, jnp.int32))

    jitted = jax.jit(
        lambda state, epoch: sharded(state, epoch, images, labels),
        donate_argnums=(0,) if donate else (),
    )
    run.steps_per_epoch = steps  # type: ignore[attr-defined]
    return run


def _global_scan_runner(
    raw_step, arrays, global_batch_size: int, *, seed: int, donate: bool,
    what: str = "examples",
):
    """The permute-slice-scan epoch skeleton shared by every
    GLOBAL-level runner (LM, pipe-LM, pipe-ViT — the steps own their
    sharding internally, so the scan wraps them on global arrays; the
    image-DDP runner scans per-device inside its own shard_map and
    stays separate). One definition so the sampler keying
    (seed+epoch), tail-drop, and donation semantics cannot drift
    between the fast/step parity guarantees of different families."""
    n = arrays[0].shape[0]
    steps = n // global_batch_size
    if steps == 0:
        raise ValueError(
            f"dataset of {n} {what} yields zero batches of "
            f"{global_batch_size}"
        )

    def epoch_fn(state, epoch, *arrs):
        perm = jax.random.permutation(jax.random.key(seed + epoch), n)

        def body(state, t):
            idx = lax.dynamic_slice(
                perm, (t * global_batch_size,), (global_batch_size,)
            )
            return raw_step(
                state, *(jnp.take(a, idx, axis=0) for a in arrs)
            )

        return lax.scan(body, state, jnp.arange(steps))

    jitted = jax.jit(
        lambda state, epoch: epoch_fn(state, epoch, *arrays),
        donate_argnums=(0,) if donate else (),
    )

    def run(state, epoch):
        return jitted(state, jnp.asarray(epoch, jnp.int32))

    run.steps_per_epoch = steps  # type: ignore[attr-defined]
    return run


def make_lm_epoch_runner(
    spec,
    optimizer,
    mesh: Mesh,
    tokens: jax.Array,
    global_batch_size: int,
    *,
    compute_dtype=jnp.float32,
    seed: int = 0,
    donate: bool = True,
    grad_accum_steps: int = 1,
    label_smoothing: float = 0.0,
):
    """Compiled-epoch fast path for the causal LM (round-3 ask #9).

    ``run(state, epoch) -> (state, stacked per-step metrics)``: the
    token dataset lives on device replicated
    (``device_put_replicated``), the per-epoch permutation is computed
    on device with ShardSampler's seed+epoch keying, and one
    ``lax.scan`` drives the SAME raw step ``make_lm_train_step``
    builds (``jit=False``) over all batches — one dispatch per epoch,
    matching the step path batch-for-batch (tests/test_fast.py).

    Unlike the image runner (which scans per-device inside one
    shard_map), the LM step already owns its sharding story
    (shard_map over seq/fsdp/model inside) — the scan wraps it at the
    global level and GSPMD keeps the per-step layouts.
    """
    from ddp_tpu.models.lm import make_lm_train_step

    raw_step = make_lm_train_step(
        spec, optimizer, mesh, donate=False, compute_dtype=compute_dtype,
        grad_accum_steps=grad_accum_steps, label_smoothing=label_smoothing,
        jit=False,
    )
    return _global_scan_runner(
        raw_step, (tokens,), global_batch_size, seed=seed, donate=donate,
        what="sequences",
    )


def make_pipe_lm_epoch_runner(
    cfg,
    optimizer,
    mesh: Mesh,
    tokens: jax.Array,
    global_batch_size: int,
    *,
    schedule: str = "gpipe",
    compute_dtype=jnp.float32,
    seed: int = 0,
    donate: bool = True,
):
    """Compiled-epoch fast path for the pipelined LM (round-5 ask #5).

    Identical shape to ``make_lm_epoch_runner``: token dataset
    device-resident, seed+epoch-keyed permutation on device, one
    ``lax.scan`` over the raw (unjitted) pipe step — GPipe, 1F1B, or
    interleaved per ``schedule``. The pipe step owns its sharding
    story (shard_map over pipe/data/fsdp/model/expert inside), so the
    scan wraps it at the global level. Runs on ``PipeLMState``; the
    trainer converts at the boundary like its per-step wrapper does.
    Loss-identical to the step loop (tests/test_trainer_fast.py).
    """
    from ddp_tpu.models.pipeline_lm import (
        make_pipe_lm_1f1b_train_step,
        make_pipe_lm_interleaved_train_step,
        make_pipe_lm_train_step,
    )

    make_step = {
        "1f1b": make_pipe_lm_1f1b_train_step,
        "interleaved": make_pipe_lm_interleaved_train_step,
    }.get(schedule, make_pipe_lm_train_step)
    raw_step = make_step(
        cfg, optimizer, mesh, donate=False, compute_dtype=compute_dtype,
        jit=False,
    )
    return _global_scan_runner(
        raw_step, (tokens,), global_batch_size, seed=seed, donate=donate,
        what="sequences",
    )


def make_pipe_vit_epoch_runner(
    cfg,
    optimizer,
    mesh: Mesh,
    images: jax.Array,
    labels: jax.Array,
    global_batch_size: int,
    *,
    schedule: str = "gpipe",
    compute_dtype=jnp.float32,
    seed: int = 0,
    donate: bool = True,
    augment_fn=None,
    label_smoothing: float = 0.0,
):
    """Compiled-epoch fast path for the pipelined ViT — the image
    sibling of ``make_pipe_lm_epoch_runner`` (same global-level scan;
    augment/label smoothing ride inside the pipe step, which already
    applies them to the global batch before microbatching). NOTE for
    CPU runs: the patch-embed conv inside a ``lax.scan`` hits the
    XLA:CPU scan-conv pathology (~200× slower than the standalone
    step, measured round 4) — this path is for TPU benches; tests pin
    correctness on tiny step counts only."""
    from ddp_tpu.models.pipeline_vit import (
        make_pipe_vit_1f1b_train_step,
        make_pipe_vit_interleaved_train_step,
        make_pipe_vit_train_step,
    )

    make_step = {
        "1f1b": make_pipe_vit_1f1b_train_step,
        "interleaved": make_pipe_vit_interleaved_train_step,
    }.get(schedule, make_pipe_vit_train_step)
    raw_step = make_step(
        cfg, optimizer, mesh, donate=False, compute_dtype=compute_dtype,
        label_smoothing=label_smoothing, augment_fn=augment_fn,
        seed=seed, jit=False,
    )
    return _global_scan_runner(
        raw_step, (images, labels), global_batch_size, seed=seed,
        donate=donate,
    )


def _linear_shard_index(axes) -> jax.Array:
    """Flat index of this device within the data-parallel axes."""
    idx = jnp.zeros((), jnp.int32)
    for a in axes:
        idx = idx * lax.axis_size(a) + lax.axis_index(a)
    return idx
