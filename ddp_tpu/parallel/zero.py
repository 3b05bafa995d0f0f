"""ZeRO-style weight-update sharding on the data-parallel path.

The reference (and our own ``parallel/ddp.py``) ends every step the
same way DDP always has: all-reduce the FULL gradient tree, then let
every replica redundantly run the identical optimizer update over the
identical replicated moments — N copies of the same math and N copies
of the Adam moments. "Automatic Cross-Replica Sharding of Weight
Update in Data-Parallel Training" (PAPERS.md #3) removes exactly that
redundancy without touching the model math:

    reduce-scatter(grads)  →  each replica owns 1/N of every bucket
    sharded optimizer update  →  moments + update math are 1/N
    all-gather(params)     →  replicas re-converge, bit-for-bit

Params stay replicated at rest (this is ZeRO stage 1, not FSDP — the
``fsdp`` axis already covers stage 3 by annotation); only the
optimizer state and the update compute shard. Total collective payload
is unchanged — a ring all-reduce IS a reduce-scatter + all-gather —
but the *all-reduce* disappears, the moments memory divides by N, and
the two half-collectives become independently schedulable per bucket.

Gradients are packed into size-targeted **buckets** (``--zero_bucket_mb``,
the knob DDP's C++ reducer calls ``bucket_cap_mb``): each bucket is a
flat fp32 vector padded to a multiple of the replica count, so a
parameter count not divisible by the axis size costs padding, never a
wrong answer. Bucketing is what buys comm/compute overlap: each
bucket's reduce-scatter depends only on ITS leaves' gradients, so the
scheduler may dispatch bucket k's scatter while backward compute for
bucket k+1's layers is still in flight — and the all-gathers pipeline
against the sharded updates the same way. ``overlap=False`` builds the
control: an ``optimization_barrier`` fence after the full backward
plus a serial chain through the collectives, the step the overlapped
one is to be measured against (not measured on a chip).

Two expressions of the same decomposition, per the paper's framing:

- ``make_zero_train_step`` — the explicit-collective ``shard_map``
  step (the DDP image family): ``lax.psum_scatter`` / sharded optax
  update / ``lax.all_gather``, every collective visible.
- ``zero_gspmd_update`` — the in-graph GSPMD expression (the causal
  LM's jit-level step): the same bucket layout pinned with
  ``with_sharding_constraint`` so the SPMD partitioner shards the
  update math and the moments, and derives the parameter all-gather.

The optimizer contract: the update rule must be *elementwise* (sgd,
momentum, adam, adamw, weight decay, schedules) because it runs on
1/N flat shards — transforms that couple elements across the tree
(full-shape parameter EMA) are rejected at construction
(train/optim.py ``check_zero_compatible``). Global-norm clipping IS
composable despite being cross-element: the norm is one scalar, and
the scattered shards partition the reduced gradient exactly, so
``psum`` of per-shard squared sums over the shard axis is the global
norm — ``grad_clip_norm`` applies it in-step, parity-pinned against
the ddp path's ``optax.clip_by_global_norm``.

Two pod-scale extensions ride the same bucket layout (ROADMAP item 3):

- ``gather_dtype=bf16`` — the cross-replica sharding paper's headline
  win (PAPERS.md #3): the updated 1/N shards cast ONCE and all-gather
  half-width, while the optimizer math and the fp32 **master shards**
  (kept in ``opt_state['master']``, data-sharded like the moments)
  stay full precision — the forward sees bf16-rounded params, the
  update never does, so rounding cannot compound across steps. The
  fp32 default is bit-identical to the pre-flag path (same opt_state
  schema, same HLO).
- ``hier`` (a mesh with a ``dcn`` axis > 1) — hierarchical collectives,
  the topology the pjit/TPUv4 paper scales on (PAPERS.md #5):
  reduce-scatter within a slice over ICI, all-reduce only the 1/N
  shards across slices over DCN, all-gather within the slice. Cross-
  slice traffic drops from the full gradient to 1/N of it;
  ``zero_comm_bytes`` prices the split per axis and the HLO cross-
  check (obs/xprof.py replica-group attribution) measures it.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ddp_tpu.parallel.common import (
    check_accum_divisible,
    make_loss_fn,
)
from ddp_tpu.parallel.ddp import StepMetrics, TrainState
from ddp_tpu.runtime.mesh import data_axes


@dataclasses.dataclass(frozen=True)
class Bucket:
    """One flat fp32 reduce-scatter unit: a contiguous run of leaves.

    ``padded`` rounds ``total`` up to a multiple of the replica count
    so the scatter tiles evenly; the pad region carries zeros end to
    end (zero grads → zero moments → zero update), so indivisible
    parameter counts are correct by construction.
    """

    leaf_ids: tuple[int, ...]
    sizes: tuple[int, ...]
    total: int
    padded: int
    shard: int


@dataclasses.dataclass(frozen=True)
class BucketLayout:
    """Assignment of every param leaf (flatten order) to a bucket."""

    buckets: tuple[Bucket, ...]
    num_leaves: int
    world: int

    @property
    def padded_total(self) -> int:
        return sum(b.padded for b in self.buckets)


def _opt_key(i: int) -> str:
    return f"b{i:03d}"


def opt_keys(layout: BucketLayout) -> list[str]:
    return [_opt_key(i) for i in range(len(layout.buckets))]


def build_layout(
    params, world: int, *, bucket_mb: float = 4.0
) -> BucketLayout:
    """Greedy size-targeted bucketing over the param leaves.

    ``params`` may be arrays or ``ShapeDtypeStruct``s — only shapes
    matter. Leaves pack in flatten order until a bucket crosses the
    byte target (fp32 accounting — the reduction dtype); a leaf larger
    than the target gets its own bucket rather than being split.
    """
    if world < 1:
        raise ValueError(f"world must be >= 1, got {world}")
    if bucket_mb <= 0:
        raise ValueError(f"bucket_mb must be > 0, got {bucket_mb}")
    leaves = jax.tree_util.tree_leaves(params)
    if not leaves:
        raise ValueError("empty parameter tree — nothing to shard")
    target_elems = max(1, int(bucket_mb * 2**20) // 4)
    buckets: list[Bucket] = []
    ids: list[int] = []
    sizes: list[int] = []
    total = 0

    def close():
        nonlocal ids, sizes, total
        if not ids:
            return
        padded = -(-total // world) * world
        buckets.append(
            Bucket(
                leaf_ids=tuple(ids),
                sizes=tuple(sizes),
                total=total,
                padded=padded,
                shard=padded // world,
            )
        )
        ids, sizes, total = [], [], 0

    for i, leaf in enumerate(leaves):
        n = 1
        for s in leaf.shape:
            n *= int(s)
        if n >= target_elems:
            # An oversized leaf gets its OWN bucket: trapping the
            # accumulated small leaves behind it would serialize their
            # scatter on the big transfer.
            close()
            ids, sizes, total = [i], [n], n
            close()
            continue
        ids.append(i)
        sizes.append(n)
        total += n
        if total >= target_elems:
            close()
    close()
    return BucketLayout(
        buckets=tuple(buckets), num_leaves=len(leaves), world=world
    )


def check_zero_mesh(mesh: Mesh, *, allow_model_axes: bool = False) -> None:
    """The sharded update scatters over the replica axes (``data``,
    hierarchically ``dcn``×``data``): fsdp/expert/pipe already own
    their own optimizer-state story (fsdp IS ZeRO-3; expert/pipe shard
    state by their rule layouts). ``allow_model_axes=True`` admits
    populated ``model``/``seq`` axes — the GSPMD expression shards the
    buckets over ``data`` while REPLICATING them over model/seq (those
    axes see identical gradients, so the flat buckets are uniform
    across them by construction; the causal-LM composition pins it)."""
    reject = ("fsdp", "expert", "pipe")
    if not allow_model_axes:
        reject = ("model", "seq") + reject
    bad = {
        a: int(mesh.shape[a])
        for a in reject
        if mesh.shape.get(a, 1) > 1
    }
    if bad:
        raise ValueError(
            f"--parallel zero shards the weight update over the data "
            f"axis only; {bad} already shard optimizer state their own "
            "way — drop the axes or the flag"
        )


GATHER_DTYPES = {"fp32": jnp.float32, "bf16": jnp.bfloat16}


def _resolve_gather_dtype(gather_dtype):
    """'fp32'/'bf16' or a jnp dtype → (jnp dtype, master_mode)."""
    if isinstance(gather_dtype, str):
        if gather_dtype not in GATHER_DTYPES:
            raise ValueError(
                f"gather_dtype must be one of {sorted(GATHER_DTYPES)}, "
                f"got {gather_dtype!r}"
            )
        gather_dtype = GATHER_DTYPES[gather_dtype]
    dt = jnp.dtype(gather_dtype)
    if dt not in (jnp.dtype(jnp.float32), jnp.dtype(jnp.bfloat16)):
        raise ValueError(
            f"gather_dtype must be float32 or bfloat16, got {dt}"
        )
    return dt, dt == jnp.dtype(jnp.bfloat16)


def _flatten_buckets(layout: BucketLayout, leaves) -> list[jax.Array]:
    """Leaf list → one flat fp32 ``[padded]`` vector per bucket."""
    flats = []
    for b in layout.buckets:
        parts = [
            leaves[i].astype(jnp.float32).reshape(-1) for i in b.leaf_ids
        ]
        pad = b.padded - b.total
        if pad:
            parts.append(jnp.zeros((pad,), jnp.float32))
        flats.append(parts[0] if len(parts) == 1 else jnp.concatenate(parts))
    return flats


def _unflatten_buckets(layout: BucketLayout, flats, like_leaves):
    """Flat ``[padded]`` vectors → leaf list shaped/typed like
    ``like_leaves`` (static slices; the pad tail is dropped)."""
    out: list[Any] = [None] * layout.num_leaves
    for b, flat in zip(layout.buckets, flats):
        off = 0
        for i, n in zip(b.leaf_ids, b.sizes):
            like = like_leaves[i]
            out[i] = (
                flat[off : off + n].reshape(like.shape).astype(like.dtype)
            )
            off += n
    return out


def _opt_template(optimizer, layout: BucketLayout, *, master: bool = False):
    """abstract optimizer state over the flat buckets + the elementwise
    contract check: every state leaf must be a scalar (schedule/Adam
    counts) or shaped exactly like its bucket — anything else means
    the update couples elements across the tree and cannot run on
    1/N shards. Shape-based, so it catches full-shape STATE (a param
    EMA of the original tree) but not STATELESS cross-element
    transforms: a chained ``optax.clip_by_global_norm`` carries
    EmptyState, slips this check, and would silently clip PER SHARD —
    use the steps' ``grad_clip_norm`` knob (which computes the true
    global norm from the shards) instead of chaining; direct-API
    callers composing their own optax chains own the elementwise
    contract for stateless members.

    ``master=True`` is the bf16-gather layout: the tree grows a
    sibling ``{"base": <optax state>, "master": {b###: [padded]}}``
    level holding the fp32 master shards — bucket-shaped, so the same
    contract (and the elastic re-bucketer's pad arithmetic) covers
    them."""
    flats = {
        _opt_key(i): jax.ShapeDtypeStruct((b.padded,), jnp.float32)
        for i, b in enumerate(layout.buckets)
    }
    tpl = jax.eval_shape(optimizer.init, flats)
    allowed = {v.shape for v in flats.values()}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tpl)[0]:
        if len(leaf.shape) and leaf.shape not in allowed:
            name = jax.tree_util.keystr(path)
            raise ValueError(
                f"optimizer state leaf {name} has shape {leaf.shape}, "
                "not the flat bucket shape — the zero update runs "
                "elementwise on 1/N shards (sgd/momentum/adam/adamw "
                "compose; parameter EMA does not — "
                "train/optim.check_zero_compatible)"
            )
    if master:
        return {"base": tpl, "master": dict(flats)}
    return tpl


def opt_state_specs(
    optimizer,
    layout: BucketLayout,
    *,
    shard_axes: tuple[str, ...] = ("data",),
    gather_dtype=jnp.float32,
):
    """PartitionSpec tree for the resting optimizer state: flat bucket
    leaves shard dim 0 over ``shard_axes`` (the scatter group — just
    ``data`` on flat and hierarchical meshes, ``('dcn','data')`` when
    one flat scatter spans the pod), scalars replicate. bf16-gather
    mode adds the fp32 master shards under ``'master'``, laid out
    exactly like the moments."""
    _, master = _resolve_gather_dtype(gather_dtype)
    tpl = _opt_template(optimizer, layout, master=master)
    return jax.tree.map(
        lambda x: P(shard_axes) if len(x.shape) else P(), tpl
    )


def create_zero_opt_state(
    params,
    optimizer,
    mesh: Mesh,
    layout: BucketLayout,
    *,
    shard_axes: tuple[str, ...] = ("data",),
    gather_dtype=jnp.float32,
):
    """Initialize the optimizer state directly into the sharded layout.

    State leaves are GLOBAL ``[padded]`` arrays resting sharded over
    the scatter axes (1/N per device — the memory win is at rest, not
    just in the step); scalars replicate. bf16-gather mode seeds the
    fp32 master shards from the initial params under ``'master'``.
    Works multi-process: every process computes the same init under
    one jit with explicit out_shardings.
    """
    _, master = _resolve_gather_dtype(gather_dtype)
    leaves = jax.tree_util.tree_leaves(params)
    flats = dict(zip(opt_keys(layout), _flatten_buckets(layout, leaves)))
    specs = opt_state_specs(
        optimizer, layout, shard_axes=shard_axes, gather_dtype=gather_dtype
    )
    shardings = jax.tree.map(lambda s: NamedSharding(mesh, s), specs)
    init = (
        (lambda f: {"base": optimizer.init(f), "master": f})
        if master
        else optimizer.init
    )
    return jax.jit(init, out_shardings=shardings)(flats)


def _replica_geometry(
    mesh: Mesh, hier: bool | None
) -> tuple[int, bool, tuple[str, ...]]:
    """(slice count, hierarchical?, scatter axes) for a zero mesh.

    ``hier=None`` auto-resolves: a populated ``dcn`` axis means the
    two-level step (scatter within a slice over ``data``, shard
    exchange across slices). ``hier=False`` on a dcn mesh is the flat
    control — ONE scatter spanning ``('dcn', 'data')``, every byte
    riding the slow fabric.
    """
    dcn = int(mesh.shape.get("dcn", 1))
    hier = (dcn > 1) if hier is None else (bool(hier) and dcn > 1)
    scatter_axes = ("dcn", "data") if (dcn > 1 and not hier) else ("data",)
    return dcn, hier, scatter_axes


def create_zero_state(
    model,
    optimizer: optax.GradientTransformation,
    sample_input,
    mesh: Mesh,
    *,
    seed: int = 0,
    bucket_mb: float = 4.0,
    gather_dtype=jnp.float32,
    hier: bool | None = None,
) -> tuple[TrainState, BucketLayout]:
    """Replicated params + step + model_state, data-sharded flat
    optimizer state. The placements ARE the contract (checkpoint
    restores template on them, like the fsdp family).

    The layout's ``world`` is the SCATTER group size: the ``data``
    axis alone on flat and hierarchical meshes (hier shards stay
    1/|data| — the dcn exchange reduces them in place, it does not
    re-shard), ``dcn×data`` when a flat scatter spans the pod.
    """
    from ddp_tpu.parallel.common import _train_kwarg

    check_zero_mesh(mesh)
    _, _, scatter_axes = _replica_geometry(mesh, hier)
    variables = model.init(
        jax.random.key(seed), sample_input, **_train_kwarg(model, False)
    )
    params = variables["params"]
    model_state = {k: v for k, v in variables.items() if k != "params"}
    shard_world = int(
        np.prod([mesh.shape[a] for a in scatter_axes])
    )
    layout = build_layout(params, shard_world, bucket_mb=bucket_mb)
    rep = NamedSharding(mesh, P())
    put = lambda t: jax.tree.map(lambda x: jax.device_put(x, rep), t)
    params = put(params)
    state = TrainState(
        step=jax.device_put(jnp.zeros((), jnp.int32), rep),
        params=params,
        opt_state=create_zero_opt_state(
            params, optimizer, mesh, layout,
            shard_axes=scatter_axes, gather_dtype=gather_dtype,
        ),
        model_state=put(model_state),
    )
    return state, layout


def _scatter_buckets(
    flats,
    *,
    sequential: bool = False,
    axes: tuple[str, ...] = ("data",),
    dcn_axis: str | None = None,
):
    """Reduce-scatter each bucket over ``axes`` (raw SUMS — callers
    divide by the replica count). ``dcn_axis`` is the hierarchical
    second level: after the within-slice scatter over ICI, all-reduce
    the 1/N shard across slices — the ONLY bytes that touch the slow
    fabric, 1/N of the flat payload. ``sequential=True`` is the
    no-overlap control: a barrier fences the collectives behind the
    ENTIRE backward, and each scatter chains on its predecessor, so
    nothing can hide under compute."""
    if sequential and len(flats) > 1:
        flats = list(lax.optimization_barrier(tuple(flats)))
    out = []
    prev = None
    for f in flats:
        if sequential and prev is not None:
            f, _ = lax.optimization_barrier((f, prev))
        s = lax.psum_scatter(f, axes, scatter_dimension=0, tiled=True)
        if dcn_axis is not None:
            s = lax.psum(s, dcn_axis)
        out.append(s)
        prev = s
    return out


def _gather_buckets(
    shards,
    *,
    sequential: bool = False,
    axes: tuple[str, ...] = ("data",),
    gather_dtype=jnp.float32,
):
    """All-gather each bucket's updated param shard back to ``[padded]``
    (tiled — member i contributes block i, the psum_scatter order).
    ``gather_dtype=bf16`` casts the shard ONCE before the collective,
    so the dominant all-gather moves half the bytes; the caller's
    unflatten casts back to the param dtype. The half-width value
    rides the wire BITCAST to uint16: a bf16 all-gather is re-widened
    to fp32 by XLA:CPU's float-normalization pass (measured — same
    values, double the bytes, and the HLO cross-check caught it),
    while an integer collective is left alone on every backend; the
    bitcasts are free reinterpretations on either side."""
    half = jnp.dtype(gather_dtype) != jnp.dtype(jnp.float32)
    out = []
    prev = None
    for s in shards:
        if sequential and prev is not None:
            s, _ = lax.optimization_barrier((s, prev))
        wire = s.astype(gather_dtype)
        if half:
            wire = lax.bitcast_convert_type(wire, jnp.uint16)
        g = lax.all_gather(wire, axes, axis=0, tiled=True)
        if half:
            g = lax.bitcast_convert_type(g, gather_dtype)
        out.append(g)
        prev = g
    return out


def make_zero_train_step(
    model,
    optimizer: optax.GradientTransformation,
    mesh: Mesh,
    layout: BucketLayout,
    *,
    compute_dtype=jnp.float32,
    donate: bool = True,
    seed: int = 0,
    aux_loss_weight: float = 0.01,
    grad_accum_steps: int = 1,
    augment_fn=None,
    label_smoothing: float = 0.0,
    overlap: bool = True,
    gather_dtype=jnp.float32,
    grad_clip_norm: float = 0.0,
    hier: bool | None = None,
) -> Callable[[TrainState, jax.Array, jax.Array], tuple[TrainState, StepMetrics]]:
    """The explicit-collective (shard_map) zero step — ``parallel/ddp.py``
    ``make_train_step``'s contract with the update stage swapped:
    ``pmean(grads) → update`` becomes ``psum_scatter → 1/N update →
    all_gather``. Loss/accuracy semantics are identical (pinned by
    tests/test_zero.py and the 2-process gloo spawn pins).

    ``grad_accum_steps=k`` accumulates into the SCATTERED shards — one
    reduce-scatter per microbatch, accumulator buffers 1/N — so the
    memory win survives accumulation (a full-tree accumulator would
    undo it).

    ``gather_dtype=bf16`` halves the parameter all-gather: the update
    runs fp32 on the master shards in ``opt_state['master']`` and only
    the cast result rides the wire (module docstring). ``hier`` (auto
    on a ``dcn`` mesh): within-slice scatter/gather over ICI plus a
    1/N cross-slice shard exchange over DCN. ``grad_clip_norm > 0``
    applies optax's global-norm clip semantics from the scattered
    shards — the psum of per-shard squared sums IS the global norm.
    """
    check_zero_mesh(mesh)
    axes = data_axes(mesh)
    gdtype, master_mode = _resolve_gather_dtype(gather_dtype)
    n_slices, hier, scatter_axes = _replica_geometry(mesh, hier)
    world = int(np.prod([mesh.shape[a] for a in scatter_axes]))
    n_replicas = int(np.prod([mesh.shape[a] for a in axes]))
    dcn_axis = "dcn" if hier else None
    if world != layout.world:
        raise ValueError(
            f"layout built for world {layout.world}, the scatter group "
            f"{scatter_axes} is {world}"
        )
    keys = opt_keys(layout)
    loss_fn = make_loss_fn(
        model, compute_dtype, aux_loss_weight, augment_fn=augment_fn,
        label_smoothing=label_smoothing,
    )

    def per_shard_step(state: TrainState, images, labels):
        mutable = list(state.model_state.keys())
        rng = jax.random.fold_in(jax.random.key(seed), state.step)
        for a in axes:
            rng = jax.random.fold_in(rng, lax.axis_index(a))

        if grad_accum_steps == 1:
            (loss, (logits, new_ms)), grads = jax.value_and_grad(
                loss_fn, has_aux=True
            )(state.params, state.model_state, images, labels, rng, mutable)
            correct = (jnp.argmax(logits.astype(jnp.float32), -1) == labels).sum()
            n_labels = labels.shape[0]
            # THE rework: where ddp.py all-reduces the full tree, each
            # bucket reduce-scatters independently — free to dispatch
            # while backward compute for later buckets is in flight.
            gshards = _scatter_buckets(
                _flatten_buckets(layout, jax.tree_util.tree_leaves(grads)),
                sequential=not overlap,
                axes=scatter_axes, dcn_axis=dcn_axis,
            )
            scale = 1.0 / n_replicas
        else:
            mb = check_accum_divisible(images.shape[0], grad_accum_steps)
            imgs = images.reshape(grad_accum_steps, mb, *images.shape[1:])
            lbls = labels.reshape(grad_accum_steps, mb)

            def micro(carry, xy):
                sh_acc, ms, loss_acc, correct_acc, i = carry
                x, y = xy
                (mloss, (mlogits, mms)), g = jax.value_and_grad(
                    loss_fn, has_aux=True
                )(state.params, ms, x, y, jax.random.fold_in(rng, i), mutable)
                # Accumulate the SCATTERED shard, not the full tree:
                # the accumulator is 1/N per replica by construction.
                sh = _scatter_buckets(
                    _flatten_buckets(layout, jax.tree_util.tree_leaves(g)),
                    sequential=not overlap,
                    axes=scatter_axes, dcn_axis=dcn_axis,
                )
                c = (jnp.argmax(mlogits.astype(jnp.float32), -1) == y).sum()
                return (
                    [a + s for a, s in zip(sh_acc, sh)],
                    mms,
                    loss_acc + mloss,
                    correct_acc + c.astype(jnp.float32),
                    i + 1,
                ), None

            zero_sh = [
                jnp.zeros((b.shard,), jnp.float32) for b in layout.buckets
            ]
            (gshards, new_ms, loss_sum, correct, _), _ = lax.scan(
                micro,
                (
                    zero_sh,
                    state.model_state,
                    jnp.zeros((), jnp.float32),
                    jnp.zeros((), jnp.float32),
                    jnp.zeros((), jnp.int32),
                ),
                (imgs, lbls),
            )
            loss = loss_sum / grad_accum_steps
            n_labels = images.shape[0]
            scale = 1.0 / (n_replicas * grad_accum_steps)

        g_tree = {k: s * scale for k, s in zip(keys, gshards)}
        # Global grad norm from disjoint shards: one scalar psum over
        # the scatter group (hier shards are already globally reduced,
        # so the within-slice psum of disjoint blocks IS the norm —
        # summing over dcn too would count every slice's copy).
        local_sq = sum(jnp.sum(jnp.square(g)) for g in g_tree.values())
        grad_norm = jnp.sqrt(lax.psum(local_sq, scatter_axes))
        if grad_clip_norm:
            # optax.clip_by_global_norm semantics on the scattered
            # shards: one scalar, same (t / norm) * clip scaling —
            # parity-pinned against the ddp path's chained transform.
            g_tree = {
                k: jnp.where(
                    grad_norm < grad_clip_norm,
                    g,
                    (g / grad_norm) * grad_clip_norm,
                )
                for k, g in g_tree.items()
            }
        p_leaves = jax.tree_util.tree_leaves(state.params)
        if master_mode:
            # The fp32 master shards live in opt_state — the bf16
            # round-trip the gathered params took never feeds back
            # into the update math.
            base_opt = state.opt_state["base"]
            p_tree = state.opt_state["master"]
        else:
            # This replica's own param block, sliced locally (params
            # are replicated — no comm; block order is psum_scatter's
            # over the scatter group, slice-major when it spans dcn).
            idx = jnp.int32(0)
            for a in scatter_axes:
                idx = idx * mesh.shape[a] + lax.axis_index(a)
            base_opt = state.opt_state
            p_flats = _flatten_buckets(layout, p_leaves)
            p_tree = {
                k: lax.dynamic_slice_in_dim(f, idx * b.shard, b.shard)
                for k, f, b in zip(keys, p_flats, layout.buckets)
            }
        # The 1/N update: same elementwise math as the replicated step,
        # restricted to the shard this replica owns.
        updates, base_opt = optimizer.update(g_tree, base_opt, p_tree)
        new_p = optax.apply_updates(p_tree, updates)
        opt_state = (
            {"base": base_opt, "master": new_p} if master_mode else base_opt
        )
        gathered = _gather_buckets(
            [new_p[k] for k in keys],
            sequential=not overlap,
            axes=scatter_axes, gather_dtype=gdtype,
        )
        params = jax.tree_util.tree_unflatten(
            jax.tree_util.tree_structure(state.params),
            _unflatten_buckets(layout, gathered, p_leaves),
        )
        # SyncBN-style non-gradient stats averaging, exactly as ddp.py.
        new_ms = jax.tree.map(
            lambda v: lax.pmean(v.astype(jnp.float32), axes), new_ms
        )
        metrics = StepMetrics(
            loss=lax.pmean(loss, axes),
            accuracy=lax.psum(correct, axes) / (n_labels * n_replicas),
            grad_norm=grad_norm,
        )
        return TrainState(state.step + 1, params, opt_state, new_ms), metrics

    ospecs = opt_state_specs(
        optimizer, layout, shard_axes=scatter_axes, gather_dtype=gdtype
    )
    state_specs = TrainState(
        step=P(), params=P(), opt_state=ospecs, model_state=P()
    )
    bspec = P(axes)
    sharded = jax.shard_map(
        per_shard_step,
        mesh=mesh,
        in_specs=(state_specs, bspec, bspec),
        out_specs=(state_specs, P()),
        check_vma=False,
    )
    return jax.jit(sharded, donate_argnums=(0,) if donate else ())


def zero_gspmd_update(
    optimizer,
    layout: BucketLayout,
    mesh: Mesh,
    grads,
    opt_state,
    params,
    *,
    gather_dtype=jnp.float32,
    grad_clip_norm: float = 0.0,
):
    """The in-graph GSPMD expression of the sharded update (used by the
    causal LM's jit-level step, models/lm.py).

    Gradients arrive already reduced (the shard_map transpose psums
    them); constraining the flat buckets to ``P('data')`` is a free
    replicated→sharded reshard, after which the SPMD partitioner runs
    the update math and lays the moments out 1/N per device. On a mesh
    with populated ``model``/``seq`` axes the same constraint
    REPLICATES the buckets over them (the composition lift — gradients
    are uniform across those axes, so the sharded update is too). The
    final replicated constraint on the new params is the derived
    all-gather; ``gather_dtype=bf16`` casts the sharded result first,
    so the derived gather moves half the bytes while the fp32 master
    shards rest in ``opt_state['master']``. Returns
    ``(new_params, new_opt_state)``.
    """
    gdtype, master_mode = _resolve_gather_dtype(gather_dtype)
    shard = NamedSharding(mesh, P("data"))
    rep = NamedSharding(mesh, P())
    keys = opt_keys(layout)
    # Composition guard (measured on jax 0.4.37 / XLA:CPU, the round-6
    # partitioner bug family): on a mesh with populated non-data axes,
    # a `concatenate` feeding a sharded→replicated reshard chain
    # compiles to PARTIAL sums over the extra axis — every value
    # doubled at model=2. Pinning the freshly-concatenated flat to
    # replicated BEFORE the data-shard constraint forces the partition
    # boundary to the safe side; a no-op on pure-data meshes (where it
    # is skipped so the flat-mesh HLO stays byte-identical).
    multi_axis = any(
        s > 1 for a, s in mesh.shape.items() if a != "data"
    )

    def to_shard(f):
        if multi_axis:
            f = lax.with_sharding_constraint(f, rep)
        return lax.with_sharding_constraint(f, shard)

    g_leaves, tdef = jax.tree_util.tree_flatten(grads)
    p_leaves = jax.tree_util.tree_leaves(params)
    g_tree = {
        k: to_shard(f)
        for k, f in zip(keys, _flatten_buckets(layout, g_leaves))
    }
    if grad_clip_norm:
        # Global-norm clip at the jit level: the buckets partition the
        # gradient exactly (pad region is zeros), so the flat sums ARE
        # optax.clip_by_global_norm's norm — same (t / norm) * clip
        # scaling, parity-pinned against the replicated chain.
        g_norm = jnp.sqrt(
            sum(jnp.sum(jnp.square(g)) for g in g_tree.values())
        )
        g_tree = {
            k: jnp.where(
                g_norm < grad_clip_norm, g, (g / g_norm) * grad_clip_norm
            )
            for k, g in g_tree.items()
        }
    if master_mode:
        base_opt = opt_state["base"]
        p_tree = {
            k: lax.with_sharding_constraint(v, shard)
            for k, v in opt_state["master"].items()
        }
    else:
        base_opt = opt_state
        p_tree = {
            k: to_shard(f)
            for k, f in zip(keys, _flatten_buckets(layout, p_leaves))
        }
    updates, new_base = optimizer.update(g_tree, base_opt, p_tree)
    # Moments REST sharded between steps — without the constraint the
    # partitioner may replicate them on output and the memory win
    # silently evaporates.
    new_base = jax.tree.map(
        lambda x: lax.with_sharding_constraint(x, shard)
        if getattr(x, "ndim", 0)
        else x,
        new_base,
    )
    new_flats = optax.apply_updates(p_tree, updates)
    if master_mode:
        new_opt = {
            "base": new_base,
            "master": {
                k: lax.with_sharding_constraint(new_flats[k], shard)
                for k in keys
            },
        }
    else:
        new_opt = new_base
    if master_mode:
        # Half-width derived gather, wire-pinned as in
        # ``_gather_buckets``: cast the SHARDED result once, bitcast
        # to uint16 so no float pass re-widens the collective, and
        # let the replicated constraint derive the u16 all-gather.
        # The barrier pins WHERE the reshard happens — without it the
        # partitioner is free to site the all-gather anywhere along
        # the elementwise cast chain and picks the fp32 end (measured).
        def _half_gather(flat):
            w = lax.with_sharding_constraint(
                lax.bitcast_convert_type(flat.astype(gdtype), jnp.uint16),
                shard,
            )
            w = lax.optimization_barrier(w)
            return lax.bitcast_convert_type(
                lax.with_sharding_constraint(w, rep), gdtype
            )

        rep_flats = [_half_gather(new_flats[k]) for k in keys]
    else:
        rep_flats = [
            lax.with_sharding_constraint(new_flats[k], rep) for k in keys
        ]
    new_params = jax.tree_util.tree_unflatten(
        tdef, _unflatten_buckets(layout, rep_flats, p_leaves)
    )
    return new_params, new_opt


# ---- elastic restore: re-bucket when the world size changed ---------

_BUCKET_KEY_RE = re.compile(r"^b\d{3}$")


def _path_bucket_key(path) -> str | None:
    """The ``b000``-style bucket key on a tree path, if any (the flat
    state leaves live under dict keys named by ``_opt_key``)."""
    for k in path:
        key = str(getattr(k, "key", k))
        if _BUCKET_KEY_RE.fullmatch(key):
            return key
    return None


@dataclasses.dataclass
class ZeroElasticReshaper:
    """Restore-time RE-BUCKETING for world-shape-agnostic checkpoints.

    Everything else in a zero run reshards on load (params and scalars
    are replicated; Orbax templates them onto the live mesh — the
    tests/test_elastic_shard.py mechanism). The flat optimizer buckets
    cannot: their GLOBAL shapes are world-dependent (``padded`` rounds
    each bucket's ``total`` up to a multiple of the replica count), so
    a checkpoint saved at world 2 literally has different array shapes
    than world 1's layout and no resharding can bridge them. Bucket
    *assignment* (which leaves, in what order, with what totals) is
    world-independent — ``build_layout`` never consults the world for
    it — so the bridge is pure padding arithmetic:

        saved ``[padded_old]`` → strip to ``[total]`` (the pad region
        is zeros end to end, the ``Bucket`` contract) → re-pad to
        ``[padded_new]`` → place 1/N over the live ``data`` axis.

    ``plan`` inspects the checkpoint's opt_state *metadata* (no array
    reads) and returns an abstract restore tree in the SAVED shapes on
    single-device placements — or None when shapes already match (the
    common, non-resized restore pays nothing). ``apply`` then performs
    the re-bucket on the host-restored values. A bucket-STRUCTURE
    mismatch (``--zero_bucket_mb`` or the model changed, not the
    world) is rejected — that state genuinely cannot be reinterpreted.

    Bit-identity contract (pinned by tests/test_elastic.py): the
    re-bucketed state equals a fresh sharding of the merged state —
    zeros in, zeros out, values untouched.
    """

    optimizer: Any
    layout: BucketLayout
    mesh: Mesh
    # bf16-gather states carry the fp32 master shards under 'master' —
    # bucket-shaped like the moments, so the same pad arithmetic
    # re-buckets them; the template just has to include them.
    gather_dtype: Any = jnp.float32

    def _live_padded(self) -> dict[str, int]:
        return {
            _opt_key(i): b.padded
            for i, b in enumerate(self.layout.buckets)
        }

    def plan(self, meta_opt) -> Any | None:
        """Checkpoint opt_state metadata → abstract restore tree in the
        saved bucket shapes, or None when no re-bucket is needed."""
        saved: dict[str, int] = {}

        def visit(path, leaf):
            k = _path_bucket_key(path)
            shape = tuple(getattr(leaf, "shape", ()) or ())
            if k is not None and len(shape) == 1:
                saved[k] = int(shape[0])

        jax.tree_util.tree_map_with_path(visit, meta_opt)
        if not saved:
            return None  # not a bucketed opt_state — nothing to plan
        new = self._live_padded()
        if set(saved) != set(new):
            raise ValueError(
                f"checkpoint opt_state has buckets {sorted(saved)} but "
                f"the live layout has {sorted(new)} — the bucket "
                "STRUCTURE changed (--zero_bucket_mb or the model), "
                "not just the world size; elastic re-bucketing only "
                "absorbs world changes. --reset_opt_state keeps the "
                "weights and drops the moments."
            )
        totals = {
            _opt_key(i): b.total
            for i, b in enumerate(self.layout.buckets)
        }
        short = {k: p for k, p in saved.items() if p < totals[k]}
        if short:
            raise ValueError(
                f"checkpoint buckets {sorted(short)} are smaller than "
                "their live totals — the parameter tree changed since "
                "the save; this is not a world resize"
            )
        if all(saved[k] == new[k] for k in new):
            return None  # same world shape — restore templated as usual
        # REPLICATED placements over the live mesh, not a per-process
        # local device: every rank must hand Orbax the SAME global
        # shardings or a multi-process restore desyncs — and a fully-
        # replicated array is host-readable on every process, which is
        # exactly what ``apply`` needs for the re-pad arithmetic.
        rep = NamedSharding(self.mesh, P())
        _, master = _resolve_gather_dtype(self.gather_dtype)
        tpl = _opt_template(self.optimizer, self.layout, master=master)

        def override(path, leaf):
            k = _path_bucket_key(path)
            shape = (
                (saved[k],)
                if k is not None and len(leaf.shape) == 1
                else leaf.shape
            )
            return jax.ShapeDtypeStruct(shape, leaf.dtype, sharding=rep)

        return jax.tree_util.tree_map_with_path(override, tpl)

    def apply(self, restored_opt):
        """Host-restored old-world state → live data-sharded state."""
        shard = NamedSharding(self.mesh, P("data"))
        rep = NamedSharding(self.mesh, P())
        totals = {
            _opt_key(i): b.total
            for i, b in enumerate(self.layout.buckets)
        }
        padded = self._live_padded()

        def fix(path, leaf):
            arr = np.asarray(leaf)
            k = _path_bucket_key(path)
            if k is not None and arr.ndim == 1:
                arr = arr[: totals[k]]
                pad = padded[k] - totals[k]
                if pad:
                    arr = np.concatenate(
                        [arr, np.zeros((pad,), arr.dtype)]
                    )
                sharding = shard
            else:
                sharding = rep
            # make_array_from_callback assembles the global array from
            # addressable shards only, so the same spelling is correct
            # single- and multi-process (each process holds the full
            # host copy — the restore placed it single-device locally).
            return jax.make_array_from_callback(
                arr.shape, sharding, lambda idx, a=arr: a[idx]
            )

        return jax.tree_util.tree_map_with_path(fix, restored_opt)


# ---- accounting: what the strategy moves and what it holds ----------


def ddp_comm_bytes(params, world: int) -> dict[str, int]:
    """Per-step per-replica collective payload of the ddp baseline,
    ring model: all-reduce = 2·(N−1)/N of the fp32 gradient bytes."""
    n = sum(
        int(jnp.size(leaf)) for leaf in jax.tree_util.tree_leaves(params)
    )
    ar = int(2 * (world - 1) / max(1, world) * n * 4)
    return {
        "all_reduce": ar, "reduce_scatter": 0, "all_gather": 0,
        "total": ar,
    }


def zero_comm_bytes(
    layout: BucketLayout,
    world: int,
    *,
    grad_accum_steps: int = 1,
    gspmd: bool = False,
    dcn: int = 1,
    gather_dtype=jnp.float32,
    hier: bool = True,
) -> dict[str, int]:
    """Per-step per-replica collective payload of the zero strategy.

    Explicit (shard_map) path: the all-reduce is GONE — replaced by a
    reduce-scatter per bucket per microbatch ((N−1)/N of the padded
    bytes each) plus one parameter all-gather. Ring-model total equals
    the ddp all-reduce at ``grad_accum_steps=1`` (RS + AG *is* an AR);
    the wins are the vanished redundant update compute, the 1/N
    moments, and the per-bucket scheduling freedom. The in-graph GSPMD
    path keeps the transpose's gradient all-reduce — ONE PER
    MICROBATCH under accumulation, exactly like the explicit path's
    scatters (models/lm.py backs through the shard_map forward inside
    each scan iteration) — and adds the parameter all-gather:
    memory-only win, priced honestly here.

    ``gather_dtype=bf16`` halves the all-gather term — and nothing
    else: the scatters still move fp32 gradients.

    ``dcn > 1`` prices the pod: ``world`` is the ICI (``data``) axis,
    ``dcn`` the slice count. ``hier=True`` (the two-level step) adds a
    ``by_axis`` split — the within-slice scatter/gather ride ``ici``
    and only the 1/world shard exchange (2·(S−1)/S of ``padded/world``
    bytes per microbatch) rides ``dcn``. ``hier=False`` is the flat
    control: one scatter group spans the pod, and every byte is
    attributed to ``dcn`` — a flat collective over slices is DCN-bound,
    which is exactly the pathology the hierarchy removes.
    """
    gdtype, _ = _resolve_gather_dtype(gather_dtype)
    b4 = layout.padded_total * 4
    bg = layout.padded_total * jnp.dtype(gdtype).itemsize
    k = max(1, grad_accum_steps)

    def _bucket(ar=0, rs=0, ag=0):
        return {
            "all_reduce": int(ar), "reduce_scatter": int(rs),
            "all_gather": int(ag), "total": int(ar) + int(rs) + int(ag),
        }

    if dcn <= 1:
        frac = (world - 1) / max(1, world)
        if gspmd:
            return _bucket(ar=int(2 * frac * b4) * k, ag=int(frac * bg))
        return _bucket(rs=int(frac * b4) * k, ag=int(frac * bg))

    n = world * dcn
    if gspmd or not hier:
        # One flat group spans the slices: all of it crosses DCN.
        frac = (n - 1) / n
        if gspmd:
            dcn_b = _bucket(ar=int(2 * frac * b4) * k, ag=int(frac * bg))
        else:
            dcn_b = _bucket(rs=int(frac * b4) * k, ag=int(frac * bg))
        ici_b = _bucket()
    else:
        ifrac = (world - 1) / world
        dfrac = (dcn - 1) / dcn
        ici_b = _bucket(rs=int(ifrac * b4) * k, ag=int(ifrac * bg))
        dcn_b = _bucket(ar=int(2 * dfrac * (b4 // world)) * k)
    out = {
        key: ici_b[key] + dcn_b[key]
        for key in ("all_reduce", "reduce_scatter", "all_gather", "total")
    }
    out["by_axis"] = {"ici": ici_b, "dcn": dcn_b}
    return out


def opt_bytes_per_device(opt_state) -> int:
    """Optimizer-state memory high-water: max over devices of the
    bytes the state's live buffers actually hold there (per-shard
    accounting over the arrays' real shardings — replicated leaves
    count in full on every device, data-sharded flats count 1/N).
    One convention, one definition: the accounting itself lives in
    ``obs/xprof.max_device_buffer_bytes`` (shared with the device-
    memory sampler's live-buffer fallback)."""
    from ddp_tpu.obs.xprof import max_device_buffer_bytes

    return max_device_buffer_bytes(jax.tree_util.tree_leaves(opt_state))
