"""Flash-decode: single-query attention over SlotCache key lanes.

The serving hot path (ROADMAP item 2): every engine step runs S
single-token queries against S cache lanes of up to ``total_len`` keys
— ``ops/flash.py`` only covers training shapes (many queries per
sequence), so until now decode paid a dense ``[S, H_kv, G, L]`` logits
tensor through XLA every step. This module is the decode-shaped
sibling:

- :func:`decode_attention_reference` — the jnp fallback, EXACTLY the
  einsum math ``models/generate.slot_decode_step`` always ran (same
  contraction strings, same fp32 casts, same ``-inf`` masking), pulled
  out so the kernel has a bit-identical baseline to pin against and
  non-TPU platforms keep the PR-3 numerics unchanged.
- :func:`flash_decode_attention` — a Pallas TPU kernel on a
  ``(S, L/block_k)`` grid that reads the cache IN ITS STORED LAYOUT
  ``[depth, S, L, H_kv, Dh]``: the index maps pick the layer and the
  lane, a grid step streams ``block_k`` rows of all kv heads of one
  lane (``[block_k, H_kv, Dh]``, 1 MB of K and of V at block_k 128 x
  16 heads x 128 fp32) through VMEM under the online-softmax
  recurrence (fp32 scratch persisting across the innermost grid dim,
  flushed on its last iteration — the ``ops/flash.py`` scheme). No
  slice, transpose or copy of a layer's lanes surrounds the call. The
  **banded read honors per-slot positions**: key rows past ``pos[s]``
  are masked; blocks that start past ``pos[s]`` are neither fetched
  (:func:`live_block` clamps the block index with the scalar-prefetched
  position, and Pallas issues no DMA for a repeated index) nor
  computed (``pl.when``), so a young lane in a long cache pays O(pos)
  HBM traffic and compute, not O(total_len). No [T, S]-style score
  tensor ever exists; per-step HBM traffic of the attention is each
  lane's live K/V blocks, once.
- **int8 KV dequantize-in-kernel**: when the cache stores int8 K/V
  with per-(position, head) scales (:func:`quantize_kv`), both paths
  dequantize at the compute site — the kernel widens int8 blocks in
  VMEM, so HBM reads stay quarter-width (the whole point of
  quantizing: decode is cache-bandwidth bound).
- :func:`packed_decode_attention`, :func:`diff_decode_attention` — rows
  stored with kv heads packed on lanes (head sizes under 128) go
  through a ``flash_decode`` call of ONE grid step a lane whose kernel
  walks the lane's live rows itself (``_walk_kernel``: double-buffered
  copies it starts, :func:`fetched_rows` of them, the same absorbs):
  a lane costs by the rows it holds, not by its length.
- :func:`index_scores`, :func:`select_rows`,
  :func:`latent_decode_attention` — decode over a LATENT cache whose
  keys a learned indexer selects (models/glm_dsa.py): a step first
  scores every live row of a lane against the lane's query
  (``sum_j w_j relu(q_j . k)`` over the indexer's heads), takes the
  ``top_k`` best (all of them while the lane is young), then attends
  those rows alone: all heads against ONE shared row of latent + rope
  key, the value the row's latent part (the up-projections absorbed
  into the query and the output by the caller). Plain ``jnp``: the
  rows are gathered by XLA, no kernel yet.
- :func:`shard_decode_attention` — mesh composition: the compiled
  Mosaic call has no partitioning rule (same wall as
  ``ops/attention.gspmd_flash_attention``), so TP serving routes the
  kernel through a ``shard_map`` island over the ``model`` axis —
  whole kv-head groups per shard, matching the Megatron head layout
  the qkv kernels already use.

Decode is a forward-only surface: no custom VJP here (generation
never differentiates), which keeps the kernel a single
``pallas_call``.

``interpret=True`` (automatic off-TPU) runs the same program through
the Pallas interpreter — how the CPU test suite pins token identity
against the reference across every prefill bucket edge
(tests/test_flash_decode.py); online-softmax reassociation can move
logits by ~1 ulp, so the pins are engine-level token streams plus
elementwise tolerance, the same contract ops/flash.py tests use.
"""

from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
from jax import lax

from ddp_tpu.obs.tracer import get_tracer, importing

with importing("jax.experimental.pallas"):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
from ddp_tpu.ops.flash import pick_block

# Per-row stats ride broadcast across the minor 128-lane dim (the
# ops/flash.py layout convention — [.., 1] would be lane-padded in
# VMEM anyway and 2-D one-row blocks are not tileable).
LANES = 128

# KV rows streamed per grid step unless the caller asks otherwise.
DEFAULT_BLOCK_K = 128

# A grid step holds ``block_k`` rows of ALL kv heads: K and V blocks,
# double-buffered, beside the block's fp32 temporaries. 2 MiB of fp32
# rows a block (256 rows of 16 heads x 128) is what a v5e's 16 MiB of
# scoped VMEM compiles; 512 such rows do not.
_MAX_BLOCK_BYTES = 2 << 20

# int8 quantization range: symmetric, NaN-free at zero rows (the amax
# floor below keeps the scale strictly positive).
_INT8_MAX = 127.0
_AMAX_FLOOR = 1e-8


# ---- int8 KV quantization -------------------------------------------


def quantize_kv(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """[..., H_kv, Dh] float K/V → (int8 rows, per-head fp32 scales).

    Symmetric per-(position, head) scaling: ``scale = amax/127`` over
    the head_dim so each head row dequantizes as ``int8 · scale``.
    Scale shape is the input's without its trailing dim. The amax
    floor keeps all-zero rows (unwritten cache lines) exact zeros
    after round-trip rather than NaN.
    """
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
    scale = jnp.maximum(amax, _AMAX_FLOOR) / _INT8_MAX
    q = jnp.clip(
        jnp.round(x.astype(jnp.float32) / scale[..., None]),
        -_INT8_MAX,
        _INT8_MAX,
    ).astype(jnp.int8)
    return q, scale.astype(jnp.float32)


def dequantize_kv(q: jax.Array, scale: jax.Array) -> jax.Array:
    """Inverse of :func:`quantize_kv` → fp32 rows."""
    return q.astype(jnp.float32) * scale[..., None].astype(jnp.float32)


def _maybe_dequant(x, scale):
    if x.dtype == jnp.int8:
        return dequantize_kv(x, scale)
    return x


# ---- jnp reference (the PR-3 decode math, verbatim) ------------------


def decode_attention_reference(q, k, v, pos, k_scale=None, v_scale=None,
                               *, scale: float | None = None):
    """Single-query banded attention → [S, H, Dh] fp32.

    ``q``: [S, H, Dh] (one query per lane); ``k``/``v``: [S, L, H_kv,
    Dh] cache lanes (fp32/bf16, or int8 with ``k_scale``/``v_scale``
    [S, L, H_kv]); ``pos``: [S] int32 — lane s attends keys at
    positions ``<= pos[s]``. GQA grouping, contraction order, fp32
    casts and the ``-inf`` mask are EXACTLY ``slot_decode_step``'s
    original inline math, so the fp32 path is bit-identical to the
    PR-3 engine (the token-identity baseline the kernel pins against).
    ``scale`` multiplies the logits; None is ``Dh**-0.5`` (a model
    whose softmax scale is its own passes it, both paths alike).
    """
    S, H, Dh = q.shape
    L, H_kv = k.shape[1], k.shape[2]
    G = H // H_kv
    kf = _maybe_dequant(k, k_scale)
    vf = _maybe_dequant(v, v_scale)
    qg = q.reshape(S, H_kv, G, Dh)
    logits = (
        jnp.einsum(
            "bkgd,blkd->bkgl",
            qg.astype(jnp.float32),
            kf.astype(jnp.float32),
        )
        * (Dh**-0.5 if scale is None else scale)
    )  # [S, H_kv, G, L]
    live = (jnp.arange(L)[None, :] <= pos[:, None])[:, None, None, :]
    logits = jnp.where(live, logits, -jnp.inf)
    w = jax.nn.softmax(logits, axis=-1)
    attn = jnp.einsum("bkgl,blkd->bkgd", w, vf.astype(jnp.float32))
    return attn.reshape(S, H, Dh)


# ---- the Pallas kernel ----------------------------------------------


def decode_block(
    L: int, H_kv: int, Dh: int, dtype, block_k: int = DEFAULT_BLOCK_K
) -> int:
    """K/V rows one grid step of the kernel streams from a length-``L``
    lane: ``ops.flash.pick_block`` of ``block_k``, capped so that a
    block of all kv heads fits VMEM (``_MAX_BLOCK_BYTES``) — never one
    full-length block for a long lane (that would defeat the banded
    read). Raises, with the shape named, when ``L`` has no tile-aligned
    divisor; the engine asks at construction."""
    cap = max(32, _MAX_BLOCK_BYTES // (H_kv * Dh * 4))
    return pick_block(L, min(block_k, cap), dtype)


def live_block(j, pos, block_k: int):
    """The K/V block grid step ``j`` of a lane at ``pos`` is given.

    Blocks up to ``pos // block_k`` hold attendable keys; every later
    step repeats that last live index. Pallas fetches a block only
    when its index differs from the previous grid step's, so a dead
    step costs no DMA (and ``pl.when`` skips its compute): the lane
    read is O(pos). A plain function of its arguments — the index
    maps below call it on the scalar-prefetched position, the tests on
    integers.
    """
    return jnp.minimum(j, pos // block_k)


def flash_decode_attention(
    q,
    k,
    v,
    pos,
    k_scale=None,
    v_scale=None,
    *,
    layer: int = 0,
    block_k: int = DEFAULT_BLOCK_K,
    interpret: bool | None = None,
    scale: float | None = None,
):
    """Pallas flash-decode → [S, H, Dh] fp32 (the reference's contract).

    ``k``/``v`` are the STORED cache, ``[depth, S, L, H_kv, Dh]``
    (scales ``[depth, S, L, H_kv]``), and ``layer`` (Python-static)
    the layer to attend: the index maps pick the layer, so nothing is
    sliced, transposed or copied on the way in — the only cache bytes
    a call moves are the live blocks of that layer, once. One layer's
    ``[S, L, H_kv, Dh]`` lanes (:func:`decode_attention_reference`'s
    operand, the paged gather) are a depth-1 stored cache.

    The grid is ``(S, L/block_k)``; a grid step streams ``block_k``
    rows of ALL kv heads of one lane, ``[block_k, H_kv, Dh]``, in the
    stored layout. What runs on a block adapts to the operands: with
    G = H/H_kv = 1 and float rows the scores are a broadcast multiply
    and a lane reduce over the whole block (full fp32 on the VPU, no
    relayout; a one-row MXU dot per head would waste the array);
    grouped queries and int8 rows take one ``[G, Dh] x [Dh, block_k]``
    dot per kv head on that head's strided rows, int8 widened (and
    scaled) at the compute site.

    ``interpret=None`` auto-detects (compiled Mosaic on TPU, the
    interpreter elsewhere so one engine config runs anywhere). The
    effective block is :func:`decode_block`'s.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if k.ndim == 4:
        k, v = k[None], v[None]
        if k_scale is not None:
            k_scale, v_scale = k_scale[None], v_scale[None]
    S, H, Dh = q.shape
    L, H_kv = k.shape[2], k.shape[3]
    G = H // H_kv
    block_k = decode_block(L, H_kv, Dh, k.dtype, block_k)
    quantized = k.dtype == jnp.int8
    all_heads = G == 1 and not quantized
    vmem = {"memory_space": pltpu.VMEM}

    # The lane position is a scalar the index maps and the kernel
    # branch on, so it rides scalar prefetch into SMEM.
    def kvmap(s, j, pos_ref):
        return (layer, s, live_block(j, pos_ref[s], block_k), 0, 0)

    def scmap(s, j, pos_ref):
        return kvmap(s, j, pos_ref)[:-1]

    # q and the output travel kv-head-major (the engine's qg =
    # q.reshape(S, H_kv, G, Dh) grouping; G = 1 needs no group dim).
    qshape = (H, Dh) if all_heads else (H_kv, G, Dh)
    qspec = pl.BlockSpec(
        (None, *qshape), lambda s, j, pos_ref: (s,) + (0,) * len(qshape),
        **vmem,
    )
    kvspec = pl.BlockSpec((None, None, block_k, H_kv, Dh), kvmap, **vmem)
    in_specs = [qspec, kvspec, kvspec]
    args = [q.reshape(S, *qshape), k, v]
    if quantized:
        scspec = pl.BlockSpec((None, None, block_k, H_kv), scmap, **vmem)
        in_specs += [scspec, scspec]
        args += [k_scale, v_scale]

    out = pl.pallas_call(
        functools.partial(
            _all_heads_kernel if all_heads else _per_head_kernel,
            scale=Dh**-0.5 if scale is None else scale, block_k=block_k,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(S, L // block_k),
            in_specs=in_specs,
            out_specs=qspec,
            scratch_shapes=[
                pltpu.VMEM(qshape, jnp.float32),
                pltpu.VMEM((*qshape[:-1], LANES), jnp.float32),
                pltpu.VMEM((*qshape[:-1], LANES), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((S, *qshape), jnp.float32),
        interpret=interpret,
        name="flash_decode",
    )(pos.astype(jnp.int32), *args)
    return out.reshape(S, H, Dh)


def _online_softmax_grid(pos_ref, o_ref, acc_ref, m_ref, l_ref, block_k, body):
    """The grid-step skeleton both kernels share: init the fp32
    scratch on a lane's first step, run ``body(j, pos)`` on live
    blocks only, flush ``acc / l`` on the last step."""
    j = pl.program_id(1)
    pos = pos_ref[pl.program_id(0)]

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)

    # Banded read: a block whose first key is past the lane position
    # is dead in full (and was not fetched: ``live_block``). Block 0
    # is always live since pos >= 0, and a live block's first key is
    # attendable, so the running max is finite from the first step on.
    @pl.when(j * block_k <= pos)
    def _compute():
        body(j, pos)

    @pl.when(j == pl.num_programs(1) - 1)
    def _flush():
        o_ref[...] = (
            acc_ref[...] / l_ref[...][..., :1]
        ).astype(o_ref.dtype)


def _all_heads_kernel(
    pos_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref,
    *, scale, block_k,
):
    """G = 1, float rows: every head of the block at once, heads on
    sublanes and Dh on lanes as stored; scores stay ``[block_k, H, 1]``
    columns, so softmax statistics reduce over the leading dim."""

    def body(j, pos):
        q = q_ref[...].astype(jnp.float32) * scale  # [H, Dh]
        kb = k_ref[...].astype(jnp.float32)  # [block_k, H, Dh]
        s = jnp.sum(q[None] * kb, axis=-1, keepdims=True)
        rows = j * block_k + lax.broadcasted_iota(jnp.int32, s.shape, 0)
        live = rows <= pos
        s = jnp.where(live, s, -jnp.inf)
        # Rows past ``pos`` are unwritten (or stale): their V must not
        # reach the sum even under a zero weight (0 · NaN).
        vb = jnp.where(live, v_ref[...].astype(jnp.float32), 0.0)
        m = m_ref[...][:, :1]
        new_m = jnp.maximum(m, s.max(axis=0))  # [H, 1]
        p = jnp.exp(s - new_m[None])
        corr = jnp.exp(m - new_m)
        acc_ref[...] = acc_ref[...] * corr + jnp.sum(p * vb, axis=0)
        l_new = l_ref[...][:, :1] * corr + p.sum(axis=0)
        m_ref[...] = jnp.broadcast_to(new_m, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    _online_softmax_grid(pos_ref, o_ref, acc_ref, m_ref, l_ref, block_k, body)


def _absorb_block(i, q, kb, vb, j, pos, block_k, acc_ref, m_ref, l_ref):
    """One online-softmax step of entry ``i`` of the scratch: queries
    ``q`` ``[rows, D]`` (scaled) against block ``j``'s keys ``kb`` and
    values ``vb`` ``[block_k, D]`` on the MXU, keys past ``pos``
    masked."""
    s = lax.dot_general(
        q, kb, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # [rows, block_k]
    cols = j * block_k + lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(cols <= pos, s, -jnp.inf)
    rows = j * block_k + lax.broadcasted_iota(jnp.int32, vb.shape, 0)
    vb = jnp.where(rows <= pos, vb, 0.0)  # see _all_heads_kernel
    m = m_ref[i][:, :1]
    new_m = jnp.maximum(m, s.max(axis=-1, keepdims=True))
    p = jnp.exp(s - new_m)
    corr = jnp.exp(m - new_m)
    acc_ref[i] = acc_ref[i] * corr + lax.dot_general(
        p, vb, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    l_new = l_ref[i][:, :1] * corr + p.sum(axis=-1, keepdims=True)
    m_ref[i] = jnp.broadcast_to(new_m, m_ref.shape[1:])
    l_ref[i] = jnp.broadcast_to(l_new, l_ref.shape[1:])


def _per_head_kernel(pos_ref, q_ref, k_ref, v_ref, *rest, scale, block_k):
    """Grouped queries and/or int8 rows: per kv head, that head's
    ``[block_k, Dh]`` rows (a strided read of the stored block) against
    its G queries on the MXU. ``rest`` is ``[ksc_ref, vsc_ref,] o_ref,
    acc_ref, m_ref, l_ref`` — scale planes only on an int8 cache."""
    *scales, o_ref, acc_ref, m_ref, l_ref = rest

    def body(j, pos):
        for h in range(k_ref.shape[1]):
            kb = k_ref[:, h, :].astype(jnp.float32)  # [block_k, Dh]
            vb = v_ref[:, h, :].astype(jnp.float32)
            if scales:
                # int8 rows widen at the compute site: HBM traffic for
                # the lane read stays quarter-width.
                kb = kb * scales[0][:, h : h + 1]
                vb = vb * scales[1][:, h : h + 1]
            q = q_ref[h].astype(jnp.float32) * scale  # [G, Dh]
            _absorb_block(h, q, kb, vb, j, pos, block_k,
                          acc_ref, m_ref, l_ref)

    _online_softmax_grid(pos_ref, o_ref, acc_ref, m_ref, l_ref, block_k, body)


# ---- heads packed on lanes: head sizes under 128 -----------------------
#
# A stored ``[.., H_kv, Dh]`` cache with ``Dh`` < 128 has no good TPU
# layout: the tiled layout pads its minor dimension to 128 lanes (twice
# the bytes at Dh 64), and XLA avoids that by storing the buffer
# transposed, then relayouts the WHOLE cache into and out of the
# kernel's row-major operand every step. So a model with such heads
# (models/granite_hybrid.py: 8 kv heads of 64) stores its rows
# ``[depth, S, L, H_kv * Dh]``, all kv heads of a position side by side
# on lanes: no padding, row-major as stored. A 128-lane group then
# holds ``P = 128 // Dh`` kv heads, and the kernel never slices inside
# it: the P heads' queries arrive block-diagonal, ``[P * G, 128]`` with
# head p's G queries in lanes ``[p Dh, (p + 1) Dh)`` and zeros
# elsewhere, so ONE ``[P G, 128] x [128, block_k]`` dot gives every
# head's scores against its own keys (the zeros cancel the neighbour's),
# and ``p @ V`` gives ``[P G, 128]`` whose diagonal blocks are the
# heads' outputs. The MXU multiplies P times the zeros; at a few rows a
# dot that is nothing beside the block's DMA.
#
# This call's grid is ``(S,)``: one step a lane, and the kernel walks the
# lane's live rows itself (``_walk_kernel``). On a grid of ``(S, L //
# block_k)`` a dead step fetched and computed nothing yet cost its
# ~0.3 us, 21 of a lane's 32 at 1,350 rows of 4,096: a third of the
# call. ``flash_decode_attention`` above keeps the grid skeleton.


def _pack_queries(q, H_kv: int):
    """``[S, H, Dh]`` -> block-diagonal ``[S, C, P * G, 128]``."""
    S, H, Dh = q.shape
    P = LANES // Dh
    eye = jnp.eye(P, dtype=q.dtype)
    qg = q.reshape(S, H_kv // P, P, H // H_kv, Dh)
    return jnp.einsum("scpgd,pq->scpgqd", qg, eye).reshape(
        S, H_kv // P, P * (H // H_kv), LANES)


def _unpack_outputs(o, H: int, Dh: int):
    """The diagonal blocks of ``[S, C, P * G, 128]`` -> ``[S, H, Dh]``."""
    S, C, R, _ = o.shape
    P = LANES // Dh
    eye = jnp.eye(P, dtype=o.dtype)
    return jnp.einsum(
        "scpgqd,pq->scpgd", o.reshape(S, C, P, R // P, P, Dh), eye
    ).reshape(S, H, Dh)


def copy_rows(L: int, W: int, block_k: int) -> int:
    """Rows one copy of the walk holds: whole absorbs of ``block_k``
    rows, as many as tile the lane in EQUAL copies under the VMEM cap
    :func:`decode_block` applies (``_MAX_BLOCK_BYTES`` of float32 a
    stream and slot): 256 rows of 1,280 lanes in lanes of 512 or 4,096,
    1,024 rows of 512 lanes. A copy is awaited whole and absorbed while
    the next one flies, so copies of one size keep the two in step: a
    full ring of 512 rows read as 384 + 128 took 0.664 ms a call on a
    v5e where 256 + 256 took 0.466 (``scripts/check_kernels.py
    --time``)."""
    cap = min(L, max(32, _MAX_BLOCK_BYTES // (W * 4)))
    return max(n for n in range(block_k, cap + 1, block_k) if L % n == 0)


def fetched_rows(pos, L: int, block_k: int = DEFAULT_BLOCK_K):
    """Rows of a lane at ``pos`` the walk copies out of HBM: its live
    blocks of ``block_k`` rows (the absorb's), whatever the lane's
    length or the copy's. A plain function of its arguments, as
    :func:`live_block`: the kernel sizes its copies with it on the
    scalar-prefetched position, a reader sets it beside the rows
    attended (``pos + 1``) to get the kernel's waste. Cut to the live
    rows' 8-row tiles a lane's last copy took 4% fewer bytes and 0.2%
    less time on a v5e (the call is bound by its absorbs, not its
    bytes) and three times the DMAs to trace and lower: dropped."""
    return (jnp.clip(pos, 0, L - 1) // block_k + 1) * block_k


def _walk_kernel(pos_ref, q_ref, k_hbm, v_hbm, o_ref, kbuf, vbuf, sems,
                 turn_ref, acc_ref, m_ref, l_ref, *, scale, block_k):
    """One grid step a lane: the lane's live rows come through two
    VMEM slots a stream in copies this kernel starts itself, and each
    ``block_k`` rows of a landed copy are absorbed per 128-lane group
    (its P kv heads' queries, block-diagonal, against the group's
    ``[block_k, 128]`` keys), blocks in ascending order.

    A copy's length is known only here (the lane's position), and a
    DMA's is static: a copy of ``n`` blocks is the binary pieces of
    ``n``, each started and awaited under the bit that calls for it.
    While a lane's last copy is awaited and absorbed the NEXT lane's
    first flies into the other slot (scratch, semaphores and the
    slot's turn persist across grid steps), so only lane 0 waits
    exposed."""
    s = pl.program_id(0)
    S = pl.num_programs(0)
    layer = pos_ref[pos_ref.shape[0] - 1]  # behind the S positions
    L = k_hbm.shape[2]
    rows = kbuf.shape[1]  # a copy's
    blocks = rows // block_k
    bits = range(blocks.bit_length() - 1, -1, -1)

    def copies(lane, c, slot, act):
        """``act`` on every DMA of copy ``c`` of ``lane``."""
        left = fetched_rows(pos_ref[lane], L, block_k) // block_k - c * blocks
        n = jnp.minimum(left, blocks)
        for b in bits:
            @pl.when((n >> b) & 1 == 1)
            def _piece(b=b):
                at = pl.multiple_of(((n >> (b + 1)) << (b + 1)) * block_k,
                                    block_k)
                size = block_k << b
                for x, (hbm, buf) in enumerate(((k_hbm, kbuf), (v_hbm, vbuf))):
                    act(pltpu.make_async_copy(
                        hbm.at[layer, lane, pl.ds(c * rows + at, size)],
                        buf.at[slot, pl.ds(at, size)], sems.at[x, slot]))

    start = lambda dma: dma.start()
    wait = lambda dma: dma.wait()

    @pl.when(s == 0)
    def _first():
        turn_ref[0] = 0
        copies(0, 0, 0, start)

    pos = pos_ref[s]
    last = jnp.clip(pos, 0, L - 1)
    n_copies = last // rows + 1
    turn = turn_ref[0]
    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
    l_ref[...] = jnp.zeros_like(l_ref)

    def walk(c, _):
        slot = (turn + c) % 2
        more = c + 1 < n_copies  # of this lane; else the next lane's first

        @pl.when(more | (s + 1 < S))
        def _ahead():
            copies(jnp.where(more, s, s + 1), jnp.where(more, c + 1, 0),
                   1 - slot, start)

        copies(s, c, slot, wait)
        first = c * blocks

        def absorb(i, _):
            at = pl.multiple_of(i * block_k, block_k)
            for g in range(q_ref.shape[0]):
                lanes = slice(g * LANES, (g + 1) * LANES)
                kb = kbuf[slot, pl.ds(at, block_k), lanes].astype(jnp.float32)
                vb = vbuf[slot, pl.ds(at, block_k), lanes].astype(jnp.float32)
                q = q_ref[g].astype(jnp.float32) * scale  # [P G, 128]
                # A slot's blocks past the lane's last live one were
                # not copied (they are another lane's): never absorbed.
                _absorb_block(g, q, kb, vb, first + i, pos, block_k,
                              acc_ref, m_ref, l_ref)

        lax.fori_loop(
            0, jnp.minimum(last // block_k + 1 - first, blocks), absorb, None)

    lax.fori_loop(0, n_copies, walk, None)
    turn_ref[0] = (turn + n_copies) % 2
    o_ref[...] = (acc_ref[...] / l_ref[...][..., :1]).astype(o_ref.dtype)


def packed_decode_attention(
    q, k, v, pos, *, layer: int = 0, impl: str = "reference",
    block_k: int = DEFAULT_BLOCK_K, interpret: bool | None = None,
    scale: float | None = None,
):
    """Single-query banded attention over a cache stored with its kv
    heads packed on lanes -> ``[S, H, Dh]`` fp32.

    ``q`` ``[S, H, Dh]``; ``k``/``v`` the STORED cache ``[depth, S, L,
    H_kv * Dh]`` float (``H_kv`` is what the width holds of ``Dh``);
    ``layer`` Python-static; ``pos``, ``scale`` and ``impl`` as
    :func:`decode_attention`'s. ``"reference"`` is
    :func:`decode_attention_reference` on the layer's rows viewed
    ``[S, L, H_kv, Dh]``; ``"flash"`` a ``flash_decode`` call of one
    grid step a lane, the same banded read and online softmax over
    ``[block_k, H_kv * Dh]`` blocks (the note above)."""
    S, H, Dh = q.shape
    L, W = k.shape[2], k.shape[3]
    H_kv = W // Dh
    if impl == "reference":
        rows = lambda c: c[layer].reshape(S, L, H_kv, Dh)
        return decode_attention_reference(q, rows(k), rows(v), pos,
                                          scale=scale)
    if impl != "flash":
        raise ValueError(
            f"unknown decode attention impl {impl!r}: expected "
            "'reference' or 'flash'"
        )
    if LANES % Dh or W % LANES or H % H_kv:
        raise ValueError(
            f"packed flash_decode needs a head size that divides {LANES} "
            f"and rows that are whole {LANES}-lane groups, got Dh {Dh}, "
            f"row width {W}, {H} query heads"
        )
    qp = _pack_queries(q, H_kv)
    out = _packed_call(qp, k, v, pos, layer=layer, block_k=block_k,
                       interpret=interpret,
                       scale=Dh**-0.5 if scale is None else scale)
    return _unpack_outputs(out, H, Dh)


def _packed_call(qp, k, v, pos, *, layer: int, block_k: int,
                 interpret: bool | None, scale: float):
    """The ``flash_decode`` call over rows stored with heads packed on
    lanes: ``qp`` ``[S, C, R, 128]``, R queries for each of the C
    128-lane groups of a stored row, zero outside the lanes of the head
    a query reads -> ``[S, C, R, 128]``, each query's softmax over its
    scores applied to the group's whole 128 lanes of V.

    The grid is ``(S,)``: K and V stay where they lie in HBM and
    :func:`_walk_kernel` copies a lane's :func:`fetched_rows` itself,
    :func:`copy_rows` at a time, so a lane costs by the rows it holds
    and not by its length. ``block_k`` is the rows an absorb takes
    (:func:`decode_block`'s). Each traced call leaves a ``decode.plan``
    record in the tracer's ring (trace time: a compiled step leaves
    none)."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    L, W = k.shape[2], k.shape[3]
    block_k = decode_block(L, 1, W, k.dtype, block_k)
    rows = copy_rows(L, W, block_k)
    get_tracer().complete(
        "decode.plan", time.perf_counter(), 0.0,
        nums=("walk", 1, block_k, rows, 2, W, L, 0),
    )
    # The layer rides scalar prefetch behind the positions, so the
    # reading layers of one stored buffer are ONE program: a step's 8
    # ring calls (and its 8 calls on the shared rows) are traced,
    # lowered and compiled once, not once a layer.
    at = jnp.concatenate([pos.astype(jnp.int32), jnp.full((1,), layer, jnp.int32)])
    return _walk_call(at, qp, k, v, block_k=block_k, rows=rows,
                      interpret=interpret, scale=scale)


@functools.partial(
    jax.jit, static_argnames=("block_k", "rows", "interpret", "scale"))
def _walk_call(at, qp, k, v, *, block_k, rows, interpret, scale):
    """:func:`_packed_call`'s ``pallas_call``; ``at`` is the lanes'
    positions and, last, the layer to read."""
    S, C, R, _ = qp.shape
    W = k.shape[3]
    qspec = pl.BlockSpec(
        (None, C, R, LANES), lambda s, at_ref: (s, 0, 0, 0),
        memory_space=pltpu.VMEM)
    rows_in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    return pl.pallas_call(
        functools.partial(_walk_kernel, scale=scale, block_k=block_k),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(S,),
            in_specs=[qspec, rows_in_hbm, rows_in_hbm],
            out_specs=qspec,
            scratch_shapes=[
                pltpu.VMEM((2, rows, W), k.dtype),
                pltpu.VMEM((2, rows, W), v.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),  # [stream, slot]
                pltpu.SMEM((1,), jnp.int32),  # the slot a lane starts in
                *[pltpu.VMEM((C, R, LANES), jnp.float32)] * 3,
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((S, C, R, LANES), jnp.float32),
        # a lane hands the next its first copy and the slot's turn
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="flash_decode",
    )(at, qp, k, v)


# ---- differential attention: two softmax maps a head pair -----------------
#
# Adjacent heads pair. Query pair p is heads (2p, 2p + 1) = (q1, q2); kv
# pair g is (k1, k2) and (v1, v2), and ``V_g = [v1 | v2]`` is ``2 Dh``
# wide; query pairs ``2g, 2g + 1`` read kv pair g. A pair's two maps are
# ``a1 = softmax(q1 k1^T) V_g`` and ``a2 = softmax(q2 k2^T) V_g``; what
# is done with them (the subtraction, the norm) is the model's. With
# ``Dh`` 64 a kv pair is one 128-lane group of a stored row
# ``[.., H_kv * Dh]``, ``[k1 | k2]`` as it lies, and the four queries of
# the group, ``[q1 | 0]`` and ``[0 | q2]`` of either query pair, are four
# rows of ``_packed_call``'s block-diagonal operand: the zeros cancel the
# other key of the pair, and ``p @ V`` is already the 128-wide value.


def diff_decode_attention_reference(q, k, v, pos, *, scale: float | None = None):
    """``q`` ``[S, H, Dh]``, ``k``/``v`` one layer's rows ``[S, L,
    H_kv * Dh]``, ``pos`` ``[S]``: lane s attends rows ``<= pos[s]`` ->
    ``[S, H // 2, 2, 2 * Dh]`` float32, ``[.., 0, :]`` a pair's ``a1``
    and ``[.., 1, :]`` its ``a2``. The two maps of a pair are formed
    separately, in :func:`decode_attention_reference`'s arithmetic."""
    S, H, Dh = q.shape
    L = k.shape[1]
    G = k.shape[2] // (2 * Dh)  # kv pairs
    J = H // (2 * G)  # query pairs a kv pair
    qg = q.reshape(S, G, J, 2, Dh).astype(jnp.float32)
    kg = k.reshape(S, L, G, 2, Dh).astype(jnp.float32)
    vg = v.reshape(S, L, G, 2 * Dh).astype(jnp.float32)
    logits = jnp.einsum("sgjwd,slgwd->sgjwl", qg, kg) * (
        Dh**-0.5 if scale is None else scale)
    live = (jnp.arange(L)[None, :] <= pos[:, None])[:, None, None, None, :]
    w = jax.nn.softmax(jnp.where(live, logits, -jnp.inf), axis=-1)
    a = jnp.einsum("sgjwl,slge->sgjwe", w, vg)
    return a.reshape(S, H // 2, 2, 2 * Dh)


def diff_decode_attention(q, k, v, pos, *, layer: int = 0,
                          impl: str = "reference",
                          block_k: int = DEFAULT_BLOCK_K,
                          interpret: bool | None = None,
                          scale: float | None = None):
    """The two softmax maps of every head pair, one query a lane, over
    rows stored ``[depth, S, L, H_kv * Dh]`` -> ``[S, H // 2, 2,
    2 * Dh]`` (:func:`diff_decode_attention_reference`'s contract).
    ``pos`` is the last attendable ROW: a full-length lane passes its
    position, a ring of W rows ``min(pos + 1, W) - 1`` (without
    positions the order of rows does not matter to a softmax).
    ``"flash"`` is the ``flash_decode`` call of
    :func:`packed_decode_attention` (``Dh`` 64: a kv pair is a 128-lane
    group)."""
    S, H, Dh = q.shape
    if impl == "auto":
        impl = "flash" if jax.default_backend() == "tpu" else "reference"
    if impl == "reference":
        return diff_decode_attention_reference(q, k[layer], v[layer], pos,
                                               scale=scale)
    if impl != "flash":
        raise ValueError(
            f"unknown decode attention impl {impl!r}: expected "
            "'auto', 'reference' or 'flash'"
        )
    W = k.shape[3]
    G = W // LANES
    if 2 * Dh != LANES or W % LANES or H % (4 * G):
        raise ValueError(
            f"differential flash_decode needs head pairs of {LANES} lanes "
            f"and two query pairs a kv pair, got Dh {Dh}, row width {W}, "
            f"{H} query heads"
        )
    J = H // G  # queries a kv pair
    qg = q.reshape(S, G, J // 2, 2, Dh)
    eye = jnp.eye(2, dtype=q.dtype)
    qp = jnp.einsum("sgjwd,wx->sgjwxd", qg, eye).reshape(S, G, J, LANES)
    out = _packed_call(qp, k, v, pos, layer=layer, block_k=block_k,
                       interpret=interpret,
                       scale=Dh**-0.5 if scale is None else scale)
    return out.reshape(S, H // 2, 2, LANES)


def _copy_kernel(slot_ref, src_ref, dst_ref):
    del slot_ref
    dst_ref[...] = src_ref[...]


def read_lane(buf, layer: int, slot, *, impl: str = "auto",
              interpret: bool | None = None):
    """Lane ``slot`` (traced) of ``layer`` (static) of a stored
    ``[depth, S, L, W]`` buffer -> ``[L, W]``.

    On the chip a ``pallas_call`` named ``read_lane`` whose index map
    picks the lane: the buffer is then an operand with a fixed
    row-major layout. Cut out with ``lax.dynamic_slice`` and reshaped
    to heads, XLA's layout assignment makes that reshape free by
    re-laying the WHOLE donated buffer out around it (a 1 GB copy in
    and out of a prefill chunk at the published size). ``impl``:
    ``"auto"`` | ``"pallas"`` | ``"jnp"`` (the dynamic slice, off the
    chip)."""
    if impl == "auto":
        impl = "pallas" if jax.default_backend() == "tpu" else "jnp"
    L, W = buf.shape[2], buf.shape[3]
    if impl == "jnp":
        return lax.dynamic_slice(buf, (layer, slot, 0, 0), (1, 1, L, W))[0, 0]
    if impl != "pallas":
        raise ValueError(f"unknown read_lane impl {impl!r}")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    rows = decode_block(L, 1, W, buf.dtype, 512)
    vmem = {"memory_space": pltpu.VMEM}
    return pl.pallas_call(
        _copy_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(L // rows,),
            in_specs=[pl.BlockSpec(
                (None, None, rows, W),
                lambda j, slot_ref: (layer, slot_ref[0], j, 0), **vmem)],
            out_specs=pl.BlockSpec(
                (rows, W), lambda j, slot_ref: (j, 0), **vmem),
        ),
        out_shape=jax.ShapeDtypeStruct((L, W), buf.dtype),
        interpret=interpret,
        name="read_lane",
    )(jnp.asarray(slot, jnp.int32).reshape(1), buf)


# ---- paged KV: gather lane views through int32 page tables ----------
#
# The paged cache (models/generate.PagedSlotCache, PR 12) stores K/V
# as a POOL of page_size-token blocks shared across lanes; a lane's
# logical [L, H_kv, Dh] view is its page table's gather. Keeping the
# gather here (rather than inline in generate.py) gives both decode
# paths one definition: the jnp reference runs the EXACT fixed-lane
# einsum math over the gathered view (bit-identical off-TPU — the
# token-identity pin), and the flash path streams the gathered lanes
# through the same Pallas kernel (the gathered view is contiguous, so
# the KV block is the fixed-lane one, not the page — a 16-row int8
# page is below Mosaic's 32-row int8 tile). The gather
# itself is one XLA dynamic-gather over int32 ids — static shape
# arithmetic, no host sync (lint TN fixture ddp002_tn.py pins the
# pattern).
#
# Honest cost note: the gather MATERIALIZES the per-lane views before
# the kernel runs, so on this path the gather pays O(total_len) HBM
# traffic per layer per step (a read of the mapped pages and a write of
# the views); the kernel then reads the views' live blocks only, as a
# depth-1 stored cache. The O(pos) paged hot path needs IN-KERNEL table
# indexing (a scalar-prefetch index_map resolving page ids per grid
# step, the vLLM/TPU paged-attention shape) — the on-chip follow-up;
# until then an on-chip capture of paged+flash measures gather +
# kernel.


def gather_paged_kv(pages: jax.Array, table: jax.Array) -> jax.Array:
    """[num_pages, page_size, ...] pool + [S, n] int32 table →
    [S, n·page_size, ...] per-lane views (works for K/V rows AND their
    int8 per-head scale planes — anything page-major)."""
    g = jnp.take(pages, table, axis=0)  # [S, n, page_size, ...]
    S, n, ps = g.shape[:3]
    return g.reshape(S, n * ps, *g.shape[3:])


def paged_decode_attention(
    q, k_pages, v_pages, table, pos, k_scale=None, v_scale=None, *,
    impl: str = "reference", interpret: bool | None = None,
):
    """Single-query banded attention over paged lanes → [S, H, Dh].

    ``k_pages``/``v_pages``: one layer's page pool ([num_pages,
    page_size, H_kv, Dh]); ``table``: [S, n_lane_pages] int32 page ids
    (0 = the engine's scratch page); ``pos``: [S] as in
    :func:`decode_attention_reference`. Semantics are EXACTLY the
    fixed-lane call over the table's gathered view — positions past
    ``pos[s]`` (including every scratch-page line) are masked, so a
    stale or zero table entry above the live region can never leak
    into the softmax. The gather materializes the full lane views
    first (the module's cost note); the flash kernel then takes them
    as a depth-1 stored cache and reads their live blocks.
    """
    k = gather_paged_kv(k_pages, table)
    v = gather_paged_kv(v_pages, table)
    ks = gather_paged_kv(k_scale, table) if k_scale is not None else None
    vs = gather_paged_kv(v_scale, table) if v_scale is not None else None
    return decode_attention(
        q, k, v, pos, ks, vs,
        impl=impl, interpret=interpret,
    )


# ---- runtime selection + mesh composition ---------------------------


def decode_attention(
    q, k, v, pos, k_scale=None, v_scale=None, *,
    impl: str = "reference", layer: int = 0,
    block_k: int = DEFAULT_BLOCK_K, interpret: bool | None = None,
    scale: float | None = None,
):
    """The engine-facing entry: ``impl`` picks the path at trace time.

    ``k``/``v`` (and int8 scales) are either one layer's lanes
    ``[S, L, H_kv, Dh]`` or the stored cache ``[depth, S, L, H_kv,
    Dh]`` with ``layer`` naming the layer to attend — what the decode
    step passes, so the flash path never slices a layer out.

    ``reference`` — the jnp einsum math over ``k[layer]``
    (bit-identical to the PR-3 engine on fp32 caches); ``flash`` — the
    Pallas kernel (compiled Mosaic on TPU, interpreter elsewhere);
    ``auto`` — flash on TPU, reference everywhere else (the serving
    default: off-TPU nothing beats XLA's fused einsums, and the PR-3
    numerics stay untouched).
    """
    if impl == "auto":
        impl = "flash" if jax.default_backend() == "tpu" else "reference"
    if impl == "flash":
        return flash_decode_attention(
            q, k, v, pos, k_scale, v_scale,
            layer=layer, block_k=block_k, interpret=interpret,
            scale=scale,
        )
    if impl != "reference":
        raise ValueError(
            f"unknown decode attention impl {impl!r}: expected "
            "'auto', 'flash' or 'reference'"
        )
    if k.ndim == 5:
        k, v = k[layer], v[layer]
        if k_scale is not None:
            k_scale, v_scale = k_scale[layer], v_scale[layer]
    return decode_attention_reference(q, k, v, pos, k_scale, v_scale,
                                      scale=scale)


def shard_decode_attention(
    mesh, *, impl: str = "auto", block_k: int = DEFAULT_BLOCK_K,
    interpret: bool | None = None,
):
    """Mesh-composable flash-decode: shard_map over the ``model`` axis.

    The compiled Mosaic custom call has no GSPMD partitioning rule
    (the ``ops/attention.gspmd_flash_attention`` wall), so a
    tensor-parallel serving step routes the kernel through a
    ``shard_map`` island: kv heads shard over ``model`` (whole GQA
    groups per shard — the Megatron layout the qkv kernels already
    use, so no resharding at the island boundary), slots/positions
    replicate along it. Falls back to a plain call when the mesh has
    no ``model`` axis > 1 or the kv heads do not divide.

    Returns ``fn(q, k, v, pos, k_scale=None, v_scale=None)``.
    """
    from jax.sharding import PartitionSpec as P

    tp = mesh.shape.get("model", 1)

    def fn(q, k, v, pos, k_scale=None, v_scale=None):
        H_kv = k.shape[2]
        if tp <= 1 or H_kv % tp:
            return decode_attention(
                q, k, v, pos, k_scale, v_scale,
                impl=impl, block_k=block_k, interpret=interpret,
            )
        qspec = P(None, "model", None)
        kvspec = P(None, None, "model", None)
        scspec = P(None, None, "model")
        has_scales = k_scale is not None
        in_specs = (qspec, kvspec, kvspec) + (
            (scspec, scspec) if has_scales else ()
        ) + (P(),)
        args = (q, k, v) + (
            (k_scale, v_scale) if has_scales else ()
        ) + (pos,)

        def island(*a):
            if has_scales:
                qq, kk, vv, ks, vs, pp = a
            else:
                qq, kk, vv, pp = a
                ks = vs = None
            return decode_attention(
                qq, kk, vv, pp, ks, vs,
                impl=impl, block_k=block_k, interpret=interpret,
            )

        return jax.shard_map(
            island,
            mesh=mesh,
            in_specs=in_specs,
            out_specs=qspec,
            check_vma=False,
        )(*args)

    return fn


# ---- a latent cache whose keys an indexer selects ---------------------
#
# models/glm_dsa.py's decode. The cache holds, a layer, ``latent``
# ``[S, L, R + Dr]`` (a position's normed key/value latent beside its
# rotated rope key: ONE row for all heads) and ``index_k`` ``[S, L, Di]``
# (the indexer's key). A step selects before it attends.


@jax.named_scope("dsa_index")
def index_scores(qi, w, ki):
    """The indexer's score of every stored row for each query:
    ``I[..., l] = sum_j w[..., j] * relu(qi[..., j, :] . ki[..., l, :])``.
    ``qi`` ``[..., Hi, Di]``, ``w`` ``[..., Hi]`` float32 (the head
    weights, already scaled), ``ki`` ``[..., L, Di]`` as stored ->
    ``[..., L]`` float32. Operands in the stored rows' dtype, float32
    accumulation, ReLU and sum in float32."""
    dots = jnp.einsum("...hd,...ld->...hl", qi.astype(ki.dtype), ki,
                      preferred_element_type=jnp.float32)
    return jnp.einsum("...hl,...h->...l", jax.nn.relu(dots),
                      w.astype(jnp.float32))


@jax.named_scope("dsa_select")
def select_rows(scores, pos, top_k: int):
    """Each lane's ``top_k`` best rows among its live ones: ``scores``
    ``[S, L]``, ``pos`` ``[S]`` (rows ``0..pos`` are live) -> (rows
    ``[S, K]`` int32, which of them count ``[S, K]`` bool), ``K =
    min(top_k, L)``. Ties go to the lower position; a lane with at most
    ``K`` live rows selects them all and the rest of its ``K`` do not
    count."""
    L = scores.shape[-1]
    live = jnp.arange(L, dtype=jnp.int32)[None, :] <= pos[:, None]
    _, rows = lax.top_k(jnp.where(live, scores, -jnp.inf), min(top_k, L))
    rows = rows.astype(jnp.int32)
    return rows, rows <= pos[:, None]


@jax.named_scope("mla_decode")
def latent_decode_attention(q, latent, rows, counted, *, rank: int,
                            scale: float):
    """Every head's query against the SELECTED rows of its lane: ``q``
    ``[S, H, W]`` (the key up-projection absorbed into its first
    ``rank`` columns, the rotated rope query after, zeros up to the
    stored width), ``latent`` ``[S, L, W]`` as stored (``R + Dr`` padded
    to whole groups of 128 lanes), ``rows``/``counted`` from
    :func:`select_rows` -> ``[S, H, R]`` float32: softmax over the
    counted rows of ``q . row * scale``, times the rows' latent part.
    The value up-projection is the caller's."""
    S, L, W = latent.shape
    flat = latent.reshape(S * L, W)
    at = jnp.arange(S, dtype=jnp.int32)[:, None] * L + rows
    picked = jnp.take(flat, at, axis=0)  # [S, K, W]
    s = jnp.einsum("shw,skw->shk", q.astype(latent.dtype), picked,
                   preferred_element_type=jnp.float32) * scale
    s = jnp.where(counted[:, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("shk,skr->shr", p.astype(latent.dtype),
                      picked[..., :rank],
                      preferred_element_type=jnp.float32)
