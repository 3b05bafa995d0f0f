"""A prefill chunk's attention over a LATENT cache under a per-(query,
key) selection mask (models/glm_dsa.py's third pass) as ONE kernel.

``C`` queries of ``H`` heads attend the stored rows of one lane: a row
is ``[c_kv R | k_rope Dr | zeros]`` for ALL heads, and a head's keys and
values are EXPANDED from it (``k_nope = c_kv @ w_k[h]``, ``v = c_kv @
w_v[h]``). Which (query, key) pairs count is the caller's: an int8 mask
shared by the heads. :func:`masked_walk` is the call, ``latent_prefill``
the kernel's name.

What the kernel holds in VMEM, a grid step a head: the head's queries
``[C, Dn + Dr]``, its two up-projections, the float32 accumulator ``[C,
Dv]`` and the running maximum and sum of every query; and, a block of
``block_k`` keys at a time, the block's stored rows and its ``[C,
block_k]`` mask (copies the kernel starts itself, double-buffered: the
next block's, or the next head's first, flies while this one is
absorbed), the block's keys and values expanded ONCE, and the float32
scores of a tile of ``block_q`` queries. Nothing of size heads x queries
x keys exists anywhere; the output ``[C, H * Dv]`` float32 is written
once. It walks EVERY live block of the lane (the mask says what of it
counts; blocks above ``n_blocks`` are never read), but for a tile of
queries whose every position lies below the block's first key: nothing
there is selected, and leaving it out changes no bit.

The arithmetic is ``glm_dsa.chunk_attention``'s ``attend_block``:
operands in the stored rows' dtype, float32 accumulation, float32
softmax, the probabilities rounded to the operand dtype before they
meet the values. One reassociation beside the online softmax's own: a
head's ``q_nope . k_nope + q_rope . k_rope`` is ONE product over ``Dn +
Dr`` columns, the rope key carried through the expansion by an identity
block of the key up-projection (``bf16 x 1.0`` summed in float32 is the
stored value again), so that the scores' contraction is two whole
128-lane groups and the kernel slices no stored row.

Shares nothing with ``ops/flash.py`` or ``flash_decode``'s bodies: a
mask shared by heads and keys expanded from a latent are needs of this
model alone.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from ddp_tpu.obs.tracer import importing

with importing("jax.experimental.pallas"):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

LANES = 128
NAME = "latent_prefill"
# A pair the mask leaves out scores _NEG; the running maximum starts at
# _FLOOR, above it and below every score, so such a pair's ``exp(s -
# m)`` is 0 whether or not its query has met a counted key yet: what
# ``attend_block``'s second ``where`` is for, at no operation.
_NEG, _FLOOR = -1e30, -1e29
# What the call may hold of a v5e's 128 MiB of VMEM: at the GLM-5
# cell's widths (C 2048, block_k 512, block_q 1024) queries, output and
# projections twice (the pipeline fetches the next head's), two slots of
# rows and mask, accumulator and statistics come to 18 MiB, the tile's
# float32 temporaries to a few more.
_VMEM_BYTES = 64 * 1024 * 1024


def tiles(C: int, n_keys: int, block_q: int, block_k: int, widths) -> bool:
    """Whether Mosaic can tile the call: whole query tiles and key
    blocks (an int8 mask tiles 32 rows at a time) of whole 128-lane
    groups, every width whole groups too."""
    return (C % block_q == 0 and n_keys % block_k == 0
            and block_q % 32 == 0 and block_k % LANES == 0
            and all(w % LANES == 0 for w in widths))


def key_projection(w_k, width: int, rope: int):
    """``w_k`` ``[R, H, Dn]`` -> ``[H, width, Dn + rope]``: a stored row
    ``[c_kv | k_rope | zeros]`` times a head's block is ``[k_nope |
    k_rope]``, the rope key handed through by an identity."""
    R, H, Dn = w_k.shape
    out = jnp.zeros((H, width, Dn + rope), w_k.dtype)
    out = out.at[:, :R, :Dn].set(w_k.transpose(1, 0, 2))
    return out.at[:, R:R + rope, Dn:].set(jnp.eye(rope, dtype=w_k.dtype))


def _kernel(at_ref, q_ref, wk_ref, wv_ref, rows_hbm, mask_hbm, o_ref,
            rbuf, mbuf, sems, k_ref, v_ref, acc_ref, m_ref, l_ref, *,
            scale, rank, block_q):
    """One grid step a head; ``at_ref`` holds the lane, the live blocks
    and each query tile's greatest position."""
    h, H = pl.program_id(0), pl.num_programs(0)
    lane, nb = at_ref[0], at_ref[1]
    B = rbuf.shape[1]
    C = q_ref.shape[0]
    cdt = k_ref.dtype

    def copies(j, slot, act):
        act(pltpu.make_async_copy(
            rows_hbm.at[lane, pl.ds(pl.multiple_of(j * B, B), B)],
            rbuf.at[slot], sems.at[0, slot]))
        act(pltpu.make_async_copy(mask_hbm.at[j], mbuf.at[slot],
                                  sems.at[1, slot]))

    start = lambda dma: dma.start()
    wait = lambda dma: dma.wait()

    @pl.when(h == 0)
    def _first():
        copies(0, 0, start)

    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, _FLOOR)
    l_ref[...] = jnp.zeros_like(l_ref)

    def block(j, _):
        slot = (h * nb + j) % 2
        more = j + 1 < nb  # of this head; else the next head's first

        @pl.when(more | (h + 1 < H))
        def _ahead():
            copies(jnp.where(more, j + 1, 0), 1 - slot, start)

        copies(j, slot, wait)
        rows = rbuf[slot]
        k_ref[...] = jnp.dot(
            rows, wk_ref[...], preferred_element_type=jnp.float32).astype(cdt)
        v_ref[...] = jnp.dot(
            rows[:, :rank], wv_ref[...],
            preferred_element_type=jnp.float32).astype(cdt)

        def tile(t, _):
            @pl.when(j * B <= at_ref[2 + t])
            def _live():
                at = pl.ds(pl.multiple_of(t * block_q, block_q), block_q)
                s = lax.dot_general(
                    q_ref[at, :], k_ref[...], (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32) * scale
                sel = mbuf[slot, at, :].astype(jnp.int32) != 0
                s = jnp.where(sel, s, _NEG)
                m = m_ref[at, :][:, :1]
                m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
                pr = jnp.exp(s - m_new)
                alpha = jnp.exp(m - m_new)
                acc_ref[at, :] = acc_ref[at, :] * alpha + jnp.dot(
                    pr.astype(cdt), v_ref[...],
                    preferred_element_type=jnp.float32)
                l_new = l_ref[at, :][:, :1] * alpha + pr.sum(
                    axis=-1, keepdims=True)
                m_ref[at, :] = jnp.broadcast_to(m_new, (block_q, LANES))
                l_ref[at, :] = jnp.broadcast_to(l_new, (block_q, LANES))

        lax.fori_loop(0, C // block_q, tile, None)

    lax.fori_loop(0, nb, block, None)
    o_ref[...] = acc_ref[...] / l_ref[...][:, :1]


def masked_walk(q, rows, w_k, w_v, mask, n_blocks, q_pos, *, lane=0,
                rope: int, scale: float, block_q: int,
                interpret: bool | None = None):
    """``q`` ``[C, H, Dn + Dr]`` against the first ``n_blocks`` (traced
    or not) blocks of lane ``lane`` (traced or not) of ``rows`` ``[S,
    L, W]`` as stored, under ``mask`` ``[L // block_k, C, block_k]``
    int8 (nonzero: the pair counts), ``q_pos`` ``[C]`` the queries'
    positions (no key above a query's counts); ``w_k`` ``[R, H, Dn]``,
    ``w_v`` ``[R, H, Dv]`` -> ``[C, H * Dv]`` float32. ``interpret``
    None: the Pallas interpreter off a TPU."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    C, H, Dq = q.shape
    block_k = mask.shape[2]
    last = q_pos.astype(jnp.int32).reshape(C // block_q, block_q).max(axis=1)
    live = jnp.clip(jnp.asarray(n_blocks, jnp.int32), 1, mask.shape[0])
    at = jnp.concatenate(
        [jnp.stack([jnp.asarray(lane, jnp.int32), live]), last])
    return _call(
        at, q.reshape(C, H * Dq), key_projection(w_k, rows.shape[2], rope),
        w_v.transpose(1, 0, 2), rows, mask, scale=scale, block_q=block_q,
        interpret=interpret)


@functools.partial(jax.jit,
                   static_argnames=("scale", "block_q", "interpret"))
def _call(at, q, wk, wv, rows, mask, *, scale, block_q, interpret):
    """:func:`masked_walk`'s ``pallas_call``, a jitted function of its
    shapes: a model's layers and its chunk programs of one width are
    ONE program, traced and lowered once."""
    H, W, Dq = wk.shape
    R, Dv = wv.shape[1:]
    C = q.shape[0]
    block_k = mask.shape[2]
    head = lambda width: pl.BlockSpec(
        (C, width), lambda h, at_ref: (0, h), memory_space=pltpu.VMEM)
    weight = lambda *shape: pl.BlockSpec(
        (None, *shape), lambda h, at_ref: (h, 0, 0), memory_space=pltpu.VMEM)
    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    return pl.pallas_call(
        functools.partial(_kernel, scale=scale, rank=R, block_q=block_q),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(H,),
            in_specs=[head(Dq), weight(W, Dq), weight(R, Dv), in_hbm, in_hbm],
            out_specs=head(Dv),
            scratch_shapes=[
                pltpu.VMEM((2, block_k, W), rows.dtype),
                pltpu.VMEM((2, C, block_k), mask.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),  # [rows | mask, slot]
                pltpu.VMEM((block_k, Dq), rows.dtype),
                pltpu.VMEM((block_k, Dv), rows.dtype),
                pltpu.VMEM((C, Dv), jnp.float32),
                pltpu.VMEM((C, LANES), jnp.float32),
                pltpu.VMEM((C, LANES), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((C, H * Dv), jnp.float32),
        # a head hands the next its first copy
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_BYTES),
        interpret=interpret,
        name=NAME,
    )(at, q, wk, wv, rows, mask)
