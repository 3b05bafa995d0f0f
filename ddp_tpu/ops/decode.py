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
  ``(S·H_kv, L/block_k)`` grid: each grid row owns one (slot, kv-head)
  pair's G grouped queries, KV blocks stream through VMEM under the
  online-softmax recurrence (fp32 scratch persisting across the
  innermost grid dim, flushed on its last iteration — the
  ``ops/flash.py`` scheme), and the **banded read honors per-slot
  positions**: key columns past ``pos[s]`` are masked, and whole
  blocks that start past ``pos[s]`` are ``pl.when``-skipped, so a
  young lane in a long cache pays O(pos) compute, not O(total_len).
  No [T, S]-style score tensor ever exists; per-step HBM traffic is
  the K/V lanes once.
- **int8 KV dequantize-in-kernel**: when the cache stores int8 K/V
  with per-(position, head) scales (:func:`quantize_kv`), both paths
  dequantize at the compute site — the kernel widens int8 blocks in
  VMEM, so HBM reads stay half-width (the whole point of quantizing:
  decode is cache-bandwidth bound).
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

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ddp_tpu.ops.flash import pick_block

# Per-row stats ride broadcast across the minor 128-lane dim (the
# ops/flash.py layout convention — [.., 1] would be lane-padded in
# VMEM anyway and 2-D one-row blocks are not tileable).
LANES = 128

# KV rows streamed per grid step unless the caller asks otherwise.
DEFAULT_BLOCK_K = 128

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


def decode_attention_reference(q, k, v, pos, k_scale=None, v_scale=None):
    """Single-query banded attention → [S, H, Dh] fp32.

    ``q``: [S, H, Dh] (one query per lane); ``k``/``v``: [S, L, H_kv,
    Dh] cache lanes (fp32/bf16, or int8 with ``k_scale``/``v_scale``
    [S, L, H_kv]); ``pos``: [S] int32 — lane s attends keys at
    positions ``<= pos[s]``. GQA grouping, contraction order, fp32
    casts and the ``-inf`` mask are EXACTLY ``slot_decode_step``'s
    original inline math, so the fp32 path is bit-identical to the
    PR-3 engine (the token-identity baseline the kernel pins against).
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
        * Dh**-0.5
    )  # [S, H_kv, G, L]
    live = (jnp.arange(L)[None, :] <= pos[:, None])[:, None, None, :]
    logits = jnp.where(live, logits, -jnp.inf)
    w = jax.nn.softmax(logits, axis=-1)
    attn = jnp.einsum("bkgl,blkd->bkgd", w, vf.astype(jnp.float32))
    return attn.reshape(S, H, Dh)


# ---- the Pallas kernel ----------------------------------------------


def flash_decode_attention(
    q,
    k,
    v,
    pos,
    k_scale=None,
    v_scale=None,
    *,
    block_k: int = DEFAULT_BLOCK_K,
    interpret: bool | None = None,
):
    """Pallas flash-decode → [S, H, Dh] fp32 (the reference's contract).

    Same signature/semantics as :func:`decode_attention_reference`;
    ``interpret=None`` auto-detects (compiled Mosaic on TPU, the
    interpreter elsewhere so one engine config runs anywhere). The
    effective KV block is ``ops.flash.pick_block(L, block_k, k.dtype)``
    — tile-aligned, never one full-length block for a long lane (that
    would defeat the ``pl.when`` dead-block skip that makes young
    lanes O(pos)); a lane length with no such block raises.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    S, H, Dh = q.shape
    L, H_kv = k.shape[1], k.shape[2]
    G = H // H_kv
    block_k = pick_block(L, block_k, k.dtype)
    quantized = k.dtype == jnp.int8
    # One grid row per (slot, kv-head): q regrouped kv-head-major
    # (exactly the engine's qg = q.reshape(S, H_kv, G, Dh) grouping),
    # K/V lanes transposed so each row streams [L, Dh] blocks.
    qt = q.reshape(S * H_kv, G, Dh)
    kt = k.transpose(0, 2, 1, 3).reshape(S * H_kv, L, Dh)
    vt = v.transpose(0, 2, 1, 3).reshape(S * H_kv, L, Dh)
    # Per-row lane position: a scalar the kernel branches on, so it
    # rides scalar prefetch into SMEM (Mosaic reads no scalars out of
    # VMEM blocks).
    pos_rows = jnp.repeat(pos.astype(jnp.int32), H_kv)  # [S·H_kv]
    vmem = {"memory_space": pltpu.VMEM}
    qmap = lambda b, j, pos_ref: (b, 0, 0)
    kmap = lambda b, j, pos_ref: (b, j, 0)
    in_specs = [
        pl.BlockSpec((1, G, Dh), qmap, **vmem),
        pl.BlockSpec((1, block_k, Dh), kmap, **vmem),
        pl.BlockSpec((1, block_k, Dh), kmap, **vmem),
    ]
    args = [qt, kt, vt]
    if quantized:
        ksc = k_scale.transpose(0, 2, 1).reshape(S * H_kv, L, 1)
        vsc = v_scale.transpose(0, 2, 1).reshape(S * H_kv, L, 1)
        in_specs += [
            pl.BlockSpec((1, block_k, 1), kmap, **vmem),
            pl.BlockSpec((1, block_k, 1), kmap, **vmem),
        ]
        args += [ksc.astype(jnp.float32), vsc.astype(jnp.float32)]

    out = pl.pallas_call(
        functools.partial(
            _quantized_kernel if quantized else _plain_kernel,
            scale=Dh**-0.5, block_k=block_k,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(S * H_kv, L // block_k),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, G, Dh), qmap, **vmem),
            scratch_shapes=[
                pltpu.VMEM((G, Dh), jnp.float32),
                pltpu.VMEM((G, LANES), jnp.float32),
                pltpu.VMEM((G, LANES), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((S * H_kv, G, Dh), jnp.float32),
        interpret=interpret,
        name="flash_decode",
    )(pos_rows, *args)
    return out.reshape(S, H, Dh)


def _plain_kernel(
    pos_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref,
    *, scale, block_k,
):
    _decode_body(
        pos_ref, q_ref, k_ref, v_ref, None, None, o_ref,
        acc_ref, m_ref, l_ref, scale=scale, block_k=block_k,
    )


def _quantized_kernel(
    pos_ref, q_ref, k_ref, v_ref, ksc_ref, vsc_ref, o_ref,
    acc_ref, m_ref, l_ref, *, scale, block_k,
):
    _decode_body(
        pos_ref, q_ref, k_ref, v_ref, ksc_ref, vsc_ref, o_ref,
        acc_ref, m_ref, l_ref, scale=scale, block_k=block_k,
    )


def _decode_body(
    pos_ref, q_ref, k_ref, v_ref, ksc_ref, vsc_ref, o_ref,
    acc_ref, m_ref, l_ref, *, scale, block_k,
):
    """Shared online-softmax body; ``pos_ref`` is the scalar-prefetched
    [S·H_kv] position vector (SMEM)."""
    j = pl.program_id(1)
    n_kb = pl.num_programs(1)
    pos = pos_ref[pl.program_id(0)]

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)

    # Banded read: a block whose first key is past the lane position
    # is dead in full — skip its MXU work entirely (block 0 is always
    # live since pos >= 0, so the denominator can never be empty).
    @pl.when(j * block_k <= pos)
    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale  # [G, Dh]
        kb = k_ref[0].astype(jnp.float32)  # [block_k, Dh]
        vb = v_ref[0].astype(jnp.float32)
        if ksc_ref is not None:
            # int8 rows widen at the compute site: HBM traffic for
            # the lane read stays half-width.
            kb = kb * ksc_ref[0][:, :1]
            vb = vb * vsc_ref[0][:, :1]
        s = lax.dot_general(
            q, kb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [G, block_k]
        cols = j * block_k + lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(cols <= pos, s, -jnp.inf)
        m = m_ref[...][:, :1]
        l = l_ref[...][:, :1]
        new_m = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        shift = jnp.where(jnp.isfinite(new_m), new_m, 0.0)
        p = jnp.exp(s - shift)
        corr = jnp.where(jnp.isfinite(m), jnp.exp(m - shift), 0.0)
        acc_ref[...] = acc_ref[...] * corr + lax.dot_general(
            p, vb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        l_new = l * corr + p.sum(axis=-1, keepdims=True)
        m_ref[...] = jnp.broadcast_to(new_m, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(j == n_kb - 1)
    def _flush():
        l = l_ref[...][:, :1]
        o_ref[0] = (
            acc_ref[...] / jnp.maximum(l, 1e-30)
        ).astype(o_ref.dtype)


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
# the kernel runs, so on this path the kernel's dead-block skip saves
# compute only — the gather already paid O(total_len) HBM traffic per
# layer per step, the bandwidth the fixed-lane banded read avoids.
# The O(pos) paged hot path needs IN-KERNEL table indexing (a
# scalar-prefetch BlockSpec index_map resolving page ids per grid
# row, the vLLM/TPU paged-attention shape) — the wired on-chip
# follow-up; until then an on-chip capture of paged+flash measures
# gather + kernel, and bench.py's serve_decode paged_kv sub-record
# should be read accordingly.


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
    into the softmax. The flash kernel's dead-block skip is
    compute-side only here — see the module's cost note: the gather
    materializes the full lane views first; in-kernel table indexing
    is the on-chip follow-up.
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
    impl: str = "reference", block_k: int = DEFAULT_BLOCK_K,
    interpret: bool | None = None,
):
    """The engine-facing entry: ``impl`` picks the path at trace time.

    ``reference`` — the jnp einsum math (bit-identical to the PR-3
    engine on fp32 caches); ``flash`` — the Pallas kernel (compiled
    Mosaic on TPU, interpreter elsewhere); ``auto`` — flash on TPU,
    reference everywhere else (the serving default: off-TPU nothing
    beats XLA's fused einsums, and the PR-3 numerics stay untouched).
    """
    if impl == "auto":
        impl = "flash" if jax.default_backend() == "tpu" else "reference"
    if impl == "flash":
        return flash_decode_attention(
            q, k, v, pos, k_scale, v_scale,
            block_k=block_k, interpret=interpret,
        )
    if impl != "reference":
        raise ValueError(
            f"unknown decode attention impl {impl!r}: expected "
            "'auto', 'flash' or 'reference'"
        )
    return decode_attention_reference(q, k, v, pos, k_scale, v_scale)


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
