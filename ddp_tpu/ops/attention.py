"""Attention kernels on [B, T, H, D] arrays.

The framework's attention contract: ``fn(q, k, v) -> out`` with all
four arrays shaped [batch, tokens, heads, head_dim]. Everything above
(the ViT family) is kernel-agnostic; everything below (dense reference,
blockwise/flash-style, the sequence-parallel ring in
ddp_tpu.parallel.ring) implements this one signature.

The reference repo has no attention at all (model.py is conv+linear);
this exists for the ViT extension config and the long-context path.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


# Large-negative mask value: -inf would produce NaN through the
# online-softmax correction terms when a whole block is masked.
MASK_VALUE = -0.5 * jnp.finfo(jnp.float32).max


# Below this many key/query positions the dense path wins on TPU: the
# flash kernel pays per-grid-cell DMA/dispatch overhead that tiny
# blocks never amortize (measured on a v5e, 2026-07: ViT-Tiny at
# T=65 runs 2× FASTER dense; isolated attention crosses over between
# T=1024 and 2048, where flash reaches 2.8× by T=4096 and the O(T²)
# dense memory starts to matter anyway).
FLASH_MIN_LEN = 1024


def use_flash(key_len: int) -> bool:
    """THE platform-and-size rule every default attention path shares
    (``best_attention``, the GSPMD island, the ring's per-hop block):
    the compiled Pallas flash kernel on TPU for at least
    ``FLASH_MIN_LEN`` keys, dense XLA otherwise."""
    return jax.default_backend() == "tpu" and key_len >= FLASH_MIN_LEN


def describe_attention(key_len: int) -> dict:
    """What ``use_flash`` builds for ``key_len`` keys, for the records
    that must say so (trainer ``run_start``): the choice is made from
    the platform, so it has to be visible, not inferred."""
    if use_flash(key_len):
        return {"impl": "flash", "kernel": "pallas-compiled"}
    return {"impl": "dense", "kernel": "xla"}


def best_attention(*, causal: bool = False, block_q: int = 512,
                   block_k: int = 512):
    """Platform- and SIZE-resolved default attention.

    Returns a ``(q, k, v) -> out`` fn that picks per call (shapes are
    static at trace time): the compiled Pallas flash kernel on TPU for
    sequences of at least ``FLASH_MIN_LEN`` keys — fused
    forward+backward, O(T) memory (ops/flash.py) — and the dense XLA
    path otherwise (short sequences, where the kernel's per-block
    overhead loses to one fused einsum chain, and every non-TPU
    platform). The model factories (vit/lm/seq/moe) call this when no
    explicit ``attention_fn`` is given. The fn also carries
    ``from_projection(qkv, heads)``, which a caller holding the fused
    projection tries first (``models/vit.py::MultiHeadAttention``).
    """
    from ddp_tpu.ops.flash import (
        LANES,
        flash_attention,
        flash_attention_projection,
    )

    def fn(q, k, v):
        if use_flash(k.shape[1]):
            return flash_attention(q, k, v, causal, block_q, block_k, False)
        return dot_product_attention(q, k, v, causal=causal)

    def from_projection(qkv, heads):
        """Attention of a fused head-major projection ``qkv``
        [B, T, heads·3·D] (models/vit.py) → [B, T, heads·D], where the
        flash kernels can read it as it lies: ``use_flash`` lengths and
        heads of whole 128-lane groups. None otherwise: the caller
        slices q, k, v out and calls ``fn``."""
        head_dim = qkv.shape[2] // (3 * heads)
        if use_flash(qkv.shape[1]) and head_dim % LANES == 0:
            return flash_attention_projection(
                qkv, heads, causal, block_q, block_k, False)
        return None

    fn.from_projection = from_projection
    return fn


def gspmd_flash_attention(mesh, *, causal: bool = False, block_q: int = 512,
                          block_k: int = 512, interpret: bool = False):
    """Size-dispatched attention usable INSIDE a GSPMD-jitted step.

    The GSPMD step (parallel/spmd.py) partitions by annotation, but a
    compiled Mosaic custom call has no partitioning rule, so the flash
    kernel can't ride plain propagation there. This wrapper routes the
    flash case through a ``shard_map`` island instead: batch over the
    data-parallel axes (the same set as ``spmd.batch_spec``), heads
    over ``model`` when tensor parallelism is on (the Megatron layout
    already shards attention heads there, so the island's specs match
    the activations' natural placement — no resharding), sequence and
    head_dim whole per shard. Below ``FLASH_MIN_LEN`` keys it returns
    the dense path exactly like ``best_attention`` (and always does on
    non-TPU platforms unless ``interpret`` forces the kernel for
    tests), so short-sequence models are untouched. The island's specs
    are written for [B, T, H, D], so it keeps the separate-operand
    entry and offers no ``from_projection``: q, k, v arrive sliced.
    """
    from ddp_tpu.runtime.mesh import data_axes

    # Same axis set AND same size-1 filter as spmd.batch_spec, so the
    # island's specs always match the GSPMD step's activation layout.
    batch_axes = tuple(
        a for a in data_axes(mesh) if mesh.shape.get(a, 1) > 1
    )
    tp = mesh.shape.get("model", 1)

    def fn(q, k, v):
        if not (
            use_flash(k.shape[1])
            or (interpret and k.shape[1] >= FLASH_MIN_LEN)
        ):
            return dot_product_attention(q, k, v, causal=causal)
        from jax.sharding import PartitionSpec as P

        from ddp_tpu.ops.flash import flash_attention

        head_ax = "model" if tp > 1 and q.shape[2] % tp == 0 else None
        spec = P(batch_axes if batch_axes else None, None, head_ax, None)
        island = jax.shard_map(
            lambda qq, kk, vv: flash_attention(
                qq, kk, vv, causal, block_q, block_k, interpret
            ),
            mesh=mesh,
            in_specs=(spec, spec, spec),
            out_specs=spec,
            check_vma=False,
        )
        return island(q, k, v)

    return fn


def dot_product_attention(q, k, v, *, causal: bool = False, q_offset=None,
                          block: int = 1):
    """Plain softmax attention, fp32 accumulation.

    [B, T, H, D] in/out. Softmax runs in fp32 regardless of input dtype
    (bf16-safe); the two matmuls stay in the input dtype for the MXU.
    ``causal=True`` masks strictly-future keys, END-anchored when
    T != S (query t sees keys up to t + S − T — the KV-cache/chunked
    convention, and exactly the flash kernel's mask, so the size
    dispatch in ``best_attention`` can never change the attention
    pattern); for square T == S this is the ordinary lower triangle.

    ``q_offset`` (optional, may be a TRACED scalar) overrides the end
    anchor: query t attends keys up to ``q_offset + t``. This is the
    masked partial-prefill primitive the serving engine's chunked
    prefill runs — the chunk's T queries start at absolute position
    ``q_offset`` inside an S = total_len key lane, so the banded mask
    depends on a runtime value while the compiled shape stays fixed
    (one program per chunk width, any chunk position).

    ``block`` > 1 makes the causal mask BLOCK-causal: key j is visible
    to the query at absolute position i iff ``j // block <= i // block``
    — bidirectional inside a block, causal between blocks (the
    block-diffusion prefill, models/sdar.py). 1 is the plain triangle.
    """
    dtype = q.dtype
    scale = q.shape[-1] ** -0.5
    logits = jnp.einsum("bthd,bshd->bhts", q, k).astype(jnp.float32) * scale
    if causal:
        T, S = logits.shape[-2:]
        offset = (S - T) if q_offset is None else q_offset
        rows = jnp.arange(T)[:, None] + offset
        if block > 1:  # a query sees its whole block
            rows = rows // block * block + (block - 1)
        mask = rows >= jnp.arange(S)[None, :]
        logits = jnp.where(mask, logits, MASK_VALUE)
    weights = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhts,bshd->bthd", weights.astype(dtype), v)


def blockwise_attention(q, k, v, *, block_size: int = 512):
    """Memory-bounded attention: online-softmax over key/value blocks.

    Flash-attention's recurrence expressed with ``lax.scan`` — O(T)
    memory in the key length instead of O(T²), XLA fuses the inner
    block math onto the MXU. Exact (not approximate): matches
    ``dot_product_attention`` to fp32 tolerance for any block size.
    Also the building block of ring attention (each ring hop feeds one
    remote KV block through the same accumulator).
    """
    B, T, H, D = q.shape
    S = k.shape[1]
    if S % block_size:
        # Fall back to one block rather than padding with masks.
        block_size = S
    n_blocks = S // block_size
    qf = q.astype(jnp.float32)
    kf = k.reshape(B, n_blocks, block_size, H, D).transpose(1, 0, 2, 3, 4)
    vf = v.reshape(B, n_blocks, block_size, H, D).transpose(1, 0, 2, 3, 4)
    scale = D**-0.5

    def step(carry, kv):
        acc, row_max, row_sum = carry
        kb, vb = kv
        logits = (
            jnp.einsum("bthd,bshd->bhts", qf, kb.astype(jnp.float32)) * scale
        )  # [B, H, T, block]
        new_max = jnp.maximum(row_max, logits.max(axis=-1))
        correction = jnp.exp(row_max - new_max)
        p = jnp.exp(logits - new_max[..., None])
        acc = acc * correction[..., None] + jnp.einsum(
            "bhts,bshd->bthd", p, vb.astype(jnp.float32)
        ).transpose(0, 2, 1, 3)
        row_sum = row_sum * correction + p.sum(axis=-1)
        return (acc, new_max, row_sum), None

    acc0 = jnp.zeros((B, H, T, D), jnp.float32)
    max0 = jnp.full((B, H, T), -jnp.inf, jnp.float32)
    sum0 = jnp.zeros((B, H, T), jnp.float32)
    (acc, _, row_sum), _ = lax.scan(step, (acc0, max0, sum0), (kf, vf))
    out = acc / row_sum[..., None]
    return out.transpose(0, 2, 1, 3).astype(q.dtype)
