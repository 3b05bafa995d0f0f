"""Vision Transformer (ViT-Tiny and friends) — the attention-path config.

BASELINE.json config 4: "ViT-Tiny on CIFAR-100 (attention path, bf16
mixed precision)". The reference has no attention anywhere
(/root/reference/model.py:8-16 is conv+linear); this adds the family
TPU-first:

- attention runs through a pluggable callable (``attention_fn``) with
  the signature ``(q, k, v) -> out`` on [B, T, H, D] arrays, so the
  same module serves dense single-chip attention and the
  sequence-parallel ring attention in ``ddp_tpu.parallel.ring`` — the
  mesh decides, the model doesn't;
- pre-LN blocks, GELU MLP, learned position embeddings, class token;
- all matmul-heavy ops inherit the input dtype (bf16 under mixed
  precision) while LayerNorm and the head stay fp32-stable.
"""

from __future__ import annotations

from typing import Callable, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from ddp_tpu.ops.attention import best_attention

AttentionFn = Callable[[jnp.ndarray, jnp.ndarray, jnp.ndarray], jnp.ndarray]


class RowParallelDense(nn.Module):
    """Megatron row-parallel Dense for use inside ``shard_map``.

    The kernel's INPUT dim is sharded over ``axis_name`` — each mesh
    member holds [d_in/tp, features] and contributes a partial
    product, combined by one ``lax.psum``; the bias (replicated) is
    added once, after the sum. Param tree paths (``kernel``/``bias``
    under the module name) match ``nn.Dense`` exactly, so a densely
    initialized checkpoint shards onto this module without renaming
    (parallel/tp.py ``seq_param_specs``).
    """

    features: int
    axis_name: str
    # True → combine with Megatron's ``g`` (psum forward, identity
    # backward) instead of a bare psum: required when the block's
    # gradient comes from an explicit jax.vjp INSIDE the shard_map
    # body (hand-scheduled pipeline schedules) — see parallel/tp.py.
    inner_vjp: bool = False

    @nn.compact
    def __call__(self, x):
        kernel = self.param(
            "kernel",
            nn.initializers.lecun_normal(),
            (x.shape[-1], self.features),
        )
        bias = self.param("bias", nn.initializers.zeros, (self.features,))
        partial_y = x @ kernel.astype(x.dtype)
        if self.inner_vjp:
            from ddp_tpu.parallel.tp import megatron_g

            y = megatron_g(partial_y, self.axis_name)
        else:
            y = lax.psum(partial_y, self.axis_name)
        return y + bias.astype(y.dtype)


class MultiHeadAttention(nn.Module):
    """QKV projection + pluggable attention kernel + output projection.

    ``attention_fn=None`` (the default everywhere in the model zoo)
    resolves to ``ops.attention.best_attention()`` at call time: on
    TPU the Pallas flash kernel for sequences past FLASH_MIN_LEN and
    dense XLA below it (where the kernel's per-block overhead loses);
    dense everywhere else. Passing a callable overrides it
    (ring/Ulysses collectives, causal variants, tests).

    ``tp_axis``/``tp_size`` (shard_map-only): Megatron tensor
    parallelism — qkv goes column-parallel (this member computes
    ``num_heads/tp_size`` heads; the attention kernel sees only local
    heads, so TP composes freely with ring/Ulysses over ``seq``) and
    the output projection row-parallel with one psum (parallel/tp.py).
    """

    num_heads: int
    attention_fn: Optional[AttentionFn] = None
    tp_axis: Optional[str] = None
    tp_size: int = 1
    # True → Megatron f/g custom-VJP plumbing for contexts that take
    # the gradient with an explicit jax.vjp inside the shard_map body
    # (hand-scheduled pipeline schedules). See parallel/tp.py.
    tp_inner_vjp: bool = False
    # Grouped-query attention: 0 → num_heads (plain MHA). Fewer KV
    # heads shrink the qkv projection and — the real win — the
    # generation KV cache and its per-step HBM reads
    # (models/generate.py stores the COMPACT kv). KV is expanded to
    # the full head count before ``attention_fn``, so flash / ring /
    # Ulysses compose unchanged. Composes with TP when tp_size
    # divides num_kv_heads (whole kv groups per member — see the
    # group-major layout note in __call__). BREAKING vs the round-3
    # layout: the fused qkv columns moved from the [q·H | k·H_kv |
    # v·H_kv] block order to group-major (same shapes — a round-3 GQA
    # checkpoint restores shape-clean but mispermuted; retrain or
    # re-export).
    num_kv_heads: int = 0

    @nn.compact
    def __call__(self, x, *, deterministic: bool = True):
        B, T, C = x.shape
        assert C % self.num_heads == 0, (C, self.num_heads)
        assert self.num_heads % self.tp_size == 0, (
            self.num_heads, self.tp_size,
        )
        head_dim = C // self.num_heads
        fn = self.attention_fn or best_attention()
        H_kv = self.num_kv_heads or self.num_heads
        if H_kv != self.num_heads:
            # ValueError (not assert): library users bypass the trainer
            # guards, and asserts vanish under ``python -O``.
            if self.num_heads % H_kv != 0:
                raise ValueError(
                    f"num_heads={self.num_heads} must be a multiple of "
                    f"num_kv_heads={H_kv}"
                )
            if H_kv % self.tp_size != 0:
                raise ValueError(
                    f"GQA under TP shards whole kv groups: num_kv_heads="
                    f"{H_kv} not divisible by tp_size={self.tp_size}"
                )
            # GROUP-MAJOR fused layout: columns ordered [kv-group g:
            # q_{g,0..G-1} | k_g | v_g] × H_kv groups. A contiguous
            # shard of the output dim — what P(..., "model") hands each
            # TP member — is a whole number of kv GROUPS, each with its
            # G query heads and its complete k AND v (the GQA analogue
            # of the MHA head-major contract above). generate.py
            # mirrors this layout.
            if self.tp_size > 1 and self.tp_inner_vjp:
                from ddp_tpu.parallel.tp import megatron_f

                x = megatron_f(x, self.tp_axis)
            g = self.num_heads // H_kv
            kv_local = H_kv // self.tp_size
            qkv = nn.Dense(
                (self.num_heads + 2 * H_kv) * head_dim // self.tp_size,
                name="qkv",
            )(x)
            qkv = qkv.reshape(B, T, kv_local, g + 2, head_dim)
            q = qkv[..., :g, :].reshape(B, T, kv_local * g, head_dim)
            k = qkv[..., g, :]  # [B, T, kv_local, head_dim]
            v = qkv[..., g + 1, :]
            k = jnp.repeat(k, g, axis=2)
            v = jnp.repeat(v, g, axis=2)
            out = fn(q, k, v)  # [B, T, H_local, D]
        else:
            heads_local = self.num_heads // self.tp_size
            if self.tp_size > 1 and self.tp_inner_vjp:
                from ddp_tpu.parallel.tp import megatron_f

                x = megatron_f(x, self.tp_axis)
            # HEAD-MAJOR qkv layout: the fused kernel's output columns
            # are ordered [head, (q|k|v), head_dim], so a contiguous
            # shard of the output dim — what P(..., "model") hands each
            # TP member — is a whole number of heads with their
            # complete q, k, AND v. (A (q|k|v)-major layout would hand
            # member 0 "all of Q plus half of K" under TP.) generate.py
            # mirrors this layout.
            qkv = nn.Dense(3 * C // self.tp_size, name="qkv")(x)
            # An attention that can read q, k and v where this matmul
            # wrote them says so (``from_projection``, ops/attention.py:
            # the flash kernels, heads of whole 128-lane groups): it
            # takes the [B, T, H_local·3·D] array whole and returns
            # [B, T, H_local·D], what ``proj`` reads, and nothing is
            # sliced, transposed or stacked on the way there or back.
            # None: not at this shape; q, k, v are strided slices.
            whole = getattr(fn, "from_projection", None)
            out = whole(qkv, heads_local) if whole else None
            if out is None:
                qkv = qkv.reshape(B, T, heads_local, 3, head_dim)
                out = fn(qkv[:, :, :, 0], qkv[:, :, :, 1], qkv[:, :, :, 2])
        out = out.reshape(B, T, C // self.tp_size)
        if self.tp_size > 1:
            return RowParallelDense(
                C, self.tp_axis, inner_vjp=self.tp_inner_vjp, name="proj"
            )(out)
        return nn.Dense(C, name="proj")(out)


@jax.custom_vjp
def held_once(y):
    """``y``, an array of the program where it is differentiated: written
    once, read by the matmul that follows and kept as that matmul's
    residual, so its weight-gradient matmul reads it too. Without it XLA
    fuses a LayerNorm's normalise-scale-shift-cast as a PRODUCER into
    every consumer, and the weight-gradient matmul re-derives it from x
    and the statistics once for every output tile (``mlp1``'s ran at
    61% of the MXU's peak for it: PERF.md section 6, PR 45). The values
    are the same either way; the cotangent passes as it is, so the
    matmul that makes it keeps the LayerNorm's backward in its
    epilogue."""
    return y


def _held_once_fwd(y):
    return lax.optimization_barrier(y), None


def _held_once_bwd(_, g):
    return (g,)


held_once.defvjp(_held_once_fwd, _held_once_bwd)


class EncoderBlock(nn.Module):
    """Pre-LN block. ``deterministic`` is a module attribute (not a call
    kwarg) so ``nn.remat(EncoderBlock)`` traces only the activation —
    a traced bool would break Dropout/BatchNorm's Python branching.

    ``tp_axis``/``tp_size``: Megatron tensor parallelism inside a
    shard_map — attention heads and the MLP hidden dim shard over the
    ``model`` mesh axis, two psums per block (after attn/proj and
    mlp2); LayerNorms and the residual stream stay replicated
    (parallel/tp.py has the layout + gradient-exactness story).

    ``hold_norm``: ``ln2``'s output goes through ``held_once`` on its
    way into ``mlp1``. The caller decides (``parallel/ddp.norm_plan``,
    from the step's mesh); the default is the plain form. ``ln1`` passes
    the same place and is NOT held: held, ``attn.qkv``'s forward ran
    0.05 ms a call faster and its weight gradient 0.12 slower (XLA
    tiles the ``[2048, 6144]`` result worse over a stored operand),
    0.18% of the one-chip step end to end (PERF.md section 6, PR 45)."""

    num_heads: int
    mlp_dim: int
    dropout_rate: float = 0.0
    attention_fn: Optional[AttentionFn] = None
    deterministic: bool = True
    tp_axis: Optional[str] = None
    tp_size: int = 1
    tp_inner_vjp: bool = False  # Megatron f/g — see MultiHeadAttention
    num_kv_heads: int = 0  # GQA — see MultiHeadAttention
    hold_norm: bool = False

    @nn.compact
    def __call__(self, x):
        assert self.mlp_dim % self.tp_size == 0, (self.mlp_dim, self.tp_size)

        def norm(x, name, held=False):
            # the one place ``ln1 -> attn.qkv`` and ``ln2 -> mlp1`` pass
            y = nn.LayerNorm(dtype=jnp.float32, name=name)(x).astype(x.dtype)
            return held_once(y) if held else y

        y = norm(x, "ln1")
        y = MultiHeadAttention(
            self.num_heads,
            attention_fn=self.attention_fn,
            tp_axis=self.tp_axis,
            tp_size=self.tp_size,
            tp_inner_vjp=self.tp_inner_vjp,
            num_kv_heads=self.num_kv_heads,
            name="attn",
        )(y, deterministic=self.deterministic)
        y = nn.Dropout(self.dropout_rate, deterministic=self.deterministic)(y)
        x = x + y
        y = norm(x, "ln2", held=self.hold_norm)
        if self.tp_size > 1 and self.tp_inner_vjp:
            from ddp_tpu.parallel.tp import megatron_f

            y = megatron_f(y, self.tp_axis)
        y = nn.Dense(self.mlp_dim // self.tp_size, name="mlp1")(y)
        y = nn.gelu(y)
        if self.tp_size > 1:
            y = RowParallelDense(
                x.shape[-1], self.tp_axis, inner_vjp=self.tp_inner_vjp,
                name="mlp2",
            )(y)
        else:
            y = nn.Dense(x.shape[-1], name="mlp2")(y)
        y = nn.Dropout(self.dropout_rate, deterministic=self.deterministic)(y)
        return x + y


class ViT(nn.Module):
    """Patch-embed → [cls] + pos-embed → N pre-LN blocks → head."""

    num_classes: int = 100
    patch_size: int = 4
    embed_dim: int = 192
    depth: int = 12
    num_heads: int = 3
    mlp_ratio: int = 4
    dropout_rate: float = 0.0
    attention_fn: Optional[AttentionFn] = None
    use_cls_token: bool = True
    # Rematerialize each encoder block in the backward pass
    # (jax.checkpoint): activations are recomputed instead of stored,
    # trading ~1 extra forward of FLOPs for O(depth) less HBM — the
    # standard TPU memory lever for deep/long-sequence configs.
    remat: bool = False

    @nn.compact
    def __call__(self, x, train: bool = False):
        B = x.shape[0]
        p = self.patch_size
        x = nn.Conv(
            self.embed_dim, (p, p), strides=(p, p), padding="VALID",
            name="patch_embed",
        )(x)  # [B, H/p, W/p, C]
        x = x.reshape(B, -1, self.embed_dim)
        if self.use_cls_token:
            cls = self.param(
                "cls_token", nn.initializers.zeros, (1, 1, self.embed_dim)
            )
            x = jnp.concatenate(
                [jnp.broadcast_to(cls, (B, 1, self.embed_dim)).astype(x.dtype), x],
                axis=1,
            )
        pos = self.param(
            "pos_embed",
            nn.initializers.normal(stddev=0.02),
            (1, x.shape[1], self.embed_dim),
        )
        x = x + pos.astype(x.dtype)
        x = nn.Dropout(self.dropout_rate, deterministic=not train)(x)
        block_cls = nn.remat(EncoderBlock) if self.remat else EncoderBlock
        for i in range(self.depth):
            x = block_cls(
                num_heads=self.num_heads,
                mlp_dim=self.embed_dim * self.mlp_ratio,
                dropout_rate=self.dropout_rate,
                attention_fn=self.attention_fn,
                deterministic=not train,
                name=f"block{i + 1}",
            )(x)
        x = nn.LayerNorm(dtype=jnp.float32, name="ln_final")(x)
        x = x[:, 0] if self.use_cls_token else x.mean(axis=1)
        return nn.Dense(self.num_classes, name="head", dtype=jnp.float32)(x)


def ViTTiny(
    num_classes: int = 100,
    patch_size: int = 4,
    depth: int = 12,
    attention_fn: Optional[AttentionFn] = None,
    **kwargs,
) -> ViT:
    return ViT(
        num_classes=num_classes,
        patch_size=patch_size,
        embed_dim=192,
        depth=depth,
        num_heads=3,
        attention_fn=attention_fn,
        **kwargs,
    )
