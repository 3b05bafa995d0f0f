"""Pallas flash-attention kernel == dense attention (values and grads).

Runs the kernel in interpreter mode on CPU — the same program the TPU
compiles. Exactness vs. the dense reference is the contract, including
under causal masking and through the custom-VJP backward.
"""

import jax
import jax.numpy as jnp
import numpy as np

from ddp_tpu.ops.attention import dot_product_attention
from ddp_tpu.ops.flash import flash_attention, make_flash_attention


def _qkv(B, T, H, D, seed=0):
    rng = np.random.default_rng(seed)
    return tuple(
        jnp.asarray(rng.normal(size=(B, T, H, D)).astype(np.float32))
        for _ in range(3)
    )


def test_flash_matches_dense():
    q, k, v = _qkv(2, 64, 3, 16)
    out = flash_attention(q, k, v, False, 16, 16, True)
    ref = dot_product_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_flash_single_block():
    """Block size ≥ T: one block, still exact."""
    q, k, v = _qkv(1, 32, 2, 8, seed=1)
    out = flash_attention(q, k, v, False, 128, 128, True)
    ref = dot_product_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_flash_causal():
    q, k, v = _qkv(1, 32, 2, 8, seed=2)
    out = flash_attention(q, k, v, True, 8, 8, True)
    # dense causal reference
    scale = q.shape[-1] ** -0.5
    logits = jnp.einsum("bthd,bshd->bhts", q, k) * scale
    mask = jnp.tril(jnp.ones((32, 32), bool))
    logits = jnp.where(mask, logits, -jnp.inf)
    w = jax.nn.softmax(logits, axis=-1)
    ref = jnp.einsum("bhts,bshd->bthd", w, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_flash_causal_rectangular():
    """T != S (KV-cache decode shape): mask anchored at the sequence end."""
    q, _, _ = _qkv(1, 4, 2, 8, seed=5)
    _, k, v = _qkv(1, 16, 2, 8, seed=6)
    out = flash_attention(q, k, v, True, 4, 8, True)
    scale = q.shape[-1] ** -0.5
    logits = jnp.einsum("bthd,bshd->bhts", q, k) * scale
    mask = jnp.tril(jnp.ones((4, 16), bool), k=16 - 4)
    logits = jnp.where(mask, logits, -jnp.inf)
    ref = jnp.einsum("bhts,bshd->bthd", jax.nn.softmax(logits, -1), v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_flash_grads_match_dense():
    q, k, v = _qkv(1, 32, 2, 8, seed=3)

    def loss_flash(q, k, v):
        return (flash_attention(q, k, v, False, 16, 16, True) ** 2).mean()

    def loss_dense(q, k, v):
        return (dot_product_attention(q, k, v) ** 2).mean()

    g_f = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_d = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_f, g_d):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)


def test_flash_in_vit():
    """The kernel slots into the model family via attention_fn."""
    from ddp_tpu.models.vit import ViT

    model = ViT(
        num_classes=10, patch_size=7, embed_dim=32, depth=1, num_heads=4,
        # 17 tokens (16 patches + cls): one whole-sequence block.
        attention_fn=make_flash_attention(block_q=32, block_k=32, interpret=True),
    )
    x = jnp.zeros((2, 28, 28, 1), jnp.float32)
    params = model.init(jax.random.key(0), x)["params"]
    logits = model.apply({"params": params}, x)
    assert logits.shape == (2, 10)
    assert np.isfinite(np.asarray(logits)).all()


def _dense_causal(q, k, v):
    """Dense reference with the same end-anchored mask as the kernel."""
    T, S = q.shape[1], k.shape[1]
    scale = q.shape[-1] ** -0.5
    logits = jnp.einsum("bthd,bshd->bhts", q, k) * scale
    mask = jnp.tril(jnp.ones((T, S), bool), k=S - T)
    logits = jnp.where(mask, logits, -jnp.inf)
    return jnp.einsum("bhts,bshd->bthd", jax.nn.softmax(logits, -1), v)


def test_flash_grads_match_dense_causal():
    """The Pallas backward (dq/dkv kernels) under the causal mask."""
    q, k, v = _qkv(2, 64, 2, 8, seed=4)

    def loss_flash(q, k, v):
        return (flash_attention(q, k, v, True, 16, 16, True) ** 2).mean()

    def loss_dense(q, k, v):
        return (_dense_causal(q, k, v) ** 2).mean()

    g_f = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_d = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_f, g_d):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)


def test_flash_grads_causal_rectangular():
    """Backward with T != S (decode shape), end-anchored causal mask."""
    q, _, _ = _qkv(1, 8, 2, 8, seed=7)
    _, k, v = _qkv(1, 32, 2, 8, seed=8)

    def loss_flash(q, k, v):
        return (flash_attention(q, k, v, True, 8, 8, True) ** 2).mean()

    def loss_dense(q, k, v):
        return (_dense_causal(q, k, v) ** 2).mean()

    g_f = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_d = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_f, g_d):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)


def test_flash_lse_matches_logsumexp():
    from ddp_tpu.ops.flash import flash_attention_with_lse

    q, k, v = _qkv(2, 32, 2, 8, seed=9)
    _, lse = flash_attention_with_lse(q, k, v, False, 16, 16, True)
    scale = q.shape[-1] ** -0.5
    logits = jnp.einsum("bthd,bshd->bhts", q, k) * scale
    ref = jax.nn.logsumexp(logits, axis=-1).transpose(0, 2, 1)  # [B, T, H]
    np.testing.assert_allclose(np.asarray(lse), np.asarray(ref), atol=2e-5)


def test_flash_lse_combine_identity():
    """(out, lse) halves over split keys combine to full attention —
    the ring-attention hop primitive."""
    from ddp_tpu.ops.flash import flash_attention_with_lse
    from ddp_tpu.parallel.ring import combine_attention_partials

    q, k, v = _qkv(1, 32, 2, 8, seed=10)
    o1, l1 = flash_attention_with_lse(q, k[:, :16], v[:, :16], False, 16, 16, True)
    o2, l2 = flash_attention_with_lse(q, k[:, 16:], v[:, 16:], False, 16, 16, True)
    o, _ = combine_attention_partials(o1, l1, o2, l2)
    ref = dot_product_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(o), np.asarray(ref), atol=2e-5)


def test_flash_lse_combine_grads():
    """Gradients flow through the lse cotangent (the delta − dlse fold)."""
    from ddp_tpu.ops.flash import flash_attention_with_lse
    from ddp_tpu.parallel.ring import combine_attention_partials

    q, k, v = _qkv(1, 32, 2, 8, seed=11)

    def loss_split(q, k, v):
        o1, l1 = flash_attention_with_lse(
            q, k[:, :16], v[:, :16], False, 16, 16, True
        )
        o2, l2 = flash_attention_with_lse(
            q, k[:, 16:], v[:, 16:], False, 16, 16, True
        )
        o, _ = combine_attention_partials(o1, l1, o2, l2)
        return (o**2).mean()

    def loss_dense(q, k, v):
        return (dot_product_attention(q, k, v) ** 2).mean()

    g_s = jax.grad(loss_split, argnums=(0, 1, 2))(q, k, v)
    g_d = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_s, g_d):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)


def test_flash_backward_memory_is_linear():
    """The whole VJP at long T compiles with O(T·D) temporaries — no
    [T, S] tensor anywhere (the round-1 backward recomputed through a
    dense O(T²) reference; VERDICT.md missing #1)."""
    T, D = 4096, 64
    shapes = jax.ShapeDtypeStruct((1, T, 1, D), jnp.float32)

    def loss_flash(q, k, v):
        return (flash_attention(q, k, v, False, 128, 128, True) ** 2).mean()

    def loss_dense(q, k, v):
        return (dot_product_attention(q, k, v) ** 2).mean()

    def peak(fn):
        lowered = jax.jit(
            lambda q, k, v: jax.grad(fn, argnums=(0, 1, 2))(q, k, v)
        ).lower(shapes, shapes, shapes)
        return lowered.compile().memory_analysis().temp_size_in_bytes

    flash_mem, dense_mem = peak(loss_flash), peak(loss_dense)
    # Dense saves the [B, H, T, S] softmax (≥ T²·4 bytes ≈ 67 MB);
    # flash residuals are q/k/v/out/lse ≈ 5·T·D·4 ≈ 5 MB.
    assert dense_mem > T * T * 4, dense_mem
    assert flash_mem < dense_mem / 4, (flash_mem, dense_mem)


def test_flash_bf16_finite():
    q, k, v = (x.astype(jnp.bfloat16) for x in _qkv(1, 64, 2, 16, seed=12))

    def loss(q, k, v):
        return (flash_attention(q, k, v, True, 32, 32, True).astype(jnp.float32) ** 2).mean()

    val, grads = jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)
    assert np.isfinite(float(val))
    for g in grads:
        assert g.dtype == jnp.bfloat16
        assert np.isfinite(np.asarray(g, dtype=np.float32)).all()
