"""Pallas flash-attention kernel == dense attention (values and grads).

Runs the kernel in interpreter mode on CPU — the same program the TPU
compiles. Exactness vs. the dense reference is the contract, including
under causal masking and through the custom-VJP backward.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

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


# ---- every block class, both operand dtypes (PR 31) ------------------
#
# The kernels visit only LIVE (q block, k block) pairs and run the
# masked program only on DIAGONAL ones. Each shape below is chosen for
# the classes it holds; T, S, block_q, block_k, causal.
SHAPES = {
    "causal_1_block": (32, 32, 32, 32, True),  # one diagonal pair
    "causal_2_blocks": (32, 32, 16, 16, True),  # 1 interior, 2 diag, 1 dead
    "causal_4_blocks": (64, 64, 16, 16, True),  # 6 / 4 / 6: the cell's grid
    "causal_T_lt_S": (32, 64, 16, 16, True),  # end-anchored: 4 / 2 / 2
    "causal_T_gt_S": (64, 32, 16, 16, True),  # rows that see no key
    "causal_uneven_blocks": (64, 64, 32, 16, True),  # two diagonal a row
    "block_causal_4": (32, 32, 16, 16, 4),
    "block_causal_4_T_lt_S": (32, 96, 16, 32, 4),
    "non_causal": (32, 48, 16, 16, False),  # interior throughout
    # blocks that fill whole 128-lane groups, heads of 128: the paths
    # the chip's shapes take (lane-dense statistics, dK/dV keys first)
    "causal_lanes": (256, 256, 128, 128, True),
    "causal_T_gt_S_lanes": (384, 256, 128, 128, True),
    "block_causal_4_lanes": (256, 384, 128, 128, 4),
    "non_causal_lanes": (128, 512, 128, 256, False),
}


def _dense_safe(q, k, v, causal):
    """``_reference`` in float32 with rows that see no key giving 0
    (and a gradient of 0) where a softmax over nothing gives NaN."""
    from ddp_tpu.ops.flash import _last_key

    T, S = q.shape[1], k.shape[1]
    q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
    logits = jnp.einsum("bthd,bshd->bhts", q, k) * q.shape[-1] ** -0.5
    seen = jnp.ones((T, S), bool)
    if causal:
        rows = _last_key(jnp.arange(T)[:, None] + (S - T), causal)
        seen = rows >= jnp.arange(S)[None, :]
    w = jax.nn.softmax(jnp.where(seen, logits, -1e30), axis=-1)
    w = w * seen.any(-1, keepdims=True)
    return jnp.einsum("bhts,bshd->bthd", w, v)


# max |kernel − dense| accepted: float32 as tightly as the tests above;
# bfloat16 by its 2^-8 rounding of operands, probabilities and outputs
# (scripts/check_kernels.py holds the chip to 2e-2 / 6e-2 at T 2048).
ATOL = {"float32": (2e-5, 2e-5), "bfloat16": (2e-2, 6e-2)}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("shape", SHAPES)
def test_flash_every_block_class(shape, dtype):
    """Outputs and all three gradients against the dense reference."""
    from ddp_tpu.ops.flash import _reference

    T, S, bq, bk, causal = SHAPES[shape]
    B, H, D = (1, 1, 128) if shape.endswith("_lanes") else (2, 2, 16)
    q, _, _ = _qkv(B, T, H, D, seed=20)
    _, k, v = _qkv(B, S, H, D, seed=21)
    w = _qkv(B, T, H, D, seed=22)[0]  # the cotangent
    q, k, v = (x.astype(dtype) for x in (q, k, v))

    def loss(attn):
        def f(q, k, v):
            out = attn(q, k, v)
            return (out.astype(jnp.float32) * w).sum(), out
        return jax.value_and_grad(f, argnums=(0, 1, 2), has_aux=True)

    (_, out), grads = loss(
        lambda q, k, v: flash_attention(q, k, v, causal, bq, bk, True)
    )(q, k, v)
    (_, ref), ref_grads = loss(
        lambda q, k, v: _dense_safe(q, k, v, causal)
    )(*(x.astype(jnp.float32) for x in (q, k, v)))
    assert out.dtype == q.dtype and all(g.dtype == q.dtype for g in grads)
    if S >= T:  # every row sees a key: the module's own reference holds
        np.testing.assert_allclose(
            np.asarray(ref), np.asarray(_reference(
                *(x.astype(jnp.float32) for x in (q, k, v)), causal)),
            atol=2e-6,
        )
    else:  # the rows before the first key attend to nothing
        assert not np.asarray(out, np.float32)[:, : T - S].any()
    out_tol, grad_tol = ATOL[dtype]
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref), atol=out_tol)
    for g, r in zip(grads, ref_grads):
        np.testing.assert_allclose(
            np.asarray(g, np.float32), np.asarray(r), atol=grad_tol)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("D", [16, 128])
def test_flash_with_lse_gradients_under_a_nonzero_dlse(D, causal, dtype):
    """``flash_attention_with_lse`` differentiated in BOTH outputs (ring
    attention's hop): the lse cotangent reaches dq and dk, with heads
    of 16 (transposed operands) and of 128 (read as [B, T, H·D])."""
    from ddp_tpu.ops.flash import flash_attention_with_lse

    q, k, v = (x.astype(dtype) for x in _qkv(1, 32, 2, D, seed=23))
    w = _qkv(1, 32, 2, D, seed=24)[0]
    u = jnp.asarray(np.random.default_rng(25).normal(size=(1, 32, 2)),
                    jnp.float32)

    def dense(q, k, v):
        logits = jnp.einsum("bthd,bshd->bhts", q, k) * q.shape[-1] ** -0.5
        if causal:
            logits = jnp.where(jnp.tril(jnp.ones((32, 32), bool)), logits,
                               -jnp.inf)
        lse = jax.nn.logsumexp(logits, axis=-1).transpose(0, 2, 1)
        out = jnp.einsum("bhts,bshd->bthd", jax.nn.softmax(logits, -1), v)
        return out, lse

    def loss(attn):
        def f(q, k, v):
            out, lse = attn(q, k, v)
            return (out.astype(jnp.float32) * w).sum() + (lse * u).sum()
        return jax.grad(f, argnums=(0, 1, 2))

    grads = loss(
        lambda q, k, v: flash_attention_with_lse(q, k, v, causal, 16, 16, True)
    )(q, k, v)
    ref_grads = loss(dense)(*(x.astype(jnp.float32) for x in (q, k, v)))
    for g, r in zip(grads, ref_grads):
        np.testing.assert_allclose(
            np.asarray(g, np.float32), np.asarray(r), atol=ATOL[dtype][1])


def _plan_layouts(since):
    """kernel -> operand_layout of the ``flash.plan`` records after
    ``since`` (a copy of the tracer's ring)."""
    from ddp_tpu.obs.tracer import SPAN_NUMS, get_tracer

    layout = SPAN_NUMS["flash.plan"].index("operand_layout")
    return {e[4][0]: e[4][layout] for e in get_tracer().ring()[len(since):]
            if e[0] == "flash.plan"}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("D", [128, 64, 16])
def test_projection_entry_and_separate_entry_agree(D, causal, dtype):
    """The two entries of the kernels (the forward and, at this size,
    the resident backward under ``flash_dkv``'s name) on the same
    numbers: the fused projection read where it lies (heads of 128: a head's q, k, v
    are 128-lane column blocks of one array, the cotangent comes back
    as one array) and separate [B, T, H, D] operands. Bit-equal to each
    other — one kernel body, one index rule — and within the dense
    reference's tolerance. Heads of 64 and 16 are not lane-aligned
    column blocks: both entries take the transposed operands."""
    from ddp_tpu.obs.tracer import get_tracer
    from ddp_tpu.ops.flash import (
        _reference, _split_projection as _split, flash_attention_projection,
    )

    B, T, H = 2, 256, 2
    rng = np.random.default_rng(30)
    qkv = jnp.asarray(rng.normal(size=(B, T, H * 3 * D)), dtype)
    w = jnp.asarray(rng.normal(size=(B, T, H * D)), jnp.float32)

    def loss(attn):
        def f(qkv):
            out = attn(qkv)
            return (out.astype(jnp.float32) * w).sum(), out
        return jax.value_and_grad(f, has_aux=True)

    before = get_tracer().ring()
    (_, out), dqkv = loss(
        lambda x: flash_attention_projection(x, H, causal, 128, 128, True)
    )(qkv)
    layout = "projection" if D % 128 == 0 else "transposed"
    assert _plan_layouts(before) == dict.fromkeys(
        ("flash_fwd", "flash_dkv"), layout)
    before = get_tracer().ring()
    (_, out_sep), dqkv_sep = loss(
        lambda x: flash_attention(
            *_split(x, H), causal, 128, 128, True).reshape(B, T, H * D)
    )(qkv)
    layout = "heads_last" if D % 128 == 0 else "transposed"
    assert set(_plan_layouts(before).values()) == {layout}
    (_, ref), dref = loss(
        lambda x: _reference(*_split(x, H), causal).reshape(B, T, H * D)
    )(qkv.astype(jnp.float32))

    assert out.dtype == dqkv.dtype == qkv.dtype
    np.testing.assert_array_equal(np.asarray(out), np.asarray(out_sep))
    np.testing.assert_array_equal(np.asarray(dqkv), np.asarray(dqkv_sep))
    out_tol, grad_tol = ATOL[dtype]
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref), atol=out_tol)
    np.testing.assert_allclose(
        np.asarray(dqkv, np.float32), np.asarray(dref), atol=grad_tol)


def _moved_activations(jaxpr, size):
    """The equations of ``jaxpr`` (and of what it calls, but not of a
    ``pallas_call``'s kernel) that move an array of at least ``size``
    elements without computing on it: (primitive, shape) pairs."""
    moving = ("transpose", "concatenate", "pad", "slice", "dynamic_slice",
              "gather", "scatter", "dynamic_update_slice", "copy")
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            continue
        avals = [v.aval for v in (*eqn.invars, *eqn.outvars)
                 if hasattr(v.aval, "shape")]
        if eqn.primitive.name in moving and any(
                int(np.prod(a.shape)) >= size for a in avals):
            found.append((eqn.primitive.name, avals[0].shape))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _moved_activations(sub, size)
    return found


@pytest.mark.parametrize("head_dim", [128, 64])
def test_nothing_moves_an_activation_around_the_fused_entry(
        head_dim, monkeypatch):
    """The jaxpr of a ``MultiHeadAttention`` forward-and-backward at a
    flash length with heads of 128 holds, outside the two
    ``pallas_call``s (the backward is ONE since PR 39, no ``flash_dq``
    beside it), no transpose, concatenate, pad or slice of an
    array of activation size: q, k, v are read where the ``qkv`` matmul
    wrote them, ``out`` is written where ``proj`` reads it, and the
    projection's cotangent is one array the kernels wrote (PR 33: 6.4 ms
    of copies a step in the train cells otherwise). Heads of 64 are no
    lane-aligned column block: they are sliced, transposed and stacked
    as before, and the check sees it."""
    from ddp_tpu.models.vit import MultiHeadAttention
    from ddp_tpu.ops.attention import best_attention

    # the kernel choice asks the backend; nothing is lowered or run
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    B, T, H = 2, 1024, 2
    C = H * head_dim
    attn = MultiHeadAttention(
        num_heads=H, attention_fn=best_attention(causal=True))
    x = jax.ShapeDtypeStruct((B, T, C), jnp.bfloat16)
    params = jax.eval_shape(attn.init, jax.random.key(0), x)

    def loss(params, x):
        return attn.apply(params, x).astype(jnp.float32).sum()

    closed = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(params, x)
    text = str(closed)
    assert all(f"name={k}" in text for k in ("flash_fwd", "flash_dkv"))
    assert "name=flash_dq" not in text
    moved = _moved_activations(closed.jaxpr, B * T * C)
    if head_dim % 128 == 0:
        assert moved == []
    else:
        assert {name for name, _ in moved} >= {"transpose", "slice"}


def _brute_classes(T, S, bq, bk, causal):
    """Class of each block pair from the mask itself, element by element."""
    from ddp_tpu.ops.flash import _DEAD, _DIAGONAL, _last_key

    seen = np.ones((T, S), bool)
    if causal:
        rows = np.array([_last_key(t + S - T, causal) for t in range(T)])
        seen = rows[:, None] >= np.arange(S)[None, :]
    tiles = seen.reshape(T // bq, bq, S // bk, bk).transpose(0, 2, 1, 3)
    return np.where(tiles.all((2, 3)), 0,
                    np.where(tiles.any((2, 3)), _DIAGONAL, _DEAD))


@pytest.mark.parametrize(
    "shape", [*SHAPES, "the_train_cells", "prefill_T_gt_S_block_causal"])
def test_block_classifier_and_live_pair_tables(shape):
    """Interior / diagonal / dead from the block corners equals the
    class the mask's own elements give; the grid visits every live
    pair exactly once, in the order of its output block, and nothing
    else but one zeroing step for an output block without a live pair."""
    from ddp_tpu.ops.flash import (
        _DEAD, _DIAGONAL, _FIRST, _LAST, _classify, _live_pairs,
    )

    T, S, bq, bk, causal = {
        **SHAPES, "the_train_cells": (2048, 2048, 512, 512, True),
        "prefill_T_gt_S_block_causal": (96, 32, 16, 8, 4),
    }[shape]
    classes = np.array(_classify(T, S, bq, bk, causal))
    np.testing.assert_array_equal(classes, _brute_classes(T, S, bq, bk, causal))
    if shape in ("the_train_cells", "causal_4_blocks"):
        assert [int((classes == c).sum()) for c in (0, _DIAGONAL, _DEAD)] == [
            6, 4, 6]
    for by_key in (False, True):
        outer, inner, flags = (
            np.array(t) for t in _live_pairs(classes.tolist(), by_key=by_key))
        grid = classes.T if by_key else classes
        live = {(o, n) for o, n in zip(*np.nonzero(grid != _DEAD))}
        visited = [(o, n) for o, n, f in zip(outer, inner, flags)
                   if not f & _DEAD]
        assert len(visited) == len(set(visited)) and set(visited) == live
        # a step's class is its pair's; a dead step stands only for an
        # output block that has no live pair
        for o, n, f in zip(outer, inner, flags):
            assert f & (_DIAGONAL | _DEAD) == grid[o, n]
            if f & _DEAD:
                assert (grid[o] == _DEAD).all() and f & _FIRST and f & _LAST
        # output blocks in order, each opened and closed exactly once
        assert list(np.unique(outer)) == list(range(grid.shape[0]))
        assert (np.diff(outer) >= 0).all()
        opens = np.r_[True, np.diff(outer) > 0]
        closes = np.r_[np.diff(outer) > 0, True]
        np.testing.assert_array_equal(flags & _FIRST != 0, opens)
        np.testing.assert_array_equal(flags & _LAST != 0, closes)


# ---- the backward's two forms (PR 39) --------------------------------
#
# ONE kernel over a head held in VMEM (``resident``: the pairs walked
# inside, five matmuls a pair) where the head fits, the ``flash_dq`` +
# ``flash_dkv`` grid pair where it does not. One pair function under
# both, so they must agree wherever both can run.

FORM_ENTRIES = {
    # entry: (head width, block): keys first wherever the block is whole
    # lane groups; a head narrower than 128 goes as transposed copies
    "projection": (128, 128),
    "heads_last": (128, 128),
    "transposed": (64, 128),
    "transposed_small_blocks": (16, 16),
}
FORM_MASKS = {"none": False, "causal": True, "block_causal_4": 4}
# (T over S in blocks, whether the LSE output is differentiated too)
FORM_SHAPES = {"T_eq_S": (2, 2, False), "T_lt_S_dlse": (1, 2, True),
               "T_gt_S": (3, 2, False)}
FORM_CASES = [
    (entry, mask, shape) for entry in FORM_ENTRIES for mask in FORM_MASKS
    for shape in FORM_SHAPES
    # the fused projection holds q, k and v of ONE length
    if entry != "projection" or shape == "T_eq_S"]


def _dense_lse(q, k, causal):
    """The LSE rows [B, T, H] of dense attention under ``_last_key``'s
    end-anchored mask (every row sees a key: T <= S)."""
    from ddp_tpu.ops.flash import _last_key

    T, S = q.shape[1], k.shape[1]
    s = jnp.einsum("bthd,bshd->bhts", q, k) * q.shape[-1] ** -0.5
    if causal:
        rows = _last_key(jnp.arange(T)[:, None] + (S - T), causal)
        s = jnp.where(rows >= jnp.arange(S)[None, :], s, -jnp.inf)
    return jax.nn.logsumexp(s, axis=-1).transpose(0, 2, 1)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("entry,mask,shape", FORM_CASES)
def test_backward_forms_agree(entry, mask, shape, dtype):
    """The resident form's dq, dk, dv equal the grid form's and lie
    within the file's tolerance of the dense reference's: through the
    fused projection (one [B, T, H·3·D] cotangent), [B, T, H·D] operands
    and transposed copies; unmasked, causal and block-causal; with as
    many queries as keys, with fewer and the LSE output differentiated
    (a ring hop), and with more (rows that see no key)."""
    from ddp_tpu.ops import flash as F

    D, block = FORM_ENTRIES[entry]
    causal = FORM_MASKS[mask]
    n_q, n_k, with_dlse = FORM_SHAPES[shape]
    B, H, T, S = 1, 2, n_q * block, n_k * block
    opts = dict(causal=causal, block_q=block, block_k=block, interpret=True)
    rng = np.random.default_rng(39)
    normal = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    q, k, v, w = normal(B, T, H, D), normal(B, S, H, D), normal(B, S, H, D), \
        normal(B, T, H, D)
    q, k, v = (x.astype(dtype) for x in (q, k, v))
    u = normal(B, T, H) if with_dlse else None

    if entry == "projection":
        qkv = jnp.stack((q, k, v), axis=3).reshape(B, T, H * 3 * D)
        out, lse = F._projection_forward(qkv, H, **opts)
        operands = [*(F._Operand(qkv, H, 3, i) for i in range(3)),
                    F._Operand(w.astype(dtype).reshape(B, T, H * D), H),
                    F._Operand(out, H)]
        split = lambda dqkv: F._split_projection(dqkv, H)
        kwargs = dict(joined=True)
        layout = "projection"
    else:
        out, lse = F._flash_forward(q, k, v, **opts)
        operands = [F._operand(x) for x in (q, k, v, w.astype(dtype), out)]
        split = lambda grads: [F._from_operand(g, B, H) for g in grads]
        kwargs = dict(dlse=None if u is None else F._to_lanes(u))
        layout = F._layout(D)
    before = _mark()
    resident = split(F._backward_resident(
        *operands, lse, D, layout, **kwargs, **opts))
    grid = split(F._backward_grid(*operands, lse, D, layout, **kwargs, **opts))
    assert [(r[0], r[7], r[8]) for r in _plans(before)] == [
        ("flash_dkv", layout, "resident"), ("flash_dq", layout, "grid"),
        ("flash_dkv", layout, "grid")]

    def loss(q, k, v):
        # the cotangent the kernels were handed, rounded as they saw it
        total = (_dense_safe(q, k, v, causal)
                 * w.astype(dtype).astype(jnp.float32)).sum()
        if u is None:
            return total
        return total + (_dense_lse(q, k, causal) * u).sum()

    dense = jax.grad(loss, argnums=(0, 1, 2))(
        *(x.astype(jnp.float32) for x in (q, k, v)))
    for got, same, want in zip(resident, grid, dense):
        assert got.dtype == jnp.dtype(dtype)
        # one pair function, the k blocks in one order: only the order
        # of a dot's own float32 sums could differ
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(same, np.float32),
            atol=1e-6 if dtype == "float32" else 2 ** -7)
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want),
            atol=ATOL[dtype][1])


def _mark():
    """A mark in the tracer's clock: ``_plans`` takes what came after
    (the ring is bounded and full late in a worker's run, so a length
    taken before marks nothing)."""
    import time

    return time.perf_counter()


def _plans(since):
    """The ``flash.plan`` records (their values) stamped after
    ``since``."""
    from ddp_tpu.obs.tracer import get_tracer

    return [e[4] for e in get_tracer().ring()
            if e[0] == "flash.plan" and e[1] >= since]


@pytest.mark.parametrize("case", [
    "the_train_cells", "the_train_cells_float32", "a_small_budget",
    "ring_hop_32k", "head_of_8k", "too_many_pairs_to_unroll"])
def test_backward_form_from_the_shapes(case):
    """The form is chosen from what the code can observe — lengths, head
    width, dtype, blocks, mask — against a VMEM budget that is an
    ARGUMENT of the planning function: resident at the train cells'
    shape, the grid under a budget the same head does not fit, for a
    ring hop at 32k, and where the unrolled walk would hold more pairs
    than ``_UNROLL_PAIRS``."""
    from ddp_tpu.ops.flash import _UNROLL_PAIRS, _VMEM_BUDGET, _backward_form

    cell = (2048, 2048, 128, "bfloat16", 512, 512, True)
    args, kwargs, want = {
        "the_train_cells": (cell, {}, "resident"),
        "the_train_cells_float32": (
            (2048, 2048, 128, "float32", 512, 512, True), {}, "resident"),
        "a_small_budget": (cell, dict(budget=16 * 2 ** 20), "grid"),
        "ring_hop_32k": (
            (32768, 32768, 128, "bfloat16", 512, 512, False), {}, "grid"),
        "head_of_8k": (
            (8192, 8192, 128, "bfloat16", 512, 512, True), {}, "grid"),
        "too_many_pairs_to_unroll": (
            (2048, 2048, 128, "bfloat16", 128, 128, True), {}, "grid"),
    }[case]
    form, vmem = _backward_form(*args, **kwargs)
    assert form == want
    if case.startswith("the_train_cells"):
        # 11-15 MB of operands, outputs and accumulators, a pair's tiles
        # and the compiler's half again: well inside the budget
        assert 16 * 2 ** 20 < vmem < _VMEM_BUDGET // 2
    if case == "head_of_8k":  # fits the VMEM; its 136 pairs do not unroll
        assert vmem < _VMEM_BUDGET and 136 > _UNROLL_PAIRS
    if case == "ring_hop_32k":
        assert vmem > _VMEM_BUDGET


def test_a_head_over_the_budget_runs_the_grid_pair(request):
    """``_backward_calls`` follows the planning function: the same call
    leaves one resident ``flash_dkv`` record, and under a budget the
    head does not fit (given to the planning function: the program has
    no switch) a ``flash_dq`` and a ``flash_dkv`` record of the grid
    form, with the same gradients."""
    q, k, v = _qkv(1, 64, 2, 16, seed=31)
    grad = lambda: jax.grad(
        lambda *a: flash_attention(*a, True, 16, 16, True).sum(),
        argnums=(0, 1, 2))(q, k, v)
    before = _mark()
    resident = grad()
    assert [(r[0], r[8], r[9], r[10]) for r in _plans(before)] == [
        ("flash_fwd", "grid", 10, 2), ("flash_dkv", "resident", 1, 5)]
    request.getfixturevalue("backward_over_budget")
    before = _mark()
    grid = grad()
    assert [(r[0], r[8], r[9], r[10]) for r in _plans(before)] == [
        ("flash_fwd", "grid", 10, 2), ("flash_dq", "grid", 10, 3),
        ("flash_dkv", "grid", 10, 4)]
    for a, b in zip(resident, grid):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


@pytest.mark.parametrize("shape", [*SHAPES, "the_train_cells"])
def test_the_resident_walk_visits_every_live_pair_once(shape):
    """``_walk``: down a k block's column the pairs are dead, then
    diagonal, then interior, so (first live, first interior) says every
    pair's class; walked keys outer, queries inner, it is ``_live_pairs``
    by key without the flags."""
    from ddp_tpu.ops.flash import (
        _DEAD, _DIAGONAL, _classify, _live_pairs, _walk,
    )

    T, S, bq, bk, causal = {
        **SHAPES, "the_train_cells": (2048, 2048, 512, 512, True)}[shape]
    classes = _classify(T, S, bq, bk, causal)
    walk = _walk(classes)
    assert len(walk) == S // bk
    walked = [(j, i, _DIAGONAL if i < mid else 0)
              for j, (lo, mid) in enumerate(walk) for i in range(lo, T // bq)]
    outer, inner, flags = _live_pairs(classes, by_key=True)
    assert walked == [(o, n, f & _DIAGONAL)
                      for o, n, f in zip(outer, inner, flags) if not f & _DEAD]
    if shape == "the_train_cells":
        assert walk == ((0, 1), (1, 2), (2, 3), (3, 4))
