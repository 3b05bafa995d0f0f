"""The block-causal mask (a block-diffusion prefill, models/sdar.py):
key j is visible to query i iff j // B <= i // B — in the dense
attention, with a chunk's traced offset into a lane, and in the flash
kernels' masks and block skipping, forward and backward."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ddp_tpu.ops.attention import dot_product_attention
from ddp_tpu.ops.flash import _last_key, _reference, flash_attention


def _qkv(T, S, H=2, D=32, seed=0):
    ks = jax.random.split(jax.random.key(seed), 3)
    return (jax.random.normal(ks[0], (1, T, H, D)),
            jax.random.normal(ks[1], (1, S, H, D)),
            jax.random.normal(ks[2], (1, S, H, D)))


def test_last_key_is_the_end_of_the_rows_block():
    assert [_last_key(r, True) for r in range(6)] == list(range(6))
    assert [_last_key(r, 1) for r in range(6)] == list(range(6))
    assert [_last_key(r, 4) for r in range(9)] == [3] * 4 + [7] * 4 + [11]


@pytest.mark.parametrize("T,S", [(16, 16), (8, 24)])
def test_dense_mask_is_block_causal(T, S):
    q, k, v = _qkv(T, S)
    got = dot_product_attention(q, k, v, causal=True, block=4)
    # by hand: softmax over exactly the keys of blocks up to the row's
    off = S - T
    logits = np.einsum("bthd,bshd->bhts", q, k) * 32 ** -0.5
    vis = (np.arange(S)[None] // 4) <= ((np.arange(T)[:, None] + off) // 4)
    logits = np.where(vis, logits, -np.inf)
    w = np.exp(logits - logits.max(-1, keepdims=True))
    w /= w.sum(-1, keepdims=True)
    want = np.einsum("bhts,bshd->bthd", w, v)
    assert np.abs(np.asarray(got) - want).max() < 1e-5
    # block 1 is the plain triangle
    assert np.abs(np.asarray(
        dot_product_attention(q, k, v, causal=True, block=1)
        - dot_product_attention(q, k, v, causal=True))).max() == 0


def test_a_chunks_traced_offset_places_its_blocks():
    """A chunk of 8 queries at position 8 of a 32-key lane sees keys
    0..11 from its first block and 0..15 from its second."""
    q, k, v = _qkv(8, 32)
    got = jax.jit(lambda o: dot_product_attention(
        q, k, v, causal=True, q_offset=o, block=4))(jnp.int32(8))
    short = dot_product_attention(q[:, :4], k[:, :12], v[:, :12])
    full = dot_product_attention(q[:, 4:], k[:, :16], v[:, :16])
    assert float(jnp.abs(got[:, :4] - short).max()) < 1e-5
    assert float(jnp.abs(got[:, 4:] - full).max()) < 1e-5


@pytest.mark.parametrize("form", ["resident", "grid"])
@pytest.mark.parametrize("T,S,bq,bk", [(64, 64, 16, 16), (32, 64, 16, 32)])
def test_flash_kernels_take_the_block_causal_mask(
        request, T, S, bq, bk, form):
    """Forward and backward under the block-causal mask, the backward
    as ONE kernel over a resident head (what the program chooses here)
    and as the grid pair (a VMEM budget the head does not fit, handed
    to the planning function): one pair function, one mask."""
    from ddp_tpu.ops import flash

    if form == "grid":
        request.getfixturevalue("backward_over_budget")
    assert flash._backward_form(
        T, S, 32, "float32", bq, bk, 4)[0] == form
    q, k, v = _qkv(T, S, seed=1)
    out = flash_attention(q, k, v, 4, bq, bk, True)
    assert float(jnp.abs(out - _reference(q, k, v, 4)).max()) < 1e-5
    assert float(jnp.abs(out - _reference(q, k, v, True)).max()) > 1e-3
    grads = jax.grad(
        lambda *a: flash_attention(*a, 4, bq, bk, True).sum(),
        argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: _reference(*a, 4).sum(),
                    argnums=(0, 1, 2))(q, k, v)
    for g, w in zip(grads, want):
        assert float(jnp.abs(g - w).max()) < 1e-4
