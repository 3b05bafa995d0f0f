"""Sequence-parallel attention == dense attention, on real shardings.

The capability the reference never had (SURVEY.md §5 long-context:
absent): attention over a token dimension sharded across the ``seq``
mesh axis. Exactness is the whole contract — ring and Ulysses must
match the dense kernel to fp32 tolerance on the gathered sequence.
"""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from ddp_tpu.ops.attention import dot_product_attention
from ddp_tpu.parallel.ring import (
    ring_attention,
    sequence_sharded_attention,
    ulysses_attention,
)


def _qkv(B, T, H, D, seed=0):
    rng = np.random.default_rng(seed)
    return tuple(
        jnp.asarray(rng.normal(size=(B, T, H, D)).astype(np.float32))
        for _ in range(3)
    )


def _seq_sharded(fn, mesh):
    spec = P(None, "seq")
    return jax.jit(
        jax.shard_map(
            fn, mesh=mesh, in_specs=(spec,) * 3, out_specs=spec, check_vma=False
        )
    )


def test_ring_matches_dense_8way(devices):
    mesh = Mesh(np.asarray(devices), ("seq",))
    q, k, v = _qkv(2, 64, 3, 8)
    out = _seq_sharded(ring_attention, mesh)(q, k, v)
    ref = dot_product_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_ring_under_data_parallel(devices):
    """data×seq factorization: batch on data, tokens on seq."""
    mesh = Mesh(np.asarray(devices).reshape(2, 4), ("data", "seq"))
    q, k, v = _qkv(4, 32, 2, 16, seed=1)
    spec = P("data", "seq")
    fn = jax.jit(
        jax.shard_map(
            ring_attention,
            mesh=mesh,
            in_specs=(spec,) * 3,
            out_specs=spec,
            check_vma=False,
        )
    )
    ref = dot_product_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(fn(q, k, v)), np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize("form", ["resident", "grid"])
@pytest.mark.parametrize("D", [128, 16])
def test_two_member_ring_on_the_flash_hop_matches_dense(
        devices, request, D, form):
    """The ring's hops on the Pallas kernels (interpreted here): K/V of
    the other member arrive as separate [B, T_local, H, D] arrays, heads
    of 128 read as [B, T_local, H·D] and heads of 16 transposed; the
    (out, lse) pairs combine to dense causal attention, and so do the
    gradients through the lse cotangent. ``resident``: each hop's
    backward is the ONE kernel over a head held in VMEM (what the
    program chooses for a hop this short; the causal hop and the
    non-causal one, each with its ``dlse``); ``grid``: the kernel pair a
    hop at 32k runs, here under a VMEM budget a head does not fit."""
    from ddp_tpu.ops import flash
    from ddp_tpu.ops.flash import flash_attention_with_lse

    if form == "grid":
        request.getfixturevalue("backward_over_budget")
    before = time.perf_counter()  # the ring is bounded: mark its clock
    mesh = Mesh(np.asarray(devices[:2]), ("seq",))
    q, k, v = _qkv(1, 64, 2, D, seed=9)

    def hop(q, k, v, causal):
        return flash_attention_with_lse(q, k, v, causal, 16, 16, True)

    ring = _seq_sharded(
        lambda a, b, c: ring_attention(a, b, c, causal=True, block_fn=hop),
        mesh,
    )
    ref = dot_product_attention(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(ring(q, k, v)), np.asarray(ref), atol=2e-5)
    grads = jax.grad(lambda *a: (ring(*a) ** 2).mean(), argnums=(0, 1, 2))
    ref_grads = jax.grad(
        lambda *a: (dot_product_attention(*a, causal=True) ** 2).mean(),
        argnums=(0, 1, 2),
    )
    for g, r in zip(grads(q, k, v), ref_grads(q, k, v)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r), atol=2e-5)
    backward = {(e[4][0], e[4][8]) for e in flash.get_tracer().ring()
                if e[0] == "flash.plan" and e[1] >= before
                and e[4][0] != "flash_fwd"}
    assert backward == {
        "resident": {("flash_dkv", "resident")},
        "grid": {("flash_dq", "grid"), ("flash_dkv", "grid")}}[form]


def test_ulysses_matches_dense(devices):
    mesh = Mesh(np.asarray(devices[:4]), ("seq",))
    q, k, v = _qkv(2, 32, 4, 8, seed=2)  # H=4 divisible by seq=4
    out = _seq_sharded(ulysses_attention, mesh)(q, k, v)
    ref = dot_product_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_ulysses_rejects_indivisible_heads(devices):
    mesh = Mesh(np.asarray(devices), ("seq",))
    q, k, v = _qkv(1, 16, 3, 4)  # 3 heads, 8-way seq axis
    with pytest.raises(ValueError, match="not divisible"):
        _seq_sharded(ulysses_attention, mesh)(q, k, v)


def _dense_causal_reference(q, k, v):
    """Explicitly-masked softmax — independent of the kernels under test."""
    qf, kf, vf = (np.asarray(a, np.float64) for a in (q, k, v))
    B, T, H, D = qf.shape
    logits = np.einsum("bthd,bshd->bhts", qf, kf) / np.sqrt(D)
    mask = np.tril(np.ones((T, T), bool))
    logits = np.where(mask, logits, -np.inf)
    logits -= logits.max(-1, keepdims=True)
    w = np.exp(logits)
    w /= w.sum(-1, keepdims=True)
    return np.einsum("bhts,bshd->bthd", w, vf).astype(np.float32)


class TestCausal:
    def test_dense_causal_matches_reference(self):
        q, k, v = _qkv(2, 16, 2, 8, seed=4)
        out = dot_product_attention(q, k, v, causal=True)
        np.testing.assert_allclose(
            np.asarray(out), _dense_causal_reference(q, k, v), atol=2e-5
        )

    def test_dense_causal_first_token_sees_only_itself(self):
        q, k, v = _qkv(1, 8, 1, 4, seed=5)
        out = dot_product_attention(q, k, v, causal=True)
        np.testing.assert_allclose(
            np.asarray(out[0, 0]), np.asarray(v[0, 0]), atol=1e-6
        )

    def test_ring_causal_matches_dense_8way(self, devices):
        """The global triangular mask must be exact across shard
        boundaries (the hop offset arithmetic)."""
        mesh = Mesh(np.asarray(devices), ("seq",))
        q, k, v = _qkv(2, 64, 3, 8, seed=6)
        fn = _seq_sharded(
            lambda a, b, c: ring_attention(a, b, c, causal=True), mesh
        )
        ref = dot_product_attention(q, k, v, causal=True)
        np.testing.assert_allclose(
            np.asarray(fn(q, k, v)), np.asarray(ref), atol=2e-5
        )

    def test_ulysses_causal_matches_dense(self, devices):
        mesh = Mesh(np.asarray(devices[:4]), ("seq",))
        q, k, v = _qkv(2, 32, 4, 8, seed=7)
        fn = _seq_sharded(
            lambda a, b, c: ulysses_attention(a, b, c, causal=True), mesh
        )
        ref = dot_product_attention(q, k, v, causal=True)
        np.testing.assert_allclose(
            np.asarray(fn(q, k, v)), np.asarray(ref), atol=2e-5
        )

    def test_dispatch_causal(self, devices):
        mesh = Mesh(np.asarray(devices[:4]), ("seq",))
        q, k, v = _qkv(1, 32, 4, 8, seed=8)
        ref = dot_product_attention(q, k, v, causal=True)
        for strategy in ("ring", "ulysses"):
            fn = _seq_sharded(
                lambda a, b, c: sequence_sharded_attention(
                    a, b, c, strategy=strategy, causal=True
                ),
                mesh,
            )
            np.testing.assert_allclose(
                np.asarray(fn(q, k, v)), np.asarray(ref), atol=2e-5
            )


def test_dispatch_strategies(devices):
    mesh = Mesh(np.asarray(devices[:4]), ("seq",))
    q, k, v = _qkv(1, 32, 4, 8, seed=3)
    ref = dot_product_attention(q, k, v)
    for strategy in ("ring", "ulysses"):
        fn = _seq_sharded(
            lambda a, b, c: sequence_sharded_attention(a, b, c, strategy=strategy),
            mesh,
        )
        np.testing.assert_allclose(np.asarray(fn(q, k, v)), np.asarray(ref), atol=2e-5)
