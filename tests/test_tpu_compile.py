"""The block-diffusion model's kernels compiled for a TPU v5e that is
described, not attached (the TPU's compiler is installed here): the
grouped expert matmuls and the decode kernel with a block's queries
folded in, at the published widths of SDAR-30B-A3B. What the Pallas
interpreter cannot show — a slice off the tiling, more fast memory than
a kernel may use — fails here, at no chip time. Nothing runs.

The topology is described inside a fixture, in this file only: one
process at a time holds the TPU's library."""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — whatever keeps it from here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def as_on_tpu(monkeypatch):
    """The kernels ask the backend whether to compile or interpret."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _shape(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("tokens", [128, 64])  # a block step, a chunk
def test_grouped_expert_kernels_compile_at_published_widths(
        one_chip, as_on_tpu, tokens):
    from ddp_tpu.ops.moe import moe_layer

    d, f, E, k = 2048, 768, 128, 8
    bf = jnp.bfloat16
    compiled = jax.jit(
        lambda x, r, g, u, w: moe_layer(x, r, g, u, w, top_k=k,
                                        impl="pallas")
    ).lower(
        _shape((tokens, d), jnp.float32, one_chip),
        _shape((tokens, E), jnp.float32, one_chip),
        _shape((E, d, f), bf, one_chip), _shape((E, d, f), bf, one_chip),
        _shape((E, f, d), bf, one_chip),
    ).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 2
    assert "moe_grouped_gate_up" in text and "moe_grouped_down" in text


def test_decode_kernel_compiles_with_a_blocks_queries_folded(
        one_chip, as_on_tpu):
    from ddp_tpu.ops.decode import decode_attention

    S, B, H, Hkv, Dh, L, depth = 32, 4, 32, 4, 128, 512, 7
    compiled = jax.jit(
        lambda q, k, v, pos: decode_attention(
            q, k, v, pos, impl="flash", layer=depth - 1)
    ).lower(
        _shape((S, H * B, Dh), jnp.float32, one_chip),
        _shape((depth, S, L, Hkv, Dh), jnp.float32, one_chip),
        _shape((depth, S, L, Hkv, Dh), jnp.float32, one_chip),
        _shape((S,), jnp.int32, one_chip),
    ).compile()
    assert "flash_decode" in compiled.as_text()
