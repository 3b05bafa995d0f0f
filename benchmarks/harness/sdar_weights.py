"""Seeded weights for the SDAR / Qwen3-MoE tree, made on the device,
one layer at a time.

The benchmark makes the weights (not the program), so the program under
test and the plain reference start from the same numbers. A layer is a
pure function of (seed, layer index): the program's tree is built layer
by layer in the stored dtype (bfloat16, as the model is published), and
the reference remakes ONE layer at a time as the float32 copy of those
bfloat16 values, so only one layer's 2.5 GB of float32 lives beside its
activations.

Tree layout (names and shapes) is ``ddp_tpu/models/sdar.py``'s, which is
the HF checkpoint's: ``embed_tokens [V, d]``, ``layers/{i}/{
input_layernorm [d], self_attn/{q_proj [d, H*Dh], k_proj, v_proj
[d, Hkv*Dh], o_proj [H*Dh, d], q_norm, k_norm [Dh]},
post_attention_layernorm [d], mlp/{gate [d, E], experts/{gate_proj,
up_proj [E, d, f], down_proj [E, f, d]}}}``, ``norm [d]``, ``lm_head
[V, d]``. Values: normal(0, 0.02) matrices (the family's
``initializer_range``), unit norm weights.
"""

from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp

from benchmarks.harness.weights import INIT_STD, nest, seed_key


def layer_shapes(*, d_model: int, num_heads: int, num_kv_heads: int,
                 head_dim: int, num_experts: int, moe_intermediate: int,
                 **_) -> dict[str, tuple[int, ...]]:
    d, Dh, E, f = d_model, head_dim, num_experts, moe_intermediate
    return {
        "input_layernorm": (d,),
        "self_attn/q_proj": (d, num_heads * Dh),
        "self_attn/k_proj": (d, num_kv_heads * Dh),
        "self_attn/v_proj": (d, num_kv_heads * Dh),
        "self_attn/o_proj": (num_heads * Dh, d),
        "self_attn/q_norm": (Dh,),
        "self_attn/k_norm": (Dh,),
        "post_attention_layernorm": (d,),
        "mlp/gate": (d, E),
        "mlp/experts/gate_proj": (E, d, f),
        "mlp/experts/up_proj": (E, d, f),
        "mlp/experts/down_proj": (E, f, d),
    }


def top_shapes(*, vocab_size: int, d_model: int, **_) -> dict:
    return {"embed_tokens": (vocab_size, d_model), "norm": (d_model,),
            "lm_head": (vocab_size, d_model)}


def _leaf(key, path: str, shape, dtype):
    if path.endswith("norm"):
        return jnp.ones(shape, dtype)
    k = jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)
    return (INIT_STD * jax.random.normal(k, shape, jnp.float32)).astype(dtype)


def _build(shapes: dict, dtype):
    return jax.jit(lambda key: nest({
        p: _leaf(key, p, s, dtype) for p, s in shapes.items()
    }))


def make_layer(seed: int, sizes: dict, i: int, dtype=jnp.bfloat16):
    """Layer ``i``'s subtree. One compiled program serves every layer:
    the layer's key is data."""
    key = jax.random.fold_in(seed_key(seed), 1 + int(i))
    return _build(layer_shapes(**sizes), dtype)(key)


def make_top(seed: int, sizes: dict, dtype=jnp.bfloat16):
    """Embedding, final norm and head."""
    return _build(top_shapes(**sizes), dtype)(
        jax.random.fold_in(seed_key(seed), 0)
    )


def make_params(seed: int, sizes: dict, dtype=jnp.bfloat16):
    """The whole tree the program takes, ``sizes["depth"]`` layers."""
    tree = make_top(seed, sizes, dtype)
    tree["layers"] = {
        str(i): make_layer(seed, sizes, i, dtype)
        for i in range(int(sizes["depth"]))
    }
    return tree


def as_float32(tree):
    """The float32 copy of stored (bfloat16) values: what the reference
    multiplies."""
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)
