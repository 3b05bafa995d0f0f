"""Seeded weights for the granite-hybrid tree (HF ``granitemoehybrid``),
made on the device, one layer at a time.

The benchmark makes the weights (not the program), so the program under
test and the plain reference start from the same numbers. A layer is a
pure function of (seed, layer index): the program's tree is built layer
by layer with its matrices in the stored dtype (bfloat16, as the model
is published), and the reference remakes ONE layer at a time as the
float32 copy of those values, so the 40-layer float32 model never
stands in memory at once.

Tree layout (names and shapes) is ``ddp_tpu/models/granite_hybrid.py``'s,
which is the HF checkpoint's: ``embed_tokens [V, d]`` (also the head),
``layers/{i}/{input_layernorm [d], post_attention_layernorm [d],
shared_mlp/{input_linear [d, 2f], output_linear [f, d]}}`` and either
``mamba/{in_proj [d, 2HP + 2N + H], conv1d/{weight [K, HP + 2N], bias},
dt_bias, A_log, D [H], norm [HP], out_proj [HP, d]}`` or
``self_attn/{q_proj, o_proj, k_proj, v_proj}``; ``norm [d]``.

Values, the configuration's ``assumed.weights``: matrices normal(0,
0.02); Mamba-2's own initialisation for the recurrence — ``A_log =
log(uniform[1, 16])``, ``dt_bias`` the inverse softplus of a log-uniform
[0.001, 0.1] time step, ``D`` = 1, the convolution uniform +-1/2 with a
zero bias — so that a step's decay runs from ~0.999 down to ~0.2 and
the state matters to the logits; norm weights 1. Vectors of the
recurrence stay float32 in both trees.
"""

from __future__ import annotations

import math
import zlib

import jax
import jax.numpy as jnp

from benchmarks.harness.weights import INIT_STD, nest, seed_key


def layer_shapes(kind: str, *, d_model: int, num_heads: int,
                 num_kv_heads: int, head_dim: int, mamba_n_heads: int,
                 mamba_d_head: int, mamba_d_state: int, mamba_d_conv: int,
                 mlp_intermediate: int, **_) -> dict[str, tuple[int, ...]]:
    d, f = d_model, mlp_intermediate
    hp = mamba_n_heads * mamba_d_head
    cd = hp + 2 * mamba_d_state
    if kind == "mamba":
        mixer = {
            "mamba/in_proj": (d, hp + cd + mamba_n_heads),
            "mamba/conv1d/weight": (mamba_d_conv, cd),
            "mamba/conv1d/bias": (cd,),
            "mamba/dt_bias": (mamba_n_heads,),
            "mamba/A_log": (mamba_n_heads,),
            "mamba/D": (mamba_n_heads,),
            "mamba/norm": (hp,),
            "mamba/out_proj": (hp, d),
        }
    else:
        mixer = {
            "self_attn/q_proj": (d, num_heads * head_dim),
            "self_attn/k_proj": (d, num_kv_heads * head_dim),
            "self_attn/v_proj": (d, num_kv_heads * head_dim),
            "self_attn/o_proj": (num_heads * head_dim, d),
        }
    return {
        "input_layernorm": (d,), **mixer,
        "post_attention_layernorm": (d,),
        "shared_mlp/input_linear": (d, 2 * f),
        "shared_mlp/output_linear": (f, d),
    }


def top_shapes(*, vocab_size: int, d_model: int, **_) -> dict:
    return {"embed_tokens": (vocab_size, d_model), "norm": (d_model,)}


def _leaf(key, path: str, shape, dtype):
    name = path.rsplit("/", 1)[-1]
    k = jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)
    if name == "norm" or name.endswith("layernorm"):
        return jnp.ones(shape, dtype)
    if name == "D":
        return jnp.ones(shape, jnp.float32)
    if name == "A_log":
        return jnp.log(jax.random.uniform(k, shape, jnp.float32, 1.0, 16.0))
    if name == "dt_bias":
        dt = jnp.exp(jax.random.uniform(
            k, shape, jnp.float32, math.log(1e-3), math.log(1e-1)))
        return dt + jnp.log(-jnp.expm1(-dt))  # softplus^-1
    if path.endswith("conv1d/weight"):
        return jax.random.uniform(k, shape, jnp.float32, -0.5, 0.5)
    if path.endswith("conv1d/bias"):
        return jnp.zeros(shape, jnp.float32)
    return (INIT_STD * jax.random.normal(k, shape, jnp.float32)).astype(dtype)


_BUILDERS: dict = {}


def _build(shapes: dict, dtype):
    """One compiled program a set of shapes: a layer's key is data."""
    sig = (tuple(shapes.items()), jnp.dtype(dtype).name)
    if sig not in _BUILDERS:
        _BUILDERS[sig] = jax.jit(lambda key: nest({
            p: _leaf(key, p, s, dtype) for p, s in shapes.items()
        }))
    return _BUILDERS[sig]


def make_layer(seed: int, sizes: dict, i: int, dtype=jnp.bfloat16):
    """Layer ``i``'s subtree; its kind is ``sizes["layer_types"][i]``."""
    key = jax.random.fold_in(seed_key(seed), 1 + int(i))
    return _build(layer_shapes(sizes["layer_types"][i], **sizes), dtype)(key)


def make_top(seed: int, sizes: dict, dtype=jnp.bfloat16):
    """Embedding (the tied head) and final norm."""
    return _build(top_shapes(**sizes), dtype)(
        jax.random.fold_in(seed_key(seed), 0)
    )


def make_params(seed: int, sizes: dict, dtype=jnp.bfloat16):
    """The whole tree the program takes."""
    tree = make_top(seed, sizes, dtype)
    tree["layers"] = {
        str(i): make_layer(seed, sizes, i, dtype)
        for i in range(len(sizes["layer_types"]))
    }
    return tree


def as_float32(tree):
    """The float32 copy of stored (bfloat16) values: what the reference
    multiplies."""
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)
