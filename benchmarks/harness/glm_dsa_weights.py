"""Seeded weights for the GLM-5 tree (HF ``glm_moe_dsa``), made on the
device, one layer at a time.

The benchmark makes the weights (not the program), so the program under
test and the plain reference start from the same numbers. A layer is a
pure function of (seed, layer index): the program's tree is built layer
by layer with its matrices in the stored dtype (bfloat16), and the
reference remakes ONE layer at a time as the float32 copy of those
values, so only one layer's float32 (3.3 GB where it routes) stands
beside the reference's activations.

Tree layout (names and shapes) is ``ddp_tpu/models/glm_dsa.py``'s
``leaf_shapes``: ``embed_tokens [V, d]``, ``norm [d]``, ``lm_head
[V, d]`` and per layer ``input_layernorm``, ``post_attention_layernorm``
``[d]``, ``self_attn/{q_a_proj [d, Rq], q_a_layernorm [Rq], q_b_proj
[Rq, H (Dn + Dr)], kv_a_proj_with_mqa [d, R + Dr], kv_a_layernorm [R],
kv_b_proj [R, H (Dn + Dv)], o_proj [H Dv, d], indexer/{wq_b [Rq, Hi Di],
wk [d, Di], k_norm/{weight, bias} [Di], weights_proj [d, Hi]}}`` and
``mlp/{gate_proj, up_proj [d, f], down_proj [f, d]}`` in a dense layer
or ``mlp/{gate [d, E], gate_bias [E], experts/{gate_proj, up_proj [Eh,
d, fm], down_proj [Eh, fm, d]}, shared_experts/{...}}`` in a routed one:
the router scores all ``E`` = ``router_outputs`` experts, the tree
stacks the ``Eh`` = ``experts_held`` this chip holds.

Values, the configuration's ``assumed.weights``: matrices normal(0,
0.02); norm weights 1, the indexer LayerNorm's bias 0; the router's
choice bias normal(0, 0.01) in float32, so that it changes choices (a
trained one balances load; its scale is that of the gaps between
neighbouring sigmoid scores here).
"""

from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp

from benchmarks.harness.weights import INIT_STD, nest, seed_key

BIAS_STD = 0.01
# What the matrices are stored in (the model is published in bfloat16).
DTYPE = jnp.bfloat16


def layer_shapes(dense: bool, *, d_model: int, num_heads: int,
                 q_lora_rank: int, kv_lora_rank: int, qk_nope_head_dim: int,
                 qk_rope_head_dim: int, v_head_dim: int, index_n_heads: int,
                 index_head_dim: int, mlp_intermediate: int,
                 moe_intermediate: int, router_outputs: int,
                 experts_held: int, n_shared_experts: int,
                 **_) -> dict[str, tuple[int, ...]]:
    d, H, Rq, R = d_model, num_heads, q_lora_rank, kv_lora_rank
    Dn, Dr, Dv = qk_nope_head_dim, qk_rope_head_dim, v_head_dim
    Hi, Di = index_n_heads, index_head_dim
    mlp = lambda b, w: {f"{b}gate_proj": (d, w), f"{b}up_proj": (d, w),
                        f"{b}down_proj": (w, d)}
    out = {
        "input_layernorm": (d,),
        "self_attn/q_a_proj": (d, Rq), "self_attn/q_a_layernorm": (Rq,),
        "self_attn/q_b_proj": (Rq, H * (Dn + Dr)),
        "self_attn/kv_a_proj_with_mqa": (d, R + Dr),
        "self_attn/kv_a_layernorm": (R,),
        "self_attn/kv_b_proj": (R, H * (Dn + Dv)),
        "self_attn/o_proj": (H * Dv, d),
        "self_attn/indexer/wq_b": (Rq, Hi * Di),
        "self_attn/indexer/wk": (d, Di),
        "self_attn/indexer/k_norm/weight": (Di,),
        "self_attn/indexer/k_norm/bias": (Di,),
        "self_attn/indexer/weights_proj": (d, Hi),
        "post_attention_layernorm": (d,),
    }
    if dense:
        out.update(mlp("mlp/", mlp_intermediate))
    else:
        Eh, fm = experts_held, moe_intermediate
        out.update({
            "mlp/gate": (d, router_outputs),
            "mlp/gate_bias": (router_outputs,),
            "mlp/experts/gate_proj": (Eh, d, fm),
            "mlp/experts/up_proj": (Eh, d, fm),
            "mlp/experts/down_proj": (Eh, fm, d),
            **mlp("mlp/shared_experts/", fm * n_shared_experts),
        })
    return out


def top_shapes(*, vocab_size: int, d_model: int, **_) -> dict:
    return {"embed_tokens": (vocab_size, d_model), "norm": (d_model,),
            "lm_head": (vocab_size, d_model)}


def _leaf(key, path: str, shape, dtype):
    name = path.rsplit("/", 1)[-1]
    k = jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)
    if name == "gate_bias":
        return BIAS_STD * jax.random.normal(k, shape, jnp.float32)
    if path.endswith("k_norm/bias"):
        return jnp.zeros(shape, dtype)
    if len(shape) == 1:  # the norms' weights
        return jnp.ones(shape, dtype)
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


def is_dense(sizes: dict, i: int) -> bool:
    return int(i) < int(sizes["first_k_dense_replace"])


def make_layer(seed: int, sizes: dict, i: int, dtype=None):
    """Layer ``i``'s subtree: dense below ``first_k_dense_replace``."""
    key = jax.random.fold_in(seed_key(seed), 1 + int(i))
    return _build(layer_shapes(is_dense(sizes, i), **sizes),
                  dtype or DTYPE)(key)


def make_top(seed: int, sizes: dict, dtype=None):
    """Embedding, final norm and the untied head."""
    return _build(top_shapes(**sizes), dtype or DTYPE)(
        jax.random.fold_in(seed_key(seed), 0)
    )


def make_params(seed: int, sizes: dict, dtype=None):
    """The whole tree the program takes."""
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
