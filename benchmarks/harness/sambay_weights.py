"""Seeded weights for the SambaY tree (HF ``phi4flash``), made on the
device, one layer at a time.

The benchmark makes the weights (not the program), so the program under
test and the plain reference start from the same numbers. A layer is a
pure function of (seed, layer index): the program's tree is built layer
by layer with its matrices in the stored dtype (bfloat16), and the
reference remakes ONE layer at a time as the float32 copy of those
values, so the 32-layer float32 model never stands in memory at once.

Tree layout (names and shapes) is ``ddp_tpu/models/sambay.py``'s
``leaf_shapes``: ``embed_tokens [V, d]`` (also the head),
``final_layernorm/{weight, bias}``, and per layer
``input_layernorm``, ``post_attention_layernorm`` ``{weight, bias} [d]``,
``mlp/{input_linear [d, 2f], output_linear [f, d]}`` and one of
``mamba/{in_proj [d, 2C], conv1d/{weight [K, C], bias}, x_proj [C, R +
2N], dt_proj/{weight [R, C], bias}, A_log [N, C], D [C], out_proj [C,
d]}``, ``attn/{Wqkv | Wq, out_proj}/{weight, bias}`` with
``attn/{lambda_q1, lambda_k1, lambda_q2, lambda_k2 [Dh], subln [2 Dh]}``,
or ``gmu/{in_proj [d, C], out_proj [C, d]}``.

Values, the configuration's ``assumed.weights``: matrices normal(0,
0.02); biases 0; norm weights 1; the four attention vectors normal(0,
0.1); Mamba-1's own initialisation for the recurrence: ``A_log =
log(1..N)`` for every channel, the time-step bias the inverse softplus
of a log-uniform [0.001, 0.1] step, ``D`` = 1, the convolution uniform
+-1/2. A step's decay then runs from ~0.999 down to ~0.2 and the state
matters to the logits. Vectors stay float32 in both trees.
"""

from __future__ import annotations

import math
import zlib

import jax
import jax.numpy as jnp

from benchmarks.harness.weights import INIT_STD, nest, seed_key


def layer_shapes(kind: str, *, d_model: int, num_heads: int,
                 num_kv_heads: int, head_dim: int, mamba_d_inner: int,
                 mamba_d_state: int, mamba_d_conv: int, mamba_dt_rank: int,
                 mlp_intermediate: int, **_) -> dict[str, tuple[int, ...]]:
    d, f, C, N = d_model, mlp_intermediate, mamba_d_inner, mamba_d_state
    H, Hkv, Dh, R = num_heads, num_kv_heads, head_dim, mamba_dt_rank
    norm = lambda b: {f"{b}/weight": (d,), f"{b}/bias": (d,)}
    if kind == "mamba":
        mixer = {
            "mamba/in_proj": (d, 2 * C),
            "mamba/conv1d/weight": (mamba_d_conv, C),
            "mamba/conv1d/bias": (C,),
            "mamba/x_proj": (C, R + 2 * N),
            "mamba/dt_proj/weight": (R, C),
            "mamba/dt_proj/bias": (C,),
            "mamba/A_log": (N, C),
            "mamba/D": (C,),
            "mamba/out_proj": (C, d),
        }
    elif kind == "gmu":
        mixer = {"gmu/in_proj": (d, C), "gmu/out_proj": (C, d)}
    else:
        proj = "Wq" if kind == "cross" else "Wqkv"
        q_out = H * Dh if kind == "cross" else (H + 2 * Hkv) * Dh
        mixer = {
            f"attn/{proj}/weight": (d, q_out),
            f"attn/{proj}/bias": (q_out,),
            "attn/out_proj/weight": (H * Dh, d),
            "attn/out_proj/bias": (d,),
            **{f"attn/lambda_{n}": (Dh,) for n in ("q1", "k1", "q2", "k2")},
            "attn/subln": (2 * Dh,),
        }
    return {
        **norm("input_layernorm"), **mixer,
        **norm("post_attention_layernorm"),
        "mlp/input_linear": (d, 2 * f),
        "mlp/output_linear": (f, d),
    }


def top_shapes(*, vocab_size: int, d_model: int, **_) -> dict:
    return {"embed_tokens": (vocab_size, d_model),
            "final_layernorm/weight": (d_model,),
            "final_layernorm/bias": (d_model,)}


def _leaf(key, path: str, shape, dtype):
    name = path.rsplit("/", 1)[-1]
    k = jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)
    if path.endswith("conv1d/weight"):
        return jax.random.uniform(k, shape, jnp.float32, -0.5, 0.5)
    if path.endswith("dt_proj/bias"):
        dt = jnp.exp(jax.random.uniform(
            k, shape, jnp.float32, math.log(1e-3), math.log(1e-1)))
        return dt + jnp.log(-jnp.expm1(-dt))  # softplus^-1
    if name == "A_log":
        return jnp.broadcast_to(
            jnp.log(jnp.arange(1, shape[0] + 1, dtype=jnp.float32))[:, None],
            shape)
    if name.startswith("lambda_"):
        return 0.1 * jax.random.normal(k, shape, jnp.float32)
    if name == "bias":
        return jnp.zeros(shape, jnp.float32)
    if len(shape) == 1:  # D, the norms' weights
        return jnp.ones(shape, jnp.float32)
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
