"""Seeded weights for the GPT-2 block stack, made on the device.

The benchmark makes the weights (not the program), so the program under
test and the plain reference start from the same numbers without the
reference taking anything the program made. One jitted call builds the
whole tree from the seed in the dtype asked for; a single leaf can be
rebuilt alone (``leaf``), which is how the reference streams a deep
model layer by layer.

Tree layout (names and shapes) is that of a Flax GPT-2 block stack:
``embed [V, d]``, ``pos_embed [1, T, d]``, ``block{i}/{ln1, attn/{qkv,
proj}, ln2, mlp1, mlp2}``, ``ln_final``. The fused qkv kernel's columns
are head-major: ``[head, (q|k|v), head_dim]``.

Values follow the family's convention (``initializer_range`` 0.02 in
the published config): normal(0, 0.02) for embeddings and kernels,
zeros for biases, ones for LayerNorm scales.
"""

from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp

INIT_STD = 0.02


def leaf_shapes(
    *, vocab_size: int, seq_len: int, d_model: int, depth: int,
    mlp_ratio: int = 4,
) -> dict[str, tuple[int, ...]]:
    """Flat ``path -> shape`` in a fixed order ('/'-joined paths)."""
    d, m = d_model, mlp_ratio * d_model
    out: dict[str, tuple[int, ...]] = {
        "embed": (vocab_size, d),
        "pos_embed": (1, seq_len, d),
    }
    for i in range(1, depth + 1):
        b = f"block{i}"
        out.update({
            f"{b}/ln1/scale": (d,), f"{b}/ln1/bias": (d,),
            f"{b}/attn/qkv/kernel": (d, 3 * d),
            f"{b}/attn/qkv/bias": (3 * d,),
            f"{b}/attn/proj/kernel": (d, d),
            f"{b}/attn/proj/bias": (d,),
            f"{b}/ln2/scale": (d,), f"{b}/ln2/bias": (d,),
            f"{b}/mlp1/kernel": (d, m), f"{b}/mlp1/bias": (m,),
            f"{b}/mlp2/kernel": (m, d), f"{b}/mlp2/bias": (d,),
        })
    out["ln_final/scale"] = (d,)
    out["ln_final/bias"] = (d,)
    return out


def seed_key(seed: int):
    """A PRNG key from any non-negative whole number (the driver's
    seeds pass 2**31)."""
    seed = int(seed)
    return jax.random.fold_in(
        jax.random.key(seed & 0x7FFFFFFF), seed >> 31
    )


def leaf(key, path: str, shape, dtype=jnp.float32):
    """One leaf from the run's key: a pure function of (key, path)."""
    name = path.rsplit("/", 1)[-1]
    if name == "bias":
        return jnp.zeros(shape, dtype)
    if name == "scale":
        return jnp.ones(shape, dtype)
    k = jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)
    return (INIT_STD * jax.random.normal(k, shape, jnp.float32)).astype(
        dtype
    )


def nest(flat: dict) -> dict:
    """'/'-joined paths -> nested dicts (the tree the program takes)."""
    tree: dict = {}
    for path, v in flat.items():
        node = tree
        *parents, last = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[last] = v
    return tree


def flatten(tree, prefix: str = "") -> dict:
    """Nested dicts -> '/'-joined paths (inverse of ``nest``)."""
    out = {}
    for k, v in tree.items():
        p = f"{prefix}/{k}" if prefix else str(k)
        if isinstance(v, dict):
            out.update(flatten(v, p))
        else:
            out[p] = v
    return out


def make_params(seed: int, sizes: dict, *, dtype=jnp.float32,
                out_shardings=None):
    """The whole tree in ONE jitted call, on the device, from the seed.

    ``sizes``: vocab_size, seq_len, d_model, depth[, mlp_ratio].
    ``out_shardings``: a nested tree of shardings (e.g. those of the
    leaves this tree replaces), or None for the default device.
    """
    shapes = leaf_shapes(**sizes)

    def build(key):
        return nest({
            p: leaf(key, p, s, dtype) for p, s in shapes.items()
        })

    return jax.jit(build, out_shardings=out_shardings)(seed_key(seed))
