"""The ``sambay`` block (HF ``model_type`` ``phi4flash``): a decoder in
two halves, for serving one token a step.

The SELF-decoder is Mamba-1 layers alternating with differential
attention over a sliding window, closed by one differential attention
layer over the whole context. The CROSS-decoder keeps no state of its
own: its gated memory units read the LAST Mamba layer's scan output at
the same position, and its cross-attention layers read the full
layer's K and V. ``LMSpec.block == "sambay"`` names the stack and
``spec.layer_types`` says which layer is which (:func:`layer_table` is
the published rule). Everything here is a function of the parameter
tree and the spec; the serve engine jits :func:`prefill_chunk` and
:func:`slot_decode_sample_step` under the GPT-2 path's signatures.

**The model** (LN = LayerNorm with weight and bias, eps
``layer_norm_eps``, float32): ``x0 = embed_tokens[tokens]``; every layer
``x = x + mixer_i(LN(x))`` then ``x = x + mlp(LN(x))``;
``logits = LN(x) @ embed_tokens^T``. No position table, no rotary.

- MLP: ``[g, u] = split(x @ input_linear)``, ``(u * silu(g)) @
  output_linear`` (``granite_hybrid.mlp``).
- Mamba-1 (``"mamba"``): ``[xs, z] = split(x @ in_proj)``;
  ``xs = silu(causal_conv(xs))``; ``[r, B, C] = split(xs @ x_proj)``;
  ``dt = softplus(r @ dt_proj + b)``, one a channel; ``A = -exp(A_log)``
  ``[N, C]``; ``h_t = exp(dt_t A) h_{t-1} + dt_t B_t xs_t``,
  ``y_t = h_t . C_t + D xs_t`` (ops/ssm.py); ``(y * silu(z)) @
  out_proj``. The last Mamba layer also hands ``m = y`` (before the
  gate) to the cross-decoder.
- Differential attention (``"window"``, ``"full"``): ``[q, k, v] =
  split(x @ Wqkv + b)``; adjacent heads pair (ops/decode.py): a query
  pair's two softmax maps ``a1``, ``a2`` over the kv pair's ``2 Dh``
  wide value; ``lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lam_init``,
  ``lam_init = 0.8 - 0.6 exp(-0.3 i)`` for layer ``i``;
  ``RMSNorm(a1 - lam a2; subln) * (1 - lam_init)``; ``@ out_proj + b``.
  Causal; a window layer attends a position and the ``W - 1`` before it.
- Cross-attention (``"cross"``): ``q = x @ Wq + b`` alone; K and V are
  the full layer's, as stored; the same differential form.
- Gated memory unit (``"gmu"``): ``(m * silu(x @ in_proj)) @ out_proj``.

Residual stream, norms, softmax, the convolution, ``dt`` and decay
arithmetic, state, tail and K/V rows are float32; every matmul takes
its operands in the WEIGHT's dtype and accumulates in float32
(``sdar._mm``).

**A lane** (``generate.SlotCache``) holds three kinds of state:
a ring of ``W`` K/V rows for each window layer (position p at row
``p mod W``), ``total_len`` rows for the full layer, and a state
``[N, C]`` with a convolution tail ``[K - 1, C]`` for each Mamba layer;
:func:`layer_rows` maps a layer to its row in its store. The rules of
``granite_hybrid``'s recurrent lane hold (reset inside the first
chunk's program, inert padding, only ``cache.live`` lanes move), and a
ring adds two: a row is written for a REAL position of a LIVE lane
only, and a chunk's window layers attend the ring's rows and the
chunk's BEFORE the chunk's rows replace the ring's.

**Prefill stops at the full layer.** The cross-decoder writes nothing
into a lane, so a prompt's positions need it only where a token is
sampled: the final chunk runs it at its last real position, behind the
``lax.cond`` that guards sampling (``generate.install_lane_sampling``);
no other chunk streams its weights. Exact, not an approximation.
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from ddp_tpu.models.generate import (
    SlotCache,
    _kv_heads,
    install_lane_sampling,
    sample_slot_tokens,
)
from ddp_tpu.models.granite_hybrid import _embed, _lane, mlp
from ddp_tpu.models.lm import LMSpec, head_dim_of
from ddp_tpu.models.sdar import _mm, rms_norm
from ddp_tpu.ops import ssm
from ddp_tpu.ops.decode import diff_decode_attention, read_lane

BLOCK = "sambay"
MAMBA, WINDOW, FULL, GMU, CROSS = "mamba", "window", "full", "gmu", "cross"
KINDS = (MAMBA, WINDOW, FULL, GMU, CROSS)
INIT_STD = 0.02
SUBLN_EPS = 1e-5
# what the serve engine asks of a block's module (serve/engine.py)
RECURRENT = True


def layer_table(depth: int) -> tuple[str, ...]:
    """The published layer table at ``depth`` layers (``mb_per_layer``
    2): Mamba on even and window attention on odd indices up to the
    middle, the full layer after it, then gated memory units on even
    and cross-attention on odd indices. 32 -> 9 / 8 / 1 / 7 / 7."""
    half = depth // 2
    out = []
    for i in range(depth):
        if i <= half:
            out.append(WINDOW if i % 2 else MAMBA)
        elif i == half + 1:
            out.append(FULL)
        else:
            out.append(CROSS if i % 2 else GMU)
    return tuple(out)


def full_index(spec: LMSpec) -> int:
    return tuple(spec.layer_types).index(FULL)


def readout_index(spec: LMSpec) -> int:
    """The Mamba layer whose scan output the gated memory units read:
    the self-decoder's last."""
    kinds = tuple(spec.layer_types)
    return max(i for i in range(full_index(spec)) if kinds[i] == MAMBA)


def validate(spec: LMSpec) -> None:
    """Raise ValueError unless ``spec`` names this module's model."""
    if spec.block != BLOCK:
        raise ValueError(f"block {spec.block!r} is not {BLOCK!r}")
    kinds = tuple(spec.layer_types)
    if len(kinds) != spec.depth or set(kinds) - set(KINDS):
        raise ValueError(
            f"the {BLOCK} block needs layer_types: one of {KINDS} for each "
            f"of its {spec.depth} layers, got {kinds}"
        )
    if kinds.count(FULL) != 1:
        raise ValueError(
            f"the {BLOCK} block has ONE full attention layer, whose K/V the "
            f"cross-decoder reads; layer_types {kinds} has "
            f"{kinds.count(FULL)}"
        )
    f = kinds.index(FULL)
    if (set(kinds[:f]) - {MAMBA, WINDOW} or set(kinds[f + 1:]) - {GMU, CROSS}
            or MAMBA not in kinds[:f]):
        raise ValueError(
            f"layer_types {kinds}: Mamba and window layers (at least one "
            "Mamba) come before the full layer, gated memory units and "
            "cross-attention after it"
        )
    if min(spec.mamba_d_inner, spec.mamba_d_state, spec.mamba_dt_rank,
           spec.mlp_intermediate) < 1 or spec.mamba_d_conv < 2:
        raise ValueError(
            "the block needs mamba_d_inner, mamba_d_state, mamba_dt_rank, "
            "mlp_intermediate >= 1 and mamba_d_conv >= 2"
        )
    if WINDOW in kinds and spec.sliding_window < 1:
        raise ValueError("window layers need sliding_window >= 1")
    if spec.position_embedding != "nope" or not spec.tie_embeddings:
        raise ValueError(
            f"the {BLOCK} block has no positions and a tied head: "
            "position_embedding must be 'nope' and tie_embeddings true, "
            f"got {spec.position_embedding!r} and {spec.tie_embeddings}"
        )
    H, Hkv = spec.num_heads, _kv_heads(spec)
    if H % 2 or Hkv % 2 or (H // 2) % (Hkv // 2):
        raise ValueError(
            f"differential attention pairs adjacent heads: {H} query and "
            f"{Hkv} kv heads do not pair"
        )
    if spec.block_length:
        raise ValueError(
            f"the {BLOCK} block generates one token a step: "
            f"block_length must be 0, got {spec.block_length}"
        )


def layer_rows(spec: LMSpec) -> tuple[tuple[str, int], ...]:
    """Layer number -> (its kind, its row in that kind's store: ``ssm``/
    ``conv`` for a Mamba layer, ``ring_k``/``ring_v`` for a window
    layer, ``k``/``v`` row 0 for the full layer AND every
    cross-attention layer that reads it; a gated memory unit has no
    store and row -1)."""
    seen = {MAMBA: 0, WINDOW: 0}
    out = []
    for kind in spec.layer_types:
        if kind in seen:
            out.append((kind, seen[kind]))
            seen[kind] += 1
        else:
            out.append((kind, -1 if kind == GMU else 0))
    return tuple(out)


def lane_bytes(spec: LMSpec) -> dict[str, int]:
    """Float32 bytes one lane holds, by kind of state."""
    kinds = tuple(spec.layer_types)
    row = 2 * _kv_heads(spec) * head_dim_of(spec) * 4
    return {
        "ring": kinds.count(WINDOW) * spec.sliding_window * row,
        "shared": spec.total_len * row,
        "state": kinds.count(MAMBA) * spec.mamba_d_inner * 4 * (
            spec.mamba_d_state + spec.mamba_d_conv - 1),
    }


def attended_rows(spec: LMSpec, rows: list[int]) -> tuple[int, int]:
    """What one decode step reads of K/V, from the rows ``pos + 1``
    each decoding lane attends: (ring rows over the window layers,
    shared rows over the full layer and its cross-attention readers)."""
    kinds = tuple(spec.layer_types)
    W = spec.sliding_window
    return (kinds.count(WINDOW) * sum(min(r, W) for r in rows),
            (1 + kinds.count(CROSS)) * sum(rows))


def leaf_shapes(spec: LMSpec) -> dict[str, tuple[int, ...]]:
    """Flat ``path -> shape`` of the parameter tree ('/'-joined). A
    linear layer's weight is stored ``[in, out]``, ``A_log`` ``[N, C]``
    as the state is laid out."""
    d, Dh, f = spec.d_model, head_dim_of(spec), spec.mlp_intermediate
    H, Hkv = spec.num_heads, _kv_heads(spec)
    C, N, K, R = (spec.mamba_d_inner, spec.mamba_d_state, spec.mamba_d_conv,
                  spec.mamba_dt_rank)
    norm = lambda b: {f"{b}/weight": (d,), f"{b}/bias": (d,)}
    out = {"embed_tokens": (spec.vocab_size, d)}
    for i, kind in enumerate(spec.layer_types):
        b = f"layers/{i}"
        out.update(norm(f"{b}/input_layernorm"))
        if kind == MAMBA:
            out.update({
                f"{b}/mamba/in_proj": (d, 2 * C),
                f"{b}/mamba/conv1d/weight": (K, C),
                f"{b}/mamba/conv1d/bias": (C,),
                f"{b}/mamba/x_proj": (C, R + 2 * N),
                f"{b}/mamba/dt_proj/weight": (R, C),
                f"{b}/mamba/dt_proj/bias": (C,),
                f"{b}/mamba/A_log": (N, C),
                f"{b}/mamba/D": (C,),
                f"{b}/mamba/out_proj": (C, d),
            })
        elif kind == GMU:
            out.update({
                f"{b}/gmu/in_proj": (d, C),
                f"{b}/gmu/out_proj": (C, d),
            })
        else:
            q_out = H * Dh if kind == CROSS else (H + 2 * Hkv) * Dh
            proj = "Wq" if kind == CROSS else "Wqkv"
            out.update({
                f"{b}/attn/{proj}/weight": (d, q_out),
                f"{b}/attn/{proj}/bias": (q_out,),
                f"{b}/attn/out_proj/weight": (H * Dh, d),
                f"{b}/attn/out_proj/bias": (d,),
                **{f"{b}/attn/lambda_{n}": (Dh,)
                   for n in ("q1", "k1", "q2", "k2")},
                f"{b}/attn/subln": (2 * Dh,),
            })
        out.update(norm(f"{b}/post_attention_layernorm"))
        out.update({
            f"{b}/mlp/input_linear": (d, 2 * f),
            f"{b}/mlp/output_linear": (f, d),
        })
    out.update(norm("final_layernorm"))
    return out


def init_leaf(key, path: str, shape, dtype):
    """One seeded leaf: matrices normal(0, 0.02) in ``dtype``; biases
    0 and norm weights 1; the four attention vectors normal(0, 0.1);
    Mamba-1's own initialisation for the recurrence: ``A_log =
    log(1..N)`` for every channel, the time-step bias the inverse
    softplus of a log-uniform [0.001, 0.1] step, ``D`` 1, the
    convolution uniform +-1/2. Vectors stay float32."""
    name = path.rsplit("/", 1)[-1]
    if path.endswith("conv1d/weight"):
        return jax.random.uniform(key, shape, jnp.float32, -0.5, 0.5)
    if path.endswith("dt_proj/bias"):
        dt = jnp.exp(jax.random.uniform(
            key, shape, jnp.float32, math.log(1e-3), math.log(1e-1)))
        return dt + jnp.log(-jnp.expm1(-dt))
    if name == "A_log":
        return jnp.broadcast_to(
            jnp.log(jnp.arange(1, shape[0] + 1, dtype=jnp.float32))[:, None],
            shape)
    if name.startswith("lambda_"):
        return 0.1 * jax.random.normal(key, shape, jnp.float32)
    if name == "bias":
        return jnp.zeros(shape, jnp.float32)
    if name in ("D", "subln") or len(shape) == 1:  # a norm's weight
        return jnp.ones(shape, jnp.float32)
    return (INIT_STD * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


def nest(flat: dict) -> dict:
    tree: dict = {}
    for path, leaf in flat.items():
        node = tree
        *parents, last = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[last] = leaf
    return tree


def init_params(spec: LMSpec, *, seed: int = 0, dtype=jnp.bfloat16):
    """The seeded tree, matrices stored in ``dtype``."""
    key = jax.random.key(seed)
    return nest({
        path: init_leaf(jax.random.fold_in(key, n), path, shape, dtype)
        for n, (path, shape) in enumerate(leaf_shapes(spec).items())
    })


def derive_spec(params: Any, *, num_heads: int = 0, **overrides) -> LMSpec:
    """The spec of a restored tree: every size the shapes show (the
    layer table among them: the last layer with its own K/V projection
    is the full one), the rest (head counts, window, cache length,
    epsilon) from the ``lm_spec.json`` sidecar."""
    try:
        vocab_size, d_model = (int(s) for s in params["embed_tokens"].shape)
        layers = params["layers"]
        own_kv = [i for i in range(len(layers))
                  if "Wqkv" in layers[str(i)].get("attn", {})]

        def kind(i):
            layer = layers[str(i)]
            if "mamba" in layer:
                return MAMBA
            if "gmu" in layer:
                return GMU
            if "Wq" in layer["attn"]:
                return CROSS
            return FULL if i == own_kv[-1] else WINDOW

        kinds = tuple(kind(i) for i in range(len(layers)))
        m = layers[str(kinds.index(MAMBA))]["mamba"]
        fields = dict(
            vocab_size=vocab_size, d_model=d_model, depth=len(kinds),
            layer_types=kinds, block=BLOCK,
            mamba_d_inner=int(m["D"].shape[0]),
            mamba_d_state=int(m["A_log"].shape[0]),
            mamba_d_conv=int(m["conv1d"]["weight"].shape[0]),
            mamba_dt_rank=int(m["dt_proj"]["weight"].shape[0]),
            mlp_intermediate=int(
                layers["0"]["mlp"]["output_linear"].shape[0]),
        )
    except (KeyError, TypeError, AttributeError, IndexError, ValueError) as e:
        raise ValueError(f"not a {BLOCK} parameter tree (missing {e})")
    fields.update(
        (k, v) for k, v in overrides.items()
        if k in LMSpec._fields and k not in fields
    )
    if num_heads and "num_heads" not in fields:
        fields["num_heads"] = num_heads
    if "total_len" not in fields:
        raise ValueError(
            f"a {BLOCK} checkpoint has no position table: its "
            "lm_spec.json must give total_len (the cache's length)"
        )
    spec = LMSpec(**fields)
    validate(spec)
    return spec


def save_checkpoint(directory: str, spec: LMSpec, params, *,
                    epoch: int = 0) -> None:
    """Write ``params`` as a checkpoint ``scripts/serve.py`` restores,
    with the ``lm_spec.json`` sidecar that carries what the shapes
    cannot (``granite_hybrid.save_checkpoint``'s twin)."""
    from ddp_tpu.train.checkpoint import save_params_with_spec

    validate(spec)
    save_params_with_spec(directory, spec, params, epoch=epoch)


# ---- the layers -------------------------------------------------------


def layer_norm(x, p, eps: float):
    x32 = x.astype(jnp.float32)
    mean = x32.mean(-1, keepdims=True)
    var = ((x32 - mean) ** 2).mean(-1, keepdims=True)
    return ((x32 - mean) * lax.rsqrt(var + eps)
            * p["weight"].astype(jnp.float32) + p["bias"].astype(jnp.float32))


def _linear(x, p):
    return _mm(x, p["weight"]) + p["bias"].astype(jnp.float32)


def head_logits(spec: LMSpec, params, x):
    return _mm(layer_norm(x, params["final_layernorm"], spec.layer_norm_eps),
               params["embed_tokens"], transposed=True)


def attn_qkv(spec: LMSpec, p, u):
    """Normed ``u`` ``[..., d]`` -> q ``[..., H, Dh]``, k and v as they
    are stored: a position's kv heads side by side, ``[..., H_kv * Dh]``."""
    Dh, H = head_dim_of(spec), spec.num_heads
    q, k, v = jnp.split(
        _linear(u, p["Wqkv"]), [H * Dh, (H + _kv_heads(spec)) * Dh], axis=-1)
    return q.reshape(*q.shape[:-1], H, Dh), k, v


def cross_q(spec: LMSpec, p, u):
    q = _linear(u, p["Wq"])
    return q.reshape(*q.shape[:-1], spec.num_heads, head_dim_of(spec))


def lam_init(i: int) -> float:
    return 0.8 - 0.6 * math.exp(-0.3 * i)


def diff_out(p, a, i: int):
    """The two maps of every head pair ``a`` ``[..., H / 2, 2, 2 Dh]``
    of layer ``i`` -> the mixer's output: subtract, norm each pair's
    ``2 Dh``, scale, the pairs side by side through ``out_proj``."""
    f32 = lambda n: p[n].astype(jnp.float32)
    lam = (jnp.exp(jnp.sum(f32("lambda_q1") * f32("lambda_k1")))
           - jnp.exp(jnp.sum(f32("lambda_q2") * f32("lambda_k2")))
           + lam_init(i))
    out = rms_norm(a[..., 0, :] - lam * a[..., 1, :], p["subln"], SUBLN_EPS)
    out = out * (1.0 - lam_init(i))
    return _linear(out.reshape(*out.shape[:-2], -1), p["out_proj"])


def diff_attention(q, k, v, mask):
    """Both softmax maps of every head pair over a run of one lane's
    tokens: ``q`` ``[T, H, Dh]``, ``k``/``v`` ``[S, H_kv * Dh]`` rows as
    stored, ``mask`` ``[T, S]`` bool -> ``[T, H / 2, 2, 2 Dh]``
    (ops/decode.py has the pairing)."""
    T, H, Dh = q.shape
    S = k.shape[0]
    G = k.shape[1] // (2 * Dh)
    qg = q.reshape(T, G, H // (2 * G), 2, Dh).astype(jnp.float32)
    kg = k.reshape(S, G, 2, Dh).astype(jnp.float32)
    vg = v.reshape(S, G, 2 * Dh).astype(jnp.float32)
    s = jnp.einsum("tgjwd,sgwd->gjwts", qg, kg) * Dh**-0.5
    w = jax.nn.softmax(jnp.where(mask[None, None, None], s, -jnp.inf), -1)
    a = jnp.einsum("gjwts,sge->tgjwe", w, vg)
    return a.reshape(T, H // 2, 2, 2 * Dh)


def _attend_mask(qpos, kpos, window: int = 0):
    """``[T, S]``: key at ``kpos`` (negative: no key there) is at or
    before the query at ``qpos`` and, with ``window``, among the
    ``window`` positions that end at it."""
    d = qpos[:, None] - kpos[None, :]
    ok = (kpos[None, :] >= 0) & (d >= 0)
    return ok & (d < window) if window else ok


def mamba_inputs(p, u):
    """Normed ``u`` ``[..., d]`` -> (xs before the convolution, the
    gate z), ``[..., C]`` each."""
    return jnp.split(_mm(u, p["in_proj"]), 2, axis=-1)


def mamba_terms(spec: LMSpec, p, conv):
    """The convolution's output -> (xs, dt after softplus ``[..., C]``,
    B, C ``[..., N]``)."""
    R, N = spec.mamba_dt_rank, spec.mamba_d_state
    xs = jax.nn.silu(conv)
    r, B, C = jnp.split(_mm(xs, p["x_proj"]), [R, R + N], axis=-1)
    return xs, jax.nn.softplus(_linear(r, p["dt_proj"])), B, C


def _mamba_A(p):
    return -jnp.exp(p["A_log"].astype(jnp.float32))


def mamba_run(spec: LMSpec, p, u, tail, state, length, *,
              scan_impl: str = "auto"):
    """The Mamba mixer over a run of one lane's tokens: ``u`` ``[T, d]``
    normed, from the convolution's ``tail`` ``[K-1, C]`` and ``state``
    ``[N, C]`` -> (output ``[T, d]``, the scan's output ``y`` with the
    ``D`` term ``[T, C]``, the tail and the state after position
    ``length - 1``). Positions from ``length`` on are padding: their
    ``dt`` is 0, so they neither move the state nor reach it, and the
    tail is cut before them."""
    w = p["conv1d"]
    pre, z = mamba_inputs(p, u)
    xs, dt, B, C = mamba_terms(
        spec, p, ssm.causal_conv(pre, tail, w["weight"], w["bias"]))
    dt = jnp.where((jnp.arange(u.shape[0]) < length)[:, None], dt, 0.0)
    y, state = ssm.selective_scan(xs, dt, _mamba_A(p), B, C, state,
                                  impl=scan_impl)
    y = y + xs * p["D"].astype(jnp.float32)[None, :]
    return (_mm(y * jax.nn.silu(z), p["out_proj"]), y,
            ssm.conv_tail(pre, tail, length), state)


def gmu(p, u, m):
    return _mm(m * jax.nn.silu(_mm(u, p["in_proj"])), p["out_proj"])


def forward_layers(spec: LMSpec, params, x, mix, start: int = 0,
                   stop: int | None = None):
    """Layers ``[start, stop)`` over the residual stream ``x``.
    ``mix(i, kind, row, p, u)`` is the mixer of layer ``i`` on its
    normed input and owns whatever state it keeps: the cache writes,
    the masks, the read-out and the shared K/V are the caller's (dense
    forward, prefill chunk, decode step)."""
    eps = spec.layer_norm_eps
    rows = layer_rows(spec)
    for i in range(start, spec.depth if stop is None else stop):
        kind, row = rows[i]
        p = params["layers"][str(i)]
        x = x + mix(i, kind, row, p, layer_norm(x, p["input_layernorm"], eps))
        x = x + mlp(p["mlp"],
                    layer_norm(x, p["post_attention_layernorm"], eps))
    return x


def _cross_mix(spec: LMSpec, m, keys, vals, mask):
    """The cross-decoder's mixers over rows whose read-out is ``m`` and
    whose attendable shared rows ``mask`` ``[T, S]`` names."""

    def mix(i, kind, row, p, u):
        if kind == GMU:
            return gmu(p["gmu"], u, m)
        a = diff_attention(cross_q(spec, p["attn"], u), keys, vals, mask)
        return diff_out(p["attn"], a, i)

    return mix


def dense_logits(spec: LMSpec, params, tokens):
    """Full forward of ``tokens`` ``[N, T]``, no cache -> logits
    ``[N, T, V]``. The parity probe."""
    T = tokens.shape[1]
    pos = jnp.arange(T)
    zero_tail = jnp.zeros((spec.mamba_d_conv - 1, spec.mamba_d_inner))
    zero_state = jnp.zeros((spec.mamba_d_state, spec.mamba_d_inner))
    f, r = full_index(spec), readout_index(spec)

    def one(toks):
        kept = {}

        def mix(i, kind, row, p, u):
            if kind == MAMBA:
                out, y, _, _ = mamba_run(spec, p["mamba"], u, zero_tail,
                                         zero_state, T)
                if i == r:
                    kept["m"] = y
                return out
            q, k, v = attn_qkv(spec, p["attn"], u)
            if kind == FULL:
                kept["k"], kept["v"] = k, v
            window = spec.sliding_window if kind == WINDOW else 0
            return diff_out(
                p["attn"],
                diff_attention(q, k, v, _attend_mask(pos, pos, window)), i)

        x = forward_layers(spec, params, _embed(spec, params, toks), mix,
                           stop=f + 1)
        x = forward_layers(
            spec, params, x,
            _cross_mix(spec, kept["m"], kept["k"], kept["v"],
                       _attend_mask(pos, pos)),
            start=f + 1)
        return head_logits(spec, params, x)

    return jnp.stack([one(t) for t in tokens])


# ---- lanes -----------------------------------------------------------


def _put_lane(buf, row: int, slot, lane):
    return lax.dynamic_update_slice(buf, lane[None, None], (row, slot, 0, 0))


def _ring_positions(last, W: int):
    """The position each of a ring's ``W`` rows holds when the newest
    position written is ``last``: the latest ``p <= last`` with
    ``p mod W == r`` (negative: nothing of this request yet)."""
    r = jnp.arange(W)
    return last - jnp.mod(last - r, W)


def ring_attend(q, k, v, old_k, old_v, start, length, W: int, *,
                lane_attend: bool):
    """A window layer over a chunk: queries at positions ``start + t``
    attend the ring AS THE PREVIOUS CHUNKS LEFT IT (``old_k``/``old_v``
    ``[W, width]``: row r holds the latest position before ``start``
    that maps to it) and then the chunk's own rows, under the window
    mask. The first chunk (``lane_attend`` False) attends itself."""
    qpos = start + jnp.arange(q.shape[0])
    if lane_attend:
        k = jnp.concatenate([old_k, k.astype(old_k.dtype)])
        v = jnp.concatenate([old_v, v.astype(old_v.dtype)])
        kpos = jnp.concatenate([_ring_positions(start - 1, W), qpos])
    else:
        kpos = qpos
    return diff_attention(q, k, v, _attend_mask(qpos, kpos, W))


def ring_update(old, new, start, length):
    """The ring ``old`` ``[W, width]`` after a chunk whose rows are
    ``new`` ``[C, width]``: a row takes the chunk's latest REAL
    position (``< start + length``) that maps to it and is otherwise
    left as it was; a padded position writes nothing."""
    W, Cw = old.shape[0], new.shape[0]
    src = _ring_positions(start + length - 1, W)
    at = jnp.clip(src - start, 0, Cw - 1)
    return jnp.where((src >= start)[:, None], new[at].astype(old.dtype), old)


def prefill_chunk(
    spec: LMSpec, params: Any, cache: SlotCache, toks, seeds, steps,
    temps, top_ps, slot, chunk, start, length, final, seed, temperature,
    top_p, *, lane_attend: bool = True, scan_impl: str = "auto",
):
    """Ingest one chunk of a prompt into lane ``slot`` — models/
    generate.prefill_chunk's contract and signature: ``chunk`` ``[C]``
    holds positions ``[start, start + length)`` and padding after;
    ``lane_attend=False`` is the self-contained FIRST chunk (``start``
    0), which attends itself and starts every Mamba layer from ZERO
    state and a zero tail whatever the lane held: the reset at
    admission. The chunk runs the SELF-decoder: state and tail are
    those after position ``length - 1``; ring and full rows are written
    for the real positions only; a window layer attends the ring's rows
    and the chunk's under the window mask before the chunk's rows
    replace the ring's. The ``final`` chunk runs the cross-decoder at
    its last real position and samples the request's first token.
    ``cache.live`` is the caller's."""
    W, Cw = spec.sliding_window, chunk.shape[0]
    t = jnp.arange(Cw)
    qpos, real = start + t, t < length
    f, r = full_index(spec), readout_index(spec)
    box = {"k": cache.k, "v": cache.v, "rk": cache.ring_k,
           "rv": cache.ring_v, "ssm": cache.ssm, "conv": cache.conv}
    kept = {}

    def recur(i, row, p, u):
        if lane_attend:
            tail, state = _lane(box["conv"], row, slot), _lane(
                box["ssm"], row, slot)
        else:
            tail = jnp.zeros(box["conv"].shape[2:])
            state = jnp.zeros(box["ssm"].shape[2:])
        out, y, tail, state = mamba_run(spec, p, u, tail, state, length,
                                        scan_impl=scan_impl)
        box["ssm"] = _put_lane(box["ssm"], row, slot, state)
        box["conv"] = _put_lane(box["conv"], row, slot, tail)
        if i == r:
            kept["m"] = y
        return out

    def window(row, q, k, v):
        old_k = read_lane(box["rk"], row, slot)
        old_v = read_lane(box["rv"], row, slot)
        a = ring_attend(q, k, v, old_k, old_v, start, length, W,
                        lane_attend=lane_attend)
        box["rk"] = _put_lane(box["rk"], row, slot,
                              ring_update(old_k, k, start, length))
        box["rv"] = _put_lane(box["rv"], row, slot,
                              ring_update(old_v, v, start, length))
        return a

    def full(q, k, v):
        for name, new in (("k", k), ("v", v)):
            buf = box[name]
            old = lax.dynamic_slice(
                buf, (0, slot, start, 0), (1, 1, Cw, buf.shape[3]))
            box[name] = lax.dynamic_update_slice(
                buf, jnp.where(real[:, None], new.astype(buf.dtype),
                               old[0, 0])[None, None],
                (0, slot, start, 0))
        if lane_attend:
            # rows past the chunk's real positions are stale, and above
            # every real query
            keys, vals = read_lane(box["k"], 0, slot), read_lane(
                box["v"], 0, slot)
            kpos = jnp.arange(keys.shape[0])
        else:
            keys, vals, kpos = k, v, qpos
        kept.update(keys=keys, vals=vals, kpos=kpos)
        return diff_attention(q, keys, vals, _attend_mask(qpos, kpos))

    def mix(i, kind, row, p, u):
        if kind == MAMBA:
            return recur(i, row, p["mamba"], u)
        q, k, v = attn_qkv(spec, p["attn"], u)
        a = window(row, q, k, v) if kind == WINDOW else full(q, k, v)
        return diff_out(p["attn"], a, i)

    x = forward_layers(spec, params, _embed(spec, params, chunk), mix,
                       stop=f + 1)

    def last_logits():
        one = lambda a: lax.dynamic_slice_in_dim(a, length - 1, 1, axis=0)
        out = forward_layers(
            spec, params, one(x),
            _cross_mix(spec, one(kept["m"]), kept["keys"], kept["vals"],
                       _attend_mask(one(qpos), kept["kpos"])),
            start=f + 1)
        return head_logits(spec, params, out)[0]

    toks, seeds, steps, temps, top_ps, first = install_lane_sampling(
        toks, seeds, steps, temps, top_ps, slot, final, seed, temperature,
        top_p, last_logits,
    )
    put = lambda a, v: lax.dynamic_update_slice(
        a, jnp.asarray(v)[None].astype(a.dtype), (slot,))
    cache = cache._replace(
        k=box["k"], v=box["v"], ring_k=box["rk"], ring_v=box["rv"],
        ssm=box["ssm"], conv=box["conv"],
        pos=put(cache.pos, start + length),
    )
    return cache, toks, seeds, steps, temps, top_ps, first


def _put_rows(buf, row: int, new, at, live):
    """Row ``at[s]`` of lane s of layer row ``row`` takes ``new[s]``
    where the lane is live, in place: an idle lane's row stays bit for
    bit (in a ring it may be a live row)."""
    lanes = jnp.arange(new.shape[0], dtype=jnp.int32)
    old = buf[row, lanes, at]
    return buf.at[row, lanes, at].set(
        jnp.where(live[:, None], new.astype(buf.dtype), old),
        indices_are_sorted=True, unique_indices=True,
    )


def slot_decode_step(spec: LMSpec, params, cache: SlotCache, tokens, *,
                     attn_impl: str = "reference", ssm_impl: str = "auto"):
    """Advance the LIVE lanes one token through all the layers:
    ``tokens`` ``[S]``, lane s's token at ``cache.pos[s]`` -> (logits
    ``[S, V]``, cache). The read-out ``m`` lives inside the step. The
    full layer writes one K/V row a live lane and reads its lane; the
    cross-attention layers read the same rows; a window layer writes
    at ``pos mod W`` and reads ``min(pos + 1, W)`` rows. An idle lane
    rides along in the batch and its logits are garbage, but nothing of
    it moves: no row, no state, no tail, no ``pos``."""
    live, pos = cache.live, cache.pos
    lanes = ssm.live_lanes(live)
    W, r = spec.sliding_window, readout_index(spec)
    at = jnp.minimum(pos, spec.total_len - 1)
    ring_at, ring_last = jnp.mod(pos, W), jnp.minimum(pos, W - 1)
    box = [cache]
    kept = {}
    attend = lambda q, k, v, last, row: diff_decode_attention(
        q, k, v, last, layer=row, impl=attn_impl)

    def recur(i, row, p, u):
        c = box[0]
        w = p["conv1d"]
        pre, z = mamba_inputs(p, u)
        conv, tail = ssm.conv_step(pre, c.conv[row], w["weight"], w["bias"])
        xs, dt, B, C = mamba_terms(spec, p, conv)
        state, y = ssm.selective_state_update(
            c.ssm, row, xs, dt, _mamba_A(p), B, C, p["D"], live,
            impl=ssm_impl, lanes=lanes,
        )
        tail = jnp.where(live[:, None, None], tail, c.conv[row])
        box[0] = c._replace(ssm=state, conv=c.conv.at[row].set(tail))
        if i == r:
            kept["m"] = y
        return _mm(y * jax.nn.silu(z), p["out_proj"])

    def mix(i, kind, row, p, u):
        if kind == MAMBA:
            return recur(i, row, p["mamba"], u)
        if kind == GMU:
            return gmu(p["gmu"], u, kept["m"])
        c = box[0]
        if kind == CROSS:
            a = attend(cross_q(spec, p["attn"], u), c.k, c.v, at, 0)
            return diff_out(p["attn"], a, i)
        q, k, v = attn_qkv(spec, p["attn"], u)
        if kind == WINDOW:
            c = c._replace(ring_k=_put_rows(c.ring_k, row, k, ring_at, live),
                           ring_v=_put_rows(c.ring_v, row, v, ring_at, live))
            a = attend(q, c.ring_k, c.ring_v, ring_last, row)
        else:
            c = c._replace(k=_put_rows(c.k, 0, k, at, live),
                           v=_put_rows(c.v, 0, v, at, live))
            a = attend(q, c.k, c.v, at, 0)
        box[0] = c
        return diff_out(p["attn"], a, i)

    x = forward_layers(spec, params, _embed(spec, params, tokens), mix)
    cache = box[0]._replace(
        pos=jnp.where(live, jnp.minimum(pos + 1, spec.total_len), pos))
    return head_logits(spec, params, x), cache


def slot_decode_sample_step(spec: LMSpec, params, cache: SlotCache, tokens,
                            seeds, steps, temps, top_ps, *,
                            attn_impl: str = "reference",
                            ssm_impl: str = "auto"):
    """:func:`slot_decode_step` with the GPT-2 path's fused sampling ->
    (tokens ``[S]`` int32, cache, advanced step counters)."""
    logits, cache = slot_decode_step(
        spec, params, cache, tokens, attn_impl=attn_impl, ssm_impl=ssm_impl)
    toks = sample_slot_tokens(logits, seeds, steps, temps, top_ps)
    return toks, cache, steps + 1
