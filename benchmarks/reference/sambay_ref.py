"""The plain reference for Phi-4-mini-flash-reasoning (HF ``phi4flash``,
the SambaY decoder), in float32 ``jax.numpy``.

Written from the layer equations of the issue that brought the
configuration (ISSUE 36, section 1; PERF.md section 4), which are the
published description's. No kernel, no cache, no ring, no early exit:
every layer runs at every position; attention is the whole sequence
under an explicit ``[T, T]`` mask; the two softmax maps of a
differential pair are formed separately, each head against its own key
(no query is padded to reach both keys at once); the state-space
recurrence is a SEQUENTIAL ``lax.scan`` over time from a zero state.
Every matmul runs at ``precision="highest"``. Imports nothing of the
program under test. ``cfg`` holds the config's own keys and the
``assumed`` Mamba-1 sizes.

The model (LN = LayerNorm, weight and bias, eps ``layer_norm_eps``):
``x0 = embed[tokens]``; every layer ``x = x + mixer_i(LN(x))``,
``x = x + mlp(LN(x))``; ``logits = LN(x) @ embed^T``. No positions.
Layer ``i`` of ``n = num_hidden_layers``: even and ``<= n/2``: Mamba-1;
odd and ``< n/2``: differential attention over a window of
``sliding_window`` keys (the position's own among them); ``n/2 + 1``:
full differential attention, whose K and V are kept; after it even:
gated memory unit on the LAST Mamba layer's scan output ``m`` (with the
``D`` term, before the gate) at the same position; odd: differential
cross-attention, its own query against the kept K and V, causal.

- MLP: ``[g, u] = split(x W_gu)``, ``(u * silu(g)) W_down``.
- Mamba-1: ``[xs, z] = split(x W_in)``; ``xs = silu(conv1d_causal(xs) +
  b)``; ``[r, B, C] = split(xs W_x)``; ``dt = softplus(r W_dt + b_dt)``;
  ``A = -exp(A_log)``; ``h_t[c, n] = exp(dt_t[c] A[c, n]) h_{t-1}[c, n] +
  dt_t[c] B_t[n] xs_t[c]``; ``y_t[c] = sum_n h_t[c, n] C_t[n] + D[c]
  xs_t[c]``; ``(y * silu(z)) W_out``.
- Differential attention: heads pair ``(2p, 2p + 1) = (q1, q2)``; kv pair
  ``g`` is ``(k1, k2)``, ``V_g = [v1 | v2]``; query pairs ``2g, 2g + 1``
  read kv pair ``g``; ``a1 = softmax(q1 k1^T / sqrt(Dh)) V_g``, ``a2``
  likewise; ``lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lam_init``,
  ``lam_init = 0.8 - 0.6 exp(-0.3 i)``; ``RMSNorm(a1 - lam a2; w, eps
  1e-5) * (1 - lam_init)``; the pairs side by side; ``W_o + b_o``.
- GMU: ``(m * silu(x W_1)) W_2``.

Departures from the source, each the configuration's ``assumed``: the
tree is laid out as the program lays it out (a linear layer's weight
``[in, out]``, the convolution's ``[K, C]``, ``A_log`` ``[N, C]``),
which changes no number; weights are the float32 copies of the stored
bfloat16 values.

``precision`` selects the CONTROL (PERF.md section 2): ``"float32"`` is
the reference; ``"float8"`` rounds every matmul operand to float8 e4m3
with a per-tensor scale, which the comparison that decides ``correct``
has to reject. The recurrence and the convolution have no matmul and
stay float32 in both.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

SUBLN_EPS = 1e-5


def _round_operand(x, precision: str):
    if precision == "float32":
        return x
    if precision == "float8":
        scale = 448.0 / (jnp.max(jnp.abs(x)) + 1e-30)
        return (x * scale).astype(jnp.float8_e4m3fn).astype(
            jnp.float32) / scale
    raise ValueError(f"unknown precision {precision!r}")


def mm(a, w, precision: str = "float32"):
    return jnp.matmul(
        _round_operand(a.astype(jnp.float32), precision),
        _round_operand(w.astype(jnp.float32), precision),
        precision="highest",
    )


def layer_kind(i: int, cfg: dict) -> str:
    half = cfg["num_hidden_layers"] // 2
    if i <= half:
        return "window" if i % 2 else "mamba"
    if i == half + 1:
        return "full"
    return "cross" if i % 2 else "gmu"


def layer_norm(x, p, eps: float):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return ((x - mean) * jax.lax.rsqrt(var + eps)
            * p["weight"].astype(jnp.float32) + p["bias"].astype(jnp.float32))


def linear(x, p, precision: str):
    return mm(x, p["weight"], precision) + p["bias"].astype(jnp.float32)


def embed(embed_w, tokens):
    """``tokens`` ``[n, T]`` -> ``[n, T, d]``."""
    return embed_w.astype(jnp.float32)[tokens]


def mlp(x, p, precision: str = "float32"):
    g, u = jnp.split(mm(x, p["input_linear"], precision), 2, axis=-1)
    return mm(u * jax.nn.silu(g), p["output_linear"], precision)


def conv1d(x, w, b):
    """Depthwise causal: ``y[t] = b + sum_k w[k] x[t - (K-1) + k]``,
    zeros before the first token. ``x`` ``[n, T, C]``, ``w`` ``[K, C]``."""
    K, T = w.shape[0], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    return b + sum(w[k] * xp[:, k:k + T] for k in range(K))


def recurrence(xs, dt, A, B, C, D):
    """One token after another. ``xs``, ``dt`` ``[n, T, C]``, ``A``
    ``[C, N]``, ``B``, ``C`` ``[n, T, N]``, ``D`` ``[C]`` -> ``y``
    ``[n, T, C]``."""
    n, T, Cn = xs.shape

    def step(h, t):
        x_t, dt_t, B_t, C_t = t
        h = (jnp.exp(dt_t[:, :, None] * A[None]) * h
             + (dt_t * x_t)[:, :, None] * B_t[:, None, :])
        return h, jnp.sum(h * C_t[:, None, :], -1) + D[None] * x_t

    time_major = lambda a: jnp.moveaxis(a, 1, 0)
    _, y = jax.lax.scan(
        step, jnp.zeros((n, Cn, A.shape[1]), jnp.float32),
        tuple(time_major(a) for a in (xs, dt, B, C)),
    )
    return jnp.moveaxis(y, 0, 1)


def mamba(x, p, cfg: dict, precision: str = "float32"):
    """-> (the mixer's output ``[n, T, d]``, the scan's output ``y``
    with the ``D`` term ``[n, T, C]``)."""
    R, N = cfg["mamba_dt_rank"], cfg["mamba_d_state"]
    f32 = lambda a: a.astype(jnp.float32)
    xs, z = jnp.split(mm(x, p["in_proj"], precision), 2, axis=-1)
    xs = jax.nn.silu(conv1d(xs, f32(p["conv1d"]["weight"]),
                            f32(p["conv1d"]["bias"])))
    r, B, C = jnp.split(mm(xs, p["x_proj"], precision), [R, R + N], axis=-1)
    dt = jax.nn.softplus(linear(r, p["dt_proj"], precision))
    # stored [N, C] as the program lays its state out; the equations' [C, N]
    A = -jnp.exp(f32(p["A_log"])).T
    y = recurrence(xs, dt, A, B, C, f32(p["D"]))
    return mm(y * jax.nn.silu(z), p["out_proj"], precision), y


def causal_mask(T: int, window: int = 0):
    i = jnp.arange(T)
    d = i[:, None] - i[None, :]
    return (d >= 0) & (d < window) if window else d >= 0


def differential(q, k, v, p, i, cfg: dict, mask, precision: str):
    """``q`` ``[n, T, H Dh]``, ``k``, ``v`` ``[n, T, H_kv Dh]`` as
    projected, layer index ``i`` (a number, or an array where one
    compiled program serves every layer of a kind), ``mask`` ``[T, T]``
    -> ``[n, T, d]``."""
    n, T, _ = q.shape
    H, Hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    Dh = q.shape[-1] // H
    f32 = lambda name: p[name].astype(jnp.float32)
    per_kv = (H // 2) // (Hkv // 2)  # query pairs that read one kv pair
    # [.., pair, which of the pair, Dh]; a kv pair repeated for its readers
    q = _round_operand(q.reshape(n, T, H // 2, 2, Dh), precision)
    k = _round_operand(jnp.repeat(
        k.reshape(n, T, Hkv // 2, 2, Dh), per_kv, axis=2), precision)
    v = _round_operand(jnp.repeat(
        v.reshape(n, T, Hkv // 2, 2 * Dh), per_kv, axis=2), precision)
    init = 0.8 - 0.6 * jnp.exp(-0.3 * jnp.asarray(i, jnp.float32))
    lam = (jnp.exp(jnp.sum(f32("lambda_q1") * f32("lambda_k1")))
           - jnp.exp(jnp.sum(f32("lambda_q2") * f32("lambda_k2"))) + init)

    def one_map(which: int):
        """Every pair's map ``which`` (0: q1 on k1, 1: q2 on k2), each
        over its kv pair's whole ``2 Dh`` wide value."""
        s = jnp.einsum("nqpd,nkpd->npqk", q[:, :, :, which],
                       k[:, :, :, which], precision="highest")
        s = jnp.where(mask[None, None], s / math.sqrt(Dh), -jnp.inf)
        w = _round_operand(jax.nn.softmax(s, axis=-1), precision)
        return jnp.einsum("npqk,nkpe->nqpe", w, v, precision="highest")

    a = one_map(0) - lam * one_map(1)  # [n, T, pairs, 2 Dh]
    a = a * jax.lax.rsqrt(jnp.mean(a * a, -1, keepdims=True) + SUBLN_EPS)
    a = a * f32("subln") * (1.0 - init)
    return linear(a.reshape(n, T, H * Dh), p["out_proj"], precision)


def layer(x, carry: dict, p, i, cfg: dict, precision: str = "float32",
          kind: str | None = None):
    """Layer ``i`` -> (x, carry). ``carry`` holds what later layers
    read: ``m`` (the last Mamba layer's scan output) and ``k``, ``v``
    (the full layer's, as projected). ``kind`` is ``layer_kind(i)``,
    given by a caller whose ``i`` is an array."""
    eps = cfg["layer_norm_eps"]
    kind = kind or layer_kind(i, cfg)
    u = layer_norm(x, p["input_layernorm"], eps)
    T = x.shape[1]
    H, Hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    Dh = cfg["hidden_size"] // H
    if kind == "mamba":
        mixed, y = mamba(u, p["mamba"], cfg, precision)
        carry = {**carry, "m": y}  # the last one written is the read-out
    elif kind == "gmu":
        g = p["gmu"]
        mixed = mm(carry["m"] * jax.nn.silu(mm(u, g["in_proj"], precision)),
                   g["out_proj"], precision)
    elif kind == "cross":
        a = p["attn"]
        mixed = differential(linear(u, a["Wq"], precision), carry["k"],
                             carry["v"], a, i, cfg, causal_mask(T), precision)
    else:
        a = p["attn"]
        q, k, v = jnp.split(linear(u, a["Wqkv"], precision),
                            [H * Dh, (H + Hkv) * Dh], axis=-1)
        window = cfg["sliding_window"] if kind == "window" else 0
        mixed = differential(q, k, v, a, i, cfg, causal_mask(T, window),
                             precision)
        if kind == "full":
            carry = {**carry, "k": k, "v": v}
    x = x + mixed
    x = x + mlp(layer_norm(x, p["post_attention_layernorm"], eps), p["mlp"],
                precision)
    return x, carry


def head(x, norm_p, embed_w, cfg: dict, precision: str = "float32"):
    """Tied head: ``[..., d]`` -> logits ``[..., V]``."""
    return mm(layer_norm(x, norm_p, cfg["layer_norm_eps"]),
              embed_w.astype(jnp.float32).T, precision)


def logits(params, tokens, cfg: dict, precision: str = "float32"):
    """The whole forward of ``tokens`` ``[n, T]`` over a whole tree
    (tests; the benchmark streams the layers itself)."""
    x, carry = embed(params["embed_tokens"], tokens), {}
    for i in range(cfg["num_hidden_layers"]):
        x, carry = layer(x, carry, params["layers"][str(i)], i, cfg,
                         precision)
    return head(x, params["final_layernorm"], params["embed_tokens"], cfg,
                precision)
