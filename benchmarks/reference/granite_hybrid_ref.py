"""The plain reference for granite-4.0-h-micro (HF ``granitemoehybrid``,
no routed experts): Mamba-2 layers and position-free grouped-query
attention layers, in float32 ``jax.numpy``.

Written from the published description (the HF config of
``ibm-granite/granite-4.0-h-micro`` and the family's modelling code).
No kernel, no cache, no batching trick: attention is the whole sequence
under a causal mask, and the state-space recurrence is a SEQUENTIAL
``lax.scan`` over time, one token after another from a zero state —
nothing of the chunked form the program prefills with. Every matmul
runs at ``precision="highest"``. Imports nothing of the program under
test. ``cfg`` holds the config's own keys.

The model (``r = residual_multiplier``; RMSNorm with weight, eps
``rms_norm_eps``): ``x0 = embed[tokens] * embedding_multiplier``; every
layer ``x = x + r * mixer(RMSNorm(x))``, ``x = x + r * mlp(RMSNorm(x))``;
``logits = RMSNorm(x) @ embed^T / logits_scaling``. No positions
(``position_embedding_type`` ``nope``).

- MLP (``num_local_experts`` 0: the shared one alone):
  ``[a, b] = split(x W_in)``, ``(silu(a) * b) W_out``.
- Attention: q ``num_attention_heads`` heads, k and v
  ``num_key_value_heads`` heads of ``hidden_size / num_attention_heads``;
  causal softmax of ``q k^T * attention_multiplier``; ``W_o``.
- Mamba-2: ``[z, xBC, dt] = split(x W_in)`` to ``H P``, ``H P + 2 G N``
  and ``H``; ``xBC = silu(conv1d_depthwise_causal(xBC) + bias)``, kernel
  ``mamba_d_conv``; ``[xs, B, C] = split(xBC)``;
  ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``; per head, state
  ``S`` in ``R^{P x N}``, zero before the first token:
  ``S_t = exp(dt_t A) S_{t-1} + dt_t xs_t B_t^T``,
  ``y_t = S_t C_t + D xs_t``; ``y = RMSNorm(y * silu(z))`` over all
  ``H P`` channels (one group), gate BEFORE the norm; ``W_out``.

Departures from the source, each the configuration's ``assumed``: the
parameter tree is laid out as the program lays it out (a linear layer's
weight stored ``[in, out]``, the convolution's ``[K, C]``), which
changes no number; ``mamba_n_groups`` must be 1 (it is); the time-step
limits are the default ``(0, inf)`` and clamp nothing; weights are the
float32 copies of the stored bfloat16 values.

``precision`` selects the CONTROL (PERF.md section 2): ``"float32"`` is
the reference; ``"float8"`` rounds every matmul operand to float8 e4m3
with a per-tensor scale (the precision below the model's bfloat16),
which the comparison that decides ``correct`` has to reject. The
recurrence and the convolution have no matmul and stay float32 in both.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


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


def rms_norm(x, w, eps: float):
    return x * jax.lax.rsqrt(
        jnp.mean(x * x, -1, keepdims=True) + eps
    ) * w.astype(jnp.float32)


def embed(embed_w, tokens, cfg: dict):
    """``tokens`` ``[n, T]`` -> ``[n, T, d]``."""
    return embed_w.astype(jnp.float32)[tokens] * cfg["embedding_multiplier"]


def mlp(x, p, precision: str = "float32"):
    a, b = jnp.split(mm(x, p["input_linear"], precision), 2, axis=-1)
    return mm(jax.nn.silu(a) * b, p["output_linear"], precision)


def attention(x, p, cfg: dict, precision: str = "float32"):
    n, T, d = x.shape
    H, Hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    Dh = d // H
    q = mm(x, p["q_proj"], precision).reshape(n, T, H, Dh)
    k = mm(x, p["k_proj"], precision).reshape(n, T, Hkv, Dh)
    v = mm(x, p["v_proj"], precision).reshape(n, T, Hkv, Dh)
    k = jnp.repeat(k, H // Hkv, axis=2)
    v = jnp.repeat(v, H // Hkv, axis=2)
    q, k, v = (_round_operand(t, precision) for t in (q, k, v))
    s = jnp.einsum("nqhd,nkhd->nhqk", q, k, precision="highest")
    s = s * cfg["attention_multiplier"]
    i = jnp.arange(T)
    s = jnp.where(i[None, :] <= i[:, None], s, -jnp.inf)
    w = _round_operand(jax.nn.softmax(s, axis=-1), precision)
    a = jnp.einsum("nhqk,nkhd->nqhd", w, v, precision="highest")
    return mm(a.reshape(n, T, H * Dh), p["o_proj"], precision)


def conv1d(x, w, b):
    """Depthwise causal: ``y[t] = b + sum_k w[k] x[t - (K-1) + k]``,
    zeros before the first token. ``x`` ``[n, T, C]``, ``w`` ``[K, C]``."""
    K, T = w.shape[0], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    return b + sum(w[k] * xp[:, k:k + T] for k in range(K))


def recurrence(xs, dt, A, B, C, D):
    """One token after another. ``xs`` ``[n, T, H, P]``, ``dt``
    ``[n, T, H]``, ``A``, ``D`` ``[H]``, ``B``, ``C`` ``[n, T, N]`` ->
    ``y`` ``[n, T, H, P]``."""
    n, T, H, P = xs.shape
    N = B.shape[-1]

    def step(S, t):
        x_t, dt_t, B_t, C_t = t
        decay = jnp.exp(dt_t * A)  # [n, H]
        S = (decay[:, :, None, None] * S
             + (dt_t[:, :, None] * x_t)[..., None] * B_t[:, None, None, :])
        y = jnp.sum(S * C_t[:, None, None, :], -1) + D[None, :, None] * x_t
        return S, y

    time_major = lambda a: jnp.moveaxis(a, 1, 0)
    _, y = jax.lax.scan(
        step, jnp.zeros((n, H, P, N), jnp.float32),
        tuple(time_major(a) for a in (xs, dt, B, C)),
    )
    return jnp.moveaxis(y, 0, 1)


def mamba(x, p, cfg: dict, precision: str = "float32"):
    n, T, _ = x.shape
    H, P, N = cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_d_state"]
    assert cfg["mamba_n_groups"] == 1
    hp = H * P
    f32 = lambda a: a.astype(jnp.float32)
    z, xbc, dt = jnp.split(mm(x, p["in_proj"], precision),
                           [hp, 2 * hp + 2 * N], axis=-1)
    xbc = jax.nn.silu(conv1d(xbc, f32(p["conv1d"]["weight"]),
                             f32(p["conv1d"]["bias"])))
    xs, B, C = jnp.split(xbc, [hp, hp + N], axis=-1)
    dt = jax.nn.softplus(dt + f32(p["dt_bias"]))
    y = recurrence(xs.reshape(n, T, H, P), dt, -jnp.exp(f32(p["A_log"])),
                   B, C, f32(p["D"]))
    y = rms_norm(y.reshape(n, T, hp) * jax.nn.silu(z), p["norm"],
                 cfg["rms_norm_eps"])
    return mm(y, p["out_proj"], precision)


def layer(x, p, cfg: dict, precision: str = "float32"):
    """One layer; its kind is the subtree it holds."""
    r, eps = cfg["residual_multiplier"], cfg["rms_norm_eps"]
    u = rms_norm(x, p["input_layernorm"], eps)
    if "mamba" in p:
        mixed = mamba(u, p["mamba"], cfg, precision)
    else:
        mixed = attention(u, p["self_attn"], cfg, precision)
    x = x + r * mixed
    return x + r * mlp(rms_norm(x, p["post_attention_layernorm"], eps),
                       p["shared_mlp"], precision)


def head(x, norm_w, embed_w, cfg: dict, precision: str = "float32"):
    """Tied head: ``[..., d]`` -> logits ``[..., V]``."""
    return mm(rms_norm(x, norm_w, cfg["rms_norm_eps"]),
              embed_w.astype(jnp.float32).T, precision
              ) / cfg["logits_scaling"]


def logits(params, tokens, cfg: dict, precision: str = "float32"):
    """The whole forward of ``tokens`` ``[n, T]`` over a whole tree
    (tests; the benchmark streams the layers itself)."""
    x = embed(params["embed_tokens"], tokens, cfg)
    for i in range(len(params["layers"])):
        x = layer(x, params["layers"][str(i)], cfg, precision)
    return head(x, params["norm"], params["embed_tokens"], cfg, precision)
