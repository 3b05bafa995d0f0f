"""The plain reference for GLM-5 (HF ``glm_moe_dsa``: latent attention
over the keys a learned indexer selects, leading dense layers, then
sigmoid-routed experts beside a shared one), in float32 ``jax.numpy``.

Written from the layer equations of the issue that brought the
configuration (ISSUE 43, Tentpole; PERF.md section 4), which are
DeepSeek-V3's (MLA, the router) and DeepSeek-V3.2's (the indexer) under
GLM-5's config keys. No kernel, no cache, no absorption: every layer
runs over the whole sequence from scratch; keys and values are EXPANDED
from their latents for every position; each query's selection is an
explicit mask over all positions, built from the ``index_topk`` largest
index scores by ``lax.top_k`` (ties to the lower position); attention
is a softmax under that mask. Query rows go through attention in blocks
of ``Q_BLOCK`` so that the maps fit beside a layer's float32 weights
(64 heads x 256 rows x 17,408 keys x 4 B = 1.1 GB); that changes no
number. Every matmul runs at ``precision="highest"``. Imports nothing
of the program under test.

The model (RMSNorm(x; w) = x * rsqrt(mean(x^2) + eps) * w, eps
``rms_norm_eps``; pre-norm): ``x0 = embed[tokens]``; every layer
``h = x + Attn(RMSNorm(x))``, ``y = h + FFN(RMSNorm(h))``;
``logits = RMSNorm(x) @ lm_head^T``. With ``u`` the normed input at
position ``t``:

- MLA: ``c_q = RMSNorm(u W_qa)``; ``q = c_q W_qb`` as H heads of
  ``[q_nope | q_rope]``; ``[c_kv | k_rope] = u W_kva``, ``c_kv =
  RMSNorm(c_kv)``, ``k_rope`` ONE vector for all heads; ``[k_nope | v]``
  a head ``= c_kv W_kvb``; rotary (``rope_theta``, interleaved: the
  pair ``(2i, 2i + 1)`` turns by ``t * theta^(-2i/Dr)``) on ``q_rope``
  and ``k_rope``; ``score_h(t, s) = (q_nope . k_nope + q_rope . k_rope)
  / sqrt(Dn + Dr)``; softmax over ``s`` in ``S_t``; ``W_o`` on the
  heads side by side.
- Indexer: ``qI = c_q W_Iq`` as Hi heads of Di; ``kI = LayerNorm(u
  W_Ik)`` (scale and bias, eps 1e-5); rotary on the first Dr columns of
  each; ``w = (u W_Iw) * Hi^-0.5 * Di^-0.5``; ``I(t, s) = sum_j w_j(t)
  relu(qI_j(t) . kI(s))`` for ``s <= t``; ``S_t`` the ``index_topk``
  largest, all of ``s <= t`` while ``t < index_topk``.
- FFN, layers below ``first_k_dense_replace``: ``(silu(u W_g) * (u
  W_u)) W_d``. The others: ``s = sigmoid(u W_r)`` over all
  ``router_outputs`` experts; the ``num_experts_per_tok`` largest of
  ``s + b`` are chosen (``b`` moves the choice only); ``g_i =
  routed_scaling_factor * s_i / sum_chosen s``; the result is ``sum_{i
  chosen, i held here} g_i E_i(u) + E_shared(u)``: this reference is
  given the same share of the experts as the program (numbers
  ``expert_offset`` onward, as many as the tree stacks), runs EVERY held
  expert on every token and weights by ``g`` (0 where not chosen); what
  experts held elsewhere would add is left out in both.

``precision`` selects the CONTROL (PERF.md section 2): ``"float32"`` is
the reference; ``"float8"`` rounds every matmul operand (projections,
index dots, attention's two products, experts, router, head) to float8
e4m3 with a per-tensor scale, which the comparison that decides
``correct`` has to reject; ``"bfloat16"`` rounds them to the
configuration's OWN precision: the WITNESS, which has to read as the
program reads (if the program's distance from float32 is rounding, this
reference is as far from float32 and close to the program).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

Q_BLOCK = 256
INDEX_NORM_EPS = 1e-5


def _round_operand(x, precision: str):
    if precision == "float32":
        return x
    if precision == "bfloat16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    if precision == "float8":
        scale = 448.0 / (jnp.max(jnp.abs(x)) + 1e-30)
        return (x * scale).astype(jnp.float8_e4m3fn).astype(
            jnp.float32) / scale
    raise ValueError(f"unknown precision {precision!r}")


def ein(spec: str, a, b, precision: str = "float32"):
    return jnp.einsum(
        spec, _round_operand(a.astype(jnp.float32), precision),
        _round_operand(b.astype(jnp.float32), precision),
        precision="highest",
    )


def mm(a, w, precision: str = "float32"):
    return ein("...i,io->...o", a, w, precision)


def rms_norm(x, w, eps: float):
    return (x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
            * w.astype(jnp.float32))


def layer_norm(x, p, eps: float = INDEX_NORM_EPS):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return ((x - mean) * lax.rsqrt(var + eps)
            * p["weight"].astype(jnp.float32) + p["bias"].astype(jnp.float32))


def rotary(x, positions, theta: float):
    """Interleaved rotary over the whole last axis of ``x``; positions
    broadcast against ``x``'s leading axes."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin], -1).reshape(
        x.shape)


def rotary_head(x, positions, n: int, theta: float):
    return jnp.concatenate(
        [rotary(x[..., :n], positions, theta), x[..., n:]], -1)


def embed(embed_w, tokens):
    return embed_w.astype(jnp.float32)[tokens]


def swiglu(u, p, precision: str):
    return mm(jax.nn.silu(mm(u, p["gate_proj"], precision))
              * mm(u, p["up_proj"], precision), p["down_proj"], precision)


def route(u, p, cfg: dict, precision: str):
    """-> the weight of every expert the router scores for every token
    ``[T, E]``: ``g`` where chosen, 0 elsewhere."""
    s = jax.nn.sigmoid(mm(u, p["gate"], precision))
    _, idx = lax.top_k(s + p["gate_bias"].astype(jnp.float32),
                       int(cfg["num_experts_per_tok"]))
    chosen = jnp.zeros(s.shape, bool).at[
        jnp.arange(s.shape[0])[:, None], idx].set(True)
    g = jnp.where(chosen, s, 0.0)
    if cfg["norm_topk_prob"]:
        g = g / g.sum(-1, keepdims=True)
    return g * float(cfg["routed_scaling_factor"])


def moe(u, p, cfg: dict, precision: str):
    g = route(u, p, cfg, precision)
    first = int(cfg.get("expert_offset", 0))
    held = p["experts"]["gate_proj"].shape[0]

    def one(y, e):
        w = jax.tree.map(lambda a: a[e], p["experts"])
        return y + g[:, first + e, None] * swiglu(u, w, precision), None

    y, _ = lax.scan(one, jnp.zeros_like(u), jnp.arange(held))
    return y + swiglu(u, p["shared_experts"], precision)


def attention(u, p, cfg: dict, precision: str, at=None, rows=None):
    """Normed ``u`` ``[T, d]`` -> (the attention output ``[T, d]``, and,
    for the positions ``at`` ``[G]``, the rows each selects ``[G, K]``
    int32 in decreasing score, -1 where it has fewer than K). ``rows``
    (traced): the sequence's real length where ``u`` is padded to a
    fixed ``T``; query blocks past it are not computed (their output
    stays 0), and no real query sees a padded key (``s <= t``)."""
    T = u.shape[0]
    H = int(cfg["num_attention_heads"])
    R, Dn, Dr, Dv = (int(cfg[k]) for k in (
        "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
        "v_head_dim"))
    Hi, Di, K = (int(cfg[k]) for k in (
        "index_n_heads", "index_head_dim", "index_topk"))
    eps, theta = float(cfg["rms_norm_eps"]), float(cfg["rope_theta"])
    pos = jnp.arange(T)
    c_q = rms_norm(mm(u, p["q_a_proj"], precision), p["q_a_layernorm"], eps)
    q = mm(c_q, p["q_b_proj"], precision).reshape(T, H, Dn + Dr)
    q = jnp.concatenate(
        [q[..., :Dn], rotary(q[..., Dn:], pos[:, None], theta)], -1)
    kv = mm(u, p["kv_a_proj_with_mqa"], precision)
    c_kv = rms_norm(kv[:, :R], p["kv_a_layernorm"], eps)
    k_rope = rotary(kv[:, R:], pos, theta)
    kvb = mm(c_kv, p["kv_b_proj"], precision).reshape(T, H, Dn + Dv)
    k = jnp.concatenate(
        [kvb[..., :Dn], jnp.broadcast_to(k_rope[:, None], (T, H, Dr))], -1)
    v = kvb[..., Dn:]
    ix = p["indexer"]
    qi = rotary_head(mm(c_q, ix["wq_b"], precision).reshape(T, Hi, Di),
                     pos[:, None], Dr, theta)
    ki = rotary_head(layer_norm(mm(u, ix["wk"], precision), ix["k_norm"]),
                     pos, Dr, theta)
    w = mm(u, ix["weights_proj"], precision) * (Hi ** -0.5 * Di ** -0.5)
    k_top = min(K, T)

    def scores_of(rows):
        """Index scores of the queries ``rows`` for every position,
        ``-inf`` after the query's own."""
        dots = ein("qhd,sd->qhs", qi[rows], ki, precision)
        I = jnp.einsum("qhs,qh->qs", jax.nn.relu(dots), w[rows],
                       precision="highest")
        return jnp.where(pos[None, :] <= rows[:, None], I, -jnp.inf)

    def block(start):
        rows = start + jnp.arange(Q_BLOCK)
        rows = jnp.minimum(rows, T - 1)
        I = scores_of(rows)
        _, idx = lax.top_k(I, k_top)
        sel = jnp.zeros(I.shape, bool).at[
            jnp.arange(Q_BLOCK)[:, None], idx].set(True)
        sel = sel & (pos[None, :] <= rows[:, None])
        s = ein("qhc,shc->hqs", q[rows], k, precision) * (Dn + Dr) ** -0.5
        pr = jax.nn.softmax(jnp.where(sel[None], s, -jnp.inf), axis=-1)
        return ein("hqs,shv->qhv", pr, v, precision).reshape(Q_BLOCK, H * Dv)

    n_blocks = -(-T // Q_BLOCK)
    out = lax.fori_loop(
        0, n_blocks if rows is None else (rows + Q_BLOCK - 1) // Q_BLOCK,
        lambda b, out: lax.dynamic_update_slice(
            out, block(b * Q_BLOCK), (b * Q_BLOCK, 0)),
        jnp.zeros((n_blocks * Q_BLOCK, H * Dv), jnp.float32))[:T]
    selected = None
    if at is not None:
        I = scores_of(at)
        vals, idx = lax.top_k(I, k_top)
        selected = jnp.where(vals > -jnp.inf, idx, -1).astype(jnp.int32)
    return mm(out, p["o_proj"], precision), selected


def layer(x, p, i, cfg: dict, precision: str = "float32", at=None, *,
          dense: bool, rows=None):
    """One layer over one sequence ``x`` ``[T, d]`` -> (x, selected).
    ``dense`` says which FFN the layer has (a property of its number:
    ``i < first_k_dense_replace``; static, as the trees differ)."""
    del i
    eps = float(cfg["rms_norm_eps"])
    a, selected = attention(rms_norm(x, p["input_layernorm"], eps),
                            p["self_attn"], cfg, precision, at, rows)
    x = x + a
    u = rms_norm(x, p["post_attention_layernorm"], eps)
    x = x + (swiglu(u, p["mlp"], precision) if dense
             else moe(u, p["mlp"], cfg, precision))
    return x, selected


def head(x, norm_w, head_w, cfg: dict, precision: str = "float32"):
    return ein("...d,vd->...v",
               rms_norm(x, norm_w, float(cfg["rms_norm_eps"])), head_w,
               precision)


def logits(params, tokens, cfg: dict, precision: str = "float32", at=None):
    """Full forward of one sequence ``tokens`` ``[T]`` -> (logits
    ``[T, V]``, the rows the positions ``at`` select in each layer
    ``[layers, G, K]`` or None)."""
    x = embed(params["embed_tokens"], tokens)
    picked = []
    for i in range(int(cfg["num_hidden_layers"])):
        x, sel = layer(x, params["layers"][str(i)], i, cfg, precision, at,
                       dense=i < int(cfg["first_k_dense_replace"]))
        picked.append(sel)
    out = head(x, params["norm"], params["lm_head"], cfg, precision)
    return out, (jnp.stack(picked) if at is not None else None)
