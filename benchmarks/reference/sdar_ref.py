"""The plain reference for SDAR-30B-A3B-Chat (HF ``sdar_moe``): the
Qwen3-MoE block and generation by masked diffusion over blocks, in
float32 ``jax.numpy``.

Written from the published description (the HF config and modelling
code of ``JetLM/SDAR-30B-A3B-Chat``, and its generate script). No
kernel, no cache, no batching: every forward is the whole sequence
under the block-causal mask, recomputed from scratch; every expert runs
on every token and is weighted by the router's (mostly zero) combine,
so no token can be dropped. Every matmul runs at ``precision="highest"``.
Imports nothing of the program under test.

The layer: ``h = x + Attn(RMSNorm(x))``, ``y = h + MoE(RMSNorm(h))``;
q, k per-head RMSNorm, rotate-half rotary over the whole head
(``inv_freq_i = theta^(-2i/Dh)``), grouped queries, softmax in float32;
router softmax over all experts, top-k, renormalised, SwiGLU experts;
final RMSNorm and an untied head. Key j is visible to query i iff
``j // B <= i // B``.

Departures from the source, each the configuration's ``assumed``:
the block length, the number of denoising steps, the mask token's id
and the unmasking strategy are the generate script's defaults, not the
config's; a masked position predicts its OWN token (no shift); the
parameter tree is laid out as the program lays it out (a linear
layer's weight stored ``[in, out]``, a layer's experts stacked), which
changes no number.

``precision`` selects the CONTROL (PERF.md section 2): ``"float32"`` is
the reference; ``"float8"`` rounds every matmul operand to float8 e4m3
with a per-tensor scale (the precision below the model's bfloat16),
which the comparison that decides ``correct`` has to reject.
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


def rotate_half(x):
    half = x.shape[-1] // 2
    return jnp.concatenate([-x[..., half:], x[..., :half]], -1)


def rotary(x, theta: float):
    """``x``: [N, T, heads, Dh] at positions 0..T-1."""
    T, Dh = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, Dh, 2, dtype=jnp.float32) / Dh)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None]
    emb = jnp.concatenate([ang, ang], -1)[None, :, None, :]
    return x * jnp.cos(emb) + rotate_half(x) * jnp.sin(emb)


def block_causal_mask(T: int, B: int):
    i = jnp.arange(T)
    return (i[None, :] // B) <= (i[:, None] // B)  # [query, key]


def attention(x, p, cfg: dict, precision: str = "float32"):
    N, T, _ = x.shape
    H, Hkv, Dh = cfg["num_heads"], cfg["num_kv_heads"], cfg["head_dim"]
    q = mm(x, p["q_proj"], precision).reshape(N, T, H, Dh)
    k = mm(x, p["k_proj"], precision).reshape(N, T, Hkv, Dh)
    v = mm(x, p["v_proj"], precision).reshape(N, T, Hkv, Dh)
    q = rotary(rms_norm(q, p["q_norm"], cfg["rms_eps"]), cfg["rope_theta"])
    k = rotary(rms_norm(k, p["k_norm"], cfg["rms_eps"]), cfg["rope_theta"])
    k = jnp.repeat(k, H // Hkv, axis=2)
    v = jnp.repeat(v, H // Hkv, axis=2)
    q, k, v = (_round_operand(t, precision) for t in (q, k, v))
    s = jnp.einsum("nqhd,nkhd->nhqk", q, k, precision="highest")
    s = s / jnp.sqrt(jnp.float32(Dh))
    s = jnp.where(block_causal_mask(T, cfg["block_length"]), s, -jnp.inf)
    w = _round_operand(jax.nn.softmax(s, axis=-1), precision)
    a = jnp.einsum("nhqk,nkhd->nqhd", w, v, precision="highest")
    return mm(a.reshape(N, T, H * Dh), p["o_proj"], precision)


def router(u, p, cfg: dict, precision: str = "float32"):
    """Tokens ``[n, d]`` -> combine weights ``[n, E]``: softmax over all
    experts, the top-k kept and (``norm_topk_prob``) renormalised."""
    probs = jax.nn.softmax(mm(u, p["gate"], precision), axis=-1)
    top, idx = jax.lax.top_k(probs, cfg["top_k"])
    if cfg.get("norm_topk_prob", True):
        top = top / top.sum(-1, keepdims=True)
    return jnp.zeros_like(probs).at[
        jnp.arange(u.shape[0])[:, None], idx
    ].add(top)


def moe(x, p, cfg: dict, precision: str = "float32"):
    """Every expert on every token, one expert at a time."""
    shape = x.shape
    u = x.reshape(-1, shape[-1])
    comb = router(u, p, cfg, precision)
    e = p["experts"]

    def one(acc, ew):
        gate, up, down, c = ew
        h = jax.nn.silu(mm(u, gate, precision)) * mm(u, up, precision)
        return acc + c[:, None] * mm(h, down, precision), None

    out, _ = jax.lax.scan(
        one, jnp.zeros_like(u),
        (e["gate_proj"], e["up_proj"], e["down_proj"], comb.T),
    )
    return out.reshape(shape)


def layer(x, p, cfg: dict, precision: str = "float32"):
    h = x + attention(
        rms_norm(x, p["input_layernorm"], cfg["rms_eps"]),
        p["self_attn"], cfg, precision,
    )
    return h + moe(
        rms_norm(h, p["post_attention_layernorm"], cfg["rms_eps"]),
        p["mlp"], cfg, precision,
    )


def embed(embed_tokens, tokens):
    return embed_tokens[tokens].astype(jnp.float32)  # not scaled


def head(x, norm, lm_head, cfg: dict, precision: str = "float32"):
    return mm(rms_norm(x, norm, cfg["rms_eps"]),
              lm_head.astype(jnp.float32).T, precision)


def forward(params, tokens, cfg: dict, precision: str = "float32"):
    """Logits ``[N, T, V]`` of ``tokens`` ``[N, T]`` (T a multiple of
    the block length, or the tail block is simply short)."""
    x = embed(params["embed_tokens"], tokens)
    for i in range(len(params["layers"])):
        x = layer(x, params["layers"][str(i)], cfg, precision)
    return head(x, params["norm"], params["lm_head"], cfg, precision)


# ---- generation -----------------------------------------------------------


def confidences(logits):
    """Greedy choice at each position: (token, log of its softmax
    probability), from logits ``[..., V]``."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    tok = logp.argmax(-1)
    return tok, jnp.take_along_axis(logp, tok[..., None], -1)[..., 0]


def unmask(logits, toks, mask, cfg: dict):
    """One denoising step on one block: logits ``[B, V]``, the block's
    tokens and mask -> (tokens, mask, positions taken). Static: the
    ``B / steps`` masked positions of highest confidence; dynamic:
    every masked position above the threshold, and never fewer than
    the static count. Ties go to the earlier position."""
    import numpy as np

    B, steps = cfg["block_length"], cfg["denoise_steps"]
    tok, logc = (np.asarray(a) for a in confidences(logits))
    toks, mask = np.array(toks), np.array(mask, bool)
    masked = np.flatnonzero(mask)
    by_conf = masked[np.argsort(-logc[masked], kind="stable")]
    take = list(by_conf[: B // steps])
    if cfg["unmask"] == "low_confidence_dynamic":
        thr = np.log(cfg["unmask_threshold"])
        take += [j for j in by_conf if logc[j] > thr and j not in take]
    elif cfg["unmask"] != "low_confidence_static":
        raise ValueError(cfg["unmask"])
    for j in take:
        toks[j], mask[j] = tok[j], False
    return toks, mask, sorted(int(j) for j in take)


def generate(params, prompt, new_tokens: int, cfg: dict,
             precision: str = "float32"):
    """Greedy generation, every forward from scratch -> (tokens,
    forwards): the ``new_tokens`` generated ids, and for each forward
    ``(pos, tokens [B], mask [B], taken)`` — the block's first
    position, its tokens and mask BEFORE the forward (masked positions
    hold the mask id), and the positions that forward unmasked (none
    on the forward over a clean block, which commits it)."""
    import numpy as np

    B, mid = cfg["block_length"], cfg["mask_token_id"]
    seq = [int(t) for t in prompt]
    P = len(seq)
    fwd = jax.jit(lambda t: forward(params, t, cfg, precision))
    pos = P // B * B
    forwards, out = [], []
    lead = P - pos
    while len(out) < new_tokens:
        toks = np.array(seq[pos:] + [mid] * (B - lead), np.int32)
        mask = np.arange(B) >= lead
        while True:
            inp = np.where(mask, mid, toks)
            logits = fwd(jnp.asarray([seq[:pos] + inp.tolist()]))[0, pos:]
            if not mask.any():
                forwards.append((pos, inp, mask.copy(), []))
                break
            toks, new_mask, taken = unmask(logits, toks, mask, cfg)
            forwards.append((pos, inp, mask.copy(), taken))
            mask = new_mask
        seq = seq[:pos] + toks.tolist()
        out += toks[lead:].tolist()
        pos, lead = pos + B, 0
    return out[:new_tokens], forwards
