"""KV-cache incremental decoding + sampling for the causal LM.

The reference ends at training (no eval, no inference — SURVEY.md §5);
round 1 added a decoder-only LM but no way to decode from it
(VERDICT.md "What's missing" #4). This module closes that gap the
TPU-friendly way: a single jitted ``lax.scan`` over decode steps, a
static-shape K/V cache updated in place with ``dynamic_update_slice``
(donated through the scan carry, so XLA keeps one buffer), and O(T)
attention per step against the cache.

It is a *functional* twin of ``models.lm.CausalLM``: the same
parameter tree (embed / pos_embed / blockN{ln1, attn{qkv, proj}, ln2,
mlp1, mlp2} / ln_final, tied head) driven step-by-step. Exactness is
pinned by tests/test_generate.py: per-position cached logits equal the
dense full-sequence forward to fp32 tolerance, which is also why the
numerics mirror Flax defaults exactly (LayerNorm eps 1e-6, tanh-GELU).

Sampling: greedy (``temperature=0``) or temperature-scaled categorical
with a per-step folded PRNG key.
"""

from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from ddp_tpu.models.lm import LMSpec, head_dim_of
from ddp_tpu.ops.attention import best_attention, dot_product_attention
from ddp_tpu.ops.decode import (
    decode_attention,
    dequantize_kv,
    gather_paged_kv,
    paged_decode_attention,
    quantize_kv,
)


class DecodeCache(NamedTuple):
    """Static-shape per-layer K/V cache.

    ``k``/``v``: [depth, B, total_len, H_kv, Dh]; ``pos``: next write
    position (scalar int32). One stacked array per side keeps the scan
    carry flat and lets the per-layer update be a ``dynamic_update_slice``
    on a leading index. Under GQA (spec.num_kv_heads < num_heads) the
    cache stores the COMPACT kv heads — the whole point: per-step
    decode HBM reads shrink by the group factor.
    """

    k: jax.Array
    v: jax.Array
    pos: jax.Array


def _kv_heads(spec: LMSpec) -> int:
    return spec.num_kv_heads or spec.num_heads


def init_cache(spec: LMSpec, batch: int, dtype=jnp.float32) -> DecodeCache:
    shape = (spec.depth, batch, spec.total_len, _kv_heads(spec),
             head_dim_of(spec))
    return DecodeCache(
        k=jnp.zeros(shape, dtype),
        v=jnp.zeros(shape, dtype),
        pos=jnp.zeros((), jnp.int32),
    )


def _layer_norm(x, p):
    """Flax LayerNorm numerics: fp32, eps 1e-6, scale+bias."""
    x32 = x.astype(jnp.float32)
    mean = x32.mean(-1, keepdims=True)
    var = ((x32 - mean) ** 2).mean(-1, keepdims=True)
    y = (x32 - mean) * lax.rsqrt(var + 1e-6)
    return y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)


def _dense(x, p):
    return x @ p["kernel"] + p["bias"]


def _block_qkv(p, x, H, Dh, H_kv=None):
    """ln1 → qkv projection → (q [B,T,H,Dh], k/v [B,T,H_kv,Dh]).
    Shared by the incremental decode (T=1) and the parallel prefill
    (T=P) so the two paths cannot drift numerically."""
    H_kv = H_kv or H
    h = _layer_norm(x, p["ln1"]).astype(x.dtype)
    qkv = _dense(h, p["attn"]["qkv"])
    if H_kv != H:
        # GQA GROUP-MAJOR fused layout [kv-group: q·G | k | v] × H_kv,
        # mirroring models/vit.py MultiHeadAttention's GQA path (whole
        # kv groups per TP column shard). q head j = g·G + i comes out
        # in natural 0..H-1 order, matching the grouped decode einsums.
        G = H // H_kv
        qkv = qkv.reshape(*x.shape[:2], H_kv, G + 2, Dh)
        q = qkv[..., :G, :].reshape(*x.shape[:2], H, Dh)
        return q, qkv[..., G, :], qkv[..., G + 1, :]
    # HEAD-MAJOR fused layout, mirroring models/vit.py
    # MultiHeadAttention: columns ordered [head, (q|k|v), head_dim] so
    # TP shards of the kernel are whole heads.
    qkv = qkv.reshape(*x.shape[:2], H, 3, Dh)
    return qkv[:, :, :, 0], qkv[:, :, :, 1], qkv[:, :, :, 2]


def _moe_mlp(p, x, *, top_k: int = 2, normalize_gates: bool = True):
    """Routed expert MLP for serving (round 5 — MoE-LM decode).

    models/moe.py MoEMLP numerics WITHOUT the capacity mechanism:
    each token's top-k experts are selected by the same iterative
    argmax, gates normalized the same way, and the combine runs as a
    dense weighting over all E expert FFNs — so the output equals the
    training forward EXACTLY while no token overflows capacity (the
    no-drop regime; capacity competition depends on the batch a layer
    sees, so a skewed router drops differently at train vs serve —
    the same caveat as any batch-size-dependent GShard eval). Dense
    E-way compute is the right serving shape here: decode batches are
    small and the capacity/dispatch einsums exist for training-scale
    token counts. ``top_k``/``normalize_gates`` come from the LMSpec
    (round-5 fix: decode no longer hardcodes the MoEMLP
    defaults — a checkpoint trained at top_k=1 or with raw gates now
    serves with its own routing)."""
    B, T, d = x.shape
    toks = x.reshape(B * T, d)
    gates = jax.nn.softmax(
        toks.astype(jnp.float32) @ p["router"]["kernel"]
        + p["router"]["bias"],
        axis=-1,
    )  # [n, E] fp32 — the router runs fp32 in training too
    E = gates.shape[-1]
    remaining = gates
    comb = jnp.zeros_like(gates)
    for _ in range(top_k):
        idx = jnp.argmax(remaining, axis=-1)
        mask = jax.nn.one_hot(idx, E, dtype=jnp.float32)
        comb = comb + remaining * mask
        remaining = remaining * (1.0 - mask)
    if normalize_gates:
        comb = comb / jnp.maximum(comb.sum(-1, keepdims=True), 1e-9)
    wi, wo = p["wi"].astype(x.dtype), p["wo"].astype(x.dtype)
    h = jax.nn.gelu(
        jnp.einsum("nd,edf->enf", toks, wi) + p["bi"].astype(x.dtype)
    )
    y = jnp.einsum("enf,efd->end", h, wo) + p["bo"].astype(x.dtype)
    out = jnp.einsum("ne,end->nd", comb.astype(x.dtype), y)
    return out.reshape(B, T, d)


def _block_finish(spec: LMSpec, p, x, attn_vec):
    """Output projection residual + MLP residual (the block's back
    half). Routed blocks (``moe`` in the tree) take the expert path —
    every decode surface (decode_step, prefill, beam_search,
    cached_logits) flows through here, so the MoE-LM serves through
    the whole stack. Routing config (top_k, gate normalization) comes
    from the spec, not the MoEMLP defaults."""
    x = x + _dense(attn_vec, p["attn"]["proj"])
    h = _layer_norm(x, p["ln2"]).astype(x.dtype)
    if "moe" in p:
        return x + _moe_mlp(
            p["moe"], h,
            top_k=spec.moe_top_k,
            normalize_gates=spec.moe_normalize_gates,
        )
    h = _dense(h, p["mlp1"])
    h = jax.nn.gelu(h)  # tanh approximation — Flax's default
    return x + _dense(h, p["mlp2"])


def decode_step(
    spec: LMSpec, params: Any, cache: DecodeCache, token: jax.Array
) -> tuple[jax.Array, DecodeCache]:
    """Feed ONE token per sequence → (logits [B, vocab], new cache).

    ``token``: [B] int32 at position ``cache.pos``. Attention runs the
    new query against the full static cache with positions > pos masked
    — O(total_len·d) per step, no [T, T] tensor.
    """
    embed = params["embed"]
    B = token.shape[0]
    H = spec.num_heads
    Dh = spec.d_model // H
    H_kv = _kv_heads(spec)
    G = H // H_kv  # 1 for MHA; the grouped einsums reduce to plain MHA
    pos = cache.pos
    x = embed[token][:, None, :]  # [B, 1, d]
    x = x + lax.dynamic_slice_in_dim(
        params["pos_embed"].astype(x.dtype), pos, 1, axis=1
    )
    # Keys at positions > pos are cache zeros — mask them out.
    live = (jnp.arange(spec.total_len) <= pos)[None, None, None, :]
    ck, cv = cache.k, cache.v
    for i in range(spec.depth):
        p = params[f"block{i + 1}"]
        q, k, v = _block_qkv(p, x, H, Dh, H_kv)
        ck = lax.dynamic_update_slice(ck, k[None], (i, 0, pos, 0, 0))
        cv = lax.dynamic_update_slice(cv, v[None], (i, 0, pos, 0, 0))
        # q head h attends through kv head h // G (h = k·G + g, the
        # same grouping jnp.repeat gives the training path).
        qg = q[:, 0].reshape(B, H_kv, G, Dh)
        logits = (
            jnp.einsum(
                "bkgd,blkd->bkgl",
                qg.astype(jnp.float32),
                ck[i].astype(jnp.float32),
            )
            * Dh**-0.5
        )  # [B, H_kv, G, L]
        logits = jnp.where(live, logits, -jnp.inf)
        w = jax.nn.softmax(logits, axis=-1)
        attn = jnp.einsum("bkgl,blkd->bkgd", w, cv[i].astype(jnp.float32))
        attn = attn.reshape(B, 1, spec.d_model).astype(x.dtype)
        x = _block_finish(spec, p, x, attn)
    x = _layer_norm(x, params["ln_final"])
    out_logits = (x[:, 0] @ embed.T.astype(jnp.float32)).astype(jnp.float32)
    return out_logits, DecodeCache(k=ck, v=cv, pos=pos + 1)


def prefill(
    spec: LMSpec, params: Any, prompt: jax.Array
) -> tuple[jax.Array, DecodeCache]:
    """Warm the cache from the prompt in ONE parallel forward.

    ``prompt``: [B, P] int32, P ≥ 1. The standard two-phase decode
    architecture: prefill processes all prompt positions at once
    (dense causal attention, MXU-shaped [B, P, ...] matmuls) and
    writes every position's K/V into the cache; generation then
    proceeds token-by-token. Returns (last position's logits, cache
    with pos = P). Pinned equal to sequential ``decode_step`` feeding
    by tests/test_generate.py.
    """
    B, P = prompt.shape
    H = spec.num_heads
    Dh = spec.d_model // H
    H_kv = _kv_heads(spec)
    G = H // H_kv
    cache = init_cache(spec, B)
    embed = params["embed"]
    x = embed[prompt]  # [B, P, d]
    x = x + params["pos_embed"].astype(x.dtype)[:, :P]
    ck, cv = cache.k, cache.v
    # Size-dispatched (flash on TPU past FLASH_MIN_LEN, dense
    # otherwise) — prefill is a full causal attention over the
    # prompt. Resolved once, like CausalLM.
    attn_fn = best_attention(causal=True)
    for i in range(spec.depth):
        p = params[f"block{i + 1}"]
        q, k, v = _block_qkv(p, x, H, Dh, H_kv)
        ck = lax.dynamic_update_slice(ck, k[None], (i, 0, 0, 0, 0))
        cv = lax.dynamic_update_slice(cv, v[None], (i, 0, 0, 0, 0))
        # The cache keeps kv compact; compute expands to full heads
        # (same jnp.repeat grouping as the training path).
        attn = attn_fn(
            q.astype(jnp.float32),
            jnp.repeat(k, G, axis=2).astype(jnp.float32),
            jnp.repeat(v, G, axis=2).astype(jnp.float32),
        )
        attn = attn.reshape(B, P, spec.d_model).astype(x.dtype)
        x = _block_finish(spec, p, x, attn)
    x = _layer_norm(x[:, -1:], params["ln_final"])
    last_logits = (x[:, 0] @ embed.T.astype(jnp.float32)).astype(jnp.float32)
    return last_logits, DecodeCache(
        k=ck, v=cv, pos=jnp.asarray(P, jnp.int32)
    )


def filter_logits(logits, *, top_k: int = 0, top_p: float = 1.0):
    """Mask logits to the top-k and/or nucleus (top-p) candidate set.

    ``top_k > 0`` keeps the k highest logits per row — tie-inclusive:
    every logit equal to the kth value survives, so exact ties can
    leave more than k candidates (the standard shape-static choice;
    masking ``logits < kth`` keeps strictly-less out only).
    ``top_p < 1``
    keeps the smallest prefix of the probability-sorted vocabulary
    whose cumulative mass reaches p (the highest-probability token
    always survives, so the set is never empty). Masked entries become
    a large negative (not −inf: the downstream ``categorical`` is
    NaN-safe that way even if a row were fully masked). Static shapes
    throughout — jit/vmap/scan-safe.
    """
    logits = logits.astype(jnp.float32)
    neg = jnp.float32(jnp.finfo(jnp.float32).min / 2)
    if top_k and top_k < logits.shape[-1]:
        kth = lax.top_k(logits, top_k)[0][..., -1:]
        logits = jnp.where(logits < kth, neg, logits)
    if top_p < 1.0:
        sorted_logits = jnp.sort(logits, axis=-1)[..., ::-1]
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        # Keep entries whose PRECEDING mass is < p (so the first token
        # is always kept); the threshold is the smallest kept logit.
        keep = jnp.concatenate(
            [jnp.ones_like(cum[..., :1], bool), cum[..., :-1] < top_p],
            axis=-1,
        )
        thresh = jnp.min(
            jnp.where(keep, sorted_logits, jnp.float32(jnp.inf)),
            axis=-1, keepdims=True,
        )
        logits = jnp.where(logits < thresh, neg, logits)
    return logits


def generate(
    spec: LMSpec,
    params: Any,
    prompt: jax.Array,
    *,
    max_new_tokens: int,
    temperature: float = 0.0,
    top_k: int = 0,
    top_p: float = 1.0,
    seed: int = 0,
) -> jax.Array:
    """Sample continuations → [B, P + max_new_tokens] int32.

    Greedy when ``temperature == 0``; otherwise categorical over
    ``filter_logits(logits / temperature, top_k, top_p)`` — the
    conventional order: temperature first, so the nucleus is computed
    on the distribution actually being sampled (a hot distribution
    keeps a wider top-p set). ``top_k=0``/``top_p=1`` disable
    filtering; combining filters with ``temperature == 0`` is an
    error (greedy ignores them — refusing beats silently recording
    settings that had no effect). The whole loop (prefill + decode)
    is jittable; positions past ``spec.total_len`` are rejected up
    front since the position table ends there.
    """
    P = prompt.shape[1]
    if P + max_new_tokens > spec.total_len:
        raise ValueError(
            f"prompt ({P}) + max_new_tokens ({max_new_tokens}) exceeds "
            f"total_len {spec.total_len}"
        )
    if not 0.0 < top_p <= 1.0:
        raise ValueError(f"top_p must be in (0, 1], got {top_p}")
    if top_k < 0:
        raise ValueError(f"top_k must be >= 0, got {top_k}")
    if temperature <= 0.0 and (top_k or top_p < 1.0):
        raise ValueError(
            "top_k/top_p only apply when sampling: set --temperature "
            "> 0 (greedy decoding ignores the filters)"
        )
    logits, cache = prefill(spec, params, prompt)
    key = jax.random.key(seed)

    def pick(logits, step_idx):
        if temperature <= 0.0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        k = jax.random.fold_in(key, step_idx)
        filtered = filter_logits(
            logits.astype(jnp.float32) / temperature,
            top_k=top_k, top_p=top_p,
        )
        return jax.random.categorical(k, filtered, axis=-1).astype(
            jnp.int32
        )

    def step(carry, step_idx):
        logits, cache = carry
        tok = pick(logits, step_idx)
        logits, cache = decode_step(spec, params, cache, tok)
        return (logits, cache), tok

    (_, _), new_tokens = lax.scan(
        step, (logits, cache), jnp.arange(max_new_tokens)
    )
    return jnp.concatenate([prompt, new_tokens.T], axis=1)


def beam_search(
    spec: LMSpec,
    params: Any,
    prompt: jax.Array,
    *,
    max_new_tokens: int,
    beam_width: int,
) -> tuple[jax.Array, jax.Array]:
    """Deterministic beam decode → (tokens [B, W, P+N], scores [B, W]).

    Standard length-synchronous beam search over the KV cache: every
    step scores all W·V continuations per sequence, keeps the top W,
    and reorders the cache rows and token history to follow their
    parent beams (one ``take`` along the cache's batch dim — the
    [depth, B·W, L, H_kv, Dh] layout makes beam bookkeeping a gather,
    not a copy loop). Beams are returned best-first with their total
    log-probabilities; ``beam_width=1`` IS greedy decoding (pinned by
    tests). All beams decode the full ``max_new_tokens`` (the LM has
    no reserved EOS token), so no length normalization is applied —
    scores are directly comparable sums.
    """
    B, P = prompt.shape
    W = beam_width
    if W < 1:
        raise ValueError(f"beam_width must be >= 1, got {W}")
    if max_new_tokens < 1:
        raise ValueError(
            f"beam search decodes at least one token, got "
            f"max_new_tokens={max_new_tokens}"
        )
    if P + max_new_tokens > spec.total_len:
        raise ValueError(
            f"prompt ({P}) + max_new_tokens ({max_new_tokens}) exceeds "
            f"total_len {spec.total_len}"
        )
    V = spec.vocab_size
    if W > V:
        raise ValueError(f"beam_width {W} exceeds vocab_size {V}")
    logits, cache = prefill(spec, params, prompt)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    scores, tok0 = lax.top_k(logp, W)  # [B, W] first expansion

    def tile(x):  # [depth, B, ...] → [depth, B·W, ...], b-major
        return jnp.repeat(x, W, axis=1)

    cache = DecodeCache(tile(cache.k), tile(cache.v), cache.pos)
    seqs = jnp.zeros((B, W, max_new_tokens), jnp.int32)
    seqs = seqs.at[:, :, 0].set(tok0)

    def step(carry, i):
        scores, toks, cache, seqs = carry
        logits, cache = decode_step(spec, params, cache, toks)
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        total = scores[..., None] + logp.reshape(B, W, V)
        scores, idx = lax.top_k(total.reshape(B, W * V), W)
        parent = idx // V  # [B, W] surviving beams' ancestors
        tok = (idx % V).astype(jnp.int32)
        flat = (jnp.arange(B)[:, None] * W + parent).reshape(-1)
        cache = DecodeCache(
            k=jnp.take(cache.k, flat, axis=1),
            v=jnp.take(cache.v, flat, axis=1),
            pos=cache.pos,
        )
        seqs = jnp.take_along_axis(seqs, parent[..., None], axis=1)
        seqs = seqs.at[:, :, i].set(tok)
        return (scores, tok.reshape(B * W), cache, seqs), None

    (scores, _, _, seqs), _ = lax.scan(
        step,
        (scores, tok0.reshape(B * W), cache, seqs),
        jnp.arange(1, max_new_tokens),
    )
    tiled_prompt = jnp.broadcast_to(prompt[:, None, :], (B, W, P))
    return jnp.concatenate([tiled_prompt, seqs], axis=2), scores


# --- slot-level primitives (ddp_tpu.serve continuous batching) -------
#
# The serving engine (serve/engine.py) keeps ONE static-shape decode
# batch of S slots alive forever; requests of different ages share it.
# That needs decode with a PER-SLOT position (DecodeCache.pos is one
# scalar for the whole batch) plus lane-level refill: prompts are
# ingested by ``prefill_chunk`` — fixed-width chunks written straight
# into a lane of the donated cache, co-scheduled with decode steps.
# Every primitive is shape-static — slot index, lengths, positions and
# sampling config are traced scalars/vectors — so a running engine's
# compiled-program set is bounded by its chunk-width buckets
# regardless of the request mix, and the decode loop is fully
# device-resident (``slot_decode_sample_step`` fuses sampling; the
# host sees [S] int32 tokens, never logits).


class SlotCache(NamedTuple):
    """Per-slot variant of DecodeCache for continuous batching.

    Same ``k``/``v`` layout ([depth, S, total_len, H_kv, Dh] — each
    slot is a lane of the batch dim), but ``pos`` is [S] int32: every
    slot decodes at its own position, so a mixed-age batch (one
    request 5 tokens in, another 200) advances in one step.

    ``k_scale``/``v_scale`` ([depth, S, total_len, H_kv] fp32) exist
    only for int8-quantized caches (``init_slot_cache(...,
    dtype=jnp.int8)`` — ops/decode.quantize_kv per-head scales,
    written alongside every K/V row); fp32/bf16 caches carry empty
    tuples there, so the plain cache's pytree (and every donation
    path over it) is unchanged. ``quantized()`` is a trace-time
    dispatch: dtype is static under jit.

    A model with recurrent layers (``spec.layer_types``,
    models/granite_hybrid.py) keeps TWO kinds of state in a lane:
    ``k``/``v`` hold rows for its attention layers only
    (``[n_attention, S, L, H_kv * Dh]``: a position's kv heads side by
    side on lanes, ops/decode.packed_decode_attention), and its
    Mamba-2 layers hold ``ssm`` ``[n_mamba, S, N, H*P]`` float32 (the
    state, laid out as ops/ssm.py says) and ``conv``
    ``[n_mamba, S, K-1, C]`` float32 (the convolution's last
    inputs). ``live`` ``[S]`` bool names the lanes
    that decode: a recurrence cannot absorb a step it was not owed (a
    K/V row written past ``pos`` is overwritten later; a state is not),
    so a step leaves every other lane's state, tail and ``pos`` as they
    are. ``granite_hybrid.layer_rows`` maps a layer's number to its row
    in either. Models without such layers carry empty tuples, as for
    the scales.

    A model with windowed layers and one shared K/V layer
    (models/sambay.py) keeps THREE kinds of state of three sizes:
    ``k``/``v`` ``[1, S, L, H_kv * Dh]`` are the one full layer's rows,
    which every cross-attention layer reads; ``ring_k``/``ring_v``
    ``[n_window, S, W, H_kv * Dh]`` hold each windowed layer's last W
    rows, position p at row ``p mod W`` (a ring forgives nothing: a row
    written for a padded position or an idle lane destroys a live one,
    so every write is masked); ``ssm`` ``[n_mamba, S, N, C]`` and
    ``conv`` ``[n_mamba, S, K-1, C]`` as above; its remaining layers
    hold nothing. ``sambay.layer_rows`` is its map. Every other model
    carries empty tuples for the ring.

    A model with latent attention over selected keys
    (models/glm_dsa.py) keeps a FOURTH kind and no ``k``/``v`` rows at
    all (they are empty): a position of a layer is ONE ``latent`` row
    shared by all heads (the normed key/value latent beside the rotated
    rope key, 512 + 64 at the published size, stored padded to whole
    groups of 128 lanes: :func:`latent_row_width`) and one ``index_k`` row
    (the indexer's key), each a tuple of one ``[S, L, W]`` array a
    layer, so a layer is read without being sliced out of a stack.
    ``sel`` ``[layers, S, K]`` int32 is what the last decode step
    selected in each lane (-1 past a young lane's rows): read by the
    engine for a request that asks. Every other model carries empty
    tuples for all three.
    """

    k: jax.Array
    v: jax.Array
    pos: jax.Array
    k_scale: Any = ()
    v_scale: Any = ()
    ssm: Any = ()
    conv: Any = ()
    live: Any = ()
    ring_k: Any = ()
    ring_v: Any = ()
    latent: Any = ()
    index_k: Any = ()
    sel: Any = ()

    def quantized(self) -> bool:
        return self.k.dtype == jnp.int8


def latent_row_width(spec: LMSpec) -> int:
    """Columns of a stored latent row: the latent and the rope key
    (512 + 64 at the published size) padded with zeros to whole groups
    of 128 lanes (640). A minor dimension that is no multiple of 128
    has no TPU layout that is neither padded nor transposed: compiled
    for a v5e, the 576-wide buffer was laid out ``[S][576][L]`` and
    copied whole (321 MB) into row order and back around every layer's
    row write, 14 copies a decode step."""
    return -(-(spec.kv_lora_rank + spec.qk_rope_head_dim) // 128) * 128


def init_slot_cache(
    spec: LMSpec, slots: int, dtype=jnp.float32
) -> SlotCache:
    """``dtype=jnp.int8`` allocates the quantized variant: int8 K/V
    plus per-(position, head) fp32 scales — cache HBM per slot drops
    to ~(1 + 4/Dh)/8 of the fp32 layout, so a chip holds more
    ``slots``."""
    if spec.kv_lora_rank:
        # latent rows and indexer keys, no K/V rows: see SlotCache
        L, n = spec.total_len, spec.depth
        rows = lambda w: tuple(
            jnp.zeros((slots, L, w), dtype) for _ in range(n))
        none = lambda: jnp.zeros((0, slots, 0, 0), dtype)
        return SlotCache(
            k=none(), v=none(), pos=jnp.zeros((slots,), jnp.int32),
            latent=rows(latent_row_width(spec)),
            index_k=rows(spec.index_head_dim),
            sel=jnp.full((n, slots, min(spec.index_topk, L)), -1, jnp.int32),
        )
    kinds = spec.layer_types
    n_ssm = sum(1 for t in kinds if t == "mamba")
    n_ring = sum(1 for t in kinds if t == "window")
    # full-length rows: every layer of a plain model; of a table, the
    # layers that keep their own (the rest hold a ring, a state, or
    # nothing: they read another layer's rows or none)
    n_rows = (sum(1 for t in kinds if t in ("attention", "full"))
              if kinds else spec.depth)
    shape = (n_rows, slots, spec.total_len, _kv_heads(spec),
             head_dim_of(spec))
    recurrent = {}
    if kinds:
        # kv heads side by side on lanes (ops/decode.py, "heads packed
        # on lanes"): a minor dimension of Dh 64 has no TPU layout that
        # neither pads nor is relayouted around the kernel
        shape = (*shape[:3], shape[3] * shape[4])
        if spec.mamba_d_inner:  # Mamba-1: the convolution is over xs alone
            inner = conv_dim = spec.mamba_d_inner
        else:
            inner = spec.mamba_n_heads * spec.mamba_d_head
            conv_dim = inner + 2 * spec.mamba_n_groups * spec.mamba_d_state
        if n_ring:
            ring = (n_ring, slots, spec.sliding_window, shape[3])
            recurrent = dict(ring_k=jnp.zeros(ring, dtype),
                             ring_v=jnp.zeros(ring, dtype))
        recurrent.update(
            ssm=jnp.zeros((n_ssm, slots, spec.mamba_d_state, inner),
                          jnp.float32),
            conv=jnp.zeros((n_ssm, slots, spec.mamba_d_conv - 1, conv_dim),
                           jnp.float32),
            live=jnp.zeros((slots,), bool),
        )
    # Two DISTINCT buffers: the cache is donated through every engine
    # program, and aliased leaves ((x,) * 2) make XLA reject the
    # donation ("same buffer twice").
    scales = (
        (jnp.zeros(shape[:-1], jnp.float32),
         jnp.zeros(shape[:-1], jnp.float32))
        if dtype == jnp.int8
        else ((), ())
    )
    return SlotCache(
        k=jnp.zeros(shape, dtype),
        v=jnp.zeros(shape, dtype),
        pos=jnp.zeros((slots,), jnp.int32),
        k_scale=scales[0],
        v_scale=scales[1],
        **recurrent,
    )


class PagedSlotCache(NamedTuple):
    """Paged variant of :class:`SlotCache` (PR 12 — serve/pages.py).

    K/V live in a POOL of ``page_size``-token pages (``k``/``v``:
    [depth, num_pages, page_size, H_kv, Dh]) instead of per-slot
    lanes; each slot's logical [total_len] lane is spelled by its row
    of ``table`` ([S, lane_pages] int32 page ids, lane_pages =
    total_len // page_size), so two slots whose prompts share a
    prefix can map the SAME pages copy-free — the radix-index reuse
    the engine's PrefixCache hands out. ``pos`` is [S] exactly as in
    SlotCache; ``k_scale``/``v_scale`` ([depth, num_pages, page_size,
    H_kv] fp32) exist only for int8 pools, mirroring the fixed-lane
    convention (empty tuples otherwise, two distinct buffers for
    donation).

    Page id 0 is the engine's reserved SCRATCH page: all-zero table
    rows (idle lanes, warmup) read and write it, and any write whose
    position falls at/past the lane's table end is dropped outright
    (the scatter indices are pushed out of bounds — cleaner than the
    fixed-lane clamp-to-last-line, and required: a clamped write
    could land in a page another lane shares).

    The cache KIND is trace-time static (isinstance dispatch), like
    the int8 dtype: one engine compiles either the paged or the
    fixed-lane program set, never both.
    """

    k: jax.Array
    v: jax.Array
    pos: jax.Array
    table: jax.Array
    k_scale: Any = ()
    v_scale: Any = ()

    def quantized(self) -> bool:
        return self.k.dtype == jnp.int8

    @property
    def page_size(self) -> int:
        return int(self.k.shape[2])

    @property
    def num_pages(self) -> int:
        return int(self.k.shape[1])


def init_paged_slot_cache(
    spec: LMSpec,
    slots: int,
    *,
    num_pages: int,
    page_size: int,
    dtype=jnp.float32,
) -> PagedSlotCache:
    """Allocate the page pool + all-zero (scratch-mapped) tables.

    ``total_len`` must be a multiple of ``page_size`` (the engine
    validates and names the flags); the pool's HBM is ``num_pages ·
    page_size`` cache lines regardless of ``slots`` — the decoupling
    that turns int8's bytes/slot win into an effective-slots win.
    """
    if spec.total_len % page_size:
        raise ValueError(
            f"page_size {page_size} must divide total_len "
            f"{spec.total_len}"
        )
    shape = (spec.depth, num_pages, page_size, _kv_heads(spec),
             head_dim_of(spec))
    scales = (
        (jnp.zeros(shape[:-1], jnp.float32),
         jnp.zeros(shape[:-1], jnp.float32))
        if dtype == jnp.int8
        else ((), ())
    )
    return PagedSlotCache(
        k=jnp.zeros(shape, dtype),
        v=jnp.zeros(shape, dtype),
        pos=jnp.zeros((slots,), jnp.int32),
        table=jnp.zeros(
            (slots, spec.total_len // page_size), jnp.int32
        ),
        k_scale=scales[0],
        v_scale=scales[1],
    )


def _page_scatter_ids(
    table: jax.Array, posns: jax.Array, page_size: int, num_pages: int
):
    """Absolute positions → (page ids, in-page offsets) for writes.

    ``table``: [..., lane_pages] int32 rows; ``posns``: positions with
    the same leading batch dims (the decode/verify path passes the
    whole [S, lane_pages] table with [S, T] positions, a chunk passes
    one lane's [lane_pages] row with [C] positions). THE one
    definition of the out-of-lane convention: positions at/past the
    table's end map to page id ``num_pages`` — OUT of bounds, so the
    scatter drops them (jit's documented mode), the paged analogue of
    the fixed-lane position-ceiling clamp, minus the garbage line.
    """
    lane_pages = table.shape[-1]
    pidx = jnp.minimum(posns // page_size, lane_pages - 1)
    pids = jnp.take_along_axis(table, pidx, axis=-1)
    pids = jnp.where(
        posns < lane_pages * page_size, pids, jnp.int32(num_pages)
    )
    return pids, posns % page_size


def _paged_write_rows(cache: PagedSlotCache, layer: int, k, v, pos):
    """Paged twin of the fixed-lane row write: scatter each lane's T
    rows through its page table (quantize-on-write on int8 pools).
    ``k``/``v``: [S, T, H_kv, Dh]; row t of lane s lands at absolute
    position ``pos[s] + t``."""
    T = k.shape[1]
    posns = pos[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]
    pids, offs = _page_scatter_ids(
        cache.table, posns, cache.page_size, cache.num_pages
    )  # both [S, T]
    ck, cv, ksc, vsc = cache.k, cache.v, cache.k_scale, cache.v_scale
    if cache.quantized():
        qk, k_s = quantize_kv(k)
        qv, v_s = quantize_kv(v)
        ck = ck.at[layer, pids, offs].set(qk)
        cv = cv.at[layer, pids, offs].set(qv)
        ksc = ksc.at[layer, pids, offs].set(k_s)
        vsc = vsc.at[layer, pids, offs].set(v_s)
    else:
        ck = ck.at[layer, pids, offs].set(k.astype(ck.dtype))
        cv = cv.at[layer, pids, offs].set(v.astype(cv.dtype))
    return cache._replace(k=ck, v=cv, k_scale=ksc, v_scale=vsc)


def _full_kv(cache, layer: int):
    """All S lanes' [L, H_kv, Dh] float views for ``layer`` —
    dequantized if int8, gathered through the page tables if paged.
    The verify step's key/value source (decode steps go through
    ops/decode instead, where the flash path avoids materializing
    this)."""
    views = [cache.k[layer], cache.v[layer], *_scales(cache, layer)]
    if isinstance(cache, PagedSlotCache):
        views = [
            x if x is None else gather_paged_kv(x, cache.table)
            for x in views
        ]
    kf, vf, ksc, vsc = views
    if cache.quantized():
        kf, vf = dequantize_kv(kf, ksc), dequantize_kv(vf, vsc)
    return kf, vf


@jax.named_scope("cache_update")
def _write_kv_rows(cache, layer: int, k, v, pos):
    """Write per-lane K/V rows at each lane's position, in place.

    ``k``/``v``: [S, T, H_kv, Dh] float rows for positions
    ``pos[s]..pos[s]+T-1``. On a quantized cache the rows quantize on
    write (ops/decode.quantize_kv — int8 rows + per-head scales), so
    the cache never holds full-precision lines. Returns the updated
    cache. One scatter of the S·T rows straight into the (donated)
    ``[depth, S, L, H_kv, Dh]`` buffer — the step moves the new rows
    and nothing that grows with the cache; a layer is never sliced
    out and written back. The write start clamps per lane to
    ``[0, L - T]`` (``dynamic_update_slice``'s rule, kept): an idle
    lane parked at ``pos == total_len`` lands on the last line, and
    callers must pre-clamp ``pos`` when T > 1 (a clamp-shift would
    silently move the write over live lines). Paged caches take the
    scatter-through-the-table twin instead (same rows, same
    positions; out-of-lane writes dropped, not clamped).
    """
    if isinstance(cache, PagedSlotCache):
        return _paged_write_rows(cache, layer, k, v, pos)
    S, T = k.shape[:2]
    lanes = jnp.arange(S, dtype=jnp.int32)[:, None]
    posns = (
        jnp.clip(pos, 0, cache.k.shape[2] - T)[:, None]
        + jnp.arange(T, dtype=jnp.int32)[None, :]
    )  # [S, T]: row-major over (lane, position), so sorted and unique

    def write(buf, rows):
        return buf.at[layer, lanes, posns].set(
            rows.astype(buf.dtype),
            indices_are_sorted=True, unique_indices=True,
        )

    ksc, vsc = cache.k_scale, cache.v_scale
    if cache.quantized():
        k, k_s = quantize_kv(k)
        v, v_s = quantize_kv(v)
        ksc, vsc = write(ksc, k_s), write(vsc, v_s)
    return cache._replace(
        k=write(cache.k, k), v=write(cache.v, v), k_scale=ksc, v_scale=vsc
    )


def _scales(cache, layer: int | None = None):
    """The int8 scale planes (one layer's when ``layer`` is given);
    ``(None, None)`` on a float cache."""
    if not cache.quantized():
        return None, None
    if layer is None:
        return cache.k_scale, cache.v_scale
    return cache.k_scale[layer], cache.v_scale[layer]


def slot_decode_step(
    spec: LMSpec,
    params: Any,
    cache: SlotCache,
    tokens: jax.Array,
    *,
    attn_impl: str = "reference",
) -> tuple[jax.Array, SlotCache]:
    """decode_step with per-slot positions → (logits [S, V], cache).

    ``tokens``: [S] int32, slot s's token written at ``cache.pos[s]``.
    Numerics per lane are identical to ``decode_step`` (same einsums,
    same mask rule ``key_pos <= pos``) — only the position bookkeeping
    is vectorized: the K/V write is one scatter of S rows into the
    donated cache (``_write_kv_rows``), the position embedding a
    per-slot gather. Per layer the step moves S new rows and, on the
    flash path, each lane's live K/V blocks once, read by the kernel
    in the stored layout — no slice, transpose or write-back of a
    layer's lanes (pinned by tests/test_flash_decode.py). Idle slots
    are decoded too (the batch shape never changes); their outputs are
    garbage the
    engine ignores, but never NaN — position 0 is always live, so the
    softmax normalizes over at least one (zero) logit. ``pos`` is
    clamped at ``total_len`` so an idle slot can sit in the batch
    indefinitely without indexing past the cache (writes at the clamp
    land on the last line, which a refill overwrites); once there it
    attends key 0 alone, so a parked lane reads one block a layer.

    ``attn_impl`` (Python-static — the engine compiles its choice in)
    picks the banded single-query attention: ``reference`` is the
    ops/decode jnp path, bit-identical to the math that used to live
    inline here; ``flash``/``auto`` route through the Pallas
    flash-decode kernel (ops/decode.py). On an int8 cache both paths
    dequantize at the compute site.
    """
    embed = params["embed"]
    S = tokens.shape[0]
    H = spec.num_heads
    Dh = spec.d_model // H
    H_kv = _kv_heads(spec)
    pos = cache.pos  # [S]
    x = embed[tokens][:, None, :]  # [S, 1, d]
    # Per-slot position embedding: row s reads pos_embed[pos[s]].
    pe = params["pos_embed"][0]  # [L, d]
    x = x + pe[jnp.minimum(pos, spec.total_len - 1)][:, None, :].astype(
        x.dtype
    )
    # A lane at the position ceiling has no reader: a live lane's last
    # decode writes line total_len - 2 at the latest (admission keeps
    # prompt + max_new_tokens inside the lane), so only an idle lane
    # that has drifted there (it keeps decoding, and its position keeps
    # rising, clamped) sits at total_len. It attends key 0 alone — on
    # the flash path an idle lane then costs one block a layer instead
    # of its whole lane, every step, for as long as it idles.
    attend = jnp.where(pos >= spec.total_len, 0, pos)
    for i in range(spec.depth):
        p = params[f"block{i + 1}"]
        q, k, v = _block_qkv(p, x, H, Dh, H_kv)
        cache = _write_kv_rows(cache, i, k, v, pos)
        if isinstance(cache, PagedSlotCache):
            # Same banded math over the table's gathered view
            # (ops/decode.paged_decode_attention) — scratch/stale
            # entries sit past ``pos`` and are masked, so the paged
            # step is token-identical to the fixed-lane one (pinned
            # by tests/test_paged.py).
            attn = paged_decode_attention(
                q[:, 0], cache.k[i], cache.v[i], cache.table, attend,
                *_scales(cache, i), impl=attn_impl,
            )  # [S, H, Dh] fp32
        else:
            # The whole stored cache and a layer index: the flash
            # kernel's index maps pick the layer's live blocks; only
            # the reference path slices ``cache.k[i]``.
            attn = decode_attention(
                q[:, 0], cache.k, cache.v, attend, *_scales(cache),
                impl=attn_impl, layer=i,
            )  # [S, H, Dh] fp32
        attn = attn.reshape(S, 1, spec.d_model).astype(x.dtype)
        x = _block_finish(spec, p, x, attn)
    x = _layer_norm(x, params["ln_final"])
    out_logits = (x[:, 0] @ embed.T.astype(jnp.float32)).astype(jnp.float32)
    return out_logits, cache._replace(
        pos=jnp.minimum(pos + 1, spec.total_len)
    )


def nucleus_filter(logits: jax.Array, top_p: jax.Array) -> jax.Array:
    """``filter_logits``'s top-p branch with a TRACED threshold.

    1-D ``logits``; ``top_p`` a traced scalar, so one compiled program
    serves every per-request nucleus setting (the serving engine's
    requirement — the static-arg variant would recompile per value).
    Semantics are identical to ``filter_logits(..., top_p=p)`` for
    p < 1: keep the smallest probability-sorted prefix reaching p, the
    best token always survives, masked entries become a large negative.
    Callers that need exact parity with ``filter_logits`` at p == 1.0
    (no filtering at all) must select the unfiltered logits themselves
    — at p == 1.0 this function can drop zero-probability tail entries
    whose preceding cumulative mass already rounds to 1.0.
    """
    logits = logits.astype(jnp.float32)
    neg = jnp.float32(jnp.finfo(jnp.float32).min / 2)
    sorted_logits = jnp.sort(logits)[::-1]
    probs = jax.nn.softmax(sorted_logits)
    cum = jnp.cumsum(probs)
    keep = jnp.concatenate(
        [jnp.ones((1,), bool), cum[:-1] < top_p]
    )
    thresh = jnp.min(
        jnp.where(keep, sorted_logits, jnp.float32(jnp.inf))
    )
    return jnp.where(logits < thresh, neg, logits)


def sample_token(
    logits: jax.Array,
    seed: jax.Array,
    step: jax.Array,
    temperature: jax.Array,
    top_p: jax.Array,
) -> jax.Array:
    """One on-device sampling decision → scalar int32 token.

    The fused-sampling half of the device-resident decode loop: the
    serving engine jits this INTO its decode/prefill programs so the
    per-step host transfer is tokens, not logits. Matches
    ``generate``'s ``pick`` decision-for-decision — greedy argmax at
    ``temperature <= 0``; otherwise ``categorical`` under the key
    ``fold_in(key(seed), step)`` over temperature-scaled,
    nucleus-filtered logits — so a seeded sampled stream is
    token-identical between the engine and per-request ``generate()``
    (pinned by tests/test_serve.py). All of seed/step/temperature/
    top_p are traced scalars: one compiled program covers any
    per-request sampling config. ``top_k`` is not supported here (its
    k is a SHAPE, so per-request values would recompile per mix);
    serve-side requests get temperature + top_p only.
    """
    logits = logits.astype(jnp.float32)

    def greedy(_):
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)

    def drawn(_):
        key = jax.random.fold_in(jax.random.key(seed), step)
        scaled = logits / temperature  # > 0 inside this branch
        # filter_logits skips filtering entirely at top_p == 1.0;
        # branch (not blend) so p == 1.0 stays bit-identical to
        # generate AND skips the vocab sort at runtime.
        cand = lax.cond(
            top_p < 1.0,
            lambda s: nucleus_filter(s, top_p),
            lambda s: s,
            scaled,
        )
        return jax.random.categorical(key, cand, axis=-1).astype(
            jnp.int32
        )

    # Real branch skip (this is a scalar cond, not a vmapped one): a
    # greedy request pays one argmax, no key derivation, no sort.
    return lax.cond(temperature > 0.0, drawn, greedy, operand=None)


@jax.named_scope("sampling")
def sample_slot_tokens(
    logits: jax.Array,
    seeds: jax.Array,
    steps: jax.Array,
    temps: jax.Array,
    top_ps: jax.Array,
) -> jax.Array:
    """Per-slot on-device sampling over [S, V] logits → [S] int32.

    Vectorized ``sample_token``, with the expensive machinery gated at
    RUNTIME (``lax.cond`` on the batch's sampling config, traced — no
    recompilation): a pure-greedy batch runs one argmax and never
    touches key derivation, and the vocab sort of the nucleus filter
    only runs when some lane actually sets top_p < 1. Mostly-greedy
    serving traffic therefore pays (almost) nothing for the fused
    sampling path — the reason the old engine kept sampling on host.

    Exactly the K=1 specialization of ``sample_slot_tokens_block``
    (offset 0 folds in ``steps + 0``), and implemented as such: the
    speculative path's seeded-acceptance guarantee depends on the two
    key-derivation/gating paths staying bit-identical, so there is
    only one.
    """
    return sample_slot_tokens_block(
        logits[:, None, :], seeds, steps, temps, top_ps
    )[:, 0]


def slot_decode_sample_step(
    spec: LMSpec,
    params: Any,
    cache: SlotCache,
    tokens: jax.Array,
    seeds: jax.Array,
    steps: jax.Array,
    temps: jax.Array,
    top_ps: jax.Array,
    *,
    attn_impl: str = "reference",
) -> tuple[jax.Array, SlotCache, jax.Array]:
    """``slot_decode_step`` with sampling fused → ([S] int32, cache,
    advanced step counters).

    The serving engine's steady-state program: advance all S lanes one
    token AND pick each lane's next token on device, so the engine
    transfers [S] int32 per step instead of [S, vocab] logits and the
    per-slot host sampling loop disappears. ``seeds``/``steps``/
    ``temps``/``top_ps`` are [S] per-slot sampling state living as
    DEVICE-RESIDENT engine state (written by ``prefill_chunk`` at
    refill, never re-uploaded per step): ``steps`` is each lane's
    emitted-token index — the ``fold_in`` counter that keeps seeded
    streams identical to ``generate`` — and is returned advanced by
    one so the loop threads it like the cache. Idle lanes sample
    garbage the engine ignores — their logits are finite (position 0
    is always live), so no NaN can propagate.
    """
    logits, cache = slot_decode_step(
        spec, params, cache, tokens, attn_impl=attn_impl
    )
    toks = sample_slot_tokens(logits, seeds, steps, temps, top_ps)
    return toks, cache, steps + 1


@jax.named_scope("sampling")
def sample_slot_tokens_block(
    logits: jax.Array,
    seeds: jax.Array,
    steps: jax.Array,
    temps: jax.Array,
    top_ps: jax.Array,
) -> jax.Array:
    """Per-(slot, offset) sampling over [S, K, V] logits → [S, K] int32.

    The verify-step sibling of ``sample_slot_tokens``: offset j of
    lane s samples under ``fold_in(key(seeds[s]), steps[s] + j)`` —
    the EXACT key the non-speculative loop would use for that lane's
    (steps[s] + j)-th emitted token, which is what makes speculative
    acceptance exact for seeded sampling (the target's tokens are the
    same stream, just computed K at a time). Same runtime gating: a
    pure-greedy batch runs one argmax, the nucleus sort only runs
    when some lane set top_p < 1.
    """
    S, K, _V = logits.shape
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    sampling = temps > 0.0

    def drawn(_):
        def lane_keys(s, st):
            return jax.vmap(
                lambda j: jax.random.fold_in(jax.random.key(s), st + j)
            )(jnp.arange(K))

        keys = jax.vmap(lane_keys)(seeds, steps)  # [S, K] keys
        safe_t = jnp.where(sampling, temps, jnp.float32(1.0))
        scaled = logits.astype(jnp.float32) / safe_t[:, None, None]

        def filtered(sc):
            return jax.vmap(
                lambda rows, p: jax.vmap(
                    lambda row: jnp.where(
                        p < 1.0, nucleus_filter(row, p), row
                    )
                )(rows)
            )(sc, top_ps)

        cand = lax.cond(
            jnp.any(sampling & (top_ps < 1.0)),
            filtered,
            lambda sc: sc,
            scaled,
        )
        return jax.vmap(
            jax.vmap(lambda k, c: jax.random.categorical(k, c, axis=-1))
        )(keys, cand).astype(jnp.int32)

    toks = lax.cond(
        jnp.any(sampling), drawn, lambda _: greedy, operand=None
    )
    return jnp.where(sampling[:, None], toks, greedy)


def slot_verify_step(
    spec: LMSpec,
    params: Any,
    cache: SlotCache,
    tokens: jax.Array,
    drafts: jax.Array,
    seeds: jax.Array,
    steps: jax.Array,
    temps: jax.Array,
    top_ps: jax.Array,
) -> tuple[jax.Array, SlotCache, jax.Array, jax.Array, jax.Array]:
    """Speculative-decoding verify: score K draft tokens per lane in
    ONE target-model step → ``(next_toks [S], cache, steps,
    target_toks [S, K], matched [S])``.

    ``tokens``: [S] — each lane's last accepted token (the decode
    loop's ``_toks``); ``drafts``: [S, K] — the draft model's K
    greedy proposals d_1..d_K. The target runs the K inputs
    ``[token, d_1..d_{K-1}]`` at positions ``pos[s]..pos[s]+K-1``
    under the banded per-lane mask (query j attends keys ``<=
    pos[s]+j``) — a K-wide chunked forward over the SAME cache lanes
    the decode step uses, K/V written (and on an int8 cache,
    quantized) before attending. Each of the K positions then samples
    the target's token with that position's own fold_in counter
    (``sample_slot_tokens_block``), so ``target_toks[s]`` is exactly
    the token stream the non-speculative loop would emit.

    Acceptance is prefix-exact: ``matched[s]`` = leading positions
    where draft == target. The lane emits ``n = min(matched + 1, K)``
    tokens — the matched drafts plus the target's correction token
    (or, on a full match, the K targets with no bonus: the K+1-th
    logit was never computed) — and ``next_toks``/``pos``/``steps``
    advance by exactly n per lane, so rejected positions' K/V rows
    sit above ``pos`` (never attendable) until the next round
    overwrites them — the engine's write-before-attend invariant.
    Output equivalence to the non-speculative stream is exact for
    greedy AND seeded sampling (tests/test_spec_decode.py).

    The write start is pre-clamped at ``total_len - K`` (the row write
    clamps its start to keep K rows in the lane, and would shift the
    write over live lines otherwise): the engine reserves K-1
    positions at admission so a LIVE lane never triggers the clamp — it only guards idle lanes
    parked at the position ceiling.
    """
    embed = params["embed"]
    S, K = drafts.shape
    H = spec.num_heads
    Dh = spec.d_model // H
    H_kv = _kv_heads(spec)
    G = H // H_kv
    pos = cache.pos  # [S]
    inputs = jnp.concatenate([tokens[:, None], drafts[:, :-1]], axis=1)
    x = embed[inputs]  # [S, K, d]
    pe = params["pos_embed"][0]  # [L, d]
    offsets = jnp.arange(K, dtype=jnp.int32)
    q_pos = jnp.minimum(
        pos[:, None] + offsets[None, :], spec.total_len - 1
    )  # [S, K]
    x = x + pe[q_pos].astype(x.dtype)
    wstart = jnp.minimum(pos, spec.total_len - K)
    live = (
        jnp.arange(spec.total_len)[None, None, :]
        <= (pos[:, None] + offsets[None, :])[:, :, None]
    )[:, None, None, :, :]  # [S, 1, 1, K, L]
    for i in range(spec.depth):
        p = params[f"block{i + 1}"]
        q, k, v = _block_qkv(p, x, H, Dh, H_kv)
        cache = _write_kv_rows(cache, i, k, v, wstart)
        # Full [S, L] float views: dequantized if int8, gathered
        # through the page tables if paged (_full_kv) — the verify
        # math itself is cache-layout-blind.
        kf, vf = _full_kv(cache, i)
        qg = q.reshape(S, K, H_kv, G, Dh)
        logits = (
            jnp.einsum(
                "bqkgd,blkd->bkgql",
                qg.astype(jnp.float32),
                kf.astype(jnp.float32),
            )
            * Dh**-0.5
        )  # [S, H_kv, G, K, L]
        logits = jnp.where(live, logits, -jnp.inf)
        w = jax.nn.softmax(logits, axis=-1)
        attn = jnp.einsum(
            "bkgql,blkd->bqkgd", w, vf.astype(jnp.float32)
        )
        attn = attn.reshape(S, K, spec.d_model).astype(x.dtype)
        x = _block_finish(spec, p, x, attn)
    x = _layer_norm(x, params["ln_final"])
    out_logits = (x @ embed.T.astype(jnp.float32)).astype(jnp.float32)
    target = sample_slot_tokens_block(
        out_logits, seeds, steps, temps, top_ps
    )  # [S, K]
    # Leading exact matches: cumprod turns the first mismatch into a
    # permanent zero, so the sum is the accepted-prefix length.
    matched = (
        jnp.cumprod((target == drafts).astype(jnp.int32), axis=1)
        .sum(axis=1)
        .astype(jnp.int32)
    )  # [S], 0..K
    n_emit = jnp.minimum(matched + 1, K)
    next_toks = jnp.take_along_axis(
        target, jnp.minimum(matched, K - 1)[:, None], axis=1
    )[:, 0]
    return (
        next_toks,
        cache._replace(pos=jnp.minimum(pos + n_emit, spec.total_len)),
        steps + n_emit,
        target,
        matched,
    )


def install_lane_sampling(
    toks, seeds, steps, temps, top_ps, slot, final, seed, temperature,
    top_p, last_logits,
):
    """The tail every one-token chunk program shares: when ``final``,
    sample the request's FIRST token from ``last_logits()`` (``[V]``
    float32 at the prompt's last position) and splice it into ``toks``
    at ``slot``; install the lane's sampling state. Returns ``(toks,
    seeds, steps, temps, top_ps, first_token)``."""

    def _sample_first(_):
        # Only the FINAL chunk owes a token: the last-position layer
        # norm, the [d]×[vocab] logits projection and the sampling
        # draw sit behind a real branch (scalar cond) so every
        # non-final chunk of a long prompt skips them entirely.
        tok = sample_token(
            last_logits(), seed, jnp.int32(0), temperature, top_p
        )
        return lax.dynamic_update_slice(toks, tok[None], (slot,)), tok

    new_toks, first = lax.cond(
        final, _sample_first, lambda _: (toks, jnp.int32(0)),
        operand=None,
    )
    put = lax.dynamic_update_slice
    seeds = put(seeds, seed[None].astype(seeds.dtype), (slot,))
    steps = put(
        steps,
        jnp.where(final, jnp.int32(1), jnp.int32(0))[None],
        (slot,),
    )
    temps = put(temps, temperature[None].astype(temps.dtype), (slot,))
    top_ps = put(top_ps, top_p[None].astype(top_ps.dtype), (slot,))
    return new_toks, seeds, steps, temps, top_ps, first


def prefill_chunk(
    spec: LMSpec,
    params: Any,
    cache: SlotCache,
    toks: jax.Array,
    seeds: jax.Array,
    steps: jax.Array,
    temps: jax.Array,
    top_ps: jax.Array,
    slot: jax.Array,
    chunk: jax.Array,
    start: jax.Array,
    length: jax.Array,
    final: jax.Array,
    seed: jax.Array,
    temperature: jax.Array,
    top_p: jax.Array,
    *,
    lane_attend: bool = True,
) -> tuple[SlotCache, jax.Array, jax.Array, jax.Array, jax.Array,
           jax.Array, jax.Array]:
    """Ingest ONE chunk of a prompt into a cache lane, in place.

    The Sarathi-style stall-free replacement for monolithic
    ``prefill_slot`` + ``write_slot``: a long prompt is split into
    fixed-width chunks, each co-scheduled with decode steps so running
    lanes never wait behind a full-width prefill. Per chunk:

    - ``chunk``: [C] int32 — prompt tokens for absolute positions
      [start, start + length), arbitrary padding after ``length``. C is
      the compiled width (one program per bucketed width); ``start``/
      ``length`` are traced, so chunk position never recompiles.
    - K/V for all C positions are written into lane ``slot`` of the
      DONATED ``cache`` first; attention then runs the C queries
      against their causal prefix. ``lane_attend`` (PYTHON-static —
      the engine compiles both variants) picks the key source: True
      reads the full lane under the banded mask ``key <= start + i``
      (``dot_product_attention(..., q_offset=start)``) — write-then-
      attend, continuation chunks see earlier chunks' cache lines;
      False attends the chunk against ITSELF (plain causal square),
      correct exactly when ``start == 0`` and C ≥ the whole prompt —
      the single-chunk fast path that keeps short prompts at
      monolithic-prefill cost instead of total_len-wide reads. Pad
      positions (>= length) write garbage ABOVE the lane's live
      region; the decode loop overwrites each line before it becomes
      attendable (the same invariant ``write_slot`` relied on).
    - The lane's ``pos`` is set to ``start + length`` — which also
      repairs the spurious ``pos`` advance idle-shape decode steps
      apply to mid-prefill lanes between chunks.
    - The lane's SAMPLING state is installed on device: ``seeds``/
      ``temps``/``top_ps`` take the request's scalars at ``slot``, and
      ``steps`` becomes 1 on the final chunk (the next decode samples
      emitted-token index 1) — so the engine never re-uploads
      per-slot sampling arrays on the steady-state path.
    - When ``final`` (traced bool) the request's FIRST token is
      sampled on device (``sample_token`` at step 0) and spliced into
      ``toks`` at ``slot``, so the refilled lane joins the very next
      decode step without any host round-trip.

    Returns ``(cache, toks, seeds, steps, temps, top_ps, first_token)``
    — ``first_token`` is the sampled scalar (0 unless ``final``; the
    whole logits/sampling tail sits behind a ``final`` branch),
    exposed so the engine can fetch the value asynchronously for the
    completion record.
    """
    C = chunk.shape[0]
    H = spec.num_heads
    Dh = spec.d_model // H
    H_kv = _kv_heads(spec)
    G = H // H_kv
    embed = params["embed"]
    x = embed[chunk][None]  # [1, C, d]
    pe = lax.dynamic_slice_in_dim(
        params["pos_embed"], start, C, axis=1
    )
    x = x + pe.astype(x.dtype)
    quantized = cache.quantized()
    paged = isinstance(cache, PagedSlotCache)
    ck, cv = cache.k, cache.v
    ksc, vsc = cache.k_scale, cache.v_scale
    if paged:
        # One lane's table row + this chunk's scatter coordinates,
        # computed once outside the layer loop: positions
        # [start, start + C) map through the row to (page id, offset)
        # pairs. The engine's min_bucket clamp keeps start + C <=
        # total_len (the tail-chunk invariant), so the only
        # non-private targets are pad positions past the lane's
        # demand — those rows land in whatever the table says (their
        # page, or scratch page 0) above the live region, overwritten
        # before they become attendable exactly like fixed-lane pads.
        row = lax.dynamic_index_in_dim(
            cache.table, slot, 0, keepdims=False
        )  # [lane_pages] int32
        pids, offs = _page_scatter_ids(
            row, start + jnp.arange(C, dtype=jnp.int32),
            cache.page_size, cache.num_pages,
        )
    for i in range(spec.depth):
        p = params[f"block{i + 1}"]
        q, k, v = _block_qkv(p, x, H, Dh, H_kv)
        if quantized:
            # Quantize-on-write (ops/decode.quantize_kv): the cache
            # only ever holds int8 rows + per-head scales — chunked
            # prefill is the bulk write path, so this is where the
            # cache-bytes halving is actually earned.
            wk, k_s = quantize_kv(k)
            wv, v_s = quantize_kv(v)
            if paged:
                ksc = ksc.at[i, pids, offs].set(k_s[0])
                vsc = vsc.at[i, pids, offs].set(v_s[0])
            else:
                ksc = lax.dynamic_update_slice(
                    ksc, k_s[:, None], (i, slot, start, 0)
                )
                vsc = lax.dynamic_update_slice(
                    vsc, v_s[:, None], (i, slot, start, 0)
                )
        else:
            wk, wv = k.astype(ck.dtype), v.astype(cv.dtype)
        if paged:
            ck = ck.at[i, pids, offs].set(wk[0])
            cv = cv.at[i, pids, offs].set(wv[0])
        else:
            ck = lax.dynamic_update_slice(
                ck, wk[:, None], (i, slot, start, 0, 0)
            )
            cv = lax.dynamic_update_slice(
                cv, wv[:, None], (i, slot, start, 0, 0)
            )
        if lane_attend:
            if paged:
                # The lane's logical [L] view is its table row's
                # gather — write-then-attend, so a continuation chunk
                # sees both the matched PREFIX pages (the hit's whole
                # point: those tokens were never prefilled here) and
                # this chunk's freshly scattered rows.
                lane_k = jnp.take(ck[i], row, axis=0)
                lane_k = lane_k.reshape(-1, *lane_k.shape[2:])
                lane_v = jnp.take(cv[i], row, axis=0)
                lane_v = lane_v.reshape(-1, *lane_v.shape[2:])
                if quantized:
                    sck = jnp.take(ksc[i], row, axis=0)
                    scv = jnp.take(vsc[i], row, axis=0)
                    lane_k = dequantize_kv(
                        lane_k, sck.reshape(-1, sck.shape[2])
                    )
                    lane_v = dequantize_kv(
                        lane_v, scv.reshape(-1, scv.shape[2])
                    )
            else:
                lane_k = lax.dynamic_index_in_dim(
                    ck[i], slot, axis=0, keepdims=False
                )
                lane_v = lax.dynamic_index_in_dim(
                    cv[i], slot, axis=0, keepdims=False
                )
                if quantized:
                    lane_k = dequantize_kv(
                        lane_k,
                        lax.dynamic_index_in_dim(
                            ksc[i], slot, axis=0, keepdims=False
                        ),
                    )
                    lane_v = dequantize_kv(
                        lane_v,
                        lax.dynamic_index_in_dim(
                            vsc[i], slot, axis=0, keepdims=False
                        ),
                    )
            attn = dot_product_attention(
                q.astype(jnp.float32),
                jnp.repeat(lane_k, G, axis=1)[None].astype(jnp.float32),
                jnp.repeat(lane_v, G, axis=1)[None].astype(jnp.float32),
                causal=True,
                q_offset=start,
            )
        else:
            attn = dot_product_attention(
                q.astype(jnp.float32),
                jnp.repeat(k, G, axis=2).astype(jnp.float32),
                jnp.repeat(v, G, axis=2).astype(jnp.float32),
                causal=True,
            )
        attn = attn.reshape(1, C, spec.d_model).astype(x.dtype)
        x = _block_finish(spec, p, x, attn)
    def last_logits():
        xt = lax.dynamic_slice_in_dim(x, length - 1, 1, axis=1)
        xt = _layer_norm(xt, params["ln_final"])
        return (
            xt[0, 0] @ embed.T.astype(jnp.float32)
        ).astype(jnp.float32)

    new_toks, seeds, steps, temps, top_ps, first = install_lane_sampling(
        toks, seeds, steps, temps, top_ps, slot, final, seed,
        temperature, top_p, last_logits,
    )
    new_pos = lax.dynamic_update_slice(
        cache.pos, (start + length)[None].astype(jnp.int32), (slot,)
    )
    return (
        # _replace keeps the cache KIND: the paged pytree carries its
        # table through untouched (tables only change at the engine's
        # bind/retire host events, never inside a program).
        cache._replace(
            k=ck, v=cv, pos=new_pos, k_scale=ksc, v_scale=vsc
        ),
        new_toks, seeds, steps, temps, top_ps, first,
    )


def cached_logits(
    spec: LMSpec, params: Any, tokens: jax.Array
) -> jax.Array:
    """Per-position logits via the cache — [B, T, vocab].

    The parity probe: must equal ``dense_lm_apply(spec, params,
    tokens)`` (full-sequence forward) to fp32 tolerance.
    """
    cache = init_cache(spec, tokens.shape[0])

    def step(cache, tok):
        logits, cache = decode_step(spec, params, cache, tok)
        return cache, logits

    _, all_logits = lax.scan(step, cache, tokens.T)
    return all_logits.transpose(1, 0, 2)
