"""The ``qwen3_moe`` block and generation by masked diffusion over
blocks, for serving (HF ``model_type`` ``sdar_moe``).

``LMSpec.block == "qwen3_moe"`` names the layer; ``block_length`` B > 0
the way of generating. Everything here is a function of the parameter
tree and the spec; the serve engine (serve/engine.py) jits
:func:`prefill_chunk` and :func:`block_step`, the tests also
:func:`dense_logits`.

**The layer** (all projections bias-free; RMSNorm(x; w) =
x * rsqrt(mean(x^2) + eps) * w with fp32 statistics):
``h = x + Attn(RMSNorm(x; input_layernorm))``,
``y = h + MoE(RMSNorm(h; post_attention_layernorm))``; after the last
layer ``RMSNorm(.; norm)`` and the untied ``lm_head``. Attn: q as H
heads of Dh, k and v as H_kv heads of Dh (H * Dh need not equal the
model width); q and k each pass an RMSNorm over the head's Dh values;
rotary positions over the whole head, rotate-half form,
``inv_freq_i = theta^(-2i/Dh)``; the cache holds K AFTER rotation;
softmax(q k^T / sqrt(Dh)) in fp32, each kv head serving H / H_kv query
heads. Key j is visible to query i iff ``j // B <= i // B``:
bidirectional inside a block, causal between blocks. MoE, every layer:
softmax over all E router logits in fp32, the top_k largest,
renormalised; ``sum_k w_k down_e(silu(gate_e u) * up_e u)``; no token
dropped, no shared expert (ops/moe.py). Residual stream, norms and
softmaxes fp32; every matmul takes its operands in the WEIGHT's dtype
(bfloat16 as stored) and accumulates in fp32 (:func:`_mm`, the one
place).

**The tree**, name for name the HF checkpoint's (a linear layer's
``weight`` ``[out, in]`` is stored transposed, ``[in, out]``, under the
module's name; the experts of a layer are stacked on a leading axis):

====================================================  =================================================
``embed_tokens`` ``[V, d]``                           ``model.embed_tokens.weight``
``layers/{i}/input_layernorm`` ``[d]``                ``model.layers.{i}.input_layernorm.weight``
``layers/{i}/self_attn/q_proj`` ``[d, H*Dh]``         ``model.layers.{i}.self_attn.q_proj.weight`` ^T
``layers/{i}/self_attn/k_proj|v_proj`` ``[d, Hkv*Dh]``  ``...self_attn.k_proj|v_proj.weight`` ^T
``layers/{i}/self_attn/o_proj`` ``[H*Dh, d]``         ``...self_attn.o_proj.weight`` ^T
``layers/{i}/self_attn/q_norm|k_norm`` ``[Dh]``       ``...self_attn.q_norm|k_norm.weight``
``layers/{i}/post_attention_layernorm`` ``[d]``       ``...post_attention_layernorm.weight``
``layers/{i}/mlp/gate`` ``[d, E]``                    ``...mlp.gate.weight`` ^T (the router)
``layers/{i}/mlp/experts/gate_proj|up_proj`` ``[E, d, f]``  ``...mlp.experts.{e}.gate_proj|up_proj.weight`` ^T
``layers/{i}/mlp/experts/down_proj`` ``[E, f, d]``    ``...mlp.experts.{e}.down_proj.weight`` ^T
``norm`` ``[d]``                                      ``model.norm.weight``
``lm_head`` ``[V, d]``                                ``lm_head.weight``
====================================================  =================================================

**Generation** (the model's own generate script; B = ``block_length``,
``denoise_steps`` steps a block). The sequence is the prompt followed
by mask tokens up to a multiple of B. Whole prompt blocks
(``len(prompt) // B``) are prefilled under the block-causal mask and
fill the cache (:func:`prefill_chunk`); the prompt's last
``len(prompt) % B`` tokens open the first generated block, unmasked. A
lane then holds a block of B positions at ``pos..pos+B-1``, some of
them masked. :func:`block_step` runs ONE forward over every lane's
block — its K/V rows written at ``pos`` before attending (the verify
round's invariant: nothing above ``pos + B - 1`` is attendable, and
``pos`` does not move, so the next forward overwrites them) — and then,
per lane: while a position is masked, takes for each masked position
its token (argmax at temperature 0, else the lane's seeded draw) and
its confidence (that token's softmax probability; a masked position
predicts its OWN token, no shift) and unmasks by ``unmask``:
``low_confidence_static`` the ``B / denoise_steps`` most confident
masked positions, ``low_confidence_dynamic`` also every masked position
above ``unmask_threshold``. Such a forward yields no token. When no
mask was left, the forward just made wrote the clean block's rows for
good: ``pos`` advances by B, the block's tokens are COMMITTED, and the
lane opens a fresh all-mask block. A block costs 2 to
``denoise_steps + 1`` forwards.
"""

from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from ddp_tpu.models.generate import (
    SlotCache,
    _write_kv_rows,
    sample_slot_tokens_block,
)
from ddp_tpu.models.lm import LMSpec, head_dim_of
from ddp_tpu.ops.attention import dot_product_attention
from ddp_tpu.ops.decode import decode_attention
from ddp_tpu.ops.moe import moe_layer

BLOCK = "qwen3_moe"
UNMASK = ("low_confidence_static", "low_confidence_dynamic")
INIT_STD = 0.02


def validate(spec: LMSpec) -> None:
    """Raise ValueError unless ``spec`` names this module's model."""
    if spec.block != BLOCK:
        raise ValueError(f"block {spec.block!r} is not {BLOCK!r}")
    B = spec.block_length
    if B < 1 or B & (B - 1):
        raise ValueError(
            f"the {BLOCK} block generates by blocks: block_length must "
            f"be a power of two >= 1, got {B}"
        )
    if not 1 <= spec.denoise_steps <= B or B % spec.denoise_steps:
        raise ValueError(
            f"denoise_steps {spec.denoise_steps} must divide "
            f"block_length {B}"
        )
    if not 0 <= spec.mask_token_id < spec.vocab_size:
        raise ValueError(
            f"mask_token_id {spec.mask_token_id} outside the vocabulary "
            f"of {spec.vocab_size}"
        )
    if spec.unmask not in UNMASK:
        raise ValueError(f"unmask must be one of {UNMASK}, got {spec.unmask!r}")
    if spec.num_experts < spec.moe_top_k or spec.moe_intermediate < 1:
        raise ValueError(
            "the block routes every MLP: needs num_experts >= moe_top_k "
            "and moe_intermediate"
        )
    if head_dim_of(spec) % 2 or spec.num_heads % (spec.num_kv_heads or 1):
        raise ValueError("head_dim must be even and H_kv divide H")


def _kv_heads(spec: LMSpec) -> int:
    return spec.num_kv_heads or spec.num_heads


def leaf_shapes(spec: LMSpec) -> dict[str, tuple[int, ...]]:
    """Flat ``path -> shape`` of the tree above ('/'-joined)."""
    d, Dh, f, E = (spec.d_model, head_dim_of(spec), spec.moe_intermediate,
                   spec.num_experts)
    H, Hkv = spec.num_heads, _kv_heads(spec)
    out = {"embed_tokens": (spec.vocab_size, d)}
    for i in range(spec.depth):
        b = f"layers/{i}"
        out.update({
            f"{b}/input_layernorm": (d,),
            f"{b}/self_attn/q_proj": (d, H * Dh),
            f"{b}/self_attn/k_proj": (d, Hkv * Dh),
            f"{b}/self_attn/v_proj": (d, Hkv * Dh),
            f"{b}/self_attn/o_proj": (H * Dh, d),
            f"{b}/self_attn/q_norm": (Dh,),
            f"{b}/self_attn/k_norm": (Dh,),
            f"{b}/post_attention_layernorm": (d,),
            f"{b}/mlp/gate": (d, E),
            f"{b}/mlp/experts/gate_proj": (E, d, f),
            f"{b}/mlp/experts/up_proj": (E, d, f),
            f"{b}/mlp/experts/down_proj": (E, f, d),
        })
    out["norm"] = (d,)
    out["lm_head"] = (spec.vocab_size, d)
    return out


def init_params(spec: LMSpec, *, seed: int = 0, dtype=jnp.bfloat16):
    """Seeded normal(0, 0.02) matrices and unit norm weights, stored in
    ``dtype`` (the model is published in bfloat16)."""
    key = jax.random.key(seed)
    tree: dict = {}
    for n, (path, shape) in enumerate(leaf_shapes(spec).items()):
        leaf = (
            jnp.ones(shape, dtype) if path.endswith("norm")
            else (INIT_STD * jax.random.normal(
                jax.random.fold_in(key, n), shape, jnp.float32
            )).astype(dtype)
        )
        node = tree
        *parents, last = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[last] = leaf
    return tree


def derive_spec(params: Any, *, num_heads: int = 0, **overrides) -> LMSpec:
    """The spec of a restored tree: every size the shapes show, the
    rest (cache length, routing, the generation's settings) from the
    ``lm_spec.json`` sidecar's fields. The head count is in the shapes
    here (``q_norm`` gives the head size), so ``num_heads`` is unused."""
    del num_heads
    try:
        vocab_size, d_model = (int(s) for s in params["embed_tokens"].shape)
        layer = params["layers"]["0"]
        attn, experts = layer["self_attn"], layer["mlp"]["experts"]
        head_dim = int(attn["q_norm"].shape[0])
        fields = dict(
            vocab_size=vocab_size, d_model=d_model,
            depth=len(params["layers"]), head_dim=head_dim,
            num_heads=int(attn["q_proj"].shape[1]) // head_dim,
            num_kv_heads=int(attn["k_proj"].shape[1]) // head_dim,
            num_experts=int(experts["gate_proj"].shape[0]),
            moe_intermediate=int(experts["gate_proj"].shape[2]),
            block=BLOCK,
        )
    except (KeyError, TypeError, AttributeError, IndexError) as e:
        raise ValueError(f"not a {BLOCK} parameter tree (missing {e})")
    fields.update(
        (k, v) for k, v in overrides.items()
        if k in LMSpec._fields and k not in fields
    )
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
    """Write ``params`` as a checkpoint ``scripts/serve.py`` restores
    (``--checkpoint_dir`` or ``--model NAME=DIR``), with the
    ``lm_spec.json`` sidecar that carries what the shapes cannot: the
    cache's length, the routing and the generation's settings. How a
    converted HF checkpoint (the table above) gets on disk."""
    from ddp_tpu.train.checkpoint import save_params_with_spec

    validate(spec)
    save_params_with_spec(directory, spec, params, epoch=epoch)


# ---- the layer --------------------------------------------------------


def _mm(x, w, *, transposed: bool = False):
    """THE matmul: operands in the weight's dtype, fp32 accumulation.
    ``transposed``: ``w`` is ``[out, in]`` (the head, stored as the
    embedding is)."""
    x = x.astype(w.dtype)
    if transposed:
        return jnp.einsum("...d,vd->...v", x, w,
                          preferred_element_type=jnp.float32)
    return jnp.dot(x, w, preferred_element_type=jnp.float32)


def rms_norm(x, w, eps: float):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return x32 * lax.rsqrt(var + eps) * w.astype(jnp.float32)


def rotary(x, positions, theta: float):
    """Rotate-half rotary embedding. ``x``: ``[..., T, heads, Dh]``,
    ``positions``: ``[..., T]`` absolute positions."""
    Dh = x.shape[-1]
    half = Dh // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / Dh)
    ang = positions.astype(jnp.float32)[..., None] * inv  # [..., T, half]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[..., None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[..., None, :]
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos + rot * sin


def attn_qkv(spec: LMSpec, p, u, positions):
    """Normed input ``u`` ``[..., T, d]`` -> q ``[..., T, H, Dh]`` and
    k, v ``[..., T, H_kv, Dh]``; q and k head-normed and rotated."""
    Dh = head_dim_of(spec)

    def heads(name, n):
        return _mm(u, p[name]).reshape(*u.shape[:-1], n, Dh)

    q = rms_norm(heads("q_proj", spec.num_heads), p["q_norm"], spec.rms_eps)
    k = rms_norm(heads("k_proj", _kv_heads(spec)), p["k_norm"], spec.rms_eps)
    v = heads("v_proj", _kv_heads(spec))
    return (rotary(q, positions, spec.rope_theta),
            rotary(k, positions, spec.rope_theta), v)


def moe_mlp(spec: LMSpec, p, u, *, impl: str = "auto"):
    """The routed MLP on normed ``u`` ``[..., d]`` -> (out, counts)."""
    flat = u.reshape(-1, u.shape[-1])
    e = p["experts"]
    out, stats = moe_layer(
        flat, _mm(flat, p["gate"]), e["gate_proj"], e["up_proj"],
        e["down_proj"], top_k=spec.moe_top_k,
        normalize=spec.moe_normalize_gates, impl=impl,
    )
    return out.reshape(u.shape), stats


def forward_layers(spec: LMSpec, params, x, positions, attend, *,
                   moe_impl: str = "auto"):
    """Every layer over ``x`` ``[..., T, d]`` at ``positions``.
    ``attend(i, q, k, v) -> [..., T, H*Dh]`` owns the keys: the cache
    write and the mask are the caller's (prefill chunk, block step,
    dense forward). Returns (x, routing counts summed over layers)."""
    stats = jnp.zeros((3,), jnp.int32)
    for i in range(spec.depth):
        p = params["layers"][str(i)]
        u = rms_norm(x, p["input_layernorm"], spec.rms_eps)
        q, k, v = attn_qkv(spec, p["self_attn"], u, positions)
        x = x + _mm(attend(i, q, k, v), p["self_attn"]["o_proj"])
        u = rms_norm(x, p["post_attention_layernorm"], spec.rms_eps)
        m, s = moe_mlp(spec, p["mlp"], u, impl=moe_impl)
        x = x + m
        stats = stats + s
    return x, stats


def head_logits(spec: LMSpec, params, x):
    return _mm(rms_norm(x, params["norm"], spec.rms_eps),
               params["lm_head"], transposed=True)


def _embed(params, tokens):
    return params["embed_tokens"][tokens].astype(jnp.float32)


def _expand_kv(x, groups: int, axis: int):
    return jnp.repeat(x, groups, axis=axis).astype(jnp.float32)


def dense_logits(spec: LMSpec, params, tokens, *, moe_impl: str = "auto"):
    """Full forward of ``tokens`` ``[N, T]`` under the block-causal
    mask, no cache -> logits ``[N, T, V]``. The parity probe."""
    G = spec.num_heads // _kv_heads(spec)
    T = tokens.shape[1]

    def attend(i, q, k, v):
        a = dot_product_attention(
            q, _expand_kv(k, G, 2), _expand_kv(v, G, 2), causal=True,
            block=spec.block_length,
        )
        return a.reshape(*a.shape[:2], -1)

    x, _ = forward_layers(
        spec, params, _embed(params, tokens),
        jnp.broadcast_to(jnp.arange(T), tokens.shape), attend,
        moe_impl=moe_impl,
    )
    return head_logits(spec, params, x)


# ---- lanes -----------------------------------------------------------


class BlockLanes(NamedTuple):
    """What a lane in a block holds on the device, beside its cache
    lane (``SlotCache.pos[s]`` is the block's first position)."""

    toks: jax.Array  # [S, B] int32: the block's tokens (unmasked ones)
    mask: jax.Array  # [S, B] bool: position still masked
    active: jax.Array  # [S] bool: lane is generating
    lead: jax.Array  # [S] int32: prompt tokens at the block's head
    remaining: jax.Array  # [S] int32: tokens the request is still owed
    seeds: jax.Array  # [S] int32
    steps: jax.Array  # [S] int32: the seeded draws' counter
    temps: jax.Array  # [S] fp32
    top_ps: jax.Array  # [S] fp32


def init_block_lanes(spec: LMSpec, slots: int) -> BlockLanes:
    B = spec.block_length
    z = lambda dt: jnp.zeros((slots,), dt)
    return BlockLanes(
        toks=jnp.full((slots, B), spec.mask_token_id, jnp.int32),
        mask=jnp.ones((slots, B), bool), active=z(bool),
        lead=z(jnp.int32), remaining=z(jnp.int32), seeds=z(jnp.int32),
        steps=z(jnp.int32), temps=z(jnp.float32),
        top_ps=jnp.ones((slots,), jnp.float32),
    )


# Columns of a block step's report, after the 2B of tokens and mask.
REPORT_EXTRA = ("active", "committed", "unmasked")


def prefill_chunk(
    spec: LMSpec, params: Any, cache: SlotCache, lanes: BlockLanes,
    slot, chunk, start, length, final, tail, tail_len, new_tokens,
    seed, temperature, top_p, *,
    lane_attend: bool = True, moe_impl: str = "auto",
):
    """Ingest one chunk of a prompt's WHOLE blocks into lane ``slot``
    (models/generate.prefill_chunk's contract: ``chunk`` ``[C]`` holds
    positions ``[start, start + length)``, K/V written before
    attending, ``lane_attend=False`` the self-contained first chunk),
    under the block-causal mask; C, ``start`` and ``length`` are
    multiples of the block length. It owes no token. The lane's
    ``pos`` becomes ``start + length`` and the lane leaves the
    generating set; the ``final`` chunk installs its first block:
    ``tail[:tail_len]`` (the prompt's last ``len % B`` tokens)
    unmasked, the rest masked, ``new_tokens`` owed, the sampling
    state. ``length`` 0 with ``final`` installs a lane whose prompt is
    shorter than a block. Returns (cache, lanes, routing counts)."""
    B = spec.block_length
    C = chunk.shape[0]
    G = spec.num_heads // _kv_heads(spec)
    ck, cv = cache.k, cache.v

    def attend(i, q, k, v):
        nonlocal ck, cv
        ck = lax.dynamic_update_slice(
            ck, k.astype(ck.dtype)[:, None], (i, slot, start, 0, 0))
        cv = lax.dynamic_update_slice(
            cv, v.astype(cv.dtype)[:, None], (i, slot, start, 0, 0))
        if lane_attend:
            lane = lambda c: _expand_kv(lax.dynamic_index_in_dim(
                c[i], slot, axis=0, keepdims=False), G, 1)[None]
            a = dot_product_attention(
                q, lane(ck), lane(cv), causal=True, q_offset=start,
                block=B,
            )
        else:
            a = dot_product_attention(
                q, _expand_kv(k, G, 2), _expand_kv(v, G, 2), causal=True,
                block=B,
            )
        return a.reshape(1, C, -1)

    _, stats = forward_layers(
        spec, params, _embed(params, chunk)[None],
        (start + jnp.arange(C, dtype=jnp.int32))[None], attend,
        moe_impl=moe_impl,
    )
    j = jnp.arange(B, dtype=jnp.int32)
    put = lambda a, v: a.at[slot].set(jnp.asarray(v).astype(a.dtype))
    opened = j < tail_len
    lanes = BlockLanes(
        toks=put(lanes.toks, jnp.where(opened, tail, spec.mask_token_id)),
        mask=put(lanes.mask, ~opened),
        active=put(lanes.active, final),
        lead=put(lanes.lead, tail_len),
        remaining=put(lanes.remaining, new_tokens),
        seeds=put(lanes.seeds, seed),
        steps=put(lanes.steps, 0),
        temps=put(lanes.temps, temperature),
        top_ps=put(lanes.top_ps, top_p),
    )
    cache = cache._replace(k=ck, v=cv, pos=put(cache.pos, start + length))
    return cache, lanes, stats


def block_forward(spec: LMSpec, params, cache: SlotCache, tokens, active,
                  *, attn_impl: str = "reference", moe_impl: str = "auto"):
    """One forward over every lane's block: ``tokens`` ``[S, B]`` at
    positions ``pos[s]..pos[s]+B-1`` -> (logits ``[S, B, V]``, cache
    with the block's K/V rows written at ``pos``, routing counts).
    All B queries of a lane see the same keys (``<= pos + B - 1``), so
    they fold into the decode kernel's grouped-query dimension
    (G -> B * G rows a kv head) with no per-query mask. A lane that is
    not generating attends its first block alone."""
    S, B = tokens.shape
    L = cache.k.shape[2]
    Hkv = _kv_heads(spec)
    G = spec.num_heads // Hkv
    Dh = head_dim_of(spec)
    pos = cache.pos
    # The row write clamps its start to keep B rows in the lane; a
    # generating lane never gets there (admission reserves the block's
    # overhang), only a lane left at the ceiling.
    wstart = jnp.minimum(pos, L - B)
    last = jnp.where(active, wstart + (B - 1), B - 1)
    box = [cache]

    def attend(i, q, k, v):
        box[0] = _write_kv_rows(box[0], i, k, v, wstart)
        qf = q.reshape(S, B, Hkv, G, Dh).transpose(0, 2, 1, 3, 4)
        a = decode_attention(
            qf.reshape(S, Hkv * B * G, Dh), box[0].k, box[0].v, last,
            impl=attn_impl, layer=i,
        )
        a = a.reshape(S, Hkv, B, G, Dh).transpose(0, 2, 1, 3, 4)
        return a.reshape(S, B, Hkv * G * Dh)

    positions = pos[:, None] + jnp.arange(B, dtype=jnp.int32)[None, :]
    x, stats = forward_layers(
        spec, params, _embed(params, tokens), positions, attend,
        moe_impl=moe_impl,
    )
    return head_logits(spec, params, x), box[0], stats


@jax.named_scope("unmask")
def choose_and_unmask(spec: LMSpec, logits, lanes: BlockLanes):
    """The block's logits ``[S, B, V]`` -> (tokens ``[S, B]`` with the
    newly unmasked positions filled in, the mask that is left, how
    many positions each lane unmasked). Ties in confidence go to the
    earlier position."""
    B = spec.block_length
    tok = sample_slot_tokens_block(
        logits, lanes.seeds, lanes.steps, lanes.temps, lanes.top_ps
    )
    temp = jnp.where(lanes.temps > 0.0, lanes.temps, 1.0)[:, None, None]
    scaled = logits.astype(jnp.float32) / temp
    conf = jnp.exp(
        jnp.take_along_axis(scaled, tok[..., None], -1)[..., 0]
        - jax.nn.logsumexp(scaled, axis=-1)
    )
    c = jnp.where(lanes.mask, conf, -jnp.inf)
    j = jnp.arange(B)
    ahead = (c[:, None, :] > c[:, :, None]) | (
        (c[:, None, :] == c[:, :, None]) & (j[None, None, :] < j[None, :, None])
    )
    take = lanes.mask & (ahead.sum(-1) < B // spec.denoise_steps)
    if spec.unmask == "low_confidence_dynamic":
        take = take | (lanes.mask & (conf > spec.unmask_threshold))
    return (jnp.where(take, tok, lanes.toks), lanes.mask & ~take,
            take.sum(-1).astype(jnp.int32))


def block_step(spec: LMSpec, params, cache: SlotCache, lanes: BlockLanes,
               *, attn_impl: str = "reference", moe_impl: str = "auto"):
    """Advance every lane one forward -> (cache, lanes, report
    ``[S, 2B + 3]`` int32, routing counts ``[3]``).

    The report is what the host learns of the step, one step behind:
    the block's tokens and mask as the forward saw them (masked
    positions read ``mask_token_id``), then ``REPORT_EXTRA``: was the
    lane generating, did it COMMIT (its block was clean: the rows this
    forward wrote stand, ``pos`` advanced by B, the reported tokens
    are its output), and how many positions it unmasked. A lane whose
    commit pays off what its request is owed stops generating on the
    device, at once; its later forwards count for nothing."""
    B = spec.block_length
    seen = jnp.where(lanes.mask, spec.mask_token_id, lanes.toks)
    logits, cache, stats = block_forward(
        spec, params, cache, seen, lanes.active,
        attn_impl=attn_impl, moe_impl=moe_impl,
    )
    toks, mask, unmasked = choose_and_unmask(spec, logits, lanes)
    clean = ~lanes.mask.any(-1)
    commit = lanes.active & clean
    fill = (lanes.active & ~clean)[:, None]
    remaining = jnp.where(
        commit, lanes.remaining - (B - lanes.lead), lanes.remaining
    )
    report = jnp.concatenate([
        seen, lanes.mask.astype(jnp.int32),
        lanes.active.astype(jnp.int32)[:, None],
        commit.astype(jnp.int32)[:, None],
        jnp.where(fill[:, 0], unmasked, 0)[:, None],
    ], axis=1)
    new = lanes._replace(
        toks=jnp.where(commit[:, None], spec.mask_token_id,
                       jnp.where(fill, toks, lanes.toks)),
        mask=commit[:, None] | jnp.where(fill, mask, lanes.mask),
        active=lanes.active & ~(commit & (remaining <= 0)),
        lead=jnp.where(commit, 0, lanes.lead),
        remaining=remaining,
        steps=jnp.where(lanes.active, lanes.steps + B, lanes.steps),
    )
    L = cache.k.shape[2]
    cache = cache._replace(
        pos=jnp.where(commit, jnp.minimum(cache.pos + B, L), cache.pos)
    )
    return cache, new, report, stats
