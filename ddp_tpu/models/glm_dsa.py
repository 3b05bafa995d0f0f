"""The ``glm_dsa`` block (HF ``model_type`` ``glm_moe_dsa``): latent
attention (MLA) over the keys a learned indexer selects (DSA), leading
dense layers, then sigmoid-routed experts of which this process holds a
share, beside a shared one; for serving one token a step.

``LMSpec.block == "glm_dsa"`` names the stack. Everything here is a
function of the parameter tree and the spec; the serve engine
(serve/engine.py) jits :func:`prefill_chunk` and
:func:`slot_decode_sample_step` under the very signatures of the GPT-2
path's (models/generate.py), the tests also :func:`dense_logits`.

**The model** (RMSNorm(x; w) = x * rsqrt(mean(x^2) + eps) * w in fp32;
no bias but the indexer's LayerNorm and the router's choice bias):
``x0 = embed_tokens[tokens]``; every layer
``h = x + Attn(RMSNorm(x; input_layernorm))``,
``y = h + FFN(RMSNorm(h; post_attention_layernorm))``;
``logits = RMSNorm(x; norm) @ lm_head^T``. With ``u`` the normed input
at position ``t`` (H heads, R = ``kv_lora_rank``, Dn/Dr/Dv =
``qk_nope``/``qk_rope``/``v_head_dim``):

- *Latent attention.* ``c_q = RMSNorm(u @ q_a_proj; q_a_layernorm)``;
  ``q = c_q @ q_b_proj`` as H heads of ``[q_nope Dn | q_rope Dr]``.
  ``[c_kv R | k_rope Dr] = u @ kv_a_proj_with_mqa``;
  ``c_kv = RMSNorm(c_kv; kv_a_layernorm)``; ``k_rope`` is ONE vector
  for all heads. ``[k_nope Dn | v Dv]`` a head ``= c_kv @ kv_b_proj``.
  Rotary (``rope_theta``, INTERLEAVED: pairs ``(2i, 2i + 1)``,
  ``inv_freq_i = theta^(-2i/Dr)``) on ``q_rope`` and ``k_rope`` only.
  ``score_h(t, s) = (q_nope . k_nope + q_rope . k_rope) / sqrt(Dn + Dr)``,
  softmax over ``s`` in ``S_t`` (below), ``out = concat_h(sum_s p v) @
  o_proj``. The cache holds ``[c_kv | rotated k_rope]``, R + Dr a
  position. A prefill chunk EXPANDS (``k_nope`` and ``v`` of a block of
  keys from their latents, a per-query mask: on a TPU ONE kernel a
  layer, ops/latent_prefill.py, which holds a head's queries,
  accumulator and statistics in VMEM, expands a block of keys once and
  keeps its scores there, and still walks every live row of the lane;
  the ``jnp`` walk elsewhere, :func:`chunk_form`); a decode step ABSORBS
  (``kv_b_proj``'s key half into the query, its value half into the
  output: H heads against one (R + Dr)-wide key whose first R columns
  are the value). Both are the equations above.
- *Indexer, every layer.* ``qI = c_q @ indexer/wq_b`` as Hi heads of
  Di; ``kI = LayerNorm(u @ indexer/wk; indexer/k_norm)`` (Di, scale and
  bias); rotary on the FIRST Dr of each; ``w = (u @ indexer/weights_proj)
  * Hi^-0.5 * Di^-0.5``. ``I(t, s) = sum_j w_j(t) relu(qI_j(t) . kI(s))``
  for ``s <= t``; ``S_t`` = the ``index_topk`` largest (ties to the lower
  position), all of ``s <= t`` while ``t < index_topk``. The cache holds
  ``kI``, Di a position. (DeepSeek's inference code also rotates qI and
  kI by a Hadamard matrix, which leaves the dot as it is, and quantises
  them to FP8, which is that implementation's precision: neither here.)
- *FFN.* Layers below ``first_k_dense_replace``: SwiGLU of
  ``mlp_intermediate``. The others: ``s = sigmoid(h @ mlp/gate)`` in
  float32 over ALL ``n_routed_experts``; the ``moe_top_k`` largest of
  ``s + mlp/gate_bias`` are chosen (the bias moves the choice only);
  ``g_i = routed_scaling_factor * s_i / sum_chosen s``;
  ``y = sum_{i chosen, i held here} g_i E_i(h) + E_shared(h)``, SwiGLU of
  ``moe_intermediate``. This process holds experts ``expert_offset ..
  expert_offset + num_experts - 1`` (ops/moe.moe_share_layer); the sum
  over the chosen runs over all of them, held or not; what experts held
  elsewhere would add is left out, and nothing stands in for them.

Residual stream, norms, softmax, the router and the index scores' ReLU
and sum are fp32; every matmul takes its operands in the WEIGHT's (or
the stored row's) dtype and accumulates in fp32 (``sdar._mm``); the
router's matmul alone takes fp32 operands at the highest precision.

**The tree** (a linear layer's ``weight`` ``[out, in]`` stored
transposed under the module's name; a layer's held experts stacked):

====================================================  ================================================
``embed_tokens`` ``[V, d]``, ``lm_head`` ``[V, d]``   ``model.embed_tokens.weight``, ``lm_head.weight``
``layers/{i}/input_layernorm`` ``[d]``                ``model.layers.{i}.input_layernorm.weight``
``layers/{i}/self_attn/q_a_proj`` ``[d, Rq]``         ``...self_attn.q_a_proj.weight`` ^T
``.../q_a_layernorm`` ``[Rq]``, ``kv_a_layernorm``    ``...self_attn.q_a_layernorm|kv_a_layernorm.weight``
``.../q_b_proj`` ``[Rq, H*(Dn+Dr)]``                  ``...self_attn.q_b_proj.weight`` ^T
``.../kv_a_proj_with_mqa`` ``[d, R+Dr]``              ``...self_attn.kv_a_proj_with_mqa.weight`` ^T
``.../kv_b_proj`` ``[R, H*(Dn+Dv)]``                  ``...self_attn.kv_b_proj.weight`` ^T
``.../o_proj`` ``[H*Dv, d]``                          ``...self_attn.o_proj.weight`` ^T
``.../indexer/wq_b`` ``[Rq, Hi*Di]``, ``wk`` ``[d, Di]``  ``...self_attn.indexer.wq_b|wk.weight`` ^T
``.../indexer/k_norm/weight|bias`` ``[Di]``           ``...self_attn.indexer.k_norm.weight|bias``
``.../indexer/weights_proj`` ``[d, Hi]``              ``...self_attn.indexer.weights_proj.weight`` ^T
``layers/{i}/post_attention_layernorm`` ``[d]``       ``...post_attention_layernorm.weight``
``layers/{i}/mlp/gate_proj|up_proj|down_proj``        dense layers: ``...mlp.*_proj.weight`` ^T
``layers/{i}/mlp/gate`` ``[d, E]``, ``gate_bias``     ``...mlp.gate.weight`` ^T, ``e_score_correction_bias``
``layers/{i}/mlp/experts/gate_proj|up_proj`` ``[Eh, d, f]``  ``...mlp.experts.{e}.*_proj.weight`` ^T, held e
``layers/{i}/mlp/shared_experts/gate_proj|...``       ``...mlp.shared_experts.*_proj.weight`` ^T
``norm`` ``[d]``                                      ``model.norm.weight``
====================================================  ================================================

**A lane** (``generate.SlotCache``, its fourth kind) holds, a layer, one
``latent`` row (stored padded to whole groups of 128 lanes) and one
``index_k`` row a position, and no K/V rows.
Nothing in it is recurrent: a row written above ``pos`` (a chunk's
padding, an idle lane's step) is above everything a query may select
(``s <= t``) and is overwritten before it is live, as on the GPT-2
path. ``cache.sel`` keeps what the last decode step selected. Both
programs return one value more than the GPT-2 path's: the call's
(expert, token) pairs, routed and held here (``[2]`` int32).
"""

from __future__ import annotations

import time
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from ddp_tpu.models.generate import (
    SlotCache,
    install_lane_sampling,
    sample_slot_tokens,
)
from ddp_tpu.models.lm import LMSpec
from ddp_tpu.models.sdar import _mm, rms_norm
from ddp_tpu.obs.tracer import get_tracer
from ddp_tpu.ops.decode import (
    index_scores,
    latent_decode_attention,
    select_rows,
)
from ddp_tpu.ops.latent_prefill import masked_walk, tiles
from ddp_tpu.ops.moe import moe_share_layer

BLOCK = "glm_dsa"
INIT_STD = 0.02
BIAS_STD = 0.01
# what the serve engine asks of a block's module (serve/engine.py)
RECURRENT = False
# Keys a prefill chunk takes at a time, and the queries whose scores
# against them the ``latent_prefill`` kernel holds at a time (one head's
# [QUERY_TILE, KEY_BLOCK] fp32, in VMEM; the ``jnp`` walk's scores of
# all heads for a block of keys are its largest temporary: H x C x
# KEY_BLOCK fp32, through HBM).
KEY_BLOCK = 512
QUERY_TILE = 1024
_NEG = -1e30


def validate(spec: LMSpec) -> None:
    """Raise ValueError unless ``spec`` names this module's model."""
    if spec.block != BLOCK:
        raise ValueError(f"block {spec.block!r} is not {BLOCK!r}")
    need = ("q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
            "qk_rope_head_dim", "v_head_dim", "index_n_heads",
            "index_head_dim", "index_topk", "mlp_intermediate")
    missing = [k for k in need if getattr(spec, k) < 1]
    if missing:
        raise ValueError(f"the {BLOCK} block needs {', '.join(missing)} >= 1")
    if spec.qk_rope_head_dim % 2 or spec.index_head_dim < spec.qk_rope_head_dim:
        raise ValueError(
            "qk_rope_head_dim must be even and at most index_head_dim (the "
            "indexer rotates its first qk_rope_head_dim columns)")
    if not 0 <= spec.first_k_dense_replace <= spec.depth:
        raise ValueError(
            f"first_k_dense_replace {spec.first_k_dense_replace} outside "
            f"the {spec.depth} layers")
    if spec.first_k_dense_replace < spec.depth:
        E, held, first = (spec.n_routed_experts, spec.num_experts,
                          spec.expert_offset)
        if min(spec.moe_intermediate, spec.moe_top_k, held) < 1 or \
                spec.moe_top_k > E or not 0 <= first <= first + held <= E:
            raise ValueError(
                f"the routed layers need moe_intermediate, moe_top_k <= "
                f"n_routed_experts ({E}) and a share of them held here: "
                f"num_experts ({held}) from expert_offset ({first})")
    if spec.block_length or spec.tie_embeddings:
        raise ValueError(
            f"the {BLOCK} block generates one token a step from an untied "
            "head: block_length must be 0 and tie_embeddings false")


def is_dense(spec: LMSpec, i: int) -> bool:
    return i < spec.first_k_dense_replace


def dsa_rows(spec: LMSpec, first: int, count: int) -> tuple[int, int]:
    """Rows the indexer scores and rows attention reads for queries at
    positions ``first .. first + count - 1``, over all layers: a query
    at ``t`` scores ``t + 1`` and attends ``min(t + 1, index_topk)``."""
    K = spec.index_topk
    last = first + count
    scored = (first + last + 1) * count // 2
    young = max(0, min(last, K) - first)  # queries with t + 1 <= K
    selected = (2 * first + young + 1) * young // 2 + (count - young) * K
    return spec.depth * scored, spec.depth * selected


def lane_dtype(params):
    """Rows are stored as the weights are (bfloat16 as published)."""
    return params["embed_tokens"].dtype


def leaf_shapes(spec: LMSpec) -> dict[str, tuple[int, ...]]:
    """Flat ``path -> shape`` of the tree above ('/'-joined)."""
    d, H = spec.d_model, spec.num_heads
    Rq, R = spec.q_lora_rank, spec.kv_lora_rank
    Dn, Dr, Dv = spec.qk_nope_head_dim, spec.qk_rope_head_dim, spec.v_head_dim
    Hi, Di = spec.index_n_heads, spec.index_head_dim
    f, fm = spec.mlp_intermediate, spec.moe_intermediate
    mlp = lambda b, w: {f"{b}/gate_proj": (d, w), f"{b}/up_proj": (d, w),
                        f"{b}/down_proj": (w, d)}
    out = {"embed_tokens": (spec.vocab_size, d)}
    for i in range(spec.depth):
        b, a = f"layers/{i}", f"layers/{i}/self_attn"
        out.update({
            f"{b}/input_layernorm": (d,),
            f"{a}/q_a_proj": (d, Rq), f"{a}/q_a_layernorm": (Rq,),
            f"{a}/q_b_proj": (Rq, H * (Dn + Dr)),
            f"{a}/kv_a_proj_with_mqa": (d, R + Dr),
            f"{a}/kv_a_layernorm": (R,),
            f"{a}/kv_b_proj": (R, H * (Dn + Dv)),
            f"{a}/o_proj": (H * Dv, d),
            f"{a}/indexer/wq_b": (Rq, Hi * Di), f"{a}/indexer/wk": (d, Di),
            f"{a}/indexer/k_norm/weight": (Di,),
            f"{a}/indexer/k_norm/bias": (Di,),
            f"{a}/indexer/weights_proj": (d, Hi),
            f"{b}/post_attention_layernorm": (d,),
        })
        if is_dense(spec, i):
            out.update(mlp(f"{b}/mlp", f))
        else:
            E, held = spec.n_routed_experts, spec.num_experts
            out.update({
                f"{b}/mlp/gate": (d, E), f"{b}/mlp/gate_bias": (E,),
                f"{b}/mlp/experts/gate_proj": (held, d, fm),
                f"{b}/mlp/experts/up_proj": (held, d, fm),
                f"{b}/mlp/experts/down_proj": (held, fm, d),
                **mlp(f"{b}/mlp/shared_experts", fm * spec.n_shared_experts),
            })
    out["norm"] = (d,)
    out["lm_head"] = (spec.vocab_size, d)
    return out


def init_leaf(key, path: str, shape, dtype):
    """One seeded leaf: matrices normal(0, 0.02); norm weights 1, the
    indexer's LayerNorm bias 0; the router's choice bias normal(0, 0.01)
    in float32 (so that it changes choices)."""
    name = path.rsplit("/", 1)[-1]
    if name.endswith("layernorm") or name == "norm" or path.endswith(
            "k_norm/weight"):
        return jnp.ones(shape, dtype)
    if path.endswith("k_norm/bias"):
        return jnp.zeros(shape, dtype)
    if name == "gate_bias":
        return BIAS_STD * jax.random.normal(key, shape, jnp.float32)
    return (INIT_STD * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


def init_params(spec: LMSpec, *, seed: int = 0, dtype=jnp.bfloat16):
    """The seeded tree, matrices stored in ``dtype`` (the model is
    published in bfloat16)."""
    key = jax.random.key(seed)
    tree: dict = {}
    for n, (path, shape) in enumerate(leaf_shapes(spec).items()):
        node = tree
        *parents, last = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[last] = init_leaf(jax.random.fold_in(key, n), path, shape, dtype)
    return tree


def derive_spec(params: Any, *, num_heads: int = 0, **overrides) -> LMSpec:
    """The spec of a restored tree: every size the shapes show, the
    rest (the head count and the head's split, the cache's length, the
    indexer's ``index_topk``, the routing and which experts the tree
    holds) from the ``lm_spec.json`` sidecar."""
    try:
        vocab_size, d_model = (int(s) for s in params["embed_tokens"].shape)
        layers = params["layers"]
        attn = layers["0"]["self_attn"]
        dense = [i for i in range(len(layers))
                 if "gate" not in layers[str(i)]["mlp"]]
        fields = dict(
            vocab_size=vocab_size, d_model=d_model, depth=len(layers),
            q_lora_rank=int(attn["q_a_proj"].shape[1]),
            kv_lora_rank=int(attn["kv_a_layernorm"].shape[0]),
            index_head_dim=int(attn["indexer"]["wk"].shape[1]),
            index_n_heads=int(attn["indexer"]["weights_proj"].shape[1]),
            first_k_dense_replace=len(dense), block=BLOCK,
        )
        fields["qk_rope_head_dim"] = (
            int(attn["kv_a_proj_with_mqa"].shape[1]) - fields["kv_lora_rank"])
        if dense:
            fields["mlp_intermediate"] = int(
                layers["0"]["mlp"]["down_proj"].shape[0])
        if len(dense) < len(layers):
            m = layers[str(len(dense))]["mlp"]
            fields.update(
                n_routed_experts=int(m["gate"].shape[1]),
                num_experts=int(m["experts"]["gate_proj"].shape[0]),
                moe_intermediate=int(m["experts"]["gate_proj"].shape[2]),
            )
    except (KeyError, TypeError, AttributeError, IndexError) as e:
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
            "lm_spec.json must give total_len (the cache's length)")
    spec = LMSpec(**fields)
    validate(spec)
    return spec


def save_checkpoint(directory: str, spec: LMSpec, params, *,
                    epoch: int = 0) -> None:
    """Write ``params`` as a checkpoint ``scripts/serve.py`` restores,
    with the ``lm_spec.json`` sidecar that carries what the shapes
    cannot (``sdar.save_checkpoint``'s twin)."""
    from ddp_tpu.train.checkpoint import save_params_with_spec

    validate(spec)
    save_params_with_spec(directory, spec, params, epoch=epoch)


# ---- the layers -------------------------------------------------------


def rotary_interleaved(x, positions, theta: float):
    """Rotary over the whole last axis of ``x`` ``[..., Dr]`` in the
    interleaved form: the pair ``(2i, 2i + 1)`` turns by
    ``positions * theta^(-2i/Dr)``. ``positions`` broadcasts against
    ``x``'s leading axes."""
    Dr = x.shape[-1]
    inv = theta ** (-jnp.arange(Dr // 2, dtype=jnp.float32) * 2.0 / Dr)
    ang = jnp.asarray(positions, jnp.float32)[..., None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x = x.astype(jnp.float32)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin], -1).reshape(x.shape)


def _rotate_head(x, positions, n: int, theta: float):
    """Rotary on the first ``n`` columns of ``x``'s last axis."""
    return jnp.concatenate(
        [rotary_interleaved(x[..., :n], positions, theta),
         x[..., n:].astype(jnp.float32)], -1)


def layer_norm(x, p, eps: float):
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return ((x - mu) * lax.rsqrt(var + eps) * p["weight"].astype(jnp.float32)
            + p["bias"].astype(jnp.float32))


def attn_inputs(spec: LMSpec, p, u, positions):
    """Normed ``u`` ``[N, d]`` at ``positions`` ``[N]`` -> what both
    attention paths take: ``q_nope`` ``[N, H, Dn]``, rotated ``q_rope``
    ``[N, H, Dr]``, the position's cache row ``[N, R + Dr]`` (normed
    latent beside the rotated rope key), the indexer's ``qi``
    ``[N, Hi, Di]``, ``ki`` ``[N, Di]`` and head weights ``w``
    ``[N, Hi]``, all float32."""
    H, R = spec.num_heads, spec.kv_lora_rank
    Dn, Dr = spec.qk_nope_head_dim, spec.qk_rope_head_dim
    Hi, Di = spec.index_n_heads, spec.index_head_dim
    eps, theta, ix = spec.rms_eps, spec.rope_theta, p["indexer"]
    c_q = rms_norm(_mm(u, p["q_a_proj"]), p["q_a_layernorm"], eps)
    q = _mm(c_q, p["q_b_proj"]).reshape(-1, H, Dn + Dr)
    q_rope = rotary_interleaved(q[..., Dn:], positions[:, None], theta)
    kv = _mm(u, p["kv_a_proj_with_mqa"])
    row = jnp.concatenate([
        rms_norm(kv[..., :R], p["kv_a_layernorm"], eps),
        rotary_interleaved(kv[..., R:], positions, theta)], -1)
    qi = _rotate_head(_mm(c_q, ix["wq_b"]).reshape(-1, Hi, Di),
                      positions[:, None], Dr, theta)
    ki = _rotate_head(layer_norm(_mm(u, ix["wk"]), ix["k_norm"],
                                 spec.layer_norm_eps), positions, Dr, theta)
    w = _mm(u, ix["weights_proj"]) * (Hi ** -0.5 * Di ** -0.5)
    return q[..., :Dn], q_rope, row, qi, ki, w


def _stored(row, width: int):
    """``row`` ``[..., R + Dr]`` as a lane stores it: zeros up to the
    stored width (``generate.latent_row_width``); a query padded alike
    meets them with zeros."""
    pad = width - row.shape[-1]
    return jnp.pad(row, [(0, 0)] * (row.ndim - 1) + [(0, pad)]) if pad else row


def _kv_b(spec: LMSpec, p):
    """``kv_b_proj`` as (key half ``[R, H, Dn]``, value half
    ``[R, H, Dv]``)."""
    H, Dn = spec.num_heads, spec.qk_nope_head_dim
    w = p["kv_b_proj"].reshape(spec.kv_lora_rank, H, Dn + spec.v_head_dim)
    return w[..., :Dn], w[..., Dn:]


def attn_scale(spec: LMSpec) -> float:
    return (spec.qk_nope_head_dim + spec.qk_rope_head_dim) ** -0.5


def swiglu(p, u):
    return _mm(jax.nn.silu(_mm(u, p["gate_proj"])) * _mm(u, p["up_proj"]),
               p["down_proj"])


def router_logits(p, u):
    """The router's matmul alone takes float32 operands at the highest
    precision: a choice among 256 sigmoids turns on the last bits."""
    return jnp.dot(u.astype(jnp.float32), p["gate"].astype(jnp.float32),
                   precision=lax.Precision.HIGHEST)


def moe_ffn(spec: LMSpec, p, u, counted=None):
    """The routed layer on normed ``u`` ``[N, d]`` -> (this process's
    part of the experts' sum + the shared expert, pair counts ``[2]``
    int32: routed and held here, over the rows ``counted`` names)."""
    e = p["experts"]
    out, stats = moe_share_layer(
        u, router_logits(p, u), e["gate_proj"], e["up_proj"], e["down_proj"],
        top_k=spec.moe_top_k, first=spec.expert_offset,
        normalize=spec.moe_normalize_gates, scoring="sigmoid",
        bias=p["gate_bias"], scale=spec.routed_scaling_factor,
        count=counted,
    )
    return out + swiglu(p["shared_experts"], u), stats[:2]


def forward_layers(spec: LMSpec, params, x, attend, counted=None):
    """Every layer over the residual stream ``x`` ``[N, d]``.
    ``attend(i, p, u) -> [N, H * Dv]`` owns the rows: the cache write,
    the selection and the mask are the caller's (dense forward, prefill
    chunk, decode step). Returns (x, the expert layers' pair counts)."""
    pairs = jnp.zeros((2,), jnp.int32)
    for i in range(spec.depth):
        p = params["layers"][str(i)]
        u = rms_norm(x, p["input_layernorm"], spec.rms_eps)
        x = x + _mm(attend(i, p["self_attn"], u), p["self_attn"]["o_proj"])
        u = rms_norm(x, p["post_attention_layernorm"], spec.rms_eps)
        if is_dense(spec, i):
            x = x + swiglu(p["mlp"], u)
        else:
            m, s = moe_ffn(spec, p["mlp"], u, counted)
            x, pairs = x + m, pairs + s
    return x, pairs


def head_logits(spec: LMSpec, params, x):
    return _mm(rms_norm(x, params["norm"], spec.rms_eps),
               params["lm_head"], transposed=True)


def _embed(params, tokens):
    return params["embed_tokens"][tokens].astype(jnp.float32)


# ---- a chunk of queries against stored rows ---------------------------


def _order_keys(scores, valid):
    """Float32 scores -> uint32 keys in the same order, 0 where not
    ``valid`` (below every real score's key)."""
    bits = lax.bitcast_convert_type(scores, jnp.uint32)
    keys = jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(1 << 31))
    return jnp.where(valid, keys, jnp.uint32(0))


def _kth_largest(keys, k: int):
    """The ``k``-th largest of each row of ``keys`` ``[C, L]`` uint32
    (0 where a row holds fewer): a radix select, one bit a pass."""
    def body(b, prefix):
        cand = prefix | (jnp.uint32(1) << (31 - b).astype(jnp.uint32))
        enough = jnp.sum(keys >= cand[:, None], axis=1) >= k
        return jnp.where(enough, cand, prefix)

    return lax.fori_loop(0, 32, body,
                         jnp.zeros((keys.shape[0],), jnp.uint32))


def chunk_form(spec: LMSpec, queries: int, n_keys: int, row_width: int,
               impl: str = "auto") -> tuple[str, int]:
    """How a chunk's third pass runs, from what the code can observe ->
    (``kernel`` | ``walk``, the kernel's query tile or 0): the
    ``latent_prefill`` kernel (ops/latent_prefill.py) on a TPU where
    queries, keys and widths are whole tiles, the ``jnp`` walk anywhere
    else. ``impl`` ``pallas`` / ``jnp`` holds a test to one form."""
    block_q = min(QUERY_TILE, queries)
    if impl == "auto":
        fits = tiles(
            queries, n_keys, block_q, min(KEY_BLOCK, n_keys),
            (spec.qk_nope_head_dim + spec.qk_rope_head_dim, spec.v_head_dim,
             spec.kv_lora_rank, row_width))
        impl = "pallas" if fits and jax.default_backend() == "tpu" else "jnp"
    if impl not in ("pallas", "jnp"):
        raise ValueError(f"unknown chunk attention impl {impl!r}: expected "
                         "'auto', 'pallas' or 'jnp'")
    return ("kernel", block_q) if impl == "pallas" else ("walk", 0)


@jax.named_scope("mla_prefill")
def chunk_attention(spec: LMSpec, p, q_nope, q_rope, qi, w, latent, index_k,
                    lane, n_blocks, q_pos, *, want_mask=False,
                    impl: str = "auto"):
    """``C`` queries at positions ``q_pos`` against the stored rows of
    lane ``lane`` of ``latent`` ``[S, n_keys, W]`` and ``index_k`` ``[S,
    n_keys, Di]``, ``KEY_BLOCK`` at a time, the first ``n_blocks``
    (traced or not) blocks; later rows are never read. Three passes: the
    index scores of every (query, key); each query's ``index_topk``-th
    largest score (a radix select: no sort); then expanded attention
    under the mask ``score above the threshold, or equal to it among the
    first ties``, by the online softmax, in the form :func:`chunk_form`
    finds: ONE kernel a layer that is handed the mask as int8 ``[blocks,
    C, KEY_BLOCK]`` and keeps a block's scores in VMEM, or the ``jnp``
    walk, whose scores of all heads for a block pass through HBM. Both
    walk every live row. -> ``[C, H * Dv]`` float32 (and the mask ``[C,
    n_keys]`` if asked)."""
    C, H = q_nope.shape[0], spec.num_heads
    R, Dv = spec.kv_lora_rank, spec.v_head_dim
    n_keys = latent.shape[1]
    B = min(KEY_BLOCK, n_keys)
    K = spec.index_topk
    w_k, w_v = _kv_b(spec, p)
    cdt = w_k.dtype
    k_pos = jnp.arange(B, dtype=jnp.int32)

    def block_of(buf, j):
        return lax.dynamic_slice(
            buf, (lane, j * B, 0), (1, B, buf.shape[2]))[0]

    def valid_of(j):
        return (j * B + k_pos)[None, :] <= q_pos[:, None]

    with jax.named_scope("dsa_index"):
        def score_block(j, keys):
            s = index_scores(qi, w, block_of(index_k, j)[None])  # [C, B]
            return lax.dynamic_update_slice(
                keys, _order_keys(s, valid_of(j)), (0, j * B))

        keys = lax.fori_loop(0, n_blocks, score_block,
                             jnp.zeros((C, n_keys), jnp.uint32))
    with jax.named_scope("dsa_select"):
        kth = _kth_largest(keys, K)
        above = jnp.sum(keys > kth[:, None], axis=1).astype(jnp.int32)
        ties_taken = K - above  # ties at the threshold, lowest first

    def select_block(j, ties):
        """Block ``j``'s mask ``[C, B]`` and the ties counted so far."""
        kb = lax.dynamic_slice(keys, (0, j * B), (C, B))
        tie = kb == kth[:, None]
        rank = ties[:, None] + jnp.cumsum(tie, axis=1) - tie
        sel = valid_of(j) & ((kb > kth[:, None])
                             | (tie & (rank < ties_taken[:, None])))
        return sel, ties + tie.sum(1).astype(jnp.int32)

    qn, qr = q_nope.astype(cdt), q_rope.astype(cdt)
    scale = attn_scale(spec)
    no_ties = jnp.zeros((C,), jnp.int32)
    form, block_q = chunk_form(spec, C, n_keys, latent.shape[2], impl)
    if form == "kernel":
        def mask_block(j, carry):
            sel, ties = select_block(j, carry[0])
            return ties, lax.dynamic_update_slice(
                carry[1], sel.astype(jnp.int8)[None], (j, 0, 0))

        _, mask = lax.fori_loop(
            0, n_blocks, mask_block,
            (no_ties, jnp.zeros((n_keys // B, C, B), jnp.int8)))
        out = masked_walk(
            jnp.concatenate([qn, qr], -1), latent, w_k, w_v, mask, n_blocks,
            q_pos, lane=lane, rope=spec.qk_rope_head_dim, scale=scale,
            block_q=block_q)
        if want_mask:
            return out, mask.transpose(1, 0, 2).reshape(C, n_keys) != 0
        return out

    def attend_block(j, carry):
        m, l, acc, ties, mask = carry
        rows = block_of(latent, j)
        c, kr = rows[:, :R], rows[:, R:R + spec.qk_rope_head_dim]
        k_nope = jnp.einsum("br,rhn->bhn", c, w_k,
                            preferred_element_type=jnp.float32).astype(cdt)
        v = jnp.einsum("br,rhv->bhv", c, w_v,
                       preferred_element_type=jnp.float32).astype(cdt)
        s = (jnp.einsum("chn,bhn->hcb", qn, k_nope,
                        preferred_element_type=jnp.float32)
             + jnp.einsum("chr,br->hcb", qr, kr.astype(cdt),
                          preferred_element_type=jnp.float32)) * scale
        sel, ties = select_block(j, ties)
        s = jnp.where(sel[None], s, _NEG)
        m_new = jnp.maximum(m, s.max(-1))
        pr = jnp.where(sel[None], jnp.exp(s - m_new[..., None]), 0.0)
        alpha = jnp.exp(m - m_new)
        acc = acc * alpha[..., None] + jnp.einsum(
            "hcb,bhv->hcv", pr.astype(cdt), v,
            preferred_element_type=jnp.float32)
        if want_mask:
            mask = lax.dynamic_update_slice(mask, sel, (0, j * B))
        return m_new, l * alpha + pr.sum(-1), acc, ties, mask

    init = (jnp.full((H, C), _NEG, jnp.float32), jnp.zeros((H, C)),
            jnp.zeros((H, C, Dv)), no_ties,
            jnp.zeros((C, n_keys if want_mask else 0), bool))
    _, l, acc, _, mask = lax.fori_loop(0, n_blocks, attend_block, init)
    out = (acc / l[..., None]).transpose(1, 0, 2).reshape(C, H * Dv)
    return (out, mask) if want_mask else out


def _own_rows(row, ki, dtype, n_keys):
    """A run's own rows as one lane of stored rows ``[1, n_keys, .]``,
    rounded as a cache stores them."""
    pad = n_keys - row.shape[0]
    return tuple(jnp.pad(a.astype(dtype), ((0, pad), (0, 0)))[None]
                 for a in (row, ki))


def dense_logits(spec: LMSpec, params, tokens, *, want_masks: bool = False):
    """Full forward of ``tokens`` ``[N, T]``, no cache -> logits
    ``[N, T, V]`` (and, asked, each layer's selection mask ``[N, layers,
    T, T]``). The parity probe: the chunk path's attention over a
    sequence's own rows."""
    T = tokens.shape[1]
    dtype = lane_dtype(params)
    pos = jnp.arange(T, dtype=jnp.int32)
    B = min(KEY_BLOCK, T)
    n_keys = -(-T // B) * B

    def one(toks):
        masks = []

        def attend(i, p, u):
            q_nope, q_rope, row, qi, ki, w = attn_inputs(spec, p, u, pos)
            out = chunk_attention(
                spec, p, q_nope, q_rope, qi, w,
                *_own_rows(row, ki, dtype, n_keys), 0, n_keys // B, pos,
                want_mask=want_masks)
            if want_masks:
                out, mask = out
                masks.append(mask[:, :T])
            return out

        x, _ = forward_layers(spec, params, _embed(params, toks), attend)
        logits = head_logits(spec, params, x)
        return (logits, jnp.stack(masks)) if want_masks else logits

    outs = [one(t) for t in tokens]
    if want_masks:
        return (jnp.stack([o[0] for o in outs]),
                jnp.stack([o[1] for o in outs]))
    return jnp.stack(outs)


# ---- lanes -----------------------------------------------------------


def _record_plan(spec: LMSpec, program: str, queries: int, n_keys: int,
                 form: str = "gather", block_q: int = 0):
    """Trace-time record of how a program selects and attends; last the
    form its attention took (a chunk's :func:`chunk_form`; a decode step
    gathers the selected rows) and the kernel's query tile."""
    get_tracer().complete(
        "dsa.plan", time.perf_counter(), 0.0,
        nums=(program, queries, n_keys, min(spec.index_topk, n_keys),
              spec.depth,
              0 if program == "decode" else min(KEY_BLOCK, n_keys),
              spec.kv_lora_rank + spec.qk_rope_head_dim,
              spec.index_head_dim, form, block_q),
    )


def prefill_chunk(
    spec: LMSpec, params: Any, cache: SlotCache, toks, seeds, steps,
    temps, top_ps, slot, chunk, start, length, final, seed, temperature,
    top_p, *, lane_attend: bool = True,
):
    """Ingest one chunk of a prompt into lane ``slot`` — models/
    generate.prefill_chunk's contract and signature: ``chunk`` ``[C]``
    holds positions ``[start, start + length)`` and padding after. The
    latent and indexer rows of all C positions are written first (pad
    rows land above ``pos`` and are overwritten before a query may
    select them); then every query scores, selects and attends
    (:func:`chunk_attention`). ``lane_attend=False`` is the
    self-contained FIRST chunk (``start`` 0): it reads nothing of the
    lane, whatever an earlier request left there. A continuing chunk
    reads the lane's blocks up to its own last row and none above. The
    ``final`` chunk samples the request's first token."""
    C = chunk.shape[0]
    L = spec.total_len
    lat, idx = list(cache.latent), list(cache.index_k)
    q_pos = start + jnp.arange(C, dtype=jnp.int32)
    real = jnp.arange(C) < length
    n_keys = L if lane_attend else C
    B = min(KEY_BLOCK, n_keys)
    if n_keys % B:
        raise ValueError(
            f"a chunk reads stored rows {B} at a time: {n_keys} rows are "
            "no whole number of such blocks")
    _record_plan(spec, "prefill_chunk" if lane_attend else "prefill_first",
                 C, n_keys, *chunk_form(spec, C, n_keys, lat[0].shape[-1]))

    def attend(i, p, u):
        q_nope, q_rope, row, qi, ki, w = attn_inputs(spec, p, u, q_pos)
        dt = lat[i].dtype
        row = _stored(row, lat[i].shape[-1])
        lat[i] = lax.dynamic_update_slice(
            lat[i], row.astype(dt)[None], (slot, start, 0))
        idx[i] = lax.dynamic_update_slice(
            idx[i], ki.astype(dt)[None], (slot, start, 0))
        if lane_attend:
            stored = lat[i], idx[i], slot, (start + C + B - 1) // B
        else:
            stored = *_own_rows(row, ki, dt, n_keys), 0, n_keys // B
        return chunk_attention(spec, p, q_nope, q_rope, qi, w, *stored,
                               q_pos)

    x, pairs = forward_layers(spec, params, _embed(params, chunk), attend,
                              real)

    def last_logits():
        xt = lax.dynamic_slice_in_dim(x, length - 1, 1, axis=0)
        return head_logits(spec, params, xt)[0]

    toks, seeds, steps, temps, top_ps, first = install_lane_sampling(
        toks, seeds, steps, temps, top_ps, slot, final, seed, temperature,
        top_p, last_logits,
    )
    pos = lax.dynamic_update_slice(
        cache.pos, (start + length)[None].astype(cache.pos.dtype), (slot,))
    cache = cache._replace(latent=tuple(lat), index_k=tuple(idx), pos=pos)
    return cache, toks, seeds, steps, temps, top_ps, first, pairs


def slot_decode_step(spec: LMSpec, params, cache: SlotCache, tokens, *,
                     attn_impl: str = "reference"):
    """Advance every lane one token: ``tokens`` ``[S]``, lane s's token
    at ``cache.pos[s]`` -> (logits ``[S, V]``, cache, the call's pair
    counts). Each layer writes the position's latent and indexer rows,
    scores the lane's rows, selects (``ops/decode.select_rows``) and
    attends the selected rows with the up-projections absorbed
    (``latent_decode_attention``). An
    idle lane rides along (the shape never changes): its logits are
    garbage, the rows it writes land at ``pos``, above everything a
    later query may select before it is overwritten. ``attn_impl`` is
    the engine's knob for the kernels of other blocks; there is one
    path here."""
    del attn_impl
    S = tokens.shape[0]
    L, R = spec.total_len, spec.kv_lora_rank
    pos = jnp.minimum(cache.pos, L - 1)
    lanes = jnp.arange(S, dtype=jnp.int32)
    lat, idx, sel = list(cache.latent), list(cache.index_k), cache.sel
    _record_plan(spec, "decode", S, L)

    def attend(i, p, u):
        nonlocal sel
        q_nope, q_rope, row, qi, ki, w = attn_inputs(spec, p, u, pos)
        dt = lat[i].dtype
        put = lambda buf, r: buf.at[lanes, pos].set(
            r.astype(dt), indices_are_sorted=True, unique_indices=True)
        lat[i] = put(lat[i], _stored(row, lat[i].shape[-1]))
        idx[i] = put(idx[i], ki)
        rows, counted = select_rows(index_scores(qi, w, idx[i]), pos,
                                    spec.index_topk)
        sel = sel.at[i].set(jnp.where(counted, rows, -1))
        w_k, w_v = _kv_b(spec, p)
        q_lat = jnp.einsum("shn,rhn->shr", q_nope.astype(w_k.dtype), w_k,
                           preferred_element_type=jnp.float32)
        o = latent_decode_attention(
            _stored(jnp.concatenate([q_lat, q_rope], -1), lat[i].shape[-1]),
            lat[i], rows, counted, rank=R, scale=attn_scale(spec))
        return jnp.einsum("shr,rhv->shv", o.astype(w_v.dtype), w_v,
                          preferred_element_type=jnp.float32).reshape(S, -1)

    x, pairs = forward_layers(spec, params, _embed(params, tokens), attend)
    cache = cache._replace(
        latent=tuple(lat), index_k=tuple(idx), sel=sel,
        pos=jnp.minimum(cache.pos + 1, L))
    return head_logits(spec, params, x), cache, pairs


def slot_decode_sample_step(spec: LMSpec, params, cache: SlotCache, tokens,
                            seeds, steps, temps, top_ps, *,
                            attn_impl: str = "reference"):
    """:func:`slot_decode_step` with the GPT-2 path's fused sampling ->
    (tokens ``[S]`` int32, cache, advanced step counters, the call's
    pair counts)."""
    logits, cache, pairs = slot_decode_step(spec, params, cache, tokens,
                                            attn_impl=attn_impl)
    toks = sample_slot_tokens(logits, seeds, steps, temps, top_ps)
    return toks, cache, steps + 1, pairs
