"""The ``granite_hybrid`` block (HF ``model_type`` ``granitemoehybrid``
with no routed experts): Mamba-2 layers and position-free grouped-query
attention layers in one stack, for serving one token a step.

``LMSpec.block == "granite_hybrid"`` names the layer stack and
``spec.layer_types`` says which layer is which. Everything here is a
function of the parameter tree and the spec; the serve engine
(serve/engine.py) jits :func:`prefill_chunk` and
:func:`slot_decode_sample_step` under the very signatures of the GPT-2
path's (models/generate.py), the tests also :func:`dense_logits`.

**The model** (RMSNorm(x; w) = x * rsqrt(mean(x^2) + eps) * w in fp32;
no bias but the convolution's; ``r`` = ``residual_multiplier``):
``x0 = embed_tokens[tokens] * embedding_multiplier``; every layer
``x = x + r * mixer(RMSNorm(x; input_layernorm))`` then
``x = x + r * mlp(RMSNorm(x; post_attention_layernorm))``;
``logits = RMSNorm(x; norm) @ embed_tokens^T / logits_scaling``. No
position table and no rotary: order reaches the model through the
recurrence and the causal mask alone.

- MLP: ``[a, b] = split(u @ input_linear)``,
  ``(silu(a) * b) @ output_linear``.
- Attention mixer: q as H heads of Dh, k and v as H_kv heads, causal
  softmax of ``q k^T * attention_multiplier`` (NOT ``Dh ** -0.5``) in
  fp32, ``o_proj``.
- Mamba-2 mixer (ops/ssm.py has the recurrence): ``[z, xBC, dt] =
  split(u @ in_proj)`` to ``H*P``, ``H*P + 2N`` and ``H``;
  ``xBC = silu(causal_conv(xBC))``, split to ``xs`` ``[H, P]``, ``B``,
  ``C`` ``[N]``; ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``;
  ``S_t = exp(dt_t A) S_{t-1} + dt_t xs_t B_t^T``,
  ``y_t = S_t C_t + D xs_t``; ``RMSNorm(y * silu(z); norm)`` over all
  ``H*P`` channels, gate BEFORE the norm; ``out_proj``.

Residual stream, norms, softmax, the convolution, ``dt`` and decay
arithmetic and the state are fp32; every matmul takes its operands in
the WEIGHT's dtype (bfloat16 as stored) and accumulates in fp32
(``sdar._mm``); the chunked scan's einsums follow the same rule.

**The tree**, name for name the HF checkpoint's (a linear layer's
``weight`` ``[out, in]`` is stored transposed under the module's name):

====================================================  =================================================
``embed_tokens`` ``[V, d]``                           ``model.embed_tokens.weight`` (also the head)
``layers/{i}/input_layernorm`` ``[d]``                ``model.layers.{i}.input_layernorm.weight``
``layers/{i}/mamba/in_proj`` ``[d, 2HP + 2N + H]``    ``...mamba.in_proj.weight`` ^T
``layers/{i}/mamba/conv1d/weight`` ``[K, HP + 2N]``   ``...mamba.conv1d.weight`` ``[C, 1, K]`` ^T
``layers/{i}/mamba/conv1d/bias`` ``[HP + 2N]``        ``...mamba.conv1d.bias``
``layers/{i}/mamba/dt_bias|A_log|D`` ``[H]``          ``...mamba.dt_bias|A_log|D``
``layers/{i}/mamba/norm`` ``[HP]``                    ``...mamba.norm.weight``
``layers/{i}/mamba/out_proj`` ``[HP, d]``             ``...mamba.out_proj.weight`` ^T
``layers/{i}/self_attn/q_proj|o_proj``                ``...self_attn.q_proj|o_proj.weight`` ^T
``layers/{i}/self_attn/k_proj|v_proj``                ``...self_attn.k_proj|v_proj.weight`` ^T
``layers/{i}/post_attention_layernorm`` ``[d]``       ``...post_attention_layernorm.weight``
``layers/{i}/shared_mlp/input_linear`` ``[d, 2f]``    ``...shared_mlp.input_linear.weight`` ^T
``layers/{i}/shared_mlp/output_linear`` ``[f, d]``    ``...shared_mlp.output_linear.weight`` ^T
``norm`` ``[d]``                                      ``model.norm.weight``
====================================================  =================================================

**A lane** (``generate.SlotCache``) holds K/V rows for the attention
layers (a position's kv heads side by side, ``[L, H_kv * Dh]``) and,
for each Mamba layer, the state and the convolution's last ``K - 1``
inputs; :func:`layer_rows` maps a layer's number to its row in either. A lane's recurrent state has three rules a K/V lane never
needed: the FIRST chunk of a prompt starts from zero state whatever the
lane held (the reset at admission, inside the chunk's own program);
a chunk's padded positions do not move it (``dt`` forced to 0, the
tail taken from the last REAL positions); and a decode step moves only
the lanes ``cache.live`` names, so a lane between two chunks of its
prompt, or idle, is left bit for bit.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from ddp_tpu.models.generate import (
    SlotCache,
    _kv_heads,
    _write_kv_rows,
    install_lane_sampling,
    sample_slot_tokens,
)
from ddp_tpu.models.lm import LMSpec, head_dim_of
from ddp_tpu.models.sdar import _mm, rms_norm
from ddp_tpu.ops import ssm
from ddp_tpu.ops.attention import dot_product_attention
from ddp_tpu.ops.decode import packed_decode_attention, read_lane

BLOCK = "granite_hybrid"
MAMBA, ATTENTION = "mamba", "attention"
INIT_STD = 0.02
# what the serve engine asks of a block's module (serve/engine.py)
RECURRENT = True


def validate(spec: LMSpec) -> None:
    """Raise ValueError unless ``spec`` names this module's model."""
    if spec.block != BLOCK:
        raise ValueError(f"block {spec.block!r} is not {BLOCK!r}")
    kinds = tuple(spec.layer_types)
    if len(kinds) != spec.depth or set(kinds) - {MAMBA, ATTENTION}:
        raise ValueError(
            f"the {BLOCK} block needs layer_types: one of {MAMBA!r} | "
            f"{ATTENTION!r} for each of its {spec.depth} layers, got {kinds}"
        )
    if ATTENTION not in kinds or MAMBA not in kinds:
        raise ValueError(
            f"the {BLOCK} block holds both kinds of layer; layer_types "
            f"{kinds} has one"
        )
    if min(spec.mamba_n_heads, spec.mamba_d_head, spec.mamba_d_state,
           spec.mlp_intermediate) < 1 or spec.mamba_d_conv < 2:
        raise ValueError(
            "the block needs mamba_n_heads, mamba_d_head, mamba_d_state, "
            "mlp_intermediate >= 1 and mamba_d_conv >= 2"
        )
    if spec.mamba_n_groups != 1:
        raise ValueError(
            f"mamba_n_groups {spec.mamba_n_groups}: B and C shared by all "
            "heads (one group) is what ops/ssm.py computes"
        )
    if spec.position_embedding != "nope" or not spec.tie_embeddings:
        raise ValueError(
            f"the {BLOCK} block has no positions and a tied head: "
            "position_embedding must be 'nope' and tie_embeddings true, "
            f"got {spec.position_embedding!r} and {spec.tie_embeddings}"
        )
    if spec.num_heads % _kv_heads(spec):
        raise ValueError("H_kv must divide H")
    if spec.block_length:
        raise ValueError(
            f"the {BLOCK} block generates one token a step: "
            f"block_length must be 0, got {spec.block_length}"
        )


def _inner(spec: LMSpec) -> int:
    return spec.mamba_n_heads * spec.mamba_d_head


def _conv_dim(spec: LMSpec) -> int:
    return _inner(spec) + 2 * spec.mamba_n_groups * spec.mamba_d_state


def attn_scale(spec: LMSpec) -> float:
    return spec.attention_multiplier or head_dim_of(spec) ** -0.5


def layer_rows(spec: LMSpec) -> tuple[tuple[str, int], ...]:
    """Layer number -> (its kind, its row in that kind's state: ``k``/
    ``v`` for an attention layer, ``ssm``/``conv`` for a Mamba one)."""
    seen = {MAMBA: 0, ATTENTION: 0}
    out = []
    for kind in spec.layer_types:
        out.append((kind, seen[kind]))
        seen[kind] += 1
    return tuple(out)


def leaf_shapes(spec: LMSpec) -> dict[str, tuple[int, ...]]:
    """Flat ``path -> shape`` of the tree above ('/'-joined)."""
    d, Dh, f = spec.d_model, head_dim_of(spec), spec.mlp_intermediate
    H, Hkv = spec.num_heads, _kv_heads(spec)
    hp, cd, Hm = _inner(spec), _conv_dim(spec), spec.mamba_n_heads
    out = {"embed_tokens": (spec.vocab_size, d)}
    for i, kind in enumerate(spec.layer_types):
        b = f"layers/{i}"
        out[f"{b}/input_layernorm"] = (d,)
        if kind == MAMBA:
            out.update({
                f"{b}/mamba/in_proj": (d, hp + cd + Hm),
                f"{b}/mamba/conv1d/weight": (spec.mamba_d_conv, cd),
                f"{b}/mamba/conv1d/bias": (cd,),
                f"{b}/mamba/dt_bias": (Hm,),
                f"{b}/mamba/A_log": (Hm,),
                f"{b}/mamba/D": (Hm,),
                f"{b}/mamba/norm": (hp,),
                f"{b}/mamba/out_proj": (hp, d),
            })
        else:
            out.update({
                f"{b}/self_attn/q_proj": (d, H * Dh),
                f"{b}/self_attn/k_proj": (d, Hkv * Dh),
                f"{b}/self_attn/v_proj": (d, Hkv * Dh),
                f"{b}/self_attn/o_proj": (H * Dh, d),
            })
        out.update({
            f"{b}/post_attention_layernorm": (d,),
            f"{b}/shared_mlp/input_linear": (d, 2 * f),
            f"{b}/shared_mlp/output_linear": (f, d),
        })
    out["norm"] = (d,)
    return out


def init_leaf(key, path: str, shape, dtype):
    """One seeded leaf, by Mamba-2's own initialisation: matrices
    normal(0, 0.02); ``A_log = log(uniform[1, 16])``; ``dt_bias`` the
    inverse softplus of a log-uniform [0.001, 0.1] time step; ``D`` and
    norm weights 1; the convolution uniform +-1/2 with a zero bias. So
    a step's decay ``exp(dt A)`` runs from ~0.999 down to ~0.2 and the
    state matters to the logits. The vectors stay float32."""
    name = path.rsplit("/", 1)[-1]
    if name in ("D", "norm") or name.endswith("layernorm"):
        return jnp.ones(shape, jnp.float32 if name == "D" else dtype)
    if name == "A_log":
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))
    if name == "dt_bias":
        dt = jnp.exp(jax.random.uniform(
            key, shape, jnp.float32, jnp.log(1e-3), jnp.log(1e-1)))
        return dt + jnp.log(-jnp.expm1(-dt))
    if path.endswith("conv1d/weight"):
        return jax.random.uniform(key, shape, jnp.float32, -0.5, 0.5)
    if path.endswith("conv1d/bias"):
        return jnp.zeros(shape, jnp.float32)
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
    """The spec of a restored tree: every size the shapes show (the
    layer table among them), the rest (the two head counts, the cache's
    length, the multipliers) from the ``lm_spec.json`` sidecar."""
    try:
        vocab_size, d_model = (int(s) for s in params["embed_tokens"].shape)
        layers = params["layers"]
        kinds = tuple(
            MAMBA if "mamba" in layers[str(i)] else ATTENTION
            for i in range(len(layers))
        )
        m = layers[str(kinds.index(MAMBA))]["mamba"]
        mlp = layers["0"]["shared_mlp"]
        Hm = int(m["A_log"].shape[0])
        inner = int(m["norm"].shape[0])
        fields = dict(
            vocab_size=vocab_size, d_model=d_model, depth=len(kinds),
            layer_types=kinds, mamba_n_heads=Hm, mamba_d_head=inner // Hm,
            mamba_d_conv=int(m["conv1d"]["weight"].shape[0]),
            mlp_intermediate=int(mlp["output_linear"].shape[0]),
            block=BLOCK,
        )
        groups = int(overrides.get("mamba_n_groups", 1))
        fields["mamba_d_state"] = (
            int(m["conv1d"]["bias"].shape[0]) - inner) // (2 * groups)
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
    cannot (``sdar.save_checkpoint``'s twin: how a converted HF
    checkpoint gets on disk)."""
    from ddp_tpu.train.checkpoint import save_params_with_spec

    validate(spec)
    save_params_with_spec(directory, spec, params, epoch=epoch)


# ---- the layers -------------------------------------------------------


def _embed(spec: LMSpec, params, tokens):
    return (params["embed_tokens"][tokens].astype(jnp.float32)
            * spec.embedding_multiplier)


def head_logits(spec: LMSpec, params, x):
    return _mm(rms_norm(x, params["norm"], spec.rms_eps),
               params["embed_tokens"], transposed=True) / spec.logits_scaling


def mlp(p, u):
    a, b = jnp.split(_mm(u, p["input_linear"]), 2, axis=-1)
    return _mm(jax.nn.silu(a) * b, p["output_linear"])


def attn_qkv(spec: LMSpec, p, u):
    """Normed ``u`` ``[..., d]`` -> q ``[..., H, Dh]``, k and v
    ``[..., H_kv, Dh]``: no bias, no head norm, no rotary."""
    Dh = head_dim_of(spec)
    heads = lambda name, n: _mm(u, p[name]).reshape(*u.shape[:-1], n, Dh)
    return (heads("q_proj", spec.num_heads), heads("k_proj", _kv_heads(spec)),
            heads("v_proj", _kv_heads(spec)))


def _causal_attention(spec: LMSpec, q, k, v, *, q_offset=None):
    """``dot_product_attention`` at this model's softmax scale (the
    queries carry the ratio to the ``Dh ** -0.5`` it applies) with each
    kv head serving its group. ``q`` ``[1, T, H, Dh]``, ``k``/``v``
    ``[1, S, H_kv, Dh]`` -> ``[1, T, H * Dh]``."""
    G = spec.num_heads // _kv_heads(spec)
    wide = lambda a: jnp.repeat(a, G, axis=2).astype(jnp.float32)
    a = dot_product_attention(
        q * (attn_scale(spec) * head_dim_of(spec) ** 0.5), wide(k), wide(v),
        causal=True, q_offset=q_offset,
    )
    return a.reshape(*a.shape[:2], -1)


def mamba_inputs(spec: LMSpec, p, u):
    """Normed ``u`` ``[..., d]`` -> (z ``[..., HP]``, xBC before the
    convolution ``[..., HP + 2N]``, dt after softplus ``[..., H]``)."""
    hp, cd = _inner(spec), _conv_dim(spec)
    z, xbc, dt = jnp.split(_mm(u, p["in_proj"]), [hp, hp + cd], axis=-1)
    return z, xbc, jax.nn.softplus(dt + p["dt_bias"].astype(jnp.float32))


def split_xbc(spec: LMSpec, xbc):
    """xBC after the convolution and its SiLU -> xs ``[..., H, P]``,
    B, C ``[..., N]``."""
    hp, N = _inner(spec), spec.mamba_d_state
    xs, B, C = jnp.split(jax.nn.silu(xbc), [hp, hp + N], axis=-1)
    return (xs.reshape(*xs.shape[:-1], spec.mamba_n_heads, spec.mamba_d_head),
            B, C)


def mamba_out(spec: LMSpec, p, y, z):
    """``y`` ``[..., H, P]`` and the gate ``z`` -> the mixer's output:
    gate, then the norm over all channels, then ``out_proj``."""
    y = y.reshape(*y.shape[:-2], -1) * jax.nn.silu(z)
    return _mm(rms_norm(y, p["norm"], spec.rms_eps), p["out_proj"])


def mamba_run(spec: LMSpec, p, u, tail, state, length):
    """The Mamba mixer over a run of one lane's tokens: ``u``
    ``[T, d]`` normed, from the convolution's ``tail`` ``[K-1, C]`` and
    ``state`` ``[N, HP]`` -> (output ``[T, d]``, the tail and the state
    after position ``length - 1``). Positions from ``length`` on are
    padding: their ``dt`` is 0, so they neither move the state nor
    reach it, and the tail is cut before them."""
    w = p["conv1d"]
    z, xbc, dt = mamba_inputs(spec, p, u)
    real = jnp.arange(u.shape[0]) < length
    dt = jnp.where(real[:, None], dt, 0.0)
    xs, B, C = split_xbc(spec, ssm.causal_conv(xbc, tail, w["weight"],
                                               w["bias"]))
    y, state = ssm.ssd_scan(
        xs, dt, -jnp.exp(p["A_log"].astype(jnp.float32)), B, C, state,
        chunk=spec.mamba_chunk_size, dtype=p["in_proj"].dtype,
    )
    y = y + xs * p["D"].astype(jnp.float32)[None, :, None]
    return mamba_out(spec, p, y, z), ssm.conv_tail(xbc, tail, length), state


def forward_layers(spec: LMSpec, params, x, attend, recur):
    """Every layer over the residual stream ``x``. ``attend(row, q, k,
    v)`` and ``recur(row, p, u)`` own the state — the cache write, the
    mask, the carried state are the caller's (dense forward, prefill
    chunk, decode step) — and return the mixer's input to ``o_proj``
    and the Mamba mixer's output."""
    r = spec.residual_multiplier
    for i, (kind, row) in enumerate(layer_rows(spec)):
        p = params["layers"][str(i)]
        u = rms_norm(x, p["input_layernorm"], spec.rms_eps)
        if kind == MAMBA:
            mixed = recur(row, p["mamba"], u)
        else:
            q, k, v = attn_qkv(spec, p["self_attn"], u)
            mixed = _mm(attend(row, q, k, v), p["self_attn"]["o_proj"])
        x = x + r * mixed
        u = rms_norm(x, p["post_attention_layernorm"], spec.rms_eps)
        x = x + r * mlp(p["shared_mlp"], u)
    return x


def dense_logits(spec: LMSpec, params, tokens):
    """Full forward of ``tokens`` ``[N, T]``, no cache -> logits
    ``[N, T, V]``. The parity probe."""
    T = tokens.shape[1]
    zero_tail = jnp.zeros((spec.mamba_d_conv - 1, _conv_dim(spec)))
    zero_state = jnp.zeros((spec.mamba_d_state, _inner(spec)))

    def one(toks):
        def attend(row, q, k, v):
            return _causal_attention(spec, q, k, v)

        def recur(row, p, u):
            return mamba_run(spec, p, u[0], zero_tail, zero_state, T)[0][None]

        x = forward_layers(spec, params, _embed(spec, params, toks)[None],
                           attend, recur)
        return head_logits(spec, params, x)[0]

    return jnp.stack([one(t) for t in tokens])


# ---- lanes -----------------------------------------------------------


def _lane(buf, row: int, slot):
    """Lane ``slot`` (traced) of layer row ``row`` of a stored buffer
    ``[rows, S, ...]``, cut out in ONE dynamic slice: slicing the row
    out first makes XLA copy all its lanes (134 MB of state a layer at
    the published size) to read one."""
    return lax.dynamic_slice(
        buf, (row, slot) + (0,) * (buf.ndim - 2), (1, 1) + buf.shape[2:]
    )[0, 0]


def prefill_chunk(
    spec: LMSpec, params: Any, cache: SlotCache, toks, seeds, steps,
    temps, top_ps, slot, chunk, start, length, final, seed, temperature,
    top_p, *, lane_attend: bool = True,
):
    """Ingest one chunk of a prompt into lane ``slot`` — models/
    generate.prefill_chunk's contract and signature: ``chunk`` ``[C]``
    holds positions ``[start, start + length)`` and padding after;
    ``lane_attend=False`` is the self-contained FIRST chunk (``start``
    0), which attends itself and starts every Mamba layer from ZERO
    state and a zero tail, whatever the lane held: that is the reset at
    admission. A continuing chunk attends the lane and carries the
    lane's state and tail on. K/V rows of all C positions are written
    (pad rows above ``pos`` are overwritten before they are attendable);
    state and tail are those after position ``length - 1``. The
    ``final`` chunk samples the request's first token. ``cache.live``
    is the caller's: the engine names the decoding lanes before each
    decode step, and a lane between two chunks is not among them."""
    ck, cv, cs, cc = cache.k, cache.v, cache.ssm, cache.conv

    def attend(row, q, k, v):
        nonlocal ck, cv
        packed = lambda a, c: a.astype(c.dtype).reshape(1, 1, a.shape[1], -1)
        ck = lax.dynamic_update_slice(ck, packed(k, ck), (row, slot, start, 0))
        cv = lax.dynamic_update_slice(cv, packed(v, cv), (row, slot, start, 0))
        if not lane_attend:
            return _causal_attention(spec, q, k, v)
        lane = lambda c: read_lane(c, row, slot).reshape(
            1, c.shape[2], *k.shape[2:])
        return _causal_attention(spec, q, lane(ck), lane(cv), q_offset=start)

    def recur(row, p, u):
        nonlocal cs, cc
        if lane_attend:
            tail, state = _lane(cc, row, slot), _lane(cs, row, slot)
        else:
            tail, state = jnp.zeros(cc.shape[2:]), jnp.zeros(cs.shape[2:])
        out, tail, state = mamba_run(spec, p, u[0], tail, state, length)
        cs = lax.dynamic_update_slice(cs, state[None, None], (row, slot, 0, 0))
        cc = lax.dynamic_update_slice(cc, tail[None, None], (row, slot, 0, 0))
        return out[None]

    x = forward_layers(spec, params, _embed(spec, params, chunk)[None],
                       attend, recur)

    def last_logits():
        xt = lax.dynamic_slice_in_dim(x[0], length - 1, 1, axis=0)
        return head_logits(spec, params, xt)[0]

    toks, seeds, steps, temps, top_ps, first = install_lane_sampling(
        toks, seeds, steps, temps, top_ps, slot, final, seed, temperature,
        top_p, last_logits,
    )
    put = lambda a, v: lax.dynamic_update_slice(
        a, jnp.asarray(v)[None].astype(a.dtype), (slot,))
    cache = cache._replace(
        k=ck, v=cv, ssm=cs, conv=cc, pos=put(cache.pos, start + length),
    )
    return cache, toks, seeds, steps, temps, top_ps, first


def slot_decode_step(spec: LMSpec, params, cache: SlotCache, tokens, *,
                     attn_impl: str = "reference", ssm_impl: str = "auto"):
    """Advance the LIVE lanes one token: ``tokens`` ``[S]``, lane s's
    token at ``cache.pos[s]`` -> (logits ``[S, V]``, cache). An idle
    lane rides along in the batch (the shape never changes) and its
    logits are garbage, but nothing of it moves: its state and tail are
    neither read nor written by the update kernel, its ``pos`` stays,
    and the K/V row it writes lands at ``pos``, above everything
    attendable."""
    S = tokens.shape[0]
    live, pos = cache.live, cache.pos
    lanes = ssm.live_lanes(live)
    box = [cache]

    def attend(row, q, k, v):
        packed = lambda a: a.reshape(S, 1, -1)
        box[0] = _write_kv_rows(box[0], row, packed(k), packed(v), pos)
        a = packed_decode_attention(
            q, box[0].k, box[0].v, jnp.minimum(pos, spec.total_len - 1),
            impl=attn_impl, layer=row, scale=attn_scale(spec),
        )
        return a.reshape(S, -1)

    def recur(row, p, u):
        c = box[0]
        w = p["conv1d"]
        z, xbc, dt = mamba_inputs(spec, p, u)
        conv, tail = ssm.conv_step(xbc, c.conv[row], w["weight"], w["bias"])
        xs, B, C = split_xbc(spec, conv)
        state, y = ssm.ssm_state_update(
            c.ssm, row, xs, dt, -jnp.exp(p["A_log"].astype(jnp.float32)),
            B, C, p["D"], live, impl=ssm_impl, lanes=lanes,
        )
        tail = jnp.where(live[:, None, None], tail, c.conv[row])
        box[0] = c._replace(ssm=state, conv=c.conv.at[row].set(tail))
        return mamba_out(spec, p, y, z)

    x = forward_layers(spec, params, _embed(spec, params, tokens), attend,
                       recur)
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
