"""Decoder-only causal language model with sequence parallelism.

The reference is a vision classifier (model.py:4-20); this is the
framework's demonstration that its long-context machinery carries a
*language-model* workload: causal ring/Ulysses attention
(parallel/ring.py, global triangular mask exact across shard
boundaries), tokens sharded over the ``seq`` mesh axis end to end, and
a next-token loss whose label shift happens on the global sequence
before sharding — so the shard-boundary token's label (the NEXT
shard's first token) is correct by construction.

Layout: token embedding → learned position embedding → pre-LN causal
blocks (models/vit.py EncoderBlock with a causal attention_fn) → final
LN → logits through the TIED embedding transpose (the standard
weight-tying trick; halves the embedding parameters).
"""

from __future__ import annotations

import time
from typing import Any, Callable, NamedTuple, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ddp_tpu.obs.tracer import get_tracer, importing

with importing("optax"):
    import optax

from ddp_tpu.models.vit import EncoderBlock
from ddp_tpu.ops.attention import best_attention
from ddp_tpu.parallel.ddp import StepMetrics, jit_train_step, norm_plan
from ddp_tpu.parallel.ring import sequence_sharded_attention


class CausalLM(nn.Module):
    """[B, T_local] int32 tokens → [B, T_local, vocab] fp32 logits.

    ``num_experts > 0`` makes every ``moe_every``-th block a routed
    MoE block (models/moe.py MoEEncoderBlock — GShard top-k with
    capacity); the load-balance aux losses land in the ``losses``
    collection when it is marked mutable. Under sequence parallelism
    each token shard routes independently (standard local routing —
    the router never sees remote tokens).
    """

    vocab_size: int
    total_len: int
    d_model: int = 64
    depth: int = 2
    num_heads: int = 4
    mlp_ratio: int = 4
    # None → ops.attention.best_attention(causal=True): size-
    # dispatched — flash kernel on TPU past FLASH_MIN_LEN, dense
    # XLA otherwise.
    attention_fn: Optional[Callable] = None
    num_experts: int = 0  # 0 = dense MLPs everywhere
    moe_every: int = 2
    # Routing config for the MoE blocks (models/moe.py MoEMLP): how
    # many experts each token visits, and whether the surviving gates
    # are renormalized to sum to 1. Threaded from LMSpec so the decode
    # path (models/generate.py) can reproduce the training routing
    # instead of assuming the defaults.
    moe_top_k: int = 2
    moe_normalize_gates: bool = True
    remat: bool = False
    # Megatron TP over the ``model`` mesh axis (shard_map-only):
    # attention heads + MLP hidden shard, embeddings/LNs/tied head
    # replicate (parallel/tp.py). Routed blocks shard their ATTENTION
    # over ``model`` too (round 5 — Megatron-MoE); their expert MLPs
    # replicate across ``model`` and shard over ``expert`` instead.
    tp_axis: Optional[str] = None
    tp_size: int = 1
    # Expert parallelism over the ``expert`` mesh axis (shard_map-only):
    # each member holds num_experts/ep_size experts, tokens all-to-all
    # to their expert's owner and back (models/moe.py MoEMLP).
    ep_axis: Optional[str] = None
    ep_size: int = 1
    num_kv_heads: int = 0  # GQA — see models/vit.py MultiHeadAttention
    # The dense blocks' ``ln2`` output held once (models/vit.py
    # EncoderBlock): ``_make_sharded_forward`` decides, from the mesh.
    hold_norm: bool = False

    @nn.compact
    def __call__(self, tokens, pos_offset=0, head: bool = True):
        """``head=False`` stops in front of the tied head: ``(ln_final's
        float32 output, the embedding)``, for a caller that wants the
        loss and not the logits (``ops/lm_head.head_loss``)."""
        embed = self.param(
            "embed",
            nn.initializers.normal(stddev=0.02),
            (self.vocab_size, self.d_model),
        )
        x = embed[tokens]  # [B, T_local, d]
        pos = self.param(
            "pos_embed",
            nn.initializers.normal(stddev=0.02),
            (1, self.total_len, self.d_model),
        )
        x = x + lax.dynamic_slice_in_dim(
            pos.astype(x.dtype), pos_offset, x.shape[1], axis=1
        )
        from ddp_tpu.models.moe import MoEEncoderBlock, is_moe_block

        block_cls = nn.remat(EncoderBlock) if self.remat else EncoderBlock
        moe_cls = (
            nn.remat(MoEEncoderBlock) if self.remat else MoEEncoderBlock
        )
        attn_fn = self.attention_fn or best_attention(causal=True)
        for i in range(self.depth):
            if is_moe_block(i, self.num_experts, self.moe_every):
                x = moe_cls(
                    num_heads=self.num_heads,
                    mlp_dim=self.d_model * self.mlp_ratio,
                    num_experts=self.num_experts,
                    top_k=self.moe_top_k,
                    normalize_gates=self.moe_normalize_gates,
                    attention_fn=attn_fn,
                    ep_axis=self.ep_axis,
                    ep_size=self.ep_size,
                    num_kv_heads=self.num_kv_heads,
                    tp_axis=self.tp_axis,
                    tp_size=self.tp_size,
                    name=f"block{i + 1}",
                )(x)
            else:
                x = block_cls(
                    num_heads=self.num_heads,
                    mlp_dim=self.d_model * self.mlp_ratio,
                    attention_fn=attn_fn,
                    tp_axis=self.tp_axis,
                    tp_size=self.tp_size,
                    num_kv_heads=self.num_kv_heads,
                    hold_norm=self.hold_norm,
                    name=f"block{i + 1}",
                )(x)
        x = nn.LayerNorm(dtype=jnp.float32, name="ln_final")(x)
        if not head:
            return x, embed
        # Tied head: logits through the embedding transpose.
        return (x @ embed.T.astype(x.dtype)).astype(jnp.float32)


class LMSpec(NamedTuple):
    vocab_size: int
    total_len: int
    d_model: int = 64
    depth: int = 2
    num_heads: int = 4
    strategy: str = "ring"  # ring | ulysses
    remat: bool = False
    num_experts: int = 0  # >0: MoE MLPs every moe_every-th block
    moe_every: int = 2
    aux_loss_weight: float = 0.01  # GShard load-balance loss weight
    # MoE routing config (round 5: decode hardcoded top_k=2 and
    # always-normalized gates — now derived from the spec, and recorded
    # in the lm_spec.json checkpoint sidecar so serving recovers it).
    moe_top_k: int = 2
    moe_normalize_gates: bool = True
    # Grouped-query attention: 0 → num_heads (MHA). The generation
    # cache stores the COMPACT num_kv_heads (models/generate.py), so
    # decode HBM reads shrink by num_heads/num_kv_heads.
    num_kv_heads: int = 0
    mlp_ratio: int = 4
    # The block's architecture. ``gpt2`` (learned positions, LayerNorm,
    # GELU, tied head) is everything above; ``qwen3_moe`` is
    # models/sdar.py's block (rotary positions, RMSNorm, per-head q/k
    # norm, every MLP ``num_experts`` routed SwiGLU experts of width
    # ``moe_intermediate``, untied head, no biases, no position table:
    # ``total_len`` is then the cache's length alone);
    # ``granite_hybrid`` is models/granite_hybrid.py's (HF
    # ``granitemoehybrid``: Mamba-2 and position-free GQA layers by
    # ``layer_types``, a dense gated MLP, four scalar multipliers, tied
    # head, one token a step); ``sambay`` is models/sambay.py's (HF
    # ``phi4flash``: a self-decoder of Mamba-1, windowed and full
    # differential attention whose one full K/V layer and last state
    # read-out feed a cross-decoder of gated memory units and
    # cross-attention; LayerNorm with bias, tied head, no positions,
    # one token a step); ``glm_dsa`` is models/glm_dsa.py's (HF
    # ``glm_moe_dsa``: latent attention over the keys an indexer
    # selects, leading dense layers, then sigmoid-routed experts of
    # which this process holds a share, beside a shared one; untied
    # head, one token a step). All four serving only.
    block: str = "gpt2"
    head_dim: int = 0  # 0 -> d_model // num_heads
    moe_intermediate: int = 0
    rope_theta: float = 1e6
    rms_eps: float = 1e-6
    # How the model generates. 0: one token a step, left to right.
    # B > 0: a block of B positions at a time by masked diffusion
    # (models/sdar.py): ``denoise_steps`` steps a block, masked
    # positions fed ``mask_token_id``, unmasked by ``unmask``
    # (low_confidence_static | low_confidence_dynamic, the latter
    # taking every position above ``unmask_threshold``).
    block_length: int = 0
    denoise_steps: int = 0
    mask_token_id: int = -1
    unmask: str = "low_confidence_static"
    unmask_threshold: float = 0.9
    # The ``granite_hybrid`` block. ``layer_types``: one of "mamba" |
    # "attention" a layer (a tuple; the sidecar's list is converted).
    # A Mamba-2 layer has ``mamba_n_heads`` heads of ``mamba_d_head``
    # channels (their product is the inner width), a state of
    # ``mamba_d_state`` a channel, B and C shared by the heads of one
    # of ``mamba_n_groups`` groups, a depthwise convolution of
    # ``mamba_d_conv`` taps in front, and prefills in chunks of
    # ``mamba_chunk_size``. ``mlp_intermediate``: the gated MLP's width.
    # The multipliers scale the embedding, the attention logits (0:
    # ``head_dim ** -0.5``), each residual branch, and DIVIDE the
    # logits. ``tie_embeddings``: the head is the embedding.
    # ``position_embedding`` "nope": no table, no rotary.
    layer_types: tuple = ()
    mamba_n_heads: int = 0
    mamba_d_head: int = 0
    mamba_d_state: int = 0
    mamba_n_groups: int = 1
    mamba_d_conv: int = 4
    mamba_chunk_size: int = 256
    mlp_intermediate: int = 0
    embedding_multiplier: float = 1.0
    attention_multiplier: float = 0.0
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0
    tie_embeddings: bool = False
    position_embedding: str = ""
    # The ``sambay`` block. ``layer_types`` over five kinds: "mamba" |
    # "window" | "full" | "gmu" | "cross". A Mamba-1 layer has
    # ``mamba_d_inner`` channels with a decay of their own for each of
    # ``mamba_d_state`` state indices, a time step projected through
    # ``mamba_dt_rank``, and ``mamba_d_conv`` taps in front. A "window"
    # layer attends ``sliding_window`` keys, the position's own among
    # them. ``layer_norm_eps``: every LayerNorm's.
    mamba_d_inner: int = 0
    mamba_dt_rank: int = 0
    sliding_window: int = 0
    layer_norm_eps: float = 1e-5
    # The ``glm_dsa`` block, under the source's names. Latent attention:
    # the query passes a latent of ``q_lora_rank``; a position's keys
    # and values are ONE latent of ``kv_lora_rank`` and one rotated key
    # of ``qk_rope_head_dim`` shared by all heads; a head's query is
    # ``qk_nope_head_dim + qk_rope_head_dim`` wide and its value
    # ``v_head_dim``. The indexer: ``index_n_heads`` heads of
    # ``index_head_dim`` score every earlier position and a query
    # attends the ``index_topk`` best. The first
    # ``first_k_dense_replace`` layers have a dense gated MLP of
    # ``mlp_intermediate``; the others route ``moe_top_k`` of
    # ``n_routed_experts`` by sigmoid scores scaled by
    # ``routed_scaling_factor``, compute the ``num_experts`` experts
    # held HERE (numbers ``expert_offset`` onward) and
    # ``n_shared_experts`` shared ones of ``moe_intermediate``.
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    index_n_heads: int = 0
    index_head_dim: int = 0
    index_topk: int = 0
    first_k_dense_replace: int = 0
    n_routed_experts: int = 0
    n_shared_experts: int = 0
    routed_scaling_factor: float = 1.0
    expert_offset: int = 0


def head_dim_of(spec: LMSpec) -> int:
    return spec.head_dim or spec.d_model // spec.num_heads


def derive_lm_spec(params: Any, *, num_heads: int, **overrides) -> LMSpec:
    """Recover an LMSpec from a restored parameter tree.

    vocab_size, total_len, d_model, depth and the GQA kv-head count
    are all visible in the shapes (embed [V, d], pos_embed [1, L, d],
    blockN count, qkv kernel columns (H + 2·H_kv)·Dh); only the head
    count is not, so it is an argument. ``overrides`` lets a
    checkpoint-sidecar config (train/checkpoint.py save_lm_spec) fill
    the fields shapes cannot carry — MoE routing (moe_top_k,
    moe_normalize_gates), strategy — and wins over the derivation.
    Raises ValueError when the tree is not a causal-LM tree or the
    head count does not explain the shapes.
    """
    if "embed_tokens" in params:
        # the HF-named trees: the sidecar says which block (a tree with
        # Mamba layers says so itself)
        if overrides.get("block") == "sambay" or "final_layernorm" in params:
            from ddp_tpu.models.sambay import derive_spec
        elif overrides.get("block") == "glm_dsa":
            from ddp_tpu.models.glm_dsa import derive_spec
        elif overrides.get("block") == "granite_hybrid" or any(
            "mamba" in layer for layer in params.get("layers", {}).values()
        ):
            from ddp_tpu.models.granite_hybrid import derive_spec
        else:
            from ddp_tpu.models.sdar import derive_spec

        return derive_spec(params, num_heads=num_heads, **overrides)
    try:
        vocab_size, d_model = (int(s) for s in params["embed"].shape)
        total_len = int(params["pos_embed"].shape[1])
        depth = sum(1 for k in params if str(k).startswith("block"))
        qkv_cols = int(params["block1"]["attn"]["qkv"]["kernel"].shape[-1])
    except (KeyError, TypeError, AttributeError) as e:
        raise ValueError(f"not a causal-LM parameter tree (missing {e})")
    if d_model % num_heads:
        raise ValueError(
            f"num_heads {num_heads} does not divide d_model {d_model}"
        )
    head_dim = d_model // num_heads
    num_kv_heads = (qkv_cols // head_dim - num_heads) // 2
    if (num_kv_heads * 2 + num_heads) * head_dim != qkv_cols:
        raise ValueError(
            f"qkv kernel has {qkv_cols} columns, which no kv-head "
            f"count explains at num_heads {num_heads} — wrong head "
            "count?"
        )
    fields = dict(
        vocab_size=vocab_size,
        total_len=total_len,
        d_model=d_model,
        depth=depth,
        num_heads=num_heads,
        num_kv_heads=0 if num_kv_heads == num_heads else num_kv_heads,
    )
    # Shape-derived fields win: the checkpoint is ground truth, a
    # sidecar can only add what shapes cannot see.
    fields.update(
        (k, v)
        for k, v in overrides.items()
        if k in LMSpec._fields and k not in fields
    )
    return LMSpec(**fields)


def _dense_lm(spec: LMSpec) -> CausalLM:
    return CausalLM(
        vocab_size=spec.vocab_size,
        total_len=spec.total_len,
        d_model=spec.d_model,
        depth=spec.depth,
        num_heads=spec.num_heads,
        num_experts=spec.num_experts,
        moe_every=spec.moe_every,
        moe_top_k=spec.moe_top_k,
        moe_normalize_gates=spec.moe_normalize_gates,
        remat=spec.remat,
        num_kv_heads=spec.num_kv_heads,
        mlp_ratio=spec.mlp_ratio,
    )


def _sharded_lm(
    spec: LMSpec, *, tp_size: int = 1, ep_size: int = 1,
    hold_norm: bool = False,
) -> CausalLM:
    def attention(q, k, v):
        return sequence_sharded_attention(
            q, k, v, axis_name="seq", strategy=spec.strategy, causal=True
        )

    local = best_attention(causal=True)

    def from_projection(qkv, heads):
        # A ``seq`` axis of one member holds the whole sequence: the
        # ring has no hop and Ulysses nothing to exchange, so the local
        # attention may read the fused projection itself (inside
        # ``shard_map`` the axis size is a Python int).
        if lax.psum(1, "seq") != 1:
            return None
        return local.from_projection(qkv, heads)

    attention.from_projection = from_projection

    return CausalLM(
        vocab_size=spec.vocab_size,
        total_len=spec.total_len,
        d_model=spec.d_model,
        depth=spec.depth,
        num_heads=spec.num_heads,
        attention_fn=attention,
        num_experts=spec.num_experts,
        moe_every=spec.moe_every,
        moe_top_k=spec.moe_top_k,
        moe_normalize_gates=spec.moe_normalize_gates,
        remat=spec.remat,
        tp_axis="model" if tp_size > 1 else None,
        tp_size=tp_size,
        ep_axis="expert" if ep_size > 1 else None,
        ep_size=ep_size,
        num_kv_heads=spec.num_kv_heads,
        mlp_ratio=spec.mlp_ratio,
        hold_norm=hold_norm,
    )


def init_lm(spec: LMSpec, *, seed: int = 0):
    """Params from a short stub — every shape is length-independent."""
    stub = min(spec.total_len, 128)
    return _dense_lm(spec).init(
        jax.random.key(seed), jnp.zeros((1, stub), jnp.int32)
    )["params"]


def dense_lm_apply(spec: LMSpec, params, tokens):
    """Single-device reference forward over the full sequence."""
    return _dense_lm(spec).apply({"params": params}, tokens)


def next_token_loss(logits, tokens, *, label_smoothing: float = 0.0):
    """Mean causal-LM loss: position t predicts token t+1.

    ``logits``/``tokens`` are GLOBAL ([B, T, V] / [B, T]); the final
    position has no target and is masked out. ``label_smoothing=ε``
    trains against (1−ε)·one-hot + ε·uniform, computed directly from
    log-probs (no [B, T, V] one-hot materialized).
    """
    targets = jnp.concatenate(
        [tokens[:, 1:], jnp.zeros_like(tokens[:, :1])], axis=1
    )
    weights = jnp.concatenate(
        [
            jnp.ones(tokens[:, 1:].shape, jnp.float32),
            jnp.zeros(tokens[:, :1].shape, jnp.float32),
        ],
        axis=1,
    )
    logits32 = logits.astype(jnp.float32)
    per_tok = _per_token_nll(logits32, targets, label_smoothing)
    return (per_tok * weights).sum() / weights.sum()


# One step/params/opt_state state shape serves every sequence-model
# family (models/seq_transformer.py defines it + the replication
# factory — uniform shardings on every leaf).
from ddp_tpu.models.seq_transformer import (  # noqa: E402
    SeqTrainState as LMTrainState,
    replicated_train_state,
)


def create_lm_train_state(
    spec: LMSpec,
    optimizer: optax.GradientTransformation,
    mesh: Mesh,
    *,
    seed: int = 0,
    zero_layout=None,
    zero_gather_dtype=None,
) -> LMTrainState:
    """Replicated state, or fsdp-sharded at rest when the mesh has an
    ``fsdp`` axis > 1 (parallel/seq_fsdp.py — moments shard with the
    params, so optimizer memory drops by the axis size too).

    ``zero_layout`` (parallel/zero.py BucketLayout) is the ZeRO
    weight-update sharding variant: params replicate as usual but the
    optimizer state rests as flat fp32 buckets sharded 1/N over
    ``data`` — the layout ``make_lm_train_step(..., zero_layout=)``
    updates in place. ``zero_gather_dtype='bf16'`` adds the fp32
    master shards the half-width gather keeps exact (parallel/zero.py
    module docstring).
    """
    from ddp_tpu.models.seq_transformer import sharded_or_replicated_state

    if zero_layout is not None:
        from ddp_tpu.parallel.zero import create_zero_opt_state

        rep = NamedSharding(mesh, P())
        params = jax.tree.map(
            lambda x: jax.device_put(x, rep), init_lm(spec, seed=seed)
        )
        return LMTrainState(
            step=jax.device_put(jnp.zeros((), jnp.int32), rep),
            params=params,
            opt_state=create_zero_opt_state(
                params, optimizer, mesh, zero_layout,
                gather_dtype=zero_gather_dtype or jnp.float32,
            ),
        )
    return sharded_or_replicated_state(
        init_lm(spec, seed=seed), optimizer, mesh
    )


def _make_sharded_forward(spec: LMSpec, mesh: Mesh, compute_dtype):
    from ddp_tpu.models.moe import is_moe_block
    from ddp_tpu.models.seq_transformer import _batch_axes
    from ddp_tpu.parallel.tp import (
        ep_size as mesh_ep_size,
        gather_sharded,
        seq_param_specs,
        tp_size as mesh_tp_size,
    )

    form, reason = norm_plan(mesh, remat=spec.remat)
    model = _sharded_lm(
        spec, tp_size=mesh_tp_size(mesh), ep_size=mesh_ep_size(mesh),
        hold_norm=form == "held",
    )
    baxes = _batch_axes(mesh)
    xspec = P(baxes, "seq")
    held = sum(
        not is_moe_block(i, spec.num_experts, spec.moe_every)
        for i in range(spec.depth)) if form == "held" else 0

    def forward(params, tokens, want_aux: bool = True, head: bool = True):
        """→ (logits sharded like the tokens, replicated MoE aux loss
        scalar — 0.0 for dense specs or ``want_aux=False``, which also
        skips the aux collection and its cross-device mean: eval has
        no use for the routing penalty). ``head=False``: in the logits'
        place ``(ln_final's output, sharded like the tokens, every
        device's compute-dtype embedding)``, what the fused head of
        ``_make_sharded_token_metrics`` takes. The embeddings are
        stacked on a leading axis sharded over the WHOLE mesh, so each
        device hands its own copy to its own loss shard and takes that
        shard's cotangent back as it is: a replicated (``P()``) output
        would be summed over the mesh at the boundary, a second
        all-reduce of the largest gradient."""
        pspecs = seq_param_specs(params, mesh)
        collect_aux = bool(spec.num_experts) and want_aux

        def per_shard_forward(params, tok_shard):
            params = gather_sharded(params, pspecs)
            t_local = tok_shard.shape[1]
            offset = lax.axis_index("seq") * t_local
            if compute_dtype != jnp.float32:
                params = jax.tree.map(
                    lambda p: p.astype(compute_dtype), params
                )
            if collect_aux:
                logits, variables = model.apply(
                    {"params": params}, tok_shard, pos_offset=offset,
                    head=head, mutable=["losses"],
                )
                leaves = jax.tree.leaves(variables.get("losses", {}))
                aux = (
                    sum(leaves) / len(leaves) if leaves else jnp.float32(0.0)
                )
                # Replicate: each shard routed its own tokens; the
                # batch aux is the mean over every shard's groups.
                aux = lax.pmean(aux, mesh.axis_names)
            else:
                logits = model.apply(
                    {"params": params}, tok_shard, pos_offset=offset,
                    head=head,
                )
                aux = jnp.float32(0.0)
            if not head:
                hidden, embed = logits
                logits = hidden, embed[None]
            return logits, aux

        return jax.shard_map(
            per_shard_forward,
            mesh=mesh,
            in_specs=(pspecs, xspec),
            out_specs=(
                xspec if head else (xspec, P(mesh.axis_names)), P()),
            check_vma=False,
        )(params, tokens)

    def record_norm_plan(tokens):
        """One ``lm.norm_plan`` record, for a traced forward that is
        differentiated (only there is anything held). Trace time, as
        ``flash.plan``: a compiled step leaves none."""
        get_tracer().complete(
            "lm.norm_plan", time.perf_counter(), 0.0,
            nums=(form, reason, held,
                  held * tokens.size * spec.d_model
                  * jnp.dtype(compute_dtype).itemsize),
        )

    forward.record_norm_plan = record_norm_plan
    return forward, xspec


def _per_token_nll(logits32, targets, label_smoothing: float):
    """[B, T] next-token NLL from fp32 logits (shared CE math)."""
    if label_smoothing:
        eps = label_smoothing
        logp = jax.nn.log_softmax(logits32, axis=-1)
        nll_target = -jnp.take_along_axis(logp, targets[..., None], -1)[..., 0]
        return (1.0 - eps) * nll_target - (
            eps / logits32.shape[-1]
        ) * logp.sum(-1)
    return optax.softmax_cross_entropy_with_integer_labels(logits32, targets)


def _make_sharded_token_metrics(
    spec: LMSpec, mesh: Mesh, *, label_smoothing: float = 0.0
):
    """Next-token (loss, correct-count) computed INSIDE shard_map.

    The train/eval steps used to run the CE + argmax on the GLOBAL
    [B, T, V] logits the forward shard_map returns, leaving the jit
    partitioner to reshard them. On jax 0.4.x CPU that miscompiles
    once the mesh has both ``model`` and ``seq`` axes (values half-
    wrong or NaN for ops fused downstream of the multi-axis shard_map
    — measured round 6). Keeping every consumer of the sharded logits
    inside shard_map sidesteps the partitioner entirely, and is the
    TPU-native shape anyway: no global reshard of train-scale logits,
    each shard reduces its own tokens, one psum carries scalars.

    The global label shift becomes a ring exchange: shard s's last
    local position targets shard s+1's first token (``ppermute``); the
    very last global position is weight-0, exactly as in
    ``next_token_loss``. Returns ``(mean loss, correct count)``
    replicated; weights sum to B·(T−1).

    The form follows what the loss needs, no knob. Integer targets
    without label smoothing need a row's log-sum-exp, its target's
    logit and its arg-max, not the logits: the returned function then
    takes ``sharded_forward(..., head=False)``'s ``(hidden, embeddings)``
    in the logits' place and runs head and loss as ONE operation
    (``ops/lm_head.head_loss``; its ``fused`` attribute says so).
    Label smoothing sums every log-probability and keeps the plain path
    over logits. Either way each traced call leaves one ``lm.head_plan``
    record.
    """
    from ddp_tpu.models.seq_transformer import _batch_axes
    from ddp_tpu.ops.lm_head import head_loss, padded_vocab

    baxes = _batch_axes(mesh)
    xspec = P(baxes, "seq")
    n_seq = mesh.shape.get("seq", 1)
    red_axes = tuple(baxes or ()) + (("seq",) if n_seq > 1 else ())
    # model/expert members hold identical logits copies, so pmean over
    # them is an identity — but it is what lets the jax-0.4.x shard_map
    # transpose treat the P() scalar outputs as replicated (same reason
    # the forward's aux output pmeans over every mesh axis).
    rep_axes = tuple(a for a in mesh.axis_names if a not in red_axes)

    fused = not label_smoothing

    def body(logits, tok_shard):
        T_l = tok_shard.shape[1]
        if n_seq > 1:
            nxt = lax.ppermute(
                tok_shard[:, :1],
                "seq",
                perm=[(k, (k - 1) % n_seq) for k in range(n_seq)],
            )
            on_last_shard = lax.axis_index("seq") == n_seq - 1
        else:
            nxt = jnp.zeros_like(tok_shard[:, :1])
            on_last_shard = jnp.bool_(True)
        targets = jnp.concatenate([tok_shard[:, 1:], nxt], axis=1)
        weights = jnp.where(
            (jnp.arange(T_l) == T_l - 1) & on_last_shard, 0.0, 1.0
        )[None, :].astype(jnp.float32)  # [1, T_l], broadcasts over B
        vocab = spec.vocab_size
        # Trace time, as ``flash.plan``: a compiled step leaves none.
        get_tracer().complete(
            "lm.head_plan", time.perf_counter(), 0.0,
            nums=("fused" if fused else "plain", targets.size, vocab,
                  padded_vocab(vocab) if fused else vocab, targets.size),
        )
        if fused:
            hidden, embeds = logits
            loss_sum, correct = head_loss(
                hidden, embeds[0], targets, weights)
        else:
            logits32 = logits.astype(jnp.float32)
            per_tok = _per_token_nll(logits32, targets, label_smoothing)
            loss_sum = (per_tok * weights).sum()
            pred = jnp.argmax(logits32, -1)
            correct = (
                (pred == targets).astype(jnp.float32) * weights).sum()
        if red_axes:
            loss_sum, correct = lax.psum((loss_sum, correct), red_axes)
        # The weight total is static — B_global·(T_global−1) — so divide
        # by the Python constant: a TRACED w_sum would become a scalar
        # residual with a nonzero cotangent, which the jax-0.4.x
        # shard_map transpose cannot express (rank-0 aval with
        # all-axes out names → _SpecError).
        b_global = tok_shard.shape[0]
        for a in baxes or ():
            b_global *= mesh.shape[a]
        loss = loss_sum / (b_global * (T_l * n_seq - 1))
        if rep_axes:
            loss, correct = lax.pmean((loss, correct), rep_axes)
        return loss, correct

    metrics = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(
            (xspec, P(mesh.axis_names)) if fused else xspec, xspec),
        out_specs=(P(), P()),
        check_vma=False,
    )
    metrics.fused = fused
    return metrics


def make_lm_eval_step(
    spec: LMSpec, mesh: Mesh, *, compute_dtype=jnp.float32
):
    """Trainer-compatible eval: next-token metrics over held-out tokens.

    Signature matches the classifier eval steps —
    ``(params, model_state, tokens, labels, weights) →
    (weighted Σ per-sequence token accuracy, weighted Σ per-sequence
    mean loss)`` — so ``Trainer.evaluate`` divides by n and reports
    average next-token accuracy where classifiers report top-1.
    ``labels`` is ignored (targets are the shifted tokens themselves).
    """
    sharded_forward, _ = _make_sharded_forward(spec, mesh, compute_dtype)
    seq_metrics = _make_sharded_seq_metrics(spec, mesh)

    def step(params, model_state, tokens, labels, weights):
        del model_state, labels
        logits, _ = sharded_forward(params, tokens, want_aux=False)
        return seq_metrics(logits, tokens, weights)

    return jax.jit(step)


def _make_sharded_seq_metrics(spec: LMSpec, mesh: Mesh):
    """Eval-side sibling of ``_make_sharded_token_metrics``: weighted
    Σ per-sequence accuracy and per-sequence mean loss, with the CE /
    argmax kept inside shard_map for the same jax-0.4.x partitioner
    reason. Per-sequence sums psum over ``seq``; the weighted batch
    sums psum over the batch axes."""
    from ddp_tpu.models.seq_transformer import _batch_axes

    baxes = _batch_axes(mesh)
    xspec = P(baxes, "seq")
    n_seq = mesh.shape.get("seq", 1)
    red_axes = tuple(baxes or ()) + (("seq",) if n_seq > 1 else ())
    rep_axes = tuple(a for a in mesh.axis_names if a not in red_axes)

    def body(logits, tok_shard, w_shard):
        T_l = tok_shard.shape[1]
        if n_seq > 1:
            nxt = lax.ppermute(
                tok_shard[:, :1],
                "seq",
                perm=[(k, (k - 1) % n_seq) for k in range(n_seq)],
            )
            on_last_shard = lax.axis_index("seq") == n_seq - 1
        else:
            nxt = jnp.zeros_like(tok_shard[:, :1])
            on_last_shard = jnp.bool_(True)
        targets = jnp.concatenate([tok_shard[:, 1:], nxt], axis=1)
        mask = jnp.where(
            (jnp.arange(T_l) == T_l - 1) & on_last_shard, 0.0, 1.0
        )[None, :].astype(jnp.float32)
        logits32 = logits.astype(jnp.float32)
        per_tok = _per_token_nll(logits32, targets, 0.0)
        pred = jnp.argmax(logits32, -1)
        seq_loss = (per_tok * mask).sum(axis=1)  # [B_l]
        seq_correct = ((pred == targets).astype(jnp.float32) * mask).sum(1)
        if n_seq > 1:
            seq_loss, seq_correct = lax.psum(
                (seq_loss, seq_correct), "seq"
            )
        denom = T_l * n_seq - 1  # targets per sequence
        acc_sum = (seq_correct / denom * w_shard).sum()
        loss_sum = (seq_loss / denom * w_shard).sum()
        if baxes:
            acc_sum, loss_sum = lax.psum((acc_sum, loss_sum), baxes)
        if rep_axes:
            acc_sum, loss_sum = lax.pmean((acc_sum, loss_sum), rep_axes)
        return acc_sum, loss_sum

    return jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(xspec, xspec, P(baxes)),
        out_specs=(P(), P()),
        check_vma=False,
    )


def make_lm_train_step(
    spec: LMSpec,
    optimizer: optax.GradientTransformation,
    mesh: Mesh,
    *,
    donate: bool = True,
    compute_dtype=jnp.float32,
    grad_accum_steps: int = 1,
    label_smoothing: float = 0.0,
    jit: bool = True,
    health: bool = False,
    health_inject: tuple[str, int] | None = None,
    zero_layout=None,
    zero_gather_dtype=None,
    zero_grad_clip_norm: float = 0.0,
):
    """dp×sp[×fsdp] causal-LM step: ``step(state, tokens)``.

    ``zero_layout`` swaps the replicated weight update for the ZeRO
    in-graph GSPMD expression (parallel/zero.py ``zero_gspmd_update``):
    gradients constrain into data-sharded flat buckets, the optimizer
    runs on 1/N shards with the moments resting sharded, and the SPMD
    partitioner derives the parameter all-gather — composing with
    populated ``model``/``seq`` axes, where the buckets shard over
    ``data`` and replicate over the model axes. Loss/metrics math is
    untouched — trajectories pin against the plain step.
    ``zero_gather_dtype='bf16'`` gathers the updated params half-width
    over fp32 master shards; ``zero_grad_clip_norm`` applies the
    global-norm clip inside the sharded update (the trainer builds the
    optimizer without the chained clip in zero mode).

    ``jit=False`` returns the raw (untraced) step for callers that
    embed it in a larger program — the compiled-epoch runner
    (train/fast.py make_lm_epoch_runner) scans it.

    ``tokens``: [B, T_global] int32. The loss/accuracy math runs
    INSIDE a second shard_map (``_make_sharded_token_metrics`` — label
    shift as a ring exchange, CE reduced per shard, one psum), so the
    jit partitioner never consumes the sharded logits; gradients
    arrive psum'd (and, for fsdp-sharded params, scatter-reduced —
    parallel/seq_fsdp.py) by the shard_map transpose. ``grad_accum_steps=k`` splits the
    batch into k STRIDED microbatches (rows i::k — contiguous splits
    would reshard the data-axis layout every step, parallel/spmd.py)
    and accumulates gradients through one ``lax.scan``. Metrics: loss
    is the mean next-token cross-entropy, accuracy the next-token
    top-1.
    """
    if zero_layout is not None and health:
        # The health stats pass reads the UPDATE tree, which the zero
        # expression only materializes as 1/N flat shards — same wall
        # the Trainer enforces at the flag level.
        raise ValueError(
            "health stats need the full update tree; the zero sharded "
            "update never materializes it — drop health or zero_layout"
        )
    sharded_forward, xspec = _make_sharded_forward(spec, mesh, compute_dtype)
    token_metrics = _make_sharded_token_metrics(
        spec, mesh, label_smoothing=label_smoothing
    )

    def loss_and_logits(params, tokens):
        sharded_forward.record_norm_plan(tokens)
        logits, aux = sharded_forward(
            params, tokens, head=not token_metrics.fused)
        loss, correct = token_metrics(logits, tokens)
        if spec.num_experts:
            loss = loss + spec.aux_loss_weight * aux
        return loss, correct

    def step(state: LMTrainState, tokens):
        tokens = lax.with_sharding_constraint(
            tokens, NamedSharding(mesh, xspec)
        )
        if grad_accum_steps == 1:
            (loss, correct), grads = jax.value_and_grad(
                loss_and_logits, has_aux=True
            )(state.params, tokens)
        else:
            from ddp_tpu.parallel.common import check_accum_divisible

            mb = check_accum_divisible(tokens.shape[0], grad_accum_steps)
            micro_toks = lax.with_sharding_constraint(
                tokens.reshape(mb, grad_accum_steps, tokens.shape[1])
                .swapaxes(0, 1),
                NamedSharding(mesh, P(None, *xspec)),
            )

            def micro(carry, toks):
                g_acc, loss_acc, correct_acc = carry
                (loss, correct), g = jax.value_and_grad(
                    loss_and_logits, has_aux=True
                )(state.params, toks)
                return (
                    jax.tree.map(jnp.add, g_acc, g),
                    loss_acc + loss,
                    correct_acc + correct,
                ), None

            zero_g = jax.tree.map(jnp.zeros_like, state.params)
            (g_sum, loss_sum, correct), _ = lax.scan(
                micro, (zero_g, jnp.float32(0.0), jnp.float32(0.0)), micro_toks
            )
            grads = jax.tree.map(lambda g: g / grad_accum_steps, g_sum)
            loss = loss_sum / grad_accum_steps
        if health_inject is not None:
            from ddp_tpu.obs.health import inject_nan

            grads = inject_nan(grads, state.step, health_inject)
        if zero_layout is not None:
            from ddp_tpu.parallel.zero import zero_gspmd_update

            params, opt_state = zero_gspmd_update(
                optimizer, zero_layout, mesh, grads,
                state.opt_state, state.params,
                gather_dtype=zero_gather_dtype or jnp.float32,
                grad_clip_norm=zero_grad_clip_norm,
            )
        else:
            with jax.named_scope("optimizer_update"):
                updates, opt_state = optimizer.update(
                    grads, state.opt_state, state.params
                )
                params = optax.apply_updates(state.params, updates)
        accuracy = correct / (tokens.shape[0] * (tokens.shape[1] - 1))
        if health:
            from ddp_tpu.obs.health import health_stats

            hstats = health_stats(grads, state.params, updates)
        else:
            hstats = None
        return (
            state._replace(
                step=state.step + 1, params=params, opt_state=opt_state
            ),
            StepMetrics(
                loss=loss, accuracy=accuracy,
                grad_norm=optax.global_norm(grads),
                health=hstats,
            ),
        )

    if not jit:
        return step
    return jit_train_step(step, mesh, donate=donate, zero_layout=zero_layout)
