"""Operations and bytes the SDAR / Qwen3-MoE forward NEEDS, from shapes
and routed counts alone (multiply-add = 2).

Two rules keep a share of a roofline honest. An expert's matrices are
counted once per call in which a row reaches it, never per row tile or
per padded row; and only the rows routed are counted, not the padding
the grouped kernels add to fill their tiles.
"""

from __future__ import annotations

WEIGHT_BYTES = 2  # bfloat16, as published and as stored


def expert_params(d_model: int, moe_intermediate: int) -> int:
    """One expert: gate, up, down."""
    return 3 * d_model * moe_intermediate


def layer_params(*, d_model: int, num_heads: int, num_kv_heads: int,
                 head_dim: int, num_experts: int, moe_intermediate: int,
                 **_) -> int:
    attn = (d_model * num_heads * head_dim  # q
            + 2 * d_model * num_kv_heads * head_dim  # k, v
            + num_heads * head_dim * d_model  # o
            + 2 * head_dim + 2 * d_model)  # q/k norm, the two RMSNorms
    return (attn + d_model * num_experts
            + num_experts * expert_params(d_model, moe_intermediate))


def param_count(sizes: dict) -> int:
    return (int(sizes["depth"]) * layer_params(**sizes)
            + 2 * sizes["vocab_size"] * sizes["d_model"] + sizes["d_model"])


def moe_kernels_flops(rows: int, d_model: int, moe_intermediate: int) -> float:
    """Both grouped kernels (gate and up, then down) over ``rows``
    routed rows (tokens x top_k, summed over the layer calls)."""
    return 2.0 * rows * expert_params(d_model, moe_intermediate)


def moe_kernels_bytes(rows: int, experts_hit: int, d_model: int,
                      moe_intermediate: int) -> float:
    """Bytes both kernels have to move: the matrices of every expert a
    row reached (``experts_hit``, summed over the layer calls), once;
    each routed row in (bf16), its SiLU product out and in again
    (bf16), its result out (fp32)."""
    weights = experts_hit * expert_params(d_model, moe_intermediate)
    per_row = d_model * 2 + 2 * moe_intermediate * 2 + d_model * 4
    return float(weights * WEIGHT_BYTES + rows * per_row)


def forward_bytes(sizes: dict, *, experts_hit_per_layer: float,
                  head: bool = True) -> float:
    """Weight bytes one forward streams: per layer the attention and
    router matrices and the experts reached, plus (a block step) the
    head. Activations and the cache rows are small beside them."""
    per_layer = (layer_params(**sizes)
                 - (sizes["num_experts"] - experts_hit_per_layer)
                 * expert_params(sizes["d_model"], sizes["moe_intermediate"]))
    total = int(sizes["depth"]) * per_layer
    if head:
        total += sizes["vocab_size"] * sizes["d_model"]
    return float(total * WEIGHT_BYTES)


def forward_flops_per_token(sizes: dict, *, context: int, top_k: int,
                            head: bool = True) -> float:
    """One token through every layer: projections, attention over
    ``context`` keys, the router, ``top_k`` experts, and the head."""
    d, H, Hkv, Dh = (sizes["d_model"], sizes["num_heads"],
                     sizes["num_kv_heads"], sizes["head_dim"])
    layer = (2.0 * d * (H + 2 * Hkv) * Dh + 2.0 * H * Dh * d
             + 2.0 * 2.0 * context * H * Dh
             + 2.0 * d * sizes["num_experts"]
             + 2.0 * top_k * expert_params(d, sizes["moe_intermediate"]))
    out = int(sizes["depth"]) * layer
    if head:
        out += 2.0 * d * sizes["vocab_size"]
    return out
