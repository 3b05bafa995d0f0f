"""Operations and bytes the GLM-5 forward NEEDS, from shapes and the
program's counters alone (multiply-add = 2).

The rules that keep a share of a roofline honest. Weights are counted
once a program execution; of the held experts only those a step's
tokens HIT (a decode step of 16 lanes routes ~8 pairs to the 16 held
experts and needs ~6 of them; a chunk needs all). A position's key and
value are expanded from its latent once (the per-token ``kv_b_proj``
product: the absorbed decode does the same count of operations in
another order), never once a chunk that reads it. The indexer's key
rows are counted as SCORED (``t + 1`` a query a layer) and the latent
rows as SELECTED (``min(t + 1, index_topk)``), as the engine's
``dsa_rows_scored_total`` / ``dsa_rows_selected_total`` count them; a
chunk reads a stored row once a layer however many of its queries
select it. Padding and idle lanes are never counted. What a dense
walk over a lane's unselected rows costs the program is its loss, not
its need.
"""

from __future__ import annotations

WEIGHT_BYTES = 2  # bfloat16 matrices, as stored
ROW_BYTES = 2  # bfloat16 latent and indexer rows


def is_dense(sizes: dict, i: int) -> bool:
    return i < sizes["first_k_dense_replace"]


def routed_layers(sizes: dict) -> int:
    return sizes["depth"] - sizes["first_k_dense_replace"]


def mla_params(sizes: dict) -> int:
    d, H, Rq, R = (sizes[k] for k in (
        "d_model", "num_heads", "q_lora_rank", "kv_lora_rank"))
    Dn, Dr, Dv = (sizes[k] for k in (
        "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim"))
    return (d * Rq + Rq * H * (Dn + Dr) + d * (R + Dr) + R * H * (Dn + Dv)
            + H * Dv * d)


def indexer_params(sizes: dict) -> int:
    d, Rq, Hi, Di = (sizes[k] for k in (
        "d_model", "q_lora_rank", "index_n_heads", "index_head_dim"))
    return Rq * Hi * Di + d * Di + d * Hi


def expert_params(sizes: dict) -> int:
    return 3 * sizes["d_model"] * sizes["moe_intermediate"]


def ffn_params(sizes: dict, dense: bool, experts: float | None = None) -> float:
    """A layer's FFN matrices: the dense SwiGLU, or the router, the
    shared experts and ``experts`` routed ones (all held by default)."""
    d = sizes["d_model"]
    if dense:
        return 3 * d * sizes["mlp_intermediate"]
    held = sizes["experts_held"] if experts is None else experts
    return (d * sizes["router_outputs"]
            + (sizes["n_shared_experts"] + held) * expert_params(sizes))


def vector_params(sizes: dict, dense: bool) -> int:
    """Norm weights, the indexer's LayerNorm and the router's bias."""
    return (2 * sizes["d_model"] + sizes["q_lora_rank"]
            + sizes["kv_lora_rank"] + 2 * sizes["index_head_dim"]
            + (0 if dense else sizes["router_outputs"]))


def layer_params(sizes: dict, i: int) -> float:
    dense = is_dense(sizes, i)
    return (mla_params(sizes) + indexer_params(sizes)
            + ffn_params(sizes, dense) + vector_params(sizes, dense))


def head_params(sizes: dict) -> int:
    """The head and the final norm (the embedding is gathered from, a
    row a token, not multiplied)."""
    return sizes["vocab_size"] * sizes["d_model"] + sizes["d_model"]


def param_count(sizes: dict) -> float:
    return (sum(layer_params(sizes, i) for i in range(sizes["depth"]))
            + head_params(sizes) + sizes["vocab_size"] * sizes["d_model"])


def weight_bytes(sizes: dict) -> float:
    return param_count(sizes) * WEIGHT_BYTES


def row_bytes(sizes: dict) -> dict[str, int]:
    """One position of one layer: its latent row and its indexer key."""
    return {
        "latent": (sizes["kv_lora_rank"] + sizes["qk_rope_head_dim"])
        * ROW_BYTES,
        "index": sizes["index_head_dim"] * ROW_BYTES,
    }


def lane_bytes(sizes: dict, cache_length: int) -> int:
    """What one lane holds: every layer's rows for every position."""
    return sizes["depth"] * cache_length * sum(row_bytes(sizes).values())


def stored_lane_bytes(sizes: dict, cache_length: int) -> int:
    """What one lane TAKES: the latent row is stored padded to whole
    groups of 128 lanes (576 -> 640: ``generate.latent_row_width``)."""
    latent = -(-(sizes["kv_lora_rank"] + sizes["qk_rope_head_dim"])
               // 128) * 128
    return sizes["depth"] * cache_length * (
        latent + sizes["index_head_dim"]) * ROW_BYTES


def experts_hit(sizes: dict, tokens: float) -> float:
    """Held experts that ``tokens`` routed tokens are expected to hit:
    each of the ``tokens * top_k * held / E`` pairs held here falls on
    one of the held experts alike."""
    held = sizes["experts_held"]
    pairs = tokens * sizes["moe_top_k"] * held / sizes["router_outputs"]
    return held * (1.0 - (1.0 - 1.0 / held) ** pairs)


def pairs_held(sizes: dict, tokens: float) -> float:
    return (tokens * sizes["moe_top_k"] * sizes["experts_held"]
            / sizes["router_outputs"])


# ---- per token -------------------------------------------------------------


def token_matmul_flops(sizes: dict, *, head: bool) -> float:
    """One token through every layer's matrices: attention and indexer
    projections, the dense MLPs, the router, the shared expert, and its
    expected share of held experts (``top_k * held / E`` of them)."""
    out = 0.0
    for i in range(sizes["depth"]):
        dense = is_dense(sizes, i)
        out += mla_params(sizes) + indexer_params(sizes) + ffn_params(
            sizes, dense, experts=pairs_held(sizes, 1.0))
    return 2.0 * (out + (sizes["vocab_size"] * sizes["d_model"]
                         if head else 0))


def scored_row_flops(sizes: dict) -> float:
    """One (query, stored row) pair of the indexer in one layer."""
    return 2.0 * sizes["index_n_heads"] * sizes["index_head_dim"]


def decode_row_flops(sizes: dict) -> float:
    """One selected row of one layer in the absorbed form: every head's
    dot with the (R + Dr)-wide key and its share of the R-wide value."""
    R, Dr = sizes["kv_lora_rank"], sizes["qk_rope_head_dim"]
    return 2.0 * sizes["num_heads"] * (2 * R + Dr)


def chunk_row_flops(sizes: dict) -> float:
    """One selected (query, key) pair of one layer in the expanded
    form: every head's (Dn + Dr)-wide dot and its Dv-wide value."""
    return 2.0 * sizes["num_heads"] * (
        sizes["qk_nope_head_dim"] + sizes["qk_rope_head_dim"]
        + sizes["v_head_dim"])


# ---- the kernels -----------------------------------------------------------


def grouped_expert_flops(sizes: dict, pairs: float) -> float:
    """The two grouped calls of one routed layer over ``pairs`` held
    (token, expert) pairs."""
    return 2.0 * pairs * expert_params(sizes)


def grouped_expert_bytes(sizes: dict, pairs: float, hit: float) -> float:
    """... the ``hit`` experts' three matrices once, each pair's row in
    (d), its hidden row out and in again (f) and its output row (d,
    float32)."""
    d, f = sizes["d_model"], sizes["moe_intermediate"]
    return (hit * expert_params(sizes) * WEIGHT_BYTES
            + pairs * (d * 2 + 2 * f * 2 + d * 4))


# ---- the programs ----------------------------------------------------------


def _static_weight_bytes(sizes: dict, *, head: bool) -> float:
    """Everything but the routed experts."""
    out = 0.0
    for i in range(sizes["depth"]):
        dense = is_dense(sizes, i)
        out += (mla_params(sizes) + indexer_params(sizes)
                + ffn_params(sizes, dense, experts=0.0)
                + vector_params(sizes, dense))
    return (out + (head_params(sizes) if head else 0)) * WEIGHT_BYTES


def decode_step_bytes(sizes: dict, *, live_lanes: float, rows_scored: float,
                      rows_selected: float) -> float:
    """One decode step: the weights once (the held experts the live
    lanes are expected to hit), the indexer rows scored and the latent
    rows selected (the engine's counts, summed over layers), a row of
    each kind written a live lane a layer, a row of the embedding a
    live lane."""
    rb = row_bytes(sizes)
    experts = (routed_layers(sizes) * experts_hit(sizes, live_lanes)
               * expert_params(sizes) * WEIGHT_BYTES)
    rows = (rows_scored * rb["index"] + rows_selected * rb["latent"]
            + live_lanes * sizes["depth"] * (rb["index"] + rb["latent"]))
    return (_static_weight_bytes(sizes, head=True) + experts + rows
            + live_lanes * sizes["d_model"] * WEIGHT_BYTES)


def decode_step_flops(sizes: dict, *, live_lanes: float, rows_scored: float,
                      rows_selected: float) -> float:
    return (live_lanes * token_matmul_flops(sizes, head=True)
            + rows_scored * scored_row_flops(sizes)
            + rows_selected * decode_row_flops(sizes))


def prefill_chunk_bytes(sizes: dict, *, tokens: float, start: float,
                        final: bool) -> float:
    """One chunk of ``tokens`` real positions from ``start``: every
    layer's weights once (a chunk's tokens hit every held expert), the
    lane's rows of both kinds below the chunk read once a layer and the
    chunk's written, a row of the embedding a token; a ``final`` chunk
    adds the head."""
    rb = row_bytes(sizes)
    experts = (routed_layers(sizes) * experts_hit(sizes, tokens)
               * expert_params(sizes) * WEIGHT_BYTES)
    rows = sizes["depth"] * (start + 2 * tokens) * (
        rb["index"] + rb["latent"])
    return (_static_weight_bytes(sizes, head=final) + experts + rows
            + tokens * sizes["d_model"] * WEIGHT_BYTES)


def prefill_chunk_flops(sizes: dict, *, tokens: float, start: float,
                        final: bool, rows_scored: float,
                        rows_selected: float) -> float:
    """... its tokens through the matrices, the (query, row) pairs its
    indexer scored and those attention read (the program's
    ``serve.chunk_selected`` record, summed over layers), and one token through the head
    where it samples."""
    del start
    return (tokens * token_matmul_flops(sizes, head=False)
            + rows_scored * scored_row_flops(sizes)
            + rows_selected * chunk_row_flops(sizes)
            + (2.0 * sizes["vocab_size"] * sizes["d_model"] if final else 0))
