"""Operations and bytes the granite-hybrid forward NEEDS, from shapes
and the program's counters alone (multiply-add = 2).

Four rules keep a share of a roofline honest. Weights are counted once
a program execution (a decode step of any number of lanes, a prefill
chunk), never once a lane. Recurrent state is counted for LIVE lanes
only, once in and once out a step. K/V rows are counted as attended
(each decoding lane's ``pos + 1`` rows, in the stored dtype), never the
lane's whole length. Padding is never counted: a chunk's operations are
those of its real positions.
"""

from __future__ import annotations

WEIGHT_BYTES = 2  # bfloat16 matrices, as published and as stored
STATE_BYTES = 4  # float32 state, tail and K/V


def _mamba(sizes: dict) -> tuple[int, int, int]:
    hp = sizes["mamba_n_heads"] * sizes["mamba_d_head"]
    return hp, hp + 2 * sizes["mamba_d_state"], sizes["mamba_n_heads"]


def mlp_params(sizes: dict) -> int:
    return 3 * sizes["d_model"] * sizes["mlp_intermediate"]


def mamba_layer_params(sizes: dict) -> int:
    d = sizes["d_model"]
    hp, cd, H = _mamba(sizes)
    return (d * (hp + cd + H)  # in_proj
            + sizes["mamba_d_conv"] * cd + cd  # convolution and its bias
            + 3 * H + hp  # dt_bias, A_log, D; the gated norm
            + hp * d + 2 * d  # out_proj; the two RMSNorms
            + mlp_params(sizes))


def attention_layer_params(sizes: dict) -> int:
    d, Dh = sizes["d_model"], sizes["head_dim"]
    return (2 * d * sizes["num_heads"] * Dh  # q, o
            + 2 * d * sizes["num_kv_heads"] * Dh  # k, v
            + 2 * d + mlp_params(sizes))


def layer_counts(sizes: dict) -> tuple[int, int]:
    kinds = sizes["layer_types"]
    n_mamba = sum(1 for k in kinds if k == "mamba")
    return n_mamba, len(kinds) - n_mamba


def param_count(sizes: dict) -> int:
    n_mamba, n_attn = layer_counts(sizes)
    return (n_mamba * mamba_layer_params(sizes)
            + n_attn * attention_layer_params(sizes)
            + sizes["vocab_size"] * sizes["d_model"] + sizes["d_model"])


def weight_bytes(sizes: dict) -> int:
    """Every parameter crosses once an execution: the layers, and the
    embedding as the tied head. (The vectors kept in float32 are 4e-5
    of the bytes and are counted at 2 like the rest.)"""
    return param_count(sizes) * WEIGHT_BYTES


def state_bytes_per_layer_per_lane(sizes: dict) -> int:
    """One Mamba layer's recurrent state of one lane, float32."""
    hp, _, _ = _mamba(sizes)
    return sizes["mamba_d_state"] * hp * STATE_BYTES


def tail_bytes_per_layer_per_lane(sizes: dict) -> int:
    _, cd, _ = _mamba(sizes)
    return (sizes["mamba_d_conv"] - 1) * cd * STATE_BYTES


def kv_row_bytes(sizes: dict) -> int:
    """One position's K and V of one attention layer, float32."""
    return 2 * sizes["num_kv_heads"] * sizes["head_dim"] * STATE_BYTES


def ssm_update_bytes(sizes: dict, live_lanes: float) -> float:
    """ONE call of ``ssm_state_update`` (one layer): each live lane's
    state in and out, and beside it the lane's row and column vectors
    in (decay and input over H*P channels, B and C over N) and y out."""
    hp, _, _ = _mamba(sizes)
    vectors = (3 * hp + 2 * sizes["mamba_d_state"]) * STATE_BYTES
    return live_lanes * (2 * state_bytes_per_layer_per_lane(sizes) + vectors)


def ssm_update_flops(sizes: dict, live_lanes: float) -> float:
    """Per state element: decay multiply, input multiply-add, and the
    multiply-add of the contraction with C."""
    hp, _, _ = _mamba(sizes)
    return 5.0 * live_lanes * sizes["mamba_d_state"] * hp


def token_matmul_flops(sizes: dict, *, head: bool) -> float:
    """One token through every layer's matrices (and the head)."""
    n_mamba, n_attn = layer_counts(sizes)
    d = sizes["d_model"]
    hp, cd, H = _mamba(sizes)
    mamba = d * (hp + cd + H) + hp * d + mlp_params(sizes)
    attn = (2 * d * sizes["num_heads"] * sizes["head_dim"]
            + 2 * d * sizes["num_kv_heads"] * sizes["head_dim"]
            + mlp_params(sizes))
    out = 2.0 * (n_mamba * mamba + n_attn * attn)
    if head:
        out += 2.0 * d * sizes["vocab_size"]
    return out


def decode_step_bytes(sizes: dict, *, live_lanes: float,
                      rows_attended: float) -> float:
    """One decode step: the weights once, each live lane's state and
    tail in and out in every Mamba layer, the K/V rows attended in
    every attention layer (``rows_attended``: the lanes' ``pos + 1``
    summed) and the new row written."""
    n_mamba, n_attn = layer_counts(sizes)
    recurrent = 2 * n_mamba * live_lanes * (
        state_bytes_per_layer_per_lane(sizes)
        + tail_bytes_per_layer_per_lane(sizes))
    kv = n_attn * (rows_attended + live_lanes) * kv_row_bytes(sizes)
    return float(weight_bytes(sizes) + recurrent + kv)


def decode_step_flops(sizes: dict, *, live_lanes: float,
                      rows_attended: float) -> float:
    n_mamba, n_attn = layer_counts(sizes)
    attn = 2.0 * 2.0 * rows_attended * sizes["num_heads"] * sizes["head_dim"]
    return (live_lanes * token_matmul_flops(sizes, head=True)
            + n_mamba * ssm_update_flops(sizes, live_lanes)
            + n_attn * attn)


def scan_flops(sizes: dict, tokens: float, chunk: int) -> float:
    """The chunked scan over ``tokens`` real positions of one layer in
    chunks of ``chunk``. A token: its row of C B^T and of the masked
    product with the chunk's inputs (the causal half of the two
    ``[q, q]`` products), its reading of the carried state and its
    share of the state's update."""
    hp, _, _ = _mamba(sizes)
    N = sizes["mamba_d_state"]
    q = min(float(chunk), max(tokens, 1.0))
    return tokens * (q * N + q * hp + 4.0 * N * hp)


def prefill_chunk_bytes(sizes: dict, *, tokens: float, start: float) -> float:
    """One prefill chunk of ``tokens`` real positions from position
    ``start``: the weights once (the head's share only where it owes a
    token; counted always, an upper bound of 6%), the lane's state and
    tail in and out, the K/V rows before the chunk read and the chunk's
    written."""
    n_mamba, n_attn = layer_counts(sizes)
    recurrent = 2 * n_mamba * (state_bytes_per_layer_per_lane(sizes)
                               + tail_bytes_per_layer_per_lane(sizes))
    kv = n_attn * (start + 2 * tokens) * kv_row_bytes(sizes)
    return float(weight_bytes(sizes) + recurrent + kv)


def prefill_chunk_flops(sizes: dict, *, tokens: float, start: float) -> float:
    n_mamba, n_attn = layer_counts(sizes)
    attn = 2.0 * 2.0 * tokens * (start + tokens / 2.0) * (
        sizes["num_heads"] * sizes["head_dim"])
    return (tokens * token_matmul_flops(sizes, head=False)
            + 2.0 * sizes["d_model"] * sizes["vocab_size"]  # one token's head
            + n_mamba * scan_flops(sizes, tokens, sizes["mamba_chunk_size"])
            + n_attn * attn)
