"""Operations and bytes the SambaY forward NEEDS, from shapes and the
program's counters alone (multiply-add = 2).

The rules that keep a share of a roofline honest. Weights are counted
once a program execution, and only the layers that execution runs: a
decode step all of them, a prefill chunk the self-decoder, the
cross-decoder and the head only where the chunk samples. Recurrent
state is counted for LIVE lanes only, once in and once out a step. K/V
rows are counted as attended, once a READING layer: a window layer's
``min(pos + 1, W)`` ring rows, the ``pos + 1`` shared rows once for the
full layer and once for each cross-attention layer (the engine's
``kv_ring_rows_attended_total`` and ``kv_shared_rows_attended_total``
count exactly these). Padding is never counted: a chunk's operations
are those of its real positions.
"""

from __future__ import annotations

WEIGHT_BYTES = 2  # bfloat16 matrices, as stored
STATE_BYTES = 4  # float32 state, tail and K/V

SELF = ("mamba", "window", "full")
CROSS = ("gmu", "cross")


def counts(sizes: dict) -> dict[str, int]:
    kinds = list(sizes["layer_types"])
    return {k: kinds.count(k) for k in SELF + CROSS}


def mlp_params(sizes: dict) -> int:
    """The gated MLP and the layer's two LayerNorms."""
    return (3 * sizes["d_model"] * sizes["mlp_intermediate"]
            + 4 * sizes["d_model"])


def mixer_params(sizes: dict, kind: str) -> int:
    d, C, N = sizes["d_model"], sizes["mamba_d_inner"], sizes["mamba_d_state"]
    R, K = sizes["mamba_dt_rank"], sizes["mamba_d_conv"]
    H, Hkv, Dh = sizes["num_heads"], sizes["num_kv_heads"], sizes["head_dim"]
    if kind == "mamba":
        return (d * 2 * C + K * C + C + C * (R + 2 * N) + R * C + C
                + N * C + C + C * d)
    if kind == "gmu":
        return 2 * d * C
    q_out = H * Dh if kind == "cross" else (H + 2 * Hkv) * Dh
    return (d * q_out + q_out + H * Dh * d + d  # projections and biases
            + 4 * Dh + 2 * Dh)  # the four vectors, the sub-layer norm


def layer_params(sizes: dict, kind: str) -> int:
    return mixer_params(sizes, kind) + mlp_params(sizes)


def head_params(sizes: dict) -> int:
    """The tied embedding and the final norm."""
    return sizes["vocab_size"] * sizes["d_model"] + 2 * sizes["d_model"]


def param_count(sizes: dict, kinds: tuple = SELF + CROSS,
                head: bool = True) -> int:
    n = counts(sizes)
    return (sum(n[k] * layer_params(sizes, k) for k in kinds)
            + (head_params(sizes) if head else 0))


def weight_bytes(sizes: dict, kinds: tuple = SELF + CROSS,
                 head: bool = True) -> int:
    """Every parameter of the named layers crosses once an execution.
    (The vectors kept in float32 are 1e-4 of the bytes and are counted
    at 2 like the rest.)"""
    return param_count(sizes, kinds, head) * WEIGHT_BYTES


def kv_row_bytes(sizes: dict) -> int:
    """One position's K and V of one layer, float32."""
    return 2 * sizes["num_kv_heads"] * sizes["head_dim"] * STATE_BYTES


def state_bytes_per_layer_per_lane(sizes: dict) -> int:
    return sizes["mamba_d_state"] * sizes["mamba_d_inner"] * STATE_BYTES


def tail_bytes_per_layer_per_lane(sizes: dict) -> int:
    return (sizes["mamba_d_conv"] - 1) * sizes["mamba_d_inner"] * STATE_BYTES


def lane_bytes(sizes: dict, cache_length: int) -> dict[str, int]:
    """What one lane holds, by kind of state."""
    n = counts(sizes)
    return {
        "ring": n["window"] * sizes["sliding_window"] * kv_row_bytes(sizes),
        "shared": cache_length * kv_row_bytes(sizes),
        "state": n["mamba"] * (state_bytes_per_layer_per_lane(sizes)
                               + tail_bytes_per_layer_per_lane(sizes)),
    }


# ---- the kernels ---------------------------------------------------------


def ssm_update_bytes(sizes: dict, live_lanes: float) -> float:
    """ONE call of ``selective_state_update`` (one layer): each live
    lane's state in and out and its vectors (``dt``, ``dt xs`` in and
    ``y`` out over C channels, B and C over N), and ``A`` once."""
    C, N = sizes["mamba_d_inner"], sizes["mamba_d_state"]
    vectors = (3 * C + 2 * N) * STATE_BYTES
    return (live_lanes * (2 * state_bytes_per_layer_per_lane(sizes) + vectors)
            + N * C * STATE_BYTES)


def ssm_update_flops(sizes: dict, live_lanes: float) -> float:
    """Per state element: the decay's product and exponential, the
    decay multiply, the input multiply-add, the contraction with C."""
    return 7.0 * live_lanes * sizes["mamba_d_state"] * sizes["mamba_d_inner"]


def attn_row_flops(sizes: dict) -> float:
    """One attended row of one reading layer: every query head's dot
    with its key (Dh) and every map's share of the 2 Dh wide value."""
    return 2.0 * sizes["num_heads"] * 3 * sizes["head_dim"]


def decode_attn_bytes(sizes: dict, rows: float) -> float:
    """The decode-attention calls over ``rows`` attended rows (ring and
    shared, summed over reading layers)."""
    return rows * kv_row_bytes(sizes)


def scan_bytes(sizes: dict, tokens: float) -> float:
    """ONE call of ``selective_scan`` (one layer, one chunk): ``xs``,
    ``dt`` in and ``y`` out over C channels a position, B and C over N,
    the state in and out and ``A``."""
    C, N = sizes["mamba_d_inner"], sizes["mamba_d_state"]
    return STATE_BYTES * (tokens * (3 * C + 2 * N) + 3 * N * C)


def scan_flops(sizes: dict, tokens: float) -> float:
    return 7.0 * tokens * sizes["mamba_d_state"] * sizes["mamba_d_inner"]


# ---- the programs --------------------------------------------------------


def token_matmul_flops(sizes: dict, kinds: tuple, *, head: bool) -> float:
    """One token through the matrices of the named layers."""
    d = sizes["d_model"]
    n = counts(sizes)
    vectors = {  # parameters that are no matrix's
        "mamba": sizes["mamba_d_inner"] * (
            sizes["mamba_d_conv"] + 3 + sizes["mamba_d_state"]),
        "gmu": 0,
    }
    out = 0.0
    for k in kinds:
        mixer = mixer_params(sizes, k) - vectors.get(k, 0)
        out += 2.0 * n[k] * (mixer + 3 * d * sizes["mlp_intermediate"])
    return out + (2.0 * d * sizes["vocab_size"] if head else 0.0)


def decode_step_bytes(sizes: dict, *, live_lanes: float, ring_rows: float,
                      shared_rows: float) -> float:
    """One decode step: the weights once, each live lane's state and
    tail in and out in every Mamba layer, the K/V rows attended (as the
    engine counts them, over reading layers) and the rows written (one
    a live lane a window layer and the full layer)."""
    n = counts(sizes)
    recurrent = 2 * n["mamba"] * live_lanes * (
        state_bytes_per_layer_per_lane(sizes)
        + tail_bytes_per_layer_per_lane(sizes))
    kv = (ring_rows + shared_rows + live_lanes * (n["window"] + 1)
          ) * kv_row_bytes(sizes)
    return float(weight_bytes(sizes) + recurrent + kv)


def decode_step_flops(sizes: dict, *, live_lanes: float, ring_rows: float,
                      shared_rows: float) -> float:
    n = counts(sizes)
    return (live_lanes * token_matmul_flops(sizes, SELF + CROSS, head=True)
            + n["mamba"] * ssm_update_flops(sizes, live_lanes)
            + (ring_rows + shared_rows) * attn_row_flops(sizes))


def _chunk_rows(sizes: dict, tokens: float, start: float):
    """Key rows a chunk's queries attend, summed over queries: in a
    window layer, in the full layer."""
    W = sizes["sliding_window"]
    full = tokens * (start + (tokens + 1) / 2.0)
    window = min(full, tokens * float(W))
    return window, full


def prefill_chunk_bytes(sizes: dict, *, tokens: float, start: float,
                        final: bool) -> float:
    """One prefill chunk of ``tokens`` real positions from ``start``:
    the self-decoder's weights once; the lane's state and tail in and
    out; in each window layer the ring's live rows read and the chunk's
    written; the shared rows before the chunk read and the chunk's
    written. A ``final`` chunk adds the cross-decoder's weights and the
    head, and one reading of the ``start + tokens`` shared rows a
    cross-attention layer."""
    n = counts(sizes)
    W = sizes["sliding_window"]
    recurrent = 2 * n["mamba"] * (state_bytes_per_layer_per_lane(sizes)
                                  + tail_bytes_per_layer_per_lane(sizes))
    rows = (n["window"] * (min(start, W) + min(tokens, W))
            + start + tokens)
    out = weight_bytes(sizes, SELF, head=False) + recurrent
    if final:
        out += weight_bytes(sizes, CROSS, head=True)
        rows += n["cross"] * (start + tokens)
    return float(out + rows * kv_row_bytes(sizes))


def prefill_chunk_flops(sizes: dict, *, tokens: float, start: float,
                        final: bool) -> float:
    n = counts(sizes)
    window, full = _chunk_rows(sizes, tokens, start)
    out = (tokens * token_matmul_flops(sizes, SELF, head=False)
           + n["mamba"] * scan_flops(sizes, tokens)
           + (n["window"] * window + full) * attn_row_flops(sizes))
    if final:
        out += (token_matmul_flops(sizes, CROSS, head=True)
                + n["cross"] * (start + tokens) * attn_row_flops(sizes))
    return out
