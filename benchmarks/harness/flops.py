"""Operations and bytes the algorithm NEEDS, from shapes alone.

A copy of the sound arithmetic in ``ddp_tpu/obs/goodput.py`` (the
program's may change; the yardstick may not). Two rules keep a share of
a peak honest: causal attention counts half the square, and the
backward pass's recomputation is never counted.
"""

from __future__ import annotations


def block_fwd_flops_per_token(
    d_model: int, seq_len: int, *, mlp_ratio: int = 4
) -> float:
    """One pre-LN GPT-2 block, forward, per token (multiply-add = 2)."""
    qkv = 2.0 * d_model * 3 * d_model
    proj = 2.0 * d_model * d_model
    attn = attention_fwd_flops_per_token(d_model, seq_len)
    mlp = 2.0 * 2.0 * mlp_ratio * d_model * d_model
    return qkv + proj + attn + mlp


def attention_fwd_flops_per_token(d_model: int, seq_len: int) -> float:
    """QK^T and PV over a CAUSAL context: a token at position t attends
    t+1 keys, T/2 on average, so half the square. All heads together
    (heads x head_dim = d_model)."""
    return 2.0 * 2.0 * (seq_len / 2.0) * d_model


def lm_train_flops_per_token(
    *, vocab_size: int, seq_len: int, d_model: int, depth: int,
    mlp_ratio: int = 4,
) -> float:
    """Forward + backward (2x forward) per trained token: blocks plus
    the tied logits matmul. No recomputation counted."""
    fwd = depth * block_fwd_flops_per_token(
        d_model, seq_len, mlp_ratio=mlp_ratio
    )
    fwd += 2.0 * d_model * vocab_size
    return 3.0 * fwd


def attention_train_flops_per_token(
    *, seq_len: int, d_model: int, depth: int
) -> float:
    """Needed causal attention work, forward + backward, all layers:
    what the flash forward, dq and dkv kernels exist to do. The
    kernels' own recomputation of the scores in the backward pass is
    NOT counted (it is how they do the work, not the work)."""
    return 3.0 * depth * attention_fwd_flops_per_token(d_model, seq_len)


def lm_param_count(
    *, vocab_size: int, seq_len: int, d_model: int, depth: int,
    mlp_ratio: int = 4,
) -> int:
    """Parameters of the tied-head GPT-2 block stack (biases and
    LayerNorms included)."""
    d, m = d_model, mlp_ratio * d_model
    block = (
        2 * 2 * d  # ln1, ln2
        + d * 3 * d + 3 * d  # qkv
        + d * d + d  # proj
        + d * m + m  # mlp1
        + m * d + d  # mlp2
    )
    return vocab_size * d + seq_len * d + depth * block + 2 * d
