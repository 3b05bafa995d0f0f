"""``flops.py`` goldens and the table of peaks."""

import pytest

from benchmarks.harness import flops, peaks

TRAIN = dict(vocab_size=50257, seq_len=2048, d_model=2048, depth=8)


def test_train_flops_per_token_golden():
    assert flops.lm_train_flops_per_token(**TRAIN) == 3_234_803_712


def test_agrees_with_the_programs_arithmetic_today():
    from ddp_tpu.obs.goodput import lm_train_flops_per_token

    for depth in (8, 24):
        assert flops.lm_train_flops_per_token(
            **{**TRAIN, "depth": depth}
        ) == lm_train_flops_per_token(
            vocab_size=50257, total_len=2048, d_model=2048, depth=depth,
            num_heads=16,
        )


def test_causal_attention_is_half_the_square():
    d, t = 2048, 2048
    full_square = 2.0 * 2.0 * t * d  # QK^T and PV against all T keys
    assert flops.attention_fwd_flops_per_token(d, t) == full_square / 2


def test_attention_train_flops_count_no_recomputation():
    # forward once, backward twice the forward: 3x, not the 4x or 5x a
    # kernel that recomputes the scores actually executes.
    per_tok = flops.attention_train_flops_per_token(
        seq_len=2048, d_model=2048, depth=8
    )
    assert per_tok == 3 * 8 * flops.attention_fwd_flops_per_token(2048, 2048)
    assert per_tok * 8192 == pytest.approx(1.6493e12, rel=1e-4)


@pytest.mark.parametrize("depth,params", [(8, 509_990_912),
                                          (24, 1_315_723_264)])
def test_param_count(depth, params):
    assert flops.lm_param_count(**{**TRAIN, "depth": depth}) == params


@pytest.mark.parametrize("kind", ["TPU v5 lite", "TPU v5e"])
def test_v5e_peaks_and_their_source(kind):
    p = peaks.peak_for(kind)
    assert p.bf16_flops_per_s == 197e12
    assert p.hbm_bytes_per_s == 819e9
    assert p.ici_bytes_per_s == 200e9  # 1,600 Gbit/s
    assert "Google Cloud" in p.source and "TPU v5e" in p.source


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", "", "TPU v5"])
def test_unlisted_kind_is_an_error(kind):
    with pytest.raises(KeyError, match="no published peak"):
        peaks.peak_for(kind)
