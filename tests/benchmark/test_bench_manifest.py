"""``BENCHMARK.json`` against the contract's limits on names, units and
shape, against the files it names, and against what it had when each
rule was written. The rules are functions of a root
(``bench_contract.py``): here they run on the checkout, and
``test_bench_extend.py`` runs the same ones on a copy that has been
added to."""

import pytest

import bench_contract as bc


@pytest.mark.parametrize("check", bc.CHECKS, ids=bc.check_id)
def test_the_checkout_keeps_the_contract(check):
    check(bc.mf.ROOT)


# ---- what may be in ``reduced``: counts, never widths ---------------------

MAY_BE_REDUCED = {
    "layers": ["n_layer", "num_hidden_layers", "num_layers",
               "mtp_num_hidden_layers", "num_nextn_predict_layers",
               "num_dense_layers"],
    "experts": ["num_experts", "num_local_experts", "n_routed_experts",
                "moe_num_experts", "zero_expert_num"],
    "heads": ["n_head", "num_attention_heads", "num_key_value_heads",
              "mamba_n_heads", "mamba_num_heads", "index_n_heads",
              "swa_num_key_value_heads", "linear_num_value_heads"],
    "vocabulary": ["vocab_size", "unpadded_vocab_size"],
}
NEVER_REDUCED = {
    "width": [
        "hidden_size", "n_embd", "n_inner", "d_model", "intermediate_size",
        "moe_intermediate_size", "shared_intermediate_size",
        "ffn_hidden_size", "expert_ffn_hidden_size", "head_dim",
        "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
        "kv_lora_rank", "q_lora_rank", "sliding_window", "mamba_d_state",
        "mamba_d_head", "mamba_d_conv", "mamba_expand", "ssm_state_size",
        "mlp_expansion_factor", "num_experts_per_tok",
        "num_experts_per_token", "moe_topk", "moe_top_k", "topk_group",
        "n_group", "num_expert_group", "index_topk",
    ],
    # not placed: a context length, a shared expert (every chip computes
    # it), a period, a count under a name the rule does not read
    None: [
        "n_positions", "max_position_embeddings", "n_shared_experts",
        "num_shared_experts", "global_attn_every_n_layers",
        "attn_layer_period", "first_k_dense_replace", "rope_theta",
        "rms_norm_eps", "transformer_num_blocks", "num_mtp_modules",
    ],
}


@pytest.mark.parametrize("key,meaning", [
    (k, m) for m, keys in MAY_BE_REDUCED.items() for k in keys])
def test_a_count_may_be_reduced(key, meaning):
    assert bc.key_meaning(key) == meaning
    assert bc.may_be_reduced(key)


@pytest.mark.parametrize("key,meaning", [
    (k, m) for m, keys in NEVER_REDUCED.items() for k in keys])
def test_a_width_or_an_unplaced_key_is_never_reduced(key, meaning):
    assert bc.key_meaning(key) == meaning
    assert not bc.may_be_reduced(key)


def test_floors_of_a_reduced_count():
    assert bc.floor_of("num_hidden_layers", 48) == 4
    assert bc.floor_of("num_dense_layers", 3) == 1  # a leading group
    assert bc.floor_of("n_routed_experts", 128) == 8
    assert bc.floor_of("num_key_value_heads", 4) == 1
    assert bc.floor_of("vocab_size", 151936) == 18992
