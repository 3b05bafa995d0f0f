"""The ``glm_dsa`` block (models/glm_dsa.py) at a tiny size on the CPU,
with ``index_topk`` (8) far below the context (48 and more) so that the
selection is LIVE at most positions: the chunk path, the cached decode
and the engine against the benchmark's plain reference
(``benchmarks/reference/glm_dsa_ref.py``: float32, no cache, no
absorption, an explicit per-query mask), logits AND selected sets; the
sigmoid router against a hand case; and the shares of the experts
adding up to the uncut layer."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import glm_dsa_ref as ref
from ddp_tpu.models import glm_dsa as gd
from ddp_tpu.models.generate import init_slot_cache
from ddp_tpu.models.lm import LMSpec
from ddp_tpu.ops import decode as dec
from ddp_tpu.ops import moe
from ddp_tpu.serve.engine import COMPLETE, ServeEngine

K, L, V = 8, 64, 97
SPEC = LMSpec(
    vocab_size=V, total_len=L, d_model=64, depth=3, num_heads=4,
    block="glm_dsa", q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=8,
    qk_rope_head_dim=8, v_head_dim=8, index_n_heads=2, index_head_dim=16,
    index_topk=K, first_k_dense_replace=1, n_routed_experts=16,
    num_experts=4, expert_offset=4, n_shared_experts=1,
    routed_scaling_factor=2.5, moe_top_k=4, moe_intermediate=32,
    mlp_intermediate=64, rms_eps=1e-5, rope_theta=1e6,
)
CFG = dict(
    num_hidden_layers=3, num_attention_heads=4, kv_lora_rank=16,
    qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=8, index_n_heads=2,
    index_head_dim=16, index_topk=K, first_k_dense_replace=1,
    num_experts_per_tok=4, routed_scaling_factor=2.5, norm_topk_prob=True,
    rms_norm_eps=1e-5, rope_theta=1e6, expert_offset=4,
)
# float32 weights and lanes against the float32 reference: what is left
# is the order of the sums (the online softmax, the absorbed products)
TOL = 2e-4


@pytest.fixture(scope="module", autouse=True)
def small_blocks():
    """Blocks of 16 keys and 16 queries, so that a lane of 64 is four
    blocks of a chunk's walk and the reference's query loop runs."""
    old = gd.KEY_BLOCK, ref.Q_BLOCK, gd.INIT_STD
    gd.KEY_BLOCK, ref.Q_BLOCK, gd.INIT_STD = 16, 16, 0.08
    yield
    gd.KEY_BLOCK, ref.Q_BLOCK, gd.INIT_STD = old


@pytest.fixture(scope="module")
def params(small_blocks):
    return gd.init_params(SPEC, seed=3, dtype=jnp.float32)


def _tokens(seed: int, n: int) -> list:
    return np.random.default_rng(seed).integers(0, V, size=n).tolist()


@pytest.fixture(scope="module")
def ref_out(params):
    @functools.lru_cache(maxsize=None)
    def run(seq: tuple):
        toks = jnp.asarray(seq)
        return jax.jit(lambda p: ref.logits(
            p, toks, CFG, at=jnp.arange(len(seq))))(params)

    return lambda seq: run(tuple(seq))


def _sets(rows) -> list:
    return [set(int(r) for r in row if r >= 0) for row in np.asarray(rows)]


# ---- the pieces ----------------------------------------------------------


def test_sigmoid_route_bias_moves_the_choice_and_not_the_weight():
    logits = jnp.log(jnp.asarray([[0.6, 0.5, 0.4, 0.2]]) / (
        1 - jnp.asarray([[0.6, 0.5, 0.4, 0.2]])))  # sigmoids 0.6 0.5 0.4 0.2
    idx, w = moe.route(logits, 2, True, scoring="sigmoid", scale=2.5)
    assert idx.tolist() == [[0, 1]]
    np.testing.assert_allclose(w, [[2.5 * 0.6 / 1.1, 2.5 * 0.5 / 1.1]],
                               rtol=1e-6)
    # a bias lifts expert 3 over experts 1 and 2: it is chosen, and its
    # weight is its OWN score (0.2), renormalised with expert 0's
    bias = jnp.asarray([0.0, 0.0, 0.0, 0.35])
    idx, w = moe.route(logits, 2, True, scoring="sigmoid", bias=bias,
                       scale=2.5)
    assert idx.tolist() == [[0, 3]]
    np.testing.assert_allclose(w, [[2.5 * 0.6 / 0.8, 2.5 * 0.2 / 0.8]],
                               rtol=1e-6)
    idx, w = moe.route(logits, 2, False, scoring="sigmoid", bias=bias)
    np.testing.assert_allclose(w, [[0.6, 0.2]], rtol=1e-6)


def test_softmax_route_is_the_route_of_before():
    logits = jax.random.normal(jax.random.key(0), (5, 16))
    idx, w = moe.route(logits, 4)
    p = jax.nn.softmax(logits, -1)
    tw, ti = jax.lax.top_k(p, 4)
    assert (idx == ti).all()
    np.testing.assert_allclose(w, tw / tw.sum(-1, keepdims=True), rtol=1e-6)
    with pytest.raises(ValueError, match="unknown router scoring"):
        moe.route(logits, 4, scoring="tanh")


@pytest.mark.parametrize("impl", ["jnp", "pallas"])
def test_the_shares_add_up_to_the_uncut_layer(params, impl):
    """4 shares of 4 of the 16 experts, the shared expert counted once,
    sum to the layer with all 16 held; and each share is the reference's
    for that share."""
    p = params["layers"]["1"]["mlp"]
    key = jax.random.key(5)
    d, f = SPEC.d_model, SPEC.moe_intermediate
    full = {n: 0.08 * jax.random.normal(jax.random.fold_in(key, i),
                                        (16,) + s, jnp.float32)
            for i, (n, s) in enumerate([("gate_proj", (d, f)),
                                        ("up_proj", (d, f)),
                                        ("down_proj", (f, d))])}
    u = jax.random.normal(jax.random.fold_in(key, 9), (24, d), jnp.float32)
    logits = gd.router_logits(p, u)
    kw = dict(top_k=4, scoring="sigmoid", bias=p["gate_bias"], scale=2.5,
              impl=impl)

    def share(first, held):
        w = {n: a[first:first + held] for n, a in full.items()}
        return moe.moe_share_layer(
            u, logits, w["gate_proj"], w["up_proj"], w["down_proj"],
            first=first, **kw)

    whole, stats = share(0, 16)
    assert stats[:2].tolist() == [24 * 4, 24 * 4]
    parts = [share(f0, 4) for f0 in (0, 4, 8, 12)]
    np.testing.assert_allclose(sum(o for o, _ in parts), whole, atol=1e-5)
    assert sum(int(s[1]) for _, s in parts) == 24 * 4
    assert all(int(s[0]) == 24 * 4 for _, s in parts)
    # the reference's share, and its whole
    for (out, _), first in zip(parts, (0, 4, 8, 12)):
        pr = {"gate": p["gate"], "gate_bias": p["gate_bias"],
              "experts": {n: a[first:first + 4] for n, a in full.items()},
              "shared_experts": p["shared_experts"]}
        want = ref.moe(u, pr, {**CFG, "expert_offset": first}, "float32")
        shared = ref.swiglu(u, p["shared_experts"], "float32")
        np.testing.assert_allclose(out, want - shared, atol=1e-5)
    with pytest.raises(ValueError, match="not among the 16"):
        moe.hold_share(jnp.zeros((2, 4), jnp.int32), 14, 4, 16)


def test_a_padded_row_is_not_counted(params):
    p = params["layers"]["1"]["mlp"]
    u = jax.random.normal(jax.random.key(1), (8, SPEC.d_model))
    real = jnp.arange(8) < 5
    _, counted = gd.moe_ffn(SPEC, p, u, real)
    _, every = gd.moe_ffn(SPEC, p, u)
    assert int(counted[0]) == 5 * 4 and int(every[0]) == 8 * 4
    assert 0 <= int(counted[1]) <= int(every[1]) <= 8 * 4


def test_wide_experts_go_through_the_kernel_a_column_block_at_a_time():
    """Where gate and up do not fit VMEM twice over the gate/up call
    walks the tiles once a column block; the accepted shape keeps its
    one-dimensional grid."""
    assert moe.column_block(2048, 768, 2) == 768
    assert moe.column_block(6144, 2048, 2) == 1024
    key = jax.random.key(2)
    E, d, f, N = 3, 32, 512, 20
    wg, wu = (jax.random.normal(jax.random.fold_in(key, i), (E, d, f))
              for i in range(2))
    idx = jax.random.randint(jax.random.fold_in(key, 3), (N, 2), 0, E)
    g = moe.group_rows(idx, E)
    x = jax.random.normal(jax.random.fold_in(key, 4), (N, d))
    rows = jnp.concatenate([x, jnp.zeros((1, d))])[g.row_token]
    want = moe.grouped_matmul_gate_up(rows, wg, wu, g, impl="jnp")
    live = int(g.live_tiles[0]) * moe.TILE_ROWS
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(moe, "column_block", lambda *a, **k: 128)
        got = moe.grouped_matmul_gate_up(rows, wg, wu, g, impl="pallas")
    np.testing.assert_allclose(got[:live], want[:live], rtol=2e-2, atol=2e-2)


def test_select_rows_takes_the_best_and_all_of_a_young_lane():
    scores = jnp.asarray([[5.0, 1.0, 4.0, 4.0, 9.0, 9.0],
                          [3.0, 2.0, 1.0, 0.0, 9.0, 9.0]])
    rows, counted = dec.select_rows(scores, jnp.asarray([3, 1]), 3)
    # lane 0: rows 0..3 live; 5, then the tie at 4 to the lower position
    assert rows[0].tolist() == [0, 2, 3] and counted[0].all()
    # lane 1: two live rows, both selected; the third does not count
    assert rows[1, :2].tolist() == [0, 1]
    assert counted[1].tolist() == [True, True, False]


def test_dsa_rows_counts_what_queries_score_and_attend():
    for first, count in [(0, 1), (0, 20), (5, 3), (7, 1), (8, 4), (30, 9)]:
        scored = sum(t + 1 for t in range(first, first + count))
        sel = sum(min(t + 1, K) for t in range(first, first + count))
        assert gd.dsa_rows(SPEC, first, count) == (3 * scored, 3 * sel)


# ---- the forward, against the reference -----------------------------------


def test_dense_forward_and_its_selection_match_the_reference(params,
                                                             ref_out):
    seq = _tokens(11, 48)
    got, masks = jax.jit(lambda p: gd.dense_logits(
        SPEC, p, jnp.asarray([seq]), want_masks=True))(params)
    want, selected = ref_out(seq)
    assert float(jnp.abs(got[0] - want).max()) < TOL
    for layer in range(SPEC.depth):
        mine = [set(np.flatnonzero(row)) for row in np.asarray(masks[0, layer])]
        assert mine == _sets(selected[layer])
        assert [len(s) for s in mine] == [min(t + 1, K) for t in range(48)]


def test_reference_in_float8_fails_the_tolerance(params, ref_out):
    seq = _tokens(11, 48)
    low, _ = ref.logits(params, jnp.asarray(seq), CFG, "float8")
    assert float(jnp.abs(low - ref_out(seq)[0]).max()) > 50 * TOL


def test_reference_in_bfloat16_lies_between_float32_and_float8(params,
                                                               ref_out):
    """The witness precision (the configuration's own): its operands are
    rounded, so it leaves float32, and by less than the control does
    (by the MEAN: at top-8 of 48 one swapped row moves a logit as far in
    either precision, so the widest distance tells them apart no more)."""
    seq = _tokens(11, 48)
    want = ref_out(seq)[0]
    far = lambda precision: float(jnp.abs(ref.logits(
        params, jnp.asarray(seq), CFG, precision)[0] - want).mean())
    assert 10 * TOL < far("bfloat16") < far("float8") / 3
    with pytest.raises(ValueError, match="unknown precision"):
        ref.logits(params, jnp.asarray(seq), CFG, "float16")


def _lane_state(S: int):
    z = lambda dt: jnp.zeros((S,), dt)
    return (z(jnp.int32), z(jnp.int32), z(jnp.int32), z(jnp.float32),
            jnp.ones((S,), jnp.float32))


@functools.partial(jax.jit, static_argnames="lane_attend")
def _chunk(params, cache, state, slot, buf, start, live, final, *,
           lane_attend):
    return gd.prefill_chunk(
        SPEC, params, cache, *state, slot, buf, start, live, final,
        jnp.int32(0), jnp.float32(0.0), jnp.float32(1.0),
        lane_attend=lane_attend)


@jax.jit
def _step(params, cache, toks):
    return gd.slot_decode_step(SPEC, params, cache, toks)[:2]


def _prefill(params, cache, state, slot: int, prompt, chunk: int = 8,
             min_bucket: int = 4):
    first = None
    for start in range(0, len(prompt), chunk):
        live = min(chunk, len(prompt) - start)
        width = max(min_bucket, 1 << (live - 1).bit_length())
        buf = np.zeros(width, np.int32)
        buf[:live] = prompt[start:start + live]
        out = _chunk(
            params, cache, state, jnp.int32(slot), jnp.asarray(buf),
            jnp.int32(start), jnp.int32(live),
            jnp.asarray(start + live == len(prompt)), lane_attend=start > 0)
        cache, state, first = out[0], out[1:6], out[6]
    return cache, state, int(first)


def _decode_forced(params, cache, slot: int, tokens):
    """Feed ``tokens`` to lane ``slot`` one a step -> (its logits, what
    each step selected ``[steps, layers, K]``, cache)."""
    S = cache.pos.shape[0]
    out, picked = [], []
    for tok in tokens:
        # the other lanes ride along with a token of their own
        logits, cache = _step(
            params, cache, jnp.full((S,), 7, jnp.int32).at[slot].set(tok))
        out.append(logits[slot])
        picked.append(cache.sel[:, slot])
    return jnp.stack(out), jnp.stack(picked), cache


@pytest.mark.parametrize("prompt_len,chunk", [
    (3, 8), (8, 8), (11, 8), (21, 8), (29, 16), (37, 16), (1, 8)])
def test_chunked_prefill_then_cached_decode_match_the_full_forward(
        params, ref_out, prompt_len, chunk):
    """Prefill in several chunks with a padded last bucket (the chunk
    EXPANDS and masks), then decode through the cache (the step SELECTS,
    gathers and ABSORBS) up to 48 positions, in lane 1 of 3: the first
    token, every decoded position's logits and every step's selected
    set against the reference's full forward."""
    seq = _tokens(prompt_len, 48)
    want, selected = ref_out(seq)
    cache, state, first = _prefill(
        params, init_slot_cache(SPEC, 3), _lane_state(3), 1,
        seq[:prompt_len], chunk=chunk)
    assert first == int(jnp.argmax(want[prompt_len - 1]))
    assert int(cache.pos[1]) == prompt_len
    got, picked, cache = _decode_forced(params, cache, 1, seq[prompt_len:])
    assert float(jnp.abs(got - want[prompt_len:]).max()) < TOL
    assert int(cache.pos[1]) == 48
    for j, t in enumerate(range(prompt_len, 48)):
        for layer in range(SPEC.depth):
            assert _sets(picked[j, layer][None]) == _sets(
                selected[layer, t][None]), (t, layer)


@pytest.mark.parametrize("prompt_len,chunk", [(21, 8), (37, 16)])
def test_bfloat16_weights_and_lanes_read_as_the_bfloat16_reference(
        params, prompt_len, chunk):
    """The configuration's own storage, at this size: weights, latent
    and indexer rows in bfloat16 through the same chunks and steps. The
    program then stands where the WITNESS stands (the reference with its
    matmul operands rounded to bfloat16): several times nearer to it
    than to float32, nearly all its selected rows the witness's, and
    far from the float8 control. (At top-8 of 48 one swapped row is an
    eighth of a query's attention, so the distances are means.)"""
    stored = jax.tree.map(lambda a: a.astype(jnp.bfloat16), params)
    assert gd.lane_dtype(stored) == jnp.bfloat16
    seen = jax.tree.map(lambda a: a.astype(jnp.float32), stored)
    seq = _tokens(prompt_len, 48)
    cache, _, _ = _prefill(
        stored, init_slot_cache(SPEC, 3, jnp.bfloat16), _lane_state(3), 1,
        seq[:prompt_len], chunk=chunk)
    assert cache.latent[0].dtype == cache.index_k[0].dtype == jnp.bfloat16
    got, picked, _ = _decode_forced(stored, cache, 1, seq[prompt_len:])

    def against(precision):
        want, selected = ref.logits(seen, jnp.asarray(seq), CFG, precision,
                                    jnp.arange(48))
        shared = [
            len(mine & theirs) / len(mine)
            for j, t in enumerate(range(prompt_len, 48))
            for mine, theirs in zip(_sets(picked[j]), _sets(selected[:, t]))]
        far = jnp.abs(got.astype(jnp.float32) - want[prompt_len:]).mean()
        return float(far), sum(shared) / len(shared)

    (near, rows), (plain, _), (low, low_rows) = (
        against(p) for p in ("bfloat16", "float32", "float8"))
    assert near < 0.02 and 2 * near < plain < low / 3
    assert rows > 0.99 and low_rows < 0.9


def test_absorbed_decode_equals_the_expanded_chunk(params):
    """The same position through both paths of the program: as the last
    row of a continuing chunk (expanded keys and values, a mask) and as
    a decode step (selected rows gathered, ``kv_b_proj`` absorbed)."""
    seq = _tokens(5, 41)
    cache, state, _ = _prefill(params, init_slot_cache(SPEC, 2),
                               _lane_state(2), 0, seq[:40], chunk=8)
    logits, _ = _step(params, cache, jnp.asarray([seq[40], 0]))
    whole = jax.jit(lambda p: gd.dense_logits(SPEC, p, jnp.asarray([seq])))(
        params)
    assert float(jnp.abs(logits[0] - whole[0, 40]).max()) < TOL


def _walk_case(case: str, params):
    """One chunk of 32 queries (two tiles of 16) of 4 heads against lane
    1 of a 64-row buffer (four blocks of 16 keys) -> (chunk_attention's
    arguments after ``p``, the rows of the output that count)."""
    p = params["layers"]["1"]["self_attn"]
    C, start, rows = 32, {"ties": 32, "young": 0, "dead_blocks": 16,
                          "padding": 16}[case], 32
    live = start + C  # the rows the lane holds once the chunk is written
    q_pos = start + jnp.arange(C, dtype=jnp.int32)
    u = jax.random.normal(jax.random.key(5), (L, SPEC.d_model))
    _, _, row, _, ki, _ = gd.attn_inputs(SPEC, p, u, jnp.arange(L))
    q_nope, q_rope, _, qi, _, w = gd.attn_inputs(SPEC, p, u[start:live],
                                                 q_pos)
    if case == "ties":
        # I(t, s) = a_s: five rows above all, then EIGHT equal rows
        # 14..21 over the boundary of blocks 0 and 1 (a query takes the
        # three lowest: 14, 15 and 16), the rest distinct and below
        a = 0.5 + 0.001 * jnp.arange(L)
        a = a.at[:5].set(10.0).at[14:22].set(5.0)
        ki = jnp.zeros_like(ki).at[:, 0].set(a)
        qi = jnp.zeros_like(qi).at[:, 0, 0].set(1.0)
        w = jnp.zeros_like(w).at[:, 0].set(1.0)
    if case == "padding":
        # the chunk holds 21 real positions: the rows its padding wrote
        # are in the lane, above every real query
        rows = 21
        pad = jnp.arange(L)[:, None] >= start + rows
        row, ki = jnp.where(pad, 50.0, row), jnp.where(pad, 50.0, ki)
    above = jnp.arange(L)[:, None] >= live
    fill = jnp.nan if case == "dead_blocks" else 0.0
    lanes = lambda a, width: jnp.stack([
        jnp.full((L, width), 7.0),  # another request's lane
        gd._stored(jnp.where(above, fill, a), width)])
    return (q_nope, q_rope, qi, w, lanes(row, 128), lanes(ki, ki.shape[1]),
            1, live // 16, q_pos), rows


def _plain_chunk(p, q_nope, q_rope, qi, w, latent, index_k, lane, n_blocks,
                 q_pos):
    """Every row of the live blocks expanded, one explicit mask from a
    STABLE sort of the index scores (ties to the lower position), a
    plain softmax; float32 at the highest precision."""
    R, Dr = SPEC.kv_lora_rank, SPEC.qk_rope_head_dim
    n = n_blocks * 16
    lat, ik = latent[lane, :n], index_k[lane, :n]
    scores = np.asarray(dec.index_scores(qi, w, ik[None]))
    mask = np.zeros((len(q_pos), n), bool)
    for c, t in enumerate(np.asarray(q_pos)):
        best = np.argsort(-scores[c, :t + 1], kind="stable")[:K]
        mask[c, best] = True
    w_k, w_v = gd._kv_b(SPEC, p)
    with jax.default_matmul_precision("highest"):
        s = (jnp.einsum("chn,br,rhn->hcb", q_nope, lat[:, :R], w_k)
             + jnp.einsum("chr,br->hcb", q_rope, lat[:, R:R + Dr]))
        pr = jax.nn.softmax(
            jnp.where(mask[None], s * gd.attn_scale(SPEC), -jnp.inf), -1)
        out = jnp.einsum("hcb,br,rhv->chv", pr, lat[:, :R], w_v)
    return out.reshape(len(q_pos), -1), mask


@pytest.mark.parametrize("impl", ["jnp", "pallas"])
@pytest.mark.parametrize("case", ["ties", "young", "dead_blocks", "padding"])
def test_a_chunks_walk_attends_what_a_plain_mask_says_in_both_forms(
        params, monkeypatch, case, impl):
    """The chunk's third pass as the ``jnp`` walk and as the
    ``latent_prefill`` kernel (under the Pallas interpreter), each held
    to the plain form: the SAME mask bit for bit, the outputs to
    float32's order of sums. Equal scores at the threshold go to the
    lower position across a block boundary; a query below ``index_topk``
    takes every row up to itself; the blocks above the live ones hold
    NaN and are never read; rows a chunk's padding wrote are above every
    real query."""
    monkeypatch.setattr(gd, "QUERY_TILE", 16)
    args, rows = _walk_case(case, params)
    p = params["layers"]["1"]["self_attn"]
    assert gd.chunk_form(SPEC, 32, L, 128, impl) == (
        ("kernel", 16) if impl == "pallas" else ("walk", 0))
    out, mask = jax.jit(lambda *a: gd.chunk_attention(
        SPEC, p, *a, want_mask=True, impl=impl))(*args)
    want, plain = _plain_chunk(p, *args)
    n = plain.shape[1]
    assert (np.asarray(mask)[:, :n] == plain).all()
    assert not np.asarray(mask)[:, n:].any()
    assert float(jnp.abs(out - want)[:rows].max()) < TOL
    if case == "ties":  # positions 32..63 all take 0..4 and 14, 15, 16
        assert sorted(np.flatnonzero(plain[0])) == [0, 1, 2, 3, 4, 14, 15, 16]
    if case == "young":
        assert (plain[:K] == np.tri(K, n, dtype=bool)).all()


def test_a_reused_lane_reads_as_a_fresh_one(params, ref_out):
    """A lane that held a longer request: its stale latent AND indexer
    rows lie above the new request's positions and are never selected."""
    old, new = _tokens(1, 60), _tokens(2, 30)
    cache, state, _ = _prefill(params, init_slot_cache(SPEC, 2),
                               _lane_state(2), 0, old, chunk=16)
    cache, state, _ = _prefill(params, cache, state, 0, new[:19], chunk=8)
    got, picked, _ = _decode_forced(params, cache, 0, new[19:])
    want, selected = ref_out(new)
    assert float(jnp.abs(got - want[19:]).max()) < TOL
    assert _sets(picked[-1]) == _sets(selected[:, 29])


# ---- the engine -----------------------------------------------------------


def _engine(params, **knobs):
    kw = dict(slots=3, prefill_chunk=8, min_bucket=4, max_queue=64)
    return ServeEngine(SPEC, params, **{**kw, **knobs})


def _greedy(ref_out, prompt, n):
    seq = list(prompt)
    for _ in range(n):
        seq.append(int(jnp.argmax(ref_out(seq)[0][-1])))
    return seq[len(prompt):]


JOBS = [(13, 6), (3, 9), (16, 4), (21, 12), (8, 1), (5, 20), (30, 5)]


def test_engine_serves_the_reference_greedy_tokens(params, ref_out):
    """Seven requests of mixed lengths through three reused lanes,
    prompts in several chunks while other lanes decode: every answer is
    the reference's greedy loop's, the selection each request recorded
    is the reference's at its last step, nothing compiles after
    warm-up, and the counters count."""
    eng = _engine(params)
    eng.warmup()
    counts = dict(eng.compile_counts())
    assert counts["lane_selection"] == 1
    assert sum(counts.values()) <= eng.compile_budget() == 2 * 2 + 1 + 1
    jobs = [(_tokens(40 + i, p), n) for i, (p, n) in enumerate(JOBS)]
    rids = []
    for prompt, n in jobs:
        adm = eng.submit(prompt, n, record_selection=True)
        assert adm.accepted, adm.reason
        rids.append(adm.request.rid)
        eng.step()
    eng.run()
    assert eng.compile_counts() == counts
    for (prompt, n), rid in zip(jobs, rids):
        c = eng.result(rid)
        assert c.status == COMPLETE
        assert c.tokens == _greedy(ref_out, prompt, n)
        if n == 1:
            assert c.selected_rows is None  # no decode step ran
            continue
        seq = list(prompt) + c.tokens[:-1]
        _, selected = ref_out(seq)
        assert _sets(c.selected_rows) == _sets(selected[:, len(seq) - 1])
    s = eng.stats()
    la = s["latent_attention"]
    scored = selected = 0
    for p, n in JOBS:
        a, b = gd.dsa_rows(SPEC, 0, p + n - 1)
        scored, selected = scored + a, selected + b
    assert la["dsa_rows_scored_total"] == scored
    assert la["dsa_rows_selected_total"] == selected < scored
    # 2 routed layers x top-4: every real prompt position once, and
    # every lane of every decode step (an idle lane rides along)
    assert la["moe_pairs_routed_total"] >= 2 * 4 * sum(
        p + n - 1 for p, n in JOBS)
    assert 0 < la["moe_pairs_held_total"] < la["moe_pairs_routed_total"]
    assert la["latent_bytes_per_slot"] == 3 * L * (128 + 16) * 4
    assert s["decode_path"]["cache_bytes_per_slot"] == (
        la["latent_bytes_per_slot"])
    from ddp_tpu.obs.promtext import render_serve, validate_promtext

    text = render_serve(s)
    validate_promtext(text)
    for name in la:
        assert f"ddp_tpu_serve_{name}" in text
    from ddp_tpu.obs.tracer import SPAN_NUMS

    ring = eng.tracer.ring()
    assert SPAN_NUMS["serve.decode_selected"] == (
        "rows_scored", "rows_selected", "live_lanes")
    recs = [e for e in ring if e[0] == "serve.decode_selected"]
    assert recs and all(e[4][0] >= e[4][1] > 0 for e in recs)
    plans = {e[4][0]: e[4] for e in ring if e[0] == "dsa.plan"}
    assert set(plans) == {"prefill_first", "prefill_chunk", "decode"}
    assert len(SPAN_NUMS["dsa.plan"]) == len(plans["decode"])
    assert plans["decode"][1:4] == (3, L, K)
    assert plans["prefill_chunk"][2:4] == (L, K)


@pytest.mark.parametrize("knobs,match", [
    (dict(page_size=8), "page_size does not apply to the glm_dsa"),
    (dict(kv_dtype="int8"), "kv_dtype does not apply to the glm_dsa"),
    (dict(spec_tokens=2, draft_spec=SPEC, draft_params={}),
     "spec_tokens does not apply to the glm_dsa"),
])
def test_knobs_that_do_not_apply_are_refused_by_name(params, knobs, match):
    with pytest.raises(ValueError, match=match):
        _engine(params, **knobs)


def test_prefix_export_is_refused_by_name(params):
    eng = _engine(params)
    with pytest.raises(ValueError, match="export_prefix does not apply"):
        eng.export_prefix([1, 2, 3])


@pytest.mark.parametrize("change,match", [
    (dict(kv_lora_rank=0), "needs kv_lora_rank"),
    (dict(qk_rope_head_dim=7), "must be even"),
    (dict(expert_offset=14), "a share of them held here"),
    (dict(first_k_dense_replace=5), "outside the 3 layers"),
    (dict(tie_embeddings=True), "untied"),
])
def test_spec_that_names_no_such_model_is_refused(change, match):
    with pytest.raises(ValueError, match=match):
        gd.validate(SPEC._replace(**change))


def test_checkpoint_round_trip_recovers_the_spec(tmp_path, params):
    from ddp_tpu.train.checkpoint import (
        CheckpointManager,
        derive_spec_with_sidecar,
    )

    gd.save_checkpoint(str(tmp_path), SPEC, params)
    mgr = CheckpointManager(str(tmp_path))
    restored, _, epoch = mgr.restore_for_inference(None)
    mgr.close()
    assert epoch == 0
    got = derive_spec_with_sidecar(str(tmp_path), restored,
                                   num_heads_fallback=4)
    assert got == SPEC
    np.testing.assert_array_equal(
        restored["layers"]["2"]["mlp"]["gate_bias"],
        params["layers"]["2"]["mlp"]["gate_bias"])
