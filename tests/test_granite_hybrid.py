"""The granite_hybrid block (models/granite_hybrid.py, ops/ssm.py, the
serve engine's one-token path over lanes with recurrent state) against
the plain reference ``benchmarks/reference/granite_hybrid_ref.py``, at a
small size on the CPU with seeded random float32 weights.

Tolerances. Program and reference compute the same float32 mathematics
in another order: the chunked scan against a sequential recurrence, a
cache against a full forward, a state stored ``[N, H*P]`` against
``[H, P, N]``. Logits of magnitude ~1 then agree to a few float32
roundings accumulated over six layers: ``TOL`` 2e-5 absolute, about a
hundred times what is seen (2e-7). A lower precision misses by far more
and has to FAIL it: every matmul operand rounded to float8 by ~0.03,
the recurrent state kept in bfloat16 by ~3e-4, a state lost by ~0.1.
"""

from __future__ import annotations

import functools
import json
import os
import signal
import subprocess
import sys
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import granite_hybrid_ref as ref
from ddp_tpu.models import granite_hybrid as gh
from ddp_tpu.models.generate import init_slot_cache
from ddp_tpu.models.lm import LMSpec
from ddp_tpu.ops import ssm
from ddp_tpu.ops.decode import (
    decode_attention_reference,
    flash_decode_attention,
)
from ddp_tpu.serve.engine import COMPLETE, ServeEngine

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOL = 2e-5
VOCAB = 256

SPEC = LMSpec(
    vocab_size=VOCAB, total_len=64, d_model=256, depth=6, num_heads=4,
    num_kv_heads=2, head_dim=64, block=gh.BLOCK,
    layer_types=("mamba", "mamba", "attention") * 2, mamba_n_heads=4,
    mamba_d_head=16, mamba_d_state=16, mamba_chunk_size=8,
    mlp_intermediate=128, embedding_multiplier=12.0,
    attention_multiplier=1 / 64, residual_multiplier=0.22,
    logits_scaling=8.0, tie_embeddings=True, position_embedding="nope",
    rms_eps=1e-5,
)
CFG = dict(
    num_attention_heads=4, num_key_value_heads=2, mamba_n_heads=4,
    mamba_d_head=16, mamba_d_state=16, mamba_n_groups=1,
    embedding_multiplier=12.0, attention_multiplier=1 / 64,
    residual_multiplier=0.22, logits_scaling=8.0, rms_norm_eps=1e-5,
)


@pytest.fixture(scope="module")
def params():
    """Float32 weights. The matrices are scaled up from the family's
    0.02 towards a gain near one at these widths (0.02 * sqrt(2048) at
    the published width), and the recurrence is given longer steps, slower
    decays and a smaller skip than Mamba-2's initialisation (16 state
    dimensions carry less than 128), so that the STATE reaches the
    logits: zeroing it moves them by ~0.1."""
    tree = gh.init_params(SPEC, seed=3, dtype=jnp.float32)
    tree = jax.tree.map(lambda a: a * 6.0 if a.ndim == 2 and min(a.shape) > 4
                        else a, tree)
    for layer in tree["layers"].values():
        if "mamba" in layer:
            m = layer["mamba"]
            m.update(dt_bias=m["dt_bias"] + 4.0, A_log=m["A_log"] - 2.0,
                     D=m["D"] * 0.1)
    return tree


def _tokens(seed: int, n: int) -> list[int]:
    return np.random.default_rng(seed).integers(0, VOCAB, n).tolist()


@pytest.fixture(scope="module")
def ref_logits(params):
    """The reference's logits of a padded batch of sequences, one
    compiled program (causal, so padding after a sequence is inert)."""
    fn = jax.jit(lambda t: ref.logits(params, t, CFG))

    def logits(seq: list[int], T: int = 48):
        return fn(jnp.asarray([seq + [0] * (T - len(seq))]))[0, : len(seq)]

    return logits


# ---- the whole forward ------------------------------------------------------


def test_dense_forward_matches_reference(params, ref_logits):
    seq = _tokens(0, 37)
    want = ref_logits(seq)
    got = jax.jit(lambda t: gh.dense_logits(SPEC, params, t))(
        jnp.asarray([seq]))[0]
    assert float(jnp.abs(want).max()) > 1.0  # the logits spread
    assert float(jnp.abs(got - want).max()) < TOL


def test_reference_in_float8_fails_the_tolerance(params, ref_logits):
    seq = _tokens(0, 37)
    low = jax.jit(lambda t: ref.logits(params, t, CFG, "float8"))(
        jnp.asarray([seq]))[0]
    assert float(jnp.abs(low - ref_logits(seq)).max()) > 100 * TOL


# ---- operators ----------------------------------------------------------------


def _scan_inputs(seed: int, T: int, H=4, P=16, N=16):
    k = jax.random.split(jax.random.key(seed), 6)
    return dict(
        x=jax.random.normal(k[0], (T, H, P)),
        dt=jax.nn.softplus(jax.random.normal(k[1], (T, H)) - 1.0),
        A=-jnp.exp(jax.random.uniform(k[2], (H,), minval=0.0, maxval=2.5)),
        B=jax.random.normal(k[3], (T, N)), C=jax.random.normal(k[4], (T, N)),
    ), jax.random.normal(k[5], (N, H * P))


@jax.jit
def _sequential(inp, state):
    """The recurrence one token at a time, by the decode step's own
    reference update (one lane, no ``D`` term)."""
    buf, ys = state[None, None], []
    for t in range(inp["x"].shape[0]):
        one = lambda a: a[t][None]
        buf, y = ssm.state_update_reference(
            buf, 0, one(inp["x"]), one(inp["dt"]), inp["A"], one(inp["B"]),
            one(inp["C"]), jnp.zeros_like(inp["A"]), jnp.ones((1,), bool))
        ys.append(y[0])
    return jnp.stack(ys), buf[0, 0]


@pytest.mark.parametrize("T,chunk", [(16, 8), (13, 8), (5, 8)])
def test_chunked_scan_matches_the_recurrence_from_a_carried_state(T, chunk):
    """From a NON-zero state, whole chunks and a ragged last one. The
    scan's einsums run float32 at ``highest`` here, so 2e-5 on values
    of magnitude ~10 is float32 reassociation; bfloat16 operands would
    miss by ~0.05."""
    inp, state = _scan_inputs(T, T)
    y, s = ssm.ssd_scan(**inp, state=state, chunk=chunk)
    want_y, want_s = _sequential(inp, state)
    assert float(jnp.abs(y - want_y).max()) < 2e-5
    assert float(jnp.abs(s - want_s).max()) < 2e-5
    # and the reference's own sequential scan says the same from zero
    y0, _ = ssm.ssd_scan(**inp, state=jnp.zeros_like(state), chunk=chunk)
    r = ref.recurrence(inp["x"][None], inp["dt"][None], inp["A"],
                       inp["B"][None], inp["C"][None],
                       jnp.zeros_like(inp["A"]))[0]
    assert float(jnp.abs(y0 - r).max()) < 2e-5


def test_scan_leaves_the_state_alone_where_dt_is_zero():
    """Padding: positions with ``dt`` 0 neither move the state nor see
    anything but it."""
    inp, state = _scan_inputs(7, 12)
    real = 7
    padded = dict(inp, dt=inp["dt"].at[real:].set(0.0))
    _, s = ssm.ssd_scan(**padded, state=state, chunk=8)
    cut = {k: v[:real] if k != "A" else v for k, v in inp.items()}
    _, want = ssm.ssd_scan(**cut, state=state, chunk=8)
    assert float(jnp.abs(s - want).max()) < 1e-6


def test_convolution_by_run_and_by_step_agree():
    k = jax.random.split(jax.random.key(5), 4)
    x = jax.random.normal(k[0], (11, 48))
    tail0 = jax.random.normal(k[1], (3, 48))
    w, b = jax.random.normal(k[2], (4, 48)), jax.random.normal(k[3], (48,))
    run = ssm.causal_conv(x, tail0, w, b)
    tail, steps = tail0[None], []
    for t in range(11):
        y, tail = ssm.conv_step(x[t][None], tail, w, b)
        steps.append(y[0])
    assert float(jnp.abs(run - jnp.stack(steps)).max()) < 1e-5
    for length in (11, 7, 2, 0):  # the tail cut before the padding
        want = jnp.concatenate([tail0, x[:length]])[-3:]
        assert jnp.array_equal(ssm.conv_tail(x, tail0, length), want)


@pytest.mark.parametrize("live", [(1, 0, 1, 1, 0), (0, 0, 0, 0, 0),
                                  (1, 1, 1, 1, 1)])
@pytest.mark.parametrize("tile", [None, 128])
def test_state_update_kernel_matches_jnp_and_skips_idle_lanes(live, tile):
    """``ssm_state_update`` in interpret mode against ``jnp``: live
    lanes agree to float32 rounding, idle lanes' state comes back BIT
    for bit (also with no lane live), other layers untouched."""
    S, H, P, N, layers = 5, 4, 64, 16, 3
    k = jax.random.split(jax.random.key(9), 7)
    state = jax.random.normal(k[0], (layers, S, N, H * P))
    args = (jax.random.normal(k[1], (S, H, P)),
            jax.nn.softplus(jax.random.normal(k[2], (S, H))),
            -jnp.exp(jax.random.normal(k[3], (H,))),
            jax.random.normal(k[4], (S, N)), jax.random.normal(k[5], (S, N)),
            jax.random.normal(k[6], (H,)))
    live = jnp.asarray(live, bool)
    want_s, want_y = ssm.state_update_reference(state, 1, *args, live)
    got_s, got_y = ssm.ssm_state_update(state, 1, *args, live, impl="pallas",
                                        tile=tile, interpret=True)
    assert float(jnp.abs(got_s - want_s).max()) < 1e-5
    assert float(jnp.abs(got_y - want_y).max()) < 1e-4
    idle = ~np.asarray(live)
    assert jnp.array_equal(got_s[1][idle], state[1][idle])
    assert jnp.array_equal(got_s[0], state[0])
    assert jnp.array_equal(got_s[2], state[2])
    assert not np.asarray(got_y)[idle].any()


@pytest.mark.parametrize("S,H,Hkv,Dh,L", [
    (3, 8, 2, 64, 256),  # this model's: 4 queries a kv head of 64
    (2, 4, 4, 128, 256),  # one query a kv head (the all-heads kernel)
])
def test_flash_decode_takes_the_softmax_scale(S, H, Hkv, Dh, L):
    k = jax.random.split(jax.random.key(2), 3)
    q = jax.random.normal(k[0], (S, H, Dh))
    kc = jax.random.normal(k[1], (2, S, L, Hkv, Dh))
    vc = jax.random.normal(k[2], (2, S, L, Hkv, Dh))
    pos = jnp.asarray([5, 200, 131][:S], jnp.int32)
    want = decode_attention_reference(q, kc[1], vc[1], pos, scale=1 / 64)
    got = flash_decode_attention(q, kc, vc, pos, layer=1, scale=1 / 64,
                                 interpret=True)
    assert float(jnp.abs(got - want).max()) < 2e-5
    # another scale is another answer
    other = decode_attention_reference(q, kc[1], vc[1], pos)
    assert float(jnp.abs(other - want).max()) > 1e-2


@pytest.mark.parametrize("S,H,Hkv,Dh,L", [
    (3, 32, 8, 64, 256),  # this model's: two kv heads to a 128-lane group
    (2, 8, 4, 32, 128),  # four to a group, two queries a kv head
])
def test_flash_decode_over_heads_packed_on_lanes(S, H, Hkv, Dh, L):
    """Rows stored ``[depth, S, L, H_kv * Dh]``: the kernel's
    block-diagonal queries give each head its own keys' scores, to
    float32 rounding of the reference on the rows viewed as heads."""
    from ddp_tpu.ops.decode import packed_decode_attention, read_lane

    k = jax.random.split(jax.random.key(6), 3)
    q = jax.random.normal(k[0], (S, H, Dh))
    kc = jax.random.normal(k[1], (2, S, L, Hkv * Dh))
    vc = jax.random.normal(k[2], (2, S, L, Hkv * Dh))
    pos = jnp.asarray([5, L - 1, 131][:S], jnp.int32)
    heads = lambda c: c[1].reshape(S, L, Hkv, Dh)
    want = decode_attention_reference(q, heads(kc), heads(vc), pos,
                                      scale=1 / 64)
    for impl in ("reference", "flash"):
        got = packed_decode_attention(q, kc, vc, pos, layer=1, impl=impl,
                                      scale=1 / 64, interpret=True)
        assert float(jnp.abs(got - want).max()) < 2e-5, impl
    # one lane's rows, by the kernel and by the slice
    for impl in ("pallas", "jnp"):
        lane = read_lane(kc, 1, jnp.int32(S - 1), impl=impl, interpret=True)
        assert jnp.array_equal(lane, kc[1, S - 1]), impl
    with pytest.raises(ValueError, match="whole 128-lane groups"):
        packed_decode_attention(q[:, :2, :48], kc[..., :96], vc[..., :96],
                                pos, impl="flash")


@pytest.mark.parametrize("S,H,Hkv,Dh,L", [
    (8, 16, 16, 128, 256),  # the GPT-2 cell's heads
    (4, 128, 4, 128, 128),  # the block-diffusion cell's: 32 rows a kv head
])
def test_default_scale_is_the_kernel_of_before(S, H, Hkv, Dh, L):
    """No scale given is ``Dh ** -0.5`` bit for bit, kernel and
    reference: the accepted cells' calls trace as they did."""
    k = jax.random.split(jax.random.key(4), 3)
    q = jax.random.normal(k[0], (S, H, Dh))
    kc = jax.random.normal(k[1], (1, S, L, Hkv, Dh))
    vc = jax.random.normal(k[2], (1, S, L, Hkv, Dh))
    pos = jnp.arange(S, dtype=jnp.int32) * 29 % L
    for fn, kv in ((flash_decode_attention, (kc, vc)),
                   (decode_attention_reference, (kc[0], vc[0]))):
        kw = {"interpret": True} if fn is flash_decode_attention else {}
        assert jnp.array_equal(fn(q, *kv, pos, **kw),
                               fn(q, *kv, pos, scale=Dh ** -0.5, **kw))


# ---- through the cache --------------------------------------------------------


def _lane_state(S: int):
    z = lambda dt: jnp.zeros((S,), dt)
    return (z(jnp.int32), z(jnp.int32), z(jnp.int32), z(jnp.float32),
            jnp.ones((S,), jnp.float32))


@functools.partial(jax.jit, static_argnames="lane_attend")
def _chunk(params, cache, state, slot, buf, start, live, final, *,
           lane_attend):
    return gh.prefill_chunk(
        SPEC, params, cache, *state, slot, buf, start, live, final,
        jnp.int32(0), jnp.float32(0.0), jnp.float32(1.0),
        lane_attend=lane_attend)


@functools.partial(jax.jit, static_argnames="impl")
def _step(params, cache, toks, *, impl):
    return gh.slot_decode_step(SPEC, params, cache, toks, ssm_impl=impl)


def _prefill(params, cache, state, slot: int, prompt, chunk: int = 8,
             min_bucket: int = 4):
    """Chunked prefill as the engine plans it: full chunks of ``chunk``,
    the last one in the smallest power-of-two bucket that holds it."""
    first = None
    for start in range(0, len(prompt), chunk):
        live = min(chunk, len(prompt) - start)
        width = max(min_bucket, 1 << (live - 1).bit_length())
        buf = np.zeros(width, np.int32)
        buf[:live] = prompt[start:start + live]
        out = _chunk(
            params, cache, state, jnp.int32(slot), jnp.asarray(buf),
            jnp.int32(start), jnp.int32(live),
            jnp.asarray(start + live == len(prompt)), lane_attend=start > 0,
        )
        cache, state, first = out[0], out[1:6], out[6]
    return cache, state, int(first)


def _decode_forced(params, cache, slot: int, tokens, impl: str = "jnp",
                   between=lambda cache: cache):
    """Feed ``tokens`` to lane ``slot`` one a step -> its logits."""
    S = cache.pos.shape[0]
    cache = cache._replace(live=jnp.zeros((S,), bool).at[slot].set(True))
    out = []
    for tok in tokens:
        logits, cache = _step(
            params, between(cache),
            jnp.zeros((S,), jnp.int32).at[slot].set(tok), impl=impl)
        out.append(logits[slot])
    return jnp.stack(out), cache


@pytest.mark.parametrize("prompt_len", [3, 8, 13, 16, 21])
def test_chunked_prefill_then_cached_decode_match_the_full_forward(
        params, ref_logits, prompt_len):
    """Prompts shorter than the smallest bucket, equal to a bucket, a
    multiple of the chunk and neither: the first token is the
    reference's choice, and every decoded position's LOGITS are the
    reference's full forward's."""
    seq = _tokens(prompt_len, prompt_len + 9)
    want = ref_logits(seq)
    cache, state, first = _prefill(params, init_slot_cache(SPEC, 3),
                                   _lane_state(3), 1, seq[:prompt_len])
    assert first == int(jnp.argmax(want[prompt_len - 1]))
    assert int(cache.pos[1]) == prompt_len
    got, _ = _decode_forced(params, cache, 1, seq[prompt_len:],
                            impl="pallas" if prompt_len == 13 else "jnp")
    assert float(jnp.abs(got - want[prompt_len:]).max()) < TOL


def test_bfloat16_state_fails_the_tolerance(params, ref_logits):
    """A state stored in bfloat16 is rounded after every step."""
    seq = _tokens(13, 22)
    cache, _, _ = _prefill(params, init_slot_cache(SPEC, 3), _lane_state(3),
                           1, seq[:13])
    low = lambda c: c._replace(
        ssm=c.ssm.astype(jnp.bfloat16).astype(jnp.float32))
    got, _ = _decode_forced(params, cache, 1, seq[13:], between=low)
    assert float(jnp.abs(got - ref_logits(seq)[13:]).max()) > 5 * TOL


def test_a_reused_lane_reads_as_a_fresh_one(params):
    """The first chunk resets the lane's state inside its own program:
    a second request in a lane another used gives bit for bit what it
    gives in a lane nobody used."""
    a, b = _tokens(1, 19), _tokens(2, 15)
    used, state, _ = _prefill(params, init_slot_cache(SPEC, 3),
                              _lane_state(3), 2, a[:11])
    _, used = _decode_forced(params, used, 2, a[11:])
    runs = []
    for cache in (used, init_slot_cache(SPEC, 3)):
        cache, _, first = _prefill(params, cache, _lane_state(3), 2, b[:10])
        logits, cache = _decode_forced(params, cache, 2, b[10:])
        runs.append((first, logits, cache.ssm[:, 2], cache.conv[:, 2]))
    assert runs[0][0] == runs[1][0]
    for x, y in zip(runs[0][1:], runs[1][1:]):
        assert jnp.array_equal(x, y)


@pytest.mark.parametrize("impl", ["jnp", "pallas"])
def test_a_step_leaves_idle_lanes_bit_for_bit(params, impl):
    """Lane 0 is between two chunks of its prompt, lane 2 is free, lane
    1 decodes: after a step the others' state, tail and position are
    what they were, and lane 0's second chunk then gives what it gives
    with no step in between."""
    p0 = _tokens(5, 16)
    cache, state, _ = _prefill(params, init_slot_cache(SPEC, 3),
                               _lane_state(3), 1, _tokens(6, 9))
    chunk = lambda c, s, start: _chunk(
        params, c, s, jnp.int32(0), jnp.asarray(p0[start:start + 8]),
        jnp.int32(start), jnp.int32(8), jnp.asarray(start == 8),
        lane_attend=start > 0)
    out = chunk(cache, state, 0)
    before, state = out[0], out[1:6]
    _, after = _decode_forced(params, before, 1, [7, 8], impl=impl)
    for lane in (0, 2):
        assert jnp.array_equal(after.ssm[:, lane], before.ssm[:, lane])
        assert jnp.array_equal(after.conv[:, lane], before.conv[:, lane])
        assert int(after.pos[lane]) == int(before.pos[lane])
    assert not jnp.array_equal(after.ssm[:, 1], before.ssm[:, 1])
    assert int(after.pos[1]) == int(before.pos[1]) + 2
    stepped, plain = chunk(after, state, 8), chunk(before, state, 8)
    assert int(stepped[6]) == int(plain[6])
    assert jnp.array_equal(stepped[0].ssm[:, 0], plain[0].ssm[:, 0])


# ---- the engine ---------------------------------------------------------------


def _engine(params, **knobs):
    kw = dict(slots=3, prefill_len=40, prefill_chunk=8, min_bucket=4,
              max_queue=16)
    return ServeEngine(SPEC, params, **{**kw, **knobs})


def _greedy(ref_logits, prompt, n):
    seq = list(prompt)
    for _ in range(n):
        seq.append(int(jnp.argmax(ref_logits(seq)[-1])))
    return seq[len(prompt):]


JOBS = [(13, 6), (3, 9), (16, 4), (21, 7), (8, 1), (5, 8), (30, 5)]


@pytest.mark.parametrize("decode_attn", ["reference", "flash"])
def test_engine_serves_the_reference_greedy_tokens(params, ref_logits,
                                                   decode_attn):
    """Seven requests of mixed lengths through three lanes, admitted
    out of step, lanes reused, prompts prefilled in several chunks with
    a padded last bucket while other lanes decode: every answer is the
    reference's greedy loop's, nothing compiles after warmup, and the
    counters count."""
    eng = _engine(params, decode_attn=decode_attn)
    eng.warmup()
    counts = dict(eng.compile_counts())
    assert sum(counts.values()) <= eng.compile_budget() == 2 * 2 + 1
    jobs = [(_tokens(40 + i, p), n) for i, (p, n) in enumerate(JOBS)]
    rids = []
    for prompt, n in jobs:
        adm = eng.submit(prompt, n)
        assert adm.accepted, adm.reason
        rids.append(adm.request.rid)
        eng.step()
    eng.run()
    assert eng.compile_counts() == counts
    for (prompt, n), rid in zip(jobs, rids):
        c = eng.result(rid)
        assert c.status == COMPLETE
        assert c.tokens == _greedy(ref_logits, prompt, n)
    s = eng.stats()
    rs = s["recurrent_state"]
    assert rs["ssm_state_resets_total"] == len(JOBS)
    assert rs["ssm_prefill_tokens_total"] == sum(p for p, _ in JOBS)
    # every token but a request's first comes from a live lane's step
    assert rs["ssm_lane_updates_total"] == sum(n - 1 for _, n in JOBS)
    assert rs["kv_bytes_per_slot"] == 2 * 2 * 64 * 2 * 64 * 4
    assert rs["ssm_state_bytes_per_slot"] == 4 * 4 * (16 * 64 + 3 * 96)
    assert s["decode_path"]["cache_bytes_per_slot"] == (
        rs["kv_bytes_per_slot"] + rs["ssm_state_bytes_per_slot"])
    assert s["kv_rows_attended_total"] > 0
    # /metricsz renders them; a plain model's exposition has none
    from ddp_tpu.obs.promtext import render_serve, validate_promtext

    text = render_serve(s)
    validate_promtext(text)
    for line in ("ddp_tpu_serve_ssm_lane_updates_total 33",
                 "ddp_tpu_serve_ssm_prefill_tokens_total 96",
                 "ddp_tpu_serve_ssm_state_resets_total 7",
                 "ddp_tpu_serve_ssm_state_bytes_per_slot 20992",
                 "ddp_tpu_serve_kv_bytes_per_slot 131072"):
        assert line in text, line
    assert "_ssm_" not in render_serve(
        {k: v for k, v in s.items() if k != "recurrent_state"})


def test_the_kernel_leaves_its_plan_record():
    from ddp_tpu.obs.tracer import SPAN_NUMS, get_tracer

    assert SPAN_NUMS["ssm.plan"] == ("kernel", "lanes_per_tile",
                                     "heads_per_tile", "state_dtype")
    ssm.ssm_state_update(
        jnp.zeros((1, 1, 16, 64)), 0, jnp.zeros((1, 4, 16)),
        jnp.zeros((1, 4)), -jnp.ones((4,)), jnp.zeros((1, 16)),
        jnp.zeros((1, 16)), jnp.ones((4,)), jnp.ones((1,), bool),
        impl="pallas", interpret=True)
    plans = [e for e in get_tracer().ring() if e[0] == "ssm.plan"]
    assert plans and plans[-1][4] == ("ssm_state_update", 1, 4, "float32")


@pytest.mark.parametrize("knobs,match", [
    (dict(page_size=8), "page_size does not apply to the granite_hybrid"),
    (dict(kv_dtype="int8"), "kv_dtype does not apply to the granite_hybrid"),
    (dict(spec_tokens=2, draft_spec=SPEC, draft_params={}),
     "spec_tokens does not apply to the granite_hybrid"),
])
def test_knobs_that_do_not_apply_are_refused_by_name(params, knobs, match):
    with pytest.raises(ValueError, match=match):
        _engine(params, **knobs)


def test_prefix_export_and_install_are_refused_by_name(params):
    from ddp_tpu.serve.disagg import PageWireError

    eng = _engine(params)
    with pytest.raises(ValueError, match="export_prefix does not apply"):
        eng.export_prefix([1, 2, 3])
    with pytest.raises(PageWireError, match="install_prefix does not apply"):
        eng.install_prefix(None)


@pytest.mark.parametrize("change,match", [
    (dict(layer_types=("mamba",) * 6), "both kinds"),
    (dict(layer_types=("mamba", "attention")), "layer_types"),
    (dict(mamba_n_groups=2), "mamba_n_groups"),
    (dict(position_embedding="rope"), "position_embedding"),
    (dict(block_length=4), "one token a step"),
])
def test_spec_that_names_no_such_model_is_refused(change, match):
    with pytest.raises(ValueError, match=match):
        gh.validate(SPEC._replace(**change))


# ---- from a checkpoint directory ------------------------------------------------


def test_checkpoint_round_trip_recovers_the_spec(tmp_path, params):
    from ddp_tpu.train.checkpoint import (
        CheckpointManager,
        derive_spec_with_sidecar,
    )

    gh.save_checkpoint(str(tmp_path), SPEC, params)
    mgr = CheckpointManager(str(tmp_path))
    restored, _, epoch = mgr.restore_for_inference(None)
    mgr.close()
    assert epoch == 0
    got = derive_spec_with_sidecar(str(tmp_path), restored,
                                   num_heads_fallback=2)
    assert got == SPEC and isinstance(got.layer_types, tuple)
    os.remove(os.path.join(str(tmp_path), "lm_spec.json"))
    with pytest.raises(ValueError, match="total_len"):
        derive_spec_with_sidecar(str(tmp_path), restored,
                                 num_heads_fallback=4)


def test_serve_script_builds_the_engine_from_a_saved_directory(
        tmp_path, params, ref_logits):
    """``scripts/serve.py --checkpoint_dir DIR`` with no flag for the
    model: its spec is its checkpoint's."""
    gh.save_checkpoint(str(tmp_path), SPEC, params)
    proc = subprocess.Popen(
        [sys.executable, os.path.join(REPO, "scripts", "serve.py"),
         "--checkpoint_dir", str(tmp_path), "--slots", "2", "--port", "0",
         "--prefill_chunk", "8"],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True, cwd=REPO,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    try:
        url = json.loads(proc.stdout.readline())["serving"]
        prompt = _tokens(77, 11)
        req = urllib.request.Request(
            url + "/generate",
            data=json.dumps({"prompt_tokens": prompt,
                             "max_new_tokens": 6}).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=180) as resp:
            out = json.loads(resp.read())
        assert out["status"] == "complete"
        assert out["tokens"] == _greedy(ref_logits, prompt, 6)
        with urllib.request.urlopen(url + "/stats", timeout=60) as resp:
            stats = json.loads(resp.read())
        assert stats["recurrent_state"]["ssm_state_resets_total"] == 1
    finally:
        proc.send_signal(signal.SIGTERM)
        try:
            proc.wait(timeout=60)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait()
