"""The sambay block (models/sambay.py, the Mamba-1 operators of
ops/ssm.py, differential decode attention in ops/decode.py, the serve
engine's one-token path over lanes with a ring, shared rows and
recurrent state) against the plain reference
``benchmarks/reference/sambay_ref.py``, at a small size on the CPU with
seeded random float32 weights: width 64, 4 query and 2 kv heads of 16,
Mamba-1 of 128 channels x 4 state indices, WINDOW 8, and the published
layer table at depth 8, ``m w m w | m f g c``, so every kind is there.

Tolerances. Program and reference compute the same float32 mathematics
in another order: a ring and a cache against explicit ``[T, T]`` masks,
paired queries against maps formed head by head, a state stored
``[N, C]`` against ``[C, N]``, a prefill that stops at the full layer
against every layer everywhere. Logits of magnitude ~1 then agree to a
few float32 roundings accumulated over eight layers with a gain above
one: ``TOL`` 5e-5 absolute, five times what is seen (at most 9e-6). A
lower precision misses by far more and has to FAIL it: every matmul
operand rounded to float8 by ~2, the recurrent state kept in bfloat16
by ~0.02, one ring row lost by ~0.7, one shared row by ~0.1.
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

from benchmarks.reference import sambay_ref as ref
from ddp_tpu.models import sambay as sy
from ddp_tpu.models.generate import init_slot_cache
from ddp_tpu.models.lm import LMSpec
from ddp_tpu.ops import ssm
from ddp_tpu.ops.decode import (
    diff_decode_attention,
    diff_decode_attention_reference,
)
from ddp_tpu.serve.engine import COMPLETE, ServeEngine

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOL = 5e-5
VOCAB = 256
W = 8

SPEC = LMSpec(
    vocab_size=VOCAB, total_len=64, d_model=64, depth=8, num_heads=4,
    num_kv_heads=2, head_dim=16, block=sy.BLOCK,
    layer_types=sy.layer_table(8), mamba_d_inner=128, mamba_d_state=4,
    mamba_d_conv=4, mamba_dt_rank=4, mlp_intermediate=96, sliding_window=W,
    layer_norm_eps=1e-5, tie_embeddings=True, position_embedding="nope",
)
CFG = dict(
    num_hidden_layers=8, num_attention_heads=4, num_key_value_heads=2,
    hidden_size=64, sliding_window=W, layer_norm_eps=1e-5, mamba_dt_rank=4,
    mamba_d_state=4,
)


@pytest.fixture(scope="module")
def params():
    """Float32 weights. The matrices are scaled up from the family's
    0.02 towards a gain near one at these widths, and the recurrence is
    given longer steps, so that every mixer (the state, the window, the
    shared rows, the read-out) reaches the logits."""
    tree = sy.init_params(SPEC, seed=3, dtype=jnp.float32)
    tree = jax.tree.map(lambda a: a * 5.0 if a.ndim == 2 and min(a.shape) > 4
                        else a, tree)
    for layer in tree["layers"].values():
        if "mamba" in layer:
            m = layer["mamba"]
            m["dt_proj"]["bias"] = m["dt_proj"]["bias"] + 3.0
            m["x_proj"] = m["x_proj"] * 5.0
    return tree


def _tokens(seed: int, n: int) -> list[int]:
    return np.random.default_rng(seed).integers(0, VOCAB, n).tolist()


@pytest.fixture(scope="module")
def ref_logits(params):
    """The reference's logits of a padded sequence, one compiled
    program (causal, so padding after a sequence is inert)."""
    fn = jax.jit(lambda t: ref.logits(params, t, CFG))

    def logits(seq: list[int], T: int = 64):
        return fn(jnp.asarray([seq + [0] * (T - len(seq))]))[0, : len(seq)]

    return logits


# ---- the model's shape --------------------------------------------------------


def test_layer_table_is_the_published_one():
    assert sy.layer_table(8) == ("mamba", "window", "mamba", "window",
                                 "mamba", "full", "gmu", "cross")
    t = sy.layer_table(32)
    assert [t.count(k) for k in sy.KINDS] == [9, 8, 1, 7, 7]
    assert t[16] == "mamba" and t[17] == "full" and t[15] == "window"
    assert t[18] == "gmu" and t[31] == "cross"
    rows = sy.layer_rows(SPEC)
    assert rows == (("mamba", 0), ("window", 0), ("mamba", 1), ("window", 1),
                    ("mamba", 2), ("full", 0), ("gmu", -1), ("cross", 0))


def test_published_shape_counts_its_parameters():
    """No weights made: ``leaf_shapes`` alone."""
    pub = SPEC._replace(
        vocab_size=200064, d_model=2560, depth=32, num_heads=40,
        num_kv_heads=20, head_dim=64, layer_types=sy.layer_table(32),
        mamba_d_inner=5120, mamba_d_state=16, mamba_dt_rank=160,
        mlp_intermediate=10240, sliding_window=512, total_len=4096)
    sy.validate(pub)
    count = sum(int(np.prod(s)) for s in sy.leaf_shapes(pub).values())
    assert count == 3_852_562_944
    assert sy.lane_bytes(pub) == {
        "ring": 41_943_040, "shared": 41_943_040, "state": 3_502_080}
    assert sy.attended_rows(pub, [100, 600]) == (8 * (100 + 512), 8 * 700)


# ---- the whole forward ----------------------------------------------------------


def test_dense_forward_matches_reference(params, ref_logits):
    seq = _tokens(0, 37)
    want = ref_logits(seq)
    got = jax.jit(lambda t: sy.dense_logits(SPEC, params, t))(
        jnp.asarray([seq]))[0]
    assert float(jnp.abs(want).max()) > 1.0  # the logits spread
    assert float(jnp.abs(got - want).max()) < TOL


def test_reference_in_float8_fails_the_tolerance(params, ref_logits):
    seq = _tokens(0, 37)
    low = jax.jit(lambda t: ref.logits(params, t, CFG, "float8"))(
        jnp.asarray([seq]))[0]
    assert float(jnp.abs(low - ref_logits(seq)).max()) > 100 * TOL


# ---- operators --------------------------------------------------------------------


def _scan_inputs(seed: int, T: int, C=256, N=4):
    k = jax.random.split(jax.random.key(seed), 6)
    return (jax.random.normal(k[0], (T, C)),
            jax.nn.softplus(jax.random.normal(k[1], (T, C)) - 1.0),
            -jnp.exp(jax.random.uniform(k[2], (N, C), minval=0.0, maxval=2.5)),
            jax.random.normal(k[3], (T, N)), jax.random.normal(k[4], (T, N)),
            jax.random.normal(k[5], (N, C)))


@pytest.mark.parametrize("T", [16, 13, 5, 130])
@pytest.mark.parametrize("impl", ["jnp", "pallas"])
def test_selective_scan_matches_the_recurrence_from_a_carried_state(T, impl):
    """Against the decode step's own reference update, one token at a
    time, from a NON-ZERO state; the kernel through the interpreter,
    with a time block that does not divide T."""
    x, dt, A, B, C, state = _scan_inputs(1, T)
    buf, ys = state[None, None], []
    for t in range(T):
        one = lambda a: a[t][None]
        buf, y = ssm.selective_update_reference(
            buf, 0, one(x), one(dt), A, one(B), one(C),
            jnp.zeros((x.shape[1],)), jnp.ones((1,), bool))
        ys.append(y[0])
    y, end = ssm.selective_scan(x, dt, A, B, C, state, impl=impl, tile=128)
    # T dependent steps of a sum of N products, magnitudes up to ~20
    assert float(jnp.abs(y - jnp.stack(ys)).max()) < 5e-5
    assert float(jnp.abs(end - buf[0, 0]).max()) < 5e-5


@pytest.mark.parametrize("impl", ["jnp", "pallas"])
def test_scan_leaves_the_state_alone_where_dt_is_zero(impl):
    x, dt, A, B, C, state = _scan_inputs(2, 16)
    dt = jnp.where((jnp.arange(16) < 11)[:, None], dt, 0.0)
    _, want = ssm.selective_scan(x[:11], dt[:11], A, B[:11], C[:11], state,
                                 impl=impl, tile=128)
    _, got = ssm.selective_scan(x, dt, A, B, C, state, impl=impl, tile=128)
    assert jnp.array_equal(got, want)


@pytest.mark.parametrize("live", [(1, 0, 1, 1, 0), (0, 0, 0, 0, 0),
                                  (1, 1, 1, 1, 1)])
@pytest.mark.parametrize("tile", [None, 128])
def test_update_kernel_matches_jnp_and_skips_idle_lanes(live, tile):
    """The kernel through the interpreter: the decay formed inside it
    from ``dt`` and a tile of ``A``; idle lanes and the other layers
    bit for bit."""
    S, C, N, layers = 5, 256, 4, 3
    x, dt, A, B, Cc, _ = _scan_inputs(3, S)
    state = jax.random.normal(jax.random.key(9), (layers, S, N, C))
    live = jnp.asarray(live, bool)
    D = jnp.full((C,), 0.5)
    got_s, got_y = ssm.selective_state_update(
        state, 1, x, dt, A, B, Cc, D, live, impl="pallas", tile=tile,
        interpret=True)
    want_s, want_y = ssm.selective_update_reference(
        state, 1, x, dt, A, B, Cc, D, live)
    assert float(jnp.abs(got_s - want_s).max()) < 1e-5
    assert float(jnp.abs(got_y - want_y).max()) < 1e-5
    idle = ~np.asarray(live)
    assert jnp.array_equal(got_s[1][idle], state[1][idle])
    assert jnp.array_equal(got_s[0], state[0])
    assert jnp.array_equal(got_s[2], state[2])
    assert not np.asarray(got_y)[idle].any()


def test_the_accepted_update_kernel_is_the_kernel_of_before():
    """Mamba-2's call shares the grid with the per-element one and
    still reads what plain ``jnp`` gives at its shapes (a head's decay
    widened outside the kernel; a float32 rounding apart), idle lanes
    bit for bit, under its own name and with no ``A`` operand."""
    S, H, P, N = 3, 2, 64, 16
    k = jax.random.split(jax.random.key(5), 6)
    state = jax.random.normal(k[0], (2, S, N, H * P))
    args = (jax.random.normal(k[1], (S, H, P)),
            jax.nn.softplus(jax.random.normal(k[2], (S, H))),
            -jnp.ones((H,)), jax.random.normal(k[3], (S, N)),
            jax.random.normal(k[4], (S, N)), jnp.ones((H,)),
            jnp.asarray([True, False, True]))
    got = ssm.ssm_state_update(state, 1, *args, impl="pallas", interpret=True)
    want = ssm.state_update_reference(state, 1, *args)
    assert float(jnp.abs(got[0] - want[0]).max()) < 1e-5
    assert float(jnp.abs(got[1] - want[1]).max()) < 1e-5  # a sum of 16
    assert jnp.array_equal(got[0][1, 1], state[1, 1])
    assert jnp.array_equal(got[0][0], state[0])
    text = str(jax.make_jaxpr(lambda s: ssm.ssm_state_update(
        s, 1, *args, impl="pallas", interpret=False))(state))
    assert "ssm_state_update" in text and "selective" not in text


@pytest.mark.parametrize("S,H,Hkv,L", [(3, 8, 4, 256), (2, 40, 20, 128)])
def test_differential_decode_attention_kernel_matches_reference(S, H, Hkv, L):
    """``flash_decode`` over block-diagonal pair queries (heads of 64:
    a kv pair is a 128-lane group) against the reference, and the
    reference against each map formed head by head."""
    Dh = 64
    k = jax.random.split(jax.random.key(6), 3)
    q = jax.random.normal(k[0], (S, H, Dh))
    kc = jax.random.normal(k[1], (2, S, L, Hkv * Dh))
    vc = jax.random.normal(k[2], (2, S, L, Hkv * Dh))
    pos = jnp.asarray([0, L - 1, 130][:S], jnp.int32) % L
    want = diff_decode_attention(q, kc, vc, pos, layer=1, impl="reference")
    got = diff_decode_attention(q, kc, vc, pos, layer=1, impl="flash",
                                interpret=True)
    assert got.shape == (S, H // 2, 2, 2 * Dh)
    assert float(jnp.abs(got - want).max()) < 2e-5
    live = jnp.arange(L)[None] <= pos[:, None]
    per_kv = (H // 2) // (Hkv // 2)
    for pair in (0, H // 2 - 1):
        g = pair // per_kv
        vg = vc[1][:, :, 2 * g * Dh:(2 * g + 2) * Dh]
        for which in (0, 1):
            kh = kc[1][:, :, (2 * g + which) * Dh:(2 * g + which + 1) * Dh]
            s = jnp.einsum("sd,sld->sl", q[:, 2 * pair + which], kh) / 8.0
            w = jax.nn.softmax(jnp.where(live, s, -jnp.inf), -1)
            one = jnp.einsum("sl,sle->se", w, vg)
            assert float(jnp.abs(one - want[:, pair, which]).max()) < 2e-5
    assert jnp.array_equal(
        want, diff_decode_attention_reference(q, kc[1], vc[1], pos))


def test_the_kernels_leave_their_plan_records():
    from ddp_tpu.obs.tracer import SPAN_NUMS, get_tracer

    assert len(SPAN_NUMS["ssm.plan"]) == 4
    x, dt, A, B, C, state = _scan_inputs(4, 8)
    ssm.selective_scan(x, dt, A, B, C, state, impl="pallas", tile=128)
    ssm.selective_state_update(
        state[None, None], 0, x[:1], dt[:1], A, B[:1], C[:1],
        jnp.ones((256,)), jnp.ones((1,), bool), impl="pallas", interpret=True)
    plans = [e[4] for e in get_tracer().ring() if e[0] == "ssm.plan"]
    assert ("selective_scan", 1, 128, "float32") in plans
    assert ("selective_state_update", 1, 256, "float32") in plans


# ---- through the cache ------------------------------------------------------------


def _lane_state(S: int):
    z = lambda dt: jnp.zeros((S,), dt)
    return (z(jnp.int32), z(jnp.int32), z(jnp.int32), z(jnp.float32),
            jnp.ones((S,), jnp.float32))


@functools.partial(jax.jit, static_argnames="lane_attend")
def _chunk(params, cache, state, slot, buf, start, live, final, *,
           lane_attend):
    return sy.prefill_chunk(
        SPEC, params, cache, *state, slot, buf, start, live, final,
        jnp.int32(0), jnp.float32(0.0), jnp.float32(1.0),
        lane_attend=lane_attend)


@functools.partial(jax.jit, static_argnames="impl")
def _step(params, cache, toks, *, impl):
    return sy.slot_decode_step(SPEC, params, cache, toks, ssm_impl=impl)


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
            jnp.asarray(start + live == len(prompt)), lane_attend=start > 0)
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


# (prompt length, chunk width): shorter than the window; a multiple of
# the chunk; longer than the window with a padded last bucket that
# would wrap onto live ring rows (11 = 8 + 3 in a bucket of 4: padding
# at position 11 maps to row 3, which position 3 holds and position 10
# still attends); chunks of half a window, so a chunk's window layers
# must see the previous chunk's rows; several windows.
@pytest.mark.parametrize("prompt_len,chunk", [
    (3, 8), (8, 8), (11, 8), (16, 8), (21, 8), (13, 4), (29, 16), (1, 8)])
def test_chunked_prefill_then_cached_decode_match_the_full_forward(
        params, ref_logits, prompt_len, chunk):
    """The first sampled token and every decoded position's LOGITS
    against the reference's full forward, decoding on until the ring
    has wrapped several times (40 positions over a window of 8)."""
    seq = _tokens(prompt_len, 40)
    want = ref_logits(seq)
    cache, state, first = _prefill(
        params, init_slot_cache(SPEC, 3), _lane_state(3), 1,
        seq[:prompt_len], chunk=chunk)
    assert first == int(jnp.argmax(want[prompt_len - 1]))
    assert int(cache.pos[1]) == prompt_len
    got, cache = _decode_forced(params, cache, 1, seq[prompt_len:])
    assert float(jnp.abs(got - want[prompt_len:]).max()) < TOL
    assert int(cache.pos[1]) == 40


def test_the_pallas_path_matches_through_the_cache(params, ref_logits):
    seq = _tokens(5, 30)
    cache, _, _ = _prefill(params, init_slot_cache(SPEC, 2), _lane_state(2),
                           0, seq[:13])
    got, _ = _decode_forced(params, cache, 0, seq[13:], impl="pallas")
    assert float(jnp.abs(got - ref_logits(seq)[13:]).max()) < TOL


def test_bfloat16_state_fails_the_tolerance(params, ref_logits):
    seq = _tokens(7, 30)
    cache, _, _ = _prefill(params, init_slot_cache(SPEC, 2), _lane_state(2),
                           0, seq[:13])
    low = lambda c: c._replace(
        ssm=c.ssm.astype(jnp.bfloat16).astype(jnp.float32))
    got, _ = _decode_forced(params, cache, 0, seq[13:], between=low)
    assert float(jnp.abs(got - ref_logits(seq)[13:]).max()) > 100 * TOL


def test_a_lost_ring_row_fails_the_tolerance(params, ref_logits):
    """What a padded position written into the ring would do."""
    seq = _tokens(8, 30)
    cache, _, _ = _prefill(params, init_slot_cache(SPEC, 2), _lane_state(2),
                           0, seq[:13])
    cache = cache._replace(ring_k=cache.ring_k.at[:, 0, 3].set(0.0))
    got, _ = _decode_forced(params, cache, 0, seq[13:16])
    assert float(jnp.abs(got - ref_logits(seq)[13:16]).max()) > 100 * TOL


def test_prefill_that_stops_at_the_full_layer_is_exact(params):
    """The chunk programs run the cross-decoder at ONE position of a
    prompt. The program that runs every layer at every position
    (``dense_logits``) puts first the token they sample, and the lane
    they leave (ring, rows, state, tail: layers 0-17 alone write them)
    decodes on to that program's logits."""
    seq = _tokens(9, 24)
    dense = jax.jit(lambda t: sy.dense_logits(SPEC, params, t))(
        jnp.asarray([seq]))[0]
    cache, _, first = _prefill(params, init_slot_cache(SPEC, 2),
                               _lane_state(2), 1, seq[:21])
    assert first == int(jnp.argmax(dense[20]))
    got, _ = _decode_forced(params, cache, 1, seq[21:])
    assert float(jnp.abs(got - dense[21:]).max()) < TOL


def test_a_reused_lane_reads_as_a_fresh_one(params, ref_logits):
    """A second, SHORTER request in a lane a longer one filled: the
    stale ring rows beyond ``min(pos + 1, W)``, the stale shared rows,
    the state and the tail are never read."""
    long_, short = _tokens(10, 40), _tokens(11, 12)
    cache, state, _ = _prefill(params, init_slot_cache(SPEC, 2),
                               _lane_state(2), 1, long_[:29])
    _, cache = _decode_forced(params, cache, 1, long_[29:])
    cache, state, first = _prefill(params, cache, state, 1, short[:5])
    want = ref_logits(short)
    assert first == int(jnp.argmax(want[4]))
    got, _ = _decode_forced(params, cache, 1, short[5:])
    assert float(jnp.abs(got - want[5:]).max()) < TOL


@pytest.mark.parametrize("impl", ["jnp", "pallas"])
def test_a_step_leaves_idle_lanes_bit_for_bit(params, impl):
    """Lane 0 decodes; lane 1 waits between two chunks of its prompt
    (its ring full, ``pos`` 8: an unmasked write would land on row 0);
    lane 2 is idle. Ring, shared rows, state, tail and ``pos`` of lanes
    1 and 2 are the same bits after the step."""
    cache, state, _ = _prefill(params, init_slot_cache(SPEC, 3),
                               _lane_state(3), 0, _tokens(12, 13))
    out = _chunk(params, cache, state, jnp.int32(1),
                 jnp.asarray(_tokens(13, 8), jnp.int32), jnp.int32(0),
                 jnp.int32(8), jnp.asarray(False), lane_attend=False)
    cache = out[0]._replace(live=jnp.asarray([True, False, False]))
    _, after = _step(params, cache, jnp.asarray([5, 6, 7], jnp.int32),
                     impl=impl)
    for name in ("k", "v", "ring_k", "ring_v", "ssm", "conv"):
        before, now = getattr(cache, name), getattr(after, name)
        assert jnp.array_equal(now[:, 1:], before[:, 1:]), name
        assert not jnp.array_equal(now[:, 0], before[:, 0]), name
    assert after.pos.tolist() == [14, 8, 0]


def test_a_decode_step_through_the_kernel_reads_ring_and_rows_alike():
    """Heads of 64 (a kv pair is a 128-lane group, what ``flash_decode``
    takes): one step over lanes whose rings have not wrapped, have just
    wrapped and wrapped long ago, one of them idle, through the kernel
    that walks a lane's live rows and through the reference: the same
    logits and the same lanes, to rounding (a layer writes rows formed
    from the attention below it)."""
    spec = SPEC._replace(head_dim=64, d_model=128, mamba_d_inner=256)
    tree = sy.init_params(spec, seed=5, dtype=jnp.float32)
    cache = init_slot_cache(spec, 4)
    keys = iter(jax.random.split(jax.random.key(8), 8))
    fill = lambda a: jax.random.normal(next(keys), a.shape, a.dtype)
    cache = cache._replace(
        k=fill(cache.k), v=fill(cache.v), ring_k=fill(cache.ring_k),
        ring_v=fill(cache.ring_v), ssm=fill(cache.ssm), conv=fill(cache.conv),
        pos=jnp.asarray([3, W - 1, W, 47], jnp.int32),
        live=jnp.asarray([True, True, False, True]))
    toks = jnp.asarray([5, 6, 7, 8], jnp.int32)
    step = lambda impl: jax.jit(lambda c: sy.slot_decode_step(
        spec, tree, c, toks, attn_impl=impl, ssm_impl="jnp"))(cache)
    (want, want_c), (got, got_c) = step("reference"), step("flash")
    live = np.asarray(cache.live)
    assert float(jnp.abs(got - want)[live].max()) < TOL
    for a, b in zip(jax.tree.leaves(got_c), jax.tree.leaves(want_c)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=TOL)


# ---- the engine ---------------------------------------------------------------------


def _engine(params, **knobs):
    kw = dict(slots=3, prefill_chunk=8, min_bucket=4, max_queue=64)
    return ServeEngine(SPEC, params, **{**kw, **knobs})


def _greedy(ref_logits, prompt, n):
    seq = list(prompt)
    for _ in range(n):
        seq.append(int(jnp.argmax(ref_logits(seq)[-1])))
    return seq[len(prompt):]


JOBS = [(13, 6), (3, 9), (16, 4), (21, 12), (8, 1), (5, 20), (30, 5)]


def test_engine_serves_the_reference_greedy_tokens(params, ref_logits):
    """Seven requests of mixed lengths through three lanes, admitted
    out of step, lanes reused, prompts prefilled in several chunks with
    a padded last bucket and without their cross-decoder while other
    lanes decode, rings wrapped: every answer is the reference's greedy
    loop's, nothing compiles after warmup, and the counters count."""
    eng = _engine(params)
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
    assert rs["prefill_self_positions_total"] == sum(p for p, _ in JOBS)
    # prefill stops at the full layer: one position a request goes on
    assert rs["prefill_cross_positions_total"] == len(JOBS)
    # every token but a request's first comes from a live lane's step
    assert rs["ssm_lane_updates_total"] == sum(n - 1 for _, n in JOBS)
    # a step at pos reads min(pos + 1, W) ring rows a window layer and
    # pos + 1 shared rows a reader (the full layer and one cross layer)
    steps = [(p + j) for p, n in JOBS for j in range(1, n)]
    assert rs["kv_ring_rows_attended_total"] == 2 * sum(
        min(r, W) for r in steps)
    assert rs["kv_shared_rows_attended_total"] == 2 * sum(steps)
    assert rs["kv_ring_bytes_per_slot"] == 2 * 2 * W * 32 * 4
    assert rs["kv_shared_bytes_per_slot"] == 2 * 64 * 32 * 4
    assert rs["kv_bytes_per_slot"] == rs["kv_shared_bytes_per_slot"]
    assert rs["ssm_state_bytes_per_slot"] == 3 * 128 * 4 * (4 + 3)
    assert s["decode_path"]["cache_bytes_per_slot"] == (
        rs["kv_ring_bytes_per_slot"] + rs["kv_shared_bytes_per_slot"]
        + rs["ssm_state_bytes_per_slot"])
    assert sy.lane_bytes(SPEC) == {
        "ring": rs["kv_ring_bytes_per_slot"],
        "shared": rs["kv_shared_bytes_per_slot"],
        "state": rs["ssm_state_bytes_per_slot"]}
    # /metricsz renders them; a plain model's exposition has none
    from ddp_tpu.obs.promtext import render_serve, validate_promtext

    text = render_serve(s)
    validate_promtext(text)
    for name in ("kv_ring_rows_attended_total", "kv_shared_bytes_per_slot",
                 "prefill_cross_positions_total", "ssm_lane_updates_total"):
        assert f"ddp_tpu_serve_{name}" in text
    # beside each decode span a record of the two kinds of rows and
    # the live lanes, under declared names; the span keeps its two
    from ddp_tpu.obs.tracer import SPAN_NUMS

    assert SPAN_NUMS["serve.decode_rows"] == (
        "ring_rows", "shared_rows", "live_lanes")
    ring = eng.tracer.ring()
    decodes = [e for e in ring if e[0] == "serve.decode"][-5:]
    rows = {e[3]: e for e in ring if e[0] == "serve.decode_rows"}
    assert decodes and all(len(e[4]) == 2 for e in decodes)
    for e in decodes:
        rec = rows[e[3]]  # one a step: the same parent
        assert rec[2] == 0.0 and rec[1] >= e[1] + e[2]  # where it ends
        assert 1 <= rec[4][2] <= e[4][0] and rec[4][1] >= rec[4][0] > 0


@pytest.mark.parametrize("knobs,match", [
    (dict(page_size=8), "page_size does not apply to the sambay"),
    (dict(kv_dtype="int8"), "kv_dtype does not apply to the sambay"),
    (dict(spec_tokens=2, draft_spec=SPEC, draft_params={}),
     "spec_tokens does not apply to the sambay"),
])
def test_knobs_that_do_not_apply_are_refused_by_name(params, knobs, match):
    with pytest.raises(ValueError, match=match):
        _engine(params, **knobs)


def test_prefix_export_and_install_are_refused_by_name(params):
    from ddp_tpu.serve.disagg import PageWireError

    eng = _engine(params)
    with pytest.raises(ValueError,
                       match="export_prefix does not apply to the sambay"):
        eng.export_prefix([1, 2, 3])
    with pytest.raises(PageWireError, match="install_prefix does not apply"):
        eng.install_prefix(None)


@pytest.mark.parametrize("change,match", [
    (dict(layer_types=("mamba", "window") * 4), "ONE full attention"),
    (dict(layer_types=("mamba", "full", "gmu")), "layer_types"),
    (dict(layer_types=("window", "full") + ("gmu",) * 6), "at least one"),
    (dict(layer_types=("mamba", "gmu", "full") + ("cross",) * 5), "before"),
    (dict(layer_types=("mamba", "attention") * 4), "layer_types"),
    (dict(mamba_d_inner=0), "mamba_d_inner"),
    (dict(sliding_window=0), "sliding_window"),
    (dict(num_heads=3), "pair"),
    (dict(position_embedding="rope"), "position_embedding"),
    (dict(block_length=4), "one token a step"),
])
def test_spec_that_names_no_such_model_is_refused(change, match):
    with pytest.raises(ValueError, match=match):
        sy.validate(SPEC._replace(**change))


def test_the_engine_asks_the_block_for_its_programs():
    """One table from ``spec.block`` to a module; the plain block has
    none, and each module answers what the engine asks."""
    from ddp_tpu.models import granite_hybrid
    from ddp_tpu.serve import engine as eng

    assert eng.block_module(SPEC) is sy
    assert eng.block_module(SPEC._replace(block="granite_hybrid")) \
        is granite_hybrid
    assert eng.block_module(SPEC._replace(block="gpt2")) is None
    for mod in (sy, granite_hybrid):
        assert mod.RECURRENT is True
        for name in ("validate", "prefill_chunk", "slot_decode_sample_step"):
            assert callable(getattr(mod, name))
    assert not hasattr(granite_hybrid, "attended_rows")


# ---- from a checkpoint directory ------------------------------------------------------


def test_checkpoint_round_trip_recovers_the_spec(tmp_path, params):
    from ddp_tpu.train.checkpoint import (
        CheckpointManager,
        derive_spec_with_sidecar,
    )

    sy.save_checkpoint(str(tmp_path), SPEC, params)
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
    sy.save_checkpoint(str(tmp_path), SPEC, params)
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
        rs = stats["recurrent_state"]
        assert rs["ssm_state_resets_total"] == 1
        assert rs["prefill_cross_positions_total"] == 1
    finally:
        proc.send_signal(signal.SIGTERM)
        try:
            proc.wait(timeout=60)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait()
