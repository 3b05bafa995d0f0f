"""ops/decode.py: flash-decode kernel + int8 KV quantization pins.

The decode-speed stack's correctness contract (ISSUE 10 / ROADMAP
item 2), layered:

- **Op level**: the Pallas kernel (interpret mode off-TPU — same
  program, same banded/online-softmax math) matches the jnp reference
  elementwise over GQA/MHA shapes, unaligned per-lane positions, and
  partial key blocks; the reference itself IS the PR-3 engine math
  (pulled out verbatim), so kernel≡reference≡engine transitively.
- **int8 KV**: quantize/dequantize round-trip error is bounded by the
  per-head scale's analytic step (amax/127), all-zero rows survive
  exactly, and the quantized attention output stays within a bounded
  divergence of fp32.
- **Engine level**: ``decode_attn="flash"`` serves token-identical to
  ``generate()`` for greedy AND seeded sampling across every prefill
  bucket edge and unaligned lane positions (mixed-age batch);
  ``kv_dtype="int8"`` holds the bounded-divergence regression pin and
  halves (better) measured cache bytes/slot; the steady-state
  transfer stays [slots] int32 under ``sanitize=True``.
- **Mesh**: ``shard_decode_attention`` routes the op through a
  shard_map island over the model axis (whole kv-head groups per
  shard) and matches the unsharded op bitwise-tolerably.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ddp_tpu.models.generate import (
    generate,
    init_slot_cache,
    slot_decode_step,
)
from ddp_tpu.models.lm import LMSpec, init_lm
from ddp_tpu.obs.tracer import SPAN_NUMS, get_tracer
from ddp_tpu.ops import decode as decode_ops
from ddp_tpu.ops.decode import (
    copy_rows,
    decode_attention,
    decode_attention_reference,
    decode_block,
    dequantize_kv,
    diff_decode_attention,
    diff_decode_attention_reference,
    fetched_rows,
    flash_decode_attention,
    live_block,
    packed_decode_attention,
    quantize_kv,
    shard_decode_attention,
)
from ddp_tpu.serve.engine import ServeEngine

SPEC = LMSpec(vocab_size=37, total_len=32, d_model=32, depth=2, num_heads=4)


@pytest.fixture(scope="module")
def params():
    return init_lm(SPEC, seed=0)


def _reference(spec, params, prompt, n, **sampling):
    return np.asarray(
        generate(
            spec, params, jnp.asarray([prompt], jnp.int32),
            max_new_tokens=n, **sampling,
        )
    )[0, len(prompt):].tolist()


def _rand_qkv(rng, S, H, H_kv, Dh, L):
    q = jnp.asarray(rng.normal(size=(S, H, Dh)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(S, L, H_kv, Dh)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(S, L, H_kv, Dh)), jnp.float32)
    return q, k, v


# The kernel grid (S, H, H_kv, Dh, L, block_k). An int8 cache tiles 32
# rows at a time: its tests run lanes and blocks four times as long
# (same block counts).
_GRID = [
    (3, 4, 4, 8, 16, 8),    # MHA (the all-heads form), two key blocks
    (2, 8, 2, 16, 32, 8),   # GQA group 4 (the per-kv-head form)
    (4, 4, 2, 8, 24, 16),   # 16 does not divide 24 → three blocks of 8
    (1, 2, 1, 4, 8, 128),   # block_k > L → clamped to L
]


class TestKernel:
    @pytest.mark.parametrize("S,H,H_kv,Dh,L,block_k", _GRID)
    def test_matches_reference(self, S, H, H_kv, Dh, L, block_k):
        """The kernel's online-softmax over banded blocks computes the
        reference einsum math (1-ulp-class reassociation only), for
        every lane position including 0 (single live key) and L-1."""
        rng = np.random.default_rng(S * 100 + L)
        q, k, v = _rand_qkv(rng, S, H, H_kv, Dh, L)
        pos = jnp.asarray(
            rng.integers(0, L, size=(S,)), jnp.int32
        ).at[0].set(0).at[-1].set(L - 1)
        ref = decode_attention_reference(q, k, v, pos)
        out = flash_decode_attention(q, k, v, pos, block_k=block_k)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), atol=1e-5, rtol=1e-5
        )

    def test_unaligned_positions_band_is_exact(self):
        """Keys past pos[s] contribute NOTHING: growing the cache with
        garbage rows above the band leaves the output unchanged — the
        banded-read guarantee the engine's write-before-attend
        invariant rests on."""
        rng = np.random.default_rng(7)
        q, k, v = _rand_qkv(rng, 3, 4, 2, 8, 16)
        pos = jnp.asarray([0, 5, 11], jnp.int32)
        out = flash_decode_attention(q, k, v, pos, block_k=8)
        poison = jnp.asarray(
            rng.normal(size=k.shape) * 100.0, jnp.float32
        )
        live = (
            jnp.arange(16)[None, :, None, None]
            <= pos[:, None, None, None]
        )
        k2 = jnp.where(live, k, poison)
        v2 = jnp.where(live, v, poison)
        out2 = flash_decode_attention(q, k2, v2, pos, block_k=8)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(out2), atol=1e-5, rtol=1e-5
        )

    def test_int8_kernel_matches_int8_reference(self):
        """Dequantize-in-kernel computes the same attention as the
        dequantize-then-reference path over the SAME int8 cache."""
        rng = np.random.default_rng(11)
        q, k, v = _rand_qkv(rng, 3, 4, 2, 8, 64)
        pos = jnp.asarray([2, 31, 63], jnp.int32)
        qk, ks = quantize_kv(k)
        qv, vs = quantize_kv(v)
        ref = decode_attention_reference(q, qk, qv, pos, ks, vs)
        # int8 rows tile 32 at a time: two blocks of one tile each.
        out = flash_decode_attention(q, qk, qv, pos, ks, vs, block_k=32)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), atol=1e-5, rtol=1e-5
        )

    def test_decode_attention_impl_dispatch(self):
        rng = np.random.default_rng(3)
        q, k, v = _rand_qkv(rng, 2, 4, 2, 8, 16)
        pos = jnp.asarray([3, 9], jnp.int32)
        ref = decode_attention(q, k, v, pos, impl="reference")
        fl = decode_attention(q, k, v, pos, impl="flash")
        np.testing.assert_allclose(
            np.asarray(fl), np.asarray(ref), atol=1e-5, rtol=1e-5
        )
        # auto resolves off-TPU to the reference path, bit-identical
        auto = decode_attention(q, k, v, pos, impl="auto")
        assert jnp.array_equal(auto, ref)
        with pytest.raises(ValueError, match="impl"):
            decode_attention(q, k, v, pos, impl="dense")


def _stored(rng, S, H, H_kv, Dh, L, kv_dtype, depth=3):
    """A random [depth, S, L, H_kv, Dh] cache (every layer different)
    → (q, k, v, k_scale, v_scale); scales None on a float cache."""
    q = jnp.asarray(rng.normal(size=(S, H, Dh)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(depth, S, L, H_kv, Dh)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(depth, S, L, H_kv, Dh)), jnp.float32)
    if kv_dtype == "int8":
        (k, ks), (v, vs) = quantize_kv(k), quantize_kv(v)
        return q, k, v, ks, vs
    return q, k, v, None, None


def _edge_pos(rng, S, L):
    return jnp.asarray(
        rng.integers(0, L, size=(S,)), jnp.int32
    ).at[0].set(0).at[-1].set(L - 1)


class TestStoredLayout:
    """The kernel reads the cache as the engine stores it — [depth, S,
    L, H_kv, Dh], the layer picked by the index map — and fetches no
    block past a lane's position."""

    @pytest.mark.parametrize("kv_dtype", ["fp32", "int8"])
    @pytest.mark.parametrize("layer", [1, 2])
    @pytest.mark.parametrize("S,H,H_kv,Dh,L,block_k", _GRID)
    def test_layer_of_stored_cache_matches_reference(
        self, S, H, H_kv, Dh, L, block_k, layer, kv_dtype
    ):
        if kv_dtype == "int8":
            L, block_k = 4 * L, 4 * block_k
        rng = np.random.default_rng(S * 100 + L + layer)
        q, k, v, ks, vs = _stored(rng, S, H, H_kv, Dh, L, kv_dtype)
        pos = _edge_pos(rng, S, L)
        sc = (None, None) if ks is None else (ks[layer], vs[layer])
        ref = decode_attention_reference(q, k[layer], v[layer], pos, *sc)
        out = flash_decode_attention(
            q, k, v, pos, ks, vs, layer=layer, block_k=block_k
        )
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), atol=1e-5, rtol=1e-5
        )
        # the engine-facing entry takes the same operands on both paths
        for impl in ("flash", "reference"):
            got = decode_attention(
                q, k, v, pos, ks, vs, impl=impl, layer=layer,
                block_k=block_k,
            )
            np.testing.assert_allclose(
                np.asarray(got), np.asarray(ref), atol=1e-5, rtol=1e-5
            )

    @pytest.mark.parametrize("kv_dtype", ["fp32", "int8"])
    @pytest.mark.parametrize(
        "H,H_kv", [(4, 4), (8, 2)], ids=["all_heads", "per_kv_head"]
    )
    def test_rows_past_pos_may_hold_nan(self, H, H_kv, kv_dtype):
        """Every cache row past pos[s] poisoned with NaN (on an int8
        cache: its scale): the output is finite and equals the
        reference's on the clean cache — a dead row reaches neither
        the softmax nor the weighted sum (0 · NaN)."""
        S, Dh, L, block_k, layer = 3, 8, 128, 32, 1
        rng = np.random.default_rng(5)
        q, k, v, ks, vs = _stored(rng, S, H, H_kv, Dh, L, kv_dtype)
        pos = jnp.asarray([0, 37, L - 2], jnp.int32)
        sc = (None, None) if ks is None else (ks[layer], vs[layer])
        ref = decode_attention_reference(q, k[layer], v[layer], pos, *sc)
        dead = jnp.arange(L)[None, None, :, None] > pos[None, :, None, None]
        if kv_dtype == "int8":
            ks, vs = (jnp.where(dead, jnp.nan, x) for x in (ks, vs))
        else:
            k, v = (jnp.where(dead[..., None], jnp.nan, x) for x in (k, v))
        out = flash_decode_attention(
            q, k, v, pos, ks, vs, layer=layer, block_k=block_k
        )
        assert np.isfinite(np.asarray(out)).all()
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), atol=1e-5, rtol=1e-5
        )

    @pytest.mark.parametrize("block_k", [8, 32, 128])
    def test_index_map_never_names_a_dead_block(self, block_k):
        """``live_block`` (what the K/V index maps return for the block
        dim): grid step j reads block j while it is live, and repeats
        the last live block after — a repeated index is no DMA."""
        L = 4 * block_k
        for pos in (0, 1, block_k - 1, block_k, 2 * block_k + 3, L - 1, L):
            last = min(pos // block_k, L // block_k - 1)
            got = [int(live_block(j, pos, block_k)) for j in range(L // block_k)]
            assert got == [min(j, last) for j in range(L // block_k)]
            assert max(got) * block_k <= pos

    def test_lane_parked_at_the_ceiling_attends_key_zero(self, params):
        """An idle lane that has drifted to ``pos == total_len`` has no
        reader; the decode step has it attend key 0 alone (one block a
        layer, not its whole lane). Rows 1.. of the parked lane hold
        NaN here: nothing of them reaches its logits, the live lane's
        logits are the reference path's, and the parked lane's write
        still lands on the last line."""
        L = SPEC.total_len
        rng = np.random.default_rng(9)
        cache = init_slot_cache(SPEC, 2)
        k = jnp.asarray(rng.normal(size=cache.k.shape), jnp.float32)
        v = jnp.asarray(rng.normal(size=cache.v.shape), jnp.float32)
        cache = cache._replace(k=k, v=v, pos=jnp.asarray([5, L], jnp.int32))
        toks = jnp.asarray([3, 4], jnp.int32)
        ref_logits, _ = slot_decode_step(SPEC, params, cache, toks)
        parked_rows = (jnp.arange(L) > 0)[None, None, :, None, None] & (
            jnp.arange(2) == 1
        )[None, :, None, None, None]
        poisoned = cache._replace(
            k=jnp.where(parked_rows, jnp.nan, k),
            v=jnp.where(parked_rows, jnp.nan, v),
        )
        logits, new = slot_decode_step(
            SPEC, params, poisoned, toks, attn_impl="flash"
        )
        assert np.isfinite(np.asarray(logits)).all()
        np.testing.assert_allclose(
            np.asarray(logits), np.asarray(ref_logits), atol=1e-4, rtol=1e-4
        )
        assert np.isfinite(np.asarray(new.k[:, 1, L - 1])).all()
        assert np.isnan(np.asarray(new.k[:, 1, 1 : L - 1])).all()
        assert new.pos.tolist() == [6, L]

    @pytest.mark.parametrize("kv_dtype", [jnp.float32, jnp.int8])
    def test_decode_step_moves_no_layer_of_lanes(self, kv_dtype):
        """Structural pin on the flash decode step: outside the kernel
        no operation produces a layer's worth of lane elements
        (S·L·H_kv·Dh) — no slice of ``cache.k[i]``, no transpose for
        the kernel, no write-back of an updated layer — and every
        cache write carries rows, not lanes. Only the cache itself
        (the in-place scatter's result) is that large."""
        S = 5  # a layer of lanes (5120) is larger than any weight
        spec = SPEC
        cache = init_slot_cache(spec, S, dtype=kv_dtype)
        layer_elems = int(np.prod(cache.k.shape[1:]))
        params = init_lm(spec, seed=0)
        assert layer_elems > max(
            x.size for x in jax.tree.leaves(params)
        )
        jaxpr = jax.make_jaxpr(
            lambda c, t: slot_decode_step(
                spec, params, c, t, attn_impl="flash"
            )
        )(cache, jnp.zeros((S,), jnp.int32))

        def walk(jp):
            for eqn in jp.eqns:
                name = eqn.primitive.name
                yield eqn
                if name == "pallas_call":
                    continue  # the kernel's own blocks
                for sub in jax.core.jaxprs_in_params(eqn.params):
                    yield from walk(sub)

        kernels = writes = 0
        for eqn in walk(jaxpr.jaxpr):
            name = eqn.primitive.name
            kernels += name == "pallas_call"
            if name in ("scatter", "dynamic_update_slice"):
                update = eqn.invars[2 if name == "scatter" else 1].aval
                if eqn.invars[0].aval.shape == cache.k.shape:
                    writes += 1
                    assert update.size == S * cache.k.shape[3] * cache.k.shape[4]
            for out in eqn.outvars:
                if out.aval.size >= layer_elems:
                    assert out.aval.shape == cache.k.shape, (
                        f"{name} makes {out.aval.shape}: a layer of "
                        "lanes moved outside the kernel"
                    )
                    assert name in ("scatter", "pjit", "jit"), name
        assert kernels == spec.depth
        assert writes == 2 * spec.depth  # K and V rows, once a layer


class TestInt8KV:
    def test_roundtrip_error_bounded_by_scale_step(self):
        """|x - dq(q(x))| <= scale/2 per element (symmetric rounding),
        where scale = amax/127 per (position, head) row."""
        rng = np.random.default_rng(0)
        x = jnp.asarray(
            rng.normal(size=(4, 16, 2, 8)) * 3.0, jnp.float32
        )
        q, s = quantize_kv(x)
        assert q.dtype == jnp.int8 and s.shape == x.shape[:-1]
        err = jnp.abs(dequantize_kv(q, s) - x)
        bound = s[..., None] / 2 + 1e-7
        assert bool(jnp.all(err <= bound))

    def test_zero_rows_survive_exactly(self):
        """Unwritten cache lines (all zeros) round-trip to exact zeros
        — no NaN from a zero amax (the scale floor)."""
        x = jnp.zeros((2, 4, 2, 8), jnp.float32)
        q, s = quantize_kv(x)
        assert bool(jnp.all(dequantize_kv(q, s) == 0.0))
        assert bool(jnp.all(jnp.isfinite(s)))

    def test_attention_divergence_bounded(self):
        """int8-cache attention stays within a bounded divergence of
        the fp32 attention — the op-level half of the engine's
        bounded-divergence pin."""
        rng = np.random.default_rng(5)
        q, k, v = _rand_qkv(rng, 3, 4, 2, 8, 24)
        pos = jnp.asarray([4, 12, 23], jnp.int32)
        fp = decode_attention_reference(q, k, v, pos)
        qk, ks = quantize_kv(k)
        qv, vs = quantize_kv(v)
        q8 = decode_attention_reference(q, qk, qv, pos, ks, vs)
        # ~1e-2-class divergence for unit-scale inputs: the int8 step
        # is amax/127 ≈ 0.03 here and softmax averaging shrinks it.
        assert float(jnp.max(jnp.abs(fp - q8))) < 0.05

    def test_cache_bytes_per_slot_halved(self, params):
        """The capacity claim, measured on live engine buffers: int8
        K/V + fp32 per-head scales cost well under half the fp32
        layout ((1 + 4/Dh)/4 of it; Dh=8 here → 0.375)."""
        fp32 = ServeEngine(SPEC, params, slots=2, prefill_len=8)
        int8 = ServeEngine(
            SPEC, params, slots=2, prefill_len=8, kv_dtype="int8"
        )
        assert int8.cache_bytes_per_slot() <= (
            0.55 * fp32.cache_bytes_per_slot()
        )
        assert int8.kv_dtype == "int8"
        assert int8._cache.quantized()
        assert not fp32._cache.quantized()

    def test_int8_scale_buffers_are_distinct(self):
        """k_scale and v_scale must be separate buffers: the cache is
        donated through every engine program, and aliased leaves make
        XLA reject the donation (the (x,)*2 regression)."""
        cache = init_slot_cache(SPEC, 2, dtype=jnp.int8)
        assert cache.k_scale.unsafe_buffer_pointer() != (
            cache.v_scale.unsafe_buffer_pointer()
        )

    def test_engine_int8_bounded_divergence_pin(self, params):
        """Regression pin: on the fixed test model the int8 engine's
        greedy stream is token-identical to fp32 generate() — the
        quantization error never crosses an argmax boundary here. A
        platform where it legitimately diverges would fail loudly and
        the pin becomes a bounded-divergence count; on this image it
        is exact."""
        eng = ServeEngine(
            SPEC, params, slots=2, prefill_len=8, kv_dtype="int8"
        )
        reqs = []
        for plen in (1, 3, 4, 7, 8):
            prompt = [(5 * plen + i) % SPEC.vocab_size for i in range(plen)]
            reqs.append((prompt, eng.submit(prompt, 5).request))
            eng.step()
        eng.run()
        for prompt, req in reqs:
            got = eng.result(req.rid)
            want = _reference(SPEC, params, prompt, 5)
            diverged = sum(a != b for a, b in zip(got.tokens, want))
            assert diverged == 0, (
                f"int8 KV diverged at {diverged}/{len(want)} tokens "
                f"for prompt_len {len(prompt)}"
            )


class TestFlashEngine:
    def test_bucket_edges_greedy_token_identity(self, params):
        """decode_attn='flash' (interpret mode on CPU — the same
        kernel program) serves token-identical to generate() across
        every bucket edge, staggered admission → unaligned per-lane
        positions in every decode step."""
        eng = ServeEngine(
            SPEC, params, slots=2, prefill_len=16,
            prefill_chunk=8, min_bucket=4, decode_attn="flash",
        )
        assert eng.buckets == [4, 8]
        assert eng.decode_attn == "flash"
        reqs = []
        for plen in (1, 3, 4, 5, 8, 9, 15, 16):
            prompt = [(7 * plen + i) % SPEC.vocab_size for i in range(plen)]
            reqs.append((prompt, eng.submit(prompt, 5).request))
            eng.step()  # staggered: mixed-age lanes
        eng.run()
        for prompt, req in reqs:
            got = eng.result(req.rid)
            assert got.status == "complete"
            assert got.tokens == _reference(SPEC, params, prompt, 5), (
                f"flash decode diverged at prompt_len {len(prompt)}"
            )

    def test_seeded_sampling_token_identity(self, params):
        """Seeded temperature/top-p through the flash kernel: the
        attention feeding the fused sampler must be exact enough to
        keep the whole sampled stream identical (argmax/categorical
        over fp32 logits)."""
        eng = ServeEngine(
            SPEC, params, slots=3, prefill_len=8, min_bucket=4,
            decode_attn="flash",
        )
        cases = [
            ([3, 1, 4, 1], 6, dict(temperature=0.8, seed=7)),
            ([2, 7], 5, dict(temperature=1.3, top_p=0.9, seed=3)),
            ([5, 3, 5, 8, 9], 4, dict(temperature=0.6, top_p=0.7,
                                      seed=-3)),
            ([9, 9], 5, dict()),  # greedy lane sharing the batch
        ]
        reqs = [
            (p, n, kw, eng.submit(p, n, **kw).request)
            for p, n, kw in cases
        ]
        eng.run()
        for p, n, kw, req in reqs:
            got = eng.result(req.rid)
            assert got.tokens == _reference(SPEC, params, p, n, **kw), (
                f"flash + sampling config {kw} diverged"
            )

    def test_flash_int8_compose_under_sanitize(self, params,
                                               monkeypatch):
        """The full stack — flash kernel + int8 cache — under the
        --sanitize transfer guard: steady-state fetches stay
        ()/[slots] int32 (never logits), and the stream matches the
        int8 reference engine (kernel-vs-reference on the SAME
        quantized cache)."""
        import ddp_tpu.serve.engine as engine_mod

        def run(attn):
            eng = ServeEngine(
                SPEC, params, slots=2, prefill_len=8,
                decode_attn=attn, kv_dtype="int8", sanitize=True,
            )
            a = eng.submit([1, 2, 3], 10).request
            b = eng.submit([4, 5], 10).request
            eng.run()
            return [eng.result(r.rid).tokens for r in (a, b)]

        want = run("reference")
        fetched = []
        real_np = np

        class _NpSpy:
            def asarray(self, x, *a, **k):
                if isinstance(x, jax.Array):
                    fetched.append((tuple(x.shape), str(x.dtype)))
                return real_np.asarray(x, *a, **k)

            def __getattr__(self, name):
                return getattr(real_np, name)

        monkeypatch.setattr(engine_mod, "np", _NpSpy())
        got = run("flash")
        monkeypatch.undo()
        assert got == want, "flash diverged from reference on int8 cache"
        assert fetched, "engine fetched nothing"
        assert all(
            shape in ((), (2,)) and dtype == "int32"
            for shape, dtype in fetched
        ), f"non-token fetch on the sanitized flash+int8 path: {fetched}"

    def test_compile_counts_stable_and_labeled(self, params):
        """The static-shape pin holds for the flash engine, and the
        xprof label names the kernel program (serve.flash_decode) so
        recompile culprits distinguish it from the jnp path."""
        from ddp_tpu.obs.xprof import Xprof

        xp = Xprof(enabled=True)
        eng = ServeEngine(
            SPEC, params, slots=2, prefill_len=8, min_bucket=4,
            decode_attn="flash", xprof=xp,
        )
        warm = eng.warmup()
        assert sum(warm.values()) <= eng.compile_budget()
        for plen in (1, 4, 6, 8):
            eng.submit(list(range(1, plen + 1)), 3)
            eng.step()
        eng.run()
        assert eng.compile_counts() == warm
        labels = {r["label"] for r in xp.ledger_records()}
        assert "serve.flash_decode" in labels
        assert "serve.decode" not in labels


class TestMeshComposition:
    def test_shard_map_island_matches_plain(self):
        """TP composition: kv heads shard over the model axis (whole
        GQA groups per shard), output re-assembles to the unsharded
        result — the flash-decode kernel stays mesh-compatible."""
        from jax.sharding import Mesh

        devs = jax.devices()
        if len(devs) < 2:
            pytest.skip("needs >= 2 (emulated) devices")
        mesh = Mesh(np.asarray(devs[:2]).reshape(1, 2), ("data", "model"))
        rng = np.random.default_rng(13)
        q, k, v = _rand_qkv(rng, 3, 8, 2, 8, 16)
        pos = jnp.asarray([1, 8, 15], jnp.int32)
        plain = decode_attention(q, k, v, pos, impl="reference")
        fn = shard_decode_attention(mesh, impl="reference")
        sharded = fn(q, k, v, pos)
        np.testing.assert_allclose(
            np.asarray(sharded), np.asarray(plain), atol=1e-5, rtol=1e-5
        )
        # int8 scales shard along the same head axis
        qk, ks = quantize_kv(k)
        qv, vs = quantize_kv(v)
        plain8 = decode_attention(
            q, qk, qv, pos, ks, vs, impl="reference"
        )
        sharded8 = fn(q, qk, qv, pos, ks, vs)
        np.testing.assert_allclose(
            np.asarray(sharded8), np.asarray(plain8),
            atol=1e-5, rtol=1e-5,
        )

    def test_indivisible_heads_fall_back(self):
        """H_kv not divisible by the model axis → the plain call (a
        clear contract beats a wrong shard)."""
        from jax.sharding import Mesh

        devs = jax.devices()
        if len(devs) < 3:
            pytest.skip("needs >= 3 (emulated) devices")
        mesh = Mesh(np.asarray(devs[:3]).reshape(1, 3), ("data", "model"))
        rng = np.random.default_rng(17)
        q, k, v = _rand_qkv(rng, 2, 4, 2, 8, 16)  # 2 kv heads, tp=3
        pos = jnp.asarray([3, 9], jnp.int32)
        fn = shard_decode_attention(mesh, impl="reference")
        np.testing.assert_allclose(
            np.asarray(fn(q, k, v, pos)),
            np.asarray(decode_attention(q, k, v, pos, impl="reference")),
            atol=1e-6, rtol=1e-6,
        )


# ---- heads packed on lanes: the kernel walks a lane's live rows itself ----
#
# ``_packed_call`` (``packed_decode_attention``, ``diff_decode_attention``)
# at the two serve cells' row widths, lanes and lengths cut down. The
# parent's form of the same call, a grid over every block of a lane
# with Pallas fetching the live ones, is kept HERE as the reference the
# walk is bit-equal to: same absorbs, same order, same masks.

# (kind, row width, lane length): the SambaY cell's shared rows and its
# window's ring (copies of 256 rows), the hybrid cell's rows (1,024).
_WALKS = {
    "diff_shared": ("diff", 1280, 1024),
    "diff_ring": ("diff", 1280, 512),
    "packed": ("packed", 512, 2048),
}
_BLOCK = decode_ops.DEFAULT_BLOCK_K


def _edge_positions(W, L, block_k=_BLOCK):
    """0, a tile's edge, an absorb's, a copy's, the lane's end: mixed in
    one call, a long lane before a short one and after it."""
    rows = copy_rows(L, W, block_k)
    edges = {0, 7, 8, block_k - 1, block_k, block_k + 1, rows - 1, rows,
             rows + 1, 2 * rows - 1, 2 * rows, L - block_k - 1, L - 1}
    pos = sorted(p for p in edges if 0 <= p < L)
    return pos[1::2] + pos[::2][::-1]


def _walk_inputs(kind, W, L, pos, depth=2, seed=0):
    Dh = 64
    H_kv = W // Dh
    H = 2 * H_kv if kind == "diff" else 4 * H_kv
    kq, kk, kv = jax.random.split(jax.random.key(seed), 3)
    S = len(pos)
    q = jax.random.normal(kq, (S, H, Dh), jnp.float32)
    k = jax.random.normal(kk, (depth, S, L, W), jnp.float32)
    v = jax.random.normal(kv, (depth, S, L, W), jnp.float32)
    return q, k, v, jnp.asarray(pos, jnp.int32)


def _walk(kind, q, k, v, pos, **kw):
    if kind == "diff":
        return diff_decode_attention(q, k, v, pos, impl="flash", **kw)
    return packed_decode_attention(q, k, v, pos, impl="flash",
                                   scale=1 / 64, **kw)


def _walk_reference(kind, q, k, v, pos, layer):
    if kind == "diff":
        return diff_decode_attention_reference(q, k[layer], v[layer], pos)
    S, L, W = k.shape[1:]
    heads = lambda c: c[layer].reshape(S, L, W // 64, 64)
    return decode_attention_reference(q, heads(k), heads(v), pos,
                                      scale=1 / 64)


def _grid_packed_call(qp, k, v, pos, *, layer, block_k, interpret, scale):
    """``_packed_call`` as the parent commit had it: grid ``(S, L //
    block_k)``, K and V blocks fetched by Pallas under ``live_block``,
    the shared grid skeleton around the same absorbs."""
    S, C, R, _ = qp.shape
    L, W = k.shape[2], k.shape[3]
    block_k = decode_block(L, 1, W, k.dtype, block_k)

    def kernel(pos_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref):
        def body(j, p):
            for c in range(C):
                lanes = slice(c * 128, (c + 1) * 128)
                decode_ops._absorb_block(
                    c, q_ref[c].astype(jnp.float32) * scale,
                    k_ref[:, lanes].astype(jnp.float32),
                    v_ref[:, lanes].astype(jnp.float32),
                    j, p, block_k, acc_ref, m_ref, l_ref)

        decode_ops._online_softmax_grid(
            pos_ref, o_ref, acc_ref, m_ref, l_ref, block_k, body)

    vmem = {"memory_space": pltpu.VMEM}
    qspec = pl.BlockSpec(
        (None, C, R, 128), lambda s, j, pos_ref: (s, 0, 0, 0), **vmem)
    kvspec = pl.BlockSpec(
        (None, None, block_k, W),
        lambda s, j, pos_ref: (
            layer, s, live_block(j, pos_ref[s], block_k), 0), **vmem)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(S, L // block_k),
            in_specs=[qspec, kvspec, kvspec], out_specs=qspec,
            scratch_shapes=[pltpu.VMEM((C, R, 128), jnp.float32)] * 3),
        out_shape=jax.ShapeDtypeStruct((S, C, R, 128), jnp.float32),
        interpret=interpret, name="flash_decode",
    )(pos.astype(jnp.int32), qp, k, v)


class TestWalk:
    @pytest.mark.parametrize("mode", ["interpret", "tpu_interpret"])
    @pytest.mark.parametrize("case", _WALKS)
    def test_matches_reference_at_every_edge(self, case, mode):
        """Every position where a tile, an absorb, a copy or the lane
        ends, mixed in one call (so a lane's first copy is started by
        the lane before it, into the slot that lane left free), in both
        of Pallas's interpreters."""
        kind, W, L = _WALKS[case]
        q, k, v, pos = _walk_inputs(kind, W, L, _edge_positions(W, L))
        interpret = True if mode == "interpret" else pltpu.InterpretParams()
        got = _walk(kind, q, k, v, pos, layer=1, interpret=interpret)
        want = _walk_reference(kind, q, k, v, pos, 1)
        assert float(jnp.abs(got.reshape(want.shape) - want).max()) < 2e-5

    @pytest.mark.parametrize("written", [300, 511, 512, 5000])
    def test_a_ring_before_and_after_it_wraps(self, written):
        """A window's ring of 512 rows is handed the count of valid
        rows less one: a ring that has not wrapped reads its first
        rows, one that has reads them all."""
        kind, W, L = _WALKS["diff_ring"]
        last = [min(written, L) - 1, 0, min(written + 7, L) - 1]
        q, k, v, pos = _walk_inputs(kind, W, L, last, seed=written)
        got = _walk(kind, q, k, v, pos, layer=0, interpret=True)
        want = _walk_reference(kind, q, k, v, pos, 0)
        assert float(jnp.abs(got - want).max()) < 2e-5

    @pytest.mark.parametrize("block_k", [32, 128])
    @pytest.mark.parametrize("case", _WALKS)
    def test_bit_equal_to_the_grid_form(self, case, block_k, monkeypatch):
        """The same absorbs in the same order under the same masks: the
        walk's output is the parent's grid form's, bit for bit."""
        kind, W, L = _WALKS[case]
        L = min(L, 1024)
        q, k, v, pos = _walk_inputs(
            kind, W, L, _edge_positions(W, L, block_k), seed=block_k)
        got = _walk(kind, q, k, v, pos, layer=1, block_k=block_k,
                    interpret=True)
        monkeypatch.setattr(decode_ops, "_packed_call", _grid_packed_call)
        grid = _walk(kind, q, k, v, pos, layer=1, block_k=block_k,
                     interpret=True)
        assert jnp.array_equal(got, grid)

    @pytest.mark.parametrize("fill", [float("nan"), 3e38])
    @pytest.mark.parametrize("case", _WALKS)
    def test_rows_past_pos_reach_nothing(self, case, fill):
        """Rows past a lane's position hold ``fill`` in HBM, and so does
        EVERY row of the lanes in between (whose outputs are not read):
        a short lane's slot then holds ``fill`` behind the blocks its
        last copy brought (stale VMEM, not only unattended HBM). Output
        and statistics of the lanes read are the clean cache's."""
        kind, W, L = _WALKS[case]
        rows = copy_rows(L, W, _BLOCK)
        # lanes 1, 3, 5 are filled to the brim and fill both slots
        pos = [3, L - 1, 0, L - 1, _BLOCK + 5, min(2 * rows, L) - 1, rows + 2, 9]
        read = np.asarray([0, 2, 4, 6, 7])
        q, k, v, pos = _walk_inputs(kind, W, L, pos, seed=3)
        want = _walk_reference(kind, q, k, v, pos, 1)
        dead = jnp.arange(L)[None, :, None] > pos[:, None, None]
        dead = dead.at[jnp.asarray([1, 3, 5])].set(True)
        k, v = (x.at[1].set(jnp.where(dead, fill, x[1])) for x in (k, v))
        got = _walk(kind, q, k, v, pos, layer=1, interpret=True)
        got = np.asarray(got.reshape(want.shape))[read]
        assert np.isfinite(got).all()
        np.testing.assert_allclose(got, np.asarray(want)[read],
                                   atol=2e-5, rtol=2e-5)

    @pytest.mark.parametrize("pos", [
        [0], [7], [8], [127], [128], [255], [256], [1023], [5, 600, 0, 1000],
    ])
    def test_fetched_rows_are_the_copies_the_kernel_starts(
            self, pos, monkeypatch):
        """Every DMA the interpreted kernel starts is counted by its
        rows: a lane's are its live blocks of 128 rows, whatever a copy
        holds, once for K and once for V."""
        kind, W, L = _WALKS["diff_shared"]
        started = []
        make = pltpu.make_async_copy

        class Counted:
            def __init__(self, src, dst, sem):
                self.dma, self.rows = make(src, dst, sem), dst.shape[0]

            def start(self):
                jax.debug.callback(lambda: started.append(self.rows))
                self.dma.start()

            def wait(self):
                self.dma.wait()

        monkeypatch.setattr(pltpu, "make_async_copy", Counted)
        q, k, v, pos = _walk_inputs(kind, W, L, pos, depth=1)
        # the call's program is traced once a shape: not one traced
        # before the count, and not the counted one after it
        decode_ops._walk_call.clear_cache()
        try:
            jax.block_until_ready(_walk(kind, q, k, v, pos, interpret=True))
            jax.effects_barrier()
        finally:
            decode_ops._walk_call.clear_cache()
        want = fetched_rows(pos, L)
        assert sum(started) == 2 * int(want.sum())
        assert [int(r) for r in want] == [(p // 128 + 1) * 128 for p in pos]
        assert int(fetched_rows(jnp.int32(L + 5), L)) == L  # never past the lane

    @pytest.mark.parametrize("case", _WALKS)
    def test_leaves_its_plan_record(self, case):
        """One ``decode.plan`` a traced call: the walk, one grid step a
        lane, rows an absorb and a copy, two copies in flight a stream,
        the row width and lane length, the last copy not cut."""
        kind, W, L = _WALKS[case]
        q, k, v, pos = _walk_inputs(kind, W, L, [3, L - 1], depth=1)
        ring = get_tracer().ring
        before = sum(e[0] == "decode.plan" for e in ring())
        jax.make_jaxpr(functools.partial(_walk, kind, interpret=True))(
            q, k, v, pos)
        plans = [e[4] for e in ring() if e[0] == "decode.plan"]
        assert len(plans) == before + 1
        rows = {1280: 256, 512: 1024}[W]
        assert plans[-1] == ("walk", 1, 128, rows, 2, W, L, 0)
        assert len(SPAN_NUMS["decode.plan"]) == len(plans[-1])

    def test_the_reading_layers_of_a_buffer_are_one_program(self):
        """The layer rides scalar prefetch: a step's calls on one stored
        buffer trace (and lower, and compile) the kernel once, whatever
        layer each reads, and still read their own layer."""
        kind, W, L = _WALKS["diff_ring"]
        q, k, v, pos = _walk_inputs(kind, W, L, [3, L - 1], depth=3)
        step = lambda q, k, v, p: [
            _walk(kind, q, k, v, p, layer=i, interpret=True)
            for i in range(3)]
        calls = [e for e in jax.make_jaxpr(step)(q, k, v, pos).eqns
                 if e.params.get("name") == "_walk_call"]
        assert len(calls) == 3
        assert len({id(e.params["jaxpr"]) for e in calls}) == 1
        for i, got in enumerate(jax.jit(step)(q, k, v, pos)):
            want = _walk_reference(kind, q, k, v, pos, i)
            assert float(jnp.abs(got - want).max()) < 2e-5

    def test_a_lane_shorter_than_an_absorb_is_one_copy(self):
        """A lane of 16 rows: the absorb, the copy and the lane are one
        block, fetched whole."""
        q, k, v, pos = _walk_inputs("packed", 512, 16, [3, 15, 0], depth=1)
        got = _walk("packed", q, k, v, pos, interpret=True)
        want = _walk_reference("packed", q, k, v, pos, 0)
        assert float(jnp.abs(got - want).max()) < 2e-5
        assert [int(r) for r in fetched_rows(pos, 16, 16)] == [16, 16, 16]
