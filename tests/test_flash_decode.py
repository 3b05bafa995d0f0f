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

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ddp_tpu.models.generate import generate, init_slot_cache
from ddp_tpu.models.lm import LMSpec, init_lm
from ddp_tpu.ops.decode import (
    decode_attention,
    decode_attention_reference,
    dequantize_kv,
    flash_decode_attention,
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


class TestKernel:
    @pytest.mark.parametrize(
        "S,H,H_kv,Dh,L,block_k",
        [
            (3, 4, 4, 8, 16, 8),    # MHA, two key blocks
            (2, 8, 2, 16, 32, 8),   # GQA group 4, four blocks
            (4, 4, 2, 8, 24, 16),   # 16 does not divide 24 → three blocks of 8
            (1, 2, 1, 4, 8, 128),   # block_k > L → clamped to L
        ],
    )
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
