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

from ddp_tpu.models.generate import (
    generate,
    init_slot_cache,
    slot_decode_step,
)
from ddp_tpu.models.lm import LMSpec, init_lm
from ddp_tpu.ops.decode import (
    decode_attention,
    decode_attention_reference,
    dequantize_kv,
    flash_decode_attention,
    live_block,
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
