"""Paged KV cache + radix prefix reuse (PR 12).

The acceptance pins:

- **Token identity**: the paged engine (page-pool cache + page-table
  gather/scatter, serve/pages.py + models/generate.PagedSlotCache) is
  token-identical to the fixed-lane cache for greedy AND seeded
  sampling, across chunk-bucket edges, page boundaries, a forked
  prefix pair (the reuse path really serves cached pages), int8
  pools, the flash kernel, and speculative decoding.
- **Transfer shapes**: with paging AND ``--sanitize`` on, the
  steady-state device→host reads stay ``()``/``[S]`` int32 — the
  PR-3 invariant re-pinned over the new layout (table uploads are
  host→device and happen only at bind/retire).
- **Allocator soundness**: a randomized acquire/release property test
  drives PrefixCache through shared-prefix traffic with eviction
  pressure — no page freed while mapped, no leak after retire, LRU
  eviction only ever frees refcount-0 cached prefixes.
- **Default-off control**: with paging off the /metricsz exposition
  is byte-identical (no prefix/pages metric appears at all).
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ddp_tpu.models.generate import generate
from ddp_tpu.models.lm import LMSpec, init_lm
from ddp_tpu.serve.engine import COMPLETE, ServeEngine
from ddp_tpu.serve.pages import PrefixCache, page_demand

SPEC = LMSpec(vocab_size=37, total_len=32, d_model=32, depth=2, num_heads=4)


@pytest.fixture(scope="module")
def params():
    return init_lm(SPEC, seed=0)


def _reference(spec, params, prompt, n, **kw):
    out = generate(
        spec, params, np.asarray([prompt]), max_new_tokens=n, **kw
    )
    return [int(t) for t in np.asarray(out)[0][len(prompt):]]


class TestTokenIdentity:
    def test_bucket_and_page_boundary_greedy(self, params):
        """Greedy outputs identical to generate() for prompt lengths
        straddling every bucket edge AND page boundary (page_size 8 →
        boundaries at 8/16; buckets {4, 8}), staggered admission."""
        eng = ServeEngine(
            SPEC, params, slots=2, prefill_len=16,
            prefill_chunk=8, min_bucket=4, page_size=8,
        )
        assert eng.buckets == [4, 8]
        reqs = []
        for plen in (1, 3, 4, 7, 8, 9, 12, 15, 16):
            prompt = [(7 * plen + i) % SPEC.vocab_size for i in range(plen)]
            reqs.append((prompt, eng.submit(prompt, 5).request))
            eng.step()
        eng.run()
        for prompt, req in reqs:
            got = eng.result(req.rid)
            assert got.status == COMPLETE
            assert got.tokens == _reference(SPEC, params, prompt, 5), (
                f"prompt_len {len(prompt)} diverged over the paged cache"
            )

    def test_seeded_sampling_matches_generate(self, params):
        """Seeded temperature/top-p sampling over the paged cache:
        same fold_in stream as generate(), mixed-config batch."""
        eng = ServeEngine(
            SPEC, params, slots=3, prefill_len=8, min_bucket=4,
            page_size=4,
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
            assert got.status == COMPLETE
            assert got.tokens == _reference(SPEC, params, p, n, **kw)

    def test_forked_prefix_pair(self, params):
        """THE reuse pin: a retired prompt's pages serve later
        requests sharing its prefix — zero prefill for the matched
        tokens, page-shared while both forks decode, and the outputs
        stay exactly generate()'s."""
        eng = ServeEngine(
            SPEC, params, slots=2, prefill_len=16, page_size=4,
        )
        pre = [(3 * i + 2) % SPEC.vocab_size for i in range(12)]
        a = eng.submit(pre + [1, 2], 6).request
        eng.run()  # A publishes the 12-token (3-page) prefix
        b = eng.submit(pre + [9, 9], 6).request
        c = eng.submit(pre + [4], 6).request
        shared_seen = 0
        while eng.pending:
            eng.step()
            shared_seen = max(shared_seen, eng.page_stats()["pages_shared"])
        for req, prompt in ((a, pre + [1, 2]), (b, pre + [9, 9]),
                            (c, pre + [4])):
            got = eng.result(req.rid)
            assert got.status == COMPLETE
            assert got.tokens == _reference(SPEC, params, prompt, 6)
        assert eng.result(a.rid).prefix_hit_tokens == 0  # the miss
        assert eng.result(b.rid).prefix_hit_tokens == 12
        assert eng.result(c.rid).prefix_hit_tokens == 12
        # B and C decoded concurrently over the same prefix pages.
        assert shared_seen >= 3, (
            f"forked lanes never shared the prefix pages "
            f"(peak shared={shared_seen})"
        )
        st = eng.page_stats()
        assert st["prefix_hits"] == 2 and st["prefix_misses"] == 1
        eng._prefix.check_invariants()

    def test_int8_paged_matches_int8_fixed_lane(self, params):
        """int8 pools quantize-on-write per page; outputs must equal
        the fixed-lane int8 engine token for token (quantization
        moves numerics off generate(), so the pin is engine vs
        engine), including through a prefix hit — cached pages store
        the SAME int8 rows + scales a private lane would."""
        pre = [(5 * i + 1) % SPEC.vocab_size for i in range(9)]
        prompts = [pre + [2], pre + [3], [4, 4]]

        def run(**kw):
            eng = ServeEngine(
                SPEC, params, slots=2, prefill_len=16,
                kv_dtype="int8", **kw,
            )
            out = []
            for p in prompts:
                r = eng.submit(p, 5).request
                eng.run()  # sequential: the paged run hits on p[1]
                out.append(eng.result(r.rid).tokens)
            return eng, out

        eng_paged, paged = run(page_size=8)
        _, fixed = run()
        assert paged == fixed
        assert eng_paged.page_stats()["prefix_hits"] == 1

    def test_flash_impl_matches_reference_paged(self, params):
        """decode_attn='flash' over the paged cache (Pallas interpret
        mode off-TPU, block_k = page_size) equals the reference paged
        engine token for token."""
        prompt = [(2 * i + 3) % SPEC.vocab_size for i in range(11)]

        def run(impl):
            eng = ServeEngine(
                SPEC, params, slots=2, prefill_len=16, page_size=8,
                decode_attn=impl,
            )
            r = eng.submit(prompt, 6).request
            eng.run()
            return eng.result(r.rid).tokens

        assert run("flash") == run("reference")
        assert run("reference") == _reference(SPEC, params, prompt, 6)

    @pytest.mark.parametrize("kv_dtype", ["fp32", "int8"])
    def test_gathered_view_is_a_depth_one_stored_cache(self, kv_dtype):
        """One kernel path: ``paged_decode_attention`` hands its
        gathered lane views to the stored-layout kernel as a depth-1
        cache, so paged flash equals fixed-lane flash over the same
        logical lanes — with the scratch page and every row past
        ``pos`` poisoned, which the banded read must never see."""
        from ddp_tpu.ops.decode import (
            decode_attention_reference,
            flash_decode_attention,
            paged_decode_attention,
            quantize_kv,
        )

        S, H, H_kv, Dh, L, ps = 3, 4, 2, 8, 64, 16
        rng = np.random.default_rng(12)
        q = jnp.asarray(rng.normal(size=(S, H, Dh)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(S, L, H_kv, Dh)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(S, L, H_kv, Dh)), jnp.float32)
        pos = jnp.asarray([0, 17, L - 1], jnp.int32)
        ks = vs = None
        if kv_dtype == "int8":
            (k, ks), (v, vs) = quantize_kv(k), quantize_kv(v)
        ref = decode_attention_reference(q, k, v, pos, ks, vs)
        fixed = flash_decode_attention(q, k, v, pos, ks, vs, block_k=32)
        n = L // ps
        table = 1 + rng.permutation(S * n).reshape(S, n).astype(np.int32)
        # pages wholly past a lane's position map to the scratch page,
        # as an engine's unallocated tail does
        table = np.where(
            np.arange(n)[None, :] * ps > np.asarray(pos)[:, None], 0, table
        )

        def pool(x, poison):
            pages = x.reshape(S * n, ps, *x.shape[2:])
            out = jnp.full((S * n + 1, *pages.shape[1:]), poison, x.dtype)
            live = table.reshape(-1) > 0
            return out.at[table.reshape(-1)[live]].set(pages[live])

        nan = jnp.nan
        pools = (
            (pool(k, 77), pool(v, 77), pool(ks, nan), pool(vs, nan))
            if kv_dtype == "int8"
            else (pool(k, nan), pool(v, nan), None, None)
        )
        paged = paged_decode_attention(
            q, pools[0], pools[1], jnp.asarray(table), pos, *pools[2:],
            impl="flash",
        )
        assert np.isfinite(np.asarray(paged)).all()
        for want in (fixed, ref):
            np.testing.assert_allclose(
                np.asarray(paged), np.asarray(want), atol=1e-5, rtol=1e-5
            )

    def test_speculative_paged_identity(self, params):
        """Spec decoding over a paged target cache (fixed-lane draft):
        greedy AND seeded streams identical to generate(), and a
        prefix hit degrades only draft acceptance, never output."""
        draft_spec = SPEC._replace(depth=1)
        draft_params = {
            k: params[k]
            for k in ["embed", "pos_embed", "ln_final", "block1"]
        }
        eng = ServeEngine(
            SPEC, params, slots=2, prefill_len=8, page_size=4,
            draft_spec=draft_spec, draft_params=draft_params,
            spec_tokens=3,
        )
        r1 = eng.submit([1, 2, 3], 8).request
        r2 = eng.submit(
            [1, 2, 3, 4], 8, temperature=0.9, top_p=0.8, seed=5
        ).request
        eng.run()
        assert eng.result(r1.rid).tokens == _reference(
            SPEC, params, [1, 2, 3], 8
        )
        assert eng.result(r2.rid).tokens == _reference(
            SPEC, params, [1, 2, 3, 4], 8,
            temperature=0.9, top_p=0.8, seed=5,
        )
        # Forked under speculation: the hit skips TARGET prefill only.
        r3 = eng.submit([1, 2, 3, 4, 9], 6).request
        eng.run()
        got = eng.result(r3.rid)
        assert got.prefix_hit_tokens == 4
        assert got.tokens == _reference(SPEC, params, [1, 2, 3, 4, 9], 6)
        eng._prefix.check_invariants()

    def test_lru_eviction_keeps_correctness(self, params):
        """A pool too small to cache every retired prompt must evict
        LRU prefixes — and stay token-exact for every request."""
        eng = ServeEngine(
            SPEC, params, slots=1, prefill_len=16, page_size=4,
            kv_pages=10,  # 1 lane of 8 pages + 1 spare + scratch
        )
        outs = {}
        for j in range(4):  # distinct prompts: each retire caches, the
            prompt = [(j * 7 + i) % SPEC.vocab_size for i in range(9)]
            r = eng.submit(prompt, 4).request  # next bind must evict
            eng.run()
            outs[r.rid] = (prompt, eng.result(r.rid).tokens)
        for prompt, toks in outs.values():
            assert toks == _reference(SPEC, params, prompt, 4)
        assert eng.page_stats()["evicted_pages"] > 0
        eng._prefix.check_invariants()


class TestTransfersAndCompiles:
    def test_steady_state_transfer_is_slot_tokens(self, params,
                                                  monkeypatch):
        """The transfer spy re-pin (ISSUE 12): paged + --sanitize,
        all lanes decoding — device→host reads stay ()/[S] int32,
        never logits, never page tables (those are host→device and
        bind-time only)."""
        import ddp_tpu.serve.engine as engine_mod

        eng = ServeEngine(
            SPEC, params, slots=2, prefill_len=8, page_size=8,
            sanitize=True,
        )
        eng.submit([1, 2, 3], 12)
        eng.submit([4, 5], 12)
        for _ in range(3):
            eng.step()

        fetched = []
        real_np = np

        class _NpSpy:
            def asarray(self, x, *a, **k):
                if isinstance(x, jax.Array):
                    fetched.append(tuple(x.shape))
                return real_np.asarray(x, *a, **k)

            def __getattr__(self, name):
                return getattr(real_np, name)

        monkeypatch.setattr(engine_mod, "np", _NpSpy())
        for _ in range(4):
            eng.step()
        monkeypatch.undo()
        assert fetched, "steady-state steps fetched nothing"
        assert all(
            shape == () or shape == (eng.num_slots,) for shape in fetched
        ), f"paged steady state fetched non-token arrays: {fetched}"
        assert eng._toks.shape == (2,) and eng._toks.dtype == jnp.int32
        eng.run()

    def test_no_recompilation_after_warmup(self, params):
        """Static-shape pin over the paged program set: warmup
        enumerates everything; hits, misses, evictions and retires
        compile nothing further (tables/pos mutate as DATA)."""
        eng = ServeEngine(
            SPEC, params, slots=2, prefill_len=16, page_size=8,
        )
        counts = eng.warmup()
        assert sum(counts.values()) <= eng.compile_budget()
        pre = [(i * 3 + 1) % SPEC.vocab_size for i in range(9)]
        for tail in ([1], [2], [3, 4]):
            eng.submit(pre + tail, 4)
            eng.step()
        eng.run()
        assert eng.page_stats()["prefix_hits"] >= 1
        assert eng.compile_counts() == counts, (
            f"paged engine recompiled: {counts} -> "
            f"{eng.compile_counts()}"
        )

    def test_metricsz_byte_identical_when_off(self, params):
        """Default-off control: a fixed-lane engine's exposition
        carries NO paged metric; a paged engine's does and lints."""
        from ddp_tpu.obs.promtext import render_serve, validate_promtext

        off = ServeEngine(SPEC, params, slots=2, prefill_len=8)
        text_off = render_serve(off.stats(), up=True)
        assert not re.search(r"prefix|pages", text_off), (
            "paged metrics leaked into the fixed-lane exposition"
        )
        on = ServeEngine(
            SPEC, params, slots=2, prefill_len=8, page_size=8,
        )
        on.submit([1, 2, 3], 4)
        on.run()
        text_on = render_serve(on.stats(), up=True)
        validate_promtext(text_on)
        for name in (
            "ddp_tpu_serve_prefix_hits_total",
            "ddp_tpu_serve_prefix_misses_total",
            "ddp_tpu_serve_prefix_hit_rate",
            "ddp_tpu_serve_pages_free",
            "ddp_tpu_serve_pages_resident",
            "ddp_tpu_serve_pages_shared",
        ):
            assert name in text_on, f"missing paged gauge {name}"

    def test_page_starved_admission_requeues_fifo(self, params):
        """Free-page admission: a pool with room for one lane's
        demand at a time delays the second request (requeued at the
        FRONT, retried after the first retires) instead of failing
        it; both complete exactly."""
        eng = ServeEngine(
            SPEC, params, slots=2, prefill_len=16, page_size=4,
            kv_pages=9,  # scratch + 8 = exactly one full lane
        )
        p1 = [(i + 1) % SPEC.vocab_size for i in range(12)]
        p2 = [(i + 5) % SPEC.vocab_size for i in range(12)]
        r1 = eng.submit(p1, 8).request  # 5 pages each: 10 > the 8
        r2 = eng.submit(p2, 8).request  # usable — the second waits
        eng.run()
        assert eng.result(r1.rid).status == COMPLETE
        assert eng.result(r2.rid).status == COMPLETE
        assert eng.result(r1.rid).tokens == _reference(
            SPEC, params, p1, 8
        )
        assert eng.result(r2.rid).tokens == _reference(
            SPEC, params, p2, 8
        )
        assert eng.page_starved_binds > 0
        # FIFO held: the starved head finished before the follower.
        assert (
            eng.result(r1.rid).finished <= eng.result(r2.rid).finished
        )
        eng._prefix.check_invariants()


class TestConstructionValidation:
    def test_rejection_matrix(self, params):
        cases = [
            (dict(page_size=3), "power of two"),
            (dict(page_size=2, kv_pages=3), "--kv_pages"),
            (dict(kv_pages=64), "--kv_pages needs --page_size"),
        ]
        for kw, match in cases:
            with pytest.raises(ValueError, match=match):
                ServeEngine(SPEC, params, slots=2, prefill_len=8, **kw)
        # page_size not dividing total_len (33 is not pow2-divisible)
        spec = SPEC._replace(total_len=40)
        with pytest.raises(ValueError, match="must divide"):
            ServeEngine(
                spec, init_lm(spec, seed=1), slots=1, prefill_len=8,
                page_size=16,
            )

    def test_page_demand_accounts_gamma_reserve(self):
        """The PR-10 admission-ceiling interaction, in pages: the
        speculative γ-1 write reserve widens the lane's page demand
        so a verify-round scatter can never target an unowned page."""
        base = page_demand(9, 6, 4, total_len=32)
        with_reserve = page_demand(9, 6, 4, total_len=32, reserve=3)
        assert base == -(-15 // 4) and with_reserve == -(-18 // 4)
        assert with_reserve > base
        # ...and capped at the position table.
        assert page_demand(9, 100, 4, total_len=32, reserve=3) == 8

    def test_spec_engine_allocates_reserve_pages(self, params):
        """A paged speculative engine's bind really maps the γ
        reserve: lane demand in pages covers prompt + budget + γ-1."""
        draft_spec = SPEC._replace(depth=1)
        draft_params = None  # filled below

        def dp(p):
            return {
                k: p[k]
                for k in ["embed", "pos_embed", "ln_final", "block1"]
            }

        eng = ServeEngine(
            SPEC, params, slots=1, prefill_len=8, page_size=4,
            draft_spec=draft_spec, draft_params=dp(params),
            spec_tokens=3,
        )
        eng.submit([1, 2, 3, 4, 5], 6).request
        eng.step()
        slot = eng._slots[0]
        want = page_demand(
            5, 6, 4, total_len=SPEC.total_len, reserve=2
        )
        assert len(slot.pages) == want
        eng.run()


class TestPrefixCacheProperty:
    def test_refcount_eviction_property(self):
        """Randomized acquire/decode/release traffic with eviction
        pressure: after every operation the allocator invariants hold
        (no page freed while mapped, free/mapped/cached partition the
        pool, cached ⊆ indexed), and full retirement leaks nothing."""
        rng = np.random.default_rng(7)
        ps, total = 4, 32
        cache = PrefixCache(num_pages=24, page_size=ps)
        prefixes = [
            [int(t) for t in rng.integers(0, 50, 12)] for _ in range(3)
        ]
        live = []  # (tokens, pids, prefilled)
        for step in range(300):
            op = rng.random()
            if op < 0.55 and len(live) < 5:
                pre = prefixes[int(rng.integers(0, len(prefixes)))]
                tail = [int(t) for t in rng.integers(0, 50, int(
                    rng.integers(1, 6)))]
                tokens = pre + tail
                demand = page_demand(
                    len(tokens), int(rng.integers(1, 8)), ps,
                    total_len=total,
                )
                got = cache.acquire(tokens, demand)
                if got is not None:
                    pids, matched = got
                    assert len(pids) == demand
                    assert matched % ps == 0
                    assert matched <= len(tokens) - 1
                    live.append((tokens, pids, len(tokens)))
            elif live:
                i = int(rng.integers(0, len(live)))
                tokens, pids, prefilled = live.pop(i)
                if rng.random() < 0.2:  # mid-prefill eviction path
                    prefilled = int(rng.integers(0, len(tokens)))
                cache.release(tokens, pids, prefilled)
            cache.check_invariants()
        for tokens, pids, prefilled in live:
            cache.release(tokens, pids, prefilled)
        cache.check_invariants()
        # Nothing mapped → pool is all free + cached prefixes.
        assert cache.mapped_pages == 0
        assert cache.free_pages + cache.cached_pages == (
            cache.num_pages - 1
        )

    def test_no_eviction_of_mapped_prefix(self):
        """Allocation pressure must never free a page a lane maps —
        including prefix pages matched in the SAME acquire."""
        ps = 2
        cache = PrefixCache(num_pages=8, page_size=ps)
        a = cache.acquire([1, 2, 3, 4, 5], 3)  # 3 pages
        assert a is not None
        cache.release([1, 2, 3, 4, 5], a[0], 5)  # caches 2 pages
        # Hit the cached prefix, then demand enough to force the
        # allocator through eviction: only the UNMATCHED cached page
        # may go.
        b = cache.acquire([1, 2, 3, 4, 9], 7)  # all non-scratch pages
        assert b is not None
        pids, matched = b
        assert matched == 4  # both full prefix pages hit
        cache.check_invariants()
        assert cache.mapped_pages == 7
        cache.release([1, 2, 3, 4, 9], pids, 5)
        cache.check_invariants()

    def test_starved_acquire_does_not_evict_prefixes(self):
        """An acquire that CANNOT succeed (demand > free + cached,
        the rest mapped by live lanes) must fail without evicting a
        single cached prefix: the starved head retries every step,
        and draining the index for a doomed allocation would collapse
        the hit rate for everyone else while it waits."""
        ps = 2
        cache = PrefixCache(num_pages=8, page_size=ps)  # 7 usable
        a_tok = [1, 2, 3, 4, 5]
        a_pids, _ = cache.acquire(a_tok, 4)  # lane A maps 4
        b_tok = [9, 8, 7, 6, 5]
        b_pids, _ = cache.acquire(b_tok, 3)  # lane B maps the rest
        cache.release(b_tok, b_pids, 5)  # B's 2 full pages cached
        assert cache.cached_pages == 2 and cache.free_pages == 1
        # Demand 7 with 4 pages pinned by lane A: unattainable.
        assert cache.acquire([40, 41, 42, 43, 44, 45, 46], 7) is None
        assert cache.cached_pages == 2, "doomed acquire evicted prefixes"
        assert cache.evicted_pages == 0
        cache.check_invariants()
        # Once A retires, the same demand succeeds (evicting then is
        # legitimate pressure).
        cache.release(a_tok, a_pids, 5)
        got = cache.acquire([40, 41, 42, 43, 44, 45, 46], 7)
        assert got is not None and len(got[0]) == 7
        cache.check_invariants()

    def test_release_publishes_only_full_prefilled_pages(self):
        ps = 4
        cache = PrefixCache(num_pages=16, page_size=ps)
        tokens = list(range(10))  # 2 full pages + a 2-token tail
        pids, matched = cache.acquire(tokens, 4)
        assert matched == 0
        # Evicted after prefilling only 5 tokens: just ONE page is
        # publishable (positions 4..9 never fully written per-page).
        cache.release(tokens, pids, prefilled_tokens=5)
        assert cache.cached_pages == 1
        # A rerun matches exactly that one page.
        pids2, matched2 = cache.acquire(tokens, 4)
        assert matched2 == ps
        cache.release(tokens, pids2, prefilled_tokens=10)
        assert cache.cached_pages == 2  # full prompt pages, tail never
        cache.check_invariants()
