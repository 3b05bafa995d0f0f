"""Speculative decoding: draft/verify equivalence + engine pins.

The spec-decode contract (ISSUE 10): the engine's speculative mode is
an EXECUTION STRATEGY, not a different sampler — greedy and seeded
streams are exactly the tokens the non-speculative loop emits, just
computed up to γ at a time. Layered pins:

- **Verify step** (models/generate.slot_verify_step): scoring K
  drafts in one batched forward reproduces the sequential
  slot_decode_sample_step stream position-for-position — full-match
  drafts advance γ tokens, garbage drafts still emit the correct
  next token (matched=0 → the target's own draw).
- **Engine**: spec mode is output-equivalent to the non-speculative
  engine (and therefore to generate()) for greedy AND seeded
  sampling, across bucket edges and staggered admission; acceptance
  is recorded per completion, per serve_step record, and in /stats;
  the compile-count pin extends to the draft/verify program set; the
  verify fetch stays small int32 ([S], [S, γ]) — never logits.
- **Front door**: draft/target mismatches (vocab, total_len, missing
  params) and budgets that cannot sustain γ-token decode lanes are
  construction errors, not runtime surprises.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ddp_tpu.models.generate import (
    generate,
    init_slot_cache,
    slot_decode_sample_step,
    slot_verify_step,
)
from ddp_tpu.models.lm import LMSpec, init_lm
from ddp_tpu.serve.engine import ServeEngine

SPEC = LMSpec(vocab_size=37, total_len=32, d_model=32, depth=2, num_heads=4)
DRAFT = SPEC._replace(d_model=16, depth=1, num_heads=2)


@pytest.fixture(scope="module")
def params():
    return init_lm(SPEC, seed=0)


@pytest.fixture(scope="module")
def draft_params():
    return init_lm(DRAFT, seed=1)


def _reference(spec, params, prompt, n, **sampling):
    return np.asarray(
        generate(
            spec, params, jnp.asarray([prompt], jnp.int32),
            max_new_tokens=n, **sampling,
        )
    )[0, len(prompt):].tolist()


def _spec_engine(params, draft_params, gamma=3, **kw):
    kw.setdefault("slots", 2)
    kw.setdefault("prefill_len", 8)
    return ServeEngine(
        SPEC, params, draft_spec=DRAFT, draft_params=draft_params,
        spec_tokens=gamma, **kw,
    )


class TestVerifyStep:
    def _state(self, params, t0, S=2, temps=0.0):
        """Feed one token per lane from an empty cache → (cache,
        next_token, sampling state): the smallest real decode state."""
        cache = init_slot_cache(SPEC, S)
        seeds = jnp.zeros((S,), jnp.int32)
        steps = jnp.ones((S,), jnp.int32)
        tv = jnp.full((S,), temps, jnp.float32)
        tp = jnp.ones((S,), jnp.float32)
        toks, cache, steps = slot_decode_sample_step(
            SPEC, params, cache, jnp.asarray(t0, jnp.int32),
            seeds, steps, tv, tp,
        )
        return cache, toks, seeds, steps, tv, tp

    def _sequential(self, params, cache, toks, seeds, steps, tv, tp, n):
        """The non-speculative stream: n more tokens, one step each."""
        out = []
        for _ in range(n):
            toks, cache, steps = slot_decode_sample_step(
                SPEC, params, cache, toks, seeds, steps, tv, tp,
            )
            out.append(np.asarray(toks))
        return np.stack(out, axis=1)  # [S, n]

    def test_full_match_advances_gamma(self, params):
        """Drafts equal to the true stream → matched=K, the verify's
        target tokens ARE the sequential stream, positions advance K."""
        K = 3
        cache, toks, seeds, steps, tv, tp = self._state(params, [5, 9])
        truth = self._sequential(
            params, cache, toks, seeds, steps, tv, tp, K
        )  # [S, K]
        nxt, vcache, vsteps, target, matched = slot_verify_step(
            SPEC, params, cache, toks, jnp.asarray(truth, jnp.int32),
            seeds, steps, tv, tp,
        )
        assert np.asarray(matched).tolist() == [K, K]
        np.testing.assert_array_equal(np.asarray(target), truth)
        np.testing.assert_array_equal(
            np.asarray(nxt), truth[:, -1]
        )
        np.testing.assert_array_equal(
            np.asarray(vcache.pos), np.asarray(cache.pos) + K
        )
        np.testing.assert_array_equal(
            np.asarray(vsteps), np.asarray(steps) + K
        )

    def test_garbage_drafts_still_emit_correct_token(self, params):
        """matched=0 lanes emit exactly one token — the target's own
        next draw — and advance one position: a useless draft costs
        speed, never correctness."""
        cache, toks, seeds, steps, tv, tp = self._state(params, [5, 9])
        truth = self._sequential(
            params, cache, toks, seeds, steps, tv, tp, 1
        )
        bad = (jnp.asarray(truth, jnp.int32) + 1) % SPEC.vocab_size
        drafts = jnp.concatenate(
            [bad, jnp.zeros((2, 2), jnp.int32)], axis=1
        )
        nxt, vcache, vsteps, target, matched = slot_verify_step(
            SPEC, params, cache, toks, drafts,
            seeds, steps, tv, tp,
        )
        assert np.asarray(matched).tolist() == [0, 0]
        np.testing.assert_array_equal(np.asarray(nxt), truth[:, 0])
        np.testing.assert_array_equal(
            np.asarray(vcache.pos), np.asarray(cache.pos) + 1
        )

    def test_seeded_sampling_same_fold_in_stream(self, params):
        """Seeded lanes: the verify samples position j under
        fold_in(key(seed), steps + j) — the exact non-speculative key
        — so target tokens equal the sequential sampled stream."""
        K = 3
        cache, toks, seeds, steps, tv, tp = self._state(
            params, [5, 9], temps=0.9
        )
        seeds = jnp.asarray([7, -3], jnp.int32)
        truth = self._sequential(
            params, cache, toks, seeds, steps, tv, tp, K
        )
        _, _, _, target, matched = slot_verify_step(
            SPEC, params, cache, toks, jnp.asarray(truth, jnp.int32),
            seeds, steps, tv, tp,
        )
        assert np.asarray(matched).tolist() == [K, K]
        np.testing.assert_array_equal(np.asarray(target), truth)


class TestSpecEngine:
    def test_greedy_equivalent_across_bucket_edges(self, params,
                                                   draft_params):
        """THE output-equivalence pin: speculative greedy === plain
        greedy === generate(), across bucket edges, staggered
        admission, mixed budgets — a small random draft's proposals
        mostly miss, so this exercises partial/zero acceptance too."""
        eng = _spec_engine(
            params, draft_params, gamma=3,
            prefill_len=16, prefill_chunk=8, min_bucket=4,
        )
        reqs = []
        for plen in (1, 4, 5, 8, 9, 15):
            prompt = [(7 * plen + i) % SPEC.vocab_size for i in range(plen)]
            reqs.append((prompt, eng.submit(prompt, 3 + plen % 4).request))
            eng.step()
        eng.run()
        for prompt, req in reqs:
            got = eng.result(req.rid)
            assert got.status == "complete"
            assert got.tokens == _reference(
                SPEC, params, prompt, req.max_new_tokens
            ), f"spec decode diverged at prompt_len {len(prompt)}"
            assert got.spec_acceptance is not None
            assert 0.0 <= got.spec_acceptance <= 1.0

    def test_seeded_equivalent(self, params, draft_params):
        """Seeded acceptance via the per-slot key machinery: sampled
        streams (negative seed included) match generate() exactly
        through draft/verify rounds."""
        eng = _spec_engine(params, draft_params, gamma=3, slots=3)
        cases = [
            ([3, 1, 4, 1], 6, dict(temperature=0.8, seed=7)),
            ([2, 7], 5, dict(temperature=1.3, top_p=0.9, seed=3)),
            ([5, 3, 5, 8], 4, dict(temperature=0.6, top_p=0.7,
                                   seed=-3)),
        ]
        reqs = [
            (p, n, kw, eng.submit(p, n, **kw).request)
            for p, n, kw in cases
        ]
        eng.run()
        for p, n, kw, req in reqs:
            assert eng.result(req.rid).tokens == _reference(
                SPEC, params, p, n, **kw
            ), f"spec + sampling config {kw} diverged"

    def test_selfdraft_acceptance_is_one(self, params):
        """Draft == target → every greedy proposal accepted: the
        acceptance accounting's upper anchor (and the γ-tokens-per-
        big-step mechanics)."""
        eng = ServeEngine(
            SPEC, params, slots=2, prefill_len=8,
            draft_spec=SPEC, draft_params=params, spec_tokens=3,
        )
        req = eng.submit([3, 1, 4], 9).request
        eng.run()
        got = eng.result(req.rid)
        assert got.tokens == _reference(SPEC, params, [3, 1, 4], 9)
        assert got.spec_acceptance == 1.0
        assert eng.spec_acceptance_rate() == 1.0
        assert eng.spec_drafted_total == eng.spec_accepted_total > 0

    def test_metrics_carry_acceptance(self, params, draft_params,
                                      tmp_path):
        """serve_step records carry per-step drafted/accepted counts,
        serve_request records the per-completion acceptance, and
        /stats + /metricsz expose the lifetime totals."""
        from ddp_tpu.obs.promtext import render_serve, validate_promtext
        from ddp_tpu.utils.metrics import MetricsWriter

        path = str(tmp_path / "serve.jsonl")
        writer = MetricsWriter(path)
        eng = _spec_engine(
            params, draft_params, gamma=3, metrics=writer,
        )
        eng.submit([1, 2, 3], 6)
        eng.run()
        writer.close()
        records = [
            json.loads(line) for line in open(path).read().splitlines()
        ]
        steps = [r for r in records if r["kind"] == "serve_step"]
        spec_steps = [r for r in steps if r.get("spec_drafted")]
        assert spec_steps, "no verify round reached the metrics stream"
        assert all(
            0 <= r["spec_accepted"] <= r["spec_drafted"]
            for r in spec_steps
        )
        reqs = [r for r in records if r["kind"] == "serve_request"]
        assert "spec_acceptance" in reqs[-1]
        st = eng.stats()["decode_path"]
        assert st["spec_tokens"] == 3
        assert st["spec_drafted_total"] >= st["spec_accepted_total"]
        assert st["spec_acceptance"] == eng.spec_acceptance_rate()
        text = render_serve(eng.stats(), up=True)
        validate_promtext(text)
        assert "ddp_tpu_serve_spec_drafted_total" in text
        assert "ddp_tpu_serve_cache_bytes_per_slot" in text

    def test_compile_counts_stable_and_labeled(self, params,
                                               draft_params):
        """The static-shape pin extends to speculation: warmup
        enumerates chunk programs for BOTH models plus draft-decode
        and verify, and a varied mix grows nothing. xprof labels name
        the new programs (serve.spec_verify, serve.draft_decode)."""
        from ddp_tpu.obs.xprof import Xprof

        xp = Xprof(enabled=True)
        eng = _spec_engine(
            params, draft_params, gamma=3, slots=3, min_bucket=4,
            xprof=xp,
        )
        warm = eng.warmup()
        assert warm["spec_verify"] == 1
        assert warm["draft_decode"] == 1
        assert sum(warm.values()) <= eng.compile_budget()
        for plen in (1, 3, 4, 6, 8):
            temp = 0.5 * (plen % 2)
            eng.submit(
                list(range(1, plen + 1)), 3 + plen % 3,
                temperature=temp, seed=plen,
            )
            eng.step()
        eng.run()
        assert eng.compile_counts() == warm, (
            "speculative mix recompiled the engine"
        )
        labels = {r["label"] for r in xp.ledger_records()}
        assert {"serve.spec_verify", "serve.draft_decode"} <= labels

    def test_transfer_stays_small_int32_under_sanitize(
        self, params, draft_params, monkeypatch
    ):
        """Spec mode's deliberate fetches are the [S] matched counts
        and [S, γ] target tokens (plus first-token scalars) — never a
        vocab-sized array — and the round runs under the transfer
        guard up to those fetches."""
        import ddp_tpu.serve.engine as engine_mod

        eng = _spec_engine(
            params, draft_params, gamma=3, sanitize=True,
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
                    fetched.append((tuple(x.shape), str(x.dtype)))
                return real_np.asarray(x, *a, **k)

            def __getattr__(self, name):
                return getattr(real_np, name)

        monkeypatch.setattr(engine_mod, "np", _NpSpy())
        for _ in range(3):
            eng.step()
        monkeypatch.undo()
        S, K = eng.num_slots, eng.spec_tokens
        assert fetched, "spec steps fetched nothing"
        allowed = {(), (S,), (S, K)}
        assert all(
            shape in allowed and dtype == "int32"
            for shape, dtype in fetched
        ), f"spec path fetched non-token arrays: {fetched}"
        eng.run()

    def test_budget_accounts_gamma_per_decode_lane(self, params,
                                                   draft_params):
        """scheduler/verify-step token budget: a decoding lane costs γ
        tokens, so the default budget grows to chunk + slots·γ and the
        construction floor rejects budgets that would starve prefill
        behind γ-wide verify rounds."""
        eng = _spec_engine(params, draft_params, gamma=3)
        assert eng.step_token_budget == eng.prefill_chunk + 2 * 3
        with pytest.raises(ValueError, match="step_token_budget"):
            _spec_engine(
                params, draft_params, gamma=3,
                min_bucket=8, step_token_budget=9,
            )
        # and the planner defers chunks behind γ-scaled decode lanes:
        # budget 16, 2 lanes decoding at γ=3 leaves 10 → an 8-wide
        # chunk fits, a 16-wide one shrinks.
        plan = eng.scheduler.plan_chunks([(0, 0, 16)], 2 * 3)
        assert plan and plan[0][1] <= eng.step_token_budget - 2 * 3

    def test_admission_reserves_verify_room(self, params, draft_params):
        """The verify round writes γ rows per lane: admission's
        context ceiling shrinks by γ-1 so a full-budget request can
        never clamp-shift the batched write over live lines."""
        gamma = 4
        eng = _spec_engine(params, draft_params, gamma=gamma)
        # total_len 32, ceiling 32 - (γ-1) = 29: an 8-prompt may book
        # at most 21 new tokens.
        assert eng.submit([1] * 8, 21).accepted
        adm = eng.submit([1] * 8, 22)
        assert not adm.accepted
        assert adm.reason == "budget_exceeds_context"

    def test_construction_validation(self, params, draft_params):
        with pytest.raises(ValueError, match="draft_spec AND"):
            ServeEngine(SPEC, params, spec_tokens=2)
        with pytest.raises(ValueError, match="vocab"):
            ServeEngine(
                SPEC, params, spec_tokens=2,
                draft_spec=DRAFT._replace(vocab_size=99),
                draft_params=draft_params,
            )
        with pytest.raises(ValueError, match="total_len"):
            ServeEngine(
                SPEC, params, spec_tokens=2,
                draft_spec=DRAFT._replace(total_len=64),
                draft_params=draft_params,
            )
        with pytest.raises(ValueError, match="spec_tokens"):
            ServeEngine(
                SPEC, params, prefill_len=8, spec_tokens=24,
                draft_spec=DRAFT, draft_params=draft_params,
            )


def _write_kv_rows_through_layer(cache, layer, k, v, pos):
    """How ``_write_kv_rows`` wrote a fixed-lane cache before PR 25:
    slice the layer out, update it lane by lane (the vmapped
    ``dynamic_update_slice``, which clamps the start), write the whole
    layer back. Kept here as the rule the in-place scatter must equal
    bit for bit."""
    from jax import lax

    from ddp_tpu.ops.decode import quantize_kv

    def write(lanes, rows):
        start = (0,) * (lanes.ndim - 2)
        return jax.vmap(
            lambda lane, row, p: lax.dynamic_update_slice(
                lane, row, (p, *start)
            )
        )(lanes, rows.astype(lanes.dtype), pos)

    def put(buf, rows):
        return buf.at[layer].set(write(buf[layer], rows))

    ksc, vsc = cache.k_scale, cache.v_scale
    if cache.quantized():
        (k, k_s), (v, v_s) = quantize_kv(k), quantize_kv(v)
        ksc, vsc = put(ksc, k_s), put(vsc, v_s)
    return cache._replace(
        k=put(cache.k, k), v=put(cache.v, v), k_scale=ksc, v_scale=vsc
    )


class TestRowWrite:
    """``_write_kv_rows`` on a fixed-lane cache: S·T rows scattered
    into the stored buffers, equal to the old slice-update-write-back
    in every bit of every buffer."""

    @pytest.mark.parametrize("kv_dtype", [jnp.float32, jnp.int8])
    @pytest.mark.parametrize("T", [1, 3], ids=["decode", "verify"])
    def test_equals_update_through_the_layer(self, T, kv_dtype):
        from ddp_tpu.models.generate import _write_kv_rows

        S, layer = 4, 1
        L = SPEC.total_len
        rng = np.random.default_rng(T)
        cache = init_slot_cache(SPEC, S, dtype=kv_dtype)
        # every row of every buffer distinct and non-zero, so a write
        # that strays (or a row left unwritten) shows
        fill = lambda x: jnp.asarray(
            rng.integers(1, 100, size=x.shape), x.dtype
        )
        cache = cache._replace(
            k=fill(cache.k), v=fill(cache.v),
            **(
                dict(k_scale=fill(cache.k_scale), v_scale=fill(cache.v_scale))
                if cache.quantized() else {}
            ),
        )
        shape = (S, T, *cache.k.shape[3:])
        k = jnp.asarray(rng.normal(size=shape), jnp.float32)
        v = jnp.asarray(rng.normal(size=shape), jnp.float32)
        # lane 0 at the start, a live lane, one on the last line, and
        # an idle lane parked at pos == total_len
        pos = jnp.asarray([0, 11, L - 1, L], jnp.int32)
        if T > 1:
            # the verify step's contract: the caller pre-clamps
            pos = jnp.minimum(pos, L - T)
        # both jitted, as the engine runs them (XLA rewrites the
        # quantizer's division; eager and jitted scales differ by an ulp)
        new, old = (
            jax.jit(fn, static_argnums=1)(cache, layer, k, v, pos)
            for fn in (_write_kv_rows, _write_kv_rows_through_layer)
        )
        for name, a, b in zip(new._fields, new, old):
            if name in ("k_scale", "v_scale") and not cache.quantized():
                continue
            assert jnp.array_equal(a, b), name
        # the rows landed where the contract says, nowhere else
        start = np.clip(np.asarray(pos), 0, L - T)
        untouched = np.ones(cache.k.shape[:3], bool)
        for s in range(S):
            untouched[layer, s, start[s] : start[s] + T] = False
        assert start[-1] == L - T  # the parked lane: the last line(s)
        np.testing.assert_array_equal(
            np.asarray(new.k)[untouched], np.asarray(cache.k)[untouched]
        )
        assert not np.array_equal(
            np.asarray(new.k)[~untouched], np.asarray(cache.k)[~untouched]
        )
