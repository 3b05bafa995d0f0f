"""ddp_tpu.tune: the self-tuning loop (ISSUE 18), layered:

- **Space**: every proposed candidate passes the engine's OWN
  construction validation (``resolve_engine_knobs`` — one rule set,
  no tuner-side re-derivation that could drift), invalid combos are
  rejected not proposed, and the accounting (proposed = rejected +
  aliased + candidates) proves nothing was silently capped.
- **Cost model**: dominance pruning on a synthetic ledger — worse on
  every known axis dies, unpriced entries are never pruned (the model
  must not prune what it cannot see), missing axes block claims.
- **Cache**: round-trip through the atomic JSON file; invalidation on
  model-shape / hardware / site-version change; corrupt files read as
  empty; ``apply_tuned`` precedence explicit > cache > default.
- **pick_block** (satellite): largest tile-aligned divisor property,
  kernel-vs-reference parity on a non-divisible L, and the xprof
  ``annotate`` plumbing that surfaces the effective block in the
  compile ledger.
- **End to end** (slow tier): a real search on a tiny LM (prunes,
  never regresses, second run is a pure hit) and the trainer's
  ``--tuned auto`` load path with explicit-flag precedence.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ddp_tpu.models.lm import LMSpec, init_lm
from ddp_tpu.ops.flash import pick_block
from ddp_tpu.ops.decode import (
    decode_attention_reference,
    flash_decode_attention,
)
from ddp_tpu.serve.engine import ServeEngine, resolve_engine_knobs
from ddp_tpu.tune import (
    CostEntry,
    TuningCache,
    apply_tuned,
    cache_key,
    canonical_trace,
    decode_block_space,
    dominates,
    measure_serve,
    model_signature,
    prune_dominated,
    resolve_cache,
    serve_space,
    tune_serve,
    tune_zero,
    zero_space,
)

SPEC = LMSpec(vocab_size=37, total_len=32, d_model=32, depth=1, num_heads=2)


@pytest.fixture(scope="module")
def params():
    return init_lm(SPEC, seed=0)


@pytest.fixture(scope="module")
def tiny_params():
    return {"w": jnp.ones((64, 64), jnp.float32)}


# ---- search space ---------------------------------------------------


class TestSpace:
    def test_every_serve_candidate_passes_engine_validation(self):
        """Validity matrix: the space only proposes what the engine
        itself would construct — re-validated here candidate by
        candidate through the same resolver the engine's __init__
        calls."""
        report = serve_space(SPEC, slots=2)
        assert report.candidates, report
        for cand in report.candidates:
            resolve_engine_knobs(SPEC, slots=2, **cand.knobs)  # no raise
            assert cand.key() in report.resolved

    def test_accounting_is_complete(self):
        report = serve_space(SPEC, slots=2)
        assert report.proposed == (
            report.rejected + report.aliased + len(report.candidates)
        )

    def test_invalid_combos_raise_in_resolver_not_in_space(self):
        """The combos the space must never emit do fail the shared
        resolver — the rejection path is the engine's, not a tuner
        re-implementation."""
        with pytest.raises(ValueError, match="step_token_budget"):
            resolve_engine_knobs(SPEC, slots=2, step_token_budget=1)
        with pytest.raises(ValueError, match="power of two"):
            resolve_engine_knobs(SPEC, slots=2, page_size=7)
        with pytest.raises(ValueError, match="draft"):
            resolve_engine_knobs(SPEC, slots=2, spec_tokens=2)
        # ...and γ>0 / paged knobs only enter the grid when the caller
        # can actually run them.
        no_draft = serve_space(SPEC, slots=2, spec_tokens=(0, 2))
        assert all(
            c.knobs.get("spec_tokens", 0) == 0 for c in no_draft.candidates
        )

    def test_gamma_proposed_with_draft(self):
        draft = SPEC._replace(d_model=16)
        rep = serve_space(SPEC, slots=2, spec_tokens=(0, 2), draft_spec=draft)
        assert any(c.knobs.get("spec_tokens") == 2 for c in rep.candidates)

    def test_zero_space_validity_and_hier_gating(self, tiny_params):
        flat = zero_space(tiny_params, 4, dcn=1)
        assert flat.candidates
        assert all(
            not c.knobs.get("hier") for c in flat.candidates
        ), "hier proposed on a single-slice mesh"
        sliced = zero_space(tiny_params, 4, dcn=2)
        assert any(c.knobs.get("hier") for c in sliced.candidates)

    def test_decode_block_space_tracks_divisors(self):
        rep = decode_block_space(48)
        effective = {
            rep.resolved[c.key()]["block_k"] for c in rep.candidates
        }
        assert all(48 % b == 0 for b in effective), effective

    def test_engine_constructs_from_proposed_candidate(self, params):
        """Spot-check past the resolver: a real engine builds from a
        non-default proposed candidate."""
        report = serve_space(SPEC, slots=2)
        cand = next(
            c for c in report.candidates
            if c.knobs.get("min_bucket") == 16
        )
        eng = ServeEngine(SPEC, params, slots=2, **cand.knobs)
        assert eng.min_bucket == 16


# ---- cost model -----------------------------------------------------


class TestDominance:
    def test_worse_on_every_axis_is_pruned(self):
        a = CostEntry("a", flops=10, bytes_accessed=10, memory_bytes=10)
        b = CostEntry("b", flops=20, bytes_accessed=20, memory_bytes=20)
        assert dominates(a, b) and not dominates(b, a)
        survivors, pruned = prune_dominated([a, b])
        assert [e.key for e in survivors] == ["a"]
        assert [e.key for e in pruned] == ["b"]

    def test_unpriced_is_never_pruned(self):
        """γ/paged candidates carry no priced axes (their payoff is
        acceptance/reuse-dependent) — the model must not prune what it
        cannot see."""
        a = CostEntry("a", flops=1, bytes_accessed=1, memory_bytes=1)
        blind = CostEntry("blind", detail={"measure_only": True})
        assert not blind.priced
        assert not dominates(a, blind)
        survivors, pruned = prune_dominated([a, blind])
        assert {e.key for e in survivors} == {"a", "blind"}
        assert not pruned

    def test_missing_axis_blocks_the_claim(self):
        """b knows an axis a can't price → a cannot dominate b, even
        while winning every shared axis."""
        a = CostEntry("a", flops=1)
        b = CostEntry("b", flops=2, bytes_accessed=5)
        assert not dominates(a, b)
        # ...but a one-axis entry still dominates a same-shape worse one.
        c = CostEntry("c", flops=3)
        assert dominates(a, c)

    def test_tie_on_all_axes_spares_both(self):
        a = CostEntry("a", flops=5, bytes_accessed=5)
        b = CostEntry("b", flops=5, bytes_accessed=5)
        assert not dominates(a, b) and not dominates(b, a)


# ---- cache ----------------------------------------------------------


class TestCache:
    def test_round_trip_atomic(self, tmp_path):
        path = str(tmp_path / "tuning_cache.json")
        cache = TuningCache(path)
        key = cache_key("serve", model_signature(SPEC))
        cache.store(key, {"prefill_chunk": 32}, provenance={"winner": "x"})
        cache.save()
        doc = json.load(open(path))
        assert doc["schema"] == TuningCache.SCHEMA
        reread = TuningCache(path)
        ent = reread.lookup(key)
        assert ent["config"] == {"prefill_chunk": 32}
        assert ent["provenance"]["winner"] == "x"
        assert not [p for p in os.listdir(tmp_path) if ".tmp." in p]

    def test_key_invalidation_axes(self, tmp_path):
        """Any change to shape, hardware, or site version is a MISS —
        a tuned config can never leak across them."""
        cache = TuningCache(str(tmp_path / "c.json"))
        key = cache_key("serve", model_signature(SPEC))
        cache.store(key, {"min_bucket": 16})
        other_shape = SPEC._replace(d_model=64)
        assert cache.lookup(
            cache_key("serve", model_signature(other_shape))
        ) is None
        assert cache.lookup(
            cache_key("serve", model_signature(SPEC), backend="tpu",
                      platform="tpu", device_kind="TPU v4")
        ) is None
        import ddp_tpu.tune.cache as cmod

        old = cmod.SITE_VERSIONS["serve"]
        try:
            cmod.SITE_VERSIONS["serve"] = old + 1
            assert cache.lookup(
                cache_key("serve", model_signature(SPEC))
            ) is None
        finally:
            cmod.SITE_VERSIONS["serve"] = old

    def test_corrupt_or_missing_reads_empty(self, tmp_path):
        bad = tmp_path / "bad.json"
        bad.write_text("{not json")
        assert TuningCache(str(bad)).entries == {}
        assert TuningCache(str(tmp_path / "absent.json")).entries == {}
        # wrong schema version: ignored, not half-parsed
        wrong = tmp_path / "wrong.json"
        wrong.write_text(json.dumps({"schema": 99, "entries": {"k": {}}}))
        assert TuningCache(str(wrong)).entries == {}

    def test_resolve_cache_off_and_auto(self, tmp_path):
        assert resolve_cache("off", str(tmp_path)) is None
        assert resolve_cache("auto", None) is None
        auto = resolve_cache("auto", str(tmp_path))
        assert auto.path == str(tmp_path / "tuning_cache.json")
        explicit = resolve_cache(str(tmp_path / "elsewhere.json"), None)
        assert explicit.path.endswith("elsewhere.json")


class TestApplyTuned:
    def test_explicit_beats_cache_beats_default(self):
        current = {"min_bucket": 4, "prefill_chunk": 16}
        entry = {"min_bucket": 16, "prefill_chunk": 64, "alien_knob": 9}
        merged, applied, overridden = apply_tuned(
            current, entry, explicit={"min_bucket"}
        )
        assert merged == {"min_bucket": 4, "prefill_chunk": 64}
        assert applied == {"prefill_chunk": 64}
        assert overridden == ["min_bucket"]
        assert "alien_knob" not in merged  # not this surface's knob

    def test_no_explicit_applies_everything_shared(self):
        merged, applied, overridden = apply_tuned(
            {"a": 1}, {"a": 2}, explicit=frozenset()
        )
        assert merged == {"a": 2} and applied == {"a": 2}
        assert overridden == []


# ---- pick_block + xprof surfacing (satellite) -----------------------


class TestPickBlock:
    """ops/flash.pick_block: the one block rule of the flash and
    flash-decode kernels (tile-aligned divisor, or raise)."""

    def test_regression_non_divisible_requested(self):
        """The ISSUE-18 pin: L=48 with the default 32 request must land
        on 24 (largest aligned divisor ≤ 32), not degrade to a
        full-length block that defeats the dead-block skip."""
        assert pick_block(48, 32, jnp.float32) == 24

    @pytest.mark.parametrize(
        "L,req,dtype,expect",
        [
            (128, 128, jnp.float32, 128),
            (7, 128, jnp.float32, 7),  # fits the request: whole lane
            (48, 16, jnp.float32, 16),
            (2048, 512, jnp.bfloat16, 512),
            (2064, 128, jnp.float32, 48),  # 2064 = 16·3·43
            (96, 64, jnp.int8, 32),  # int8 rows tile 32 at a time
        ],
    )
    def test_known_values(self, L, req, dtype, expect):
        assert pick_block(L, req, dtype) == expect

    @pytest.mark.parametrize(
        "L,req,dtype",
        [
            (97, 64, jnp.float32),  # prime: used to degrade to 1-wide
            (200, 128, jnp.bfloat16),  # 8-aligned divisors only
            (2064, 128, jnp.int8),  # the paged int8 case: 16 | L, 32 ∤ L
        ],
    )
    def test_non_dividing_length_raises_with_shape(self, L, req, dtype):
        with pytest.raises(ValueError, match=f"length-{L}"):
            pick_block(L, req, dtype)

    def test_flash_blocks_never_fall_to_whole_sequence(self):
        """ops/flash._pick_blocks used to make the block the WHOLE
        sequence when the request did not divide it (one [T, T] cell
        in VMEM at long T); now it is an aligned divisor or an
        error."""
        from ddp_tpu.ops.flash import _pick_blocks

        assert _pick_blocks(2048, 2048, 512, 512, jnp.bfloat16) == (512, 512)
        assert _pick_blocks(1536, 1536, 1024, 1024, jnp.float32) == (768, 768)
        with pytest.raises(ValueError, match="length-1031"):
            _pick_blocks(1031, 1031, 512, 512, jnp.float32)

    def test_aligned_divisor_property(self):
        for dtype, align in ((jnp.float32, 8), (jnp.int8, 32)):
            for L in range(1, 160):
                for req in (1, 8, 13, 32, 128):
                    try:
                        got = pick_block(L, req, dtype)
                    except ValueError:
                        assert L > req and not any(
                            L % d == 0
                            for d in range(align, req + 1, align)
                        ), (L, req)
                        continue
                    assert L % got == 0 and got <= max(req, 1), (L, req)
                    assert got == L or got % align == 0, (L, req, got)

    def test_engine_rejects_untileable_lane_at_construction(self):
        spec = SPEC._replace(total_len=2064)
        with pytest.raises(ValueError, match="total_len 2064"):
            resolve_engine_knobs(
                spec, decode_attn="flash", kv_dtype="int8"
            )
        knobs = resolve_engine_knobs(spec, decode_attn="flash")
        assert knobs["decode_block_k"] == 48

    def test_flash_matches_reference_on_non_divisible_L(self):
        """The fallback path computes the same attention: L=48 keys,
        block request 32 → effective 24, two banded blocks."""
        rng = np.random.default_rng(48)
        S, H, H_kv, Dh, L = 3, 4, 2, 8, 48
        q = jnp.asarray(rng.normal(size=(S, H, Dh)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(S, L, H_kv, Dh)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(S, L, H_kv, Dh)), jnp.float32)
        pos = jnp.asarray([0, 23, 47], jnp.int32)
        ref = decode_attention_reference(q, k, v, pos)
        out = flash_decode_attention(q, k, v, pos, block_k=32)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), atol=1e-5, rtol=1e-5
        )

    def test_xprof_annotate_lands_in_ledger(self):
        """The engine's block_k annotation route: notes attached before
        OR after the compile both surface on the ledger record; a
        disabled profiler stays free (no state kept)."""
        from ddp_tpu.obs.xprof import Xprof

        xp = Xprof(enabled=True)
        xp.annotate("tune.probe", block_k_requested=32, block_k=24)
        f = xp.instrument(jax.jit(lambda x: x * 2), "tune.probe")
        f(jnp.ones((4,), jnp.float32))
        rec = [
            p for p in xp.ledger_records() if p["label"] == "tune.probe"
        ]
        assert rec and rec[0]["notes"]["block_k"] == 24
        xp.annotate("tune.probe", block_k=12)  # post-compile merge
        rec = [
            p for p in xp.ledger_records() if p["label"] == "tune.probe"
        ]
        assert rec[0]["notes"] == {"block_k_requested": 32, "block_k": 12}

        off = Xprof(enabled=False)
        off.annotate("x", a=1)
        assert off._notes == {}


# ---- the search end to end ------------------------------------------


def test_cache_hit_is_pure(params, tmp_path):
    """Smoke-tier pin: a warm cache answers without building a single
    engine or pricing a single program — the loaded-by-default path is
    free at startup."""
    cache = TuningCache(str(tmp_path / "c.json"))
    key = cache_key("serve", model_signature(SPEC))
    cache.store(
        key, {"prefill_chunk": 32}, provenance={"winner": "cached"}
    )
    rep = tune_serve(SPEC, params, cache=cache, slots=2)
    assert rep["cache_hit"] and rep["measured"] == 0
    assert rep["config"] == {"prefill_chunk": 32}
    assert rep["search_wall_s"] == 0.0


def test_tune_serve_end_to_end(params, tmp_path):
    """Cold search on the tiny LM: prunes (pruned_fraction > 0), never
    regresses (default is always measured; winner is the p50 argmin),
    accounts for every dropped candidate, and the second invocation is
    a pure cache hit."""
    cache = TuningCache(str(tmp_path / "c.json"))
    cold = tune_serve(SPEC, params, cache=cache, slots=2, max_measure=2)
    assert not cold["cache_hit"]
    assert cold["pruned_fraction"] > 0
    assert cold["tuned_p50"] <= cold["default_p50"]
    assert cold["proposed"] == (
        cold["rejected"] + cold["aliased"] + cold["priced"]
    )
    assert cold["measured"] >= 1
    warm = tune_serve(SPEC, params, cache=cache, slots=2, max_measure=2)
    assert warm["cache_hit"] and warm["measured"] == 0
    assert warm["config"] == cold["config"]


def test_measured_tokens_identical_across_bucket_edges(params):
    """Speed-not-results: a knob variant serves the SAME tokens as the
    default on a trace whose prompts straddle bucket edges — the
    identity the tuner asserts for every measured candidate, pinned
    here explicitly engine-vs-engine."""
    trace = canonical_trace(
        vocab_size=SPEC.vocab_size, prefill_len=16, requests=5,
        new_tokens=6,
    )
    default = resolve_engine_knobs(SPEC, slots=2)
    base = measure_serve(
        SPEC, params,
        {"prefill_chunk": default["chunk"],
         "min_bucket": default["min_bucket"],
         "step_token_budget": default["step_token_budget"]},
        trace=trace, slots=2,
    )
    variant = measure_serve(
        SPEC, params,
        {"prefill_chunk": 8, "min_bucket": 4, "step_token_budget": 32},
        trace=trace, slots=2,
    )
    assert base["tokens"] == variant["tokens"]
    assert base["p50"] is not None and variant["p50"] is not None


def test_tune_zero_end_to_end(tiny_params, tmp_path):
    cache = TuningCache(str(tmp_path / "c.json"))
    rep = tune_zero(tiny_params, 4, cache=cache, model_sig="t")
    assert not rep["cache_hit"] and rep["winner"]
    warm = tune_zero(tiny_params, 4, cache=cache, model_sig="t")
    assert warm["cache_hit"] and warm["measured"] == 0
    assert warm["config"] == rep["config"]


# ---- trainer load path ----------------------------------------------


def _zero_cfg(tmp_path, **overrides):
    from ddp_tpu.train.config import TrainConfig

    base = dict(
        epochs=1,
        batch_size=8,
        model="causal_lm",
        parallel="zero",
        optimizer="adam",
        lr=1e-3,
        seq_len=16,
        vocab_size=32,
        model_dim=32,
        model_depth=1,
        checkpoint_dir=str(tmp_path / "ck"),
        data_root=str(tmp_path / "data"),
        synthetic_size=64,
        log_interval=4,
        eval_every=0,
    )
    base.update(overrides)
    return TrainConfig(**base)


def _seed_zero_cache(cfg, config_dict):
    from ddp_tpu.tune import train_signature
    from ddp_tpu.tune.cache import default_cache_path

    cache = TuningCache(default_cache_path(cfg.checkpoint_dir))
    cache.store(
        cache_key("zero", train_signature(cfg)), config_dict,
        provenance={"winner": "seeded"},
    )
    cache.save()
    return cache


def test_trainer_loads_zero_cache_by_default(tmp_path):
    """--tuned auto (the default): a seeded cache entry lands on the
    config before zero-layout construction, provenance is stamped on
    run_start AND a dedicated tuning record, and the applied bucket
    size actually shapes the layout."""
    from ddp_tpu.train.trainer import Trainer

    cfg = _zero_cfg(
        tmp_path, metrics_file=str(tmp_path / "m.jsonl")
    )
    _seed_zero_cache(
        cfg, {"zero_bucket_mb": 8.0, "zero_gather_dtype": "bf16"}
    )
    t = Trainer(cfg)
    try:
        assert cfg.zero_bucket_mb == 8.0
        assert cfg.zero_gather_dtype == "bf16"
        assert t._tuning is not None
        assert t._tuning["applied"] == {
            "zero_bucket_mb": 8.0, "zero_gather_dtype": "bf16"
        }
        summary = t.train()
        assert summary["epochs_run"] == 1
    finally:
        t.close()
    records = [
        json.loads(line)
        for line in open(cfg.metrics_file)
        if line.strip()
    ]
    tuning = [r for r in records if r.get("kind") == "tuning"]
    assert tuning and tuning[0]["cache_hit"] is True
    assert tuning[0]["site"] == "zero"
    run_start = [r for r in records if r.get("kind") == "run_start"]
    assert run_start and "tuning" in run_start[0]


def test_trainer_explicit_flag_beats_cache(tmp_path):
    """A non-default zero_bucket_mb counts as explicit (the from_args
    path records real argv flags; direct construction falls back to
    default-comparison) — the cache must NOT override it."""
    from ddp_tpu.train.trainer import Trainer

    cfg = _zero_cfg(tmp_path, zero_bucket_mb=2.0)
    _seed_zero_cache(
        cfg, {"zero_bucket_mb": 8.0, "zero_gather_dtype": "bf16"}
    )
    t = Trainer(cfg)
    try:
        assert cfg.zero_bucket_mb == 2.0  # explicit survived
        assert cfg.zero_gather_dtype == "bf16"  # default got filled
        assert t._tuning["overridden"] == ["zero_bucket_mb"]
    finally:
        t.close()


def test_trainer_tuned_off_is_inert(tmp_path):
    from ddp_tpu.train.trainer import Trainer

    cfg = _zero_cfg(tmp_path, tuned="off")
    _seed_zero_cache(
        cfg, {"zero_bucket_mb": 8.0, "zero_gather_dtype": "bf16"}
    )
    t = Trainer(cfg)
    try:
        assert cfg.zero_bucket_mb == 4.0
        assert t._tuning is None
    finally:
        t.close()


def test_from_args_records_explicit_flags():
    from ddp_tpu.train.config import TrainConfig

    cfg = TrainConfig.from_args(
        ["--zero_bucket_mb", "2.0", "--epochs", "1"]
    )
    assert "zero_bucket_mb" in cfg.explicit_flags
    assert "epochs" in cfg.explicit_flags
    assert "zero_gather_dtype" not in cfg.explicit_flags
    # plain attribute, not a field: records/asdict stay unchanged
    import dataclasses

    assert "explicit_flags" not in dataclasses.asdict(cfg)
