"""ddp_tpu.serve: continuous batching, admission control, HTTP front.

The acceptance pins live here:

- **Correctness**: for greedy decoding AND seeded temperature/top-p
  sampling the engine produces token-identical outputs to per-request
  models/generate.py decode, for requests of different lengths
  admitted at different times into one running batch — including
  prompt lengths straddling every chunk-bucket boundary
  (``TestEngine::test_greedy_matches_generate``,
  ``TestDecodePath``).
- **Static shapes**: ``warmup()`` compiles the engine's WHOLE program
  set (one first-chunk + one continuation-chunk program per bucket
  width + one fused decode+sample program, ≤ 2·len(buckets) + 1),
  after which a varied request mix
  (staggered arrivals, mixed lengths, evictions, refills) triggers no
  new XLA compilations — asserted via the engine's jit
  compilation-cache counters
  (``TestEngine::test_no_recompilation_after_warmup``).
- **Device-resident decode**: the steady-state per-step device→host
  transfer is the [num_slots] int32 token vector (plus per-refill
  first-token scalars) — never logits
  (``TestDecodePath::test_steady_state_transfer_is_slot_tokens``).
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ddp_tpu.models.generate import generate
from ddp_tpu.models.lm import LMSpec, init_lm
from ddp_tpu.serve.engine import (
    COMPLETE,
    REJECTED_TOO_LONG,
    TIMEOUT_EVICTED,
    TIMEOUT_QUEUE,
    ServeEngine,
)
from ddp_tpu.serve.scheduler import (
    BUDGET_EXCEEDS_CONTEXT,
    BUDGET_NONPOSITIVE,
    PROMPT_EMPTY,
    PROMPT_TOO_LONG,
    QUEUE_FULL,
    SEED_OUT_OF_RANGE,
    TOKEN_OUT_OF_RANGE,
    TOP_P_OUT_OF_RANGE,
    TOP_P_WITHOUT_SAMPLING,
    Scheduler,
)

SPEC = LMSpec(vocab_size=37, total_len=32, d_model=32, depth=2, num_heads=4)


@pytest.fixture(scope="module")
def params():
    return init_lm(SPEC, seed=0)


class FakeClock:
    """Injectable time for deadline tests — no sleeps, no flakes."""

    def __init__(self):
        self.t = 0.0

    def __call__(self) -> float:
        return self.t


def _reference(spec, params, prompt, n, **sampling):
    return np.asarray(
        generate(
            spec, params, jnp.asarray([prompt], jnp.int32),
            max_new_tokens=n, **sampling,
        )
    )[0, len(prompt):].tolist()


class TestScheduler:
    def mk(self, **kw):
        kw.setdefault("max_queue", 2)
        kw.setdefault("prefill_len", 8)
        kw.setdefault("total_len", 16)
        kw.setdefault("vocab_size", 37)
        return Scheduler(**kw)

    def test_admission_control(self):
        """Every rejection is an explicit machine-readable reason."""
        s = self.mk()
        assert s.submit([], 4).reason == PROMPT_EMPTY
        assert s.submit([1] * 9, 4).reason == PROMPT_TOO_LONG
        assert s.submit([1, 2], 0).reason == BUDGET_NONPOSITIVE
        assert s.submit([1] * 8, 9).reason == BUDGET_EXCEEDS_CONTEXT
        assert s.submit([1, 99], 4).reason == TOKEN_OUT_OF_RANGE
        assert s.submit([1, -1], 4).reason == TOKEN_OUT_OF_RANGE
        assert s.submit([1, 2], 4, top_p=0.0).reason == TOP_P_OUT_OF_RANGE
        assert s.submit([1, 2], 4, top_p=1.5).reason == TOP_P_OUT_OF_RANGE
        # greedy + nucleus filter: generate() refuses it, so does the door
        assert (
            s.submit([1, 2], 4, top_p=0.8).reason
            == TOP_P_WITHOUT_SAMPLING
        )
        assert s.submit([1, 2], 4, seed=2**31).reason == SEED_OUT_OF_RANGE
        assert s.depth == 0  # nothing bad was queued
        assert s.submit([1, 2], 4).accepted
        assert s.submit([3], 2).accepted
        # Bounded queue: the third submit backpressures, not OOMs.
        full = s.submit([4], 2)
        assert not full.accepted and full.reason == QUEUE_FULL
        assert s.depth == 2

    def test_fifo_order_and_ids(self):
        s = self.mk(max_queue=8)
        rids = [s.submit([i + 1], 2).request.rid for i in range(3)]
        assert rids == sorted(rids)
        assert [s.next_request().rid for _ in range(3)] == rids
        assert s.next_request() is None

    def test_deadline_eviction_from_queue(self):
        clock = FakeClock()
        s = self.mk(max_queue=8, clock=clock)
        keep = s.submit([1], 2).request
        drop = s.submit([2], 2, timeout=5.0).request
        clock.t = 6.0
        evicted = s.evict_expired()
        assert [r.rid for r in evicted] == [drop.rid]
        assert s.depth == 1 and s.next_request().rid == keep.rid

    def test_chunk_width_powers_of_two(self):
        s = self.mk(prefill_len=64, total_len=128, chunk=32, min_bucket=4)
        assert s.bucket_list() == [4, 8, 16, 32]
        # full chunks while a full chunk remains
        assert s.chunk_width(0, 32) == 32
        assert s.chunk_width(0, 100) == 32
        # partial chunk: smallest pow2 covering the remainder, floored
        # at min_bucket, capped at chunk
        assert s.chunk_width(32, 1) == 4
        assert s.chunk_width(32, 4) == 4
        assert s.chunk_width(32, 5) == 8
        assert s.chunk_width(32, 9) == 16
        assert s.chunk_width(32, 17) == 32

    def test_chunk_width_never_overruns_cache(self):
        """The covering bucket shrinks when its pad overhang would
        cross total_len — an overrunning dynamic_update_slice would
        CLAMP the write start and silently shift the chunk over live
        cache lines (the PR-3 review repro: start 32, remaining 4,
        total_len 38 must pick 4, not the covering-by-default 8)."""
        s = self.mk(prefill_len=36, total_len=38, chunk=16, min_bucket=2)
        assert s.chunk_width(32, 4) == 4  # 8 would overrun 38
        assert s.chunk_width(34, 2) == 2
        # no covering bucket fits: take the largest that does (the
        # chunk becomes non-final and the tail continues next step)
        assert s.chunk_width(32, 6) == 4

    def test_plan_chunks_token_budget(self):
        """Sarathi accounting: chunk widths + decode lanes fit the
        per-step budget; FIFO order is preserved; a tight budget
        shrinks the head's chunk instead of starving it; an idle
        engine always makes progress."""
        s = self.mk(
            prefill_len=64, total_len=128,
            chunk=16, min_bucket=4, token_budget=24,
        )
        # 4 decode lanes leave 20 budget tokens: one full 16-chunk
        # fits, the next (width 16) shrinks to the leftover 4 — FIFO
        # preserved, head never blocks followers it already served.
        plan = s.plan_chunks([(0, 0, 40), (1, 0, 30), (2, 0, 2)],
                             decoding=4)
        assert plan == [(0, 16), (1, 4)]
        # no decode lanes: 24 tokens fit 16 + 4 (bucketed) + 4 (shrunk)
        plan = s.plan_chunks([(0, 0, 40), (1, 0, 3), (2, 0, 50)],
                             decoding=0)
        assert plan == [(0, 16), (1, 4), (2, 4)]
        # starvation guard: budget smaller than any width still plans
        # one chunk when nothing is decoding
        tight = self.mk(
            prefill_len=64, total_len=128,
            chunk=16, min_bucket=4, token_budget=2,
        )
        assert tight.plan_chunks([(3, 0, 40)], decoding=0) == [(3, 16)]
        # ...but defers to running lanes when there are any
        assert tight.plan_chunks([(3, 0, 40)], decoding=2) == []


class TestEngine:
    def test_greedy_matches_generate(self, params):
        """THE correctness pin: mixed lengths, staggered admission,
        one running batch — token-identical to per-request decode."""
        eng = ServeEngine(SPEC, params, slots=2, prefill_len=8)
        first = [
            eng.submit([3, 1, 4], 6).request,
            eng.submit([2, 7, 1, 8, 2, 8], 9).request,
        ]
        for _ in range(3):  # both slots mid-decode...
            eng.step()
        late = [
            eng.submit([9], 7).request,  # ...then a third arrives and
            eng.submit([5, 3, 5, 8, 9], 4).request,  # queues behind it
        ]
        eng.run()
        for req in first + late:
            got = eng.result(req.rid)
            assert got is not None and got.status == COMPLETE
            assert got.tokens == _reference(
                SPEC, params, req.prompt, req.max_new_tokens
            ), f"request {req.rid} diverged from generate()"
            assert got.ttft >= 0.0

    def test_moe_routing_config_threaded(self):
        """MoE-LM serves through the engine with its OWN routing
        config (top_k=1: the round-5 hardcode would compute
        top-2 here and diverge from the training forward)."""
        spec = SPEC._replace(
            num_experts=4, moe_every=2, moe_top_k=1,
            moe_normalize_gates=False,
        )
        params = init_lm(spec, seed=1)
        eng = ServeEngine(spec, params, slots=2, prefill_len=8)
        reqs = [
            eng.submit([3, 1, 4, 1], 5).request,
            eng.submit([2, 7], 6).request,
        ]
        eng.run()
        for req in reqs:
            assert eng.result(req.rid).tokens == _reference(
                spec, params, req.prompt, req.max_new_tokens
            )

    @pytest.mark.parametrize("committed", [False, True])
    def test_no_recompilation_after_warmup(self, params, committed):
        """THE static-shape pin: ``warmup()`` compiles the engine's
        WHOLE bounded program set — one chunk program per bucket width
        plus the fused decode+sample program — and a varied mix
        (staggered arrivals, every prompt length, mixed sampling
        configs, evictions, refills) grows it by NOTHING. Also under
        COMMITTED weights, which is what a restored checkpoint hands
        the server: jit keys on it, and an uncommitted fresh cache
        used to make the first request recompile warmup's first
        program."""
        clock = FakeClock()
        if committed:
            params = jax.device_put(params, jax.devices()[0])
        eng = ServeEngine(
            SPEC, params, slots=3, prefill_len=8,
            prefill_chunk=8, min_bucket=2, clock=clock,
        )
        assert eng.buckets == [2, 4, 8]
        warm = eng.warmup()
        # The compile-count BUDGET: a shape explosion (per-length
        # prefill, per-sampling-config decode) fails here fast.
        assert warm["prefill_first"] == len(eng.buckets)
        assert warm["prefill_chunk"] == len(eng.buckets)
        assert warm["decode"] == 1
        assert sum(warm.values()) <= 2 * len(eng.buckets) + 1

        # Varied mix: all 8 prompt lengths (covering every bucket),
        # mixed budgets, per-request sampling configs, a queued
        # timeout, a running eviction, slot churn across 3 slots.
        for plen in range(1, 9):
            temp = 0.5 * (plen % 3)
            adm = eng.submit(
                list(range(1, plen + 1)), 3 + plen % 4,
                temperature=temp,
                # nucleus only on sampling lanes (greedy+top_p is a
                # front-door error, like generate())
                top_p=1.0 - 0.1 * (plen % 2) if temp > 0 else 1.0,
                seed=plen,
            )
            assert adm.accepted
            eng.step()
        eng.submit([4, 4], 6, timeout=1e-9)  # expires in the queue
        victim = eng.submit([6, 6, 6], 20, timeout=5.0).request
        eng.step()
        clock.t = 10.0  # running deadline passes mid-decode
        eng.run()
        assert eng.result(victim.rid).status in (
            TIMEOUT_EVICTED, TIMEOUT_QUEUE,
        )
        assert eng.compile_counts() == warm, (
            "request mix recompiled the engine"
        )

    def test_timeout_evicts_running_and_frees_slot(self, params):
        clock = FakeClock()
        eng = ServeEngine(SPEC, params, slots=1, prefill_len=8, clock=clock)
        slow = eng.submit([1, 2], 20, timeout=5.0).request
        queued = eng.submit([3, 4, 5], 3).request  # waits for the slot
        eng.step()
        assert eng.active == 1 and eng.scheduler.depth == 1
        clock.t = 6.0
        eng.run()
        evicted = eng.result(slow.rid)
        assert evicted.status == TIMEOUT_EVICTED
        assert 0 < len(evicted.tokens) < 20  # partial output kept
        done = eng.result(queued.rid)
        assert done.status == COMPLETE  # the freed slot served it
        assert done.tokens == _reference(SPEC, params, queued.prompt, 3)

    def test_rejection_and_budget_accounting(self, params):
        eng = ServeEngine(SPEC, params, slots=1, prefill_len=4, max_queue=1)
        assert eng.submit([1] * 5, 2).reason == PROMPT_TOO_LONG
        one = eng.submit([1, 2], 1).request  # budget 1: prefill only
        eng.run()
        assert eng.result(one.rid).tokens == _reference(
            SPEC, params, [1, 2], 1
        )

    def test_too_long_past_front_door_rejected_with_status(self, params):
        """A prompt longer than the engine can serve that SLIPPED PAST
        admission (misconfigured front door) completes as
        REJECTED_TOO_LONG — a distinct machine-readable status, not a
        cryptic shape error from inside a jitted program."""
        eng = ServeEngine(SPEC, params, slots=1, prefill_len=4)
        # Simulate the front-door/engine config drift the guard is
        # for: the scheduler's ceiling is mutated above the engine's.
        eng.scheduler.prefill_len = 31
        adm = eng.submit([1] * 9, 2)
        assert adm.accepted  # the (broken) front door let it through
        eng.run()
        done = eng.result(adm.request.rid)
        assert done is not None
        assert done.status == REJECTED_TOO_LONG
        assert done.tokens == [] and done.ttft is None
        # ...and the engine survives to serve the next valid request.
        ok = eng.submit([1, 2], 2).request
        eng.run()
        assert eng.result(ok.rid).status == COMPLETE

    def test_mid_prefill_eviction_frees_lane(self, params):
        """A deadline that fires BETWEEN prefill chunks (possible now
        that long prompts are ingested across steps) evicts with no
        tokens and ttft=None, and the half-prefilled lane's garbage
        K/V never leaks into the next occupant (write-before-attend
        invariant)."""
        clock = FakeClock()
        eng = ServeEngine(
            SPEC, params, slots=1, prefill_len=16, prefill_chunk=4,
            min_bucket=4, step_token_budget=5, clock=clock,
        )
        victim = eng.submit(
            list(range(1, 13)), 8, timeout=5.0
        ).request  # 12 tokens = 3 chunks, 1 per budgeted step
        eng.step()
        assert eng._slots[0].prefilling
        assert eng._slots[0].prefill_pos == 4
        clock.t = 6.0  # expires mid-prefill, before any token
        eng.run()
        dead = eng.result(victim.rid)
        assert dead.status == TIMEOUT_EVICTED
        assert dead.tokens == [] and dead.ttft is None
        # the lane serves the next request token-identically
        ok = eng.submit([1, 2, 3], 3).request
        eng.run()
        assert eng.result(ok.rid).tokens == _reference(
            SPEC, params, [1, 2, 3], 3
        )

    def test_queue_timeout_ttft_excluded(self, params, tmp_path):
        """Requests that never produced a token (queue timeout) carry
        ttft=None and are EXCLUDED from the TTFT summary + metrics —
        queue-wait times must not pollute first-token latency."""
        from ddp_tpu.utils.metrics import MetricsWriter

        clock = FakeClock()
        path = str(tmp_path / "serve.jsonl")
        writer = MetricsWriter(path)
        eng = ServeEngine(
            SPEC, params, slots=1, prefill_len=8, clock=clock,
            metrics=writer,
        )
        served = eng.submit([1, 2], 3).request  # owns the only slot
        starved = eng.submit([3, 4], 3, timeout=5.0).request  # queued
        eng.step()
        clock.t = 6.0  # starved expires before ever reaching a slot
        eng.run()
        writer.close()
        dead = eng.result(starved.rid)
        assert dead.status == TIMEOUT_QUEUE and dead.ttft is None
        ok = eng.result(served.rid)
        assert ok.status == COMPLETE and ok.ttft is not None
        # Summary aggregates exactly the requests that saw a token.
        assert eng.ttft.count == 1
        records = [
            json.loads(line) for line in open(path).read().splitlines()
        ]
        by_rid = {
            r["rid"]: r for r in records if r["kind"] == "serve_request"
        }
        assert "ttft_s" not in by_rid[starved.rid]
        assert by_rid[served.rid]["ttft_s"] >= 0.0

    def test_metrics_stream(self, params, tmp_path):
        """serve_step / serve_request / serve_reject records land in
        the JSONL stream with their operational fields."""
        from ddp_tpu.utils.metrics import MetricsWriter

        path = str(tmp_path / "serve.jsonl")
        writer = MetricsWriter(path)
        eng = ServeEngine(
            SPEC, params, slots=2, prefill_len=8, max_queue=1,
            metrics=writer,
        )
        eng.submit([1, 2, 3], 4)
        eng.submit([2, 2], 3)  # queue_full → serve_reject
        eng.run()
        writer.close()
        records = [
            json.loads(line) for line in open(path).read().splitlines()
        ]
        kinds = {r["kind"] for r in records}
        assert {"serve_step", "serve_request", "serve_reject"} <= kinds
        steps = [r for r in records if r["kind"] == "serve_step"]
        assert all(
            {"queue_depth", "slot_occupancy", "evictions"} <= set(r)
            for r in steps
        )
        reqs = [r for r in records if r["kind"] == "serve_request"]
        assert reqs[-1]["status"] == COMPLETE
        assert reqs[-1]["new_tokens"] == 4
        assert "ttft_s" in reqs[-1]
        rej = [r for r in records if r["kind"] == "serve_reject"]
        assert rej and rej[0]["reason"] == QUEUE_FULL


class TestDecodePath:
    """The device-resident decode loop's acceptance pins: equivalence
    across chunk/bucket boundaries for greedy AND seeded sampling, and
    the [num_slots]-int32 steady-state transfer bound."""

    def test_bucket_boundary_greedy_matches_generate(self, params):
        """Greedy outputs are token-identical to generate() for prompt
        lengths straddling every power-of-two bucket edge and the
        full-chunk boundary (buckets {4, 8}, chunk 8, prompts up to
        2×chunk) — the chunked/masked partial prefill computes exactly
        the monolithic prefill's math."""
        eng = ServeEngine(
            SPEC, params, slots=2, prefill_len=16,
            prefill_chunk=8, min_bucket=4,
        )
        assert eng.buckets == [4, 8]
        reqs = []
        # around the 4-edge, the 8-edge, and the chunk boundary (9,
        # 12, 15, 16 take a full chunk + a bucketed remainder)
        for plen in (1, 3, 4, 5, 7, 8, 9, 12, 15, 16):
            prompt = [(7 * plen + i) % SPEC.vocab_size for i in range(plen)]
            reqs.append((prompt, eng.submit(prompt, 5).request))
            eng.step()  # staggered admission: mixed-age batch
        eng.run()
        for prompt, req in reqs:
            got = eng.result(req.rid)
            assert got.status == COMPLETE
            assert got.tokens == _reference(SPEC, params, prompt, 5), (
                f"prompt_len {len(prompt)} diverged across a bucket edge"
            )

    def test_seeded_sampling_matches_generate(self, params):
        """On-device fused sampling is token-identical to a seeded
        generate(): same fold_in key stream, same temperature scaling,
        same nucleus filter — per slot, in one mixed-config batch."""
        eng = ServeEngine(
            SPEC, params, slots=3, prefill_len=8, min_bucket=4,
        )
        cases = [
            ([3, 1, 4, 1], 6, dict(temperature=0.8, seed=7)),
            ([2, 7], 5, dict(temperature=1.3, top_p=0.9, seed=3)),
            # negative seed: must hit generate()'s exact key(-3), not
            # a masked rewrite of it
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
            assert got.tokens == _reference(SPEC, params, p, n, **kw), (
                f"sampling config {kw} diverged from generate()"
            )

    def test_tail_chunk_near_total_len_matches_generate(self, params):
        """PR-3 review regression: a final chunk whose covering bucket
        would overrun an UNALIGNED total_len (prompt 17 in a 19-long
        cache: tail at start 16 must take width 2, not a min_bucket-8
        that would cross 19) stays token-identical — an overrunning
        dynamic_update_slice would clamp-shift the write over live
        cache lines and silently corrupt the output."""
        spec = SPEC._replace(total_len=19)
        p19 = init_lm(spec, seed=0)
        eng = ServeEngine(
            spec, p19, slots=1, prefill_len=17, prefill_chunk=8,
            min_bucket=8,  # engine clamps to fit total_len - prefill_len
        )
        assert eng.min_bucket == 2  # prev_pow2(19 - 17 + 1)
        prompt = [(3 * i + 1) % spec.vocab_size for i in range(17)]
        req = eng.submit(prompt, 2).request
        eng.run()
        got = eng.result(req.rid)
        assert got.status == COMPLETE
        assert got.tokens == _reference(spec, p19, prompt, 2)

    def test_step_token_budget_floor_validated(self, params):
        """A budget that cannot sustain prefill progress while lanes
        decode is a config error at construction, not a silent
        TTFT-balloon at runtime."""
        with pytest.raises(ValueError, match="step_token_budget"):
            ServeEngine(
                SPEC, params, slots=4, prefill_len=8,
                min_bucket=8, step_token_budget=4,
            )

    def test_steady_state_transfer_is_slot_tokens(self, params,
                                                  monkeypatch):
        """THE transfer pin: once all lanes are decoding, the only
        device→host reads are [num_slots] int32 token vectors (and
        per-refill first-token scalars) — never [slots, vocab] logits."""
        import ddp_tpu.serve.engine as engine_mod

        eng = ServeEngine(SPEC, params, slots=2, prefill_len=8)
        eng.submit([1, 2, 3], 12)
        eng.submit([4, 5], 12)
        for _ in range(3):  # both lanes past prefill, mid-decode
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
        ), f"steady-state path fetched non-token arrays: {fetched}"
        # ...and the token vector itself is [S] int32 on device.
        assert eng._toks.shape == (2,) and eng._toks.dtype == jnp.int32
        eng.run()


class TestServer:
    def test_http_roundtrip(self, params):
        """POST /generate parity + healthz/stats + error codes, one
        server instance (sockets are the slow part)."""
        import urllib.error
        import urllib.request

        from ddp_tpu.serve.server import LMServer

        eng = ServeEngine(SPEC, params, slots=2, prefill_len=8)
        with LMServer(eng) as srv:
            def post(body, path="/generate"):
                req = urllib.request.Request(
                    srv.url + path, data=json.dumps(body).encode()
                )
                try:
                    r = urllib.request.urlopen(req, timeout=60)
                    return r.status, json.loads(r.read())
                except urllib.error.HTTPError as e:
                    return e.code, json.loads(e.read())

            status, out = post(
                {"prompt_tokens": [1, 2, 3], "max_new_tokens": 5}
            )
            assert status == 200 and out["status"] == COMPLETE
            assert out["tokens"] == _reference(SPEC, params, [1, 2, 3], 5)

            # seeded sampling through the HTTP surface (top_p wired)
            status, out = post(
                {"prompt_tokens": [2, 7], "max_new_tokens": 4,
                 "temperature": 0.9, "top_p": 0.8, "seed": 5}
            )
            assert status == 200
            assert out["tokens"] == _reference(
                SPEC, params, [2, 7], 4,
                temperature=0.9, top_p=0.8, seed=5,
            )

            status, out = post({"prompt_tokens": [1] * 99,
                                "max_new_tokens": 2})
            assert status == 400 and out["error"] == PROMPT_TOO_LONG

            status, out = post({"wrong": 1})
            assert status == 400

            health = json.loads(
                urllib.request.urlopen(
                    srv.url + "/healthz", timeout=10
                ).read()
            )
            assert health["ok"] and health["slots"] == 2
            stats = json.loads(
                urllib.request.urlopen(
                    srv.url + "/stats", timeout=10
                ).read()
            )
            assert stats["compile_counts"] == eng.compile_counts()
            assert stats["ttft_s"]["count"] >= 1

    def test_queue_full_429_carries_retry_after(self, params):
        """Backpressure 503/429s must tell clients WHEN to come back
        (ISSUE 14 satellite): a queue_full rejection carries a
        Retry-After header derived from the queue drain rate (static
        fallback before any retire window exists), matching the drain
        path's existing header — so the fleet router (and any
        client) backs off instead of hammering."""
        import urllib.error
        import urllib.request

        from ddp_tpu.serve.server import LMServer

        eng = ServeEngine(SPEC, params, slots=2, prefill_len=8)
        with LMServer(eng) as srv:
            # deterministic backpressure: shrink the bound so EVERY
            # submit rejects queue_full, no racing the engine loop
            eng.scheduler.max_queue = 0
            req = urllib.request.Request(
                srv.url + "/generate",
                data=json.dumps(
                    {"prompt_tokens": [1, 2], "max_new_tokens": 2}
                ).encode(),
            )
            with pytest.raises(urllib.error.HTTPError) as exc:
                urllib.request.urlopen(req, timeout=30)
            assert exc.value.code == 429
            retry_after = exc.value.headers["Retry-After"]
            assert retry_after is not None and int(retry_after) >= 1
            body = json.loads(exc.value.read())
            assert body["error"] == "queue_full"
            assert body["retry_after_s"] >= 1.0
            # no retire history yet: the static drain hint backs it
            assert body["retry_after_s"] == srv.drain_retry_after

    def test_queue_drain_eta_math(self):
        """The Retry-After derivation is pure and pinned: recent
        retire rate over the synthetic window, depth over rate."""
        from ddp_tpu.serve.engine import drain_eta_s

        # 5 retires over 2s -> 2 req/s; 6 queued -> 3s
        times = [10.0, 10.5, 11.0, 11.5, 12.0]
        assert drain_eta_s(times, 6) == pytest.approx(3.0)
        # empty queue still returns one retirement period (never
        # "retry immediately")
        assert drain_eta_s(times, 0) == pytest.approx(0.5)
        # no usable window -> None (caller falls back to the static
        # hint)
        assert drain_eta_s([], 4) is None
        assert drain_eta_s([1.0], 4) is None
        assert drain_eta_s([2.0, 2.0], 4) is None

    def test_graceful_drain(self, params):
        """The SIGTERM drain contract (scripts/serve.py): admissions
        stop with 503 + Retry-After, running lanes finish, and the
        drain state is visible on /healthz, /statusz and as the
        /metricsz gauge."""
        import urllib.error
        import urllib.request

        from ddp_tpu.serve.server import LMServer

        eng = ServeEngine(SPEC, params, slots=2, prefill_len=8)
        with LMServer(eng) as srv:
            metrics = urllib.request.urlopen(
                srv.url + "/metricsz", timeout=10
            ).read().decode()
            assert "ddp_tpu_serve_draining 0" in metrics

            # a request admitted BEFORE the drain completes normally
            status, out = srv.submit_and_wait(
                {"prompt_tokens": [1, 2, 3], "max_new_tokens": 4}
            )
            assert status == 200 and out["status"] == COMPLETE

            srv.begin_drain()
            req = urllib.request.Request(
                srv.url + "/generate",
                data=json.dumps(
                    {"prompt_tokens": [1, 2], "max_new_tokens": 2}
                ).encode(),
            )
            with pytest.raises(urllib.error.HTTPError) as exc:
                urllib.request.urlopen(req, timeout=30)
            assert exc.value.code == 503
            assert exc.value.headers["Retry-After"] == str(
                int(srv.drain_retry_after)
            )
            assert json.loads(exc.value.read())["error"] == "draining"

            health = json.loads(
                urllib.request.urlopen(
                    srv.url + "/healthz", timeout=10
                ).read()
            )
            assert health["ok"] and health["draining"] is True
            statusz = json.loads(
                urllib.request.urlopen(
                    srv.url + "/statusz", timeout=10
                ).read()
            )
            assert statusz["draining"] is True
            metrics = urllib.request.urlopen(
                srv.url + "/metricsz", timeout=10
            ).read().decode()
            assert "ddp_tpu_serve_draining 1" in metrics

            # nothing in flight → the drain completes immediately
            assert srv.drain(timeout=10) is True
