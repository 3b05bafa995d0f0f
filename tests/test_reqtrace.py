"""Request-level distributed tracing (ddp_tpu.obs.reqtrace).

Acceptance pins (ISSUE 11):

1. **Span schema + causal ordering** — every completion's lifecycle
   (admit → queue → prefill chunks → [spec rounds] → decode → retire)
   reconstructs from the exported Perfetto trace and passes the
   causal validator; the exported document still passes the PR-2
   trace-schema lint (async events carry id + cat).
2. **Disabled is free** — request tracing off allocates no
   per-request trace state (tracemalloc pin), completions carry no
   ``trace`` digest, the serve_request stream keeps its pre-reqtrace
   schema, and engine stats carry no ``reqtrace`` key.
3. **The PR-3 transfer invariant survives** — token identity vs
   ``generate()`` AND the steady-state [S]-int32-only transfer spy
   re-run green with request tracing (and the sanitizer) enabled.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ddp_tpu.models.generate import generate
from ddp_tpu.models.lm import LMSpec, init_lm
from ddp_tpu.obs.reqtrace import (
    ADMIT,
    DECODE,
    PREFILL_CHUNK,
    QUEUE,
    RETIRE,
    derive_trace_id,
    format_trace_id,
    reconstruct_requests,
    validate_request_timeline,
)
from ddp_tpu.obs.tracer import Tracer, validate_trace_file
from ddp_tpu.serve.engine import ServeEngine

SPEC = LMSpec(vocab_size=37, total_len=32, d_model=32, depth=2, num_heads=4)


@pytest.fixture(scope="module")
def params():
    return init_lm(SPEC, seed=0)


class FakeClock:
    """Injectable time (the test_serve pattern): no sleeps, no flakes."""

    def __init__(self):
        self.t = 0.0

    def __call__(self) -> float:
        return self.t


def mk_engine(params, *, tracer=None, reqtrace=True, **kw):
    kw.setdefault("slots", 2)
    kw.setdefault("prefill_len", 8)
    return ServeEngine(
        SPEC, params, tracer=tracer, reqtrace=reqtrace, trace_seed=7,
        **kw,
    )


class TestTraceIds:
    def test_64bit_nonzero_deterministic(self):
        ids = {derive_trace_id(7, rid) for rid in range(1000)}
        assert len(ids) == 1000  # distinct per rid
        assert all(0 < i < 2**64 for i in ids)
        assert derive_trace_id(7, 3) == derive_trace_id(7, 3)
        assert derive_trace_id(7, 3) != derive_trace_id(8, 3)

    def test_assigned_at_admission(self, params):
        """The scheduler stamps the id on the Request itself — it
        exists before any engine step runs."""
        eng = mk_engine(params)
        adm = eng.submit([1, 2, 3], 2)
        assert adm.accepted
        assert adm.request.trace_id == derive_trace_id(7, adm.request.rid)

    def test_format_is_hex16(self):
        assert format_trace_id(0xDEADBEEF) == "0x00000000deadbeef"


class TestEngineTimelines:
    def test_completion_carries_trace_digest(self, params):
        eng = mk_engine(params)
        eng.submit([1, 2, 3], 4)
        eng.submit([4, 5], 3)
        done = eng.run()
        assert len(done) == 2
        for c in done:
            t = c.trace
            assert t is not None
            assert t["trace_id"].startswith("0x") and len(t["trace_id"]) == 18
            assert t["queue_s"] >= 0 and t["prefill_chunks"] >= 1
            assert t["decode_steps"] >= 1 and t["reason"] == "complete"
            assert t["decode_s"] <= t["total_s"] + 1e-9

    def test_requestz_lookup_by_rid_and_trace_id(self, params):
        eng = mk_engine(params)
        adm = eng.submit([1, 2, 3], 3)
        eng.run()
        by_rid = eng.request_timeline(adm.request.rid)
        by_tid = eng.request_timeline(
            format_trace_id(adm.request.trace_id)
        )
        assert by_rid is not None and by_rid == by_tid
        names = [e["name"] for e in by_rid["events"]]
        assert names[0] == ADMIT and names[-1] == RETIRE
        assert QUEUE in names and PREFILL_CHUNK in names and DECODE in names
        assert by_rid["live"] is False
        assert eng.request_timeline("0xdoesnotparse") is None
        assert eng.request_timeline(99999) is None

    def test_queue_timeout_still_retires_a_timeline(self, params):
        clock = FakeClock()
        eng = ServeEngine(
            SPEC, params, slots=1, prefill_len=8, clock=clock,
            reqtrace=True, trace_seed=7,
        )
        eng.submit([1, 2, 3], 20)  # hogs the only lane
        eng.submit([4, 5], 4, timeout=0.5)
        clock.t = 1.0
        eng.step()
        tl = eng.request_timeline(1)
        assert tl is not None
        names = [e["name"] for e in tl["events"]]
        # Never bound a lane: admit → retire, no prefill/decode.
        assert names == [ADMIT, QUEUE, RETIRE] or names == [ADMIT, RETIRE]
        assert tl["summary"]["reason"] == "timeout_queue"
        eng.run()

    def test_retained_ring_is_bounded(self, params):
        eng = ServeEngine(
            SPEC, params, slots=2, prefill_len=8, reqtrace=True,
            reqtrace_keep=3, trace_seed=7,
        )
        for i in range(5):
            eng.submit([1 + i, 2, 3], 2)
        eng.run()
        assert eng._reqtrace.retired_count == 3
        assert eng.request_timeline(0) is None  # evicted
        assert eng.request_timeline(4) is not None


class TestPerfettoExport:
    def test_exported_spans_reconstruct_causally(self, params, tmp_path):
        """The smoke-tier schema + causal-ordering pin: staggered
        mixed-length traffic, export through the tracer, schema-lint
        the file, reconstruct EVERY request, validate each."""
        tracer = Tracer(enabled=True, process_id=0)
        eng = mk_engine(params, tracer=tracer)
        eng.submit(list(range(1, 8)), 4)  # multi-chunk prompt
        eng.submit([4, 5], 5)
        eng.step()
        eng.submit([6, 7, 8], 3)  # arrives mid-flight
        eng.run()
        path = str(tmp_path / "t.trace.json")
        tracer.export(path)
        doc = validate_trace_file(path)  # async events pass the lint
        timelines = reconstruct_requests(doc["traceEvents"])
        assert len(timelines) == 3
        for tid, timeline in timelines.items():
            summary = validate_request_timeline(timeline)
            assert summary["reason"] == "complete"
            assert summary["chunks"] >= 1
        # ...and trace ids in the document match the engine's.
        engine_ids = {
            eng.request_timeline(r)["trace_id"] for r in range(3)
        }
        assert engine_ids == set(timelines)

    def test_validator_rejects_acausal_timeline(self):
        """The causal validator actually validates: a retire stamped
        before its decode span's end fails, naming the violation."""
        tid = "0x0000000000000001"
        mk = lambda name, ph, ts, **kw: {  # noqa: E731
            "name": name, "ph": ph, "ts": ts, "cat": "request",
            "id": tid, "pid": 0, "tid": 1, **kw,
        }
        events = [
            mk("request", "b", 0.0), mk("request", "e", 100.0),
            mk(ADMIT, "n", 0.0),
            mk(QUEUE, "b", 0.0), mk(QUEUE, "e", 10.0),
            mk(PREFILL_CHUNK, "b", 20.0, args={"i": 0}),
            mk(PREFILL_CHUNK, "e", 40.0),
            mk(DECODE, "b", 50.0), mk(DECODE, "e", 300.0),  # past retire
            mk(RETIRE, "n", 100.0, args={"reason": "complete"}),
        ]
        timeline = reconstruct_requests(events)[tid]
        with pytest.raises(ValueError, match="decode span runs past"):
            validate_request_timeline(timeline)
        # Chunks out of order fail too.
        events2 = [
            mk("request", "b", 0.0), mk("request", "e", 100.0),
            mk(ADMIT, "n", 0.0),
            mk(PREFILL_CHUNK, "b", 20.0, args={"i": 1}),
            mk(PREFILL_CHUNK, "e", 30.0),
            mk(PREFILL_CHUNK, "b", 40.0, args={"i": 0}),
            mk(PREFILL_CHUNK, "e", 50.0),
            mk(RETIRE, "n", 100.0, args={"reason": "complete"}),
        ]
        timeline2 = reconstruct_requests(events2)[tid]
        with pytest.raises(ValueError, match="chunk indices"):
            validate_request_timeline(timeline2)

    def test_emit_request_spans_retroactively(self, params):
        """The bench path: retire with the tracer's measuring mode
        OFF, then emit retained spans after — same timelines, original
        stamps, no double emission."""
        tracer = Tracer(enabled=False)
        eng = mk_engine(params, tracer=tracer)
        eng.submit([1, 2, 3], 3)
        eng.run()
        tracer.enabled = True
        assert eng.emit_request_spans() == 1
        assert eng.emit_request_spans() == 0  # idempotent
        timelines = reconstruct_requests(
            tracer.trace_document()["traceEvents"]
        )
        assert len(timelines) == 1
        validate_request_timeline(next(iter(timelines.values())))


class TestSpecRounds:
    def test_spec_engine_timeline_carries_rounds(self, params):
        """Speculative engines attribute their verify rounds per
        request: spec_round events (drafted/accepted/emitted) inside
        the decode span, causal like everything else. Slow tier —
        the draft program set compiles."""
        draft = SPEC._replace(d_model=16, depth=1, num_heads=2)
        tracer = Tracer(enabled=True)
        eng = ServeEngine(
            SPEC, params, slots=2, prefill_len=8, tracer=tracer,
            reqtrace=True, trace_seed=7,
            draft_spec=draft, draft_params=init_lm(draft, seed=1),
            spec_tokens=3,
        )
        adm = eng.submit([1, 2, 3], 6)
        eng.run()
        tl = eng.request_timeline(adm.request.rid)
        rounds = [
            e for e in tl["events"] if e["name"] == "req.spec_round"
        ]
        assert rounds, "no spec_round events on a speculative engine"
        assert all(
            e["args"]["drafted"] == 3
            and 0 <= e["args"]["accepted"] <= 3
            and 1 <= e["args"]["emitted"] <= 3
            for e in rounds
        )
        summ = tl["summary"]
        assert summ["spec"]["rounds"] == len(rounds)
        assert summ["spec"]["drafted"] == 3 * len(rounds)
        timelines = reconstruct_requests(
            tracer.trace_document()["traceEvents"]
        )
        v = validate_request_timeline(next(iter(timelines.values())))
        assert v["spec_rounds"] == len(rounds)


class TestDisabledPin:
    def test_off_is_allocation_free_and_schema_unchanged(
        self, params, tmp_path
    ):
        """Request tracing off: no trace digests, no reqtrace stats
        key, serve_request records keep the pre-reqtrace schema, and
        steady-state steps allocate no growing trace state."""
        import tracemalloc

        from ddp_tpu.utils.metrics import MetricsWriter

        mpath = tmp_path / "m.jsonl"
        eng = ServeEngine(
            SPEC, params, slots=2, prefill_len=8,
            metrics=MetricsWriter(str(mpath)), reqtrace=False,
        )
        eng.submit([1, 2, 3], 20)
        eng.submit([4, 5], 20)
        for _ in range(4):
            eng.step()  # warm: past prefill, mid-decode
        tracemalloc.start()
        for _ in range(6):
            eng.step()
        snap1 = tracemalloc.take_snapshot()
        for _ in range(8):
            eng.step()
        snap2 = tracemalloc.take_snapshot()
        tracemalloc.stop()
        import ddp_tpu.obs.reqtrace as reqtrace_mod

        grew = [
            s
            for s in snap2.compare_to(snap1, "filename")
            if reqtrace_mod.__file__ in (s.traceback[0].filename,)
            and s.size_diff > 0
        ]
        assert not grew, f"disabled reqtrace allocated: {grew}"
        done = eng.run()
        assert all(c.trace is None for c in done)
        assert "reqtrace" not in eng.stats()
        eng.metrics.close()
        recs = [
            json.loads(line)
            for line in mpath.read_text().splitlines()
        ]
        reqs = [r for r in recs if r["kind"] == "serve_request"]
        assert reqs and all("trace_id" not in r for r in reqs)

    def test_requestz_off_engine_answers_404(self, params):
        from ddp_tpu.serve.server import LMServer

        eng = ServeEngine(SPEC, params, slots=1, prefill_len=8)
        srv = LMServer(eng)
        status, payload = srv.requestz("id=0")
        assert status == 404 and "off" in payload["error"]
        srv._httpd.server_close()


class TestTransferInvariant:
    @pytest.mark.parametrize("enabled", [True, False])
    def test_token_identity_and_spy_with_tracing_enabled(
        self, params, monkeypatch, enabled
    ):
        """The ISSUE-11 re-pin: with request tracing AND the span
        tracer AND --sanitize all on, the engine still produces
        token-identical output to generate() and the steady-state
        fetches stay ()/[S] int32 — request events are stamped only
        at existing host-touch points. And the ISSUE-24 one: tracing
        never synchronises. Whether the tracer is ``enabled`` or only
        its always-on ring runs, a steady-state step calls
        ``block_until_ready`` zero times (the old "measuring mode"
        called it after every dispatch)."""
        import ddp_tpu.serve.engine as engine_mod

        tracer = Tracer(enabled=enabled)
        eng = ServeEngine(
            SPEC, params, slots=2, prefill_len=8, tracer=tracer,
            reqtrace=True, trace_seed=7, sanitize=True,
        )
        prompt = [1, 2, 3]
        adm = eng.submit(prompt, 12)
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

        syncs = []
        monkeypatch.setattr(engine_mod, "np", _NpSpy())
        monkeypatch.setattr(
            jax, "block_until_ready", lambda x: syncs.append(x) or x
        )
        for _ in range(4):
            eng.step()
        monkeypatch.undo()
        assert not syncs, f"tracing added {len(syncs)} device syncs"
        steps = [e for e in tracer.ring() if e[0] == "serve.step"]
        assert len(steps) == 3 + 4  # spanned, enabled or not
        assert fetched and all(
            shape == () or shape == (eng.num_slots,) for shape in fetched
        ), f"tracing-enabled steady state fetched: {fetched}"
        eng.run()
        ref = np.asarray(
            generate(
                SPEC, params, jnp.asarray([prompt], jnp.int32),
                max_new_tokens=12,
            )
        )[0, len(prompt):].tolist()
        c = eng.result(adm.request.rid)
        assert c.tokens == ref, "token identity broken under tracing"
        assert c.trace is not None and c.trace["reason"] == "complete"
