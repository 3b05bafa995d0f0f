"""Fleet-wide distributed tracing (ISSUE 19).

One causal timeline per request across router, migration, and MPMD
hops — the cross-PROCESS half of the ISSUE-11 request tracer:

1. **Context line** — ``00-<trace>-<span>-<parent>`` round-trips;
   every malformation parses to None (never raises) so a peer's
   garbage costs one counter bump, not a crash.
2. **Wire carriage** — the context rides the DPKV migration header
   and the ACTV p2p ``meta`` side-channel; with tracing off both
   encoders produce bytes IDENTICAL to the pre-trace builds
   (absent-key gating, pinned at the byte level).
3. **Adoption** — a replica engine adopts a valid inbound context
   (its timeline hangs off the router's span, ``trace_propagated``),
   mints locally on garbage (``trace_orphaned``, request still
   served).
4. **Router spans** — dispatch/retry/hedge hops are emitted on the
   request's trace id, exactly one winner per request, losers close
   as cancelled; the untraced router's bodies, digests and state()
   stay byte-identical.
5. **Fleet reconstruction** — router + replica events merge into one
   causally-validated timeline per trace id (in-process smoke here;
   the real 3-process disagg drill is the slow tier below).
6. **Zero added syncs** — the ISSUE-3 transfer spy re-runs green
   with a fleet-ADOPTED trace context and router hops attached.
"""

from __future__ import annotations

import itertools
import json
import os
import random
import subprocess
import sys
import threading
import time

import pytest

from ddp_tpu.obs.reqtrace import (
    ADMIT,
    HOP_CAT,
    HOP_DISPATCH,
    HOP_HEDGE,
    HOP_MIGRATE_EXPORT,
    HOP_MIGRATE_INSTALL,
    HOP_RETRY,
    RequestTracer,
    derive_span_id,
    derive_trace_id,
    encode_trace_context,
    format_trace_id,
    parse_trace_context,
    reconstruct_fleet,
    validate_fleet_timeline,
)
from ddp_tpu.obs.tracer import Tracer, validate_trace_file
from ddp_tpu.serve.fleet import (
    Replica,
    ReplicaUnreachable,
    Router,
    RouterConfig,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------
# Fakes (the test_fleet.py idiom)
# ---------------------------------------------------------------------


class FakeCall:
    def __init__(self, fn, body):
        self.fn = fn
        self.body = body
        self.cancelled = False

    def run(self):
        return self.fn(self.body, self)

    def cancel(self):
        self.cancelled = True


class FakeTransport:
    def __init__(self, handlers):
        self.handlers = handlers
        self.calls: list[FakeCall] = []

    def start(self, url, path, body, timeout):
        call = FakeCall(self.handlers[url], body)
        self.calls.append(call)
        return call

    def get_json(self, url, path, timeout):
        return {"ok": True}


def _replicas(n, slots=2):
    reps = [Replica(i, f"http://replica{i}") for i in range(n)]
    for r in reps:
        r.slots = slots
    return reps


def _router(handlers, reps=None, tracer=None, **cfg):
    """Deterministic first pick: affinity_page=0 = least-loaded =
    lowest index on an idle fleet (the test_fleet.py helper, plus the
    tracer wire)."""
    reps = reps or _replicas(len(handlers))
    defaults = dict(
        affinity=True, affinity_page=0,
        retry_backoff_s=0.001, retry_backoff_cap_s=0.01,
    )
    defaults.update(cfg)
    router = Router(
        reps,
        RouterConfig(**defaults),
        transport=FakeTransport(
            {r.url: handlers[i] for i, r in enumerate(reps)}
        ),
        rng=random.Random(0),
        tracer=tracer,
    )
    return router, reps


def _fake_engine(rtracer, rid_iter):
    """A fake replica that behaves like a traced ServeEngine: adopts
    the inbound context, drives a REAL RequestTracer through a
    causally-ordered admit→chunk→decode→retire, emits into
    ``rtracer``, and echoes the adopted trace id — the engine half of
    the fleet contract without a process."""
    rtr = RequestTracer(keep=64)

    def handler(body, call):
        ctx = parse_trace_context(body["trace"])
        assert ctx is not None, body.get("trace")
        rid = next(rid_iter)
        t = rtr.admit(rid, ctx[0], parent=f"{ctx[1]:016x}")
        now = time.perf_counter()
        t.bind(now)
        t.prefill_chunk(
            now, 1e-4, start=0, bucket=8,
            tokens=len(body["prompt_tokens"]), final=True,
        )
        t.decode_step(now + 2e-4)
        t.decode_step(now + 3e-4)
        # let the wall clock pass the stamped offsets: retire (real
        # perf_counter) must close AFTER the last decode stamp or the
        # causal validator rightly rejects the timeline
        time.sleep(0.002)
        rtr.retire(rid, "complete", tracer=rtracer)
        return 200, {
            "rid": rid, "status": "complete", "tokens": [1, 2],
            "trace_id": format_trace_id(ctx[0]),
        }

    return handler


# ---------------------------------------------------------------------
# 1. Context line
# ---------------------------------------------------------------------


class TestContext:
    def test_roundtrip(self):
        for tid, span, parent in [
            (1, 2, 0),
            (0xDEADBEEFCAFEF00D, 0x123456789ABCDEF0, 0xFFFFFFFFFFFFFFFF),
            (derive_trace_id(7, 3), derive_span_id(derive_trace_id(7, 3), 1), 5),
        ]:
            line = encode_trace_context(tid, span, parent)
            assert parse_trace_context(line) == (tid, span, parent)
            assert len(line) == 2 + 3 * 17  # "00" + 3 x "-<16-hex>"

    def test_malformations_parse_to_none_never_raise(self):
        tid = derive_trace_id(1, 1)
        good = encode_trace_context(tid, 2, 0)
        assert parse_trace_context(good) is not None
        bad = [
            None,                                   # wrong type
            123,                                    # wrong type
            "",                                     # empty
            good.replace("00-", "01-", 1),          # version
            good[:-1],                              # width
            good.replace("-", "_"),                 # separators
            "00-" + "zz" * 8 + good[19:],           # non-hex
            encode_trace_context(0, 2, 0),          # zero trace id
            good + "-0000000000000000",             # field count
        ]
        for line in bad:
            assert parse_trace_context(line) is None, line

    def test_derived_spans_nonzero_deterministic_salt_distinct(self):
        tid = derive_trace_id(7, 42)
        spans = {derive_span_id(tid, salt) for salt in range(64)}
        assert len(spans) == 64 and 0 not in spans
        assert derive_span_id(tid, 3) == derive_span_id(tid, 3)


# ---------------------------------------------------------------------
# 2. Wire carriage: DPKV migration header + ACTV p2p meta
# ---------------------------------------------------------------------


class TestWireCarriage:
    def _pages(self):
        import numpy as np

        depth, n_pages, ps, h_kv, d_head = 2, 1, 4, 2, 4
        rng = np.random.default_rng(0)
        k = rng.standard_normal(
            (depth, n_pages, ps, h_kv, d_head)
        ).astype(np.float32)
        v = rng.standard_normal(k.shape).astype(np.float32)
        return list(range(n_pages * ps)), k, v, ps

    def test_dpkv_header_roundtrip_and_absent_key_bytes(self):
        from ddp_tpu.serve.disagg import (
            PageWireError,
            decode_pages,
            encode_pages,
        )

        tokens, k, v, ps = self._pages()
        tid = derive_trace_id(9, 1)
        line = encode_trace_context(tid, derive_span_id(tid, 2), 0)
        traced = encode_pages(tokens, k, v, page_size=ps, trace=line)
        frame = decode_pages(traced)
        assert frame.trace == line
        assert parse_trace_context(frame.trace)[0] == tid
        # absent-key gating, at the byte level: trace=None IS the
        # pre-trace wire — no key, not a null
        untraced = encode_pages(tokens, k, v, page_size=ps)
        assert untraced == encode_pages(
            tokens, k, v, page_size=ps, trace=None
        )
        assert b'"trace"' in traced and b'"trace"' not in untraced
        assert decode_pages(untraced).trace is None
        # the trace field does not weaken wire validation: a torn
        # traced payload still fails loudly
        with pytest.raises(PageWireError):
            decode_pages(traced[: len(traced) - 3])

    def test_actv_meta_roundtrip_and_absent_key_bytes(self):
        import numpy as np

        from ddp_tpu.runtime.p2p import KIND_ACT, decode_msg, encode_msg

        tid = derive_trace_id(9, 2)
        line = encode_trace_context(tid, derive_span_id(tid, 1), 0)
        arrays = {"x": np.arange(6, dtype=np.float32).reshape(2, 3)}
        traced = encode_msg(
            KIND_ACT, 3, 0, arrays, meta={"trace": line}
        )
        msg = decode_msg(traced)
        assert msg.meta["trace"] == line
        assert parse_trace_context(msg.meta["trace"])[0] == tid
        # meta=None is byte-identical to the pre-trace encoder (the
        # header always carried an empty meta dict)
        assert encode_msg(KIND_ACT, 3, 0, arrays, meta=None) == \
            encode_msg(KIND_ACT, 3, 0, arrays)
        assert b'"trace"' not in encode_msg(KIND_ACT, 3, 0, arrays)


# ---------------------------------------------------------------------
# 3. Router spans (unit tier: fake transport, real tracer)
# ---------------------------------------------------------------------


class TestRouterSpans:
    def test_traced_dispatch_stamps_context_hops_and_spans(self):
        tracer = Tracer(enabled=True)
        seen = {}

        def echo(body, call):
            seen.update(body)
            ctx = parse_trace_context(body["trace"])
            return 200, {
                "rid": 1, "status": "complete", "tokens": [1, 2],
                "trace_id": format_trace_id(ctx[0]),
            }

        router, _ = _router([echo], tracer=tracer)
        status, payload = router.dispatch(
            {"prompt_tokens": [1, 2, 3], "max_new_tokens": 2}
        )
        assert status == 200
        d = payload["router"]
        # outbound body carried the context + staging hop seconds
        ctx = parse_trace_context(seen["trace"])
        assert ctx is not None
        assert format_trace_id(ctx[0]) == d["trace_id"]
        assert "queue_s" in seen["hops"]
        # the digest answers "which hop paid" in seconds
        assert d["hops"]["queue_s"] >= 0
        assert d["hops"]["dispatch_s"] > 0
        # the echo counted as propagation
        assert router.trace_propagated_total == 1
        assert router.trace_orphaned_total == 0
        assert "dispatch" in router.state()["hop_seconds"]
        # the hop span is on the wire-visible trace id, marked winner
        fleet = reconstruct_fleet(tracer.trace_document()["traceEvents"])
        hops = fleet[d["trace_id"]]["hops"]
        wins = [
            h for h in hops
            if h["name"] == HOP_DISPATCH
            and (h.get("args") or {}).get("winner")
        ]
        assert len(wins) == 1
        assert (wins[0]["args"]).get("span") == f"{ctx[1]:016x}"
        # /requestz ring serves the hop chain back
        entry = router.requestz(d["trace_id"])
        assert entry is not None
        assert entry["router"]["digest"]["trace_id"] == d["trace_id"]
        assert any(
            h["name"] == HOP_DISPATCH for h in entry["router"]["hops"]
        )

    def test_no_echo_counts_orphaned(self):
        tracer = Tracer(enabled=True)

        def mute(body, call):  # an old replica: serves, no echo
            return 200, {"rid": 1, "status": "complete", "tokens": [1]}

        router, _ = _router([mute], tracer=tracer)
        status, _ = router.dispatch(
            {"prompt_tokens": [1], "max_new_tokens": 1}
        )
        assert status == 200
        assert router.trace_orphaned_total == 1
        assert router.trace_propagated_total == 0

    def test_hedge_emits_one_winner_and_a_cancelled_loser(self):
        tracer = Tracer(enabled=True)
        release = threading.Event()

        def slow(body, call):
            release.wait(5.0)
            if call.cancelled:
                raise ReplicaUnreachable(
                    "unreachable", sent=True, cancelled=True
                )
            return 200, {"src": "slow"}

        def fast(body, call):
            ctx = parse_trace_context(body["trace"])
            return 200, {
                "src": "fast",
                "trace_id": format_trace_id(ctx[0]),
            }

        reps = _replicas(2)
        reps[1].inflight = 1  # straggler first: least-loaded = slow
        router, _ = _router(
            [slow, fast], reps=reps, tracer=tracer, hedge_after_s=0.03
        )
        status, payload = router.dispatch(
            {"prompt_tokens": [1], "max_new_tokens": 1}
        )
        release.set()
        assert status == 200 and payload["src"] == "fast"
        tid = payload["router"]["trace_id"]
        fleet = reconstruct_fleet(tracer.trace_document()["traceEvents"])
        hops = fleet[tid]["hops"]
        dispatches = [h for h in hops if h["name"] == HOP_DISPATCH]
        assert len(dispatches) == 2  # primary + hedge
        winners = [
            h for h in dispatches
            if (h.get("args") or {}).get("winner")
        ]
        cancelled = [
            h for h in dispatches
            if (h.get("args") or {}).get("cancelled")
        ]
        assert len(winners) == 1 and len(cancelled) == 1
        assert winners[0] is not cancelled[0]
        assert any(h["name"] == HOP_HEDGE for h in hops)

    def test_replay_closes_failed_span_and_marks_retry(self):
        tracer = Tracer(enabled=True)

        def dead(body, call):
            raise ReplicaUnreachable("unreachable", sent=True)

        def echo(body, call):
            ctx = parse_trace_context(body["trace"])
            return 200, {
                "rid": 1, "status": "complete", "tokens": [1],
                "trace_id": format_trace_id(ctx[0]),
            }

        router, _ = _router([dead, echo], tracer=tracer)
        status, payload = router.dispatch(
            {"prompt_tokens": [1], "max_new_tokens": 1}
        )
        assert status == 200 and payload["router"]["replays"] == 1
        tid = payload["router"]["trace_id"]
        fleet = reconstruct_fleet(tracer.trace_document()["traceEvents"])
        hops = fleet[tid]["hops"]
        dispatches = [h for h in hops if h["name"] == HOP_DISPATCH]
        assert len(dispatches) == 2
        failed = [
            h for h in dispatches
            if (h.get("args") or {}).get("error")
        ]
        winners = [
            h for h in dispatches
            if (h.get("args") or {}).get("winner")
        ]
        assert len(failed) == 1 and len(winners) == 1
        assert any(h["name"] == HOP_RETRY for h in hops)

    def test_untraced_router_is_byte_identical(self):
        """Tracing off (no tracer, or a disabled one): outgoing
        bodies carry no trace/hops keys, digests carry no hops, and
        state() has no trace block — the PR-18 shapes exactly."""
        for tracer in (None, Tracer(enabled=False)):
            seen = {}

            def capture(body, call):
                seen.update(body)
                return 200, {
                    "rid": 1, "status": "complete", "tokens": [1],
                }

            router, _ = _router([capture], tracer=tracer)
            status, payload = router.dispatch(
                {"prompt_tokens": [1], "max_new_tokens": 1}
            )
            assert status == 200
            assert "trace" not in seen and "hops" not in seen
            assert "hops" not in payload["router"]
            state = router.state()
            assert "trace_propagated_total" not in state
            assert "trace_orphaned_total" not in state
            assert "hop_seconds" not in state
            assert router.requestz(payload["router"]["trace_id"]) is None


# ---------------------------------------------------------------------
# 4. Engine adoption (real jax engine, tiny model)
# ---------------------------------------------------------------------


from ddp_tpu.models.lm import LMSpec, init_lm  # noqa: E402
from ddp_tpu.serve.engine import ServeEngine  # noqa: E402

SPEC = LMSpec(vocab_size=37, total_len=32, d_model=32, depth=2, num_heads=4)


@pytest.fixture(scope="module")
def params():
    return init_lm(SPEC, seed=0)


def mk_engine(params, *, tracer=None, reqtrace=True, **kw):
    kw.setdefault("slots", 2)
    kw.setdefault("prefill_len", 8)
    return ServeEngine(
        SPEC, params, tracer=tracer, reqtrace=reqtrace, trace_seed=7,
        **kw,
    )


class TestEngineAdoption:
    def test_valid_context_is_adopted(self, params):
        eng = mk_engine(params)
        tid = derive_trace_id(99, 1)
        line = encode_trace_context(tid, derive_span_id(tid, 5), 0)
        adm = eng.submit([1, 2, 3], 2, trace=line)
        assert adm.accepted
        # the request's identity IS the router's — not a local mint
        assert adm.request.trace_id == tid
        assert eng.trace_propagated == 1 and eng.trace_orphaned == 0
        assert eng.stats()["reqtrace"]["propagated"] == 1

    def test_garbage_context_mints_locally_and_counts(self, params):
        eng = mk_engine(params)
        adm = eng.submit([1, 2], 2, trace="not-a-context")
        assert adm.accepted  # a peer's garbage never rejects
        assert adm.request.trace_id == derive_trace_id(7, adm.request.rid)
        assert eng.trace_orphaned == 1 and eng.trace_propagated == 0

    def test_adopted_timeline_hangs_off_router_span(self, params):
        tracer = Tracer(enabled=True)
        eng = mk_engine(params, tracer=tracer)
        tid = derive_trace_id(99, 2)
        span = derive_span_id(tid, 3)
        eng.submit([1, 2, 3], 2, trace=encode_trace_context(tid, span, 0))
        eng.run()
        eng.emit_request_spans()
        events = tracer.trace_document()["traceEvents"]
        admits = [
            e for e in events
            if e.get("name") == ADMIT
            and e.get("id") == format_trace_id(tid)
        ]
        assert admits
        assert all(
            e["args"].get("parent") == f"{span:016x}" for e in admits
        )

    def test_router_hops_stamped_on_serve_request_record(
        self, params, tmp_path
    ):
        from ddp_tpu.utils.metrics import MetricsWriter

        mpath = tmp_path / "m.jsonl"
        mw = MetricsWriter(str(mpath))
        eng = mk_engine(params, metrics=mw)
        tid = derive_trace_id(99, 3)
        line = encode_trace_context(tid, derive_span_id(tid, 1), 0)
        eng.submit(
            [1, 2, 3], 2, trace=line,
            hops={"queue_s": 0.001, "migrate_s": 0.002},
        )
        eng.submit([4, 5], 2)  # untraced rider: no hops key
        eng.run()
        mw.close()
        recs = [
            json.loads(l) for l in mpath.read_text().splitlines()
        ]
        served = [r for r in recs if r["kind"] == "serve_request"]
        assert len(served) == 2
        hopped = [r for r in served if "hops" in r]
        assert len(hopped) == 1  # absent-key gated on the rider
        hops = hopped[0]["hops"]
        assert hops["queue_s"] == 0.001 and hops["migrate_s"] == 0.002
        # the engine joins its own split so ONE record attributes TTFT
        assert "engine_queue_s" in hops and "engine_decode_s" in hops
        assert hopped[0]["trace_id"] == format_trace_id(tid)

    def test_transfer_spy_green_with_fleet_adoption(
        self, params, monkeypatch
    ):
        """The acceptance re-pin: fleet tracing ON (adopted context +
        router hops + span tracer + reqtrace) adds ZERO device syncs —
        steady-state fetches stay ()/[S] int32 and tokens match
        generate()."""
        import jax
        import jax.numpy as jnp
        import numpy as np

        import ddp_tpu.serve.engine as engine_mod
        from ddp_tpu.models.generate import generate

        tracer = Tracer(enabled=True)
        eng = mk_engine(params, tracer=tracer, sanitize=True)
        tid = derive_trace_id(99, 4)
        line = encode_trace_context(tid, derive_span_id(tid, 1), 0)
        prompt = [1, 2, 3]
        adm = eng.submit(
            prompt, 12, trace=line, hops={"queue_s": 0.001}
        )
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
        assert fetched and all(
            s == () or s == (eng.num_slots,) for s in fetched
        ), f"fleet-traced steady state fetched: {fetched}"
        eng.run()
        ref = np.asarray(
            generate(
                SPEC, params, jnp.asarray([prompt], jnp.int32),
                max_new_tokens=12,
            )
        )[0, len(prompt):].tolist()
        c = eng.result(adm.request.rid)
        assert c.tokens == ref
        assert c.trace["trace_id"] == format_trace_id(tid)


# ---------------------------------------------------------------------
# 5. Cross-replica causal reconstruction (smoke tier, in-process)
# ---------------------------------------------------------------------


def _traced_cluster(n_replicas=2, **cfg):
    """Traced router + fake replica engines sharing one replica-side
    tracer; returns (router, router_tracer, replica_tracer)."""
    router_tracer = Tracer(enabled=True)
    replica_tracer = Tracer(enabled=True, process_id=1)
    rid_iter = itertools.count(1)
    handlers = [
        _fake_engine(replica_tracer, rid_iter) for _ in range(n_replicas)
    ]
    router, reps = _router(handlers, tracer=router_tracer, **cfg)
    return router, reps, router_tracer, replica_tracer


def _merged_events(*tracers):
    out = []
    for t in tracers:
        out.extend(t.trace_document()["traceEvents"])
    return out


class TestFleetReconstruction:
    def test_each_request_yields_one_causal_timeline(self):
        router, _, rt, pt = _traced_cluster(2)
        tids = []
        for i in range(3):
            status, payload = router.dispatch(
                {"prompt_tokens": [i + 1, i + 2], "max_new_tokens": 2}
            )
            assert status == 200
            tids.append(payload["router"]["trace_id"])
        assert len(set(tids)) == 3  # one trace id per request
        fleet = reconstruct_fleet(_merged_events(rt, pt))
        for tid in tids:
            summary = validate_fleet_timeline(fleet[tid])
            assert summary["attempts"] == 1
            assert not summary["hedged"] and not summary["migrated"]
            assert summary["request"]["reason"] == "complete"
            assert summary["hop_seconds"].get(HOP_DISPATCH, 0) > 0

    def test_hedged_request_validates_with_single_winner(self):
        release = threading.Event()
        router_tracer = Tracer(enabled=True)
        replica_tracer = Tracer(enabled=True, process_id=1)
        winner = _fake_engine(replica_tracer, itertools.count(1))

        def straggler(body, call):
            release.wait(5.0)
            raise ReplicaUnreachable(
                "unreachable", sent=True, cancelled=True
            )

        reps = _replicas(2)
        reps[1].inflight = 1  # straggler dispatched first
        router, _ = _router(
            [straggler, winner], reps=reps, tracer=router_tracer,
            hedge_after_s=0.03,
        )
        status, payload = router.dispatch(
            {"prompt_tokens": [1, 2], "max_new_tokens": 2}
        )
        release.set()
        assert status == 200
        tid = payload["router"]["trace_id"]
        fleet = reconstruct_fleet(
            _merged_events(router_tracer, replica_tracer)
        )
        summary = validate_fleet_timeline(fleet[tid])
        assert summary["hedged"] and summary["attempts"] == 2
        assert summary["winner_replica"] == 1
        assert summary["request"]["reason"] == "complete"

    def test_interleaved_processes_do_not_cross_pair(self):
        """Regression: a hedge winner and its cancelled loser emit
        the SAME span names under one trace id from two processes,
        time-interleaved. Folding must scope b/e pairing per pid —
        LIFO over (id, name) alone hands the winner's umbrella and
        decode spans the LOSER's later end timestamps, and the
        causal validator rightly rejects the winner's own timeline
        ("decode span runs past retire")."""

        class _Clock:
            def __init__(self, t):
                self.t = t

            def __call__(self):
                return self.t

        tid = derive_trace_id(31, 1)
        wspan = derive_span_id(tid, 1)
        lspan = derive_span_id(tid, 2)
        aid = format_trace_id(tid)

        def replica(process_id, parent, t0, t_retire):
            tracer = Tracer(enabled=True, process_id=process_id)
            clock = _Clock(t0)
            rtr = RequestTracer(keep=4, clock=clock)
            t = rtr.admit(7, tid, parent=f"{parent:016x}")
            t.bind(t0 + 0.001)
            t.prefill_chunk(
                t0 + 0.001, 0.001, start=0, bucket=8, tokens=4,
                final=True,
            )
            t.decode_step(t0 + 0.003)
            clock.t = t_retire
            rtr.retire(7, "complete", tracer=tracer)
            return tracer

        base = time.perf_counter()
        # loser admits LATER and retires LATER: its begins nest
        # inside the winner's open spans in the merged order
        win = replica(1, wspan, base, base + 0.010)
        lose = replica(2, lspan, base + 0.005, base + 0.020)
        router_t = Tracer(enabled=True)
        router_t.async_complete(
            HOP_DISPATCH, base - 0.002, 0.013, aid,
            {"replica": 0, "span": f"{wspan:016x}", "winner": True},
            cat=HOP_CAT,
        )
        router_t.async_complete(
            HOP_DISPATCH, base - 0.001, 0.022, aid,
            {"replica": 1, "span": f"{lspan:016x}", "cancelled": True},
            cat=HOP_CAT,
        )
        fleet = reconstruct_fleet(_merged_events(router_t, win, lose))
        summary = validate_fleet_timeline(fleet[tid_hex := aid])
        assert summary["attempts"] == 2
        # the winner's umbrella kept ITS end, not the loser's
        umbrella = [
            e for e in fleet[tid_hex]["request"]
            if e["name"] == "request"
            and (e.get("args") or {}).get("parent") == f"{wspan:016x}"
        ]
        assert len(umbrella) == 1
        assert umbrella[0]["dur"] == pytest.approx(10_000, abs=500)

    def _valid_entry(self):
        router, _, rt, pt = _traced_cluster(1)
        status, payload = router.dispatch(
            {"prompt_tokens": [1, 2], "max_new_tokens": 2}
        )
        assert status == 200
        fleet = reconstruct_fleet(_merged_events(rt, pt))
        return fleet[payload["router"]["trace_id"]]

    def test_validator_rejects_two_winners(self):
        entry = self._valid_entry()
        win = next(
            h for h in entry["hops"]
            if h["name"] == HOP_DISPATCH and h["args"].get("winner")
        )
        entry["hops"] = entry["hops"] + [dict(win)]
        with pytest.raises(ValueError, match="one winning dispatch"):
            validate_fleet_timeline(entry)

    def test_validator_rejects_missing_replica_admit(self):
        entry = self._valid_entry()
        # a SIGKILLed replica loses its ring: hops with no request
        # events must be NAMED as missing, not silently pass
        entry["request"] = []
        with pytest.raises(ValueError, match="no replica admit"):
            validate_fleet_timeline(entry)

    def test_validator_rejects_install_before_export(self):
        entry = self._valid_entry()
        ts = entry["hops"][0]["ts"]
        entry["hops"] = entry["hops"] + [
            {
                "name": HOP_MIGRATE_EXPORT, "ph": "X",
                "ts": ts, "dur": 100.0, "args": {},
            },
            {
                "name": HOP_MIGRATE_INSTALL, "ph": "X",
                "ts": ts - 500.0, "dur": 50.0, "args": {},
            },
        ]
        with pytest.raises(ValueError, match="install precedes"):
            validate_fleet_timeline(entry)

    def test_validator_rejects_dispatch_after_admit(self):
        entry = self._valid_entry()
        win = next(
            h for h in entry["hops"]
            if h["name"] == HOP_DISPATCH and h["args"].get("winner")
        )
        win["ts"] = win["ts"] + 10_000_000  # router clock 10s late
        with pytest.raises(ValueError, match="follows replica admit"):
            validate_fleet_timeline(entry)


# ---------------------------------------------------------------------
# 6. Export schema + trace_merge fleet sidecar + surfaces
# ---------------------------------------------------------------------


class TestMergedSurfaces:
    def test_exported_hop_spans_pass_trace_schema(self, tmp_path):
        router, _, rt, pt = _traced_cluster(1)
        router.dispatch({"prompt_tokens": [1], "max_new_tokens": 1})
        path = rt.export_to_dir(str(tmp_path / "router"))
        doc = validate_trace_file(path)  # PR-2 schema lint
        assert any(
            e.get("cat") == HOP_CAT for e in doc["traceEvents"]
        )

    def test_trace_merge_builds_fleet_sidecar(self, tmp_path):
        router, _, rt, pt = _traced_cluster(2)
        tids = []
        for i in range(2):
            status, payload = router.dispatch(
                {"prompt_tokens": [i + 1], "max_new_tokens": 1}
            )
            tids.append(payload["router"]["trace_id"])
        rt.export_to_dir(str(tmp_path / "router"))
        pt.export_to_dir(str(tmp_path / "replica0"))
        merged = tmp_path / "merged.trace.json"
        mfile = tmp_path / "m.jsonl"
        out = subprocess.run(
            [
                sys.executable,
                os.path.join(REPO, "scripts", "trace_merge.py"),
                str(tmp_path / "router"), str(tmp_path / "replica0"),
                "-o", str(merged),
                "--metrics_file", str(mfile),
                "--request", tids[0],
            ],
            capture_output=True, text=True, check=True, cwd=REPO,
        ).stdout.splitlines()
        summary = json.loads(out[0])
        assert summary["fleet"]["count"] == 2
        assert summary["fleet"]["causal_ok"] == 2
        assert "dispatch" in str(summary["fleet"]["hop_p99_s"])
        # --request on a fleet id prints the hop chain + verdict
        req = json.loads(out[1])
        assert req["request"] == tids[0]
        assert req["fleet_summary"]["attempts"] == 1
        # the merged document embeds the same sidecar
        doc = json.loads(merged.read_text())
        assert doc["ddp_tpu"]["fleet"]["causal_ok"] == 2
        # --metrics_file wrote the triage record health_report reads
        rec = [
            json.loads(l) for l in mfile.read_text().splitlines()
        ][-1]
        assert rec["kind"] == "fleet_trace"
        assert rec["requests"] == 2 and rec["causal_ok"] == 2
        report = subprocess.run(
            [
                sys.executable,
                os.path.join(REPO, "scripts", "health_report.py"),
                str(mfile),
            ],
            capture_output=True, text=True, check=True, cwd=REPO,
        ).stdout
        assert "fleet trace   : 2 request(s) reconstructed" in report
        assert "2 causal-ok (100.0%)" in report
        assert "worst hop" in report

    def test_health_report_fleet_trace_line_gated(self, tmp_path):
        stream = tmp_path / "train.jsonl"
        stream.write_text(
            json.dumps({"kind": "step", "step": 1, "loss": 1.0}) + "\n"
        )
        out = subprocess.run(
            [
                sys.executable,
                os.path.join(REPO, "scripts", "health_report.py"),
                str(stream),
            ],
            capture_output=True, text=True, check=True, cwd=REPO,
        ).stdout
        assert "fleet trace" not in out

    def test_render_fleet_trace_gauges_gated(self):
        from ddp_tpu.obs.promtext import render_fleet, validate_promtext

        router, _, rt, pt = _traced_cluster(1)
        router.dispatch({"prompt_tokens": [1], "max_new_tokens": 1})
        snap = {
            **router.state(),
            "restarts_total": 0,
            "rolling_restarts_total": 0,
        }
        text = render_fleet(snap, up=True, draining=False)
        assert validate_promtext(text) > 0
        assert "ddp_tpu_fleet_trace_propagated_total 1" in text
        assert "ddp_tpu_fleet_trace_orphaned_total 0" in text
        assert "ddp_tpu_fleet_hop_seconds" in text
        # untraced router: the exposition has NO trace family at all
        plain, _ = _router(
            [lambda body, call: (200, {"status": "complete"})]
        )
        plain.dispatch({"prompt_tokens": [1], "max_new_tokens": 1})
        text2 = render_fleet(
            {
                **plain.state(),
                "restarts_total": 0,
                "rolling_restarts_total": 0,
            },
            up=True, draining=False,
        )
        assert validate_promtext(text2) > 0
        assert "trace_propagated" not in text2
        assert "hop_seconds" not in text2


# ---------------------------------------------------------------------
# 7. Slow tier: the real 3-process disaggregated fleet drill
# ---------------------------------------------------------------------


@pytest.mark.slow
def test_disagg_fleet_trace_drill_one_causal_timeline_per_request(
    tmp_path,
):
    """3-process disagg fleet (prefill, decode, decode) under fire:

    - a SIGKILL takes the busy decode replica down MID-DECODE (its
      in-flight request replays to the survivor), then a hedged stage
      runs once the fleet recovers;
    - every request still completes (zero dropped);
    - the merged router + replica trace dirs reconstruct into exactly
      ONE causally-valid fleet timeline per request — single trace id,
      winning dispatch before the winning admit, handoff/migration
      staged before the win — including a hedged and a replayed one.
    """
    from ddp_tpu.serve.fleet import (
        HEALTHY,
        ROLE_DECODE,
        ROLE_PREFILL,
        FleetServer,
        ReplicaManager,
        Router,
        RouterConfig,
    )

    trace_root = tmp_path / "trace"
    mgr = ReplicaManager(
        3,
        [
            "--init_demo", "--slots", "2", "--seq_len", "128",
            "--vocab_size", "64", "--page_size", "16",
        ],
        workdir=str(tmp_path),
        max_restarts=2,
        restart_backoff=0.2,
        roles=[ROLE_PREFILL, ROLE_DECODE, ROLE_DECODE],
        trace_dir=str(trace_root),
    )
    tracer = Tracer(enabled=True)

    def long_prompt(i):
        return [(i * 7 + j) % 64 for j in range(48)]  # over the cutoff

    try:
        mgr.start()
        router = mgr.attach_router(
            Router(
                mgr.replicas,
                RouterConfig(
                    affinity=True, affinity_page=0,  # least-loaded
                    # spreads the concurrent pair over BOTH decode
                    # replicas, so the kill provably catches in-flight
                    # work (a replay, not just a refused retry)
                    disagg=True, prefill_cutoff_tokens=32,
                    retry_backoff_s=0.02, trace_seed=11,
                ),
                tracer=tracer,
            )
        )
        assert mgr.wait_healthy(300), "fleet never became healthy"

        # Stage A: two concurrent long requests (prefill handoff +
        # /pages migration each) land one per decode replica; once
        # BOTH are past staging and in flight, SIGKILL decode
        # replica 1 — its request MUST replay to the survivor.
        results = {}
        lock = threading.Lock()

        def client(i, max_new=32):
            status, payload = router.dispatch(
                {"prompt_tokens": long_prompt(i), "max_new_tokens": max_new}
            )
            with lock:
                results[i] = (status, payload)

        threads = [
            threading.Thread(target=client, args=(i,)) for i in (0, 1)
        ]
        for t in threads:
            t.start()
        deadline = time.monotonic() + 240
        while time.monotonic() < deadline:
            if mgr.replicas[1].inflight >= 1:
                break
            time.sleep(0.05)
        assert mgr.replicas[1].inflight >= 1, "victim never got traffic"
        # give the second request a moment to reach the other decode
        # replica too (ties race; not load-bearing for the replay)
        spread = time.monotonic() + 10
        while time.monotonic() < spread:
            if mgr.replicas[2].inflight >= 1:
                break
            time.sleep(0.05)
        mgr.kill_replica(1)
        for t in threads:
            t.join()
        assert mgr.chaos_kills == 1
        for i in (0, 1):
            status, payload = results[i]
            assert status == 200, (i, status, payload.get("error"))
        assert router.replays_total >= 1, "kill drew no replay"
        assert router.migrations_total >= 1
        assert any(
            results[i][1]["router"]["replays"] >= 1 for i in (0, 1)
        )

        # Recovery: the supervisor restarts the victim (same trace
        # dir — argparse last-wins keeps the export path stable).
        deadline = time.monotonic() + 240
        while time.monotonic() < deadline:
            if mgr.restarts_total == 1 and all(
                r.state == HEALTHY for r in mgr.replicas
            ):
                break
            time.sleep(0.25)
        assert mgr.restarts_total == 1
        assert all(r.state == HEALTHY for r in mgr.replicas)

        # More migration coverage on the healed fleet.
        for i in (2, 3):
            client(i, max_new=8)
            assert results[i][0] == 200

        # Stage B: short prompts under an aggressive hedge timer —
        # CPU decode of 16 tokens far outlasts 10ms, so the request
        # hedges to the second decode replica; first answer wins.
        router.config = RouterConfig(
            affinity=True, affinity_page=0, disagg=True,
            prefill_cutoff_tokens=32, retry_backoff_s=0.02,
            hedge_after_s=0.01, trace_seed=11,
        )
        hedged_payloads = []
        for i in range(2):
            status, payload = router.dispatch(
                {
                    "prompt_tokens": [(i * 3 + j) % 64 for j in range(8)],
                    "max_new_tokens": 16,
                }
            )
            assert status == 200
            hedged_payloads.append(payload)
        assert router.hedges_total >= 1
        all_tids = [
            results[i][1]["router"]["trace_id"] for i in sorted(results)
        ] + [p["router"]["trace_id"] for p in hedged_payloads]
        assert len(set(all_tids)) == len(all_tids)

        # The fleet front door serves the assembled hop chain.
        import urllib.request

        with FleetServer(mgr, router, port=0) as server:
            probe_tid = hedged_payloads[-1]["router"]["trace_id"]
            with urllib.request.urlopen(
                f"{server.url}/requestz?id={probe_tid}", timeout=10
            ) as resp:
                reqz = json.loads(resp.read())
            assert reqz["trace_id"] == probe_tid
            assert any(
                h["name"] == HOP_DISPATCH for h in reqz["router"]["hops"]
            )
    finally:
        # Graceful drain, NOT the default 0.1s SIGKILL: each replica
        # exports its trace file on the SIGTERM path, and a killed
        # process exports nothing.
        mgr.stop(drain_timeout=90)

    tracer.export_to_dir(str(trace_root / "router"))
    import glob as _glob

    dirs = [str(trace_root / "router")] + sorted(
        _glob.glob(str(trace_root / "replica*"))
    )
    assert len(dirs) == 4  # router + 3 replicas
    merged = tmp_path / "merged.trace.json"
    out = subprocess.run(
        [
            sys.executable,
            os.path.join(REPO, "scripts", "trace_merge.py"),
            *dirs, "-o", str(merged),
        ],
        capture_output=True, text=True, cwd=REPO, timeout=120,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    sidecar = json.loads(out.stdout.splitlines()[0])["fleet"]
    assert sidecar["count"] == len(all_tids)
    assert sidecar["causal_ok"] == len(all_tids), sidecar.get("problems")
    assert sidecar["migrated"] >= 1
    assert sidecar["hedged"] >= 1

    # Re-derive the verdicts from raw events (not just the sidecar):
    # ONE causally-valid timeline per request, and the drill's hedged
    # and replayed requests both validate.
    doc = json.loads(merged.read_text())
    fleet = reconstruct_fleet(doc["traceEvents"])
    summaries = {
        tid: validate_fleet_timeline(fleet[tid]) for tid in all_tids
    }
    assert all(
        s["request"]["reason"] == "complete" for s in summaries.values()
    )
    assert any(s["hedged"] for s in summaries.values())
    assert any(s["attempts"] >= 2 for s in summaries.values())
    assert any(s["migrated"] for s in summaries.values())
