"""ddp_tpu.obs: span tracing, step-time attribution, goodput/MFU.

Three contracts pinned here:

1. **Schema** — every emitted trace is Perfetto/Chrome-loadable
   ``trace_event`` JSON (``validate_trace_file`` runs in the smoke
   tier so an exporter regression fails tier-1 fast).
2. **Disabled is free** — tracing off triggers zero XLA compilations
   and no growing per-step allocations; the attributor hands back the
   caller's iterator untouched.
3. **Numbers are right** — golden FLOPs per model, exact count/mean/
   min/max under StatSummary.merge, MFU ≤ 1 on real runs, goodput
   accumulating across a simulated restart.
"""

import json
import math
import os
import random
import subprocess
import sys
import time

import numpy as np
import pytest

from ddp_tpu.obs.goodput import (
    GoodputAccountant,
    cnn_train_flops,
    lm_train_flops_per_token,
    mfu,
    peak_flops_per_chip,
    resnet_train_flops,
    train_flops_per_example,
    vit_train_flops,
)
from ddp_tpu.obs.steptime import CompileCounter, StepAttributor
from ddp_tpu.obs.tracer import Tracer, validate_trace_file
from ddp_tpu.utils.metrics import StatSummary

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---- tracer ----------------------------------------------------------


def test_trace_schema_valid(tmp_path):
    """Smoke-tier exporter pin: spans + instants + nested spans round-
    trip through export and pass the shared schema validator."""
    t = Tracer(enabled=True, ring_events=256, process_id=2)
    with t.span("outer", {"k": 1}):
        with t.span("inner"):
            time.sleep(0.001)
        t.instant("marker", {"note": "hi"})
    t.complete("retro", time.perf_counter() - 0.01, 0.01)
    path = t.export(str(tmp_path / "t.trace.json"))
    doc = validate_trace_file(path)
    names = {e["name"] for e in doc["traceEvents"]}
    assert {"outer", "inner", "marker", "retro", "process_name"} <= names
    # pid carries the rank; X events carry microsecond durations
    xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert all(e["pid"] == 2 for e in xs)
    inner = next(e for e in xs if e["name"] == "inner")
    assert inner["dur"] >= 900  # ≥0.9ms in µs
    # duration summaries ride along, mergeable
    states = doc["ddp_tpu"]["span_summaries"]
    assert states["inner"]["count"] == 1
    # the validator actually rejects garbage
    bad = tmp_path / "bad.trace.json"
    bad.write_text('{"traceEvents": [{"ph": "X", "name": "x"}]}')
    with pytest.raises(ValueError, match="ts"):
        validate_trace_file(str(bad))


def test_tracer_ring_is_bounded():
    t = Tracer(enabled=True, ring_events=16)
    for i in range(100):
        with t.span("s"):
            pass
    doc = t.trace_document()
    # 16 ring slots + 1 process_name metadata event
    assert len(doc["traceEvents"]) == 17
    assert doc["ddp_tpu"]["dropped_events"] == 84
    # exact summaries survive the ring overwrite (count is all 100)
    assert doc["ddp_tpu"]["span_summaries"]["s"]["count"] == 100


def test_disabled_tracer_is_pinned_free():
    """The always-on level's contract, with nothing switched on: every
    span reaches the ring, the ring never passes ``maxlen``, nothing
    compiles, nothing is allocated beyond the (full) ring, nothing is
    summarised or exported as the ``enabled`` level would, and a span
    costs microseconds. The attributor stays off and hands back the
    caller's iterator. The program has one compile listener whatever
    is constructed."""
    from jax._src import monitoring

    # ONE compile listener in the process, installed with the
    # process-global tracer and always on: however many tracers and
    # attributors are made, on or off, JAX holds the same listeners,
    # and the counter is a view of that one.
    listeners = len(monitoring.get_event_duration_listeners())
    t = Tracer(enabled=False, ring_events=512)
    attr = StepAttributor(enabled=False)
    StepAttributor(enabled=True, tracer=Tracer(enabled=True))
    CompileCounter.install()
    assert CompileCounter.installed()
    assert len(monitoring.get_event_duration_listeners()) == listeners
    # batches() hands back a plain iterator over the input, unwrapped
    data = [1, 2, 3]
    it = attr.batches(data)
    assert list(it) == data
    assert attr.on_step(object()) is None
    before = CompileCounter.count()

    def hot(n):
        for _ in range(n):
            with t.span("hot", nums=(1, 2)) as outer:
                with t.span("inner", parent=outer.t0):
                    pass
            t.instant("i")
            t.counter("c", {"v": 1})
            t.complete("c", 0.0, 0.0, nums=(3,))
            attr.on_step(None)

    hot(1_000)  # fill the ring first: its 512 tuples are the budget
    assert len(t.ring()) == 512
    import tracemalloc

    tracemalloc.start()
    base = tracemalloc.get_traced_memory()[0]
    hot(20_000)
    growth = tracemalloc.get_traced_memory()[0] - base
    tracemalloc.stop()
    # the count moves only when something compiles: nothing did
    assert CompileCounter.count() == before
    assert growth < 64 * 1024, f"always-on obs leaked {growth} bytes"
    ring = t.ring()
    assert len(ring) == 512 == t.ring_events
    name, t0, dur, parent, nums = ring[-1]
    assert (name, nums) == ("c", (3,))
    inner = next(e for e in reversed(ring) if e[0] == "inner")
    outer = next(e for e in reversed(ring) if e[0] == "hot")
    assert inner[3] == outer[1]  # parent is the causing span's t0
    assert outer[1] <= inner[1] and inner[1] + inner[2] <= outer[1] + outer[2]
    # the enabled level's extras stayed off: no summaries, no instants
    # or counter samples; the ring itself is the trace /statusz shows
    snap = t.snapshot(limit=8)
    assert snap["span_summaries"] == {} and snap["dropped_events"] == 0
    assert {e["ph"] for e in snap["traceEvents"]} == {"X"}
    assert snap["traceEvents"][-1]["args"] == {"n0": 3}
    # per-span cost: the chip's host has to stay under 3 us (measured
    # there by scripts/span_cost.py); a shared CPU sandbox gets 10x
    n = 50_000
    t_a = time.perf_counter()
    for _ in range(n):
        with t.span("hot"):
            pass
    per_span = (time.perf_counter() - t_a) / n
    assert per_span < 30e-6, f"{per_span * 1e6:.2f} us a span"


def test_compile_counter_sees_recompiles():
    import jax
    import jax.numpy as jnp

    CompileCounter.install()
    f = jax.jit(lambda x: x * 2 + 1)
    before = CompileCounter.count()
    f(jnp.ones((3,)))
    first = CompileCounter.count()
    assert first > before  # fresh shape → compile
    f(jnp.ones((3,)))
    assert CompileCounter.count() == first  # cached → no event
    f(jnp.ones((4, 4)))
    assert CompileCounter.count() > first  # recompile flagged


# ---- StatSummary.merge ----------------------------------------------


def test_statsummary_merge_exact_property():
    """Property test: for random shardings, merged count/mean/min/max
    equal the pooled-stream values exactly."""
    rng = random.Random(0)
    for trial in range(20):
        n_shards = rng.randint(1, 6)
        shards = [
            [rng.uniform(-1e3, 1e3) for _ in range(rng.randint(0, 400))]
            for _ in range(n_shards)
        ]
        pooled = [v for s in shards for v in s]
        summaries = []
        for i, vals in enumerate(shards):
            s = StatSummary(max_samples=64, seed=i)
            for v in vals:
                s.add(v)
            summaries.append(s)
        merged = summaries[0]
        for s in summaries[1:]:
            merged.merge(s)
        assert merged.count == len(pooled)
        if pooled:
            snap = merged.to_state()
            assert snap["min"] == min(pooled)
            assert snap["max"] == max(pooled)
            assert math.isclose(
                snap["sum"] / snap["count"],
                math.fsum(pooled) / len(pooled),
                rel_tol=1e-9, abs_tol=1e-9,
            )
            # reservoir stays bounded and inside the observed range
            assert len(snap["samples"]) <= 64
            assert all(min(pooled) <= v <= max(pooled) for v in snap["samples"])


def test_statsummary_state_roundtrip():
    s = StatSummary(max_samples=8)
    for v in [3.0, 1.0, 4.0, 1.5]:
        s.add(v)
    r = StatSummary.from_state(s.to_state())
    assert r.count == 4
    assert r.snapshot() == s.snapshot()


# ---- FLOPs goldens ---------------------------------------------------


def test_flops_goldens():
    """Pinned analytic values — any estimator change must be deliberate
    (these feed every published MFU number)."""
    assert cnn_train_flops((28, 28, 1), 10) == 91_069_440.0
    assert resnet_train_flops(
        (32, 32, 3), 10, stage_sizes=(2, 2, 2, 2)
    ) == 3_332_536_320.0
    # ResNet-50/224 ≈ the published ~4.1 GMACs forward
    r50 = resnet_train_flops(
        (224, 224, 3), 1000, stage_sizes=(3, 4, 6, 3),
        bottleneck=True, cifar_stem=False,
    )
    assert r50 == 24_535_105_536.0
    assert abs(r50 / 3 - 2 * 4.1e9) / (2 * 4.1e9) < 0.01
    assert vit_train_flops(
        (32, 32, 3), 100, patch_size=4, embed_dim=192, depth=12,
        num_heads=3,
    ) == 2_190_804_480.0
    # the d1024x8 LM; GQA shrinks it, MoE top-2 grows it
    mha = lm_train_flops_per_token(
        vocab_size=8192, total_len=2048, d_model=1024, depth=8,
        num_heads=8,
    )
    assert mha == 754_974_720.0
    gqa = lm_train_flops_per_token(
        vocab_size=8192, total_len=2048, d_model=1024, depth=8,
        num_heads=8, num_kv_heads=2,
    )
    assert gqa < mha
    moe = lm_train_flops_per_token(
        vocab_size=256, total_len=128, d_model=64, depth=2,
        num_heads=4, num_experts=4, moe_every=2, moe_top_k=2,
    )
    assert moe == 984_576.0
    # registry resolution: unknown model → None (absent, never zero)
    assert train_flops_per_example("no_such_model") is None
    assert train_flops_per_example(
        "simple_cnn", image_shape=(28, 28, 1), num_classes=10
    ) == 91_069_440.0


def test_peak_flops_per_chip_is_strict():
    """Listed TPU kind → its peak; unlisted TPU kind → error; anything
    else → no peak, so no MFU (a CPU number never sits under a device
    metric's name)."""
    import types

    def dev(platform, kind):
        return types.SimpleNamespace(platform=platform, device_kind=kind)

    assert peak_flops_per_chip(dev("tpu", "TPU v5 lite")) == 197e12
    assert peak_flops_per_chip(dev("tpu", "TPU v5p chip")) == 459e12
    with pytest.raises(ValueError, match="TPU v9 mystery"):
        peak_flops_per_chip(dev("tpu", "TPU v9 mystery"))
    assert peak_flops_per_chip(dev("cpu", "cpu")) is None
    assert peak_flops_per_chip() is None  # the suite runs on CPU
    assert mfu(1000.0, 1e9, peak_flops_per_chip()) is None


# ---- goodput accountant ---------------------------------------------


def test_goodput_survives_restart(tmp_path):
    sidecar = str(tmp_path / "goodput.json")
    clock = {"t": 1000.0}
    acc = GoodputAccountant(sidecar, clock=lambda: clock["t"])
    acc.start_run()
    clock["t"] += 10.0
    acc.add_productive(6.0)
    acc.flush()
    snap = acc.snapshot()
    assert snap["restarts"] == 0
    assert snap["goodput"] == pytest.approx(0.6)
    # simulated kill + relaunch: wall keeps running, sidecar reloads
    clock["t"] += 10.0  # downtime
    acc2 = GoodputAccountant(sidecar, clock=lambda: clock["t"])
    acc2.start_run()
    clock["t"] += 10.0
    acc2.add_productive(9.0)
    acc2.flush()
    snap2 = acc2.snapshot()
    assert snap2["restarts"] == 1
    assert snap2["productive_s"] == pytest.approx(15.0)
    assert snap2["wall_s"] == pytest.approx(30.0)  # since FIRST launch
    assert snap2["goodput"] == pytest.approx(0.5)
    # disabled / corrupt-sidecar robustness
    GoodputAccountant(None).start_run()
    (tmp_path / "goodput.json").write_text("{not json")
    acc3 = GoodputAccountant(sidecar, clock=lambda: clock["t"])
    acc3.start_run()
    assert acc3.restarts == 0  # fresh start, no crash


# ---- trainer integration --------------------------------------------


def _train_config(tmp_path, **kw):
    from ddp_tpu.train.config import TrainConfig

    defaults = dict(
        epochs=1,
        batch_size=4,
        checkpoint_dir=str(tmp_path / "ck"),
        data_root=str(tmp_path / "data"),
        synthetic_data=True,
        synthetic_size=256,  # 256/(4*8) = 8 steps
        log_interval=2,
        eval_every=0,
        metrics_file=str(tmp_path / "metrics.jsonl"),
        trace_dir=str(tmp_path / "traces"),
    )
    defaults.update(kw)
    return TrainConfig(**defaults)


def _records(tmp_path):
    lines = (tmp_path / "metrics.jsonl").read_text().splitlines()
    return [json.loads(l) for l in lines]


def test_trainer_trace_dir_attribution_and_mfu(tmp_path, monkeypatch):
    """Acceptance pin: a --trace_dir run emits a Perfetto-loadable
    trace, per-step records carry input_wait_s/compute_s/recompiles/
    mfu, and mfu ≤ 1 on the step path. A CPU has no peak, so the MFU
    wiring is pinned against a stand-in chip peak."""
    import ddp_tpu.train.trainer as trainer_mod
    from ddp_tpu.train.trainer import Trainer

    monkeypatch.setattr(
        trainer_mod, "peak_flops_per_chip", lambda device: 197e12
    )
    t = Trainer(_train_config(tmp_path))
    t.train()
    t.close()

    steps = [r for r in _records(tmp_path) if r["kind"] == "step"]
    assert steps
    for r in steps:
        for key in ("input_wait_s", "dispatch_s", "compute_s", "recompiles"):
            assert key in r, f"step record missing {key}"
        assert 0.0 <= r["mfu"] <= 1.0
        assert r["input_wait_s"] >= 0 and r["compute_s"] >= 0
    # the first logged step paid the compile; it is flagged
    assert steps[0]["recompiles"] >= 1
    epoch = next(r for r in _records(tmp_path) if r["kind"] == "epoch")
    assert 0.0 <= epoch["mfu"] <= 1.0
    assert epoch["recompiles"] >= 1
    assert 0.0 < epoch["goodput"] <= 1.0
    assert epoch["input_wait_s"] >= 0 and epoch["compute_s"] >= 0
    final = next(r for r in _records(tmp_path) if r["kind"] == "final")
    assert final["goodput"]["productive_s"] > 0

    doc = validate_trace_file(
        str(tmp_path / "traces" / "trace_rank0.trace.json")
    )
    names = {e["name"] for e in doc["traceEvents"]}
    # input wait and dispatch are spanned where they happen (the
    # loader, the step call), once; the attributor adds the device wait
    assert {
        "epoch", "data.next_batch", "train.dispatch", "step.compute",
        "checkpoint.save",
    } <= names
    assert not {"step.input_wait", "step.dispatch"} & names
    # goodput sidecar persisted next to the checkpoints
    sidecar = json.load(open(tmp_path / "ck" / "goodput.json"))
    assert sidecar["productive_s"] > 0


def test_trainer_fast_path_epoch_attribution(tmp_path):
    """--fast_epoch attribution is per-epoch (one dispatch): the epoch
    record carries dispatch/compute/recompiles; the trace shows the
    staging + epoch spans."""
    from ddp_tpu.train.trainer import Trainer

    t = Trainer(_train_config(tmp_path, fast_epoch=True))
    t.train()
    t.close()

    epoch = next(r for r in _records(tmp_path) if r["kind"] == "epoch")
    assert epoch["recompiles"] >= 1
    assert epoch["dispatch_s"] >= 0 and epoch["compute_s"] >= 0
    doc = validate_trace_file(
        str(tmp_path / "traces" / "trace_rank0.trace.json")
    )
    names = {e["name"] for e in doc["traceEvents"]}
    assert {"fast.stage_dataset", "epoch.dispatch", "epoch.compute"} <= names


def test_trainer_tracing_off_changes_nothing(tmp_path):
    """trace_dir=None: attribution disabled, step records keep the
    pre-obs schema (no attribution keys), no trace files appear —
    and, on a CPU (no peak), no mfu anywhere: never a CPU number under
    a device metric's name."""
    from ddp_tpu.train.trainer import Trainer

    t = Trainer(_train_config(tmp_path, trace_dir=None))
    assert t.tracer.enabled is False and t._attr.enabled is False
    t.train()
    t.close()
    steps = [r for r in _records(tmp_path) if r["kind"] == "step"]
    for r in steps:
        assert "input_wait_s" not in r and "recompiles" not in r
    assert not any("mfu" in r for r in _records(tmp_path))
    assert not list(tmp_path.glob("**/*.trace.json"))


# ---- serve integration ----------------------------------------------


def test_serve_spans_statusz_and_goodput(tmp_path):
    from ddp_tpu.models.lm import LMSpec, init_lm
    from ddp_tpu.serve.engine import ServeEngine
    from ddp_tpu.serve.server import LMServer

    spec = LMSpec(vocab_size=37, total_len=32, d_model=32, depth=2, num_heads=4)
    tracer = Tracer(enabled=True, ring_events=1024, process_id=0)
    engine = ServeEngine(
        spec, init_lm(spec, seed=0), slots=2, prefill_len=8,
        tracer=tracer,
    )
    engine.submit([1, 2, 3], 4)
    engine.submit([4, 5], 3)
    engine.run()

    stats = engine.stats()
    gp = stats["goodput"]
    assert gp["productive_s"] > 0 and 0 < gp["goodput"] <= 1
    # spans for chunked prefill / decode / sampled-token retirement
    doc_names = {e["name"] for e in tracer.trace_document()["traceEvents"]}
    assert {
        "serve.prefill_chunk", "serve.decode", "serve.sample",
    } <= doc_names
    # /statusz serves stats + a loadable live trace tail
    server = LMServer(engine)
    try:
        statusz = server.snapshot("/statusz")
    finally:
        server._httpd.server_close()
    assert statusz["ok"] is True
    assert statusz["stats"]["goodput"]["productive_s"] > 0
    trace = statusz["trace"]
    assert trace["enabled"] is True
    assert any(e["name"] == "serve.decode" for e in trace["traceEvents"])
    # the exported file validates like the trainer's
    path = tracer.export(str(tmp_path / "serve.trace.json"))
    validate_trace_file(path)


# ---- the always-on span layer, inside the program ----------------------


def _tiny_engine(**kw):
    from ddp_tpu.models.lm import LMSpec, init_lm
    from ddp_tpu.serve.engine import ServeEngine

    spec = LMSpec(vocab_size=37, total_len=32, d_model=32, depth=2,
                  num_heads=4)
    kw.setdefault("slots", 2)
    kw.setdefault("prefill_len", 8)
    return ServeEngine(spec, init_lm(spec, seed=0), **kw)


def _since(ring_before):
    """The global ring's spans recorded after ``ring_before`` was
    taken (the ring is process-global: other tests wrote to it)."""
    from ddp_tpu.obs.tracer import get_tracer

    ring = get_tracer().ring()
    t_last = ring_before[-1][1] + ring_before[-1][2] if ring_before else 0.0
    return [e for e in ring if e[1] >= t_last]


def test_engine_and_server_record_into_the_global_ring():
    """No tracer argument, nothing switched on: a tiny engine behind a
    tiny LMServer serves a few requests, and the process-global ring
    then holds one ``serve.step`` per engine step whose children lie
    inside it, do not overlap and name it as ``parent``, and one
    ``server.request`` per request with its rid and its waits."""
    from concurrent.futures import ThreadPoolExecutor

    from ddp_tpu.obs.tracer import SPAN_NUMS, get_tracer
    from ddp_tpu.serve.server import LMServer

    before = get_tracer().ring()
    eng = _tiny_engine()
    assert eng.tracer is get_tracer() and not eng.tracer.enabled
    steps_before = eng._steps
    bodies = [
        {"prompt_tokens": [1, 2, 3], "max_new_tokens": 4},
        {"prompt_tokens": [4, 5], "max_new_tokens": 3},
        {"prompt_tokens": [6, 7, 8, 9], "max_new_tokens": 5},
    ]
    with LMServer(eng) as server:
        assert server.tracer is get_tracer()
        with ThreadPoolExecutor(3) as pool:
            answers = list(pool.map(server.submit_and_wait, bodies))
    assert [a[0] for a in answers] == [200, 200, 200]
    spans = _since(before)
    steps = [e for e in spans if e[0] == "serve.step"]
    assert len(steps) == eng._steps - steps_before > 0
    emitted = 0
    for name, t0, dur, parent, nums in steps:
        assert parent is None and len(nums) == len(SPAN_NUMS[name])
        emitted += nums[0]
        kids = sorted(
            (e for e in spans if e[3] == t0), key=lambda e: e[1]
        )
        assert {"serve.retire", "serve.admit"} <= {k[0] for k in kids}
        end = t0
        for k in kids:  # inside the step, one after the other
            assert k[1] >= end and k[1] + k[2] <= t0 + dur
            end = k[1] + k[2]
    assert emitted == eng.tokens_emitted_total == 4 + 3 + 5
    # a token fetch inside retire is retire's child, not the step's
    for e in spans:
        if e[0] == "serve.sample":
            owner = next(p for p in spans if p[1] == e[3])
            assert owner[0] in ("serve.step", "serve.retire")
    reqs = [e for e in spans if e[0] == "server.request"]
    assert sorted(e[4][0] for e in reqs) == sorted(
        a[1]["rid"] for a in answers
    )
    for _, _, dur, _, (rid, lock_wait_s, pickup_s, poll_wait_s) in reqs:
        assert 0 <= lock_wait_s <= dur and 0 <= pickup_s <= dur
        assert poll_wait_s >= 0
    # ... and where ttft goes: stats() and /metricsz
    stats = eng.stats()
    assert stats["accepted_total"] == 3
    assert stats["lock_wait_s"]["count"] == stats["pickup_s"]["count"] == 3
    from ddp_tpu.obs.promtext import render_serve, validate_promtext

    text = render_serve(stats)
    validate_promtext(text)
    assert "ddp_tpu_serve_accepted_total 3" in text
    assert "ddp_tpu_serve_submit_lock_wait_seconds_count 3" in text
    assert "ddp_tpu_serve_result_pickup_seconds_count 3" in text


def test_kv_rows_counters_against_a_hand_count():
    """``kv_rows_attended_total`` / ``kv_rows_lane_total``: three
    requests on a two-lane engine (one has to wait for a lane). A
    request with prompt P and N tokens takes N - 1 decode steps, its
    first token coming from prefill, and attends P + e rows in the
    step that emits token e + 1 — whatever the schedule. Each
    ``serve.decode`` span carries its step's share as ``nums[1]``."""
    from ddp_tpu.obs.promtext import render_serve, validate_promtext
    from ddp_tpu.obs.tracer import SPAN_NUMS, get_tracer

    assert SPAN_NUMS["serve.decode"] == ("lanes", "rows_attended")
    before = get_tracer().ring()
    eng = _tiny_engine()
    for prompt, n in (([1, 2, 3], 4), ([4, 5], 3), ([6, 7, 8, 9], 5)):
        eng.submit(prompt, n)
    eng.run()
    # P=3,N=4: 4+5+6 = 15; P=2,N=3: 3+4 = 7; P=4,N=5: 5+6+7+8 = 26
    assert eng.kv_rows_attended_total == 15 + 7 + 26
    decodes = [e for e in _since(before) if e[0] == "serve.decode"]
    assert sum(e[4][1] for e in decodes) == 48
    # at most two lanes decode in a step, each at most total_len deep
    assert all(1 <= e[4][0] <= 2 and e[4][1] <= 2 * 32 for e in decodes)
    # the lanes hold slots x total_len rows, decoded or idle
    assert eng.kv_rows_lane_total == len(decodes) * 2 * 32
    stats = eng.stats()
    assert stats["kv_rows_attended_total"] == 48
    assert stats["kv_rows_lane_total"] == eng.kv_rows_lane_total
    text = render_serve(stats)
    validate_promtext(text)
    assert "ddp_tpu_serve_kv_rows_attended_total 48" in text
    assert (
        f"ddp_tpu_serve_kv_rows_lane_total {eng.kv_rows_lane_total}" in text
    )


def test_stats_and_metricsz_do_not_take_the_engine_lock():
    """/stats and /metricsz are reads of plain host-side state: they
    answer while another thread holds the lock the engine loop holds
    for every step (13 s for one locked read on the chip)."""
    import threading

    from ddp_tpu.serve.server import LMServer

    eng = _tiny_engine()
    eng.submit([1, 2, 3], 4)
    eng.run()
    server = LMServer(eng)
    out = {}
    try:
        with server._lock:  # the engine loop, mid-step
            th = threading.Thread(target=lambda: out.update(
                stats=server.snapshot("/stats"),
                metricsz=server.snapshot("/metricsz"),
            ))
            th.start()
            th.join(timeout=30)
            assert not th.is_alive(), "a stats read waited for the lock"
    finally:
        server._httpd.server_close()
    assert out["stats"]["tokens_total"] == 4
    assert "ddp_tpu_serve_tokens_total 4" in out["metricsz"]


def test_trainer_and_loader_span_every_step(tmp_path):
    """A tiny Trainer over its ShardedLoader, no --trace_dir: one
    ``data.next_batch`` with rows and one ``train.dispatch`` a step in
    the global ring, the fetch before the dispatch it feeds."""
    from ddp_tpu.obs.tracer import get_tracer
    from ddp_tpu.train.trainer import Trainer

    before = get_tracer().ring()
    t = Trainer(_train_config(tmp_path, trace_dir=None))
    assert t.tracer is get_tracer() and t.loader.tracer is get_tracer()
    spe = t.loader.steps_per_epoch()
    t.train()
    t.close()
    spans = _since(before)
    fetches = [e for e in spans if e[0] == "data.next_batch" and e[4][0]]
    dispatches = [e for e in spans if e[0] == "train.dispatch"]
    assert len(fetches) == len(dispatches) == spe == 8
    assert all(e[4] == (t.loader.local_batch_size,) for e in fetches)
    # the one fetch that found the epoch exhausted says so
    empty = [e for e in spans if e[0] == "data.next_batch" and not e[4][0]]
    assert len(empty) == 1
    for f, d in zip(fetches, dispatches):
        assert f[1] + f[2] <= d[1] + d[2]


def test_spans_land_in_the_profilers_trace(tmp_path):
    """With a profiler session open the same spans are events on a host
    line of the ``.xplane.pb``, on the profiler's clock: nothing else
    has to be switched on."""
    import glob

    import jax

    eng = _tiny_engine()
    eng.submit([1, 2, 3], 3)
    eng.step()  # compile outside the session
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        eng.run()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(
        str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb")
    )
    pd = jax.profiler.ProfileData.from_file(path)
    host = {}
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("serve."):
                        host.setdefault(ev.name, []).append(
                            (ev.start_ns, ev.duration_ns))
    assert {"serve.step", "serve.retire", "serve.admit", "serve.decode",
            "serve.sample"} <= set(host)
    s0, sd = host["serve.step"][0]
    r0, rd = host["serve.retire"][0]
    assert s0 <= r0 and r0 + rd <= s0 + sd  # nested on that clock too


def test_programs_and_kernels_carry_their_names(request):
    """What the device trace tells programs and kernels apart by, read
    from the lowered text: the engine's programs are modules of their
    own, the Pallas kernels carry ``flash_fwd`` / ``flash_dkv`` (the
    backward's ONE kernel where a head fits the VMEM; with ``flash_dq``
    the grid pair where it does not) / ``flash_decode``, the optimizer
    update, the cache update and sampling their scopes."""
    import re

    import jax
    import jax.numpy as jnp

    from ddp_tpu.ops.flash import flash_attention

    eng = _tiny_engine(decode_attn="flash")
    state = (eng.params, eng._cache, eng._toks, eng._seeds,
             eng._sample_steps, eng._temps, eng._top_ps)
    decode = eng._decode.lower(*state).as_text(debug_info=True)
    assert "module @jit_serve_decode " in decode
    for scope in ("flash_decode", "cache_update", "sampling"):
        assert re.search(rf"\b{scope}\b", decode), scope
    chunk = (jnp.int32(0), jnp.zeros((8,), jnp.int32), jnp.int32(0),
             jnp.int32(3), jnp.asarray(True), jnp.int32(0),
             jnp.float32(0.0), jnp.float32(1.0))
    for fn, name in ((eng._chunk_first, "serve_prefill_first"),
                     (eng._chunk_cont, "serve_prefill_chunk")):
        assert fn.lower(*state, *chunk).as_text().startswith(
            f"module @jit_{name} ")

    q = jnp.zeros((1, 64, 2, 32), jnp.float32)
    grad = lambda: jax.jit(jax.grad(
        lambda q, k, v: flash_attention(q, k, v, True, 32, 32, True).sum(),
        argnums=(0, 1, 2),
    )).lower(q, q, q).as_text(debug_info=True)
    resident = grad()
    for kernel in ("flash_fwd", "flash_dkv"):
        assert re.search(rf"\b{kernel}\b", resident), kernel
    assert not re.search(r"\bflash_dq\b", resident)
    # a head over the VMEM budget: the grid pair, under both names
    request.getfixturevalue("backward_over_budget")
    over = grad()
    for kernel in ("flash_fwd", "flash_dq", "flash_dkv"):
        assert re.search(rf"\b{kernel}\b", over), kernel


def test_train_step_scopes_the_optimizer_update():
    import re

    import jax.numpy as jnp
    import optax

    import jax

    from ddp_tpu.models.lm import (
        LMSpec, create_lm_train_state, make_lm_train_step,
    )
    from ddp_tpu.runtime.mesh import MeshSpec, make_mesh

    spec = LMSpec(vocab_size=37, total_len=16, d_model=32, depth=1,
                  num_heads=4)
    mesh = make_mesh(MeshSpec(data=1, seq=1), devices=jax.devices()[:1])
    opt = optax.adam(1e-3)
    step = make_lm_train_step(spec, opt, mesh)
    state = create_lm_train_state(spec, opt, mesh, seed=0)
    text = step.lower(state, jnp.zeros((2, 16), jnp.int32)).as_text(
        debug_info=True)
    assert re.search(r"\boptimizer_update\b", text)


def test_serve_cli_session_emits_valid_trace(tmp_path):
    """Acceptance pin, end-to-end: a scripts/serve.py session (real
    process, real HTTP) answers /statusz and leaves a Perfetto-loadable
    trace + a flushed metrics tail on shutdown."""
    import signal
    import urllib.request

    proc = subprocess.Popen(
        [
            sys.executable, os.path.join(REPO, "scripts", "serve.py"),
            "--init_demo", "--vocab_size", "64", "--seq_len", "32",
            "--slots", "2", "--port", "0",
            "--trace_dir", str(tmp_path),
            "--metrics_file", str(tmp_path / "serve_metrics.jsonl"),
        ],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        cwd=REPO,
    )
    try:
        banner = json.loads(proc.stdout.readline())
        url = banner["serving"]
        body = json.dumps(
            {"prompt_tokens": [1, 2, 3], "max_new_tokens": 3}
        ).encode()
        req = urllib.request.Request(
            url + "/generate", data=body,
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req, timeout=120) as resp:
            out = json.loads(resp.read())
        assert out["status"] == "complete" and len(out["tokens"]) == 3
        with urllib.request.urlopen(url + "/statusz", timeout=30) as resp:
            statusz = json.loads(resp.read())
        assert statusz["ok"] is True
        assert statusz["stats"]["goodput"]["productive_s"] > 0
        assert any(
            e["name"] == "serve.decode"
            for e in statusz["trace"]["traceEvents"]
        )
        proc.send_signal(signal.SIGINT)
        proc.wait(timeout=60)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait(10)
    doc = validate_trace_file(str(tmp_path / "trace_rank0.trace.json"))
    names = {e["name"] for e in doc["traceEvents"]}
    assert {
        "serve.prefill_chunk", "serve.decode", "serve.sample",
    } <= names
    # the metrics tail survived shutdown (explicit close in the CLI)
    recs = [
        json.loads(l)
        for l in (tmp_path / "serve_metrics.jsonl").read_text().splitlines()
    ]
    assert any(r["kind"] == "serve_request" for r in recs)


# ---- trace_merge ----------------------------------------------------


def test_trace_merge_cli(tmp_path):
    ranks = []
    for rank in range(2):
        t = Tracer(enabled=True, ring_events=64, process_id=rank)
        for _ in range(3 + rank):
            with t.span("work"):
                pass
        ranks.append(t)
        t.export_to_dir(str(tmp_path))
    out = tmp_path / "merged.trace.json"
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "trace_merge.py"),
         str(tmp_path), "-o", str(out)],
        capture_output=True, text=True, cwd=REPO,
    )
    assert proc.returncode == 0, proc.stderr
    doc = validate_trace_file(str(out))
    xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert len(xs) == 7  # 3 + 4
    assert {e["pid"] for e in xs} == {0, 1}
    # Re-merging with the output inside the input dir (the documented
    # usage) must NOT ingest the previous merged file: counts stay
    # exact, events don't duplicate.
    proc_again = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "trace_merge.py"),
         str(tmp_path), "-o", str(out)],
        capture_output=True, text=True, cwd=REPO,
    )
    assert proc_again.returncode == 0, proc_again.stderr
    doc = validate_trace_file(str(out))
    assert len([e for e in doc["traceEvents"] if e["ph"] == "X"]) == 7
    merged = doc["ddp_tpu"]["span_summaries"]["work"]
    assert merged["count"] == 7
    pooled = [
        s for t in ranks for s in t.summary_states()["work"]["samples"]
    ]
    assert merged["min"] == min(pooled)
    assert merged["max"] == max(pooled)
    assert math.isclose(
        merged["sum"], math.fsum(pooled), rel_tol=1e-12
    )
    # a corrupt input fails loudly, naming the file
    bad = tmp_path / "bad.trace.json"
    bad.write_text("{]")
    proc2 = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "trace_merge.py"),
         str(bad), "-o", str(tmp_path / "m2.json")],
        capture_output=True, text=True, cwd=REPO,
    )
    assert proc2.returncode != 0
    assert "bad.trace.json" in proc2.stderr


# ---- CI/tooling -----------------------------------------------------


def test_compileall_package_and_scripts():
    """Smoke-tier syntax gate over the package and scripts/ (files the
    test suite doesn't import still have to parse)."""
    proc = subprocess.run(
        [sys.executable, "-m", "compileall", "-q", "ddp_tpu", "scripts"],
        capture_output=True, text=True, cwd=REPO,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_launch_env_installs_rank_tracer(tmp_path, monkeypatch):
    """The launcher wiring: DDP_TPU_TRACE_DIR flips the global tracer
    on with pid=rank (no worker-signature changes needed)."""
    from ddp_tpu.obs import tracer as tr

    monkeypatch.delenv(tr.TRACE_DIR_ENV, raising=False)
    before = tr.get_tracer()
    assert tr.install_from_env(5) is before  # env unset → untouched
    monkeypatch.setenv(tr.TRACE_DIR_ENV, str(tmp_path))
    monkeypatch.setenv(tr.RING_EVENTS_ENV, "128")
    installed = tr.install_from_env(5, register_atexit=False)
    try:
        assert installed.enabled and installed.process_id == 5
        assert installed.ring_events == 128
        assert tr.get_tracer() is installed
        with installed.span("w"):
            pass
        path = installed.export_to_dir(str(tmp_path))
        assert path.endswith("trace_rank5.trace.json")
        validate_trace_file(path)
    finally:
        tr._GLOBAL = before
