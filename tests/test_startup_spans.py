"""The program's account of its own start (obs/tracer.py ``phase`` /
``phase_complete`` / ``startup()``, the one compile listener, the
phases in ``Trainer`` and ``ServeEngine``, obs/startup.py's line):
what is kept, by what name, and that the hot path's records are the
ones they were."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from ddp_tpu.obs import tracer as tr
from ddp_tpu.obs.startup import programs, startup_line, union_s
from ddp_tpu.obs.tracer import KEPT_RECORDS, SPAN_NUMS, Tracer

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
COMPILE = ("compile.trace", "compile.lower", "compile.backend")


@pytest.fixture
def tracer(monkeypatch):
    """A fresh process-global tracer: where the listener's records and
    the phases of a ``Trainer`` or ``ServeEngine`` made here go."""
    fresh = Tracer()
    monkeypatch.setattr(tr, "_GLOBAL", fresh)
    return fresh


def _of(tracer, fun):
    return [e for e in tracer.startup() if e[0] in COMPILE and e[4][0] == fun]


# ---- the kept store --------------------------------------------------------


def test_a_phase_is_a_span_that_is_also_kept():
    t = Tracer(ring_events=64)
    with t.phase("startup.state", nums=("engine",)) as state:
        with t.phase("startup.lane_cache", parent=state.t0):
            pass
        t.phase_complete("startup.model_init", state.t0, 0.0,
                         parent=state.t0)
    with t.span("serve.step"):
        pass
    assert [e[0] for e in t.ring()] == [
        "startup.lane_cache", "startup.model_init", "startup.state",
        "serve.step"]
    kept = t.startup()  # oldest first, a phase before what it contains
    assert [e[0] for e in kept] == [
        "startup.state", "startup.model_init", "startup.lane_cache"]
    assert kept[0] == t.ring()[2] and kept[0][4] == ("engine",)
    assert all(e[3] == kept[0][1] for e in kept[1:])


def test_a_full_store_keeps_the_start_and_counts_what_it_refuses():
    t = Tracer()
    for i in range(KEPT_RECORDS + 10):
        t.phase_complete("compile.trace", float(i), 0.5, nums=(f"f{i}",))
    kept = t.startup()
    assert len(kept) == KEPT_RECORDS
    assert kept[0][4] == ("f0",) and kept[-1][4] == (f"f{KEPT_RECORDS - 1}",)
    assert t.startup_refused == 10 == t.startup_snapshot()["refused"]


def test_a_tracer_beside_the_global_one_keeps_with_it(tracer, monkeypatch):
    tracer.phase_complete("startup.import", 1.0, 2.0, nums=("m",))
    own = Tracer(enabled=True, kept_with=tracer)
    own.phase_complete("startup.state", 3.0, 1.0, nums=("trainer",))
    assert [e[0] for e in tracer.startup()] == [
        "startup.import", "startup.state"] == [e[0] for e in own.startup()]
    # ... and so does the one ``install_from_env`` puts in its place
    monkeypatch.setenv(tr.TRACE_DIR_ENV, "/nowhere")
    installed = tr.install_from_env(register_atexit=False)
    assert installed is tr.get_tracer() is not tracer
    assert installed.startup() == tracer.startup()


def test_statusz_view_names_the_nums():
    t = Tracer()
    t.phase_complete("startup.import", 10.0, 2.0, nums=("orbax.checkpoint",))
    t.phase_complete("compile.backend", 13.0, 0.5, nums=("train_step", 1))
    t.phase_complete("compile.trace", 13.1, 0.0002, nums=("add",))
    t.phase_complete("compile.trace", 13.2, 0.0003, nums=("_where",))
    t.phase_complete("startup.warmup_wait", 13.6, 0.0001)  # a phase: listed
    assert t.startup_snapshot() == {
        "refused": 0, "brief_compile_records": 2,
        "brief_compile_seconds": 0.0005, "records": [
            {"name": "startup.import", "at_s": 0.0, "seconds": 2.0,
             "module": "orbax.checkpoint"},
            {"name": "compile.backend", "at_s": 3.0, "seconds": 0.5,
             "fun_name": "train_step", "cache_hit": 1},
            {"name": "startup.warmup_wait", "at_s": 3.6, "seconds": 0.0001},
        ]}
    assert len(t.startup()) == 5  # the store has them all


def test_every_kept_name_is_in_the_table():
    for name in ("startup.import", "startup.state", "startup.warmup",
                 "startup.warmup_program", "startup.warmup_wait", *COMPILE):
        assert name in SPAN_NUMS


# ---- the one compile listener ----------------------------------------------


def test_a_first_call_leaves_three_records_by_name_and_a_second_none(tracer):
    def kept_by_name(x):
        return x * 2 + 1

    f = jax.jit(kept_by_name)
    f(jnp.ones((3,)))
    first = _of(tracer, "kept_by_name")
    assert [e[0] for e in first] == list(COMPILE)  # in that order in time
    assert first[2][4] == ("kept_by_name", 0)  # no persistent cache here
    assert all(e[3] is None for e in first)  # no parent: by containment
    f(jnp.ones((3,)))
    assert _of(tracer, "kept_by_name") == first
    f(jnp.ones((4, 4)))  # another shape: another compile
    assert len(_of(tracer, "kept_by_name")) == 6


def test_a_nested_jit_leaves_nested_records_whose_union_is_the_outers(
        tracer):
    @jax.jit
    def kept_inner(x):
        return jnp.tanh(x) * 2

    @jax.jit
    def kept_outer(x):
        return kept_inner(x) + kept_inner(x * 3)

    kept_outer(jnp.ones((7,)))
    outer = [e for e in _of(tracer, "kept_outer") if e[0] == "compile.trace"]
    inner = [e for e in _of(tracer, "kept_inner") if e[0] == "compile.trace"]
    assert len(outer) == 1 and len(inner) >= 1
    (_, t0, dur, _, _), = outer
    slack = 2e-3  # the event's clock is time.time, the record's perf_counter
    for e in inner:
        assert t0 - slack <= e[1] and e[1] + e[2] <= t0 + dur + slack
    assert union_s(outer + inner) == pytest.approx(dur, abs=2 * slack)
    assert sum(e[2] for e in outer + inner) > dur
    # only the outer program reached lowering and the backend
    assert [e[0] for e in _of(tracer, "kept_inner")] == ["compile.trace"] * len(
        inner)


CACHE_HIT = """
import sys, tempfile
import jax, jax.numpy as jnp
jax.config.update("jax_enable_compilation_cache", True)
jax.config.update("jax_compilation_cache_dir", tempfile.mkdtemp())
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
from ddp_tpu.obs.tracer import get_tracer
def kept_cached(x):
    return jnp.tanh(x) * 3 + 1
f = jax.jit(kept_cached)
f(jnp.ones((5,)))
jax.clear_caches()
f(jnp.ones((5,)))
print("HITS", [e[4][1] for e in get_tracer().startup()
               if e[0] == "compile.backend" and e[4][0] == "kept_cached"])
"""


def test_a_program_the_persistent_cache_supplies_reads_cache_hit_1():
    """In a process of its own: the suite keeps the persistent cache
    off on this host (tests/conftest.py says why)."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": REPO,
           "JAX_ENABLE_COMPILATION_CACHE": "true"}
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    p = subprocess.run([sys.executable, "-c", CACHE_HIT], env=env, cwd=REPO,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-2000:]
    assert "HITS [0, 1]" in p.stdout, p.stdout


def test_compile_counter_is_a_view_of_the_listener(tracer):
    from ddp_tpu.obs.steptime import CompileCounter

    x = jnp.ones((2, 3))
    before = CompileCounter.count()
    jax.jit(lambda x: x - 5)(x)
    assert CompileCounter.count() == before + 1 == tr.compile_count()
    assert [e[4][0] for e in tracer.startup()
            if e[0] == "compile.backend"][-1] == "<lambda>"


def test_the_program_has_one_monitoring_listener():
    hits = subprocess.run(
        ["grep", "-rln", "register_event", os.path.join(REPO, "ddp_tpu")],
        capture_output=True, text=True).stdout.split()
    assert [os.path.relpath(h, REPO) for h in hits
            if h.endswith(".py")] == ["ddp_tpu/obs/tracer.py"]


# ---- the phases, where the work happens -------------------------------------


def _engine(**kw):
    from ddp_tpu.models.lm import LMSpec, init_lm
    from ddp_tpu.serve.engine import ServeEngine

    spec = LMSpec(vocab_size=37, total_len=32, d_model=32, depth=1,
                  num_heads=4)
    return ServeEngine(spec, init_lm(spec, seed=0), slots=2, prefill_len=8,
                       **kw)


def _inside(child, parent, slack=0.0):
    return (parent[1] - slack <= child[1]
            and child[1] + child[2] <= parent[1] + parent[2] + slack)


def test_an_engine_leaves_one_state_and_its_warmup_one_program_a_call(
        tracer):
    engine = _engine()
    counts = engine.warmup()
    kept = tracer.startup()
    (state,) = [e for e in kept if e[0] == "startup.state"]
    assert state[4] == ("engine",)
    children = [e for e in kept if e[3] == state[1]]
    assert [e[0] for e in children] == ["startup.lane_cache"]
    assert _inside(children[0], state)
    (warm,) = [e for e in kept if e[0] == "startup.warmup"]
    calls = [e for e in kept if e[0] == "startup.warmup_program"]
    assert len(calls) == sum(counts.values()) == warm[4][0]
    assert sorted({e[4][0] for e in calls}) == sorted(counts)
    assert {e[4] for e in calls} >= {
        ("prefill_first", w) for w in engine.buckets} | {("decode", 0)}
    (wait,) = [e for e in kept if e[0] == "startup.warmup_wait"]
    assert all(e[3] == warm[1] and _inside(e, warm) for e in calls + [wait])
    # each call's compile is inside its interval: the reader's rule
    decode = next(e for e in calls if e[4][0] == "decode")
    backends = [e for e in _of(tracer, "serve_decode")
                if e[0] == "compile.backend"]
    assert len(backends) == 1 and _inside(backends[0], decode, slack=2e-3)
    assert "serve_decode" in programs(kept)


def test_a_trainer_leaves_one_state_with_its_children_inside(tracer,
                                                             tmp_path):
    from ddp_tpu.train.config import TrainConfig
    from ddp_tpu.train.trainer import Trainer

    t = Trainer(TrainConfig(
        epochs=1, batch_size=4, synthetic_data=True, synthetic_size=64,
        eval_every=0, log_interval=1,
        checkpoint_dir=str(tmp_path / "ck"), data_root=str(tmp_path / "d"),
    ))
    try:
        kept = tracer.startup()
        (state,) = [e for e in kept if e[0] == "startup.state"]
        assert state[4] == ("trainer",)
        children = [e for e in kept if e[3] == state[1]]
        assert [e[0] for e in children] == ["startup.model_init"]
        assert all(e[0] in SPAN_NUMS and _inside(e, state) for e in children)
        assert not [e for e in kept if e[0] == "train.dispatch"]
        batch = next(iter(t.loader.epoch(0)))
        for _ in range(3):
            t.state, m = t.train_step(t.state, batch.images, batch.labels)
        jax.block_until_ready(m.loss)
        # the first call, which compiled the step, is kept; the ring has
        # all three, as it had
        assert len([e for e in tracer.startup()
                    if e[0] == "train.dispatch"]) == 1
        assert len([e for e in tracer.ring()
                    if e[0] == "train.dispatch"]) == 3
    finally:
        t.close()


def test_the_hot_path_evicts_the_start_from_the_ring_and_not_from_the_store(
        tracer):
    engine = _engine()
    engine.warmup()
    start = [e for e in tracer.startup() if e[0].startswith("startup.")]
    assert start and set(start) <= set(tracer.ring())
    compiles = [e for e in tracer.startup() if e[0] in COMPILE]
    for _ in range(70_000):
        with tracer.span("serve.step"):
            pass
    assert {e[0] for e in tracer.ring()} == {"serve.step"}
    kept = tracer.startup()
    assert [e for e in kept if e[0].startswith("startup.")] == start
    assert [e for e in kept if e[0] in COMPILE] == compiles


def test_a_warm_engine_step_adds_the_records_it_always_did(tracer):
    """Name for name the parent's: a step after warm-up leaves serve.*
    records in the ring and not one record in the store."""
    engine = _engine()
    engine.warmup()
    kept = len(tracer.startup())
    mark = len(tracer.ring())
    engine.submit([1, 2, 3], 4)
    engine.run()
    added = [e[0] for e in tracer.ring()[mark:]]
    assert added and all(n.startswith("serve.") for n in added)
    assert {"serve.step", "serve.admit", "serve.prefill_chunk",
            "serve.decode", "serve.sample", "serve.retire"} >= set(added)
    assert len(tracer.startup()) == kept


# ---- what an operator gets ---------------------------------------------------


def test_statusz_carries_startup(tracer):
    from ddp_tpu.serve.server import LMServer

    engine = _engine()
    engine.warmup()
    server = LMServer(engine)
    try:
        startup = server.snapshot("/statusz")["startup"]
    finally:
        server._httpd.server_close()
    json.dumps(startup)  # what the handler will do with it
    names = [r["name"] for r in startup["records"]]
    assert startup["refused"] == 0 and "startup.warmup" in names
    decode = next(r for r in startup["records"]
                  if r["name"] == "startup.warmup_program"
                  and r["program"] == "decode")
    assert decode["width"] == 0 and decode["seconds"] > 0
    assert startup["records"][0]["at_s"] == 0.0


def test_the_one_line(tracer):
    t = Tracer()
    assert startup_line(t) == "start-up: nothing recorded"
    engine = _engine()
    engine.warmup()
    line = startup_line(tracer)
    assert "\n" not in line and line.startswith("start-up ")
    for part in ("imports ", "state ", "warm-up ", "cache hits",
                 "serve_prefill", "trace/lower/backend"):
        assert part in line, line


def test_the_docs_table_and_span_nums_agree_name_for_name():
    import re

    with open(os.path.join(REPO, "docs", "OBSERVABILITY.md")) as f:
        section = f.read().split("### Start-up", 1)[1].split("\n### ", 1)[0]
    first_cells = [ln.split("|")[1] for ln in section.splitlines()
                   if ln.startswith("| `")]
    documented = set(re.findall(r"`((?:startup|compile)\.[a-z_]+)`",
                                " ".join(first_cells)))
    kept_names = {n for n in SPAN_NUMS
                  if n.startswith(("startup.", "compile."))}
    assert documented == kept_names
