"""The per-layer metrics that read the program's own spans and names
(``benchmarks/harness/program_spans.py`` and its ten readers), on
synthetic runs: blocks, a filled global ring, the trace recorded on the
chip with its kernels renamed in a copy. Each number is worked out by
hand; a reader whose span or name is absent (the parent of the PR that
brought them) reports nothing and does not raise. That the manifest
still lists them, with their cells, is the contract's
``check_the_benchmark_lost_nothing_it_had`` (``bench_contract.py``,
run by ``test_bench_manifest.py`` and ``test_bench_extend.py``)."""

import copy
import json
import os
import sys
from types import SimpleNamespace

import pytest

from bench_contract import SERVE_NEW, TRAIN_NEW
from benchmarks.harness import manifest
from benchmarks.harness import program_spans as ps
from benchmarks.harness import trace as bt
from benchmarks.harness.window import Block

HERE = os.path.dirname(os.path.abspath(__file__))
DEV = "/device:TPU:0"


@pytest.fixture
def tracer(monkeypatch):
    """A fresh process-global tracer: the ring the readers reach."""
    from ddp_tpu.obs import tracer as tr

    fresh = tr.Tracer()
    monkeypatch.setattr(tr, "_GLOBAL", fresh)
    return fresh


@pytest.fixture(scope="module")
def readers():
    serve = manifest.load_cell("cgpt1.3b-serve-chat-sat").layer_readers()
    train = manifest.load_cell("cgpt1.3b-train-1chip").layer_readers()
    return {**serve, **train}


def _run(blocks, *, counters=None, trace=None, cell="some-cell"):
    return SimpleNamespace(
        blocks=blocks, counters=counters or {}, trace=trace, spans=None,
        window={}, device={"kind": "TPU v5 lite"},
        cell=SimpleNamespace(name=cell, root="/checkout"),
    )


# A traced block [100, 102] and two untraced ones [102, 104], [104, 106].
BLOCKS = [
    Block(100.0, 102.0, 10, steps=2, traced=True),
    Block(102.0, 104.0, 10, steps=2),
    Block(104.0, 106.0, 10, steps=2),
]


def test_manifest_lost_none_of_the_ten_entries_and_may_gain():
    """Each cell still finds a reader for every entry PR 24 brought, in
    their order; what stands after or beside them is a gain."""
    for cell, brought in (("cgpt1.3b-serve-chat-sat", SERVE_NEW),
                          ("cgpt1.3b-train-1chip", TRAIN_NEW),
                          ("cgpt1.3b-train-ddp4", TRAIN_NEW)):
        have = list(manifest.load_cell(cell).layer_readers())
        assert [n for n in have if n in brought] == brought, cell


def test_serve_host_time_is_the_step_less_its_waits(tracer, readers,
                                                    capsys):
    c = tracer.complete
    # traced block: one step of 120 ms with a 100 ms wait inside
    c("serve.step", 100.5, 0.120, nums=(8, 8, 3))
    c("serve.sample", 100.51, 0.100, parent=100.5, nums=(8,))
    # untraced: 110 ms with 100 + 4 ms of waits; 108 ms with 105 ms
    c("serve.step", 102.5, 0.110, nums=(8, 8, 3))
    c("serve.sample", 102.50, 0.004, parent=102.5, nums=(1,))
    c("serve.sample", 102.51, 0.100, parent=102.5, nums=(8,))
    c("serve.step", 104.5, 0.108, nums=(8, 8, 3))
    c("serve.sample", 104.501, 0.105, parent=104.5, nums=(8,))
    # a step that straddles the window's end belongs to no block
    c("serve.step", 105.95, 0.110, nums=(8, 8, 3))
    # a wait outside any step is nobody's
    c("serve.sample", 103.5, 0.050, nums=(8,))
    run = _run(BLOCKS, counters={"slots": 8})
    got = readers["serve_host_ms_per_step"].read(run)
    assert got == pytest.approx((6.0 + 3.0) / 2)
    line = [l for l in capsys.readouterr().out.splitlines()
            if l.startswith("# program_span ")]
    said = json.loads(line[0][len("# program_span "):])
    assert said["metric"] == "serve_host_ms_per_step"
    assert said["untraced_blocks"] == pytest.approx(4.5)
    assert said["traced_blocks"] == pytest.approx(20.0)


def test_request_waits_are_medians_over_requests_handed_back(tracer,
                                                             readers):
    c = tracer.complete
    # (rid, lock_wait_s, pickup_s, poll_wait_s); a request counts where
    # it ENDS: the first began long before the window
    c("server.request", 80.0, 23.0, nums=(1, 0.200, 2.0, 0.1))   # ends 103
    c("server.request", 102.5, 1.0, nums=(2, 0.400, 4.0, 0.1))   # 103.5
    c("server.request", 103.0, 2.5, nums=(3, 0.900, 9.0, 0.1))   # 105.5
    c("server.request", 101.0, 0.5, nums=(4, 7.000, 70.0, 0.1))  # traced
    c("server.request", 104.0, 9.0, nums=(5, 8.000, 80.0, 0.1))  # after
    run = _run(BLOCKS, counters={"slots": 8})
    assert readers["serve_submit_lock_wait_ms_p50"].read(
        run) == pytest.approx(400.0)
    assert readers["serve_result_pickup_ms_p50"].read(
        run) == pytest.approx(4000.0)


def test_train_host_spans_per_step_and_the_longest(tracer, readers,
                                                   capsys):
    c = tracer.complete
    c("data.next_batch", 100.1, 0.004, nums=(4,))   # traced block
    c("train.dispatch", 100.2, 0.006)
    for t in (102.1, 103.1, 104.1, 105.1):          # 4 untraced steps
        c("data.next_batch", t, 0.001, nums=(4,))
        c("train.dispatch", t + 0.2, 0.002)
    c("data.next_batch", 105.5, 0.0, nums=(0,))     # epoch exhausted
    c("train.dispatch", 104.5, 0.050)               # a stalled dispatch
    run = _run(BLOCKS, counters={"timed_steps": 4})
    assert readers["train_loader_ms_per_step"].read(
        run) == pytest.approx(4 * 1.0 / 4)
    assert readers["train_dispatch_ms_per_step"].read(
        run) == pytest.approx((4 * 2.0 + 50.0) / 4)
    capsys.readouterr()
    assert readers["train_host_max_span_ms"].read(
        run) == pytest.approx(50.0)
    said = json.loads(capsys.readouterr().out.splitlines()[0][
        len("# program_span "):])
    assert said["span"] == "train.dispatch"
    assert said["at_s"] == pytest.approx(4.5)  # from the first block on
    assert said["traced_blocks"] == pytest.approx(6.0)
    assert said["by_name"]["train.dispatch"] == {
        "count": 5, "total_ms": pytest.approx(58.0),
        "max_ms": pytest.approx(50.0)}
    assert said["by_name"]["data.next_batch"]["count"] == 5


def test_no_span_no_number(tracer, readers):
    """The parent's side: an empty ring, programs and kernels under
    their old names, no program annotation in the profiler's file."""
    with open(os.path.join(HERE, "data", "train_step_trace.json")) as f:
        recorded = bt.Trace.from_json(json.load(f))
    run = _run(BLOCKS, trace=recorded,
               counters={"slots": 8, "timed_steps": 4, "traced_steps": 1})
    for name in SERVE_NEW + TRAIN_NEW:
        assert readers[name].read(run) is None, name
    run.trace = None
    for name in SERVE_NEW + TRAIN_NEW:
        assert readers[name].read(run) is None, name


def test_no_ring_at_all_is_nothing_too(monkeypatch, readers):
    from ddp_tpu.obs import tracer as tr

    monkeypatch.setattr(tr, "get_tracer", lambda: object())
    assert ps.ring() == []
    assert readers["train_loader_ms_per_step"].read(_run(BLOCKS)) is None


def test_flash_kernels_by_name_add_up_to_all_pallas_time(readers):
    """The recorded step (8 layers: 24 Pallas kernels, all
    ``attn`` then) with the names the program gives them now."""
    with open(os.path.join(HERE, "data", "train_step_trace.json")) as f:
        recorded = bt.Trace.from_json(json.load(f))
    ops = list(recorded.device_ops)
    kernels = sorted((i for i, e in enumerate(ops) if bt.is_kernel(e)),
                     key=lambda i: ops[i][5])
    assert len(kernels) == 24
    want = {"flash_fwd": 0, "flash_dq": 0, "flash_dkv": 0}
    for k, i in enumerate(kernels):
        # forward pass first; then dq and dkv layer by layer
        name = "flash_fwd" if k < 8 else ("flash_dq", "flash_dkv")[k % 2]
        e = ops[i]
        ops[i] = (e[0], e[1], f"{name}.{k}", *e[3:])
        want[name] += e[6]
    renamed = copy.copy(recorded)
    renamed.device_ops = ops
    assert {"custom-call:flash_fwd", "custom-call:flash_dq",
            "custom-call:flash_dkv"} <= set(bt.seconds_by_class(renamed))
    run = _run([], trace=renamed, counters={"traced_steps": 1})
    fwd = readers["train_attn_fwd_ms_per_step"].read(run)
    bwd = readers["train_attn_bwd_ms_per_step"].read(run)
    assert fwd == pytest.approx(want["flash_fwd"] / 1e6)
    assert bwd == pytest.approx(
        (want["flash_dq"] + want["flash_dkv"]) / 1e6)
    assert fwd + bwd == pytest.approx(34.798108)  # all Pallas time
    run.counters["traced_steps"] = 2
    assert readers["train_attn_fwd_ms_per_step"].read(
        run) == pytest.approx(fwd / 2)


def ev(line, inst, opcode, start, dur, detail=""):
    return (DEV, line, inst, opcode, detail, start, dur)


def test_prefill_programs_by_name(readers):
    tr = bt.Trace(window_ns=(0, 1_000_000_000), device_ops=[
        ev("module", "jit_serve_decode", "", 0, 100_000_000),
        ev("module", "jit_serve_prefill_first", "", 100_000_000, 4_000_000),
        ev("module", "jit_serve_decode", "", 104_000_000, 100_000_000),
        ev("module", "jit_serve_prefill_chunk", "", 204_000_000, 8_000_000),
        # a draft model's prefill is another program
        ev("module", "jit_serve_draft_prefill_chunk", "", 300_000_000,
           1_000_000),
        ev("ops", "fusion.1", "fusion", 0, 1, "kLoop"),
    ])
    run = _run([], trace=tr, counters={"slots": 8})
    assert readers["serve_prefill_dev_ms_per_chunk"].read(
        run) == pytest.approx((4.0 + 8.0) / 2)


def test_idle_share_a_program_span_covers(monkeypatch, readers, capsys):
    # busy 0-50, 100-140, 150-170, 400-500 us;
    # idle 50-100, 140-150, 170-400, 500-600 us
    tr = bt.Trace(window_ns=(0, 600_000), device_ops=[
        ev("ops", "f.1", "fusion", 0, 50_000, "kLoop"),
        ev("ops", "f.2", "fusion", 100_000, 40_000, "kLoop"),
        ev("ops", "f.3", "fusion", 150_000, 20_000, "kLoop"),
        ev("ops", "f.4", "fusion", 400_000, 100_000, "kLoop"),
    ], host_spans=[("bench.other", 0, 600_000)])
    # the first step covers the first gap wholly and so does its child,
    # the token fetch: the inner span names it. Of the gap 170-400 us
    # the steps cover 30 + 50 us: the larger share names it. The step
    # in flight when the session closed (from 500 us on) is not in the
    # file: the window ends where the annotations do, at 500 us.
    spans = [("serve.sample", 40_000, 70_000),
             ("serve.step", 0, 200_000),
             ("serve.step", 350_000, 150_000)]
    monkeypatch.setattr(ps, "annotated", lambda run: spans)
    run = _run([], trace=tr, counters={"slots": 8})
    gaps = ps.idle_gaps(run)
    assert gaps == {
        "serve.step": pytest.approx(230_000 / 1e9),
        "serve.sample": pytest.approx(50_000 / 1e9),
        "under_20us": pytest.approx(10_000 / 1e9),
    }
    assert tr.host_spans == [("bench.other", 0, 600_000)]  # a copy
    assert tr.window_ns == (0, 600_000)
    assert readers["serve_idle_attributed_pct"].read(
        run) == pytest.approx(100.0)
    assert "# idle_by_program_span " in capsys.readouterr().out
    # with no step over it, the long gap is unspanned
    spans[1:] = [("serve.step", 0, 170_000),
                 ("serve.step", 400_000, 100_000)]
    assert readers["serve_idle_attributed_pct"].read(
        run) == pytest.approx(50_000 / 280_000 * 100)


def test_run_directory_as_run_py_resolves_it(monkeypatch):
    run = _run([], cell="cgpt1.3b-serve-chat-sat")
    monkeypatch.setattr(sys, "argv", ["benchmarks/run.py", "--workload",
                                      "cgpt1.3b-serve-chat-sat"])
    assert ps.out_dir(run) == os.path.join(
        "/checkout", "chiprun_out", "bench", "cgpt1.3b-serve-chat-sat")
    monkeypatch.setattr(sys, "argv", ["benchmarks/run.py", "--out",
                                      "/elsewhere", "--trace", "1"])
    assert ps.out_dir(run) == "/elsewhere"
    monkeypatch.setattr(sys, "argv", ["benchmarks/run.py",
                                      "--out=/elsewhere"])
    assert ps.out_dir(run) == "/elsewhere"
    # the same rule as run.py itself
    from benchmarks import run as bench_run

    args = bench_run.parse(["--workload", "w", "--out", "/elsewhere"])
    assert args.out == "/elsewhere"
    assert bench_run.parse(["--workload", "w"]).out is None


def test_annotations_are_read_from_the_profilers_own_file(
    tmp_path, monkeypatch, tracer
):
    """A profile recorded the way ``trace.record`` records it, with the
    program's spans open: ``annotated`` finds them in the run's
    ``trace`` directory, shortest first, on the profiler's clock."""
    import time

    monkeypatch.setattr(sys, "argv", ["run.py", "--out", str(tmp_path)])
    run = _run([])
    assert ps.annotated(run) == [] and ps.idle_gaps(run) is None
    with bt.record(str(tmp_path / "trace")):
        with tracer.span("serve.step") as step:
            with tracer.span("serve.admit", parent=step.t0):
                time.sleep(0.002)
        with tracer.span("unrelated.span"):
            pass
    got = ps.annotated(run)
    assert [s[0] for s in got] == ["serve.admit", "serve.step"]
    (_, a0, ad), (_, s0, sd) = got
    assert s0 <= a0 and a0 + ad <= s0 + sd and ad >= 2_000_000
