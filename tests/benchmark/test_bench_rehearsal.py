"""A tiny CPU rehearsal of each driver under its own name — the whole
control flow of a run, no device metric written — and the measuring
path's refusal to run without a TPU."""

import json
import os
import subprocess
import sys

import pytest

from bench_helpers import ROOT, run_cell


def _result(lines):
    assert all(ln.startswith("# ") for ln in lines[:-1]), lines
    return json.loads(lines[-1])


@pytest.fixture(scope="module")
def train_rehearsal(bench_copy):
    return run_cell(bench_copy, "tiny-train-1", seed=3_000_000_011)


def test_train_rehearsal_runs_and_is_correct(train_rehearsal):
    rc, run, lines = train_rehearsal
    res = _result(lines)
    assert rc == 0 and res["correct"] is True
    assert res["attempted"] > 0 and res["failed"] == 0
    assert {c.name for c in run.checks} == {
        "loss_rel", "grad_leaf_rel", "delta_leaf_rel", "compiles_in_window"}
    assert all(c.ok for c in run.checks)


def test_train_rehearsal_writes_no_device_metric(train_rehearsal):
    _, _, lines = train_rehearsal
    res = _result(lines)
    assert res["metrics"] == {} and res["device"]["platform"] == "cpu"
    assert "busy_s" not in res["device"]
    assert any(ln.startswith("# rehearsal ") for ln in lines)
    for ln in lines:
        assert "tokens/s" not in ln or ln.startswith("# window ")


def test_train_rehearsal_prints_both_readings(train_rehearsal):
    _, run, lines = train_rehearsal
    (w,) = [json.loads(ln[len("# window "):]) for ln in lines
            if ln.startswith("# window ")]
    assert w["blocks"] == len(w["block_rates"]) == len(w["block_seconds"])
    assert w["median_block_rate"] > 0 and w["window_quotient"] > 0
    assert run.counters["compiles_in_window"] == 0
    assert run.counters["timed_steps"] == 4 * w["blocks"]


def test_train_rehearsal_setup_split(train_rehearsal):
    _, run, _ = train_rehearsal
    for k in ("backend_up_s", "state_s", "first_steps_s", "warm_steps_s",
              "trace_s", "lower_s", "backend_compile_or_cache_load_s"):
        assert run.setup_split[k] >= 0
    assert run.end_to_end["setup_s"] >= run.setup_split["state_s"]


def test_train_rehearsal_ddp4_traced(bench_copy):
    rc, run, lines = run_cell(bench_copy, "tiny-train-4", seed=11, trace=1)
    res = _result(lines)
    assert rc == 0 and res["correct"] is True and res["metrics"] == {}
    assert run.spans.count["bench.dispatch"] == run.attempted
    assert run.spans.count["bench.loader_fetch"] == run.attempted
    assert run.counters["traced_steps"] == 4
    assert run.blocks[0].traced and not run.blocks[-1].traced


@pytest.fixture(scope="module")
def serve_rehearsal(bench_copy):
    return run_cell(bench_copy, "tiny-serve-1", seed=2_147_483_777,
                    seconds=2, trace=1)


def test_serve_rehearsal_runs_and_is_correct(serve_rehearsal):
    rc, run, lines = serve_rehearsal
    res = _result(lines)
    assert rc == 0 and res["correct"] is True, [c.to_json()
                                                for c in run.checks]
    assert res["attempted"] > 10 and res["failed"] == 0
    assert run.notes["checked_tokens"] > 0


def test_serve_rehearsal_writes_no_device_metric(serve_rehearsal):
    _, _, lines = serve_rehearsal
    res = _result(lines)
    assert res["metrics"] == {} and "breakdown" not in res


def test_serve_rehearsal_counts(serve_rehearsal):
    _, run, _ = serve_rehearsal
    assert run.counters["compiles_in_window"] == 0
    assert sum(b.work for b in run.blocks) > 0
    assert run.window["finished_requests"] >= run.window["tpot_samples"] > 0
    assert run.window["generator_max_late_s"] < 5.0  # a loaded CPU box
    assert all("active" in b.extra for b in run.blocks)


def test_serve_traffic_same_schedule_for_every_seed(bench_copy):
    from benchmarks.harness import manifest

    cell = manifest.load_cell("cgpt1.3b-serve-chat-sat")
    gen = cell.generator()
    a = gen.generate(cell.traffic, seed=1, vocab_size=50257, seconds=35)
    b = gen.generate(cell.traffic, seed=2**31 + 9, vocab_size=50257,
                     seconds=35)
    # one schedule: the same lengths and arrivals in the same order
    assert [len(r.prompt) for r in a] == [len(r.prompt) for r in b]
    assert [r.max_new_tokens for r in a] == [r.max_new_tokens for r in b]
    assert [r.due_s for r in a] == [r.due_s for r in b]
    # the seed draws the tokens
    assert [r.prompt for r in a] != [r.prompt for r in b]
    again = gen.generate(cell.traffic, seed=1, vocab_size=50257, seconds=35)
    assert [r.prompt for r in again] == [r.prompt for r in a]
    # the mix itself
    assert sum(r.due_s == 0 for r in a) == cell.traffic["burst"] == 16
    assert min(len(r.prompt) for r in a) >= 16
    assert max(len(r.prompt) for r in a) <= 1024
    assert max(len(r.prompt) + r.max_new_tokens for r in a) <= 2048
    assert len(set(len(r.prompt) for r in a[:8])) > 4  # shuffled
    rate = (len(a) - 16) / a[-1].due_s
    assert rate == pytest.approx(cell.traffic["rate_rps"], rel=0.1)


def test_serve_traffic_another_order_seed_is_another_realisation(bench_copy):
    from benchmarks.harness import manifest

    cell = manifest.load_cell("cgpt1.3b-serve-chat-sat")
    gen = cell.generator()
    a = gen.generate(cell.traffic, seed=1, vocab_size=50257, seconds=35)
    b = gen.generate(dict(cell.traffic, order_seed=1), seed=1,
                     vocab_size=50257, seconds=35)
    # the same mix of lengths and gaps, in another order
    assert sorted(len(r.prompt) for r in a) == sorted(
        len(r.prompt) for r in b)
    assert sorted(r.max_new_tokens for r in a) == sorted(
        r.max_new_tokens for r in b)
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in b]
    assert a[-1].due_s == pytest.approx(b[-1].due_s)
    # the order belongs to the mix: a traffic file has to state it
    with pytest.raises(KeyError):
        gen.generate({k: v for k, v in cell.traffic.items()
                      if k != "order_seed"}, seed=1, vocab_size=50257,
                     seconds=35)


@pytest.mark.parametrize("kind,metric", [
    ("train", "train_tokens_per_s_per_chip"),
    ("serve", "serve_tokens_per_s"),
])
def test_end_to_end_rate_is_all_work_over_all_time(
        kind, metric, train_rehearsal, serve_rehearsal):
    _, run, _ = train_rehearsal if kind == "train" else serve_rehearsal
    timed = [b for b in run.blocks if not b.traced]
    wall = timed[-1].end - timed[0].start
    assert run.end_to_end[metric] == pytest.approx(
        sum(b.work for b in timed) / wall)
    assert run.end_to_end[metric] == run.window["window_quotient"]


def test_serve_load_is_open_loop(bench_copy):
    """A request is sent when it is due, whatever the earlier ones are
    doing: one slow answer holds no later request back."""
    import threading
    import time

    from benchmarks.harness import manifest

    cell = manifest.load_cell("tiny-serve-1", bench_copy)
    drv, gen = cell.driver(), cell.generator()
    release = threading.Event()

    class Slow:
        def submit(self, body):
            if body["max_new_tokens"] == 0:
                release.wait(5.0)  # the first request hangs
            return 200, {"status": "complete", "tokens": [1]}

    reqs = [gen.Request(due_s=0.01 * i, prompt=[1], max_new_tokens=i)
            for i in range(12)]
    load = drv.Load(Slow(), reqs)
    t0 = load.start()
    load._thread.join()
    deadline = time.perf_counter() + 5.0
    while len(load.snapshot()) < 11 and time.perf_counter() < deadline:
        time.sleep(0.01)
    done = sorted(r.index for r in load.snapshot())
    assert done == list(range(1, 12))  # all but the one that hangs
    assert max(r.sent - r.due for r in load.snapshot()) < 0.5
    release.set()
    load.join()
    assert len(load.snapshot()) == 12 and t0 == load.clock0


def test_train_rows_all_differ_and_follow_the_seed():
    from benchmarks.harness import manifest

    cell = manifest.load_cell("cgpt1.3b-train-1chip")
    gen = cell.generator()
    kw = dict(vocab_size=50257, seq_len=2048, global_batch=4)
    a = gen.generate(cell.traffic, seed=2**31 + 5, **kw)
    assert a.shape == (256, 2048) and a.dtype.name == "int32"
    assert len({r.tobytes() for r in a}) == len(a)
    assert (a == gen.generate(cell.traffic, seed=2**31 + 5, **kw)).all()
    assert (a != gen.generate(cell.traffic, seed=5, **kw)).any()


@pytest.mark.parametrize("workload", ["cgpt1.3b-train-1chip",
                                      "cgpt1.3b-serve-chat-sat"])
def test_no_tpu_no_result(workload):
    """The measuring path exits non-zero and prints no result line
    where JAX finds no TPU."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", workload, "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300,
    )
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "refusing to measure" in p.stderr


def test_no_program_no_result(tmp_path):
    """In a directory that holds only BENCHMARK.json and the files
    under ``paths`` there is nothing to measure."""
    import shutil

    root = str(tmp_path)
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    shutil.copytree(os.path.join(ROOT, "benchmarks"),
                    os.path.join(root, "benchmarks"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    p = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload",
         "cgpt1.3b-train-1chip", "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        cwd=root, env=dict(env, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300,
    )
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "no program to measure" in p.stderr
