"""The per-layer metrics under ``setup_s``: seven readers of the
program's own account of its start (``benchmarks/layer_metrics/
setup_*.py`` over ``_setup_common.py``; the records are
``ddp_tpu.obs.tracer.get_tracer().startup()``).

On a rehearsal of one train and one serve cell, run in a process of its
own so that the records are that run's and no earlier test's, every
reader finds a positive number; by hand on a few records, each number
is the UNION of its intervals; on a program that keeps nothing (the
parent of the PR that brought the store) every reader gives None and
none raises."""

import json
import os
import subprocess
import sys
from types import SimpleNamespace

import pytest

import bench_contract as bc
from bench_helpers import ROOT
from benchmarks.harness import manifest
from benchmarks.harness.window import Block

ALL = ["setup_import_s", "setup_state_s", "setup_trace_s", "setup_lower_s",
       "setup_backend_s", "setup_warmup_s", "setup_spanned_pct"]
TRAIN = [n for n in ALL if n != "setup_warmup_s"]

REHEARSE = """
import json, sys
sys.path[:0] = [{tests!r}, {root!r}]
from bench_helpers import run_cell
from benchmarks.harness import manifest
rc, run, lines = run_cell({copy!r}, {cell!r}, seed=3_000_000_019)
assert rc == 0, lines
readers = manifest.load_cell({cell!r}, {copy!r}).layer_readers()
out = {{n: r.read(run) for n, r in readers.items() if n.startswith("setup_")}}
from ddp_tpu.obs.tracer import get_tracer
opens = run.blocks[0].start
out["kept"] = sum(e[1] + e[2] <= opens for e in get_tracer().startup())
print("READINGS " + json.dumps(out))
"""
CELLS = ("tiny-train-1", "tiny-serve-1")


@pytest.fixture(scope="module")
def readings(bench_copy):
    """Each cell's rehearsal in a process of its own, side by side."""
    procs = {
        cell: subprocess.Popen(
            [sys.executable, "-c", REHEARSE.format(
                tests=os.path.dirname(os.path.abspath(__file__)),
                root=ROOT, copy=bench_copy, cell=cell)],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            cwd=ROOT, env={**os.environ, "JAX_PLATFORMS": "cpu"})
        for cell in CELLS
    }
    out = {}
    for cell, p in procs.items():
        stdout, stderr = p.communicate(timeout=600)
        assert p.returncode == 0, stderr[-3000:]
        line = [ln for ln in stdout.splitlines()
                if ln.startswith("READINGS ")]
        out[cell] = json.loads(line[-1][len("READINGS "):])
    return out


@pytest.mark.parametrize("name", TRAIN)
def test_a_train_rehearsal_gives_every_reader_a_number(readings, name):
    got = readings["tiny-train-1"]
    assert set(got) - {"kept"} == set(TRAIN)  # no warm-up in a train cell
    assert got[name] is not None and got[name] > 0


@pytest.mark.parametrize("name", ALL)
def test_a_serve_rehearsal_gives_every_reader_a_number(readings, name):
    got = readings["tiny-serve-1"]
    assert got[name] is not None and got[name] > 0


def test_the_rehearsals_records_fit_the_store(readings):
    from ddp_tpu.obs.tracer import KEPT_RECORDS

    for cell in CELLS:
        assert 0 < readings[cell]["kept"] < KEPT_RECORDS


# ---- by hand ---------------------------------------------------------------


@pytest.fixture
def tracer(monkeypatch):
    from ddp_tpu.obs import tracer as tr

    fresh = tr.Tracer()
    monkeypatch.setattr(tr, "_GLOBAL", fresh)
    return fresh


@pytest.fixture(scope="module")
def readers():
    got = manifest.load_cell("cgpt1.3b-serve-chat-sat").layer_readers()
    return {n: got[n] for n in ALL}


def _run(setup_s=20.0, opens=120.0):
    return SimpleNamespace(
        blocks=[Block(opens, opens + 2.0, 10, steps=2, traced=True),
                Block(opens + 2.0, opens + 4.0, 10, steps=2)],
        end_to_end={"setup_s": setup_s}, counters={}, trace=None,
        cell=SimpleNamespace(name="some-cell", root="/checkout"))


def _fill(t):
    """Set-up from 100 to 120: 6 s of imports (two nested), 3 s of
    state, 8 s of warm-up with two programs; a trace nested in another;
    a compile that ends inside the window, which is not set-up's."""
    t.phase_complete("startup.import", 100.0, 6.0, nums=("prog.trainer",))
    t.phase_complete("startup.import", 101.0, 4.0, nums=("orbax",))
    t.phase_complete("startup.state", 106.0, 3.0, nums=("engine",))
    t.phase_complete("startup.lane_cache", 106.5, 1.0, parent=106.0)
    t.phase_complete("startup.warmup", 110.0, 8.0, nums=(2,))
    t.phase_complete("startup.warmup_program", 110.0, 5.0, parent=110.0,
                     nums=("prefill_first", 64))
    t.phase_complete("startup.warmup_program", 115.0, 2.5, parent=110.0,
                     nums=("decode", 0))
    t.phase_complete("startup.warmup_wait", 117.5, 0.5, parent=110.0)
    t.phase_complete("compile.trace", 110.0, 2.0, nums=("serve_prefill",))
    t.phase_complete("compile.trace", 110.5, 1.0, nums=("_einsum",))
    t.phase_complete("compile.trace", 115.0, 0.5, nums=("serve_decode",))
    t.phase_complete("compile.lower", 112.0, 1.0, nums=("serve_prefill",))
    t.phase_complete("compile.lower", 115.5, 1.5, nums=("serve_decode",))
    t.phase_complete("compile.backend", 113.0, 2.0,
                     nums=("serve_prefill", 1))
    t.phase_complete("compile.backend", 117.0, 0.5, nums=("serve_decode", 0))
    t.phase_complete("compile.backend", 119.5, 1.0, nums=("late", 0))
    # the lead traffic's steps, in the ring alone
    t.complete("serve.step", 118.0, 1.5)


HAND = {
    "setup_import_s": 6.0,  # the inner import is covered once
    "setup_state_s": 3.0,
    "setup_trace_s": 2.5,  # 2.0 + 0.5; the nested second counts once
    "setup_lower_s": 2.5,
    "setup_backend_s": 2.5,  # the one that ends in the window is left out
    "setup_warmup_s": 8.0,
    # [100, 106] + [106, 109] + [110, 118] + the ring's [118, 119.5] of
    # the twenty seconds; the late compile is kept, so it counts here
    "setup_spanned_pct": 100.0 * 19.0 / 20.0,
}


@pytest.mark.parametrize("name", ALL)
def test_each_number_is_the_union_of_its_records(tracer, readers, name,
                                                 capsys):
    _fill(tracer)
    assert readers[name].read(_run()) == pytest.approx(HAND[name])
    said = [json.loads(ln.split(" ", 2)[2])
            for ln in capsys.readouterr().out.splitlines()
            if ln.startswith("# setup_span ")]
    assert [s["metric"] for s in said] == [name]


def test_the_lines_beside_the_numbers_name_modules_and_programs(
        tracer, readers, capsys):
    _fill(tracer)
    for name in ("setup_import_s", "setup_state_s", "setup_backend_s",
                 "setup_warmup_s"):
        readers[name].read(_run())
    said = {s["metric"]: s for s in (
        json.loads(ln.split(" ", 2)[2])
        for ln in capsys.readouterr().out.splitlines())}
    assert said["setup_import_s"]["dearest_modules_s"] == {
        "prog.trainer": 6.0, "orbax": 4.0}
    assert said["setup_state_s"]["children_s"] == {"startup.lane_cache": 1.0}
    back = said["setup_backend_s"]
    assert (back["executables"], back["cache_hits"]) == (2, 1)
    assert list(back["dearest_programs"]) == ["serve_prefill",
                                              "serve_decode", "_einsum"]
    assert back["dearest_programs"]["serve_decode"] == {
        "trace_s": 0.5, "lower_s": 1.5, "backend_s": 0.5,
        "compiles": 1, "cache_hits": 0}
    assert said["setup_warmup_s"]["programs_s"] == {
        "prefill_first/64": 5.0, "decode/0": 2.5}
    assert said["setup_warmup_s"]["wait_s"] == 0.5


@pytest.mark.parametrize("name", ALL)
def test_an_empty_store_is_none_not_zero(tracer, readers, name, capsys):
    tracer.complete("serve.step", 118.0, 1.5)  # a ring, but nothing kept
    assert readers[name].read(_run()) is None
    assert capsys.readouterr().out == ""


@pytest.mark.parametrize("name", ALL)
def test_a_program_without_the_store_is_none_too(monkeypatch, readers, name):
    """The parent commit under this PR's benchmark files: a tracer with
    a ring and no ``startup``."""
    from ddp_tpu.obs import tracer as tr

    class Parent:
        def ring(self):
            return [("serve.step", 118.0, 1.5, None, ())]

    monkeypatch.setattr(tr, "_GLOBAL", Parent())
    assert readers[name].read(_run()) is None


def test_the_manifest_holds_the_seven_entries_and_keeps_the_contract():
    """In the three Cerebras cells: the three later serve cells' own
    accepted tests hold each to exactly the per-layer metrics it came
    with (PERF.md section 7), so only a ``benchmark`` PR can list them
    here, though the readers read the same records there."""
    m = bc.manifest_of(bc.mf.ROOT)
    mine = [e for e in m["per_layer"] if e["moves"] == "setup_s"]
    assert [e["name"] for e in mine] == ALL == [
        e["name"] for e in m["per_layer"][-7:]]
    cells = [w["name"] for w in m["workloads"]
             if w["config"].startswith("cerebras-gpt-1.3b-")]
    assert len(cells) == 3
    for e in mine:
        want = (["cgpt1.3b-serve-chat-sat"]
                if e["name"] == "setup_warmup_s" else cells)
        assert e["workloads"] == want, e["name"]
        assert e["layer"] == "CLI / launcher, runtime"
    for cell, want in (("cgpt1.3b-train-1chip", TRAIN),
                       ("cgpt1.3b-serve-chat-sat", ALL)):
        got = [x["name"] for x in bc.mf.load_cell(cell).per_layer()]
        assert got[-len(want):] == want
    # The two failures that stand on every checkout are two other
    # tests' own asserts (the manifest's length when they were written);
    # every rule of the contract holds.
    assert bc.failures(bc.mf.ROOT) == {}
