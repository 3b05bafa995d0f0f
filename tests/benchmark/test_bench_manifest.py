"""``BENCHMARK.json`` against the contract's limits on names, units and
shape, and against the files it names."""

import json
import os
import re

import pytest

from benchmarks.harness import manifest as mf

ROOT = mf.ROOT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def m():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _metrics(m):
    return m["end_to_end"] + m["per_layer"]


def test_top_level_keys_and_size(m):
    assert set(m) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert isinstance(m["run_seconds"], int) and 1 <= m["run_seconds"] <= 51


def test_run_seconds_fits_a_full_check_of_24_cells(m):
    runs = 2 + 14 * 24
    total = runs * (m["run_seconds"] + 60) + 24 * 2 * 90 + 1200
    assert total <= 43200


def test_command_and_paths(m):
    assert 1 <= len(m["paths"]) <= 16
    for p in m["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
        assert os.path.isdir(os.path.join(ROOT, p))
    assert len(m["command"]) <= 32
    script = m["command"][1]
    assert any(script.startswith(p + "/") for p in m["paths"])
    assert os.path.isfile(os.path.join(ROOT, script))


def test_names_are_unique_and_well_formed(m):
    for section in ("configs", "workloads"):
        names = [e["name"] for e in m[section]]
        assert len(set(names)) == len(names)
        assert all(NAME.match(n) for n in names), names
    names = [e["name"] for e in _metrics(m)]
    assert len(set(names)) == len(names)
    assert all(NAME.match(n) for n in names), names
    for w in m["workloads"]:
        assert NAME.match(w["traffic"]) and NAME.match(w["config"])


def test_units_sources_and_directions(m):
    for e in _metrics(m):
        assert UNIT.match(e["unit"]), e
        assert e["better"] in ("lower", "higher")
        assert e["source"] in SOURCES
    for e in m["end_to_end"]:
        assert e["source"] in ("host_clock", "device_trace")
        assert 0 < e["bound"] <= 0.1
        assert set(e) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
    assert any(e["name"] == "setup_s" and "workloads" not in e
               for e in m["end_to_end"])


def test_entries_have_just_the_keys_shown(m):
    for c in m["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert len(c["reduced"]) <= 16
    for w in m["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4)
    for e in m["per_layer"]:
        assert set(e) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert "bound" not in e


def test_one_line_texts(m):
    texts = [w["why"] for w in m["workloads"]]
    texts += [c["why"] for c in m["configs"]]
    texts += [c["source"] for c in m["configs"]]
    texts += [e["layer"] for e in m["per_layer"]] + m["command"]
    for t in texts:
        assert 1 <= len(t) <= 200 and "\n" not in t and "\t" not in t, t


def test_four_chip_cells_within_the_quarter(m):
    four = sum(w["chips"] == 4 for w in m["workloads"])
    assert four <= max(1, len(m["workloads"]) // 4)


def test_configs_files_and_reduced(m):
    files = [c["file"] for c in m["configs"]]
    assert len(set(files)) == len(files)
    used = {w["config"] for w in m["workloads"]}
    for c in m["configs"]:
        assert c["name"] in used
        assert any(c["file"].startswith(p + "/") for p in m["paths"])
        cfg = mf.load_json(os.path.join(ROOT, c["file"]))
        assert cfg["source"] == c["source"]
        assert cfg["reduced"] == c["reduced"]
        for key in c["reduced"]:
            assert NAME.match(key)
            assert not re.search(r"(_dim|_rank|embd|inner|hidden|head)", key)
        assert cfg["kind"] in ("train", "serve")


def test_published_widths_are_never_cut(m):
    for c in m["configs"]:
        cfg = mf.load_json(os.path.join(ROOT, c["file"]))
        assert (cfg["n_embd"], cfg["n_head"], cfg["n_inner"],
                cfg["vocab_size"], cfg["n_positions"]) == (
            2048, 16, 8192, 50257, 2048)
        assert cfg["n_layer"] == 24 or "n_layer" in c["reduced"]


def test_every_cell_resolves_and_reports_enough(m):
    e2e = {e["name"] for e in m["end_to_end"]}
    for w in m["workloads"]:
        cell = mf.load_cell(w["name"])
        assert os.path.isfile(os.path.join(
            cell.bench_dir, "drivers", cell.config["kind"] + ".py"))
        assert os.path.isfile(os.path.join(
            cell.bench_dir, "generators",
            cell.traffic["generator"] + ".py"))
        names = [x["name"] for x in cell.end_to_end()]
        assert "setup_s" in names and len(names) >= 2
        assert len(cell.per_layer()) >= 1
        for x in cell.per_layer():
            assert x["moves"] in names  # the cell reports what it moves
    for e in m["per_layer"]:
        assert e["moves"] in e2e


def test_each_per_layer_metric_is_a_reader_of_its_own(m):
    d = os.path.join(ROOT, "benchmarks", "layer_metrics")
    for e in m["per_layer"]:
        mod = mf.load_module(os.path.join(d, e["name"] + ".py"),
                             "t_" + e["name"])
        assert (mod.NAME, mod.UNIT, mod.LAYER, mod.MOVES, mod.SOURCE) == (
            e["name"], e["unit"], e["layer"], e["moves"], e["source"])
        assert callable(mod.read)


def test_roofline_and_mfu_shares_are_percentages(m):
    for e in _metrics(m):
        if e["name"].endswith("_roofline_pct") or "mfu" in e["name"]:
            assert e["unit"] == "%"


def test_harness_holds_no_cell_configuration_or_metric_name(m):
    names = {e["name"] for s in ("configs", "workloads", "end_to_end",
                                 "per_layer") for e in m[s]}
    names -= {"setup_s"}
    for rel in ("run.py", "sweep.py", "harness/manifest.py",
                "harness/window.py", "harness/trace.py",
                "harness/result.py", "harness/device.py"):
        text = open(os.path.join(ROOT, "benchmarks", rel)).read()
        held = [n for n in names if n in text]
        assert not held, (rel, held)


def test_files_under_paths_are_named_from_allowed_characters(m):
    for p in m["paths"]:
        for d, _, fs in os.walk(os.path.join(ROOT, p)):
            if "__pycache__" in d:
                continue
            for f in fs:
                rel = os.path.relpath(os.path.join(d, f), ROOT)
                assert PATH.match(rel), rel
