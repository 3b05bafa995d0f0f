"""Driven by data: a configuration, a traffic mix, a cell and a
per-layer metric are each added as NEW files and NEW entries — no edit
to a file that is there — and the harness lists and resolves them."""

import json
import os
import shutil

from benchmarks.harness import manifest as mf


def test_add_config_traffic_cell_and_metric_without_an_edit(tmp_path):
    root = str(tmp_path)
    shutil.copy(os.path.join(mf.ROOT, "BENCHMARK.json"), root)
    shutil.copytree(os.path.join(mf.ROOT, "benchmarks"),
                    os.path.join(root, "benchmarks"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    b = os.path.join(root, "benchmarks")
    digest = {
        os.path.join(d, f): open(os.path.join(d, f), "rb").read()
        for d, _, fs in os.walk(b) for f in fs
    }

    # new files
    with open(os.path.join(b, "configs", "dummy-model.json"), "w") as f:
        json.dump({"kind": "dummy", "source": "a paper", "reduced": [],
                   "sizes": {"d": 8}}, f)
    with open(os.path.join(b, "traffic", "dummy-mix.json"), "w") as f:
        json.dump({"generator": "dummy_gen", "n": 3}, f)
    with open(os.path.join(b, "generators", "dummy_gen.py"), "w") as f:
        f.write("def generate(params, *, seed):\n"
                "    return [seed + i for i in range(params['n'])]\n")
    with open(os.path.join(b, "drivers", "dummy.py"), "w") as f:
        f.write("def run(cell, args, ctx):\n"
                "    return cell.generator().generate(cell.traffic, "
                "seed=args.seed)\n")
    with open(os.path.join(b, "layer_metrics", "dummy_depth.py"), "w") as f:
        f.write("NAME = 'dummy_depth'\nUNIT = 'items'\nLAYER = 'Queue'\n"
                "MOVES = 'dummy_rate'\nSOURCE = 'program_counter'\n\n\n"
                "def read(run):\n    return run.counters.get('depth')\n")

    # new entries
    path = os.path.join(root, "BENCHMARK.json")
    m = mf.load_json(path)
    m["configs"].append({
        "name": "dummy-model", "source": "a paper",
        "file": "benchmarks/configs/dummy-model.json", "reduced": [],
        "why": "test"})
    m["workloads"].append({
        "name": "dummy-cell", "config": "dummy-model",
        "traffic": "dummy-mix", "chips": 1, "why": "test"})
    m["end_to_end"].append({
        "name": "dummy_rate", "unit": "items/s", "better": "higher",
        "bound": 0.01, "source": "host_clock", "workloads": ["dummy-cell"]})
    m["per_layer"].append({
        "name": "dummy_depth", "unit": "items", "better": "lower",
        "source": "program_counter", "layer": "Queue",
        "moves": "dummy_rate", "workloads": ["dummy-cell"]})
    with open(path, "w") as f:
        json.dump(m, f)

    # nothing that was there changed
    for p, content in digest.items():
        assert open(p, "rb").read() == content, p

    cell = mf.load_cell("dummy-cell", root)
    assert cell.config["sizes"] == {"d": 8} and cell.traffic["n"] == 3
    assert {x["name"] for x in cell.end_to_end()} == {"dummy_rate",
                                                      "setup_s"}
    assert list(cell.layer_readers()) == ["dummy_depth"]

    class Args:
        seed = 40

    assert cell.driver().run(cell, Args, None) == [40, 41, 42]

    class Run:
        counters = {"depth": 7}

    assert cell.layer_readers()["dummy_depth"].read(Run) == 7
    # and the cells that were there are untouched by the newcomer
    old = mf.load_cell("cgpt1.3b-train-1chip", root)
    assert "dummy_depth" not in old.layer_readers()
    assert "dummy_rate" not in [x["name"] for x in old.end_to_end()]
