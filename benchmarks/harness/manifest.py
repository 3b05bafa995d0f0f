"""Find a cell's files by the names in ``BENCHMARK.json``.

Nothing here knows a cell's, a configuration's or a metric's name: a
later PR adds a cell by adding entries to the manifest and files beside
the ones that are there, and edits nothing.
"""

from __future__ import annotations

import importlib.util
import json
import os
import sys
from dataclasses import dataclass

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def load_module(path: str, name: str):
    """Import one file by path (``generators/x.py`` -> module)."""
    if not os.path.isfile(path):
        raise FileNotFoundError(f"{name}: no file {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod  # dataclasses look their module up here
    spec.loader.exec_module(mod)
    return mod


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict
    traffic_name: str
    traffic: dict
    manifest: dict
    root: str

    @property
    def bench_dir(self) -> str:
        return os.path.join(self.root, "benchmarks")

    def driver(self):
        kind = self.config["kind"]
        return load_module(
            os.path.join(self.bench_dir, "drivers", f"{kind}.py"),
            f"bench_driver_{kind}",
        )

    def generator(self):
        gen = self.traffic["generator"]
        return load_module(
            os.path.join(self.bench_dir, "generators", f"{gen}.py"),
            f"bench_generator_{gen}",
        )

    def _wanted(self, section: str) -> list[dict]:
        """The manifest's metrics of one section that this cell
        reports: those that list it under ``workloads``, or list
        nothing and (per-layer) move a metric this cell reports."""
        e2e_here = {
            m["name"] for m in self.manifest["end_to_end"]
            if "workloads" not in m or self.name in m["workloads"]
        }
        out = []
        for m in self.manifest[section]:
            if "workloads" in m:
                if self.name in m["workloads"]:
                    out.append(m)
            elif section == "end_to_end" or m["moves"] in e2e_here:
                out.append(m)
        return out

    def end_to_end(self) -> list[dict]:
        return self._wanted("end_to_end")

    def per_layer(self) -> list[dict]:
        return self._wanted("per_layer")

    def layer_readers(self) -> dict:
        """name -> reader module, for this cell's per-layer metrics.
        The directory is globbed: a metric is a file of its own."""
        d = os.path.join(self.bench_dir, "layer_metrics")
        mods = {}
        for fn in sorted(os.listdir(d)):
            if fn.endswith(".py") and not fn.startswith("_"):
                mod = load_module(
                    os.path.join(d, fn), f"bench_layer_{fn[:-3]}"
                )
                mods[mod.NAME] = mod
        wanted = [m["name"] for m in self.per_layer()]
        missing = [n for n in wanted if n not in mods]
        if missing:
            raise FileNotFoundError(
                f"per-layer metrics without a reader in {d}: {missing}"
            )
        return {n: mods[n] for n in wanted}


def load_cell(workload: str, root: str = ROOT) -> Cell:
    manifest = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise KeyError(
            f"no workload {workload!r} in BENCHMARK.json "
            f"(has: {sorted(cells)})"
        )
    w = cells[workload]
    configs = {c["name"]: c for c in manifest["configs"]}
    cfg_entry = configs[w["config"]]
    config = load_json(os.path.join(root, cfg_entry["file"]))
    traffic = load_json(
        os.path.join(root, "benchmarks", "traffic", f"{w['traffic']}.json")
    )
    return Cell(
        name=workload, chips=int(w["chips"]), config_name=w["config"],
        config=config, traffic_name=w["traffic"], traffic=traffic,
        manifest=manifest, root=root,
    )
