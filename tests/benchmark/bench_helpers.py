"""Helpers shared by the benchmark's tests (imported by name, so that
they do not depend on which ``conftest`` module Python finds first)."""

from __future__ import annotations

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def _write(path: str, obj: dict) -> None:
    with open(path, "w") as f:
        json.dump(obj, f, indent=1)


def _load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def add_entries(manifest: dict, *, config: dict, cells: list[dict],
                like: dict[str, str]) -> None:
    """New entries only: a configuration, its cells, and each new cell's
    name beside the cell it is ``like`` wherever a metric lists cells."""
    manifest["configs"].append(config)
    manifest["workloads"].extend(cells)
    for section in ("end_to_end", "per_layer"):
        for m in manifest[section]:
            for new, old in like.items():
                if old in m.get("workloads", ()):
                    m["workloads"].append(new)


def run_cell(root: str, workload: str, *, seed: int = 5, seconds: float = 1,
             trace: int = 0, break_path=None, out: str | None = None):
    """One rehearsal run through the benchmark's own ``main``, the look
    for a chip skipped. Returns (exit code, Run, the printed lines)."""
    import contextlib
    import io

    from benchmarks import run as bench_run

    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc, r = bench_run.main(
            ["--workload", workload, "--seed", str(seed), "--seconds",
             str(seconds), "--trace", str(trace), "--out",
             out or os.path.join(root, "out", workload)],
            root=root, require_chip=False, break_path=break_path,
        )
    return rc, r, buf.getvalue().strip().splitlines()
