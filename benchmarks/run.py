"""The benchmark's one command.

    python3 benchmarks/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Finds the cell in ``BENCHMARK.json``, its configuration and traffic
files, the driver for the configuration's ``kind`` and the generator
the traffic names; with ``--trace 1`` the per-layer metrics' readers.
Holds no cell's, no configuration's and no metric's name.

Prints earlier lines that start with ``#`` (set-up split, the block
series with the block-median reading beside the whole-window quotient,
each number compared beside its limit) and, as the LAST line, one JSON
object: the result. Exits non-zero with no result where it finds no
TPU, fewer chips than the cell asks for, or no program to measure.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()  # as near to the process's start as Python gets

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from dataclasses import dataclass, field  # noqa: E402
from typing import Callable, Optional  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


@dataclass
class Context:
    """What the harness hands a driver."""

    t0: float
    backend_up_s: float
    on_tpu: bool
    out_dir: str
    spans: object
    ledger: object
    devices: list = field(default_factory=list)
    # Test hook: called with the driver's harnessed program before the
    # first step, to break the timed path underneath.
    break_path: Optional[Callable] = None

    def memory_peak(self) -> int:
        from benchmarks.harness.device import memory_peak_bytes

        return memory_peak_bytes(self.devices)


def parse(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--seconds", type=float, default=None)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument(
        "--out", default=None,
        help="directory for this run's files (default "
        "chiprun_out/bench/<workload>/ in the checkout)",
    )
    return p.parse_args(argv)


def main(argv=None, *, root: str = ROOT, require_chip: bool = True,
         break_path: Optional[Callable] = None, t0: float = T0):
    """Run one cell once. Returns (exit code, Run | None).

    ``require_chip=False`` is for rehearsals and tests only: the run's
    control flow on whatever backend there is, and a result line that
    carries NO metric (a CPU's numbers are not device numbers).
    """
    args = parse(argv)
    from benchmarks.harness import manifest
    from benchmarks.harness.compiles import CompileLedger
    from benchmarks.harness.device import (
        NoChip, describe, place_compile_cache, require_tpu,
    )
    from benchmarks.harness.result import emit, result_line
    from benchmarks.harness.spans import Spans

    cell = manifest.load_cell(args.workload, root)
    if args.seconds is None:
        args.seconds = float(cell.manifest["run_seconds"])
    try:
        import ddp_tpu  # noqa: F401 — the system under test
    except ImportError as e:
        print(f"benchmark: no program to measure: {e}", file=sys.stderr)
        return 2, None

    import jax

    if require_chip:
        try:
            devs = require_tpu(cell.chips)
        except NoChip as e:
            return int(e.code), None
        cache_dir = place_compile_cache(root)
    else:
        devs = jax.devices()
        cache_dir = None
    on_tpu = devs[0].platform == "tpu"
    backend_up_s = time.perf_counter() - t0
    out_dir = args.out or os.path.join(
        root, "chiprun_out", "bench", args.workload
    )
    os.makedirs(out_dir, exist_ok=True)
    ctx = Context(
        t0=t0, backend_up_s=backend_up_s, on_tpu=on_tpu, out_dir=out_dir,
        spans=Spans(), ledger=CompileLedger().install(),
        devices=devs[: cell.chips], break_path=break_path,
    )
    run = cell.driver().run(cell, args, ctx)
    run.device = {**describe(devs, cell.chips), **run.device}

    units = {
        m["name"]: m["unit"]
        for sec in ("end_to_end", "per_layer")
        for m in cell.manifest[sec]
    }
    breakdown = None
    metrics = {}
    if not on_tpu:
        # A rehearsal: counts and control flow only. No reader runs and
        # no rate is printed, so no CPU number gets a device metric's
        # name.
        wanted = cell.per_layer() if args.trace else cell.end_to_end()
        emit("rehearsal", {"platform": devs[0].platform,
                           "metrics_withheld": [m["name"] for m in wanted]})
    elif args.trace:
        from benchmarks.harness import trace as btrace

        for name, reader in cell.layer_readers().items():
            v = reader.read(run)
            if v is not None:
                metrics[name] = v
        if run.trace is not None:
            b = btrace.busy(run.trace)
            run.device["busy_s"] = b["busy_s"]
            run.device["window_s"] = b["window_s"]
            breakdown = btrace.breakdown(run.trace)
            btrace.save(run.trace, os.path.join(out_dir, "trace.json"))
    else:
        metrics = {
            m["name"]: run.end_to_end[m["name"]]
            for m in cell.end_to_end()
        }

    emit("setup", {"cache_dir": cache_dir, **run.setup_split})
    emit("window", run.window)
    for c in run.checks:
        emit("check", c.to_json())
    line = result_line(run, metrics, units, breakdown)
    with open(os.path.join(out_dir, f"run_seed{args.seed}_trace{args.trace}.json"), "w") as f:
        json.dump({
            "args": vars(args), "result": json.loads(line),
            "setup": run.setup_split, "window": run.window,
            "checks": [c.to_json() for c in run.checks],
            "notes": run.notes,
        }, f, indent=1, default=float)
    print(line, flush=True)
    return 0, run


if __name__ == "__main__":
    sys.exit(main()[0])
