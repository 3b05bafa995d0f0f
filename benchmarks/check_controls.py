"""Run the controls of ``correct`` on the chip, at a cell's own size.

    python3 benchmarks/check_controls.py --workload <cell> --seeds 1,2,3

For each seed the cell's driver computes its control — the reference in
the program's place, in the precision below the one the configuration
states — through the comparison that decides ``correct``, and prints
each number beside its limit. Every control has to come out NOT
correct; the exit code is 1 if one passes. The benchmark's own runs do
not run this. A serve cell's control reads the prompts and served
tokens that a run of the same seed wrote (run the cell first).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--out", default=None)
    p.add_argument(
        "--readings", action="store_true",
        help="limit-setting mode: the driver's sound readings AND "
        "controls over the seeds in one set-up (drivers that have it)",
    )
    args = p.parse_args(argv)

    from benchmarks.harness import manifest
    from benchmarks.harness.device import place_compile_cache, require_tpu

    cell = manifest.load_cell(args.workload)
    # A control is the single-device reference: one chip is enough.
    require_tpu(cell.chips if args.readings else 1)
    place_compile_cache(ROOT)
    out_dir = args.out or os.path.join(
        ROOT, "chiprun_out", "bench", args.workload
    )
    seeds = [int(s) for s in args.seeds.split(",")]
    if args.readings:
        from benchmarks.harness.compiles import CompileLedger
        from benchmarks.harness.spans import Spans
        from benchmarks.run import Context

        os.makedirs(out_dir, exist_ok=True)
        ctx = Context(t0=0.0, backend_up_s=0.0, on_tpu=True,
                      out_dir=out_dir, spans=Spans(),
                      ledger=CompileLedger().install())
        rows = cell.driver().readings(cell, seeds, ctx)
        for key in rows[0]["sound"]:
            print(json.dumps({
                "number": key,
                "sound_max": max(r["sound"][key] for r in rows),
                "control_min": min(r["control"][key] for r in rows),
            }))
        return 0
    passed = 0
    for seed in seeds:
        res = cell.driver().control(cell, seed, out_dir)
        passed += bool(res["correct"])
        print("# control " + json.dumps(res, default=float), flush=True)
    print(json.dumps({"controls_that_passed": passed}))
    return 1 if passed else 0


if __name__ == "__main__":
    sys.exit(main())
