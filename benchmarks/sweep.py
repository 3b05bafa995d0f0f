"""The knee sweep of a ``kind: serve`` cell: several offered rates in ONE
set-up, one command.

    python3 benchmarks/sweep.py --workload <cell> --rates 0.6,0.9,1.2,1.5 \
        --seconds 40 --seed 1

For each rate the cell's traffic mix is generated at that rate (same
lengths, same burst and lead), offered open loop for ``--seconds`` after
the lead, and then the engine is left to drain. A rate is sustained when
the backlog does not grow through its window: the queue's depth at the
end is no larger than at the start plus one request. The knee is the
highest sustained rate; the benchmark's cells sit at fixed multiples of
it, written into their traffic files as numbers. Prints one JSON line
per rate and a table. Needs the chip, like the benchmark.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def one_rate(driver, served, cell, rate: float, seconds: float,
             seed: int) -> dict:
    traffic = dict(cell.traffic, rate_rps=rate)
    requests = cell.generator().generate(
        traffic, seed=seed, vocab_size=served.sizes["vocab_size"],
        seconds=seconds,
    )
    load = driver.Load(served, requests)
    clock0 = load.start()
    time.sleep(float(traffic["lead_s"]))
    blocks = driver.sample_blocks(served, seconds,
                                  float(traffic["block_s"]))
    w0, w1 = blocks[0].start, blocks[-1].end
    load.stop()
    # Drain: every request sent is answered before the next rate.
    load.join()
    drained_s = time.perf_counter() - w1
    recs = load.snapshot()
    fin = [r for r in recs if w0 <= r.done <= w1]
    tpots = [t for t in map(driver.tpot_engine_ms, fin) if t is not None]
    ttfts = [r.ttft_s for r in recs
             if r.ttft_s is not None and w0 <= r.due < w1]
    depths = [b.extra["queue_depth"] for b in blocks]
    half = len(depths) // 2
    return {
        "rate_rps": rate,
        "tokens_per_s": driver.window_quotient(blocks),
        "tpot_p50_ms": statistics.median(tpots) if tpots else None,
        "ttft_p50_s": statistics.median(ttfts) if ttfts else None,
        "ttft_max_s": max(ttfts) if ttfts else None,
        "occupancy": sum(b.extra["active"] for b in blocks)
        / len(blocks) / served.slots,
        "queue_depth_start": depths[0],
        "queue_depth_mid": depths[half],
        "queue_depth_end": depths[-1],
        "queue_depth_max": max(depths),
        "finished": len(fin),
        "sent": len(recs),
        "drain_s": drained_s,
        "sustained": depths[-1] <= depths[0] + 1
        and statistics.mean(depths[half:]) <= statistics.mean(depths[:half]) + 1,
    }


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--workload", required=True)
    p.add_argument("--rates", required=True,
                   help="comma-separated requests/s, ascending")
    p.add_argument("--seconds", type=float, default=40.0)
    p.add_argument("--seed", type=int, default=1)
    args = p.parse_args(argv)

    from benchmarks.harness import manifest
    from benchmarks.harness.device import (
        NoChip, place_compile_cache, require_tpu,
    )

    cell = manifest.load_cell(args.workload)
    try:
        require_tpu(cell.chips)
    except NoChip as e:
        return int(e.code)
    place_compile_cache(ROOT)
    driver = cell.driver()
    served = driver.Served(cell.config, args.seed)
    rows = []
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        row = one_rate(driver, served, cell, rate, args.seconds,
                       args.seed + i)
        rows.append(row)
        print(json.dumps(row), flush=True)
    served.server.stop()
    sustained = [r["rate_rps"] for r in rows if r["sustained"]]
    print("| rate req/s | tokens/s | TPOT p50 ms | TTFT p50 s | TTFT max s "
          "| occupancy | queue start/mid/end | sustained |")
    print("| --- | --- | --- | --- | --- | --- | --- | --- |")
    for r in rows:
        print(f"| {r['rate_rps']} | {r['tokens_per_s']:.1f} | "
              f"{r['tpot_p50_ms']:.1f} | {r['ttft_p50_s']:.2f} | "
              f"{r['ttft_max_s']:.2f} | {r['occupancy']:.2f} | "
              f"{r['queue_depth_start']}/{r['queue_depth_mid']}/"
              f"{r['queue_depth_end']} | {r['sustained']} |")
    print(json.dumps({"knee_rps": max(sustained) if sustained else None}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
