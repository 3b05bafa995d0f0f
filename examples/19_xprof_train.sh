#!/usr/bin/env bash
# Compiled-program introspection (docs/OBSERVABILITY.md "Compiled-
# program introspection"): --xprof dispatches the hot-path jit
# programs through a compile ledger — label, arg-shape signature,
# compile wall-time, XLA-measured FLOPs, memory_analysis() breakdown,
# HLO collective payloads — and samples the device-memory high-water
# into step/epoch records, /metricsz, the Perfetto trace (counter
# track), and the flight recorder's crash dumps.
# Runs on a CPU dev box with 2 emulated devices (so the comm-bytes
# cross-check has real collectives to read); on a TPU slice drop the
# emulation env vars and the HBM fields come from memory_stats().
set -euo pipefail
cd "$(dirname "$0")/.."

WORK=${WORK:-/tmp/ddp_tpu_example19}
rm -rf "$WORK" && mkdir -p "$WORK"
export JAX_PLATFORMS=${JAX_PLATFORMS:-cpu}
export XLA_FLAGS="${XLA_FLAGS:-} --xla_force_host_platform_device_count=2"

# 1. Train with the ledger on (plus tracing, so recompile culprits
#    land in the span args and HBM rides a counter track). The
#    metrics stream gains "compile" records and hbm_* step fields,
#    and the first compiled step logs the comm-bytes cross-check
#    (analytic ddp all-reduce estimate vs the HLO's collectives).
python train.py --epochs 2 --batch_size 8 \
    --synthetic_data --synthetic_size 256 \
    --xprof --trace_dir "$WORK/traces" \
    --checkpoint_dir "$WORK/ck" --data_root "$WORK/data" \
    --metrics_file "$WORK/metrics.jsonl" \
    --log_interval 4 --eval_every 0

# 2. The compile ledger in the stream: every XLA build with its
#    label, signature, and wall time — a recompile would carry a
#    shape_diff naming the argument that changed.
grep '"kind": "compile"' "$WORK/metrics.jsonl"
grep '"kind": "xprof_check"' "$WORK/metrics.jsonl"

# 3. Triage: the report grows compile and hbm lines (builds by label,
#    total compile seconds, memory high-water).
python scripts/health_report.py "$WORK/metrics.jsonl"

# 4. The merged trace carries the HBM counter track; the sidecar
#    summarizes each series' max so "how high did memory get" is
#    greppable without opening Perfetto.
python scripts/trace_merge.py "$WORK/traces" -o "$WORK/merged.trace.json"
