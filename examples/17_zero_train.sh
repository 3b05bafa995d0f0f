#!/usr/bin/env bash
# ZeRO-style weight-update sharding (docs/COMPOSITIONS.md "ZeRO
# weight-update sharding"): reduce-scatter grads in buckets, run the
# optimizer on 1/N shards (Adam moments REST data-sharded), all-gather
# params. Same training math as DDP — parity-pinned — with the
# redundant per-replica update compute and moment memory gone.
# Runs on a CPU dev box with 2 emulated devices; on a TPU slice drop
# the emulation env vars and the replica axis is the chip count.
set -euo pipefail
cd "$(dirname "$0")/.."

WORK=${WORK:-/tmp/ddp_tpu_example17}
rm -rf "$WORK" && mkdir -p "$WORK"
export JAX_PLATFORMS=${JAX_PLATFORMS:-cpu}
export XLA_FLAGS="${XLA_FLAGS:-} --xla_force_host_platform_device_count=2"

# 1. Train with the sharded update. --zero_bucket_mb is the overlap
#    knob (DDP's bucket_cap_mb analogue): smaller buckets give the
#    scheduler more independently-dispatchable collectives. The
#    sanitizer rides along, proving the new hot loop implicit-
#    transfer-free (the PR-6 guard, same hazard class).
python train.py --epochs 2 --batch_size 16 \
    --optimizer adam --lr 1e-3 \
    --parallel zero --zero_bucket_mb 0.25 \
    --synthetic_data --synthetic_size 512 \
    --checkpoint_dir "$WORK/ck" --data_root "$WORK/data" \
    --metrics_file "$WORK/metrics.jsonl" \
    --log_interval 4 --eval_every 0 \
    --sanitize --sanitize_timeout 0

# 2. The metrics stream now carries comm_bytes — the per-step
#    collective payload estimate (all_reduce term is ZERO under zero;
#    the same total rides reduce_scatter + all_gather instead) — and
#    the triage report surfaces it.
python scripts/health_report.py "$WORK/metrics.jsonl"

# 3. The causal LM rides the in-graph GSPMD expression of the same
#    layout: the SPMD partitioner shards the update and the moments.
python train.py --epochs 1 --batch_size 8 \
    --model causal_lm --seq_len 64 --vocab_size 64 \
    --model_dim 32 --model_depth 1 \
    --optimizer adam --lr 1e-3 \
    --parallel zero \
    --checkpoint_dir "$WORK/ck_lm" --data_root "$WORK/data" \
    --synthetic_size 128 --log_interval 4 --eval_every 0
