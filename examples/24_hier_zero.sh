#!/usr/bin/env bash
# Comm efficiency at pod scale (docs/COMPOSITIONS.md "Hierarchical
# ZeRO"): a two-level dcn×data mesh where the zero step reduce-
# scatters within a slice over ICI and exchanges only 1/N shards
# across slices over DCN, plus bf16 param gathers over fp32 master
# shards. Emulated on a CPU dev box: 2 spawned processes × 2 devices
# = 2 "slices" of 2 chips, the process boundary standing in for the
# slow inter-slice fabric (the cross-slice collectives really cross
# it — gloo). On a real multi-slice pod drop the emulation flags;
# slices come from the devices' slice_index.
set -euo pipefail
cd "$(dirname "$0")/.."

WORK=${WORK:-/tmp/ddp_tpu_example24}
rm -rf "$WORK" && mkdir -p "$WORK"
export JAX_PLATFORMS=${JAX_PLATFORMS:-cpu}

# 1. FLAT control at the same world 4: every reduce-scatter/all-gather
#    spans both "slices" — on a pod, every byte of it would ride DCN.
python train.py --spawn 2 --emulate_devices 2 \
    --epochs 1 --batch_size 8 \
    --optimizer adam --lr 1e-3 \
    --parallel zero --zero_bucket_mb 0.25 \
    --synthetic_data --synthetic_size 256 \
    --checkpoint_dir "$WORK/ck_flat" --data_root "$WORK/data" \
    --metrics_file "$WORK/flat.jsonl" \
    --log_interval 4 --eval_every 0

# 2. HIERARCHICAL: --mesh_dcn 2 maps the outermost mesh axis onto the
#    process boundary. The step becomes RS-within-slice / all-reduce
#    the 1/N shards across slices / AG-within-slice, and every
#    step/epoch record now carries the per-fabric split
#    (comm_bytes_ici / comm_bytes_dcn) — cross-slice bytes are 1/N of
#    the flat payload. --zero_gather_dtype bf16 halves the ICI
#    all-gather on top (fp32 master shards keep the update exact),
#    and --grad_clip_norm rides the scattered shards (the lifted
#    composition — one psum IS the global norm).
python train.py --spawn 2 --emulate_devices 2 \
    --epochs 1 --batch_size 8 \
    --optimizer adam --lr 1e-3 --grad_clip_norm 1.0 \
    --parallel zero --zero_bucket_mb 0.25 \
    --mesh_dcn 2 --zero_gather_dtype bf16 \
    --synthetic_data --synthetic_size 256 \
    --checkpoint_dir "$WORK/ck_hier" --data_root "$WORK/data" \
    --metrics_file "$WORK/hier.jsonl" \
    --log_interval 4 --eval_every 0

# 3. The triage screens, side by side: the flat run's comm line is one
#    number; the hierarchical run's shows the ici/dcn split (the dcn
#    side is the small one — that is the point).
echo "--- flat ---"
python scripts/health_report.py "$WORK/flat.jsonl" | grep -E "comm/step|loss" || true
echo "--- hierarchical (ici/dcn split) ---"
python scripts/health_report.py "$WORK/hier.jsonl" | grep -E "comm/step|loss" || true

# 4. The stamped records themselves: the hier stream carries
#    comm_bytes_ici / comm_bytes_dcn on every step record.
python - "$WORK/hier.jsonl" <<'PY'
import json, sys
step = next(
    json.loads(l) for l in open(sys.argv[1])
    if json.loads(l).get("kind") == "step"
)
print("comm_bytes      :", step["comm_bytes"])
print("comm_bytes_ici  :", step["comm_bytes_ici"])
print("comm_bytes_dcn  :", step["comm_bytes_dcn"])
assert step["comm_bytes_dcn"] < step["comm_bytes_ici"]
PY
