#!/usr/bin/env bash
# MPMD pipeline runtime (ISSUE 17 / docs/COMPOSITIONS.md "MPMD
# pipeline runtime"): one OS process per pipeline stage, each
# compiling ONLY its stage, activations/cotangents on the CRC-checked
# ACTV wire, 1F1B over processes. A clean 2-stage causal-LM run, then
# the same run with stage 1 SIGKILLed mid-training — exactly one
# classified restart, survivors roll back without recompiling, final
# metrics identical — triaged by health_report. Green on CPU.
set -euo pipefail
cd "$(dirname "$0")/.."

WORK=${WORK:-/tmp/ddp_tpu_example26}
rm -rf "$WORK" && mkdir -p "$WORK"
export JAX_PLATFORMS=${JAX_PLATFORMS:-cpu}

RUN="python -m ddp_tpu.parallel.mpmd --stages 2 --steps 6
     --batch_size 8 --microbatches 4 --seq_len 16 --d_model 32"

# 1. The clean run: a supervisor + 2 stage processes. The printed
#    summary carries the final loss and each stage's compile ledger
#    (stage<k>_xprof.json in the workdir — each stage compiled 1/S of
#    the model; the in-graph schedule would compile all of it into
#    every process).
$RUN --workdir "$WORK/clean" --metrics_file "$WORK/clean.jsonl" \
    --json "$WORK/clean.json" >/dev/null
python - "$WORK" <<'EOF'
import json
import sys

clean = json.load(open(f"{sys.argv[1]}/clean.json"))
assert clean["restarts"] == 0, clean
print(json.dumps({
    "loss": round(clean["loss"], 6),
    "restarts": clean["restarts"],
    "per_stage_compile_s": {
        k: round(v["compile_s"], 2) for k, v in clean["final"].items()
    },
}, indent=1))
EOF

# 2. The kill drill: chaos SIGKILLs stage 1 at step 3. The supervisor
#    classifies the exit, restarts ONLY that stage from its
#    stage-sliced checkpoint, stage 0 rolls back in place (no
#    recompile), and the final metrics land exactly on the clean
#    trajectory — the fault is invisible in the result.
$RUN --workdir "$WORK/drill" --metrics_file "$WORK/drill.jsonl" \
    --json "$WORK/drill.json" --chaos kill:stage1@step3 >/dev/null
python - "$WORK" <<'EOF'
import json
import sys

clean = json.load(open(f"{sys.argv[1]}/clean.json"))
drill = json.load(open(f"{sys.argv[1]}/drill.json"))
assert drill["restarts"] == 1, drill["restarts"]
(entry,) = drill["restart_log"]
assert entry["stage"] == 1 and "SIGKILL" in entry["exit"], entry
assert abs(drill["loss"] - clean["loss"]) < 5e-5
print(json.dumps({
    "restart": entry,
    "final_loss_gap": abs(drill["loss"] - clean["loss"]),
}, indent=1))
EOF

# 3. Triage: the mpmd line (stages, loss trajectory, bubble %,
#    restarts) appears only on streams carrying stage-tagged records.
echo "--- health_report (mpmd triage)"
python scripts/health_report.py "$WORK/drill.jsonl" | grep -E "mpmd"

echo "example 26 OK"
