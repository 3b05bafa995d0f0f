#!/usr/bin/env bash
# The decode-speed stack (docs/SERVING.md "Raw decode speed"):
# flash-decode kernel, speculative decoding, int8 KV cache.
# Runs green end to end on a CPU dev box: the kernel pins run through
# the Pallas interpreter, flash `auto` honestly resolves to the
# bit-identical jnp reference off-TPU, and the speculative/int8
# layers exercise their real engine machinery.
set -euo pipefail
cd "$(dirname "$0")/.."

WORK=${WORK:-/tmp/ddp_tpu_example20}
rm -rf "$WORK" && mkdir -p "$WORK"
export JAX_PLATFORMS=${JAX_PLATFORMS:-cpu}

# 1. Token-identity across the stack, in one shot: the Pallas kernel
#    (interpret mode here) vs the jnp reference at the op level, the
#    flash engine vs generate() across bucket edges for greedy AND
#    seeded sampling, int8 bounded divergence, and the spec-decode
#    output-equivalence pins.
python -m pytest tests/test_flash_decode.py tests/test_spec_decode.py \
    -q -p no:cacheprovider

# 2. A speculative + int8-KV server with no training run: --init_demo
#    synthesizes the target AND a half-width draft; the startup line
#    reports the decode path (attn impl, kv dtype, cache bytes/slot,
#    spec_tokens). --sanitize arms the transfer guard around the hot
#    loop while we drive real traffic through it.
python scripts/serve.py --init_demo --port 8031 \
    --slots 4 --spec_tokens 3 --kv_dtype int8 \
    --sanitize --metrics_file "$WORK/serve.jsonl" \
    >"$WORK/server.log" 2>&1 &
SERVER=$!
trap 'kill $SERVER 2>/dev/null || true' EXIT
for _ in $(seq 60); do
    curl -sf localhost:8031/healthz >/dev/null 2>&1 && break
    sleep 1
done

# Greedy and seeded requests through the speculative engine...
curl -s localhost:8031/generate \
    -d '{"prompt_tokens": [7, 3, 9], "max_new_tokens": 24}'; echo
curl -s localhost:8031/generate \
    -d '{"prompt_tokens": [1, 2, 3, 4], "max_new_tokens": 16,
         "temperature": 0.8, "top_p": 0.9, "seed": 42}'; echo

# ...and the acceptance accounting they produced: per-request
# spec_acceptance in /stats' decode_path block, lifetime counters on
# /metricsz, and cache_bytes_per_slot showing the int8 layout.
curl -s localhost:8031/stats | python -c \
    'import json,sys; print(json.dumps(json.load(sys.stdin)["decode_path"], indent=1))'
curl -s localhost:8031/metricsz | grep -E \
    'ddp_tpu_serve_(spec_(drafted|accepted)_total|spec_acceptance|cache_bytes_per_slot)'

kill $SERVER 2>/dev/null || true
wait $SERVER 2>/dev/null || true

# 3. The serve_step records carry the per-step drafted/accepted
#    counts (None-safe: prefill-only steps report 0 drafted).
grep -m 3 '"spec_drafted"' "$WORK/serve.jsonl"

echo "example 20 OK"
