#!/usr/bin/env bash
# Paged KV + radix prefix cache (PR 12 / docs/SERVING.md "Paged KV &
# prefix cache"): a --page_size server, two clients sharing a system
# prompt — the second request's prefix pages come from the radix
# index (zero prefill compute for the matched tokens), the hit rate
# and page gauges climb on /statusz + /metricsz, token identity
# against a fixed-lane control, and the health_report page triage
# line. Green on CPU.
set -euo pipefail
cd "$(dirname "$0")/.."

WORK=${WORK:-/tmp/ddp_tpu_example22}
rm -rf "$WORK" && mkdir -p "$WORK"
export JAX_PLATFORMS=${JAX_PLATFORMS:-cpu}

# 1. A demo server with the paged cache: 16-token pages, metrics
#    stream for the triage screen, --sanitize arming the transfer
#    guard over the paged decode dispatch (the ()/[S]-int32 steady
#    state invariant holds with paging on).
python scripts/serve.py --init_demo --port 8043 \
    --slots 2 --page_size 16 --sanitize \
    --metrics_file "$WORK/serve.jsonl" \
    >"$WORK/server.log" 2>&1 &
trap 'kill $(jobs -p) 2>/dev/null || true' EXIT
for _ in $(seq 60); do
    curl -sf localhost:8043/healthz >/dev/null 2>&1 && break
    sleep 1
done

# 2. Two clients sharing a 40-token system prompt (tails differ).
#    The FIRST pays the full prefill and publishes the prefix pages
#    at retire; the SECOND maps them copy-free — watch
#    prefix_hit_tokens in the metrics stream.
SYS=$(python -c 'print([(7*i+3) % 256 for i in range(40)])')
curl -s localhost:8043/generate -d "{
    \"prompt_tokens\": $(python -c "print($SYS + [1, 2])"),
    \"max_new_tokens\": 12}" >/dev/null
curl -s localhost:8043/generate -d "{
    \"prompt_tokens\": $(python -c "print($SYS + [9])"),
    \"max_new_tokens\": 12}" >/dev/null

# 3. The reuse, on every surface: the paged block on /statusz (hits,
#    pages free/resident/shared, hit rate) and the linted gauges on
#    /metricsz.
echo "--- /statusz .stats.paged"
curl -s localhost:8043/statusz | python -c \
    'import json,sys; print(json.dumps(
        json.load(sys.stdin)["stats"]["paged"], indent=1))'
echo "--- /metricsz (prefix + pages gauges)"
curl -s localhost:8043/metricsz | grep -E "prefix|pages"

# 4. Token identity through the HTTP surface: the same two prompts on
#    a FIXED-LANE server must produce byte-identical token streams
#    (the paged cache is a layout, never a numerics change).
python scripts/serve.py --init_demo --port 8044 --slots 2 \
    >"$WORK/server_fixed.log" 2>&1 &
for _ in $(seq 60); do
    curl -sf localhost:8044/healthz >/dev/null 2>&1 && break
    sleep 1
done
python - <<'EOF'
import json
import urllib.request

sys_prompt = [(7 * i + 3) % 256 for i in range(40)]
for tail in ([1, 2], [9]):
    outs = []
    for port in (8043, 8044):
        body = json.dumps({
            "prompt_tokens": sys_prompt + tail, "max_new_tokens": 12,
        }).encode()
        with urllib.request.urlopen(
            urllib.request.Request(
                f"http://localhost:{port}/generate", data=body
            ), timeout=120,
        ) as r:
            outs.append(json.load(r)["tokens"])
    assert outs[0] == outs[1], (tail, outs)
    print(f"tail {tail}: paged == fixed-lane ({outs[0][:6]}...)")
EOF

# 5. The triage screen: the metrics stream now carries paged
#    serve_step fields, so health_report prints the page/prefix line.
kill $(jobs -p) 2>/dev/null || true; wait 2>/dev/null || true
echo "--- health_report (pages line)"
python scripts/health_report.py "$WORK/serve.jsonl" | grep -E "serve|pages"

echo "example 22 OK"
