#!/usr/bin/env bash
# Elastic-reconnect smoke test (role of reference test/reconnect.sh): start two
# nodes with crossed UDP discovery ports, kill node 2, restart it, verify both
# re-converge via the logs. A CPU ring drill, like failover_drill.sh and
# ring_budget_drill.sh: two daemons are two processes, and a chip belongs to
# one process at a time — on a chip host the second would never get the device.
set -euo pipefail
cd "$(dirname "$0")/.."
export DEBUG_DISCOVERY=1 JAX_PLATFORMS=cpu
python -m xotorch_support_jetson_tpu.main --node-id node1 --listen-port 5678 --broadcast-port 5679 --disable-tui --chatgpt-api-port 52415 &
N1=$!
python -m xotorch_support_jetson_tpu.main --node-id node2 --listen-port 5679 --broadcast-port 5678 --disable-tui --chatgpt-api-port 52416 &
N2=$!
sleep 8
echo "--- killing node2 ---"
kill $N2; sleep 8
echo "--- restarting node2 ---"
python -m xotorch_support_jetson_tpu.main --node-id node2 --listen-port 5679 --broadcast-port 5678 --disable-tui --chatgpt-api-port 52416 &
N2=$!
sleep 8
curl -s localhost:52415/v1/topology | python -m json.tool
kill $N1 $N2 2>/dev/null || true
