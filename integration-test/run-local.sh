#!/bin/sh
# Subprocess-level black-box e2e: launches the real server as a child
# process (`python -m ratelimit_tpu.runner` with the example config)
# and runs every scenario in scripts-local/ against live surfaces.
# 01-03 are the compose stack's scenarios (run-all.sh: happy path, 429
# after quota, shadow mode never blocks) minus the Envoy hop (no envoy
# binary here); 04-08 are local-only and launch their own server
# processes: 04 checkpoint/restart + kill-9 recovery, 05 multi-replica
# cluster (joint enforcement, live membership, SIGKILL failover),
# 06 sharded backend, 07 TLS+auth cluster hop, 08 host lanes +
# per-lane checkpoint recovery.
#
# Usage:  sh integration-test/run-local.sh     (or `make e2e-local`,
# which records the transcript in integration-test/results/).
set -e
cd "$(dirname "$0")/.."

PY="${PY:-python}"

echo "# local subprocess e2e | $(date -u +%Y-%m-%dT%H:%M:%SZ) | commit $(git rev-parse --short HEAD 2>/dev/null || echo unknown)"

# A stale server on 8080 would silently absorb the scenarios (and its
# half-consumed quotas would corrupt them): refuse to run.
if curl -s -o /dev/null http://localhost:8080/healthcheck; then
  echo "port 8080 already serving — stop the existing server first"
  exit 1
fi

RLROOT=$(mktemp -d)
mkdir -p "$RLROOT/ratelimit/config"
cp examples/ratelimit/config/example.yaml "$RLROOT/ratelimit/config/"

# CPU platform for the counter engine: these scenarios check wire
# behaviour, not the chip (chip_smoke.py does that, on the chip).
export JAX_PLATFORMS=cpu

RUNTIME_ROOT="$RLROOT" RUNTIME_SUBDIRECTORY=ratelimit \
  TPU_NUM_SLOTS=65536 TPU_BATCH_WINDOW_US=200 \
  "$PY" -m ratelimit_tpu.runner >"$RLROOT/server.log" 2>&1 &
SERVER_PID=$!
cleanup() {
  kill "$SERVER_PID" 2>/dev/null || true
  wait "$SERVER_PID" 2>/dev/null || true
  rm -rf "$RLROOT"
}
trap cleanup EXIT

echo "waiting for server (pid $SERVER_PID) ..."
up=0
for i in $(seq 1 120); do
  if ! kill -0 "$SERVER_PID" 2>/dev/null; then
    echo "server died during startup:"
    tail -20 "$RLROOT/server.log"
    exit 1
  fi
  if curl -s -o /dev/null http://localhost:8080/healthcheck; then
    up=1
    break
  fi
  sleep 1
done
[ "$up" = "1" ] || { echo "server never came up"; tail -20 "$RLROOT/server.log"; exit 1; }
echo "server is up"

for script in integration-test/scripts-local/*.sh; do
  echo "=== $script"
  if ! PY="$PY" sh "$script"; then
    echo "--- scenario failed; server log tail:"
    tail -30 "$RLROOT/server.log"
    exit 1
  fi
done
echo "ALL LOCAL E2E SCENARIOS PASSED"
