#!/bin/sh
# Black-box sharded backend: a real server process running
# BACKEND_TYPE=tpu-sharded over an 8-device virtual CPU mesh (the
# reference's cluster-topology analog, Makefile:74-102) serves the
# same wire contract — 429 after quota, live per-bank gauges on the
# debug port.  Self-contained like 04/05: own ports (4908x), own env.
set -e
cd "$(dirname "$0")/../.."
export JAX_PLATFORMS=cpu
export XLA_FLAGS="--xla_force_host_platform_device_count=8"

if curl -s -o /dev/null "http://localhost:49080/healthcheck"; then
  echo "port 49080 already serving — stop the stale server first"
  exit 1
fi

RL=$(mktemp -d)
mkdir -p "$RL/ratelimit/config"
cp examples/ratelimit/config/example.yaml "$RL/ratelimit/config/"
cleanup() {
  kill "$PID" 2>/dev/null || true
  wait "$PID" 2>/dev/null || true
  rm -rf "$RL"
}
trap cleanup EXIT

RUNTIME_ROOT="$RL" RUNTIME_SUBDIRECTORY=ratelimit \
  BACKEND_TYPE=tpu-sharded TPU_NUM_SLOTS=65536 TPU_BATCH_WINDOW_US=200 \
  PORT=49080 GRPC_PORT=49081 DEBUG_PORT=49070 \
  "${PY:-python}" -m ratelimit_tpu.runner >"$RL/server.log" 2>&1 &
PID=$!

up=0
for i in $(seq 1 120); do
  kill -0 "$PID" 2>/dev/null || {
    echo "sharded server died during startup:"; tail -8 "$RL/server.log"; exit 1
  }
  if curl -s -o /dev/null http://localhost:49080/healthcheck; then
    up=1; break
  fi
  sleep 1
done
[ "$up" = "1" ] || { echo "sharded server never came up"; tail -8 "$RL/server.log"; exit 1; }

# foo is 2/minute: wire-exact joint enforcement on the mesh backend.
out=""
for i in 1 2 3; do
  code=$(printf '{"domain":"rl","descriptors":[{"entries":[{"key":"foo","value":"shmesh"}]}]}' | \
    curl -s -o /dev/null -w "%{http_code}" -XPOST --data @/dev/stdin http://localhost:49080/json)
  out="$out $code"
done
[ "$out" = " 200 200 429" ] || { echo "expected 200 200 429 on the sharded backend, got:$out"; tail -8 "$RL/server.log"; exit 1; }

# The bank gauges are live and the counter landed on the mesh table.
live=$(curl -s http://localhost:49070/stats | grep "ratelimit.tpu.bank0.live_keys" | grep -o "[0-9]*$")
[ "$live" -ge 1 ] 2>/dev/null || { echo "sharded bank gauge not live (live_keys=$live)"; exit 1; }
echo ok-sharded
