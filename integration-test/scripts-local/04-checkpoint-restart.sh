#!/bin/sh
# Checkpoint/restart (black-box, aux-subsystem e2e): counters survive
# a graceful restart via TPU_CHECKPOINT_DIR — the durability the
# reference delegates to Redis persistence.  Unlike siblings 01-03
# (pure curl against the harness's server), this scenario launches its
# own two server generations on alternate ports (1808x) with a shared
# checkpoint dir, so it sets the platform env itself and can run
# standalone from the repo root.
set -e
cd "$(dirname "$0")/../.."
export JAX_PLATFORMS=cpu

# A stale server on 18080 (e.g. a SIGKILLed prior run — EXIT traps do
# not fire on untrapped signals) would absorb the scenario with old
# quotas: refuse to run, same guard as run-local.sh's 8080 check.
if curl -s -o /dev/null http://localhost:18080/healthcheck; then
  echo "port 18080 already serving — stop the stale server first"
  exit 1
fi

CKPT=$(mktemp -d)
RL=$(mktemp -d)
mkdir -p "$RL/ratelimit/config"
cp examples/ratelimit/config/example.yaml "$RL/ratelimit/config/"
SPID=""
cleanup() {
  # kill, then WAIT: the graceful-shutdown checkpoint must finish
  # writing before the directories are removed.
  if [ -n "$SPID" ]; then
    kill "$SPID" 2>/dev/null || true
    wait "$SPID" 2>/dev/null || true
  fi
  rm -rf "$CKPT" "$RL"
}
trap cleanup EXIT

start_server() {
  RUNTIME_ROOT="$RL" RUNTIME_SUBDIRECTORY=ratelimit \
    PORT=18080 GRPC_PORT=18081 DEBUG_PORT=16070 \
    TPU_NUM_SLOTS=65536 TPU_BATCH_WINDOW_US=200 \
    TPU_CHECKPOINT_DIR="$CKPT" TPU_CHECKPOINT_INTERVAL_S=30 \
    "${PY:-python}" -m ratelimit_tpu.runner >"$1" 2>&1 &
  SPID=$!
}
wait_up() {
  for i in $(seq 1 90); do
    curl -s -o /dev/null http://localhost:18080/healthcheck && return 0
    kill -0 "$SPID" 2>/dev/null || { echo "server died:"; tail -5 "$1"; exit 1; }
    sleep 1
  done
  echo "server never came up"; tail -5 "$1"; exit 1
}
fail() {  # fail <msg> <log>: keep the evidence before the trap wipes it
  echo "$1"
  echo "--- server log tail:"
  tail -20 "$2"
  exit 1
}

body='{"domain":"rl","descriptors":[{"entries":[{"key":"hourly","value":"restart"}]}]}'
start_server "$RL/gen1.log"; wait_up "$RL/gen1.log"
for want in 200 200 429; do
  code=$(curl -s -o /dev/null -w "%{http_code}" -XPOST --data "$body" http://localhost:18080/json)
  [ "$code" = "$want" ] || fail "gen1 expected $want, got $code" "$RL/gen1.log"
done

kill -TERM "$SPID"
wait "$SPID" 2>/dev/null || true
[ -n "$(ls -A "$CKPT")" ] || fail "no checkpoint written on shutdown" "$RL/gen1.log"

start_server "$RL/gen2.log"; wait_up "$RL/gen2.log"
code=$(curl -s -o /dev/null -w "%{http_code}" -XPOST --data "$body" http://localhost:18080/json)
[ "$code" = "429" ] || fail "restarted server forgot the counter: got $code" "$RL/gen2.log"
echo ok

# Phase 1's gen2 is still running and the EXIT trap is about to be
# replaced: stop it explicitly and wait for the ports to quiesce (the
# gRPC listener uses SO_REUSEPORT, so a lingering old server would
# otherwise share the port with phase 2's and absorb its traffic).
kill -TERM "$SPID"
wait "$SPID" 2>/dev/null || true
SPID=""
for i in $(seq 1 30); do
  curl -s -o /dev/null http://localhost:18080/healthcheck || break
  sleep 1
done

# --- phase 2: CRASH recovery (kill -9, restore from the periodic
# checkpoint instead of the graceful-shutdown one) ---
CKPT2=$(mktemp -d)
RL2=$(mktemp -d)
mkdir -p "$RL2/ratelimit/config"
cp examples/ratelimit/config/example.yaml "$RL2/ratelimit/config/"
cleanup2() {
  if [ -n "$SPID" ]; then
    kill -9 "$SPID" 2>/dev/null || true
    wait "$SPID" 2>/dev/null || true
  fi
  rm -rf "$CKPT2" "$RL2" "$CKPT" "$RL"
}
trap cleanup2 EXIT

start_server2() {
  RUNTIME_ROOT="$RL2" RUNTIME_SUBDIRECTORY=ratelimit \
    PORT=18080 GRPC_PORT=18081 DEBUG_PORT=16070 \
    TPU_NUM_SLOTS=65536 TPU_BATCH_WINDOW_US=200 \
    TPU_CHECKPOINT_DIR="$CKPT2" TPU_CHECKPOINT_INTERVAL_S=1 \
    "${PY:-python}" -m ratelimit_tpu.runner >"$1" 2>&1 &
  SPID=$!
}

body='{"domain":"rl","descriptors":[{"entries":[{"key":"hourly","value":"crash"}]}]}'
start_server2 "$RL2/gen1.log"; wait_up "$RL2/gen1.log"
for want in 200 200 429; do
  code=$(curl -s -o /dev/null -w "%{http_code}" -XPOST --data "$body" http://localhost:18080/json)
  [ "$code" = "$want" ] || fail "crash-gen1 expected $want, got $code" "$RL2/gen1.log"
done
sleep 3  # >= one periodic checkpoint interval after the hits landed
kill -9 "$SPID"   # hard crash: no graceful final checkpoint
wait "$SPID" 2>/dev/null || true
[ -n "$(ls -A "$CKPT2")" ] || fail "no periodic checkpoint on disk" "$RL2/gen1.log"

start_server2 "$RL2/gen2.log"; wait_up "$RL2/gen2.log"
code=$(curl -s -o /dev/null -w "%{http_code}" -XPOST --data "$body" http://localhost:18080/json)
[ "$code" = "429" ] || fail "crash-restarted server forgot the counter: got $code" "$RL2/gen2.log"
echo ok-crash
