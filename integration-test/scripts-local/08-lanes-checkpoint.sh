#!/bin/sh
# Host lanes e2e (r5): a server with TPU_NUM_LANES=2 enforces limits
# at the wire, spreads keys over BOTH lane banks (visible in the
# per-bank live_keys gauges), and survives a kill -9 via per-lane
# checkpoints (bank0 + bank1 files, role-guarded).  Self-contained:
# own ports (2608x), own env.
set -e
cd "$(dirname "$0")/../.."
export JAX_PLATFORMS=cpu

if curl -s -o /dev/null http://localhost:26080/healthcheck; then
  echo "port 26080 already serving — stop the stale server first"
  exit 1
fi

CKPT=$(mktemp -d)
RL=$(mktemp -d)
mkdir -p "$RL/ratelimit/config"
cp examples/ratelimit/config/example.yaml "$RL/ratelimit/config/"
SPID=""
cleanup() {
  if [ -n "$SPID" ]; then
    kill -9 "$SPID" 2>/dev/null || true
    wait "$SPID" 2>/dev/null || true
  fi
  rm -rf "$CKPT" "$RL"
}
trap cleanup EXIT

start_server() {
  RUNTIME_ROOT="$RL" RUNTIME_SUBDIRECTORY=ratelimit \
    PORT=26080 GRPC_PORT=26081 DEBUG_PORT=26070 \
    TPU_NUM_SLOTS=65536 TPU_NUM_LANES=2 TPU_BATCH_WINDOW_US=200 \
    TPU_CHECKPOINT_DIR="$CKPT" TPU_CHECKPOINT_INTERVAL_S=1 \
    "${PY:-python}" -m ratelimit_tpu.runner >"$1" 2>&1 &
  SPID=$!
}
wait_up() {
  for i in $(seq 1 90); do
    curl -s -o /dev/null http://localhost:26080/healthcheck && return 0
    kill -0 "$SPID" 2>/dev/null || { echo "server died:"; tail -5 "$1"; exit 1; }
    sleep 1
  done
  echo "server never came up"; tail -5 "$1"; exit 1
}
fail() {
  echo "$1"; echo "--- server log tail:"; tail -20 "$2"; exit 1
}

start_server "$RL/gen1.log"; wait_up "$RL/gen1.log"

# Spread keys until both lane banks hold state.
for i in $(seq 1 24); do
  body='{"domain":"rl","descriptors":[{"entries":[{"key":"hourly","value":"lane'$i'"}]}]}'
  code=$(curl -s -o /dev/null -w "%{http_code}" -XPOST --data "$body" http://localhost:26080/json)
  [ "$code" = "200" ] || fail "spread call $i got $code" "$RL/gen1.log"
done
b0=$(curl -s http://localhost:26070/stats | grep "ratelimit.tpu.bank0.live_keys" | grep -o "[0-9]*$")
b1=$(curl -s http://localhost:26070/stats | grep "ratelimit.tpu.bank1.live_keys" | grep -o "[0-9]*$")
[ "${b0:-0}" -ge 1 ] && [ "${b1:-0}" -ge 1 ] || \
  fail "keys did not spread over both lanes (bank0=$b0 bank1=$b1)" "$RL/gen1.log"

# Wire-exact joint enforcement on one key (hourly = 2/hour).
body='{"domain":"rl","descriptors":[{"entries":[{"key":"hourly","value":"lanelimit"}]}]}'
for want in 200 200 429; do
  code=$(curl -s -o /dev/null -w "%{http_code}" -XPOST --data "$body" http://localhost:26080/json)
  [ "$code" = "$want" ] || fail "expected $want, got $code" "$RL/gen1.log"
done
echo ok-lanes

# Crash + restore: per-lane checkpoints bring BOTH banks back.
sleep 3  # >= one periodic checkpoint interval
kill -9 "$SPID"
wait "$SPID" 2>/dev/null || true
[ -f "$CKPT/bank0.npz" ] && [ -f "$CKPT/bank1.npz" ] || \
  fail "expected per-lane checkpoint files, got: $(ls "$CKPT")" "$RL/gen1.log"

start_server "$RL/gen2.log"; wait_up "$RL/gen2.log"
code=$(curl -s -o /dev/null -w "%{http_code}" -XPOST --data "$body" http://localhost:26080/json)
[ "$code" = "429" ] || fail "restarted lanes forgot the counter: got $code" "$RL/gen2.log"
echo ok-lanes-crash
