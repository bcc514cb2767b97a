#!/bin/sh
# Multi-replica joint enforcement (black-box): two replica server
# processes + the stateless rendezvous front proxy; a 2/minute key
# through the proxy is jointly enforced (docs/MULTI_REPLICA.md), and
# the same key hits exactly one replica's counter.  Self-contained
# like 04: own ports (1908x/19090), own env.
set -e
cd "$(dirname "$0")/../.."
export JAX_PLATFORMS=cpu

# Stale-process guards: HTTP healthchecks for the replicas, raw TCP
# probes for the gRPC-only ports (curl's HTTP probe cannot see a
# stale gRPC listener) — a SIGKILLed prior run leaves all of them.
for port in 19080 29080; do
  if curl -s -o /dev/null "http://localhost:$port/healthcheck"; then
    echo "port $port already serving — stop the stale server first"
    exit 1
  fi
done
for port in 19081 29081 19090; do
  if "${PY:-python}" -c "import socket,sys; s=socket.socket(); s.settimeout(0.5); sys.exit(0 if s.connect_ex(('127.0.0.1',$port))==0 else 1)"; then
    echo "gRPC port $port already bound — stop the stale process first"
    exit 1
  fi
done

RL=$(mktemp -d)
mkdir -p "$RL/r1/ratelimit/config" "$RL/r2/ratelimit/config"
cp examples/ratelimit/config/example.yaml "$RL/r1/ratelimit/config/"
cp examples/ratelimit/config/example.yaml "$RL/r2/ratelimit/config/"
PIDS=""
cleanup() {
  for p in $PIDS; do kill "$p" 2>/dev/null || true; done
  for p in $PIDS; do wait "$p" 2>/dev/null || true; done
  rm -rf "$RL"
}
trap cleanup EXIT

RUNTIME_ROOT="$RL/r1" RUNTIME_SUBDIRECTORY=ratelimit \
  PORT=19080 GRPC_PORT=19081 DEBUG_PORT=19070 TPU_NUM_SLOTS=65536 \
  "${PY:-python}" -m ratelimit_tpu.runner >"$RL/r1.log" 2>&1 &
PIDS="$PIDS $!"
RUNTIME_ROOT="$RL/r2" RUNTIME_SUBDIRECTORY=ratelimit \
  PORT=29080 GRPC_PORT=29081 DEBUG_PORT=29070 TPU_NUM_SLOTS=65536 \
  "${PY:-python}" -m ratelimit_tpu.runner >"$RL/r2.log" 2>&1 &
PIDS="$PIDS $!"

up=0
for i in $(seq 1 90); do
  for p in $PIDS; do
    kill -0 "$p" 2>/dev/null || {
      echo "a replica died during startup:"
      tail -5 "$RL/r1.log" "$RL/r2.log"
      exit 1
    }
  done
  if curl -s -o /dev/null http://localhost:19080/healthcheck \
    && curl -s -o /dev/null http://localhost:29080/healthcheck; then
    up=1
    break
  fi
  sleep 1
done
[ "$up" = "1" ] || { echo "replicas never came up"; tail -5 "$RL/r1.log" "$RL/r2.log"; exit 1; }

"${PY:-python}" -m ratelimit_tpu.cluster.proxy \
  --replicas 127.0.0.1:19081,127.0.0.1:29081 \
  --host 127.0.0.1 --port 19090 >"$RL/proxy.log" 2>&1 &
PROXY_PID=$!
PIDS="$PIDS $PROXY_PID"
# Poll the proxy's gRPC port (no fixed sleep; bind failures die fast).
up=0
for i in $(seq 1 30); do
  kill -0 "$PROXY_PID" 2>/dev/null || { echo "proxy died:"; tail -5 "$RL/proxy.log"; exit 1; }
  if "${PY:-python}" -c "import socket,sys; s=socket.socket(); s.settimeout(0.5); sys.exit(0 if s.connect_ex(('127.0.0.1',19090))==0 else 1)"; then
    up=1
    break
  fi
  sleep 1
done
[ "$up" = "1" ] || { echo "proxy never bound 19090"; tail -5 "$RL/proxy.log"; exit 1; }

# foo is 2/minute: through the proxy, call 3 must be OVER_LIMIT even
# though two replicas each hold a full quota locally.
out=""
for i in 1 2 3; do
  code=$("${PY:-python}" -m ratelimit_tpu.cli.client \
    --dial_string 127.0.0.1:19090 --domain rl --descriptors foo=proxye2e \
    2>/dev/null | grep -c "overall_code: OVER_LIMIT" || true)
  out="$out $code"
done
[ "$out" = " 0 0 1" ] || { echo "expected joint 2/min enforcement, got:$out"; tail -5 "$RL/proxy.log"; exit 1; }

# Single ownership: exactly one replica rejects the key directly.
over=0
for addr in 127.0.0.1:19081 127.0.0.1:29081; do
  c=$("${PY:-python}" -m ratelimit_tpu.cli.client \
    --dial_string "$addr" --domain rl --descriptors foo=proxye2e \
    2>/dev/null | grep -c "overall_code: OVER_LIMIT" || true)
  over=$((over + c))
done
[ "$over" = "1" ] || { echo "expected the counter on exactly one replica, got $over"; exit 1; }
echo ok

# --- phase 2: LIVE membership growth (--replicas-file) ---
# A third replica joins by appending to the watched file; the proxy
# swaps membership without restarting, and traffic keeps flowing.
RUNTIME_ROOT="$RL/r1" RUNTIME_SUBDIRECTORY=ratelimit \
  PORT=39080 GRPC_PORT=39081 DEBUG_PORT=39070 TPU_NUM_SLOTS=65536 \
  "${PY:-python}" -m ratelimit_tpu.runner >"$RL/r3.log" 2>&1 &
PIDS="$PIDS $!"
for i in $(seq 1 90); do
  curl -s -o /dev/null http://localhost:39080/healthcheck && break
  sleep 1
done

printf '127.0.0.1:19081\n127.0.0.1:29081\n' > "$RL/replicas.txt"
"${PY:-python}" -m ratelimit_tpu.cluster.proxy \
  --replicas-file "$RL/replicas.txt" --poll-seconds 0.5 \
  --host 127.0.0.1 --port 29090 --debug-port 29091 >"$RL/proxy2.log" 2>&1 &
PIDS="$PIDS $!"
for i in $(seq 1 30); do
  "${PY:-python}" -c "import socket,sys; s=socket.socket(); s.settimeout(0.5); sys.exit(0 if s.connect_ex(('127.0.0.1',29090))==0 else 1)" && break
  sleep 1
done

# Traffic flows on the initial 2-replica membership.
c=$("${PY:-python}" -m ratelimit_tpu.cli.client \
  --dial_string 127.0.0.1:29090 --domain rl --descriptors foo=member1 \
  2>/dev/null | grep -c "overall_code: OK" || true)
[ "$c" = "1" ] || { echo "proxy not serving before growth"; tail -5 "$RL/proxy2.log"; exit 1; }

# Grow membership atomically (write-temp + rename) and wait for the
# watcher to log the swap.
printf '127.0.0.1:19081\n127.0.0.1:29081\n127.0.0.1:39081\n' > "$RL/replicas.txt.tmp"
mv "$RL/replicas.txt.tmp" "$RL/replicas.txt"
grew=0
for i in $(seq 1 20); do
  if grep -q "cluster membership now 3 replicas" "$RL/proxy2.log"; then
    grew=1
    break
  fi
  sleep 1
done
[ "$grew" = "1" ] || { echo "membership growth never observed"; tail -5 "$RL/proxy2.log"; exit 1; }

# Traffic still flows after the swap, and across many keys at least
# one routes to the NEW replica (its counter appears on r3).
for i in $(seq 1 30); do
  "${PY:-python}" -m ratelimit_tpu.cli.client \
    --dial_string 127.0.0.1:29090 --domain rl --descriptors "foo=grown$i" \
    >/dev/null 2>&1 || { echo "proxy broke after membership swap"; exit 1; }
done
r3_keys=$(curl -s http://localhost:39070/stats | grep "ratelimit.tpu.bank0.live_keys" | grep -o "[0-9]*$")
[ "$r3_keys" -ge 1 ] 2>/dev/null || { echo "new replica never received a key (live_keys=$r3_keys)"; exit 1; }
echo ok-membership

# --- phase 3: replica failover (r4 VERDICT next #5) ---
# SIGKILL one of the three replicas: the proxy must keep serving ALL
# keys — descriptors owned by the dead replica re-own to survivors
# (their windows restart: the documented amnesia envelope), and the
# proxy ejects it after consecutive connection failures.
R3_PID=""
for p in $PIDS; do
  if [ -d "/proc/$p" ] && grep -q "GRPC_PORT=39081" "/proc/$p/environ" 2>/dev/null; then
    R3_PID=$p
  fi
done
# Fallback: match by port listener via environ is linux-only; if not
# found, pick the runner started last (r3 was the most recent runner).
if [ -z "$R3_PID" ]; then
  for p in $PIDS; do
    if ps -o cmd= -p "$p" 2>/dev/null | grep -q "ratelimit_tpu.runner"; then
      R3_PID=$p  # last runner pid wins
    fi
  done
fi
[ -n "$R3_PID" ] || { echo "could not locate r3 pid"; exit 1; }
kill -9 "$R3_PID"

# Every key keeps answering through the proxy (survivors absorb the
# dead replica's keyspace; the first hits on a dead owner fail over
# transparently inside one request).
fails=0
for i in $(seq 1 30); do
  "${PY:-python}" -m ratelimit_tpu.cli.client \
    --dial_string 127.0.0.1:29090 --domain rl --descriptors "foo=failover$i" \
    >/dev/null 2>&1 || fails=$((fails + 1))
done
[ "$fails" = "0" ] || { echo "$fails/30 requests failed after replica kill"; tail -8 "$RL/proxy2.log"; exit 1; }

# The proxy observed the death and ejected the replica.
ejected=0
for i in $(seq 1 10); do
  if grep -q "ejected after" "$RL/proxy2.log"; then ejected=1; break; fi
  sleep 1
done
[ "$ejected" = "1" ] || { echo "dead replica never ejected"; tail -8 "$RL/proxy2.log"; exit 1; }
echo ok-failover

# The proxy's debug listener reflects the failover: ejections counted,
# live membership shrunk to 2 of 3.
snap=$(curl -s http://127.0.0.1:29091/stats.json)
echo "$snap" | grep -q '"ejections": 1' || { echo "debug stats missing ejection: $snap"; exit 1; }
echo "$snap" | grep -q '"live_replicas": 2' || { echo "debug stats wrong liveness: $snap"; exit 1; }
curl -s -o /dev/null -w "%{http_code}" http://127.0.0.1:29091/healthcheck | grep -q 200 \
  || { echo "proxy debug healthcheck not 200"; exit 1; }
echo ok-debug-port
