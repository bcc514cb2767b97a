# Developer entry points (reference Makefile analog: 3 binaries ->
# python -m entry points; test tiers; docker packaging).

PY ?= python
CPU_ENV = JAX_PLATFORMS=cpu XLA_FLAGS="--xla_force_host_platform_device_count=8"

.PHONY: all test lint sanitize native-asan sanitize-native bench chip-smoke bench-host perf-gate replay-smoke cluster-smoke chaos-smoke protos native serve check_config smoke_client metrics-smoke docker_image e2e e2e-local ci clean

# C++ hot-path library: slot table + decide kernel (auto-built on
# first import too; this forces it).  Goes through the Python builder
# so the content stamp is written — a bare g++ call would leave a
# stamp mismatch and the loader would just rebuild at import.
native:
	$(PY) -c "from ratelimit_tpu.backends import native_slot_table as n; \
	  import sys; sys.exit(0 if n._build() else 1)"

all: test

# Tier 1+2: unit + in-process integration (runs on an 8-device virtual
# CPU mesh; no TPU needed).
test:
	$(PY) -m pytest tests/ -q

# tpu-lint v2 static analysis: per-file rules (jax-host-sync,
# lock-discipline, env-discipline, dtype-discipline, ...) plus the
# whole-program passes (lock-order-cycle, blocking-under-lock,
# shared-state, dtype-pack-contract — docs/STATIC_ANALYSIS.md).
# Fails on any unsuppressed finding; pure stdlib, no jax needed.
lint:
	PY=$(PY) sh scripts/lint.sh

# Tier-1 under the runtime lock/atomicity sanitizer: every
# threading.Lock/RLock created by package code is wrapped to record
# REAL acquisition orders; lock-order cycles or blocking calls while
# holding a lock observed anywhere in the run fail the session
# (analysis/sanitizer.py, docs/STATIC_ANALYSIS.md).
sanitize:
	TPU_SANITIZE=1 $(PY) -m pytest tests/ -q

# ASan+UBSan side-path build of the native library (never touches
# the production .so or its content stamp).
native-asan:
	$(PY) scripts/sanitize_native.py --build-only

# Native differential suites + the seeded 10k-batch fuzzer against the
# instrumented library (scripts/sanitize_native.py; skips with a
# one-line reason when the toolchain is absent — never fails ci for
# a missing g++).
sanitize-native:
	$(PY) scripts/sanitize_native.py

# Kernel-layer scan + bit-exact digests; TPU only (exits non-zero
# anywhere else).  The served path is not measured by it.
bench:
	$(PY) bench.py

# Served-path bring-up proof on the chip: starts the real server,
# loads 1M keys over gRPC, checks decisions against the host oracle
# and that the device did the work.  One process per chip.
chip-smoke:
	$(PY) chip_smoke.py

# Host-path smoke: quick-mode profile_host_path.py asserting the
# descriptor-resolution cache reports a nonzero hit rate after warmup
# and the fast path stays engaged (no misses once warm) —
# docs/HOST_PATH.md.  Pure host work; no device step.
bench-host:
	$(CPU_ENV) $(PY) benchmarks/profile_host_path.py --quick

# Perf-regression ratchet: the committed benchmarks/results artifacts
# checked against the committed budgets (benchmarks/perf_budget.json)
# — hard ceilings plus a >25% creep check vs each metric's last
# baselined value.  After intentionally regenerating artifacts, run
# `python scripts/perf_gate.py --write-baseline` (ceilings are
# hand-edited only).  Pure stdlib, no jax needed.
perf-gate:
	$(PY) scripts/perf_gate.py --fail-on-new

# Overload-control smoke: replay the committed tiny flight ring
# (benchmarks/data/flight_ring_sample.jsonl) at forced overload
# through a live controller and assert shed counters move, shed-coded
# flight records land in the ring, and the p99 artifact rows are
# well-formed (benchmarks/replay.py; docs/OBSERVABILITY.md).
replay-smoke:
	$(CPU_ENV) $(PY) benchmarks/replay.py --smoke

# Elastic-cluster smoke: two in-process replicas behind the proxy's
# RouterHolder; kill one (ejection + failover), kill both (degraded
# CLUSTER_FAILURE_MODE answer), then join a third with counter
# handoff over the real /debug/cluster admin endpoints and assert the
# moved key's window did NOT restart (docs/MULTI_REPLICA.md).
cluster-smoke:
	$(CPU_ENV) $(PY) scripts/cluster_smoke.py

# Device-path chaos smoke: hang a bank's kernel launches under
# sustained replay load and assert the fault-domain envelope — bounded
# p99 (quarantine within one KERNEL_DEADLINE_S, no dispatch-timeout
# stall), fallback admissions per DEVICE_FAILURE_MODE, and a
# supervised warm restart that restores counters exactly (no window
# restart); the uncontrolled leg shows the stall this PR retires.
# Writes benchmarks/results/device_faults.json (docs/RESILIENCE.md).
chaos-smoke:
	$(CPU_ENV) $(PY) scripts/chaos_smoke.py

# Regenerate committed protobuf classes after editing protos/.
protos:
	sh scripts/gen_protos.sh

# Local dev server against the example config.
serve:
	RUNTIME_ROOT=examples RUNTIME_SUBDIRECTORY=ratelimit USE_STATSD=false \
	LOG_LEVEL=INFO $(PY) -m ratelimit_tpu.runner

# Offline config validation (reference config_check_cmd).
check_config:
	$(PY) -m ratelimit_tpu.cli.config_check --config_dir examples/ratelimit/config

# One smoke RPC against a running server (reference client_cmd).
smoke_client:
	$(PY) -m ratelimit_tpu.cli.client --dial_string localhost:8081 \
	  --domain rl --descriptors foo=bar

# Observability smoke: in-process server, one traced RPC, then assert
# /metrics (Prometheus text, cumulative phase buckets) and
# /debug/tracez (trace visible under the inbound traceparent id) are
# well-formed (docs/OBSERVABILITY.md).
metrics-smoke:
	$(CPU_ENV) $(PY) scripts/metrics_smoke.py

docker_image:
	docker build -t ratelimit-tpu:latest .

# Black-box e2e: compose stack (ratelimit + statsd-exporter + envoy),
# then the scripted scenarios (reference integration-test/ analog).
e2e:
	docker compose -f docker-compose-example.yml up --build -d
	sh integration-test/run-all.sh
	docker compose -f docker-compose-example.yml down

# Docker-less e2e: real server child process + the same scenarios
# against its live surfaces; transcript goes to integration-test/results/.
# (No tee: a pipeline would mask the suite's exit status under /bin/sh.)
e2e-local:
	PY=$(PY) sh integration-test/run-local.sh > integration-test/results/local-e2e.txt 2>&1 \
	  || { cat integration-test/results/local-e2e.txt; exit 1; }
	cat integration-test/results/local-e2e.txt

# The full CI recipe (.github/workflows/ci.yaml runs a subset): native
# build, tests, offline config validation, black-box e2e — all on the
# CPU platform.  Nothing here touches a chip; `make chip-smoke` and
# `make bench` do, and only there.
ci: lint perf-gate native test sanitize sanitize-native check_config metrics-smoke bench-host replay-smoke cluster-smoke chaos-smoke e2e-local

clean:
	find . -name __pycache__ -type d -prune -exec rm -rf {} \;
