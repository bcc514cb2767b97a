"""In-process integration: full Runner + real gRPC/HTTP clients.

Model: reference test/integration/integration_test.go — the service is
started in-process via the runner and exercised over real connections
(:600-620, :371-598); config reload is tested by writing a YAML into
the watched dir (:622-711).  Runs against the real TPU backend path
(counter engine + micro-batching dispatcher) on the CPU mesh.
"""

import json
import os
import urllib.request

import grpc
import pytest

from ratelimit_tpu.runner import Runner
from ratelimit_tpu.settings import Settings
from ratelimit_tpu.utils.time import PinnedTimeSource

from ratelimit_tpu.server import pb  # noqa: F401  (sys.path for generated)
from envoy.service.ratelimit.v3 import rls_pb2  # noqa: E402
from grpchealth.v1 import health_pb2  # noqa: E402

BASIC_YAML = """
domain: basic
descriptors:
  - key: key1
    rate_limit:
      unit: minute
      requests_per_unit: 5
  - key: one_per_minute
    value: something
    rate_limit:
      unit: minute
      requests_per_unit: 1
"""


@pytest.fixture(scope="module")
def runner(tmp_path_factory):
    root = tmp_path_factory.mktemp("runtime")
    config_dir = root / "ratelimit" / "config"
    config_dir.mkdir(parents=True)
    (config_dir / "basic.yaml").write_text(BASIC_YAML)

    settings = Settings(
        host="127.0.0.1",
        port=0,
        grpc_host="127.0.0.1",
        grpc_port=0,
        debug_host="127.0.0.1",
        debug_port=0,
        use_statsd=False,
        backend_type="tpu",
        tpu_num_slots=1 << 12,
        tpu_batch_window_us=200,
        tpu_batch_buckets=[8, 32],
        runtime_path=str(root),
        runtime_subdirectory="ratelimit",
        local_cache_size_in_bytes=0,
        expiration_jitter_max_seconds=0,
        # Open the capture endpoints for the introspection test; the
        # default-closed gate is covered by
        # test_profiling_capture_endpoints_are_gated.
        debug_profiling=True,
    )
    # Pinned clock through the Runner seam: window-progression
    # assertions can't straddle a real second/minute rollover
    # (reference MockClock, test/service/ratelimit_test.go:72-76).
    r = Runner(settings, time_source=PinnedTimeSource(1_000_000))
    r.start()
    yield r
    r.stop()


def _grpc_call(runner, request_pb, metadata=None):
    with grpc.insecure_channel(
        f"127.0.0.1:{runner.grpc_server.bound_port}"
    ) as channel:
        method = channel.unary_unary(
            "/envoy.service.ratelimit.v3.RateLimitService/ShouldRateLimit",
            request_serializer=rls_pb2.RateLimitRequest.SerializeToString,
            response_deserializer=rls_pb2.RateLimitResponse.FromString,
        )
        return method(request_pb, timeout=30, metadata=metadata)


def _request(domain, entries, hits=0):
    req = rls_pb2.RateLimitRequest(domain=domain, hits_addend=hits)
    d = req.descriptors.add()
    for k, v in entries:
        e = d.entries.add()
        e.key, e.value = k, v
    return req


def _http(runner, path, body=None, port=None):
    port = port or runner.http_server.bound_port
    url = f"http://127.0.0.1:{port}{path}"
    req = urllib.request.Request(url, data=body)
    try:
        with urllib.request.urlopen(req, timeout=30) as resp:
            return resp.status, resp.read()
    except urllib.error.HTTPError as e:
        return e.code, e.read()


def test_grpc_over_limit_progression(runner):
    """5/min limit: calls 1-5 OK, 6+ OVER_LIMIT (reference
    integration_test.go over-limit loop :436-496)."""
    codes = []
    remaining = []
    for _ in range(7):
        resp = _grpc_call(runner, _request("basic", [("key1", "foo")]))
        codes.append(resp.overall_code)
        remaining.append(resp.statuses[0].limit_remaining)
    OK = rls_pb2.RateLimitResponse.OK
    OVER = rls_pb2.RateLimitResponse.OVER_LIMIT
    assert codes == [OK] * 5 + [OVER] * 2
    assert remaining[:5] == [4, 3, 2, 1, 0]
    assert remaining[5:] == [0, 0]
    # DescriptorStatus details (integration_test.go:406-433).
    resp = _grpc_call(runner, _request("basic", [("key1", "foo")]))
    st = resp.statuses[0]
    assert st.current_limit.requests_per_unit == 5
    assert st.current_limit.unit == rls_pb2.RateLimitResponse.RateLimit.MINUTE
    assert 0 < st.duration_until_reset.seconds <= 60


def test_grpc_unknown_descriptor_is_ok(runner):
    resp = _grpc_call(runner, _request("basic", [("nosuch", "x")]))
    assert resp.overall_code == rls_pb2.RateLimitResponse.OK
    assert resp.statuses[0].current_limit.requests_per_unit == 0


def test_grpc_empty_domain_errors(runner):
    with pytest.raises(grpc.RpcError) as err:
        _grpc_call(runner, _request("", [("key1", "foo")]))
    assert err.value.code() == grpc.StatusCode.UNKNOWN
    assert "domain must not be empty" in err.value.details()


def test_json_endpoint_maps_status_codes(runner):
    """OK->200, OVER_LIMIT->429 (server_impl.go:102-106); bad body->400
    (server_impl.go:76-82; test model server_impl_test.go:44-85)."""
    body = json.dumps(
        {
            "domain": "basic",
            "descriptors": [
                {"entries": [{"key": "one_per_minute", "value": "something"}]}
            ],
        }
    ).encode()
    status, out = _http(runner, "/json", body)
    assert status == 200
    parsed = json.loads(out)
    assert parsed["overallCode"] == "OK"

    status, out = _http(runner, "/json", body)
    assert status == 429
    assert json.loads(out)["overallCode"] == "OVER_LIMIT"

    status, _ = _http(runner, "/json", b"not json {")
    assert status == 400


def test_healthcheck_and_grpc_health(runner):
    status, out = _http(runner, "/healthcheck")
    assert (status, out) == (200, b"OK")

    with grpc.insecure_channel(
        f"127.0.0.1:{runner.grpc_server.bound_port}"
    ) as channel:
        check = channel.unary_unary(
            "/grpc.health.v1.Health/Check",
            request_serializer=health_pb2.HealthCheckRequest.SerializeToString,
            response_deserializer=health_pb2.HealthCheckResponse.FromString,
        )
        resp = check(health_pb2.HealthCheckRequest(), timeout=10)
    assert resp.status == health_pb2.HealthCheckResponse.SERVING

    runner.health.fail()
    try:
        status, out = _http(runner, "/healthcheck")
        assert status == 500
    finally:
        runner.health.ok()


def test_debug_endpoints(runner):
    status, out = _http(runner, "/stats", port=runner.debug_server.bound_port)
    assert status == 200
    text = out.decode()
    assert "ratelimit.service.config_load_success" in text
    assert "ratelimit_server.ShouldRateLimit.total_requests" in text

    status, out = _http(runner, "/rlconfig", port=runner.debug_server.bound_port)
    assert status == 200
    assert "basic" in out.decode()


def test_config_hot_reload(runner):
    """Write a new config file into the watched dir; the watcher picks
    it up (integration_test.go:622-711, deterministically via
    force_update)."""
    config_dir = os.path.join(runner.runtime.root, "config")
    with open(os.path.join(config_dir, "reloaded.yaml"), "w") as f:
        f.write(
            "domain: reloaded\n"
            "descriptors:\n"
            "  - key: newkey\n"
            "    rate_limit:\n"
            "      unit: hour\n"
            "      requests_per_unit: 2\n"
        )
    assert runner.runtime.force_update()
    resp = _grpc_call(runner, _request("reloaded", [("newkey", "v")]))
    assert resp.statuses[0].current_limit.requests_per_unit == 2


def test_runner_wires_settings_reloader(runner):
    """ADVICE r1 (low): the Runner must hand RateLimitService a
    settings reloader so SHADOW_MODE / header env flips are re-read on
    every config reload (reference ratelimit.go:77-89)."""
    assert runner.service._settings_reloader is not None
    s = runner.service._settings_reloader()
    assert hasattr(s, "global_shadow_mode")


def test_backend_death_flips_health_and_fast_fails(tmp_path):
    """VERDICT r1 #5: kill the collector thread; /healthcheck must go
    500 and RPCs must error fast (no dispatch-timeout burn) — the
    Redis active-connection health analog (driver_impl.go:31-52).
    KERNEL_DEADLINE_S=0 pins the PRE-fault-domain envelope: with the
    fault domain on (the runner default) a dead collector degrades
    and serves via the fallback instead — see
    test_backend_death_degrades_but_keeps_serving."""
    import time as _time

    root = tmp_path / "runtime"
    config_dir = root / "ratelimit" / "config"
    config_dir.mkdir(parents=True)
    (config_dir / "basic.yaml").write_text(BASIC_YAML)
    settings = Settings(
        host="127.0.0.1", port=0, grpc_host="127.0.0.1", grpc_port=0,
        debug_host="127.0.0.1", debug_port=0, use_statsd=False,
        backend_type="tpu", tpu_num_slots=1 << 10,
        tpu_batch_window_us=200, tpu_batch_buckets=[8],
        tpu_dispatch_timeout_s=30.0,
        kernel_deadline_s=0.0,  # fault domain OFF: legacy envelope
        runtime_path=str(root), runtime_subdirectory="ratelimit",
        local_cache_size_in_bytes=0, expiration_jitter_max_seconds=0,
    )
    r = Runner(settings)
    r.start()
    try:
        # Alive: healthcheck 200, RPC answers.
        assert _http(r, "/healthcheck")[0] == 200
        resp = _grpc_call(r, _request("basic", [("key1", "x")]))
        assert resp.overall_code == rls_pb2.RateLimitResponse.OK

        # Kill the collector with a poison queue entry.
        d = next(iter(r.cache._dispatchers.values()))
        with d._buf_cv:
            d._buf.append(object())
            d._buf_cv.notify()
        deadline = _time.monotonic() + 5
        while d.dead is None and _time.monotonic() < deadline:
            _time.sleep(0.01)
        assert d.dead is not None

        assert _http(r, "/healthcheck")[0] == 500

        t0 = _time.monotonic()
        with pytest.raises(grpc.RpcError) as exc_info:
            _grpc_call(r, _request("basic", [("key1", "x")]))
        assert _time.monotonic() - t0 < 5.0  # fast, not the 30s timeout
        assert exc_info.value.code() == grpc.StatusCode.UNKNOWN
    finally:
        r.stop()


def test_backend_death_degrades_but_keeps_serving(tmp_path):
    """The PR 10 envelope (docs/RESILIENCE.md): with the fault domain
    on (the runner default), a dead collector quarantines its bank —
    /healthcheck stays 200 (degraded: the fallback is answering), the
    RPC still gets a decision, and /debug/faults reports the
    quarantine."""
    import json as _json
    import time as _time

    root = tmp_path / "runtime"
    config_dir = root / "ratelimit" / "config"
    config_dir.mkdir(parents=True)
    (config_dir / "basic.yaml").write_text(BASIC_YAML)
    settings = Settings(
        host="127.0.0.1", port=0, grpc_host="127.0.0.1", grpc_port=0,
        debug_host="127.0.0.1", debug_port=0, use_statsd=False,
        backend_type="tpu", tpu_num_slots=1 << 10,
        tpu_batch_window_us=200, tpu_batch_buckets=[8],
        tpu_dispatch_timeout_s=30.0,
        kernel_deadline_s=0.25, device_failure_mode="host",
        # No restart during the test window: the quarantined state is
        # what's being asserted.
        device_restart_backoff_s=60.0,
        runtime_path=str(root), runtime_subdirectory="ratelimit",
        local_cache_size_in_bytes=0, expiration_jitter_max_seconds=0,
    )
    r = Runner(settings)
    r.start()
    try:
        assert _http(r, "/healthcheck")[0] == 200
        resp = _grpc_call(r, _request("basic", [("key1", "x")]))
        assert resp.overall_code == rls_pb2.RateLimitResponse.OK

        d = next(iter(r.cache._dispatchers.values()))
        with d._buf_cv:
            d._buf.append(object())
            d._buf_cv.notify()
        deadline = _time.monotonic() + 5
        while d.dead is None and _time.monotonic() < deadline:
            _time.sleep(0.01)
        assert d.dead is not None

        # RPCs keep answering (host fallback), fast.
        t0 = _time.monotonic()
        resp = _grpc_call(r, _request("basic", [("key1", "x")]))
        assert _time.monotonic() - t0 < 5.0
        assert resp.overall_code == rls_pb2.RateLimitResponse.OK

        # Health: serving but degraded.
        status, body = _http(r, "/healthcheck")
        assert status == 200
        assert b"degraded" in body

        # /debug/faults reports the quarantine.
        status, body = _http(
            r, "/debug/faults", port=r.debug_server.bound_port
        )
        assert status == 200
        faults = _json.loads(body)
        assert faults["quarantined_banks"] == 1
        assert faults["banks"][0]["state"] == "quarantined"
    finally:
        r.stop()


def test_debug_introspection_endpoints(runner):
    """Live introspection (VERDICT r2 #7; reference pprof analog,
    server_impl.go:238-269): threadz shows real threads, the sampling
    profiler returns a profile, the xla_trace capture writes a real
    trace while a serving batch runs."""
    port = runner.debug_server.bound_port

    status, out = _http(runner, "/debug/pprof/", port=port)
    assert status == 200 and b"/debug/threadz" in out

    status, out = _http(runner, "/debug/threadz", port=port)
    assert status == 200
    text = out.decode()
    # The dispatcher (collector) thread and this test thread both show.
    assert "tpu-dispatcher" in text
    assert "MainThread" in text or "threadz" in text

    status, out = _http(
        runner, "/debug/profile?seconds=0.3&hz=50", port=port
    )
    assert status == 200
    assert b"statistical cpu profile" in out

    # Capture a trace WHILE a serving batch flows through the engine.
    import threading as _threading

    traffic_statuses = []

    def traffic():
        body = json.dumps(
            {
                "domain": "basic",
                "descriptors": [
                    {"entries": [{"key": "key1", "value": "traced"}]}
                ],
            }
        ).encode()
        for _ in range(5):
            s, _ = _http(runner, "/json", body)
            traffic_statuses.append(s)

    t = _threading.Thread(target=traffic)
    t.start()
    status, out = _http(runner, "/debug/xla_trace?seconds=0.5", port=port)
    t.join()
    assert status == 200, out
    # The capture genuinely overlapped served batches (a silently
    # failing traffic thread would make this a trace of idleness).
    assert traffic_statuses and all(s == 200 for s in traffic_statuses)
    text = out.decode()
    assert "trace written to" in text
    trace_dir = text.splitlines()[0].split("trace written to ")[1]
    found = []
    for root, _dirs, names in os.walk(trace_dir):
        found.extend(names)
    assert any(n.endswith((".trace.json.gz", ".pb", ".json.gz")) or "trace" in n for n in found), found


def test_grpc_hits_addend_wire_level(runner):
    """hits_addend over the REAL wire (reference wire-level accounting;
    VERDICT r2 #8): a 5/min limit consumed in 3+3 hits — first OK with
    remaining 2, second OVER_LIMIT (partial attribution)."""
    req = _request("basic", [("key1", "wirehits")], hits=3)
    resp = _grpc_call(runner, req)
    assert resp.overall_code == rls_pb2.RateLimitResponse.OK
    assert resp.statuses[0].limit_remaining == 2

    resp = _grpc_call(runner, req)
    assert resp.overall_code == rls_pb2.RateLimitResponse.OVER_LIMIT
    assert resp.statuses[0].limit_remaining == 0

    # Third request: fully over.
    resp = _grpc_call(runner, _request("basic", [("key1", "wirehits")], hits=1))
    assert resp.overall_code == rls_pb2.RateLimitResponse.OVER_LIMIT


def test_json_endpoint_survives_hostile_bodies(runner):
    """Malformed/hostile bodies must map to 4xx/5xx without harming
    the server (reference server_impl_test.go:44-85 400-path, widened:
    junk bytes, invalid utf-8, wrong shapes, huge-ish payloads)."""
    hostile = [
        b"not json {",
        b"\xff\xfe\x00\x01binary",
        b"{}",  # missing domain -> service error
        b'{"domain": 42}',
        b'{"descriptors": "nope", "domain": "basic"}',
        b'{"domain":"basic","descriptors":[{"entries":"x"}]}',
        json.dumps(
            {"domain": "basic", "descriptors": [{"entries": [{"key": "k" * 10000, "value": "v" * 10000}]}]}
        ).encode(),
        json.dumps(
            {
                "domain": "basic",
                "descriptors": [
                    {"entries": [{"key": f"k{i}", "value": f"v{i}"}]}
                    for i in range(300)
                ],
            }
        ).encode(),
    ]
    for body in hostile:
        status, _ = _http(runner, "/json", body)
        assert status in (200, 400, 429, 500), (status, body[:40])
    # The server is still healthy and serving real traffic.
    status, out = _http(runner, "/healthcheck")
    assert (status, out) == (200, b"OK")
    resp = _grpc_call(runner, _request("basic", [("key1", "afterfuzz")]))
    assert resp.overall_code == rls_pb2.RateLimitResponse.OK


def test_grpc_extreme_hits_addend(runner):
    """hits_addend at the uint32 ceiling: one request exhausts any
    limit, attribution never wraps negative, and the server survives."""
    req = _request("basic", [("key1", "maxhits")], hits=0xFFFFFFFF)
    resp = _grpc_call(runner, req)
    assert resp.overall_code == rls_pb2.RateLimitResponse.OVER_LIMIT
    assert resp.statuses[0].limit_remaining == 0
    # Follow-up normal request on the same key: still over, sane.
    resp = _grpc_call(runner, _request("basic", [("key1", "maxhits")]))
    assert resp.overall_code == rls_pb2.RateLimitResponse.OVER_LIMIT


def test_grpc_health_watch_streams_transitions(runner):
    """grpc.health.v1 Watch: the stream yields the current status
    immediately and pushes transitions as they happen (the reference
    registers the standard health service whose Watch does exactly
    this; our impl is condition-variable driven, server/health.py)."""
    import queue as _queue
    import threading as _threading

    with grpc.insecure_channel(
        f"127.0.0.1:{runner.grpc_server.bound_port}"
    ) as channel:
        watch = channel.unary_stream(
            "/grpc.health.v1.Health/Watch",
            request_serializer=health_pb2.HealthCheckRequest.SerializeToString,
            response_deserializer=health_pb2.HealthCheckResponse.FromString,
        )
        stream = watch(health_pb2.HealthCheckRequest(), timeout=30)
        updates: "_queue.Queue" = _queue.Queue()

        def reader():
            try:
                for resp in stream:
                    updates.put(resp.status)
            except Exception:
                pass

        t = _threading.Thread(target=reader, daemon=True)
        t.start()
        try:
            first = updates.get(timeout=10)
            assert first == health_pb2.HealthCheckResponse.SERVING
            runner.health.fail()
            assert (
                updates.get(timeout=10)
                == health_pb2.HealthCheckResponse.NOT_SERVING
            )
            runner.health.ok()
            assert (
                updates.get(timeout=10)
                == health_pb2.HealthCheckResponse.SERVING
            )
        finally:
            runner.health.ok()
            stream.cancel()
            t.join(timeout=5)


def test_stats_json_endpoint(runner):
    """/stats.json mirrors /stats as machine-readable JSON (counters,
    gauges, timer summaries)."""
    status, out = _http(
        runner, "/stats.json", port=runner.debug_server.bound_port
    )
    assert status == 200
    parsed = json.loads(out)
    assert "stats" in parsed and "timers" in parsed
    assert any(
        k.startswith("ratelimit.service.") for k in parsed["stats"]
    )


def test_stats_json_carries_readback_ready(runner):
    """Every bank's `readback_ready` counter — launches whose result
    was ready when the completer took them up — is in /stats.json,
    beside the launch count it is a share of."""
    body = json.dumps(
        {
            "domain": "basic",
            "descriptors": [{"entries": [{"key": "key1", "value": "rr"}]}],
        }
    ).encode()
    assert _http(runner, "/json", body)[0] in (200, 429)
    status, out = _http(
        runner, "/stats.json", port=runner.debug_server.bound_port
    )
    assert status == 200
    stats = json.loads(out)["stats"]
    ready = stats["ratelimit.tpu.bank0.readback_ready"]
    assert isinstance(ready, int) and ready >= 0
    assert stats["ratelimit.tpu.launch.rate"] >= 1


def test_per_second_bank_wired_through_runner(tmp_path_factory):
    """TPU_PERSECOND=true gives SECOND-unit limits their own counter
    bank + dispatcher (the dual-Redis analog, fixed_cache_impl.go:
    77-87), wired by the Runner and visible in the bank gauges."""
    root = tmp_path_factory.mktemp("persec-runtime")
    config_dir = root / "ratelimit" / "config"
    config_dir.mkdir(parents=True)
    (config_dir / "ps.yaml").write_text(
        "domain: ps\n"
        "descriptors:\n"
        "  - key: persec\n"
        "    rate_limit:\n"
        "      unit: second\n"
        "      requests_per_unit: 2\n"
        "  - key: perminute\n"
        "    rate_limit:\n"
        "      unit: minute\n"
        "      requests_per_unit: 100\n"
    )
    r = Runner(
        Settings(
            host="127.0.0.1",
            port=0,
            grpc_host="127.0.0.1",
            grpc_port=0,
            debug_host="127.0.0.1",
            debug_port=0,
            use_statsd=False,
            backend_type="tpu",
            tpu_num_slots=1 << 10,
            tpu_per_second=True,
            tpu_per_second_num_slots=1 << 10,
            tpu_batch_window_us=200,
            tpu_batch_buckets=[8, 32],
            runtime_path=str(root),
            runtime_subdirectory="ratelimit",
            local_cache_size_in_bytes=0,
            expiration_jitter_max_seconds=0,
        ),
        # 2/SECOND progression: a real clock could roll the one-second
        # window between calls.
        time_source=PinnedTimeSource(1_000_000),
    )
    r.start()
    try:
        assert r.cache.per_second_engine is not None
        OK = rls_pb2.RateLimitResponse.OK
        OVER = rls_pb2.RateLimitResponse.OVER_LIMIT
        codes = [
            _grpc_call(r, _request("ps", [("persec", "x")])).overall_code
            for _ in range(3)
        ]
        assert codes == [OK, OK, OVER]
        # The per-minute key rode the MAIN bank; the per-second key
        # landed on bank1 (dual-bank gauges both live).
        _grpc_call(r, _request("ps", [("perminute", "y")]))
        r.cache.flush()
        assert len(r.cache.per_second_engine.slot_table) == 1
        assert len(r.cache.engine.slot_table) == 1
        status, out = _http(r, "/stats", port=r.debug_server.bound_port)
        assert status == 200
        text = out.decode()
        assert "ratelimit.tpu.bank0.live_keys: 1" in text
        assert "ratelimit.tpu.bank1.live_keys: 1" in text
    finally:
        r.stop()


def test_traceparent_roundtrip_grpc_phase_spans(runner):
    """Observability acceptance: a gRPC request carrying a W3C
    traceparent (sampled) produces a committed trace under the SAME
    trace id with the full phase breakdown — decode, service, backend
    dispatch, kernel — and that trace renders in /debug/tracez."""
    from ratelimit_tpu.observability import TRACER

    trace_id = "1f" * 16
    parent_span = "2e" * 8
    header = f"00-{trace_id}-{parent_span}-01"
    resp = _grpc_call(
        runner,
        _request("basic", [("key1", "traceme")]),
        metadata=[("traceparent", header)],
    )
    assert resp.overall_code == rls_pb2.RateLimitResponse.OK

    match = [t for t in TRACER.recent() if t.trace_id == trace_id]
    assert match, "inbound traceparent's trace id not in the ring"
    trace = match[-1]
    assert trace.parent_id == parent_span
    names = {s["name"] for s in trace.spans}
    # >= 4 phase spans, kernel leg included (the request hit the
    # engine through the dispatcher).
    assert {
        "decode",
        "service.should_rate_limit",
        "backend.do_limit",
        "backend.dispatch",
        "kernel.step",
    } <= names
    root = [s for s in trace.spans if s["name"] == "grpc.should_rate_limit"]
    assert root and root[0]["parent_id"] == parent_span

    # The kernel span sits inside the backend.do_limit span's window.
    by_name = {s["name"]: s for s in trace.spans}
    backend = by_name["backend.do_limit"]
    kernel = by_name["kernel.step"]
    assert kernel["start_ms"] >= backend["start_ms"]
    assert kernel["attrs"]["lanes"] >= 1

    # /debug/tracez shows the trace by id with its span tree.
    status, out = _http(
        runner, "/debug/tracez", port=runner.debug_server.bound_port
    )
    assert status == 200
    text = out.decode()
    assert trace_id in text
    assert "kernel.step" in text


def test_traceparent_roundtrip_http_json(runner):
    """The /json bridge: inbound traceparent header adopts the trace,
    and the response echoes a traceparent continuing the SAME trace."""
    from ratelimit_tpu.observability import TRACER

    trace_id = "3d" * 16
    header = f"00-{trace_id}-{'4c' * 8}-01"
    body = json.dumps(
        {
            "domain": "basic",
            "descriptors": [{"entries": [{"key": "key1", "value": "httptrace"}]}],
        }
    ).encode()
    url = f"http://127.0.0.1:{runner.http_server.bound_port}/json"
    req = urllib.request.Request(url, data=body)
    req.add_header("traceparent", header)
    with urllib.request.urlopen(req, timeout=30) as resp:
        assert resp.status == 200
        echoed = resp.headers.get("traceparent")
    assert echoed is not None and echoed.split("-")[1] == trace_id
    assert any(t.trace_id == trace_id for t in TRACER.recent())


def test_metrics_endpoint_serves_phase_histograms(runner):
    """GET /metrics: valid Prometheus text with per-phase histogram
    buckets — cumulative, +Inf == _count — from which p99 is
    derivable."""
    # Ensure at least one request has been observed.
    _grpc_call(runner, _request("basic", [("key1", "metricsprobe")]))
    status, out = _http(runner, "/metrics", port=runner.debug_server.bound_port)
    assert status == 200
    text = out.decode()
    for phase in ("decode", "service", "serialize"):
        assert (
            f"# TYPE ratelimit_server_ShouldRateLimit_phase_{phase}_ms "
            "histogram" in text
        )
    prefix = "ratelimit_server_ShouldRateLimit_response_ms"
    bucket_lines = [
        l for l in text.splitlines() if l.startswith(prefix + "_bucket")
    ]
    assert bucket_lines, text
    counts = [int(l.rsplit(" ", 1)[1]) for l in bucket_lines]
    assert counts == sorted(counts)  # cumulative buckets
    count_line = [
        l for l in text.splitlines() if l.startswith(prefix + "_count")
    ][0]
    total = int(count_line.rsplit(" ", 1)[1])
    assert total >= 1
    assert counts[-1] == total  # +Inf bucket equals _count
    # p99 derivable: find the first bucket holding the 0.99 rank.
    import re as _re

    rank = 0.99 * total
    for line, cum in zip(bucket_lines, counts):
        if cum >= rank:
            le = _re.search(r'le="([^"]+)"', line).group(1)
            assert le == "+Inf" or float(le) > 0
            break
    else:
        pytest.fail("no bucket covers the p99 rank")
    # Counters and gauges are present too.
    assert "ratelimit_server_ShouldRateLimit_total_requests" in text
    assert "ratelimit_tpu_bank0_live_keys" in text
    # Device-path telemetry: dispatcher queue gauges + high-water
    # marks, in-flight launches, slot-table capacity/fill/evictions/
    # rollovers, batch-shape histograms, and the hot-key family.
    for family in (
        "ratelimit_tpu_bank0_dispatch_queue",
        "ratelimit_tpu_bank0_dispatch_queue_hwm",
        "ratelimit_tpu_bank0_inflight_launches",
        "ratelimit_tpu_bank0_inflight_hwm",
        "ratelimit_tpu_bank0_num_slots",
        "ratelimit_tpu_bank0_slot_fill_pct",
        "ratelimit_tpu_hotkeys_tracked",
    ):
        assert f"# TYPE {family} gauge" in text, family
    for family in (
        "ratelimit_tpu_bank0_evictions",
        "ratelimit_tpu_bank0_window_rollovers",
        "ratelimit_tpu_hotkeys_observed",
        "ratelimit_tpu_hotkeys_evictions",
    ):
        assert f"# TYPE {family} counter" in text, family
    assert "# TYPE ratelimit_tpu_bank0_batch_lanes histogram" in text
    assert "ratelimit_tpu_bank0_batch_items_bucket" in text
    # The served request above rolled at least one fresh window slot
    # and landed in at least one launched batch.
    rollovers = int(
        [
            l for l in text.splitlines()
            if l.startswith("ratelimit_tpu_bank0_window_rollovers ")
        ][0].rsplit(" ", 1)[1]
    )
    assert rollovers >= 1
    lanes_count = int(
        [
            l for l in text.splitlines()
            if l.startswith("ratelimit_tpu_bank0_batch_lanes_count")
        ][0].rsplit(" ", 1)[1]
    )
    assert lanes_count >= 1


def test_profiling_capture_endpoints_are_gated():
    """/debug/profile and /debug/xla_trace refuse with 403 unless
    DEBUG_PROFILING is set; /debug/threadz stays open either way."""
    from ratelimit_tpu.server.debug_profiling import add_profiling_routes
    from ratelimit_tpu.server.http_server import HttpServer

    def get(port, path):
        try:
            with urllib.request.urlopen(
                f"http://127.0.0.1:{port}{path}", timeout=30
            ) as r:
                return r.status, r.read()
        except urllib.error.HTTPError as e:
            return e.code, e.read()

    closed = HttpServer("127.0.0.1", 0, name="debug-closed")
    add_profiling_routes(closed)  # default: disabled
    closed.start()
    try:
        assert get(closed.bound_port, "/debug/threadz")[0] == 200
        code, body = get(closed.bound_port, "/debug/profile?seconds=0.1")
        assert code == 403 and b"DEBUG_PROFILING" in body
        assert get(closed.bound_port, "/debug/xla_trace?seconds=0.1")[0] == 403
    finally:
        closed.stop()

    opened = HttpServer("127.0.0.1", 0, name="debug-open")
    add_profiling_routes(opened, profiling_enabled=True)
    opened.start()
    try:
        code, body = get(opened.bound_port, "/debug/profile?seconds=0.2")
        assert code == 200
        assert b"statistical cpu profile" in body
    finally:
        opened.stop()


def test_debug_hotkeys_ranks_served_traffic(runner):
    """/debug/hotkeys through the real server: skewed traffic ranks
    the heavy stem first, with exact counts at this cardinality."""
    for _ in range(5):
        _grpc_call(runner, _request("basic", [("key1", "hotprobe")]))
    _grpc_call(runner, _request("basic", [("key1", "coldprobe")]))
    status, out = _http(
        runner, "/debug/hotkeys", port=runner.debug_server.bound_port
    )
    assert status == 200
    body = json.loads(out.decode())
    keys = {k["key"]: k for k in body["keys"]}
    hot = keys["basic_key1_hotprobe_"]
    cold = keys["basic_key1_coldprobe_"]
    assert hot["hits"] >= 5 and cold["hits"] >= 1
    assert hot["hits"] > cold["hits"]
    ranked = [k["hits"] for k in body["keys"]]
    assert ranked == sorted(ranked, reverse=True)


def test_unsampled_requests_stay_out_of_the_ring(runner):
    """No traceparent, sample_rate 0: a clean request must not commit
    a trace (the error/over-limit override stays for bad ones)."""
    from ratelimit_tpu.observability import TRACER

    before = len(TRACER.recent())
    resp = _grpc_call(runner, _request("basic", [("nosuch", "quiet")]))
    assert resp.overall_code == rls_pb2.RateLimitResponse.OK
    assert len(TRACER.recent()) == before


def test_over_limit_commits_trace_without_sampling(runner):
    """Tail-sampling override: an OVER_LIMIT decision commits even
    with no traceparent and rate 0."""
    from ratelimit_tpu.observability import TRACER

    req = _request("basic", [("one_per_minute", "something")])
    codes = {_grpc_call(runner, req).overall_code for _ in range(3)}
    assert rls_pb2.RateLimitResponse.OVER_LIMIT in codes
    over = [t for t in TRACER.recent() if t.status == "over_limit"]
    assert over, [t.status for t in TRACER.recent()]


def test_window_rollover_and_decay_over_the_wire(runner):
    """The reference's DurationUntilReset-decay and window-rollover
    integration assertions (integration_test.go:436-496,585-596),
    previously untestable at the wire level without flakes — the
    Runner's injected PinnedTimeSource makes them deterministic:
    duration decays as the clock advances, and crossing the minute
    boundary grants a fresh quota for the same key."""
    clock = runner.time_source
    start = clock.now
    # Derived from whatever the fixture pinned (epoch-independent);
    # the fixture guarantees a mid-window start.
    to_boundary = 60 - start % 60
    assert 7 < to_boundary < 60
    try:
        OK = rls_pb2.RateLimitResponse.OK
        OVER = rls_pb2.RateLimitResponse.OVER_LIMIT
        req = _request("basic", [("key1", "rollover")])

        # Exhaust the 5/min quota; duration reflects the pinned offset.
        codes = [
            _grpc_call(runner, req).overall_code for _ in range(6)
        ]
        assert codes == [OK] * 5 + [OVER]
        st = _grpc_call(runner, req).statuses[0]
        assert st.duration_until_reset.seconds == to_boundary

        # Decay: +7s inside the same window — still OVER.
        clock.advance(7)
        st = _grpc_call(runner, req).statuses[0]
        assert st.code == OVER
        assert st.duration_until_reset.seconds == to_boundary - 7

        # Rollover: cross the boundary — fresh quota for the SAME key.
        clock.advance(to_boundary - 7)
        resp = _grpc_call(runner, req)
        assert resp.overall_code == OK
        assert resp.statuses[0].limit_remaining == 4
        assert resp.statuses[0].duration_until_reset.seconds == 60
    finally:
        clock.now = start  # don't leak time travel into other tests
