"""Overload control (overload/controller.py): SLO-burn shedding with
per-domain priority, hot-key promotion, detector-triggered
backpressure — all on the FakeMonotonicClock seam, zero sleeps — plus
the wiring contracts: priority config validation, the service shed
path, flight-record shed codes through the real /json transport, the
/debug/overload and /debug/flight endpoints, statsd parity for the new
counter families, and the decisions-byte-identical-when-disabled
parity the acceptance criteria pin."""

import json
import socket
import urllib.error
import urllib.request

import pytest

from ratelimit_tpu.api import Code, Descriptor, RateLimitRequest
from ratelimit_tpu.config.loader import ConfigError, ConfigFile, load_config
from ratelimit_tpu.observability import (
    AnomalyDetectors,
    FLIGHT_CODE_SHED,
    SloEngine,
    make_flight_recorder,
)
from ratelimit_tpu.overload import (
    DEFAULT_DOMAIN_PRIORITY,
    OverloadController,
    PromotionCache,
    REASON_BACKPRESSURE,
    REASON_SLO_BURN,
)
from ratelimit_tpu.stats.manager import Manager, StatsStore
from ratelimit_tpu.utils.time import FakeMonotonicClock, PinnedTimeSource

SLOW_MS = 500.0  # over the default 50ms latency SLO threshold
FAST_MS = 1.0


def make_controller(**kw):
    clock = kw.pop("clock", FakeMonotonicClock(100.0))
    mgr = kw.pop("manager", Manager())
    slo = SloEngine(mgr, clock=clock)
    kw.setdefault("shed_enabled", True)
    kw.setdefault("shed_burn_threshold", 8.0)
    kw.setdefault("shed_min_requests", 10)
    kw.setdefault("shed_ewma_alpha", 1.0)  # undamped: deterministic math
    ctrl = OverloadController(slo=slo, clock=clock, **kw)
    return ctrl, slo, clock, mgr


def drive(slo, domain, n, ms):
    for _ in range(n):
        slo.observe(domain, over_limit=False, latency_ms=ms)


# -- priority config key ------------------------------------------------------


def test_priority_key_parses_and_defaults():
    mgr = Manager()
    cfg = load_config(
        [
            ConfigFile(
                "a",
                "domain: paying\npriority: 3\ndescriptors:\n"
                "  - key: k\n    rate_limit: {unit: hour, requests_per_unit: 10}\n",
            ),
            ConfigFile(
                "b",
                "domain: plain\ndescriptors:\n"
                "  - key: k\n    rate_limit: {unit: hour, requests_per_unit: 10}\n",
            ),
            ConfigFile(
                "c",
                "domain: sheddable\npriority: 0\ndescriptors:\n"
                "  - key: k\n    rate_limit: {unit: hour, requests_per_unit: 10}\n",
            ),
        ],
        mgr,
    )
    assert cfg.priorities == {
        "paying": 3,
        "plain": DEFAULT_DOMAIN_PRIORITY,
        "sheddable": 0,
    }


@pytest.mark.parametrize(
    "priority", ["high", -1, True, 1.5]
)
def test_priority_key_rejects_non_uint(priority):
    yaml = (
        f"domain: d\npriority: {json.dumps(priority)}\ndescriptors:\n"
        "  - key: k\n    rate_limit: {unit: hour, requests_per_unit: 10}\n"
    )
    # Floats die in the generic whitelist leaf check ("error checking
    # config"), everything else in the priority validator.
    with pytest.raises(ConfigError, match="priority|error checking config"):
        load_config([ConfigFile("a", yaml)], Manager())


def test_priority_key_rejected_on_descriptors():
    yaml = (
        "domain: d\ndescriptors:\n"
        "  - key: k\n    priority: 2\n"
        "    rate_limit: {unit: hour, requests_per_unit: 10}\n"
    )
    with pytest.raises(ConfigError, match="domain-level"):
        load_config([ConfigFile("a", yaml)], Manager())


# -- shed lifecycle (burn crossing -> shed -> recovery -> un-shed) ------------


def test_burn_crossing_sheds_lowest_priority_first_and_recovers():
    ctrl, slo, clock, _ = make_controller()
    slo.set_domains(["paying", "guest"])
    ctrl.set_priorities({"paying": 2, "guest": 0})

    ctrl.tick()  # seeds the delta cursors; no burn yet
    assert not ctrl.shedding
    assert ctrl.admit("guest") == (None, None)

    # Overload: the protected tier burns latency budget hard.
    drive(slo, "paying", 50, SLOW_MS)
    clock.advance(1.0)
    ctrl.tick()
    assert ctrl.shedding
    assert ctrl.shed_floor_priority == 2
    # Lowest priority (and unconfigured strangers) shed; the top
    # priority tier is NEVER shed.
    assert ctrl.admit("guest")[0] == REASON_SLO_BURN
    assert ctrl.admit("stranger")[0] == REASON_SLO_BURN
    assert ctrl.admit("paying") == (None, None)

    # Budget recovery: protected traffic fast again -> floor unwinds.
    for _ in range(2):
        drive(slo, "paying", 50, FAST_MS)
        clock.advance(1.0)
        ctrl.tick()
    assert not ctrl.shedding
    assert ctrl.admit("guest") == (None, None)
    assert ctrl.shed_transitions == 2


def test_unshed_hysteresis_holds_floor_in_the_band():
    # Burn between clear (4.0) and trip (8.0): once shedding, the
    # floor must HOLD (no flapping), and an un-tripped controller must
    # not start shedding at the same level.
    ctrl, slo, clock, _ = make_controller()
    slo.set_domains(["paying"])
    ctrl.set_priorities({"paying": 2})
    ctrl.tick()

    def tick_with_slow_fraction(frac, n=100):
        drive(slo, "paying", int(n * frac), SLOW_MS)
        drive(slo, "paying", n - int(n * frac), FAST_MS)
        clock.advance(1.0)
        ctrl.tick()

    # 0.6% slow with budget 0.1% -> burn 6.0: inside the band.
    tick_with_slow_fraction(0.006, 1000)
    assert not ctrl.shedding  # below trip threshold: never starts

    tick_with_slow_fraction(0.02, 1000)  # burn 20: trips
    assert ctrl.shedding
    tick_with_slow_fraction(0.006, 1000)  # burn 6: in the band
    assert ctrl.shedding  # hysteresis: holds
    tick_with_slow_fraction(0.001, 1000)  # burn 1 < clear 4: releases
    assert not ctrl.shedding


def test_shed_floor_never_reaches_top_priority():
    ctrl, slo, clock, _ = make_controller()
    slo.set_domains(["gold", "silver", "bronze"])
    ctrl.set_priorities({"gold": 3, "silver": 2, "bronze": 1})
    ctrl.tick()
    for _ in range(10):  # way past the number of levels
        drive(slo, "gold", 50, SLOW_MS)
        clock.advance(1.0)
        ctrl.tick()
    # Floor parks at the top level: gold still admitted.
    assert ctrl.shed_floor_priority == 3
    assert ctrl.admit("gold") == (None, None)
    assert ctrl.admit("silver")[0] == REASON_SLO_BURN
    assert ctrl.admit("bronze")[0] == REASON_SLO_BURN


def test_shed_domains_recovering_do_not_vote_to_unshed():
    # Guest (shed) reads healthy the moment it sheds — its burn must
    # not relax the floor while paying still burns.
    ctrl, slo, clock, _ = make_controller()
    slo.set_domains(["paying", "guest"])
    ctrl.set_priorities({"paying": 2, "guest": 0})
    ctrl.tick()
    drive(slo, "paying", 50, SLOW_MS)
    drive(slo, "guest", 50, SLOW_MS)
    clock.advance(1.0)
    ctrl.tick()
    assert ctrl.shedding
    # Next tick: guest now "healthy" (no traffic), paying still slow.
    drive(slo, "paying", 50, SLOW_MS)
    clock.advance(1.0)
    ctrl.tick()
    assert ctrl.shedding


def test_thin_traffic_never_sheds():
    ctrl, slo, clock, _ = make_controller(shed_min_requests=20)
    slo.set_domains(["paying"])
    ctrl.set_priorities({"paying": 2})
    ctrl.tick()
    drive(slo, "paying", 5, SLOW_MS)  # 5 < min_requests
    clock.advance(1.0)
    ctrl.tick()
    assert not ctrl.shedding


def test_per_domain_reason_counters_and_folding():
    ctrl, slo, clock, mgr = make_controller()
    ctrl.register_stats(mgr.store)
    slo.set_domains(["paying", "guest"])
    ctrl.set_priorities({"paying": 2, "guest": 0})
    ctrl.tick()
    drive(slo, "paying", 50, SLOW_MS)
    clock.advance(1.0)
    ctrl.tick()
    ctrl.admit("guest")
    ctrl.admit("guest")
    ctrl.admit("total-stranger")  # unconfigured: folds to _other
    counters = mgr.store.counters()
    assert counters["ratelimit.overload.shed.guest.slo_burn"] == 2
    assert counters["ratelimit.overload.shed._other.slo_burn"] == 1
    assert counters["ratelimit.overload.shed_total"] == 3
    assert "ratelimit.overload.shed.total-stranger.slo_burn" not in counters
    assert mgr.store.gauges()["ratelimit.overload.shedding"] == 1


# -- promotion ----------------------------------------------------------------


def test_promotion_ttl_expiry_and_capacity():
    clock = FakeMonotonicClock(0.0)
    promo = PromotionCache(ttl_s=2.0, capacity=2, clock=clock)
    promo.promote("a")
    assert promo.contains("a")
    assert promo.hits == 1
    clock.advance(3.0)
    assert not promo.contains("a")  # lazy expiry
    assert promo.expirations == 1
    # Capacity eviction: closest-to-expiry entry goes.
    promo.promote("b")
    clock.advance(1.0)
    promo.promote("c")
    promo.promote("d")
    assert promo.evictions == 1
    assert not promo.contains("b")
    assert promo.contains("c") and promo.contains("d")
    assert len(promo) == 2


def test_promotion_tick_uses_per_tick_deltas():
    # A stem with heavy HISTORICAL over-limit share but a clean
    # current tick must NOT be promoted; a currently-bad stem must.
    from ratelimit_tpu.observability import HotKeySketch

    clock = FakeMonotonicClock(0.0)
    sketch = HotKeySketch(8)
    ctrl = OverloadController(
        hotkeys=sketch,
        clock=clock,
        promote_enabled=True,
        promote_ttl_s=5.0,
        promote_over_share=0.5,
        promote_min_hits=10,
    )
    bad = sketch.track("stem_bad")
    was_bad = sketch.track("stem_was_bad")
    was_bad.hits, was_bad.over_limit = 1000, 900  # all historical
    ctrl.tick()  # absorbs history as the baseline... first sight
    # First sight counts from zero, so was_bad's history IS its first
    # delta — promoted once.  The point is the SECOND tick: clean
    # traffic must not re-promote it while bad keeps qualifying.
    assert ctrl.promotion.contains("stem_was_bad")
    clock.advance(10.0)  # everything promoted so far expires
    ctrl.promotion.sweep()
    bad.hits += 100
    bad.over_limit += 80
    was_bad.hits += 100  # clean tick for the historical offender
    ctrl.tick()
    assert ctrl.promotion.contains("stem_bad")
    assert not ctrl.promotion.contains("stem_was_bad")


def test_promotion_short_circuits_device_in_do_limit_resolved(clock):
    from ratelimit_tpu.backends.engine import CounterEngine
    from ratelimit_tpu.backends.tpu_cache import TpuRateLimitCache

    mono = FakeMonotonicClock(0.0)
    engine = CounterEngine(num_slots=1 << 10)
    cache = TpuRateLimitCache(engine, clock)
    mgr = Manager()
    cfg = load_config(
        [
            ConfigFile(
                "a",
                "domain: d\ndescriptors:\n"
                "  - key: k\n    rate_limit: {unit: hour, requests_per_unit: 10}\n",
            )
        ],
        mgr,
    )
    req = RateLimitRequest("d", [Descriptor.of(("k", "v"))], 1)
    statuses, limits, _ = cache.do_limit_resolved(req, cfg)
    assert statuses[0].code is Code.OK
    rule = limits[0]
    over_before = rule.stats.over_limit.value()

    promo = PromotionCache(ttl_s=5.0, capacity=8, clock=mono)
    cache.promotion = promo
    rd = cache.resolver.resolve(cfg, "d", req.descriptors[0])
    promo.promote(rd.stem)
    statuses, _, _ = cache.do_limit_resolved(req, cfg)
    assert statuses[0].code is Code.OVER_LIMIT
    assert statuses[0].limit_remaining == 0
    assert promo.hits == 1
    # Books like the host over-limit cache: over_limit + the
    # with_local_cache marker.
    assert rule.stats.over_limit.value() == over_before + 1
    assert rule.stats.over_limit_with_local_cache.value() == 1
    # TTL expiry restores the device path.
    mono.advance(10.0)
    statuses, _, _ = cache.do_limit_resolved(req, cfg)
    assert statuses[0].code is Code.OK
    cache.close()


# -- backpressure -------------------------------------------------------------


def test_backpressure_ratchet_and_release():
    clock = FakeMonotonicClock(0.0)
    ctrl = OverloadController(
        clock=clock,
        backpressure_enabled=True,
        backpressure_tokens=4,
        backpressure_max_wait_s=0.0,  # zero-sleep admission
        backpressure_hold_s=10.0,
    )
    ctrl.set_priorities({"d": 2})
    assert ctrl.admit("d") == (None, None)  # gate off: no token needed

    ctrl.on_detector_trip("error_rate", "not a backpressure trigger")
    assert ctrl.admit("d") == (None, None)

    ctrl.on_detector_trip("queue_saturation", "queue hwm 900 >= 512")
    assert ctrl.bp_trips == 1
    reason, gate = ctrl.admit("d")
    assert reason is None and gate is not None

    # Ratchet: a second trip halves the tokens (4 -> 2).
    ctrl.on_detector_trip("latency_spike", "p99 40x baseline")
    s = ctrl.summary()["backpressure"]
    assert s["active"] and s["level"] == 2 and s["tokens"] == 2
    g2 = ctrl.admit("d")[1]
    g3 = ctrl.admit("d")[1]
    assert g2 is not None and g3 is not None
    # New gate exhausted -> graceful shed with the backpressure reason.
    reason, g4 = ctrl.admit("d")
    assert reason == REASON_BACKPRESSURE and g4 is None
    # Releasing into the gates we actually hold frees permits.
    g2.release()
    assert ctrl.admit("d")[1] is not None
    gate.release()  # old (pre-ratchet) gate: released safely, unused

    # Hold expiry releases the gate entirely.
    clock.advance(11.0)
    ctrl.tick()
    assert ctrl.admit("d") == (None, None)
    assert ctrl.summary()["backpressure"]["active"] is False
    assert ctrl.summary()["backpressure"]["level"] == 0


def test_detector_trips_reach_the_controller_through_the_sampler():
    class Trip:
        name = "queue_saturation"

        def __init__(self):
            self.reasons = ["depth 900"] * 3

        def evaluate(self):
            return self.reasons.pop(0) if self.reasons else None

    clock = FakeMonotonicClock(0.0)
    ctrl = OverloadController(
        clock=clock,
        backpressure_enabled=True,
        backpressure_tokens=8,
        backpressure_max_wait_s=0.0,
        backpressure_hold_s=60.0,
    )
    dets = AnomalyDetectors(
        StatsStore(), [Trip()], clock=clock, cooldown_s=60.0, overload=ctrl
    )
    assert len(dets.tick()) == 1
    assert ctrl.bp_trips == 1
    assert ctrl.ticks == 1  # sampler ticks the controller too
    clock.advance(1.0)
    dets.tick()  # inside incident cooldown: capture suppressed...
    assert ctrl.bp_trips == 2  # ...but the trip still reaches the gate
    assert ctrl.summary()["backpressure"]["level"] == 2


# -- service integration ------------------------------------------------------


class _Runtime:
    def __init__(self, files):
        self._files = files

    def snapshot(self):
        files = self._files

        class Snap:
            def keys(self):
                return sorted(files)

            def get(self, key):
                return files.get(key, "")

        return Snap()

    def add_update_callback(self, fn):
        pass


SERVICE_YAML = (
    "domain: paying\npriority: 2\ndescriptors:\n"
    "  - key: k\n    rate_limit: {unit: hour, requests_per_unit: 1000}\n"
)
GUEST_YAML = (
    "domain: guest\npriority: 0\ndescriptors:\n"
    "  - key: k\n    rate_limit: {unit: hour, requests_per_unit: 1000}\n"
)


def build_service(clock, with_overload=False, mono=None, **ctrl_kw):
    from ratelimit_tpu.backends.engine import CounterEngine
    from ratelimit_tpu.backends.tpu_cache import TpuRateLimitCache

    engine = CounterEngine(num_slots=1 << 10)
    cache = TpuRateLimitCache(engine, clock)
    mgr = Manager()
    svc = None
    ctrl = None
    if with_overload:
        mono = mono or FakeMonotonicClock(0.0)
        slo = SloEngine(mgr, clock=mono)
        ctrl_kw.setdefault("shed_enabled", True)
        ctrl = OverloadController(slo=slo, clock=mono, **ctrl_kw)
    svc = RateLimitServiceFactory(mgr, cache, clock)
    if ctrl is not None:
        svc.overload = ctrl
        ctrl.set_priorities(svc.get_current_config().priorities)
    return svc, cache, ctrl, mgr


def RateLimitServiceFactory(mgr, cache, clock):
    from ratelimit_tpu.service import RateLimitService

    return RateLimitService(
        _Runtime({"config.a": SERVICE_YAML, "config.b": GUEST_YAML}),
        cache,
        mgr,
        clock=clock,
    )


def test_service_shed_response_shape_and_priorities_adopted():
    clock = PinnedTimeSource(1_700_000_000)
    svc, cache, ctrl, _ = build_service(clock, with_overload=True)
    try:
        assert ctrl._priorities == {"paying": 2, "guest": 0}
        # Force the floor (the lifecycle is covered above; this pins
        # the service-side contract).
        ctrl._floor = 1
        ctrl._recompute_shed_locked()
        req = RateLimitRequest(
            "guest", [Descriptor.of(("k", "a")), Descriptor.of(("k", "b"))], 1
        )
        resp = svc.should_rate_limit(req)
        assert resp.overall_code is Code.OVER_LIMIT
        assert resp.shed_reason == REASON_SLO_BURN
        assert len(resp.statuses) == 2
        assert all(s.code is Code.OVER_LIMIT for s in resp.statuses)
        # The protected domain still gets real decisions.
        ok = svc.should_rate_limit(
            RateLimitRequest("paying", [Descriptor.of(("k", "a"))], 1)
        )
        assert ok.overall_code is Code.OK
        assert ok.shed_reason is None
    finally:
        cache.close()


def test_decisions_byte_identical_with_idle_controller_attached():
    """The parity contract: an ATTACHED but untripped controller (all
    three loops enabled, nothing promoted, floor at 0, gate off) must
    not change a single status field vs no controller at all."""
    clock_a = PinnedTimeSource(1_700_000_000)
    clock_b = PinnedTimeSource(1_700_000_000)
    svc_a, cache_a, _, _ = build_service(clock_a, with_overload=False)
    svc_b, cache_b, ctrl, _ = build_service(
        clock_b,
        with_overload=True,
        promote_enabled=True,
        backpressure_enabled=True,
        backpressure_max_wait_s=0.0,
    )
    cache_b.promotion = ctrl.promotion  # attached and empty
    try:
        reqs = [
            RateLimitRequest(
                dom, [Descriptor.of(("k", f"v{i % 7}"))], 1 + i % 3
            )
            for i, dom in enumerate(
                ["paying", "guest", "stranger"] * 40
            )
        ]
        for req in reqs:
            ra = svc_a.should_rate_limit(req)
            rb = svc_b.should_rate_limit(req)
            assert ra.overall_code == rb.overall_code
            assert rb.shed_reason is None
            fa = [
                (s.code, s.current_limit, s.limit_remaining,
                 s.duration_until_reset)
                for s in ra.statuses
            ]
            fb = [
                (s.code, s.current_limit, s.limit_remaining,
                 s.duration_until_reset)
                for s in rb.statuses
            ]
            assert fa == fb
    finally:
        cache_a.close()
        cache_b.close()


def test_shed_code_stamped_into_flight_ring_via_json_transport():
    from ratelimit_tpu.server.http_server import HttpServer, add_json_handler

    clock = PinnedTimeSource(1_700_000_000)
    svc, cache, ctrl, _ = build_service(clock, with_overload=True)
    flight = make_flight_recorder(64)
    ctrl._floor = 1
    ctrl._recompute_shed_locked()
    server = HttpServer("127.0.0.1", 0, name="overload-test")
    add_json_handler(server, svc, flight=flight, slo=None)
    server.start()
    try:
        body = json.dumps(
            {
                "domain": "guest",
                "descriptors": [
                    {"entries": [{"key": "k", "value": "x"}]}
                ],
            }
        ).encode()
        req = urllib.request.Request(
            f"http://127.0.0.1:{server.bound_port}/json",
            data=body,
            method="POST",
        )
        try:
            urllib.request.urlopen(req, timeout=10)
            raise AssertionError("shed response should be 429")
        except urllib.error.HTTPError as e:
            assert e.code == 429
        recs = flight.snapshot_dicts()
        assert recs, "shed decision must land in the ring"
        assert recs[0]["code"] == FLIGHT_CODE_SHED
        assert recs[0]["shed"] is True
        assert recs[0]["domain"] == "guest"
        # A normal decision records the protocol code, un-annotated.
        body2 = json.dumps(
            {
                "domain": "paying",
                "descriptors": [
                    {"entries": [{"key": "k", "value": "x"}]}
                ],
            }
        ).encode()
        urllib.request.urlopen(
            urllib.request.Request(
                f"http://127.0.0.1:{server.bound_port}/json",
                data=body2,
                method="POST",
            ),
            timeout=10,
        )
        recs = flight.snapshot_dicts()
        assert recs[0]["code"] == int(Code.OK)
        assert "shed" not in recs[0]
    finally:
        server.stop()
        cache.close()


# -- statsd parity (counter_fn delta-cursor path) -----------------------------


def test_statsd_flushes_overload_counters_as_deltas():
    from ratelimit_tpu.stats.statsd import StatsdExporter

    recv = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    recv.bind(("127.0.0.1", 0))
    recv.settimeout(5)
    port = recv.getsockname()[1]

    ctrl, slo, clock, mgr = make_controller(promote_enabled=True)
    ctrl.register_stats(mgr.store)
    slo.set_domains(["paying", "guest"])
    ctrl.set_priorities({"paying": 2, "guest": 0})
    ctrl.tick()
    drive(slo, "paying", 50, SLOW_MS)
    clock.advance(1.0)
    ctrl.tick()
    ctrl.admit("guest")
    ctrl.admit("guest")
    ctrl.promotion.promote("stem_x")

    exporter = StatsdExporter(mgr.store, "127.0.0.1", port, interval_s=60)
    exporter.flush()
    lines = set(recv.recv(65536).decode().split("\n"))
    assert "ratelimit.overload.shed.guest.slo_burn:2|c" in lines
    assert "ratelimit.overload.shed_total:2|c" in lines
    assert "ratelimit.overload.promotion.promoted:1|c" in lines

    # Delta cursor: unchanged tallies emit nothing on the next flush.
    ctrl.admit("guest")
    exporter.flush()
    payload = recv.recv(65536).decode()
    assert "ratelimit.overload.shed.guest.slo_burn:1|c" in payload.split("\n")
    assert "promotion.promoted" not in payload
    exporter.stop()
    recv.close()


# -- debug endpoints ----------------------------------------------------------


def _get(port, path):
    return urllib.request.urlopen(
        f"http://127.0.0.1:{port}{path}", timeout=10
    )


def test_debug_overload_endpoint_and_404_when_unwired():
    from ratelimit_tpu.server.http_server import HttpServer, add_debug_routes

    ctrl, slo, clock, mgr = make_controller(
        promote_enabled=True, backpressure_enabled=True,
        backpressure_max_wait_s=0.0,
    )
    ctrl.set_priorities({"paying": 2})
    server = HttpServer("127.0.0.1", 0, name="ov-debug")
    add_debug_routes(server, mgr.store, overload=ctrl)
    server.start()
    try:
        with _get(server.bound_port, "/debug/overload") as r:
            body = json.loads(r.read())
        assert body["enabled"] == {
            "shed": True, "promotion": True, "backpressure": True
        }
        assert body["shed"]["priorities"] == {"paying": 2}
        assert body["promotion"]["live"] == []
        assert body["backpressure"]["active"] is False
    finally:
        server.stop()

    server = HttpServer("127.0.0.1", 0, name="ov-debug2")
    add_debug_routes(server, StatsStore())
    server.start()
    try:
        with pytest.raises(urllib.error.HTTPError) as e:
            _get(server.bound_port, "/debug/overload")
        assert e.value.code == 404
    finally:
        server.stop()


def test_debug_flight_endpoint_gated_and_jsonl():
    from ratelimit_tpu.server.http_server import HttpServer, add_debug_routes

    flight = make_flight_recorder(32)
    flight.note(0xABCD, 1)
    flight.record("d1", 1, 1, 0.5)
    flight.record("d2", 2, 3, 7.0)

    # Gated like /debug/profile: 403 without DEBUG_PROFILING.
    server = HttpServer("127.0.0.1", 0, name="fl-gated")
    add_debug_routes(server, StatsStore(), flight=flight)
    server.start()
    try:
        with pytest.raises(urllib.error.HTTPError) as e:
            _get(server.bound_port, "/debug/flight")
        assert e.value.code == 403
    finally:
        server.stop()

    server = HttpServer("127.0.0.1", 0, name="fl-open")
    add_debug_routes(
        server, StatsStore(), profiling_enabled=True, flight=flight
    )
    server.start()
    try:
        with _get(server.bound_port, "/debug/flight?format=jsonl") as r:
            assert r.headers["Content-Type"] == "application/x-ndjson"
            lines = [ln for ln in r.read().decode().splitlines() if ln]
        recs = [json.loads(ln) for ln in lines]
        assert len(recs) == 2
        # Oldest first (replay consumes chronological inter-arrivals).
        assert recs[0]["domain"] == "d1" and recs[1]["domain"] == "d2"
        assert recs[0]["stem_hash"] == f"{0xABCD:08x}"
        assert recs[1]["hits"] == 3
        with _get(server.bound_port, "/debug/flight?format=json") as r:
            body = json.loads(r.read())
        assert body["capacity"] == 32
        assert len(body["records"]) == 2
        # 404 when the recorder is off but profiling is on.
        server2 = HttpServer("127.0.0.1", 0, name="fl-none")
        add_debug_routes(server2, StatsStore(), profiling_enabled=True)
        server2.start()
        try:
            with pytest.raises(urllib.error.HTTPError) as e:
                _get(server2.bound_port, "/debug/flight")
            assert e.value.code == 404
        finally:
            server2.stop()
    finally:
        server.stop()
