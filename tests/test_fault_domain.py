"""Device-path fault domain (backends/fault_domain.py): watchdog,
quarantine + failure-mode fallback, supervised warm restart, and the
deadline satellites.

Faults are INJECTED at the engine seam (cluster/faults.py
DeviceFaultInjector) so the tests exercise the exact dispatcher-stamp /
wait-deadline / classification path real device faults take.  The
supervisor thread is disabled (fault_interval_s=0) and tick() driven
manually, so restarts happen deterministically.
"""

import gc
import threading
import time

import numpy as np
import pytest

from ratelimit_tpu.api import Code, Descriptor, RateLimitRequest
from ratelimit_tpu.backends.engine import CounterEngine
from ratelimit_tpu.backends.fault_domain import (
    FAULT_DEVICE_LOST,
    FAULT_EXCEPTION,
    FAULT_HANG,
    KernelDeadlineExceeded,
    classify_fault,
)
from ratelimit_tpu.backends.tpu_cache import TpuRateLimitCache
from ratelimit_tpu.cluster.faults import DeviceFaultInjector, DeviceLostError
from ratelimit_tpu.config.loader import ConfigFile, load_config
from ratelimit_tpu.observability import (
    FLIGHT_CODE_FALLBACK,
    make_flight_recorder,
)
from ratelimit_tpu.observability.spans import BG_GC, BG_SNAPSHOT_GRAB, SPANS
from ratelimit_tpu.stats.manager import Manager
from ratelimit_tpu.utils.time import PinnedTimeSource

YAML = """
domain: d
descriptors:
  - key: k
    rate_limit:
      unit: minute
      requests_per_unit: 20
  - key: shadowed
    rate_limit:
      unit: minute
      requests_per_unit: 1
    shadow_mode: true
"""


def _rule(mgr, key="k"):
    cfg = load_config([ConfigFile("config.c", YAML)], mgr)
    return cfg.get_limit("d", Descriptor.of((key, "x")))


def _req(key="k", hits=1):
    return RateLimitRequest("d", [Descriptor.of((key, "x"))], hits)


def make_cache(inj=None, mode="host", deadline=0.25, **kw):
    engine = CounterEngine(num_slots=256, buckets=(8,))
    if inj is not None:
        engine = inj.wrap_engine("lane0", engine)
    kw.setdefault("fault_restart_backoff_s", 0.05)
    kw.setdefault("fault_snapshot_interval_s", 1000.0)
    kw.setdefault("fault_probe_timeout_s", 10.0)
    return TpuRateLimitCache(
        engine,
        time_source=PinnedTimeSource(1234),
        batch_window_us=100,
        kernel_deadline_s=deadline,
        device_failure_mode=mode,
        fault_interval_s=0,  # no supervisor thread: tick() manually
        **kw,
    )


# ---------------------------------------------------------------------------
# classification
# ---------------------------------------------------------------------------


def test_classify_fault_taxonomy():
    assert classify_fault(TimeoutError("stuck")) == FAULT_HANG
    assert classify_fault(DeviceLostError("lane0")) == FAULT_DEVICE_LOST
    assert classify_fault(RuntimeError("XlaRuntimeError: foo")) == (
        FAULT_DEVICE_LOST
    )
    assert classify_fault(ValueError("bad batch")) == FAULT_EXCEPTION
    wrapped = RuntimeError("batch dispatcher is dead")
    wrapped.__cause__ = DeviceLostError("lane0")
    assert classify_fault(wrapped) == FAULT_DEVICE_LOST
    # The watchdog's own verdict is a TimeoutError subclass: a hang.
    assert classify_fault(KernelDeadlineExceeded("stuck 1s")) == FAULT_HANG
    # Socket vocabulary is no device's: with direct PJRT there is no
    # link between host and chip that could reset.
    assert classify_fault(ConnectionResetError("connection reset by peer")) == (
        FAULT_EXCEPTION
    )


# ---------------------------------------------------------------------------
# hang -> bounded wait -> quarantine -> fallback
# ---------------------------------------------------------------------------


def test_hang_bounds_the_rpc_and_quarantines():
    """A hung launch answers within ~KERNEL_DEADLINE_S (never the
    120 s dispatch timeout), records a hang fault, and re-routes the
    bank to the host mirror which keeps counting."""
    inj = DeviceFaultInjector()
    cache = make_cache(inj, deadline=0.2)
    mgr = Manager()
    rule = _rule(mgr)
    try:
        for _ in range(5):
            assert cache.do_limit(_req(), [rule])[0].code is Code.OK
        cache.fault_domain.snapshot_now()
        inj.hang("lane0")
        t0 = time.monotonic()
        status = cache.do_limit(_req(), [rule])[0]
        elapsed = time.monotonic() - t0
        assert elapsed < 2.0, f"blocked {elapsed}s, not deadline-bounded"
        assert status.code is Code.OK  # mirror continues the count
        fd = cache.fault_domain
        assert fd.stat_faults[FAULT_HANG] == 1
        assert fd.is_quarantined(0)
        # Fallback keeps enforcing the real limit: 6 admitted so far,
        # 14 more admit, then deny.
        admitted = 6
        for _ in range(30):
            admitted += cache.do_limit(_req(), [rule])[0].code is Code.OK
        assert admitted == 20
        assert fd.stat_fallback_decisions >= 30
    finally:
        inj.heal()
        cache.close()


def test_exception_fault_classified_and_served():
    inj = DeviceFaultInjector()
    cache = make_cache(inj)
    mgr = Manager()
    rule = _rule(mgr)
    try:
        assert cache.do_limit(_req(), [rule])[0].code is Code.OK
        inj.raise_error("lane0")
        status = cache.do_limit(_req(), [rule])[0]
        assert status.code is Code.OK
        assert cache.fault_domain.stat_faults[FAULT_EXCEPTION] == 1
    finally:
        inj.heal()
        cache.close()


def test_device_lost_fault_classified():
    inj = DeviceFaultInjector()
    cache = make_cache(inj)
    mgr = Manager()
    rule = _rule(mgr)
    try:
        assert cache.do_limit(_req(), [rule])[0].code is Code.OK
        inj.device_lost("lane0", at="complete")
        assert cache.do_limit(_req(), [rule])[0].code is Code.OK
        assert cache.fault_domain.stat_faults[FAULT_DEVICE_LOST] == 1
    finally:
        inj.heal()
        cache.close()


def test_watchdog_tick_detects_hang_without_traffic():
    """The watchdog quarantines a stuck bank from the stamp check
    alone — no RPC has to sacrifice itself."""
    inj = DeviceFaultInjector()
    cache = make_cache(inj, deadline=0.15)
    mgr = Manager()
    rule = _rule(mgr)
    try:
        assert cache.do_limit(_req(), [rule])[0].code is Code.OK
        inj.hang("lane0")
        # Submit in a background thread (the RPC will be answered by
        # the fallback once the watchdog quarantines).
        got = {}

        def rpc():
            got["status"] = cache.do_limit(_req(), [rule])[0]

        t = threading.Thread(target=rpc)
        t.start()
        deadline = time.monotonic() + 5
        while (
            not cache.fault_domain.is_quarantined(0)
            and time.monotonic() < deadline
        ):
            cache.fault_domain.tick()
            time.sleep(0.02)
        assert cache.fault_domain.is_quarantined(0)
        t.join(timeout=5)
        assert not t.is_alive()
        assert got["status"].code is Code.OK
    finally:
        inj.heal()
        cache.close()


class _GatedKernelEngine(CounterEngine):
    """CounterEngine whose jitted serving call can be held open from
    the test — the stand-in for a kernel call that takes longer than
    the deadline (XLA compilation when the shape is new, a wedged
    device when it is not)."""

    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        self.gate = threading.Event()
        self.gate.set()
        self.entered = threading.Event()
        real = self.model.step_counters_unique_packed

        def gated(*args):
            self.entered.set()
            assert self.gate.wait(30)
            return real(*args)

        self.model.step_counters_unique_packed = gated


def _hold_one_request(cache, rule, engine, req):
    """Start `req` on a thread and return once its kernel call is
    parked at the gate."""
    got = {}
    engine.entered.clear()
    engine.gate.clear()
    limits = [rule] * len(req.descriptors)
    t = threading.Thread(
        target=lambda: got.update(status=cache.do_limit(req, limits)[0])
    )
    t.start()
    assert engine.entered.wait(10)
    return t, got


def test_deadline_spares_first_seen_shape_and_binds_repeat_shape():
    """The kernel deadline is per SHAPE, not per bank: a first-seen
    (bucket, dtype) shape that outlasts it is compilation and must not
    quarantine — however many other shapes the bank has completed —
    while the same overrun on a shape that has completed before is a
    hang.  Synthetic stamp clock: no real time passes."""
    from ratelimit_tpu.utils.time import FakeMonotonicClock

    clock = FakeMonotonicClock()
    engine = _GatedKernelEngine(num_slots=256, buckets=(8, 32))
    cache = TpuRateLimitCache(
        engine,
        time_source=PinnedTimeSource(1234),
        batch_window_us=100,
        kernel_deadline_s=0.25,
        fault_clock=clock,
        fault_interval_s=0,  # tick() manually
        fault_snapshot_interval_s=1000.0,
    )
    mgr = Manager()
    rule = _rule(mgr)
    fd = cache.fault_domain
    small = _req()  # 1 lane -> bucket 8
    big = RateLimitRequest(  # 9 distinct keys -> bucket 32
        "d", [Descriptor.of(("k", f"v{i}")) for i in range(9)], 1
    )
    try:
        fd.snapshot_now()  # keep tick() from queueing one behind the gate
        # First-seen shape #1, held far past the deadline: not a hang.
        t, got = _hold_one_request(cache, rule, engine, small)
        clock.advance(10.0)
        fd.tick()
        assert not fd.is_quarantined(0)
        engine.gate.set()
        t.join(10)
        assert got["status"].code is Code.OK
        assert engine.placement()["shapes_compiled"] == 1

        # First-seen shape #2 on a bank that HAS completed launches
        # (the old per-bank grace would have called this a hang).
        t, got = _hold_one_request(cache, rule, engine, big)
        clock.advance(10.0)
        fd.tick()
        assert not fd.is_quarantined(0)
        engine.gate.set()
        t.join(10)
        assert got["status"].code is Code.OK
        assert engine.placement()["shapes_compiled"] == 2
        assert fd.stat_faults[FAULT_HANG] == 0

        # Repeat of shape #1, same overrun: a hang.
        t, got = _hold_one_request(cache, rule, engine, small)
        clock.advance(10.0)
        fd.tick()
        assert fd.is_quarantined(0)
        assert fd.stat_faults[FAULT_HANG] == 1
        engine.gate.set()
        t.join(10)
        assert not t.is_alive()
        assert got["status"].code is Code.OK  # answered by the mirror
    finally:
        engine.gate.set()
        cache.close()


def test_host_work_inside_a_launch_is_not_on_the_deadline_clock():
    """Slot assignment runs inside the same engine call as the kernel
    launch, and at a million keys one native-table rehash outlasts the
    deadline (chip_smoke found the bank quarantining itself there).
    Only the device call itself is timed: host work holding a launch
    of a PROVEN shape for ten deadlines is not a hang."""
    from ratelimit_tpu.utils.time import FakeMonotonicClock

    gate, entered = threading.Event(), threading.Event()
    gate.set()

    class _SlowHostEngine(CounterEngine):
        def submit_packed(self, now, blob, meta, *watch):
            entered.set()
            assert gate.wait(30)  # the rehash
            return super().submit_packed(now, blob, meta, *watch)

    clock = FakeMonotonicClock()
    cache = TpuRateLimitCache(
        _SlowHostEngine(num_slots=256, buckets=(8,)),
        time_source=PinnedTimeSource(1234),
        batch_window_us=100,
        kernel_deadline_s=0.25,
        fault_clock=clock,
        fault_interval_s=0,
        fault_snapshot_interval_s=1000.0,
    )
    mgr = Manager()
    rule = _rule(mgr)
    fd = cache.fault_domain
    try:
        fd.snapshot_now()
        assert cache.do_limit(_req(), [rule])[0].code is Code.OK  # proven
        got = {}
        entered.clear()
        gate.clear()
        t = threading.Thread(
            target=lambda: got.update(status=cache.do_limit(_req(), [rule])[0])
        )
        t.start()
        assert entered.wait(10)
        clock.advance(2.5)
        fd.tick()
        assert not fd.is_quarantined(0)
        gate.set()
        t.join(10)
        assert got["status"].code is Code.OK
        assert fd.stat_faults[FAULT_HANG] == 0
    finally:
        gate.set()
        cache.close()


def test_slow_collector_is_not_a_device_hang():
    """A bank whose collector is busy with host work (a snapshot of a
    million-key table, a deep queue) makes RPCs wait longer than the
    kernel deadline without any device call being stuck: the RPCs
    keep waiting (dispatch timeout), nothing is quarantined."""
    cache = make_cache(deadline=0.1)
    mgr = Manager()
    rule = _rule(mgr)
    try:
        assert cache.do_limit(_req(), [rule])[0].code is Code.OK
        cache.fault_domain.snapshot_now()
        release = threading.Event()
        d = next(iter(cache._dispatchers.values()))
        blocker = threading.Thread(
            target=lambda: d.run_on_thread(lambda: release.wait(10))
        )
        blocker.start()
        got = {}
        t = threading.Thread(
            target=lambda: got.update(status=cache.do_limit(_req(), [rule])[0])
        )
        t.start()
        time.sleep(0.5)  # five deadlines
        cache.fault_domain.tick()
        assert t.is_alive()  # still waiting, not failed over
        assert not cache.fault_domain.is_quarantined(0)
        release.set()
        t.join(10)
        blocker.join(10)
        assert got["status"].code is Code.OK
        assert cache.fault_domain.stat_faults[FAULT_HANG] == 0
        assert cache.fault_domain.stat_fallback_decisions == 0
    finally:
        cache.close()


# ---------------------------------------------------------------------------
# a collection is not a hang; a snapshot that is not served is counted
# ---------------------------------------------------------------------------


def _gated_cache(deadline: float):
    engine = _GatedKernelEngine(num_slots=256, buckets=(8,))
    cache = TpuRateLimitCache(
        engine,
        time_source=PinnedTimeSource(1234),
        batch_window_us=100,
        kernel_deadline_s=deadline,
        fault_interval_s=0,  # tick() manually
        fault_snapshot_interval_s=1000.0,
    )
    return cache, engine, _rule(Manager())


def test_a_collection_inside_a_device_call_is_not_a_hang_a_stuck_call_is():
    """A full collection stops every Python thread: a device call of a
    proven shape that stays open for its length is the interpreter's
    time, not the device's (counted as gc_excused); the same call open
    as long with no collection running is a hang."""
    cache, engine, rule = _gated_cache(deadline=0.15)
    fd = cache.fault_domain
    req = RateLimitRequest("d", [Descriptor.of(("k", "v"))], 1)

    def slow_collection(phase, info):  # inside rl.bg.gc's bracket
        if phase == "start" and info["generation"] == 2:
            time.sleep(0.5)

    SPANS.watch_gc()
    gc.callbacks.append(slow_collection)
    try:
        assert cache.do_limit(req, [rule])[0].code is Code.OK  # shape proven
        fd.snapshot_now()
        before = SPANS.summary()["count"][BG_GC]
        t, got = _hold_one_request(cache, rule, engine, req)
        gc.collect()
        assert SPANS.summary()["count"][BG_GC] == before + 1
        d = next(iter(cache._dispatchers.values()))
        assert d.stuck_age(excuse_gc=False) > 0.5 > 0.15 > d.stuck_age()
        fd.tick()
        assert not fd.is_quarantined(0)
        assert fd.stat_gc_excused == 1
        assert fd.summary()["gc_excused"] == 1
        engine.gate.set()
        t.join(10)
        assert got["status"].code is Code.OK
        assert fd.stat_faults[FAULT_HANG] == 0

        gc.callbacks.remove(slow_collection)
        t, got = _hold_one_request(cache, rule, engine, req)
        time.sleep(0.3)  # two deadlines, no collection
        fd.tick()
        assert fd.is_quarantined(0)
        assert fd.stat_faults[FAULT_HANG] == 1
        assert fd.stat_gc_excused == 1
        engine.gate.set()
        t.join(10)
        assert got["status"].code is Code.OK  # answered by the mirror
    finally:
        if slow_collection in gc.callbacks:
            gc.callbacks.remove(slow_collection)
        SPANS.watch_gc(False)
        engine.gate.set()
        cache.close()


def test_a_snapshot_the_collector_cannot_serve_counts_one_timeout():
    """The grab waits behind the collector's queue; past its timeout it
    is counted (ratelimit.tpu.fault.snapshot_timeouts), not a fault.
    Served, it records the entries it holds and the collector's share
    of the work (rl.bg.snapshot.grab)."""
    cache, engine, rule = _gated_cache(deadline=0.05)  # timeout max(1, 4 x 0.05) = 1 s
    fd = cache.fault_domain
    store = Manager().store
    fd.register_stats(store)
    release = threading.Event()
    d = next(iter(cache._dispatchers.values()))
    blocker = threading.Thread(target=lambda: d.run_on_thread(lambda: release.wait(30)))
    try:
        for value in ("a", "b", "c"):
            req = RateLimitRequest("d", [Descriptor.of(("k", value))], 1)
            assert cache.do_limit(req, [rule])[0].code is Code.OK
        grabs = SPANS.summary()["count"][BG_SNAPSHOT_GRAB]
        assert fd.snapshot_now() == 1
        assert fd.snapshot_entries() == 3
        assert SPANS.summary()["count"][BG_SNAPSHOT_GRAB] == grabs + 1
        blocker.start()
        assert fd.snapshot_now() == 0  # one second behind the blocker
        assert fd.stat_snapshot_timeouts == 1
        assert fd.summary()["snapshot_timeouts"] == 1
        assert fd.summary()["snapshot_entries"] == 3
        counters = store.counters()
        assert counters["ratelimit.tpu.fault.snapshot_timeouts"] == 1
        assert not fd.is_quarantined(0) and sum(fd.stat_faults.values()) == 0
    finally:
        release.set()
        if blocker.is_alive():
            blocker.join(10)
        cache.close()


# ---------------------------------------------------------------------------
# failure modes
# ---------------------------------------------------------------------------


def test_mode_allow_answers_ok_without_stats():
    inj = DeviceFaultInjector()
    cache = make_cache(inj, mode="allow")
    mgr = Manager()
    rule = _rule(mgr)
    try:
        inj.raise_error("lane0")
        before = {
            k: v for k, v in mgr.store.counters().items() if "over_limit" in k
        }
        for _ in range(50):  # far past the limit of 20
            assert cache.do_limit(_req(), [rule])[0].code is Code.OK
        after = {
            k: v for k, v in mgr.store.counters().items() if "over_limit" in k
        }
        assert before == after  # no rule stats moved for unevaluated traffic
    finally:
        inj.heal()
        cache.close()


def test_mode_deny_answers_over_limit_but_not_shadow():
    inj = DeviceFaultInjector()
    cache = make_cache(inj, mode="deny")
    mgr = Manager()
    rule = _rule(mgr)
    shadow_rule = _rule(mgr, "shadowed")
    try:
        inj.raise_error("lane0")
        assert cache.do_limit(_req(), [rule])[0].code is Code.OVER_LIMIT
        s = cache.do_limit(_req("shadowed"), [shadow_rule])[0]
        assert s.code is Code.OK  # shadow rules never enforce
    finally:
        inj.heal()
        cache.close()


# ---------------------------------------------------------------------------
# supervised warm restart
# ---------------------------------------------------------------------------


def test_warm_restart_restores_counters_no_window_restart():
    """The acceptance envelope: snapshot -> fault -> fallback counts ->
    supervised restart imports the mirror -> the fixed-limit key
    admits EXACTLY its limit across the whole episode."""
    inj = DeviceFaultInjector()
    cache = make_cache(inj, deadline=0.2)
    mgr = Manager()
    rule = _rule(mgr)
    fd = cache.fault_domain
    try:
        admitted = 0
        for _ in range(5):
            admitted += cache.do_limit(_req(), [rule])[0].code is Code.OK
        assert fd.snapshot_now() == 1
        inj.hang("lane0")
        for _ in range(10):
            admitted += cache.do_limit(_req(), [rule])[0].code is Code.OK
        assert fd.is_quarantined(0)
        inj.heal()
        # Drive the supervisor: backoff is 0.05s, so a tick after that
        # performs the restart (probe + mirror import + swap).
        deadline = time.monotonic() + 20
        while fd.is_quarantined(0) and time.monotonic() < deadline:
            time.sleep(0.06)
            fd.tick()
        assert not fd.is_quarantined(0)
        assert fd.stat_restarts == 1
        # Remaining budget enforced by the NEW device engine.
        for _ in range(20):
            admitted += cache.do_limit(_req(), [rule])[0].code is Code.OK
        assert admitted == 20
    finally:
        inj.heal()
        cache.close()


def test_request_that_waited_out_a_restart_is_answered_by_the_device():
    """A restart holds the bank's fallback lock for the whole counter
    import (seconds at a million keys) and every request for the bank
    queues behind it; when the lock opens the mirror is gone.  Those
    requests must be answered by the re-admitted device bank — they
    used to crash on the missing mirror (chip_smoke found it)."""
    inj = DeviceFaultInjector()
    cache = make_cache(inj, deadline=0.2)
    mgr = Manager()
    rule = _rule(mgr)
    fd = cache.fault_domain
    try:
        assert cache.do_limit(_req(), [rule])[0].code is Code.OK
        inj.raise_error("lane0")
        assert cache.do_limit(_req(), [rule])[0].code is Code.OK  # mirror
        assert fd.is_quarantined(0)
        inj.heal()
        rec = fd._records[0]
        importing, release = threading.Event(), threading.Event()
        real_export = rec.fallback.export_keys

        def slow_export(*a, **kw):  # runs under the fallback lock
            importing.set()
            assert release.wait(30)
            return real_export(*a, **kw)

        rec.fallback.export_keys = slow_export
        time.sleep(0.06)  # past the 0.05 s restart backoff
        restart = threading.Thread(target=fd.tick)
        restart.start()
        assert importing.wait(20)
        got = {}
        t = threading.Thread(
            target=lambda: got.update(status=cache.do_limit(_req(), [rule])[0])
        )
        t.start()
        time.sleep(0.2)
        assert t.is_alive()  # parked on the fallback lock
        release.set()
        restart.join(20)
        t.join(10)
        assert not t.is_alive() and not restart.is_alive()
        assert not fd.is_quarantined(0)
        assert got["status"].code is Code.OK
        # One episode, one restart: the waiting request did not fault
        # the fresh bank on its way through.
        assert sum(fd.stat_faults.values()) == 1
        assert fd.stat_restarts == 1
    finally:
        inj.heal()
        cache.close()


def test_fall_back_on_a_readmitted_bank_is_bounded_and_never_raises():
    """The leg of _fall_back a request takes when its bank was
    re-admitted under it (no mirror left): the device bank gets one
    try inside the caller's deadline; a fault there is recorded and
    answered by the new mirror; nothing escapes as an error."""
    inj = DeviceFaultInjector()
    cache = make_cache(inj, deadline=0.2)
    rule = _rule(Manager())
    fd = cache.fault_domain

    def fall_back(deadline):
        items, statuses, *_ = cache._prepare(_req(), [rule])
        bank, _engine, item = items[0]
        cache._fall_back(fd, bank, item, deadline)
        return statuses[0]

    try:
        # A closed bank has no mirror: the device answers.
        assert fall_back(None).code is Code.OK
        assert sum(fd.stat_faults.values()) == 0
        assert fd.stat_fallback_decisions == 0
        # The caller is out of time and the bank does not answer: the
        # failure-mode answer, at once, and no fault on the bank.
        inj.hang("lane0")
        t0 = time.monotonic()
        assert fall_back(time.monotonic() - 1.0).code is Code.OK
        assert time.monotonic() - t0 < 5.0  # not the 120 s dispatch timeout
        assert sum(fd.stat_faults.values()) == 0
        assert cache.stat_deadline_answers == 1
        inj.heal()
        # The re-admitted bank fails: a fault like any other, and the
        # mirror it leaves behind answers.
        inj.raise_error("lane0")
        assert fall_back(None).code is Code.OK
        assert fd.is_quarantined(0)
        assert fd.stat_faults[FAULT_EXCEPTION] == 1
        assert fd.stat_fallback_decisions == 1
    finally:
        inj.heal()
        cache.close()


def test_probe_failure_keeps_bank_quarantined():
    """Half-open discipline: while the device is still broken the
    restart probe fails, the bank stays on the fallback, and the
    backoff grows; once healed the next attempt re-admits."""
    inj = DeviceFaultInjector()

    def wrapped_factory(bank, old):
        from ratelimit_tpu.backends.fault_domain import (
            default_engine_factory,
        )

        return inj.wrap_engine("lane0", default_engine_factory(bank, old))

    cache = make_cache(
        inj,
        deadline=0.2,
        engine_factory=wrapped_factory,
        fault_probe_timeout_s=0.5,
    )
    mgr = Manager()
    rule = _rule(mgr)
    fd = cache.fault_domain
    try:
        assert cache.do_limit(_req(), [rule])[0].code is Code.OK
        inj.raise_error("lane0")
        assert cache.do_limit(_req(), [rule])[0].code is Code.OK  # fallback
        assert fd.is_quarantined(0)
        backoff0 = fd._records[0].backoff_s
        time.sleep(backoff0 + 0.02)
        fd.tick()  # probe against the still-raising replacement engine
        assert fd.is_quarantined(0)
        assert fd.stat_probe_failures == 1
        assert fd._records[0].backoff_s > backoff0
        inj.heal()
        deadline = time.monotonic() + 20
        while fd.is_quarantined(0) and time.monotonic() < deadline:
            time.sleep(0.06)
            fd.tick()
        assert not fd.is_quarantined(0)
        assert fd.stat_restarts == 1
        assert cache.do_limit(_req(), [rule])[0].code is Code.OK
    finally:
        inj.heal()
        cache.close()


# ---------------------------------------------------------------------------
# deadline satellites
# ---------------------------------------------------------------------------


def test_wait_never_sleeps_past_caller_deadline_without_fault_domain():
    """The service-side twin of the cluster's
    test_retry_never_sleeps_past_caller_deadline: even with the fault
    domain OFF, a hung dispatch answers per DEVICE_FAILURE_MODE by the
    caller's deadline instead of burning the 120 s dispatch timeout."""
    inj = DeviceFaultInjector()
    engine = inj.wrap_engine("lane0", CounterEngine(num_slots=256, buckets=(8,)))
    cache = TpuRateLimitCache(
        engine,
        time_source=PinnedTimeSource(1234),
        batch_window_us=100,
        dispatch_timeout_s=30.0,
        kernel_deadline_s=0.0,  # fault domain OFF
        device_failure_mode="allow",
    )
    mgr = Manager()
    rule = _rule(mgr)
    try:
        assert cache.do_limit(_req(), [rule])[0].code is Code.OK
        inj.hang("lane0")
        req = _req()
        req.deadline = time.monotonic() + 0.3
        t0 = time.monotonic()
        status = cache.do_limit(req, [rule])[0]
        elapsed = time.monotonic() - t0
        assert elapsed < 1.5, elapsed
        assert status.code is Code.OK  # allow
        assert cache.stat_deadline_answers == 1
        assert cache.fault_domain is None
    finally:
        inj.heal()
        cache.close()


def test_caller_deadline_shorter_than_kernel_deadline_does_not_fault():
    """A caller-bound timeout answers the RPC but must NOT quarantine
    the (possibly just slow) bank."""
    inj = DeviceFaultInjector()
    cache = make_cache(inj, mode="deny", deadline=5.0)
    mgr = Manager()
    rule = _rule(mgr)
    try:
        assert cache.do_limit(_req(), [rule])[0].code is Code.OK
        inj.hang("lane0")
        req = _req()
        req.deadline = time.monotonic() + 0.2
        t0 = time.monotonic()
        status = cache.do_limit(req, [rule])[0]
        assert time.monotonic() - t0 < 1.5
        assert status.code is Code.OVER_LIMIT  # deny
        assert not cache.fault_domain.is_quarantined(0)
        assert cache.stat_deadline_answers == 1
    finally:
        inj.heal()
        cache.close()


# ---------------------------------------------------------------------------
# observability
# ---------------------------------------------------------------------------


def test_fallback_stamps_flight_code():
    inj = DeviceFaultInjector()
    cache = make_cache(inj)
    cache.flight = make_flight_recorder(64)
    mgr = Manager()
    rule = _rule(mgr)
    try:
        inj.raise_error("lane0")
        status = cache.do_limit(_req(), [rule])[0]
        # The transport stamps after the decision; mimic it on the
        # same thread (the note is thread-local).
        cache.flight.record("d", int(status.code), 1, 1.0)
        rec = cache.flight.snapshot_dicts()[0]
        assert rec["code"] == FLIGHT_CODE_FALLBACK
        assert rec["fallback"] is True
        # The note is CONSUMED: the next record is a plain decision.
        cache.flight.record("d", int(Code.OK), 1, 1.0)
        assert "fallback" not in cache.flight.snapshot_dicts()[0]
    finally:
        inj.heal()
        cache.close()


def test_fault_counters_and_debug_summary():
    inj = DeviceFaultInjector()
    cache = make_cache(inj)
    mgr = Manager()
    cache.register_stats(mgr.store)
    rule = _rule(mgr)
    try:
        inj.raise_error("lane0")
        cache.do_limit(_req(), [rule])
        counters = mgr.store.counters()
        assert counters["ratelimit.tpu.fault.exception"] == 1
        assert counters["ratelimit.tpu.fault.fallback_decisions"] >= 1
        gauges = mgr.store.snapshot()
        assert gauges["ratelimit.tpu.fault.quarantined_banks"] == 1
        summary = cache.fault_domain.summary()
        assert summary["failure_mode"] == "host"
        # Where it ran, as JAX reports it (not as BACKEND_TYPE says).
        assert summary["device"] == {
            "platform": "cpu",
            "device_kind": "cpu",
            "device_count": 8,
        }
        bank = summary["banks"][0]
        assert bank["slot_table"] in ("native", "python")
        assert bank["state_devices"] == ["cpu:0"]
        assert bank["state"] == "quarantined"
        assert bank["fault_kind"] == "exception"
        assert bank["mirror_live_keys"] >= 0
    finally:
        inj.heal()
        cache.close()


def test_swap_safe_gauges_follow_restart():
    """bank gauges resolve the engine by INDEX: after a warm restart
    they must read the NEW engine, not the dead one."""
    inj = DeviceFaultInjector()
    cache = make_cache(inj, deadline=0.2)
    mgr = Manager()
    cache.register_stats(mgr.store)
    rule = _rule(mgr)
    fd = cache.fault_domain
    try:
        for _ in range(3):
            cache.do_limit(_req(), [rule])
        inj.raise_error("lane0")
        cache.do_limit(_req(), [rule])
        inj.heal()
        deadline = time.monotonic() + 20
        while fd.is_quarantined(0) and time.monotonic() < deadline:
            time.sleep(0.06)
            fd.tick()
        assert not fd.is_quarantined(0)
        cache.do_limit(_req(), [rule])
        cache.flush()
        # The new engine's live_keys gauge must be non-zero (the old
        # object would report its frozen pre-fault state or worse).
        assert (
            mgr.store.snapshot()["ratelimit.tpu.bank0.live_keys"] >= 1
        )
    finally:
        inj.heal()
        cache.close()


def test_disabled_fault_domain_is_inert():
    """kernel_deadline_s=0 (the library default): no domain, no
    watchdog thread, decisions identical to the pre-PR-10 path."""
    cache = TpuRateLimitCache(
        CounterEngine(num_slots=256, buckets=(8,)),
        time_source=PinnedTimeSource(1234),
        batch_window_us=100,
    )
    mgr = Manager()
    rule = _rule(mgr)
    try:
        assert cache.fault_domain is None
        codes = [cache.do_limit(_req(), [rule])[0].code for _ in range(25)]
        assert codes.count(Code.OK) == 20
        assert codes.count(Code.OVER_LIMIT) == 5
    finally:
        cache.close()


def test_bad_failure_mode_rejected():
    with pytest.raises(ValueError, match="DEVICE_FAILURE_MODE"):
        TpuRateLimitCache(
            CounterEngine(num_slots=64, buckets=(8,)),
            device_failure_mode="open",
        )
