"""Program spans, the light capture, the launch and request legs, and
the record background work leaves (observability/spans.py and the
seams that call it)."""

import glob
import os
import sys
import threading
import time

import numpy as np
import pytest

from ratelimit_tpu.api import Descriptor, RateLimitRequest
from ratelimit_tpu.backends.dispatcher import (
    BatchDispatcher,
    Lane,
    LaunchStamps,
    WorkItem,
)
from ratelimit_tpu.backends.engine import CounterEngine
from ratelimit_tpu.backends.fault_domain import FAULT_HANG
from ratelimit_tpu.backends.tpu_cache import TpuRateLimitCache
from ratelimit_tpu.config.loader import ConfigFile, load_config
from ratelimit_tpu.observability import (
    LAUNCH_DTYPE,
    EventJournal,
    LaunchRecorder,
)
from ratelimit_tpu.observability import spans as span_names
from ratelimit_tpu.observability.spans import SPANS, ProgramSpans
from ratelimit_tpu.stats.manager import Manager, StatsStore
from ratelimit_tpu.utils.time import PinnedTimeSource

YAML = """
domain: d
descriptors:
  - key: k
    rate_limit:
      unit: minute
      requests_per_unit: 20
"""


def _item(key="k1", got=None):
    def apply(decisions):
        if got is not None:
            got.append(np.asarray(decisions.codes).tolist())

    return WorkItem(
        now=1_000,
        lanes=[Lane(key, 1_060, 10, False, 1)],
        apply=apply,
        defer_apply=True,
    )


def _read_xplane(trace_dir):
    """{line index: [(name, start_ns, duration_ns, stats)]} of the
    host plane, rl.* events only."""
    try:
        from jaxlib._profile_data import ProfileData
    except ImportError:
        from jax.profiler import ProfileData
    (path,) = glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")
    )
    lines = {}
    for plane in ProfileData.from_file(path).planes:
        for i, line in enumerate(plane.lines):
            events = [
                (e.name, int(e.start_ns), int(e.duration_ns), dict(e.stats))
                for e in line.events
                if e.name.startswith("rl.")
            ]
            if events:
                lines[(plane.name, i)] = events
    return lines


@pytest.fixture
def journal(monkeypatch):
    j = EventJournal(64)
    monkeypatch.setattr(SPANS, "journal", j)
    return j


def _background_events(journal, what):
    return [
        e
        for e in journal.snapshot()
        if e["type"] == "background_work" and e["what"] == what
    ]


# ---------------------------------------------------------------------------
# A. spans in the profiler's own trace; B. the light capture
# ---------------------------------------------------------------------------


def test_capture_holds_a_launch_with_its_children_and_two_clock_marks(tmp_path):
    engine = CounterEngine(num_slots=256, buckets=(8,))
    d = BatchDispatcher(engine, batch_window_us=100)
    blocked = {}
    capture = threading.Thread(
        target=lambda: blocked.update(SPANS.capture(str(tmp_path), 0.6))
    )
    t_before = time.monotonic_ns()
    capture.start()
    try:
        deadline = time.monotonic() + 10
        while not SPANS.capturing and time.monotonic() < deadline:
            time.sleep(0.005)
        assert SPANS.capturing
        got = []
        item = _item(got=got)
        d.submit(item)
        item.wait(10)
        assert got == [[1]]
    finally:
        capture.join(30)
        d.stop()
    t_after = time.monotonic_ns()
    assert not capture.is_alive() and not SPANS.capturing
    assert blocked["start_trace_ms"] >= 0 and blocked["stop_trace_ms"] >= 0

    lines = _read_xplane(str(tmp_path))
    by_name = {}
    for key, events in lines.items():
        for name, start, dur, stats in events:
            by_name.setdefault(name.split(".17")[0], []).append(
                (key, start, start + dur, stats)
            )

    def one(name):
        (ev,) = by_name[name]
        return ev

    launch = one("rl.launch")
    assert launch[3]["launch_id"] == 1 and launch[3]["bank"] == 0
    for child in ("rl.launch.assign", "rl.launch.pack", "rl.launch.device_call"):
        c = one(child)
        assert c[0] == launch[0], f"{child} is on the collector's line"
        assert launch[1] <= c[1] and c[2] <= launch[2], f"rl.launch holds {child}"
    readback = one("rl.complete.readback")
    assert readback[0] != launch[0], "the completer has a line of its own"
    for name in ("rl.complete.decide", "rl.complete.signal"):
        assert one(name)[0] == readback[0]
    assert one("rl.complete.signal")[3]["launch_id"] == 1
    # rl.clock.<monotonic_ns>, right after start and right before stop:
    # the two give one offset between the trace's time and
    # CLOCK_MONOTONIC, which every other record of the program is on.
    marks = [
        (int(name[len("rl.clock."):]), start)
        for events in lines.values()
        for name, start, _dur, _stats in events
        if name.startswith("rl.clock.")
    ]
    assert len(marks) == 2
    (m0, s0), (m1, s1) = sorted(marks)
    assert t_before <= m0 < m1 <= t_after
    assert m1 - m0 >= 0.6e9
    assert abs((m1 - s1) - (m0 - s0)) < 5e6  # same offset, to 5 ms


def test_without_a_capture_a_span_builds_and_allocates_nothing():
    spans = ProgramSpans()
    assert spans.span(span_names.LAUNCH, 0, 7) is spans.span(span_names.GC)
    built = []
    assert not spans.capturing
    # One launch's worth of spans, many times over: the interpreter's
    # count of allocated blocks does not move.
    names = (
        span_names.LAUNCH,
        span_names.LAUNCH_ASSIGN,
        span_names.LAUNCH_PACK,
        span_names.LAUNCH_DEVICE_CALL,
        span_names.COMPLETE_READBACK,
        span_names.COMPLETE_DECIDE,
        span_names.COMPLETE_SIGNAL,
        span_names.COLLECT_IDLE,
        span_names.COMPLETE_IDLE,
    )
    span = spans.span

    def launches(n):
        for i in range(n):
            for name in names:
                with span(name, 0, 5):
                    pass

    launches(100)
    before = sys.getallocatedblocks()
    launches(2000)
    grown = sys.getallocatedblocks() - before
    assert grown <= 8, grown
    # ... and while one runs, the same call builds an annotation.
    spans._annotation = lambda name, **stats: built.append((name, stats))
    spans.span(span_names.LAUNCH, 2, 9)
    spans.span(span_names.GC, 2)
    spans.span(span_names.COLLECT_WINDOW)
    assert built == [
        ("rl.launch", {"bank": 2, "launch_id": 9}),
        ("rl.gc", {"bank": 2}),
        ("rl.collect.window", {}),
    ]


# ---------------------------------------------------------------------------
# C. the legs of a launch and of a request
# ---------------------------------------------------------------------------


def test_launch_record_legs_lie_inside_their_phases():
    engine = CounterEngine(num_slots=256, buckets=(8,))
    d = BatchDispatcher(engine, batch_window_us=100)
    lr = LaunchRecorder(64)
    d.launches = lr
    d.launch_bank = 3
    try:
        for i in range(12):
            item = _item(key=f"key{i % 4}")
            d.submit(item)
            item.wait(10)
            assert item.submit_ns <= item.launch.launched_ns
            assert item.launch.launched_ns <= item.launch.signal_ns <= item.woke_ns
            assert (item.launch.bank, item.launch.launch_id) == (3, i + 1)
    finally:
        d.stop()
    live = lr.snapshot()
    assert live.dtype == LAUNCH_DTYPE and len(live) == 12
    assert live["launch_id"].tolist() == list(range(1, 13))
    for field in (
        "assign_ns", "device_submit_ns", "handoff_ns", "readback_ns", "decide_ns",
    ):
        assert (live[field] >= 0).all(), field
    assert (live["assign_ns"] > 0).all() and (live["readback_ns"] > 0).all()
    assert (live["assign_ns"] + live["device_submit_ns"] <= live["launch_ns"]).all()
    assert (live["readback_ns"] + live["decide_ns"] <= live["complete_ns"]).all()
    # The JSON view keeps the fields the benchmark reads and gains the legs.
    row = lr.snapshot_dicts()[-1]
    for key in ("queue_wait_us", "launch_us", "complete_us", "lanes", "items", "ts_ns", "outcome"):
        assert key in row
    assert row["launch_id"] == 12 and row["outcome"] == "ok"
    assert row["assign_us"] + row["device_submit_us"] <= row["launch_us"] + 0.2


def test_a_request_leaves_its_legs_and_the_tracer_reads_the_same_stamps():
    from ratelimit_tpu.observability import TRACER

    mgr = Manager()
    cfg = load_config([ConfigFile("config.c", YAML)], mgr)
    cache = TpuRateLimitCache(
        CounterEngine(num_slots=256, buckets=(8,)),
        time_source=PinnedTimeSource(1234),
        batch_window_us=100,
    )
    TRACER.configure(sample_rate=1.0, enabled=True)
    TRACER.clear()
    try:
        request = RateLimitRequest("d", [Descriptor.of(("k", "x"))], 1)
        t_in = time.monotonic_ns()
        with TRACER.start_span("test.root"):
            cache.do_limit_resolved(request, cfg)
        t_out = time.monotonic_ns()
    finally:
        TRACER.configure(sample_rate=0.0)
        cache.close()
    queued_ns, signal_ns, woke_ns = request.legs
    assert t_in <= queued_ns <= signal_ns <= woke_ns <= t_out
    (trace,) = TRACER.recent()
    spans = {s["name"]: s for s in trace.as_dict()["spans"]}
    TRACER.clear()
    for name in ("backend.dispatch", "kernel.step", "wake"):
        assert name in spans, sorted(spans)
        assert spans[name]["duration_ms"] >= 0
    assert spans["wake"]["duration_ms"] == pytest.approx(
        (woke_ns - signal_ns) / 1e6, abs=1e-3
    )


def test_handler_observes_the_request_legs_once_each():
    from ratelimit_tpu.server.grpc_server import ServerReporter

    store = StatsStore()
    reporter = ServerReporter(store)
    ms = 1_000_000
    # executor 1 ms before entry; service in at +0.1 ms; queued at +0.5;
    # signalled at +3.0; woke at +3.25; service out at +3.5 ms.
    reporter.observe_legs(
        9 * ms, 10 * ms, 10 * ms + 100_000, 13 * ms + 500_000,
        (10 * ms + 500_000, 13 * ms, 13 * ms + 250_000),
    )
    h = store.histograms()
    base = "ratelimit_server.ShouldRateLimit."
    assert h[base + "pool_wait_ms"]["total_ms"] == pytest.approx(1.0)
    assert h[base + "prepare_ms"]["total_ms"] == pytest.approx(0.4)
    assert h[base + "wake_ms"]["total_ms"] == pytest.approx(0.25)
    assert h[base + "apply_ms"]["total_ms"] == pytest.approx(0.25)
    # Not through the stamping executor, answered without a launch: the
    # legs that did not happen are not observed as zeros.
    reporter.observe_legs(0, 10 * ms, 10 * ms, 11 * ms, (10 * ms + 1000, 0, 0))
    reporter.observe_legs(0, 10 * ms, 10 * ms, 11 * ms, None)
    h = store.histograms()
    assert h[base + "pool_wait_ms"]["count"] == 1
    assert h[base + "prepare_ms"]["count"] == 2
    assert h[base + "wake_ms"]["count"] == 1 and h[base + "apply_ms"]["count"] == 1


# ---------------------------------------------------------------------------
# D. background work and hangs leave a record
# ---------------------------------------------------------------------------


def _fault_cache(**kw):
    return TpuRateLimitCache(
        CounterEngine(num_slots=256, buckets=(8,)),
        time_source=PinnedTimeSource(1234),
        batch_window_us=100,
        kernel_deadline_s=0.25,
        device_failure_mode="host",
        fault_interval_s=0,  # no supervisor thread
        fault_snapshot_interval_s=1000.0,
        **kw,
    )


def test_a_snapshot_leaves_one_event_with_a_duration(journal):
    cache = _fault_cache()
    try:
        fd = cache.fault_domain
        before = SPANS.summary()
        assert fd.snapshot_now() == 1
    finally:
        cache.close()
    (snap,) = _background_events(journal, "rl.bg.snapshot")
    (token,) = _background_events(journal, "rl.call_token")
    assert snap["bank"] == token["bank"] == 0
    assert snap["thread"] == threading.current_thread().name
    assert token["thread"].startswith("tpu-dispatcher")
    # From asking for the token to holding the copy: the grab on the
    # collector lies inside the snapshot.
    assert snap["duration_ms"] >= token["duration_ms"] > 0
    assert snap["start_mono_ns"] <= token["start_mono_ns"]
    after = SPANS.summary()
    for what in ("rl.bg.snapshot", "rl.call_token"):
        assert after["count"][what] == before["count"][what] + 1
        assert after["total_ms"][what] > before["total_ms"][what]
    assert after["open"] == []


def test_an_incident_capture_leaves_one_event_with_a_duration(journal):
    from ratelimit_tpu.observability.detectors import AnomalyDetectors

    class Tripped:
        name = "always"

        def evaluate(self):
            return "tripped for the test"

    detectors = AnomalyDetectors(
        StatsStore(), [Tripped()], interval_s=0, events=journal
    )
    (incident,) = detectors.tick()
    assert incident["detector"] == "always"
    (ev,) = _background_events(journal, "rl.bg.incident_capture")
    assert ev["duration_ms"] > 0 and ev["start_mono_ns"] > 0
    assert ev["thread"] == threading.current_thread().name


def test_checkpoint_of_a_bank_is_background_work_with_children(journal, tmp_path):
    from ratelimit_tpu.backends.checkpoint import CheckpointManager

    cache = _fault_cache()
    try:
        CheckpointManager(cache, str(tmp_path)).checkpoint()
    finally:
        cache.close()
    (ev,) = _background_events(journal, "rl.bg.checkpoint")
    assert ev["bank"] == 0 and ev["duration_ms"] > 0
    assert os.path.exists(tmp_path / "bank0.npz")


def test_ticks_reach_the_journal_only_when_slow(journal):
    with SPANS.background(span_names.BG_WATCHDOG_TICK):
        pass
    assert _background_events(journal, "rl.bg.watchdog_tick") == []
    with SPANS.background(span_names.BG_WATCHDOG_TICK):
        time.sleep(span_names.SLOW_TICK_NS / 1e9 + 0.002)
    (ev,) = _background_events(journal, "rl.bg.watchdog_tick")
    assert ev["duration_ms"] >= span_names.SLOW_TICK_NS / 1e6
    with pytest.raises(ValueError):
        with SPANS.background("rl.bg.not_in_the_table"):
            pass


def test_a_hang_carries_the_background_work_open_beside_it(journal):
    cache = _fault_cache()
    cache.fault_domain.events = journal
    try:
        fd = cache.fault_domain
        # One launch first, so both dispatcher threads have a last leg.
        mgr = Manager()
        cfg = load_config([ConfigFile("config.c", YAML)], mgr)
        cache.do_limit_resolved(
            RateLimitRequest("d", [Descriptor.of(("k", "x"))], 1), cfg
        )
        assert "last_hang" not in fd.summary()["banks"][0]
        with SPANS.background(span_names.BG_SNAPSHOT, 0):
            time.sleep(0.01)
            fd.record_fault(0, FAULT_HANG, fd.hang_error(4.14))
        summary = fd.summary()
    finally:
        cache.close()
    hang = summary["banks"][0]["last_hang"]
    assert "stuck 4.140s" in hang["error"]
    (work,) = hang["during"]["background"]
    assert work["what"] == "rl.bg.snapshot" and work["bank"] == 0
    assert work["thread"] == threading.current_thread().name
    assert work["open_s"] >= 0.01
    threads = {t["thread"]: t for t in hang["during"]["threads"]}
    assert threads["collector"]["last_leg"] == "rl.launch.device_call"
    assert threads["completer"]["last_leg"] == "rl.complete.signal"
    assert threads["completer"]["last_leg_ended_s_ago"] >= 0
    (quarantine,) = [
        e for e in journal.snapshot() if e["type"] == "bank_quarantine"
    ]
    assert quarantine["during"] == hang["during"]
    # The snapshot has ended since: /debug/faults shows nothing open.
    assert summary["background"]["open"] == []
    # A fault of another kind asks for no witness.
    cache2 = _fault_cache()
    try:
        cache2.fault_domain.record_fault(0, "exception", RuntimeError("x"))
        assert "last_hang" not in cache2.fault_domain.summary()["banks"][0]
    finally:
        cache2.close()


def test_launch_stamps_default_names_no_launch():
    s = LaunchStamps()
    assert (s.bank, s.launch_id, s.launched_ns, s.signal_ns) == (-1, -1, 0, 0)


def test_full_garbage_collections_are_background_work(journal):
    import gc

    spans = ProgramSpans()
    spans.journal = journal
    spans.watch_gc()
    try:
        before = spans.summary()["count"]["rl.bg.gc"]
        gc.collect(0)
        gc.collect(1)
        assert spans.summary()["count"]["rl.bg.gc"] == before  # young generations: not recorded
        gc.collect()  # a full collection
        after = spans.summary()
        assert after["count"]["rl.bg.gc"] == before + 1
        assert after["total_ms"]["rl.bg.gc"] > 0 and after["open"] == []
    finally:
        spans.watch_gc(False)
    assert spans._on_gc not in gc.callbacks
    gc.collect()
    assert spans.summary()["count"]["rl.bg.gc"] == before + 1
    # Like the ticks, a collection reaches the journal only when slow.
    assert all(
        e["duration_ms"] >= span_names.SLOW_TICK_NS / 1e6
        for e in _background_events(journal, "rl.bg.gc")
    )


def test_a_collection_tripped_inside_background_work_does_not_wedge_it(journal):
    """A full collection starts on whichever thread allocates next —
    also inside background()'s own lock or the journal's.  With every
    few allocations tripping one (thresholds 1, 1, 1 over a frozen
    heap: the interpreter skips full collections while the old
    generation dwarfs what is new), background work, the journal and
    the read surface must all still come back."""
    import gc

    spans = ProgramSpans()
    spans.journal = journal
    done = threading.Event()

    def work():
        old = gc.get_threshold()
        gc.freeze()
        gc.collect()
        spans.watch_gc()
        gc.set_threshold(1, 1, 1)
        try:
            for _ in range(20):
                with spans.background(span_names.BG_WATCHDOG_TICK):
                    with spans.background(span_names.BG_SNAPSHOT, 0):
                        spans.open_work()
                spans.summary()
        finally:
            gc.set_threshold(*old)
            spans.watch_gc(False)
            gc.unfreeze()
        done.set()

    t = threading.Thread(target=work, daemon=True)
    t.start()
    assert done.wait(30), "background work deadlocked on its own collection"
    summary = spans.summary()
    assert summary["count"]["rl.bg.snapshot"] == 20 and summary["open"] == []
    assert summary["count"]["rl.bg.gc"] >= 5
    assert len(_background_events(journal, "rl.bg.snapshot")) == 20


def test_the_collection_callback_takes_neither_lock(journal):
    """Both locks held (non-reentrant: a second acquire would block
    for ever), the callback still runs to its end."""
    spans = ProgramSpans()
    spans.journal = journal
    done = threading.Event()

    def collect():
        with spans._lock, journal._lock:
            spans._on_gc("start", {"generation": 2})
            spans._on_gc("stop", {"generation": 2})
        done.set()

    threading.Thread(target=collect, daemon=True).start()
    assert done.wait(10), "the gc callback waits for a lock its own thread holds"
    assert spans.summary()["count"]["rl.bg.gc"] == 1


def test_a_slow_collection_reaches_the_journal_at_the_next_end(journal, monkeypatch):
    spans = ProgramSpans()
    spans.journal = journal
    monkeypatch.setattr(span_names, "SLOW_TICK_NS", 0)
    spans._on_gc("start", {"generation": 2})
    assert spans.summary()["count"]["rl.bg.gc"] == 0
    spans._on_gc("stop", {"generation": 2})
    assert spans.summary()["count"]["rl.bg.gc"] == 1
    assert _background_events(journal, "rl.bg.gc") == []  # no lock, no emit, in the callback
    with spans.background(span_names.BG_SNAPSHOT, 1):
        pass
    (event,) = _background_events(journal, "rl.bg.gc")
    assert event["duration_ms"] >= 0 and event["start_mono_ns"] > 0
    with pytest.raises(ValueError):
        spans.background(span_names.BG_GC).__enter__()
