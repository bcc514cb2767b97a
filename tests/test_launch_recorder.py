"""Launch flight recorder (observability/launches.py): wraparound,
concurrent stamping, the ``since=`` cursor, derived metric families,
the disabled (LAUNCH_RECORDER_SIZE=0) path, and the dispatcher/cache
stamping seams end to end."""

import threading

import numpy as np

from ratelimit_tpu.api import Descriptor, RateLimitRequest
from ratelimit_tpu.backends.dispatcher import BatchDispatcher, Lane, WorkItem
from ratelimit_tpu.backends.engine import CounterEngine
from ratelimit_tpu.backends.tpu_cache import TpuRateLimitCache
from ratelimit_tpu.config.loader import ConfigFile, load_config
from ratelimit_tpu.observability import (
    LAUNCH_DTYPE,
    OUTCOME_FAULT,
    OUTCOME_OK,
    LaunchRecorder,
    make_launch_recorder,
)
from ratelimit_tpu.stats.manager import Manager, StatsStore
from ratelimit_tpu.utils.time import FakeMonotonicClock


def test_disabled_mode_returns_none():
    assert make_launch_recorder(0) is None
    assert make_launch_recorder(-3) is None
    assert isinstance(make_launch_recorder(4), LaunchRecorder)


def test_record_and_snapshot_fields():
    clock = FakeMonotonicClock(10.0)
    lr = LaunchRecorder(16, clock=clock)
    lr.record(2, 0, 8, 3, 5, 1_500, 340_000, 90_000, OUTCOME_OK, 0xBEEF)
    live = lr.snapshot()
    assert live.dtype == LAUNCH_DTYPE
    assert len(live) == 1
    rec = live[0]
    assert rec["seq"] == 1
    assert rec["ts_ns"] == int(10.0 * 1e9)
    assert rec["bank"] == 2
    assert rec["lanes"] == 8
    assert rec["items"] == 3
    assert rec["dedup_groups"] == 5
    assert rec["queue_wait_ns"] == 1_500
    assert rec["launch_ns"] == 340_000
    assert rec["complete_ns"] == 90_000
    assert rec["outcome"] == OUTCOME_OK
    d = lr.snapshot_dicts()[0]
    assert d["algorithm"] == "fixed_window"  # algo id 0
    assert d["outcome"] == "ok"
    assert d["queue_wait_us"] == 1.5
    assert d["launch_us"] == 340.0
    assert d["complete_us"] == 90.0
    assert d["corr"] == f"{0xBEEF:016x}"


def test_wraparound_keeps_latest_records():
    lr = LaunchRecorder(8)
    for i in range(20):
        lr.record(0, 0, 1, i + 1, 1, 0, 0, 0, OUTCOME_OK)
    live = lr.snapshot()
    assert len(live) == 8
    assert live["seq"].tolist() == list(range(13, 21))
    assert live["items"].tolist() == list(range(13, 21))
    assert lr.stamped() == 20


def test_since_cursor_is_resumable():
    lr = LaunchRecorder(16)
    for i in range(5):
        lr.record(0, 0, 1, 1, 1, 0, 0, 0, OUTCOME_OK)
    first = lr.snapshot_dicts()
    assert [d["seq"] for d in first] == [1, 2, 3, 4, 5]
    cursor = first[-1]["seq"]
    assert lr.snapshot_dicts(since=cursor) == []
    lr.record(0, 0, 1, 1, 1, 0, 0, 0, OUTCOME_OK)
    assert [d["seq"] for d in lr.snapshot_dicts(since=cursor)] == [6]
    # limit= keeps the NEWEST rows of the window.
    assert [d["seq"] for d in lr.snapshot_dicts(limit=2)] == [5, 6]


def test_concurrent_stamping_from_many_threads():
    """Collector/completer contract: concurrent stampers never tear a
    record — every row satisfies a writer-enforced invariant
    (lanes == items * 7 + 1) and live seqs are unique and ordered."""
    lr = LaunchRecorder(256)
    n_threads, per_thread = 8, 2000
    start = threading.Barrier(n_threads)

    def stamp(tid: int):
        start.wait()
        for j in range(per_thread):
            x = tid * per_thread + j
            lr.record(0, 0, x * 7 + 1, x, 1, 0, 0, 0, OUTCOME_OK)

    threads = [
        threading.Thread(target=stamp, args=(t,)) for t in range(n_threads)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()

    live = lr.snapshot()
    assert len(live) == 256
    assert lr.stamped() == n_threads * per_thread
    seqs = live["seq"].tolist()
    assert len(set(seqs)) == len(seqs)
    assert seqs == sorted(seqs)
    assert (live["lanes"] == live["items"] * 7 + 1).all()


def test_p99_and_coalesce_exclude_non_ok():
    lr = LaunchRecorder(32)
    for i in range(10):
        lr.record(0, 0, 4, 4, 2, 0, 1_000 * (i + 1), 0, OUTCOME_OK)
    # A fault with a huge launch_ns must not poison the ok-only p99.
    lr.record(0, 0, 1, 1, 1, 0, 10_000_000, 0, OUTCOME_FAULT)
    assert lr.p99_launch_ns() <= 10_000
    # coalesce is over ALL live launches (faults included).
    assert lr.coalesce_ratio() == round((10 * 4 + 1) / 11, 3)


def test_register_stats_family_and_items_by_algo():
    lr = LaunchRecorder(32)
    store = StatsStore()
    lr.register_stats(store)
    lr.record(0, 0, 4, 3, 2, 0, 5_000, 0, OUTCOME_OK)
    lr.record(0, 0, 4, 5, 2, 0, 7_000, 0, OUTCOME_OK)
    assert store.gauges()["ratelimit.tpu.launch.capacity"] == 32
    assert store.counters()["ratelimit.tpu.launch.rate"] == 2
    assert store.gauges()["ratelimit.tpu.launch.p99_launch_ns"] <= 7_000
    assert store.float_gauges()["ratelimit.tpu.launch.coalesce_ratio"] == 4.0
    assert lr.items_by_algo()["fixed_window"] == 8


def test_dispatcher_stamps_real_launches():
    """The submit/launch/complete seams: a burst of items through a
    real BatchDispatcher lands as coalesced ok records with every
    phase field populated."""
    engine = CounterEngine(num_slots=64)
    d = BatchDispatcher(engine, batch_window_us=50_000, batch_limit=4096)
    lr = make_launch_recorder(64)
    d.launches = lr
    d.launch_bank = 3
    try:
        items = []
        for i in range(8):
            it = WorkItem(
                now=0,
                lanes=[
                    Lane(
                        key=f"k{i}_0",
                        expiry=60,
                        limit=10,
                        shadow=False,
                        hits=1,
                    )
                ],
                apply=lambda dec: None,
            )
            items.append(it)
            d.submit(it)
        d.flush()
        for it in items:
            it.wait(10.0)
    finally:
        d.stop()
    live = lr.snapshot()
    ok = live[live["outcome"] == OUTCOME_OK]
    assert len(ok) >= 1
    assert int(ok["items"].sum()) == 8
    assert int(ok["lanes"].sum()) == 8
    assert (ok["bank"] == 3).all()
    assert (ok["launch_ns"] > 0).all()
    assert (ok["complete_ns"] > 0).all()
    # submit() stamped submit_ns, so the collector derived a wait.
    assert (ok["queue_wait_ns"] > 0).all()
    assert (ok["dedup_groups"] > 0).all()


YAML = """
domain: d
descriptors:
  - key: k
    rate_limit:
      unit: minute
      requests_per_unit: 100
"""


def test_cache_attach_wires_recorder_and_decisions_unchanged(clock):
    """attach_launch_recorder reaches the live dispatchers, records
    carry the bank's algorithm name, and decisions match a
    recorder-less twin request for request."""
    mgr1, mgr2 = Manager(), Manager()
    plain = TpuRateLimitCache(
        CounterEngine(num_slots=256), time_source=clock, batch_window_us=500
    )
    recorded = TpuRateLimitCache(
        CounterEngine(num_slots=256), time_source=clock, batch_window_us=500
    )
    lr = make_launch_recorder(256)
    recorded.attach_launch_recorder(lr)
    try:
        cfg1 = load_config([ConfigFile("config.c", YAML)], mgr1)
        cfg2 = load_config([ConfigFile("config.c", YAML)], mgr2)
        desc = Descriptor.of(("k", "x"))
        rule1 = cfg1.get_limit("d", desc)
        rule2 = cfg2.get_limit("d", desc)
        for i in range(30):
            req = RateLimitRequest("d", [desc], 1)
            s1 = plain.do_limit(req, [rule1])
            s2 = recorded.do_limit(req, [rule2])
            assert s1[0].code == s2[0].code, i
            assert s1[0].limit_remaining == s2[0].limit_remaining, i
    finally:
        plain.close()
        recorded.close()
    assert lr.stamped() >= 1
    d = lr.snapshot_dicts()[-1]
    assert d["algorithm"] == "fixed_window"
    assert d["outcome"] == "ok"
    assert lr.items_by_algo()["fixed_window"] == 30


# -- the ledger beside each wall stamp (ISSUE 41) ------------------------

LEDGER = (
    "device_submit_cpu_ns", "readback_cpu_ns", "assign_gil_ns", "decide_gil_ns",
)


def test_the_ledger_fields_default_to_not_measured_and_are_left_out():
    """A record stamped without them (the fallback path, an older
    caller) reads -1 in every ledger field, and /debug/launches leaves
    those out instead of printing a negative time."""
    lr = LaunchRecorder(4)
    lr.record(0, 0, 8, 1, 8, 100, 200, 300, OUTCOME_OK)
    lr.record(
        0, 0, 8, 1, 8, 100, 200, 300, OUTCOME_OK,
        device_submit_cpu_ns=150_000, assign_gil_ns=2_500,
        decide_gil_ns=0,
    )
    bare, some = lr.snapshot()
    assert LAUNCH_DTYPE.names[-len(LEDGER):] == LEDGER
    assert [int(bare[f]) for f in LEDGER] == [-1] * len(LEDGER)
    d_bare, d_some = lr.snapshot_dicts()
    assert not any(f[:-2] + "us" in d_bare for f in LEDGER)
    assert d_some["device_submit_cpu_us"] == 150.0
    assert d_some["assign_gil_us"] == 2.5
    assert d_some["decide_gil_us"] == 0.0
    assert "readback_cpu_us" not in d_some
    # What was there reads as before.
    assert d_bare["launch_us"] == d_some["launch_us"] == 0.2


def test_without_the_traced_runs_switch_no_cpu_clock_is_read():
    """DEBUG_PROFILING unset: the dispatcher threads read no CPU clock
    (on the chip's host one read is a trap into gVisor's sentry) — the
    record's CPU fields say "not measured", the bank's wall / cpu sums
    stand still together; the GIL's return, a vDSO read, is always on."""
    from ratelimit_tpu.backends import native_slot_table

    engine = CounterEngine(num_slots=64)
    d = BatchDispatcher(engine, batch_window_us=100, batch_limit=4096)
    lr = make_launch_recorder(8)
    d.launches = lr
    try:
        it = WorkItem(
            now=0,
            lanes=[Lane(key="k", expiry=60, limit=10, shadow=False, hits=1)],
            apply=lambda dec: None,
        )
        d.submit(it)
        it.wait(10.0)
        d.flush()
    finally:
        d.stop()
    (rec,) = lr.snapshot()
    assert rec["device_submit_cpu_ns"] == rec["readback_cpu_ns"] == -1
    assert engine.total_submit_wall_ns == engine.total_submit_cpu_ns == 0
    assert engine.total_readback_wall_ns == engine.total_ready_wall_ns == 0
    assert engine.total_readback_cpu_ns == engine.total_ready_cpu_ns == 0
    assert rec["device_submit_ns"] > 0 and rec["readback_ns"] > 0
    if native_slot_table.available():
        assert rec["assign_gil_ns"] >= 0 and engine.count_decide_gil == 1


def test_a_real_launch_fills_the_ledger_and_the_engines_counters():
    """Through a real BatchDispatcher with the traced run's switch
    (cpu_clock): every launch record carries the on-CPU time of both
    device-call brackets (never more than the bracket plus a clock's
    grain) and the GIL-return time of both native calls on the native
    table; the same sums stand in the engine's counters, and the
    arrived-copy pair holds the readbacks of exactly the launches
    readback_ready counted."""
    from ratelimit_tpu.backends import native_slot_table

    engine = CounterEngine(num_slots=64)
    d = BatchDispatcher(
        engine, batch_window_us=100, batch_limit=4096, cpu_clock=True
    )
    lr = make_launch_recorder(64)
    d.launches = lr
    try:
        for i in range(6):
            it = WorkItem(
                now=0,
                lanes=[Lane(key=f"k{i}", expiry=60, limit=10, shadow=False, hits=1)],
                apply=lambda dec: None,
            )
            d.submit(it)
            it.wait(10.0)
        d.flush()
    finally:
        d.stop()
    ok = lr.snapshot()
    ok = ok[ok["outcome"] == OUTCOME_OK]
    assert len(ok) == 6
    grain = 1_000_000  # a scheduler tick's worth of slack on CPU clocks
    assert (ok["device_submit_cpu_ns"] >= 0).all()
    assert (ok["device_submit_cpu_ns"] <= ok["device_submit_ns"] + grain).all()
    assert (ok["readback_cpu_ns"] >= 0).all()
    assert (ok["readback_cpu_ns"] <= ok["readback_ns"] + grain).all()
    if native_slot_table.available():
        assert (ok["assign_gil_ns"] >= 0).all()
        assert (ok["decide_gil_ns"] >= 0).all()
        assert engine.count_assign_gil == engine.count_decide_gil == 6
        assert engine.total_assign_gil_ns == int(ok["assign_gil_ns"].sum())
        assert engine.total_decide_gil_ns == int(ok["decide_gil_ns"].sum())
    else:
        assert (ok["assign_gil_ns"] == -1).all()
    assert engine.total_submit_wall_ns == int(ok["device_submit_ns"].sum())
    assert engine.total_submit_cpu_ns == int(ok["device_submit_cpu_ns"].sum())
    assert engine.total_readback_wall_ns == int(ok["readback_ns"].sum())
    assert engine.total_readback_cpu_ns == int(ok["readback_cpu_ns"].sum())
    # A launch whose copy had not arrived adds to neither side.
    assert 0 <= engine.stat_readback_ready <= 6
    assert 0 <= engine.total_ready_wall_ns <= engine.total_readback_wall_ns
    assert 0 <= engine.total_ready_cpu_ns <= engine.total_readback_cpu_ns
    assert (engine.total_ready_wall_ns > 0) == (engine.stat_readback_ready > 0)
    if engine.stat_readback_ready == 6:
        assert engine.total_ready_wall_ns == engine.total_readback_wall_ns
        assert engine.total_ready_cpu_ns == engine.total_readback_cpu_ns
