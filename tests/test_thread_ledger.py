"""What a wall-clock bracket is made of (ISSUE 41): the thread's
on-CPU clock and the GIL's return beside the device-call brackets' wall
stamps — read passively, by the thread that does the work — and the
same clock as a hang fault's witness, read by the watchdog.

The numbers a chip run gives are in PERF.md; here: the reader is right
about a busy loop and a sleep, from its own thread and from another,
the GIL's return is large beside a thread that holds the GIL and small
in an idle process, the instrumentation changes no answer and adds no
thread, and an injected hang leaves a ledger that says "off the CPU".
"""

import json
import os
import re
import sys
import threading
import time

import numpy as np
import pytest

from ratelimit_tpu.api import Code, Descriptor, RateLimitRequest
from ratelimit_tpu.backends import native_slot_table
from ratelimit_tpu.backends.dispatcher import LANE_DTYPE
from ratelimit_tpu.backends.engine import CallWatch, CounterEngine
from ratelimit_tpu.backends.fault_domain import FAULT_HANG
from ratelimit_tpu.backends.tpu_cache import TpuRateLimitCache
from ratelimit_tpu.cluster.faults import DeviceFaultInjector
from ratelimit_tpu.config.loader import ConfigFile, load_config
from ratelimit_tpu.stats.manager import Manager
from ratelimit_tpu.utils.threads import ThreadClock
from ratelimit_tpu.utils.time import PinnedTimeSource

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
needs_native = pytest.mark.skipif(
    not native_slot_table.available(), reason="no native library"
)


# -- the reader ---------------------------------------------------------------


def _spin(ns):
    t0 = time.monotonic_ns()
    while time.monotonic_ns() - t0 < ns:
        pass


TICK_NS = 10_000_000  # a thread CPU clock may advance in 10 ms steps (gVisor's does; PERF.md section 5)


def test_a_busy_loop_reads_on_cpu_about_wall():
    """Spin until the interpreter's own reading of this thread's CPU
    time (`time.thread_time_ns`) has advanced 300 ms — however long
    that takes on a core shared with five other test workers: the
    clock under test must agree with it to a tick, and can never have
    run ahead of the wall.  On a free core that is "about wall"; on a
    shared one the wall is only the upper bound."""
    clock = ThreadClock()
    t0, c0, own0 = time.monotonic_ns(), clock.ns(), time.thread_time_ns()
    while time.thread_time_ns() - own0 < 300_000_000:
        _spin(5_000_000)
    own, cpu, wall = time.thread_time_ns() - own0, clock.ns() - c0, time.monotonic_ns() - t0
    assert abs(cpu - own) <= TICK_NS, (wall, cpu, own)
    assert 300_000_000 - TICK_NS <= cpu <= wall + TICK_NS, (wall, cpu, own)


def test_a_sleep_reads_on_cpu_about_zero():
    """Half a second asleep: at most one tick of CPU (a coarse clock
    may charge the wake-up a whole one) and a fiftieth of the wall."""
    clock = ThreadClock()
    t0, c0 = time.monotonic_ns(), clock.ns()
    time.sleep(0.5)
    wall, cpu = time.monotonic_ns() - t0, clock.ns() - c0
    assert wall >= 500_000_000 and cpu <= TICK_NS + 0.02 * wall, (wall, cpu)


def test_it_is_the_threads_own_clock_and_another_thread_can_read_it():
    """``ns()`` from the owner agrees with ``time.thread_time_ns()``;
    from outside it is the OWNER's time that is read, not the reader's:
    the owner asleep, the reader spinning, it stands still."""
    made, go, done = [], threading.Event(), threading.Event()

    def owner():
        clock = ThreadClock()
        lo = time.thread_time_ns()
        mid = clock.ns()
        hi = time.thread_time_ns()
        made.extend([clock, lo <= mid <= hi])
        go.set()
        done.wait(10)

    t = threading.Thread(target=owner)
    t.start()
    go.wait(10)
    clock, own_agrees = made
    assert own_agrees
    first = clock.ns()
    _spin(50_000_000)  # this thread's CPU time, not the sleeper's
    assert 0 <= clock.ns() - first <= 5_000_000
    done.set()
    t.join()


def test_without_a_per_thread_clock_id_it_reads_none_and_nothing_raises(monkeypatch):
    def no_clock(ident):
        raise OSError("no clock id for a thread here")

    monkeypatch.setattr(time, "pthread_getcpuclockid", no_clock)
    assert ThreadClock().ns() is None
    monkeypatch.undo()
    clock = ThreadClock()
    monkeypatch.setattr(time, "clock_gettime_ns", no_clock)
    assert clock.ns() is None
    # The watch then has a wall clock and no split, and says so by
    # leaving the split out.
    monkeypatch.undo()
    monkeypatch.setattr(time, "pthread_getcpuclockid", no_clock)
    watch = CallWatch(time.monotonic)
    watch.bind()
    watch.begin(True)
    watch.glance()
    row = watch.ledger()
    watch.end()
    assert row["wall_ms"] >= 0 and "on_cpu_ms" not in row and "observed_ms" not in row


# -- the GIL's return ---------------------------------------------------------


def _assign_once(table, i, n=1):
    keys = b"".join(b"key%04d%05d" % (i, j) for j in range(n))
    table.assign_dedup_packed(
        np.frombuffer(keys, dtype=np.uint8),
        np.full(n, 12, np.int64),
        1_000,
        np.full(n, 2_000, np.int64),
        np.ones(n, np.uint32),
        np.full(n, 10, np.uint32),
    )
    return table.returned.gil_ns


def _decide_once(stamp, n=1):
    native_slot_table.decide_reconstruct(
        np.full(n, 3, np.uint32), np.ones(n, np.uint64),
        np.arange(n, dtype=np.int32), np.zeros(n, np.uint64),
        np.ones(n, np.uint32), np.full(n, 10, np.uint32),
        np.zeros(n, np.uint8), 0.8, int(Code.OK), int(Code.OVER_LIMIT),
        stamp,
    )
    return stamp.gil_ns


@needs_native
@pytest.mark.parametrize("call", ["assign", "decide"])
def test_the_gils_return_is_small_when_idle_and_large_beside_a_held_gil(call):
    """The C side stamps CLOCK_MONOTONIC as its last act; ctypes takes
    the GIL back before Python runs again.  Idle, that is microseconds.
    Beside a thread spinning in Python, a native call long enough for
    the spinner to take the GIL meanwhile (some thousands of lanes)
    returns into the GIL's queue and stands there for a switch
    interval (set to 20 ms here)."""
    table = native_slot_table.NativeSlotTable(1 << 16)
    stamp = native_slot_table.ReturnStamp()
    assert table.returned.gil_ns == stamp.gil_ns == -1
    if call == "assign":
        once = lambda i, n=1: _assign_once(table, i, n)  # noqa: E731
        big = 4096
    else:
        once = lambda i, n=1: _decide_once(stamp, n)  # noqa: E731
        big = 400_000
    idle = min(once(i) for i in range(20))
    assert 0 <= idle < 1_000_000, idle
    assert once(50, big) < 5_000_000  # a long call alone returns as fast

    stop = threading.Event()

    def spin():
        while not stop.is_set():
            pass

    old = sys.getswitchinterval()
    sys.setswitchinterval(0.02)
    t = threading.Thread(target=spin)
    t.start()
    try:
        held = max(once(100 + i, big) for i in range(5))
    finally:
        stop.set()
        t.join()
        sys.setswitchinterval(old)
    assert held >= 5_000_000 > idle, (idle, held)


def test_the_python_table_has_no_gil_return():
    engine = CounterEngine(num_slots=64, buckets=(8,), native_table=False)
    meta = np.zeros(1, dtype=LANE_DTYPE)
    meta[0] = (2_000_000_000, 1, 50, 2, 0, 0, 0)
    token = engine.submit_packed(1_000, b"ab", meta)
    assert engine.stat_assign_gil_ns == -1 and engine.count_assign_gil == 0
    engine.step_complete(token)
    # Nobody's watch asked for the CPU clock: not measured.
    assert engine.stat_device_submit_cpu_ns == engine.stat_readback_cpu_ns == -1
    watch = CallWatch(time.monotonic, cpu_clock=True)
    engine.step_complete(engine.submit_packed(1_001, b"ab", meta.copy(), watch), watch)
    assert engine.stat_device_submit_cpu_ns >= 0
    assert engine.stat_readback_cpu_ns >= 0
    assert engine.total_submit_wall_ns >= engine.stat_device_submit_ns > 0


# -- the watch's ledger -------------------------------------------------------


@pytest.mark.parametrize("cpu_clock", [True, False], ids=["traced", "untraced"])
@pytest.mark.parametrize("busy", [True, False], ids=["spinning", "asleep"])
def test_the_ledger_tells_on_cpu_from_off_since_the_watchdogs_glance(busy, cpu_clock):
    """The watchdog reads the stuck thread's CPU-time clock by its id:
    once as it first finds the bracket open (``glance``), once as it
    records the fault (``ledger``).  The difference over the
    ``observed_ms`` between the two is the thread's on-CPU time; the
    rest it stood off the CPU — the difference between a host that was
    computing and a call that never came back.  One variant: the
    traced run's own CPU stamps (``cpu_clock``) are for the bracket
    sums, and the witness reads the same either way."""
    watch = CallWatch(time.monotonic, cpu_clock=cpu_clock)
    assert watch.ledger() == {}  # nothing open, nobody bound
    opened, done = threading.Event(), threading.Event()

    def stuck():
        watch.bind()
        watch.last_gil_ns = 2_500
        watch.begin(True)
        opened.set()
        if busy:
            while not done.is_set():
                pass
        else:
            done.wait(10)
        watch.end()

    t = threading.Thread(target=stuck)
    t.start()
    opened.wait(10)
    assert "on_cpu_ms" not in watch.ledger()  # nobody has looked yet
    watch.glance()
    seen = watch._seen
    assert seen[0] == watch.wall0_ns != 0
    time.sleep(0.2)
    watch.glance()  # one look a bracket: the second changes nothing
    assert watch._seen == seen
    row = watch.ledger(watchdog_late_ms=0.25)
    done.set()
    t.join()
    watch.glance()  # nothing open: nothing read
    assert watch._seen == seen
    assert watch.ledger() == {}  # the bracket is closed
    assert set(row) == {
        "wall_ms", "observed_ms", "on_cpu_ms", "off_cpu_ms",
        "last_gil_return_us", "watchdog_late_ms",
    }
    assert 200 <= row["observed_ms"] <= row["wall_ms"]
    assert abs(row["on_cpu_ms"] + row["off_cpu_ms"] - row["observed_ms"]) <= 0.01
    assert row["last_gil_return_us"] == 2.5 and row["watchdog_late_ms"] == 0.25
    if busy:  # it shares the GIL with this thread's sleep only
        assert row["on_cpu_ms"] >= 0.5 * row["observed_ms"]
    else:
        assert row["on_cpu_ms"] <= 10
        assert row["off_cpu_ms"] >= 0.9 * row["observed_ms"]


class _Scripted:
    """A device array whose is_ready() answers what the test says."""

    def __init__(self, array, ready):
        self._array, self._ready = array, ready

    def is_ready(self):
        return self._ready

    def __array__(self, dtype=None, copy=None):
        return np.asarray(self._array)


@pytest.mark.parametrize(
    "arrived, counted",
    [((True, True, True), 1), ((True, False, True), 0), ((False, False, False), 0)],
    ids=["all-arrived", "one-late", "none-arrived"],
)
def test_the_arrived_copy_sums_hold_whole_launches_or_nothing(arrived, counted):
    """`readback_ready` counts a launch ALL of whose chunks had arrived;
    `readback_ready.wall_ns` / `.cpu_ns` add that launch's readback
    brackets whole, and nothing of a launch with a late chunk — so the
    sums and the count they are divided by cover the same launches.
    Plain sums both: the reader subtracts, nothing here clamps."""
    engine = CounterEngine(num_slots=256, buckets=(8,))
    lanes = 20  # three chunks of the one 8-lane bucket
    meta = np.zeros(lanes, dtype=LANE_DTYPE)
    meta[:] = (2_000_000_000, 1, 50, 3, 0, 0, 0)  # 3-byte keys, all distinct
    keys = b"".join(b"k%02d" % i for i in range(lanes))
    watch = CallWatch(time.monotonic, cpu_clock=True)
    token = engine.submit_packed(1_000, keys, meta, watch)
    hits, limits, shadow, chunks, now = token
    assert len(chunks) == len(arrived) == 3
    chunks = [
        (_Scripted(c[0], ok),) + tuple(c[1:]) for c, ok in zip(chunks, arrived)
    ]
    engine.step_complete((hits, limits, shadow, chunks, now), watch)
    assert engine.stat_readback_ready == counted
    assert engine.total_readback_wall_ns == engine.stat_readback_ns > 0
    assert engine.total_readback_cpu_ns == engine.stat_readback_cpu_ns >= 0
    if counted:
        assert engine.total_ready_wall_ns == engine.total_readback_wall_ns
        assert engine.total_ready_cpu_ns == engine.total_readback_cpu_ns
    else:
        assert engine.total_ready_wall_ns == engine.total_ready_cpu_ns == 0


YAML = """
domain: d
descriptors:
  - key: k
    rate_limit:
      unit: minute
      requests_per_unit: 20
"""


@pytest.mark.parametrize("traced", [True, False], ids=["DEBUG_PROFILING=1", "DEBUG_PROFILING=0"])
@pytest.mark.parametrize("seam, role", [("submit", "collector"), ("complete", "completer")])
def test_an_injected_hang_leaves_a_ledger_that_says_off_the_cpu(seam, role, traced):
    """The thread stands in its device call, asleep: by the time the
    deadline's clock (unchanged) declares the hang, the witness reads
    the stall as off the CPU and on_cpu_ms about nothing — a call the
    runtime or the device kept, not the host.  The baseline is the
    watchdog's glance at the open bracket, some ticks before the
    deadline; the traced run's switch changes nothing of it."""
    inj = DeviceFaultInjector()
    engine = inj.wrap_engine("lane0", CounterEngine(num_slots=256, buckets=(8,)))
    cache = TpuRateLimitCache(
        engine,
        time_source=PinnedTimeSource(1234),
        batch_window_us=100,
        kernel_deadline_s=0.2,
        device_failure_mode="host",
        fault_interval_s=0.04,
        fault_restart_backoff_s=60.0,
        fault_snapshot_interval_s=1000.0,
        thread_clocks=traced,
    )
    cfg = load_config([ConfigFile("config.c", YAML)], Manager())
    rule = cfg.get_limit("d", Descriptor.of(("k", "x")))
    req = RateLimitRequest("d", [Descriptor.of(("k", "x"))], 1)
    try:
        for _ in range(3):
            assert cache.do_limit(req, [rule])[0].code is Code.OK
        fd = cache.fault_domain
        fd.snapshot_now()
        inj.hang("lane0", at=seam)
        assert cache.do_limit(req, [rule])[0].code is Code.OK  # the mirror's
        assert fd.stat_faults[FAULT_HANG] == 1
        hang = fd.summary()["banks"][0]["last_hang"]
    finally:
        inj.heal()
        cache.close()
    rows = {t["thread"]: t for t in hang["during"]["threads"]}
    stuck = rows[role]
    assert stuck["in_device_call_s"] >= 0.2
    assert stuck["wall_ms"] >= 200
    assert stuck["on_cpu_ms"] <= 5
    assert 0 <= stuck["watchdog_late_ms"] < 100
    # The watchdog looked within a tick or two of the call opening.
    assert 120 <= stuck["observed_ms"] <= stuck["wall_ms"]
    assert stuck["off_cpu_ms"] >= 0.9 * stuck["observed_ms"]
    if native_slot_table.available():
        assert 0 <= stuck["last_gil_return_us"] < 50_000
    other = rows["completer" if role == "collector" else "collector"]
    assert "wall_ms" not in other and "on_cpu_ms" not in other


def test_the_watchdogs_lateness_is_counted_and_shown():
    """A tick that runs late against interval_s — because the whole
    process stood — is summed and counted; /debug/faults and the stats
    family show both."""
    from ratelimit_tpu.observability.spans import SLOW_TICK_NS
    from ratelimit_tpu.stats.manager import StatsStore

    cache = TpuRateLimitCache(
        CounterEngine(num_slots=256, buckets=(8,)),
        time_source=PinnedTimeSource(1234),
        batch_window_us=100,
        kernel_deadline_s=0.25,
        fault_interval_s=0.02,
        fault_snapshot_interval_s=1000.0,
    )
    fd = cache.fault_domain
    store = StatsStore()
    fd.register_stats(store)
    try:
        time.sleep(0.1)  # a few ticks on time
        assert fd.stat_ticks_late == 0 or fd.stat_tick_late_ns > 0
        before = fd.stat_ticks_late
        # Hold the GIL past several intervals: the supervisor's wait
        # ends on time, and it stands in the GIL's queue until we let go.
        old = sys.getswitchinterval()
        sys.setswitchinterval(0.5)
        t0 = time.monotonic()
        while time.monotonic() - t0 < 0.15:
            pass
        sys.setswitchinterval(old)
        deadline = time.monotonic() + 5
        while fd.stat_ticks_late == before and time.monotonic() < deadline:
            time.sleep(0.01)
    finally:
        fd.stop()
        cache.close()
    summary = fd.summary()
    assert fd.stat_ticks_late >= before + 1, (fd.stat_tick_late_ns, fd.last_tick_late_ns)
    assert summary["ticks_late"] == fd.stat_ticks_late
    assert summary["tick_late_ms"] >= SLOW_TICK_NS / 1e6
    values = store.counter_fn_values()
    assert values["ratelimit.tpu.fault.ticks_late"] == fd.stat_ticks_late
    assert values["ratelimit.tpu.fault.tick_late_ms"] == fd.stat_tick_late_ns // 1_000_000


# -- the server: same answers, same threads -----------------------------------

RUNNER_YAML = """
domain: basic
descriptors:
  - key: key1
    rate_limit:
      unit: minute
      requests_per_unit: 5
  - key: shadowed
    shadow_mode: true
    rate_limit:
      unit: minute
      requests_per_unit: 2
"""

#: The threads a started runner adds (digits folded to N; the gRPC pool
#: grows with the calls in flight: 0..GRPC_MAX_WORKERS `grpc-rpc_N`).
#: Pinned: this PR measures on the threads that do the work and adds
#: none — no sampler, no probe, no timer — in either mode.  It is the
#: parent's list too (commit 531b896).
RUNNER_THREADS = [
    "Thread-N (_serve)",
    "anomaly-sampler",
    "api-listener",
    "debug-listener",
    "device-supervisor",
    "runtime-watcher",
    "tpu-dispatcher",
    "tpu-dispatcher-complete",
    "tpu-dispatcher-gcra",
    "tpu-dispatcher-gcra-complete",
    "tpu-dispatcher-sliding_window",
    "tpu-dispatcher-sliding_window-complete",
    "tsdb-sampler",
]


def _replay(tmp_path, profiling):
    """Start a runner, replay one fixed request sequence over gRPC;
    returns (the raw response bytes, the threads the runner added,
    /stats.json, one /debug/launches record)."""
    import urllib.request

    import grpc

    from ratelimit_tpu.runner import Runner
    from ratelimit_tpu.server import pb  # noqa: F401
    from ratelimit_tpu.settings import Settings
    from envoy.service.ratelimit.v3 import rls_pb2

    root = tmp_path / f"runtime{int(profiling)}"
    config = root / "ratelimit" / "config"
    config.mkdir(parents=True)
    (config / "basic.yaml").write_text(RUNNER_YAML)
    settings = Settings(
        host="127.0.0.1", port=0, grpc_host="127.0.0.1", grpc_port=0,
        debug_host="127.0.0.1", debug_port=0, use_statsd=False,
        backend_type="tpu", tpu_num_slots=1 << 12, tpu_batch_buckets=[8, 32],
        runtime_path=str(root), runtime_subdirectory="ratelimit",
        local_cache_size_in_bytes=0, expiration_jitter_max_seconds=0,
        debug_profiling=profiling,
    )
    before = set(threading.enumerate())
    runner = Runner(settings, time_source=PinnedTimeSource(1_000_000))
    runner.start()
    try:
        added = sorted(
            re.sub(r"\d+", "N", t.name)
            for t in set(threading.enumerate()) - before
        )
        answers = []
        with grpc.insecure_channel(
            f"127.0.0.1:{runner.grpc_server.bound_port}"
        ) as channel:
            call = channel.unary_unary(
                "/envoy.service.ratelimit.v3.RateLimitService/ShouldRateLimit",
                request_serializer=rls_pb2.RateLimitRequest.SerializeToString,
                response_deserializer=lambda raw: raw,
            )
            for i in range(40):
                req = rls_pb2.RateLimitRequest(domain="basic", hits_addend=i % 3)
                for j in range(1 + i % 4):
                    entry = req.descriptors.add().entries.add()
                    entry.key = "shadowed" if (i + j) % 5 == 0 else "key1"
                    entry.value = f"v{(i * 7 + j) % 6}"
                answers.append(call(req, timeout=30))
        after = sorted(
            {
                re.sub(r"\d+", "N", t.name)
                for t in set(threading.enumerate()) - before
            }
            - {"grpc-rpc_N"}
        )
        port = runner.debug_server.bound_port

        def get(path):
            with urllib.request.urlopen(
                f"http://127.0.0.1:{port}{path}", timeout=30
            ) as resp:
                return json.load(resp)

        stats = get("/stats.json")
        launches = get("/debug/launches")
        faults = get("/debug/faults")
    finally:
        runner.stop()
    return answers, (added, after), stats, launches, faults


@pytest.fixture(scope="module")
def replays(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("ledger")
    return {p: _replay(tmp, p) for p in (False, True)}


def test_a_replayed_sequence_answers_byte_for_byte_alike_with_profiling_off_and_on(replays):
    off, on = replays[False][0], replays[True][0]
    assert len(off) == len(on) == 40
    assert off == on
    assert len(set(off)) > 3  # OK, OVER_LIMIT, several remainders: not one answer 40 times


@pytest.mark.parametrize("profiling", [False, True], ids=["DEBUG_PROFILING=0", "DEBUG_PROFILING=1"])
def test_a_started_runner_has_exactly_todays_threads(replays, profiling):
    added, after = replays[profiling][1]
    assert added == RUNNER_THREADS
    assert after == RUNNER_THREADS  # serving 40 requests started nothing but pool workers


def test_an_rpc_thread_reads_no_clock_in_either_mode(replays):
    """What this PR reads, the two dispatcher threads and the watchdog
    read: the handler's histograms are the parent's, profiling or not,
    and a request carries no clock."""
    off, on = replays[False][2]["histograms"], replays[True][2]["histograms"]
    assert sorted(off) == sorted(on)
    assert not [n for n in on if "_cpu_ms" in n or "_runq_ms" in n]
    assert not hasattr(RateLimitRequest("d", []), "clock")


@pytest.mark.parametrize("profiling", [False, True], ids=["DEBUG_PROFILING=0", "DEBUG_PROFILING=1"])
def test_what_a_launch_reads_with_profiling_off_and_on(replays, profiling):
    """Always: the GIL's return (a vDSO read) and the watchdog's
    lateness.  Only with DEBUG_PROFILING=1: the thread CPU clock (wall
    and cpu sums advance together or not at all, so a share is never
    read against nothing)."""
    _, _, stats, launches, faults = replays[profiling]
    s = stats["stats"]
    bank = "ratelimit.tpu.bank0."
    legs = ("device_submit", "readback", "readback_ready")
    if profiling:
        assert 0 < s[bank + "device_submit.cpu_ns"] <= 1.05 * s[bank + "device_submit.wall_ns"]
        assert 0 < s[bank + "readback.cpu_ns"] <= 1.05 * s[bank + "readback.wall_ns"] + 1e6
        assert 0 <= s[bank + "readback_ready.wall_ns"] <= s[bank + "readback.wall_ns"]
        assert 0 <= s[bank + "readback_ready.cpu_ns"] <= s[bank + "readback.cpu_ns"]
        assert (s[bank + "readback_ready.wall_ns"] > 0) == (s[bank + "readback_ready"] > 0)
    else:
        for leg in legs:
            assert s[f"{bank}{leg}.cpu_ns"] == s[f"{bank}{leg}.wall_ns"] == 0
    if native_slot_table.available():
        assert s[bank + "assign_gil.count"] == s[bank + "decide_gil.count"] > 0
        assert s[bank + "assign_gil.total_ns"] > 0
    # What nothing reads is not there: no run-queue family, no maxima,
    # no clamped difference.
    family = [k for k in s if k.startswith(bank)]
    assert not [k for k in family if "runq" in k or "max_ns" in k or "blocked" in k]
    assert not [k for k in family if k.split(".")[3] in ("launch", "handoff", "complete")]
    records = launches["launches"] if isinstance(launches, dict) else launches
    last = records[-1]
    assert ("device_submit_cpu_us" in last) == ("readback_cpu_us" in last) == profiling
    assert not [k for k in last if "runq" in k]
    assert faults["tick_late_ms"] >= 0 and faults["ticks_late"] >= 0


# -- the benchmark's readers ---------------------------------------------------

NEW_METRICS = {
    "device_submit_cpu_share.paced": ("%", "higher", "engine (host)"),
    "readback_cpu_share.paced": ("%", "higher", "engine (host)"),
    "readback_ready_blocked_us.paced": ("us", "lower", "engine (host)"),
    "collector_gil_return_us.paced": ("us", "lower", "engine (host)"),
    "completer_gil_return_us.paced": ("us", "lower", "engine (host)"),
    "watchdog_late_ms.paced": ("ms", "lower", "fault domain + background"),
}

def _reader(name):
    with open(os.path.join(ROOT, "chipbench", "layer_metrics", name + ".json")) as f:
        return json.load(f)["reader"]


def _recorded(side):
    with open(os.path.join(ROOT, "tests", "data", f"pr41_obs_{side}.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", list(NEW_METRICS))
def test_each_new_reader_reads_this_tree_and_leaves_the_parent_out(name):
    """tests/data/pr41_obs_{change,parent}.json: what chipbench's
    readers are given (/stats.json and /debug/faults, before and after
    some traffic), recorded from a runner of this tree and of the
    parent commit on the CPU, trimmed to bank 0's family, the handler's
    histograms and /debug/faults' scalars.  On this tree every reader
    finds a number; on the parent, whose program has no such counter,
    it finds None and raises nothing — the harness then leaves the
    metric out of the line, which the driver accepts from a parent."""
    from chipbench import layers

    reader = _reader(name)
    # Never `launches`: that one indexes a record's field bare.  The
    # arrived copy's off-CPU time is a `difference` of two ratios: the
    # reader subtracts the sums' deltas, and nothing clamps the result.
    parts = [reader["a"], reader["b"]] if reader["kind"] == "difference" else [reader]
    assert (reader["kind"] == "difference") == (name == "readback_ready_blocked_us.paced")
    for part in parts:
        assert part["kind"] in ("ratio", "delta")
        assert part["endpoint"] in ("stats", "faults")
    value = layers.read(reader, _recorded("change"))
    assert isinstance(value, float), value
    if reader["kind"] == "difference":
        wall, cpu = (layers.read(part, _recorded("change")) for part in parts)
        assert wall > 0 and cpu >= 0 and value == wall - cpu
    else:
        assert value >= 0
    if NEW_METRICS[name][0] == "%":
        assert value <= 105
    assert layers.read(reader, _recorded("parent")) is None
    # An accepted reader reads both recordings: the fixtures are whole.
    for side in ("change", "parent"):
        assert layers.read(_reader("wake_us.paced"), _recorded(side)) > 0


def test_the_new_entries_are_appended_and_every_string_fits():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    # PR 41 added no cell: its metrics list the four there were, by name
    # (later cells, and the metrics that list only those, come after).
    cells = [w["name"] for w in bench["workloads"]]
    cells = cells[:cells.index("bulk-recipients.paced") + 1]
    assert len(cells) == 4
    bench["per_layer"] = [m for m in bench["per_layer"] if set(m["workloads"]) & set(cells)]
    new = bench["per_layer"][-len(NEW_METRICS):]
    assert [m["name"] for m in new] == list(NEW_METRICS)
    name_ok = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
    unit_ok = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
    for m in new:
        assert set(m) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert (m["unit"], m["better"], m["layer"]) == NEW_METRICS[m["name"]]
        assert m["source"] == "program_counter" and m["moves"] == "p50_ms"
        assert m["workloads"][:4] == cells
        assert name_ok.match(m["name"]) and unit_ok.match(m["unit"])
        for key, text in m.items():
            if isinstance(text, str):
                assert 1 <= len(text) <= 200 and text.isprintable(), (m["name"], key)
        assert os.path.exists(
            os.path.join(ROOT, "chipbench", "layer_metrics", m["name"] + ".json")
        )
    layers_there = {m["layer"] for m in bench["per_layer"][: -len(NEW_METRICS)]}
    assert {m["layer"] for m in new} <= layers_there  # no layer invented
    assert len({m["name"] for m in bench["per_layer"]}) == len(bench["per_layer"])
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024
