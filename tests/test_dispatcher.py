"""Micro-batching dispatcher tests.

Async batching is made deterministic via flush() — the lesson the
reference codifies as AutoFlushForIntegrationTests for its async
memcache writes (reference src/memcached/cache_impl.go:54,176-178).
"""

import threading
import time

import numpy as np
import pytest

from ratelimit_tpu.api import Code, Descriptor, RateLimitRequest
from ratelimit_tpu.backends.dispatcher import BatchDispatcher, Lane, WorkItem
from ratelimit_tpu.backends.engine import CounterEngine
from ratelimit_tpu.backends.tpu_cache import TpuRateLimitCache
from ratelimit_tpu.config.loader import ConfigFile, load_config
from ratelimit_tpu.service import CacheError
from ratelimit_tpu.stats.manager import Manager

YAML = """
domain: d
descriptors:
  - key: k
    rate_limit:
      unit: minute
      requests_per_unit: 100
"""


def _rule(mgr):
    cfg = load_config([ConfigFile("config.c", YAML)], mgr)
    return cfg.get_limit("d", Descriptor.of(("k", "x")))


def test_batched_cache_matches_inline(clock):
    mgr1, mgr2 = Manager(), Manager()
    inline = TpuRateLimitCache(
        CounterEngine(num_slots=256), time_source=clock
    )
    batched = TpuRateLimitCache(
        CounterEngine(num_slots=256),
        time_source=clock,
        batch_window_us=500,
    )
    try:
        rule1, rule2 = _rule(mgr1), _rule(mgr2)
        for i in range(120):
            req = RateLimitRequest("d", [Descriptor.of(("k", "x"))], 1)
            s1 = inline.do_limit(req, [rule1])
            s2 = batched.do_limit(req, [rule2])
            assert s1[0].code == s2[0].code, i
            assert s1[0].limit_remaining == s2[0].limit_remaining
        assert mgr1.store.counters() == {
            k.replace("ratelimit.", "ratelimit."): v
            for k, v in mgr2.store.counters().items()
        }
    finally:
        batched.close()


def test_concurrent_requests_share_batches(clock):
    """Many threads against one batched cache: decisions must account
    every hit exactly once (the atomicity property the memcached
    backend's read-then-write race loses, cache_impl.go:1-14)."""
    mgr = Manager()
    cache = TpuRateLimitCache(
        CounterEngine(num_slots=256),
        time_source=clock,
        batch_window_us=2000,
        batch_limit=64,
    )
    try:
        rule = _rule(mgr)
        codes = []
        lock = threading.Lock()

        def worker():
            req = RateLimitRequest("d", [Descriptor.of(("k", "x"))], 1)
            st = cache.do_limit(req, [rule])
            with lock:
                codes.append(st[0].code)

        threads = [threading.Thread(target=worker) for _ in range(150)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        cache.flush()

        over = sum(1 for c in codes if c == Code.OVER_LIMIT)
        ok = sum(1 for c in codes if c == Code.OK)
        # 100/minute limit, 150 hits in the same pinned-clock window:
        # exactly 50 must be rejected regardless of batching layout.
        assert (ok, over) == (100, 50)
        snap = mgr.store.counters()
        assert snap["ratelimit.service.rate_limit.d.k.total_hits"] == 150
        assert snap["ratelimit.service.rate_limit.d.k.over_limit"] == 50
        assert snap["ratelimit.service.rate_limit.d.k.within_limit"] == 100
    finally:
        cache.close()


def test_flush_waits_for_prior_items():
    engine = CounterEngine(num_slots=64)
    d = BatchDispatcher(engine, batch_window_us=50_000, batch_limit=4096)
    try:
        seen = []

        def apply(decisions):
            seen.append(int(decisions.afters[0]))

        item = WorkItem(
            now=0,
            lanes=[Lane(key="a_1_0", expiry=60, limit=10, shadow=False, hits=1)],
            apply=apply,
        )
        d.submit(item)
        # flush must short-circuit the 50ms window and process the item.
        d.flush()
        assert item.event.is_set()
        assert seen == [1]
    finally:
        d.stop()


def test_lane_limit_caps_batch():
    engine = CounterEngine(num_slots=64, buckets=(8, 32))
    d = BatchDispatcher(engine, batch_window_us=100_000, batch_limit=2)
    try:
        items = [
            WorkItem(
                now=0,
                lanes=[
                    Lane(key=f"k{i}_0", expiry=60, limit=10, shadow=False, hits=1)
                ],
                apply=lambda dec: None,
            )
            for i in range(4)
        ]
        for it in items:
            d.submit(it)
        # 2-lane cap: batches of 2 dispatch immediately without waiting
        # out the 100ms window.
        for it in items:
            it.wait()
    finally:
        d.stop()


def test_dispatcher_telemetry_hwm_and_batch_histograms():
    """queue/in-flight high-water marks advance and the batch-shape
    histograms observe one sample per LAUNCH (lanes and items)."""
    from ratelimit_tpu.stats.manager import Histogram

    engine = CounterEngine(num_slots=64, buckets=(8, 32))
    d = BatchDispatcher(engine, batch_window_us=100_000, batch_limit=2)
    d.batch_lanes_hist = Histogram(
        "test.batch_lanes", bounds=(1.0, 2.0, 4.0, 8.0)
    )
    d.batch_items_hist = Histogram(
        "test.batch_items", bounds=(1.0, 2.0, 4.0, 8.0)
    )
    try:
        assert d.queue_depth_hwm() == 0 and d.inflight_hwm() == 0
        items = [
            WorkItem(
                now=0,
                lanes=[
                    Lane(key=f"k{i}_0", expiry=60, limit=10, shadow=False, hits=1)
                ],
                apply=lambda dec: None,
            )
            for i in range(4)
        ]
        for it in items:
            d.submit(it)
        for it in items:
            it.wait()
        d.flush()
        # 4 single-lane items through a 2-lane cap: two+ launches of
        # <=2 lanes each, every lane/item accounted exactly once.
        lanes = d.batch_lanes_hist.summary()
        batches = d.batch_items_hist.summary()
        assert lanes["total_ms"] == 4.0  # sum of observed lane counts
        assert batches["total_ms"] == 4.0
        assert lanes["count"] == batches["count"] >= 2
        assert lanes["max_ms"] <= 2.0
        assert d.queue_depth_hwm() >= 1
        assert 1 <= d.inflight_hwm() <= 2
        assert d.inflight() == 0  # all completed
    finally:
        d.stop()


def test_engine_error_propagates_as_cache_error(clock):
    class BrokenEngine(CounterEngine):
        def submit_packed(self, *args, **kwargs):
            raise RuntimeError("device lost")

    mgr = Manager()
    cache = TpuRateLimitCache(
        BrokenEngine(num_slots=64), time_source=clock, batch_window_us=100
    )
    try:
        rule = _rule(mgr)
        with pytest.raises(CacheError):
            cache.do_limit(
                RateLimitRequest("d", [Descriptor.of(("k", "x"))], 1), [rule]
            )
    finally:
        cache.close()


def test_collector_runs_periodic_gc(clock):
    """Expired keys are reclaimed proactively (Redis active-expiry
    analog): without periodic gc they would linger until the free
    list emptied, holding the table at high-water and skewing the
    live_keys gauge.  The gc clock is the ITEMS' time source, never
    the wall clock (tests pin time)."""
    engine = CounterEngine(num_slots=64, buckets=(8,))
    d = BatchDispatcher(engine, batch_window_us=100, batch_limit=4096)
    try:
        it = WorkItem(
            now=0,
            lanes=[Lane(key="old_0", expiry=1, limit=10, shadow=False, hits=1)],
            apply=lambda dec: None,
        )
        d.submit(it)
        it.wait(30)
        assert len(engine.slot_table) == 1

        # Make the next collect cycle due for gc, then drive traffic
        # whose `now` is past the first key's expiry.
        d.gc_interval_s = 0.0
        d._next_gc_monotonic = 0.0
        it2 = WorkItem(
            now=10,
            lanes=[Lane(key="new_0", expiry=60, limit=10, shadow=False, hits=1)],
            apply=lambda dec: None,
        )
        d.submit(it2)
        it2.wait(30)
        d.flush()
        deadline = time.monotonic() + 5
        while len(engine.slot_table) > 1 and time.monotonic() < deadline:
            time.sleep(0.01)
        assert len(engine.slot_table) == 1  # old_0 reclaimed, new_0 lives
    finally:
        d.stop()

def test_eager_idle_launches_lone_item_but_coalesces_under_load():
    """r5 eager-idle: a lone arrival at a fully idle dispatcher
    launches without waiting the window; items arriving while a batch
    is IN FLIGHT still coalesce (the window discipline under load is
    unchanged).  Deterministic via an engine whose completion blocks
    until released."""
    release = threading.Event()
    batches = []

    class _GatedEngine(CounterEngine):
        def step_complete(self, token, *watch):
            release.wait(10)
            return super().step_complete(token, *watch)

        def submit_packed(self, now, blob, meta, *watch):
            batches.append(len(meta))
            return super().submit_packed(now, blob, meta, *watch)

    engine = _GatedEngine(num_slots=256, buckets=(8, 32))
    # Generous window: only eager-idle could launch item A quickly.
    d = BatchDispatcher(engine, batch_window_us=150_000, batch_limit=4096)
    try:
        def item(name):
            return WorkItem(
                now=0,
                lanes=[Lane(key=f"{name}_0", expiry=60, limit=10,
                            shadow=False, hits=1)],
                apply=lambda dec: None,
            )

        release.set()  # first launches complete immediately
        warm = item("warm")  # pay the first-shape XLA compile untimed
        d.submit(warm)
        warm.wait(30)
        batches.clear()

        a = item("a")
        t0 = time.monotonic()
        d.submit(a)
        a.wait(5)
        # Loose bound: well under the 150ms window proves the eager
        # launch fired; tight real-time bounds flake on loaded CI.
        assert time.monotonic() - t0 < 0.1
        assert batches == [1]

        # Hold the NEXT completion: while it is in flight, b and c
        # must coalesce instead of each launching eagerly.
        release.clear()
        d.submit(item("hold"))  # eager (idle again) -> in flight, held
        deadline = time.monotonic() + 5
        while len(batches) < 2 and time.monotonic() < deadline:
            time.sleep(0.001)
        assert batches == [1, 1]
        b, c = item("b"), item("c")
        d.submit(b)
        d.submit(c)
        time.sleep(0.05)  # well under the 150ms window
        assert len(batches) == 2  # nothing launched while held
        release.set()
        b.wait(5)
        c.wait(5)
        assert batches == [1, 1, 2]  # b+c rode ONE coalesced batch
    finally:
        release.set()
        d.stop()
