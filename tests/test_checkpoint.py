"""Checkpoint/restore: a restart must not forgive open windows
(the gap called out in SURVEY.md section 5 — the reference leans on
Redis durability; the TPU engine snapshots its HBM counters)."""

import numpy as np

from ratelimit_tpu.api import Code, Descriptor, RateLimitRequest
from ratelimit_tpu.backends.checkpoint import (
    CheckpointManager,
    restore_engine,
    save_engine,
)
from ratelimit_tpu.backends.engine import CounterEngine
from ratelimit_tpu.backends.tpu_cache import TpuRateLimitCache
from ratelimit_tpu.config.loader import ConfigFile, load_config
from ratelimit_tpu.parallel import ShardedCounterEngine, make_mesh
from ratelimit_tpu.stats.manager import Manager

YAML = """
domain: d
descriptors:
  - key: k
    rate_limit:
      unit: minute
      requests_per_unit: 5
"""


def _rule(mgr):
    return load_config([ConfigFile("config.c", YAML)], mgr).get_limit(
        "d", Descriptor.of(("k", "x"))
    )


def _hit(cache, rule, n=1):
    codes = []
    for _ in range(n):
        st = cache.do_limit(
            RateLimitRequest("d", [Descriptor.of(("k", "x"))], 1), [rule]
        )
        codes.append(st[0].code)
    return codes


def test_restart_does_not_forgive_window(tmp_path, clock):
    path = str(tmp_path / "bank0.npz")
    cache_a = TpuRateLimitCache(CounterEngine(num_slots=64), time_source=clock)
    rule = _rule(Manager())
    assert _hit(cache_a, rule, 3) == [Code.OK] * 3
    save_engine(cache_a.engine, path)

    # "Restart": a fresh engine restores the snapshot and continues the
    # same window (clock pinned): 2 more OK, then OVER_LIMIT.
    cache_b = TpuRateLimitCache(CounterEngine(num_slots=64), time_source=clock)
    assert restore_engine(cache_b.engine, path)
    assert _hit(cache_b, rule, 3) == [Code.OK, Code.OK, Code.OVER_LIMIT]


def test_restore_missing_or_mismatched(tmp_path, clock):
    engine = CounterEngine(num_slots=64)
    assert restore_engine(engine, str(tmp_path / "nope.npz")) is False

    save_engine(engine, str(tmp_path / "bank0.npz"))
    other = CounterEngine(num_slots=128)
    assert restore_engine(other, str(tmp_path / "bank0.npz")) is False
    assert len(other.slot_table) == 0


def test_sharded_checkpoint_roundtrip(tmp_path, clock):
    mesh = make_mesh(8)
    path = str(tmp_path / "bank0.npz")
    cache_a = TpuRateLimitCache(
        ShardedCounterEngine(mesh, num_slots=64), time_source=clock
    )
    rule = _rule(Manager())
    assert _hit(cache_a, rule, 4) == [Code.OK] * 4
    save_engine(cache_a.engine, path)

    cache_b = TpuRateLimitCache(
        ShardedCounterEngine(make_mesh(8), num_slots=64), time_source=clock
    )
    assert restore_engine(cache_b.engine, path)
    np.testing.assert_array_equal(
        cache_b.engine.export_counts(), cache_a.engine.export_counts()
    )
    assert _hit(cache_b, rule, 2) == [Code.OK, Code.OVER_LIMIT]


def test_checkpoint_manager_with_dispatcher(tmp_path, clock):
    """Snapshots run on the dispatcher thread while batching is on."""
    cache = TpuRateLimitCache(
        CounterEngine(num_slots=64), time_source=clock, batch_window_us=200
    )
    try:
        rule = _rule(Manager())
        _hit(cache, rule, 3)
        mgr = CheckpointManager(cache, str(tmp_path), interval_s=3600)
        mgr.checkpoint()

        fresh = TpuRateLimitCache(CounterEngine(num_slots=64), time_source=clock)
        mgr2 = CheckpointManager(
            TpuRateLimitCache(fresh.engine, time_source=clock),
            str(tmp_path),
            interval_s=3600,
        )
        assert mgr2.restore() == 1
        assert _hit(fresh, rule, 3) == [Code.OK, Code.OK, Code.OVER_LIMIT]
    finally:
        cache.close()


def test_restore_refuses_stale_snapshot(tmp_path, clock):
    """Restore-age guard: a snapshot older than the longest window
    unit (one day) is refused — every counter in it expired, and
    restoring would resurrect dead windows.  The wall clock is a seam
    (FakeMonotonicClock) so the test needs no real day."""
    from ratelimit_tpu.backends.checkpoint import MAX_RESTORE_AGE_S
    from ratelimit_tpu.utils.time import FakeMonotonicClock

    path = str(tmp_path / "bank0.npz")
    cache_a = TpuRateLimitCache(CounterEngine(num_slots=64), time_source=clock)
    rule = _rule(Manager())
    assert _hit(cache_a, rule, 5) == [Code.OK] * 5
    import time as _time

    saved_at = _time.time()
    save_engine(cache_a.engine, path)

    # Within the age bound: restores, window still enforced.
    wall = FakeMonotonicClock(saved_at + 60.0)
    fresh = CounterEngine(num_slots=64)
    assert restore_engine(fresh, path, wall_now=wall.now) is True
    assert len(fresh.slot_table) == 1

    # Older than the longest window unit: refused, engine stays fresh.
    wall.advance(MAX_RESTORE_AGE_S + 120.0)
    stale = CounterEngine(num_slots=64)
    assert restore_engine(stale, path, wall_now=wall.now) is False
    assert len(stale.slot_table) == 0

    # max_age_s=0 disables the guard (operator override).
    assert restore_engine(stale, path, max_age_s=0, wall_now=wall.now) is True
    assert len(stale.slot_table) == 1


def test_crash_mid_snapshot_preserves_previous(tmp_path, clock, monkeypatch):
    """Atomicity (temp-file + rename): a crash MID-write must leave
    the previous snapshot intact and readable — the restart path then
    restores the older-but-consistent state instead of a torn file."""
    import numpy as _np

    from ratelimit_tpu.backends import checkpoint as cp

    path = str(tmp_path / "bank0.npz")
    cache = TpuRateLimitCache(CounterEngine(num_slots=64), time_source=clock)
    rule = _rule(Manager())
    assert _hit(cache, rule, 3) == [Code.OK] * 3
    save_engine(cache.engine, path)  # snapshot v1: 3 hits

    # Crash the NEXT snapshot mid-write: savez writes garbage to the
    # temp file then dies before os.replace can run.
    real_savez = _np.savez_compressed

    def dying_savez(f, **arrays):
        f.write(b"\x00garbage")
        raise OSError("disk died mid-write")

    monkeypatch.setattr(cp.np, "savez_compressed", dying_savez)
    _hit(cache, rule, 1)
    try:
        save_engine(cache.engine, path)
        assert False, "expected the injected crash"
    except OSError:
        pass
    monkeypatch.setattr(cp.np, "savez_compressed", real_savez)

    # The previous snapshot is untouched and restores cleanly: the
    # window continues from 3 hits (2 more OK, then OVER_LIMIT).
    fresh = TpuRateLimitCache(CounterEngine(num_slots=64), time_source=clock)
    assert restore_engine(fresh.engine, path)
    assert _hit(fresh, rule, 3) == [Code.OK, Code.OK, Code.OVER_LIMIT]


def test_snapshot_under_concurrent_traffic_is_consistent(tmp_path, clock):
    """A snapshot taken while the dispatcher is serving restores to a
    CONSISTENT per-row state: every restored per-key count is a true
    prefix of that key's committed hits (the dispatcher-thread copy
    can never tear a row), and a post-drain snapshot is exact."""
    import threading as _threading

    cache = TpuRateLimitCache(
        CounterEngine(num_slots=256),
        time_source=clock,
        batch_window_us=100,
    )
    mgr = Manager()
    config = load_config(
        [
            ConfigFile(
                "config.c",
                """
domain: d
descriptors:
  - key: k
    rate_limit:
      unit: minute
      requests_per_unit: 1000000
""",
            )
        ],
        mgr,
    )
    rule = config.get_limit("d", Descriptor.of(("k", "x")))
    n_threads, per_thread = 4, 50
    mgr_dir = str(tmp_path)
    manager = CheckpointManager(cache, mgr_dir, interval_s=1000.0)

    def traffic(tid):
        for _ in range(per_thread):
            cache.do_limit(
                RateLimitRequest(
                    "d", [Descriptor.of(("k", f"t{tid}"))], 1
                ),
                [rule],
            )

    threads = [
        _threading.Thread(target=traffic, args=(t,)) for t in range(n_threads)
    ]
    for t in threads:
        t.start()
    # Snapshots race the traffic: each must be internally consistent.
    mid_counts = []
    for _ in range(5):
        manager.checkpoint()
        eng = CounterEngine(num_slots=256)
        assert restore_engine(eng, str(tmp_path / "bank0.npz"), "lane0of1")
        counts = np.asarray(eng.export_counts())
        entries = eng.slot_table.export_packed().tuples()
        per_key = {k: int(counts[s]) for k, s, _e in entries}
        for k, c in per_key.items():
            assert 0 <= c <= per_thread, (k, c)  # a prefix, never more
        mid_counts.append(sum(per_key.values()))
    for t in threads:
        t.join()
    assert mid_counts == sorted(mid_counts)  # monotone across snapshots
    cache.flush()
    manager.checkpoint()
    eng = CounterEngine(num_slots=256)
    assert restore_engine(eng, str(tmp_path / "bank0.npz"), "lane0of1")
    counts = np.asarray(eng.export_counts())
    total = sum(
        int(counts[s]) for s in eng.slot_table.export_packed().slots
    )
    assert total == n_threads * per_thread  # drained snapshot is exact
    cache.close()


def test_checkpoint_snapshots_mirror_while_quarantined(tmp_path, clock):
    """During a quarantine episode the on-disk checkpointer snapshots
    the HOST MIRROR (the state actually serving), so a process restart
    mid-episode restores the mirror's counters — and a broken bank
    never starves the other banks of snapshots."""
    from ratelimit_tpu.cluster.faults import DeviceFaultInjector

    inj = DeviceFaultInjector()
    engine = inj.wrap_engine("lane0", CounterEngine(num_slots=64, buckets=(8,)))
    cache = TpuRateLimitCache(
        engine,
        time_source=clock,
        batch_window_us=100,
        kernel_deadline_s=0.2,
        device_failure_mode="host",
        fault_interval_s=0,
        fault_snapshot_interval_s=1000.0,
    )
    rule = _rule(Manager())
    try:
        assert _hit(cache, rule, 3) == [Code.OK] * 3
        cache.fault_domain.snapshot_now()
        inj.raise_error("lane0")
        assert _hit(cache, rule, 1) == [Code.OK]  # 4th, served by mirror
        assert cache.fault_domain.is_quarantined(0)

        manager = CheckpointManager(cache, str(tmp_path), interval_s=1000.0)
        manager.checkpoint()  # must not raise on the dead dispatcher

        fresh = TpuRateLimitCache(
            CounterEngine(num_slots=64), time_source=clock
        )
        assert restore_engine(
            fresh.engine, str(tmp_path / "bank0.npz"), "lane0of1"
        )
        # 4 hits restored (3 device + 1 mirror): 1 more OK, then over.
        assert _hit(fresh, rule, 2) == [Code.OK, Code.OVER_LIMIT]
    finally:
        inj.heal()
        cache.close()
