"""Adversarial / reference-depth scenarios (round-3 VERDICT #8).

Models: the reference's sustained over-limit progression
(test/integration/integration_test.go:436-496), wire-level
hits_addend accounting (test/redis/fixed_cache_impl_test.go:282+),
restart-restore under load, and a many-thread duplicate-key stress
run checked against exact-counting invariants and the memory oracle.
"""

import threading
import time

import numpy as np
import pytest

from ratelimit_tpu.api import Code, Descriptor, RateLimitRequest
from ratelimit_tpu.backends.engine import CounterEngine
from ratelimit_tpu.backends.memory_cache import MemoryRateLimitCache
from ratelimit_tpu.backends.tpu_cache import TpuRateLimitCache
from ratelimit_tpu.config.loader import ConfigFile, load_config
from ratelimit_tpu.stats.manager import Manager

YAML = """
domain: adv
descriptors:
  - key: twenty
    rate_limit:
      unit: minute
      requests_per_unit: 20
  - key: stress
    rate_limit:
      unit: hour
      requests_per_unit: 1000000
"""


def _cfg(mgr):
    return load_config([ConfigFile("config.adv", YAML)], mgr)


def _req(entries, hits=0):
    return RateLimitRequest("adv", [Descriptor.of(*e) for e in entries], hits)


def _limits(cfg, req):
    return [cfg.get_limit(req.domain, d) for d in req.descriptors]


def _snap(mgr, rule_key):
    base = f"ratelimit.service.rate_limit.adv.{rule_key}"
    c = mgr.store.counters()
    return {
        k: c[f"{base}.{k}"]
        for k in (
            "total_hits",
            "over_limit",
            "near_limit",
            "within_limit",
            "shadow_mode",
            "over_limit_with_local_cache",
        )
    }


# -- sustained over-limit progression ---------------------------------


def test_25_call_progression_against_20_per_minute(clock):
    """Reference integration_test.go:436-496: 25 calls against 20/min.
    Calls 1-20 OK with exact decreasing remaining, 21-25 OVER_LIMIT;
    stat attribution: near threshold floor(20*0.8)=16, so hits 17-20
    are near-limit, 1-16 within, 21-25 over."""
    mgr = Manager()
    cfg = _cfg(mgr)
    cache = TpuRateLimitCache(
        CounterEngine(num_slots=256, buckets=(8, 32)), time_source=clock
    )
    try:
        codes, remaining = [], []
        for _ in range(25):
            req = _req([[("twenty", "prog")]])
            st = cache.do_limit(req, _limits(cfg, req))[0]
            codes.append(st.code)
            remaining.append(st.limit_remaining)
        assert codes == [Code.OK] * 20 + [Code.OVER_LIMIT] * 5
        assert remaining == list(range(19, -1, -1)) + [0] * 5
        s = _snap(mgr, "twenty")
        assert s["total_hits"] == 25
        assert s["over_limit"] == 5
        assert s["within_limit"] == 20
        assert s["near_limit"] == 4  # hits 17..20
        # Reset decays within the window (integration_test.go:585-596).
        req = _req([[("twenty", "prog")]])
        st = cache.do_limit(req, _limits(cfg, req))[0]
        assert 0 < st.duration_until_reset <= 60
        clock.now += 17
        req = _req([[("twenty", "prog")]])
        st2 = cache.do_limit(req, _limits(cfg, req))[0]
        assert st2.duration_until_reset == st.duration_until_reset - 17
    finally:
        cache.close()


def test_window_rollover_resets_progression(clock):
    """After the minute rolls over, the same key counts from zero
    (fixed-window semantics; key embeds the window start)."""
    mgr = Manager()
    cfg = _cfg(mgr)
    cache = TpuRateLimitCache(
        CounterEngine(num_slots=256, buckets=(8, 32)), time_source=clock
    )
    try:
        clock.now = 60  # window-aligned
        for _ in range(21):
            req = _req([[("twenty", "roll")]])
            st = cache.do_limit(req, _limits(cfg, req))[0]
        assert st.code == Code.OVER_LIMIT
        clock.now = 121  # next minute window
        req = _req([[("twenty", "roll")]])
        st = cache.do_limit(req, _limits(cfg, req))[0]
        assert st.code == Code.OK
        assert st.limit_remaining == 19
    finally:
        cache.close()


# -- hits_addend accounting -------------------------------------------


def test_hits_addend_batched_accounting(clock):
    """hits_addend>1 with partial attribution across the near and over
    thresholds (reference base_limiter.go:150-179; wire-level analog of
    fixed_cache_impl_test.go:282+)."""
    mgr = Manager()
    cfg = _cfg(mgr)
    cache = TpuRateLimitCache(
        CounterEngine(num_slots=256, buckets=(8, 32)), time_source=clock
    )
    try:
        # 20/min, near threshold 16.
        # Request 1: 10 hits -> within (0..10).
        req = _req([[("twenty", "ha")]], hits=10)
        st = cache.do_limit(req, _limits(cfg, req))[0]
        assert (st.code, st.limit_remaining) == (Code.OK, 10)
        s = _snap(mgr, "twenty")
        assert (s["within_limit"], s["near_limit"], s["over_limit"]) == (
            10,
            0,
            0,
        )
        # Request 2: 8 hits -> 10..18 straddles near=16: 2 near.
        req = _req([[("twenty", "ha")]], hits=8)
        st = cache.do_limit(req, _limits(cfg, req))[0]
        assert (st.code, st.limit_remaining) == (Code.OK, 2)
        s = _snap(mgr, "twenty")
        assert (s["within_limit"], s["near_limit"], s["over_limit"]) == (
            18,
            2,
            0,
        )
        # Request 3: 10 hits -> 18..28 straddles limit=20: 2 over-
        # attributed hits go near (18..20 above 16), 8 over.
        req = _req([[("twenty", "ha")]], hits=10)
        st = cache.do_limit(req, _limits(cfg, req))[0]
        assert (st.code, st.limit_remaining) == (Code.OVER_LIMIT, 0)
        s = _snap(mgr, "twenty")
        assert (s["within_limit"], s["near_limit"], s["over_limit"]) == (
            18,
            4,
            8,
        )
        # Request 4: fully over -> all hits over.
        req = _req([[("twenty", "ha")]], hits=3)
        st = cache.do_limit(req, _limits(cfg, req))[0]
        assert st.code == Code.OVER_LIMIT
        s = _snap(mgr, "twenty")
        assert s["over_limit"] == 11
        assert s["total_hits"] == 31
    finally:
        cache.close()


def test_hits_addend_wire_level(clock):
    """Same accounting through the REAL gRPC wire (request proto
    hits_addend field) — see test_server_integration for the runner
    plumbing; here the in-process codec path is exercised via
    request_from_pb."""
    from ratelimit_tpu.server import pb  # noqa: F401
    from envoy.service.ratelimit.v3 import rls_pb2
    from ratelimit_tpu.server.codec import request_from_pb

    pb_req = rls_pb2.RateLimitRequest(domain="adv", hits_addend=7)
    d = pb_req.descriptors.add()
    e = d.entries.add()
    e.key, e.value = "twenty", "wire"
    req = request_from_pb(pb_req)
    assert req.hits_addend == 7

    mgr = Manager()
    cfg = _cfg(mgr)
    cache = TpuRateLimitCache(
        CounterEngine(num_slots=256, buckets=(8, 32)), time_source=clock
    )
    try:
        st = cache.do_limit(req, _limits(cfg, req))[0]
        assert (st.code, st.limit_remaining) == (Code.OK, 13)
        assert _snap(mgr, "twenty")["total_hits"] == 7
    finally:
        cache.close()


# -- checkpoint/restore under traffic ---------------------------------


def test_checkpoint_restore_under_traffic(tmp_path, clock):
    """Checkpoints taken WHILE traffic flows are internally consistent
    (counter value matches the slot table's keys at snapshot time),
    and a restore resumes enforcement from the snapshot."""
    from ratelimit_tpu.backends.checkpoint import CheckpointManager

    mgr = Manager()
    cfg = _cfg(mgr)
    cache = TpuRateLimitCache(
        CounterEngine(num_slots=512, buckets=(8, 32)),
        time_source=clock,
        batch_window_us=100,
    )
    ckpt_dir = str(tmp_path / "ckpt")
    cm = CheckpointManager(cache, ckpt_dir)
    stop = threading.Event()
    sent = [0]
    errors = []

    def traffic():
        i = 0
        try:
            while not stop.is_set():
                req = _req([[("stress", f"t{i % 7}")]])
                cache.do_limit(req, _limits(cfg, req))
                sent[0] += 1
                i += 1
        except Exception as e:  # pragma: no cover - fail loudly below
            errors.append(e)

    threads = [threading.Thread(target=traffic) for _ in range(4)]
    try:
        for t in threads:
            t.start()
        deadline = time.monotonic() + 2.0
        snaps = 0
        while time.monotonic() < deadline:
            cm.checkpoint()
            snaps += 1
        stop.set()
        for t in threads:
            t.join(timeout=10)
        assert not errors, errors
        assert snaps >= 2 and sent[0] > 0
        cache.flush()
        total_sent = sent[0]
        cm.checkpoint()  # final, post-drain

        # Restore into a fresh cache: the final snapshot carries every
        # hit (taken after flush), and enforcement resumes from it.
        cache2 = TpuRateLimitCache(
            CounterEngine(num_slots=512, buckets=(8, 32)),
            time_source=clock,
            batch_window_us=100,
        )
        try:
            cm2 = CheckpointManager(cache2, ckpt_dir)
            assert cm2.restore() == 1
            restored = int(cache2.engine.export_counts().sum())
            assert restored == total_sent
            # Same keys live in the restored table.
            assert len(cache2.engine.slot_table) == min(7, total_sent)
        finally:
            cache2.close()
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=10)
        cm.stop(final_checkpoint=False)
        cache.close()


# -- many-thread duplicate-key stress vs oracle ------------------------


def test_many_thread_duplicate_key_stress_exact_counting(clock):
    """8 threads hammer 5 keys through the batching dispatcher with
    random hits_addend.  Whatever the interleaving:
    - every hit lands exactly once (final device counters == sum of
      hits per key — the exact-counting property Redis INCRBY gives
      the reference);
    - stat attribution conserves hits (within + over == total);
    - the memory oracle fed the same per-key totals agrees on the
      final counter values."""
    mgr = Manager()
    cfg = _cfg(mgr)
    cache = TpuRateLimitCache(
        CounterEngine(num_slots=512, buckets=(8, 32, 128)),
        time_source=clock,
        batch_window_us=200,
    )
    KEYS = [f"s{i}" for i in range(5)]
    per_thread_totals = []
    errors = []

    def worker(seed):
        rng = np.random.default_rng(seed)
        totals = {k: 0 for k in KEYS}
        try:
            for _ in range(60):
                k = KEYS[int(rng.integers(0, len(KEYS)))]
                hits = int(rng.integers(1, 4))
                req = _req([[("stress", k)]], hits=hits)
                st = cache.do_limit(req, _limits(cfg, req))[0]
                assert st.code == Code.OK  # limit is 1M: never over
                totals[k] += hits
        except Exception as e:  # pragma: no cover
            errors.append(e)
        per_thread_totals.append(totals)

    threads = [threading.Thread(target=worker, args=(s,)) for s in range(8)]
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not errors, errors
        cache.flush()

        want = {
            k: sum(t[k] for t in per_thread_totals) for k in KEYS
        }
        total_hits = sum(want.values())

        # 1. Exact counting on the device.
        counts = cache.engine.export_counts()
        assert int(counts.sum()) == total_hits
        # Per-key: look the slots up through the table.
        entries = {
            key: int(counts[slot])
            for key, slot, _exp in cache.engine.slot_table.export_packed().tuples()
        }
        for k, n in want.items():
            matching = [v for key, v in entries.items() if f"_{k}_" in key]
            assert matching == [n], (k, matching, n)

        # 2. Stat conservation.
        s = _snap(mgr, "stress")
        assert s["total_hits"] == total_hits
        assert s["within_limit"] + s["over_limit"] == total_hits
        assert s["over_limit"] == 0

        # 3. Memory-oracle agreement on final counters.
        omgr = Manager()
        ocfg = _cfg(omgr)
        oracle = MemoryRateLimitCache(time_source=clock)
        for k, n in want.items():
            req = _req([[("stress", k)]], hits=n)
            st = oracle.do_limit(req, _limits(ocfg, req))[0]
            # after == n on a fresh key: remaining == limit - n.
            assert st.limit_remaining == 1000000 - n
    finally:
        cache.close()


def test_unicode_and_long_keys_roundtrip(clock):
    """Hostile descriptor values: unicode, separators, very long —
    distinct counters, exact counting, native slot table safe with
    arbitrary utf-8."""
    mgr = Manager()
    cfg = _cfg(mgr)
    cache = TpuRateLimitCache(
        CounterEngine(num_slots=256, buckets=(8, 32)),
        time_source=clock,
        batch_window_us=100,
    )
    try:
        values = [
            "ümläut-中文",
            "a" * 500,
            "with_underscores_and_1234",
            "sp aces and\ttabs",
        ]
        for v in values:
            for _ in range(2):
                req = _req([[("stress", v)]])
                st = cache.do_limit(req, _limits(cfg, req))[0]
                assert st.code == Code.OK
        cache.flush()
        counts = cache.engine.export_counts()
        assert int(counts.sum()) == 2 * len(values)
        assert len(cache.engine.slot_table) == len(values)
    finally:
        cache.close()


def test_multi_chunk_submission_exact(clock):
    """One submission larger than the biggest bucket exercises the
    multi-chunk fused path (chunked assign+dedup under one pin scope,
    engine.submit_packed): counting stays exact, duplicates spanning
    chunk boundaries included."""
    mgr = Manager()
    cfg = _cfg(mgr)
    cache = TpuRateLimitCache(
        CounterEngine(num_slots=512, buckets=(8, 32)),  # max_batch 32
        time_source=clock,
    )
    try:
        # 80 lanes in ONE request: 3 chunks (32+32+16); keys repeat
        # every 10 lanes so duplicates land in different chunks.
        entries = [[("stress", f"c{i % 10}")] for i in range(80)]
        req = _req(entries, hits=1)
        statuses = cache.do_limit(req, _limits(cfg, req))
        assert all(s.code == Code.OK for s in statuses)
        # Lane i is the (i//10 + 1)-th hit on its key: remaining
        # decreases per duplicate IN PIPELINE ORDER across chunks.
        for i, s in enumerate(statuses):
            assert s.limit_remaining == 1000000 - (i // 10 + 1), i
        cache.flush()
        counts = cache.engine.export_counts()
        assert int(counts.sum()) == 80
        assert len(cache.engine.slot_table) == 10
    finally:
        cache.close()


def test_write_behind_many_thread_stress_exact(clock):
    """The write-behind mode under the same 8-thread duplicate-key
    hammering: decisions never block on the device, and after flush
    the device counters carry every hit exactly once."""
    from ratelimit_tpu.backends.write_behind import WriteBehindRateLimitCache

    mgr = Manager()
    cfg = _cfg(mgr)
    cache = WriteBehindRateLimitCache(
        CounterEngine(num_slots=512, buckets=(8, 32, 128)),
        time_source=clock,
        batch_window_us=200,
    )
    KEYS = [f"w{i}" for i in range(5)]
    totals_per_thread = []
    errors = []

    def worker(seed):
        rng = np.random.default_rng(seed)
        totals = {k: 0 for k in KEYS}
        try:
            for _ in range(60):
                k = KEYS[int(rng.integers(0, len(KEYS)))]
                hits = int(rng.integers(1, 4))
                req = _req([[("stress", k)]], hits=hits)
                st = cache.do_limit(req, _limits(cfg, req))[0]
                assert st.code == Code.OK
                totals[k] += hits
        except Exception as e:  # pragma: no cover
            errors.append(e)
        totals_per_thread.append(totals)

    threads = [threading.Thread(target=worker, args=(s,)) for s in range(8)]
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not errors, errors
        cache.flush()
        want_total = sum(sum(t.values()) for t in totals_per_thread)
        assert int(cache.engine.export_counts().sum()) == want_total
        # The reconciled host view agrees with the device exactly.
        for k, entry in cache._view.items():
            assert entry[1] == 0, f"pending not drained for {k}"
        view_total = sum(e[0] for e in cache._view.values())
        assert view_total == want_total
    finally:
        cache.close()


def test_empty_descriptor_and_unknown_domain_wire_shapes(clock):
    """Reference edge semantics: a descriptor with zero entries and a
    domain with no config both produce OK with no limit (GetLimit
    returns nil -> no counter touched, ratelimit.go:104-146)."""
    mgr = Manager()
    cfg = _cfg(mgr)
    cache = TpuRateLimitCache(
        CounterEngine(num_slots=64, buckets=(8,)), time_source=clock
    )
    try:
        # Zero-entry descriptor.
        req = RateLimitRequest("adv", [Descriptor(())], 0)
        lim = [cfg.get_limit(req.domain, d) for d in req.descriptors]
        assert lim == [None]
        st = cache.do_limit(req, lim)[0]
        assert st.code == Code.OK
        assert st.current_limit is None
        # Unknown domain.
        req = RateLimitRequest("nosuchdomain", [Descriptor.of(("a", "b"))], 0)
        lim = [cfg.get_limit(req.domain, d) for d in req.descriptors]
        assert lim == [None]
        st = cache.do_limit(req, lim)[0]
        assert st.code == Code.OK
        # Neither touched the counter table.
        cache.flush()
        assert int(cache.engine.export_counts().sum()) == 0
    finally:
        cache.close()


def test_config_check_cli_accepts_example_and_rejects_bad(tmp_path, capsys):
    """The offline validator binary semantics (reference
    config_check_cmd/main.go:104-143): exit 0 on the shipped example
    config, exit 1 with the loader's error on a malformed dir."""
    from ratelimit_tpu.cli import config_check

    import os

    example_dir = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "examples",
        "ratelimit",
        "config",
    )
    assert config_check.main(["--config_dir", example_dir]) == 0
    out = capsys.readouterr().out
    assert "rl.foo" in out  # dump() of the loaded config printed

    bad = tmp_path / "bad"
    bad.mkdir()
    (bad / "broken.yaml").write_text(
        "domain: d\ndescriptors:\n  - key: k\n    rate_limit:\n"
        "      unit: lightyears\n      requests_per_unit: 1\n"
    )
    assert config_check.main(["--config_dir", str(bad)]) == 1
    assert "error loading config" in capsys.readouterr().err
