"""Descriptor-resolution cache tests (limiter/resolution.py).

Covers the invalidation contract (config generation flip, FAILED
reload keeping the warm cache, lane-count re-route), the bypasses
(request-supplied overrides), stats identity across reloads, byte-
identical keys and lane records vs an oracle built from
CacheKeyGenerator and the record construction the cache replaced (every
rule family, across a window rollover, a generation change and a lane
rehash), what a cached key keeps on the collector's lists, the
second-chance capacity policy, /metrics exposure, and decision parity
between the resolved fast path and the uncached path (shadow,
unlimited, override, and window-rollover cases).
"""

import gc
import random

from zlib import crc32

import numpy as np
import pytest

from ratelimit_tpu.api import (
    Code,
    Descriptor,
    LimitOverride,
    RateLimitRequest,
    Unit,
)
from ratelimit_tpu.backends import CounterEngine, TpuRateLimitCache
from ratelimit_tpu.backends.dispatcher import LANE_DTYPE
from ratelimit_tpu.config import ConfigFile, load_config
from ratelimit_tpu.limiter.cache_key import CacheKeyGenerator, build_stem
from ratelimit_tpu.limiter.resolution import ResolutionCache, record
from ratelimit_tpu.models.registry import get_algorithm
from ratelimit_tpu.service import RateLimitService
from ratelimit_tpu.stats.manager import Manager
from ratelimit_tpu.utils.time import PinnedTimeSource

BASIC_YAML = """
domain: test-domain
descriptors:
  - key: key1
    value: value1
    rate_limit:
      unit: minute
      requests_per_unit: 10
  - key: wild
    rate_limit:
      unit: hour
      requests_per_unit: 5
  - key: unlim
    rate_limit:
      unlimited: true
  - key: shady
    shadow_mode: true
    rate_limit:
      unit: second
      requests_per_unit: 2
"""


def make_config(mgr, yaml=BASIC_YAML, name="config.basic"):
    return load_config([ConfigFile(name, yaml)], mgr)


@pytest.fixture(scope="module")
def shared_engine():
    return CounterEngine(num_slots=1 << 10, buckets=(8, 32))


@pytest.fixture
def engine(shared_engine):
    shared_engine.reset()
    return shared_engine


# -- ResolutionCache unit behavior ------------------------------------


def test_hit_returns_same_entry_and_counts():
    mgr = Manager()
    cfg = make_config(mgr)
    res = ResolutionCache(LANE_DTYPE)
    d = Descriptor.of(("key1", "value1"))
    e1 = res.resolve(cfg, "test-domain", d)
    e2 = res.resolve(cfg, "test-domain", d)
    assert e1 is e2
    assert (res.hits, res.misses) == (1, 1)
    assert e1.rs.rule.limit.requests_per_unit == 10
    assert not e1.rs.per_second and e1.rs.unit == Unit.MINUTE
    # Equal entries built afresh (as every decoded request's are) hit.
    assert res.resolve(cfg, "test-domain", Descriptor.of(("key1", "value1"))) is e1


def test_no_rule_and_unlimited_are_cached_negative_entries():
    mgr = Manager()
    cfg = make_config(mgr)
    res = ResolutionCache(LANE_DTYPE)
    none = res.resolve(cfg, "test-domain", Descriptor.of(("nope", "x")))
    assert none.rs.rule is None and not none.rs.unlimited
    unlim = res.resolve(cfg, "test-domain", Descriptor.of(("unlim", "y")))
    assert unlim.rs.rule is not None and unlim.rs.unlimited
    # Both hit on re-resolve (no trie walk).
    res.resolve(cfg, "test-domain", Descriptor.of(("nope", "x")))
    res.resolve(cfg, "test-domain", Descriptor.of(("unlim", "y")))
    assert res.hits == 2


def test_generation_flip_invalidates_stale_rule():
    mgr = Manager()
    cfg1 = make_config(mgr)
    res = ResolutionCache(LANE_DTYPE)
    d = Descriptor.of(("key1", "value1"))
    e1 = res.resolve(cfg1, "test-domain", d)
    assert e1.rs.rule.limit.requests_per_unit == 10
    cfg2 = make_config(mgr, BASIC_YAML.replace("requests_per_unit: 10",
                                               "requests_per_unit: 99"))
    assert cfg2.generation > cfg1.generation
    e2 = res.resolve(cfg2, "test-domain", d)
    # Stale rule never served: the new generation re-resolves.
    assert e2 is not e1
    assert e2.rs.rule.limit.requests_per_unit == 99
    assert res.misses == 2
    # The table is the new generation's now (one wholesale clear); a
    # request still holding the old config is answered from that
    # config, and nothing of it is kept.
    assert res.clears == 1 and len(res) == 1
    e_old = res.resolve(cfg1, "test-domain", d)
    assert e_old.rs.rule.limit.requests_per_unit == 10
    assert len(res) == 1
    assert res.resolve(cfg2, "test-domain", d) is e2


def test_override_descriptor_bypasses():
    mgr = Manager()
    cfg = make_config(mgr)
    res = ResolutionCache(LANE_DTYPE)
    d = Descriptor.of(
        ("key1", "value1"), limit=LimitOverride(3, Unit.MINUTE)
    )
    assert res.resolve(cfg, "test-domain", d) is None
    assert (res.hits, res.misses) == (0, 0)
    assert len(res) == 0


def test_lane_count_change_reroutes():
    mgr = Manager()
    cfg = make_config(mgr)
    res = ResolutionCache(LANE_DTYPE)
    d = Descriptor.of(("key1", "value1"))
    e = res.resolve(cfg, "test-domain", d)
    assert e.lane(1) == 0
    assert e.lane(2) == crc32(e.stem_bytes) % 2
    # A changed lane count re-routes the same entry: the route is the
    # stem's hash under whatever modulus the backend has.
    assert res.resolve(cfg, "test-domain", d) is e
    assert e.lane(3) == crc32(e.stem_bytes) % 3


def test_capacity_evicts_one_entry_not_the_table():
    mgr = Manager()
    cfg = make_config(mgr)
    res = ResolutionCache(LANE_DTYPE, capacity=2)
    for v in ("a", "b", "c"):
        res.resolve(cfg, "test-domain", Descriptor.of(("key1", v)))
    assert res.clears == 0 and res.evictions == 1
    assert len(res) == 2  # "a", the oldest, made room for "c"
    res.resolve(cfg, "test-domain", Descriptor.of(("key1", "b")))
    res.resolve(cfg, "test-domain", Descriptor.of(("key1", "c")))
    assert res.hits == 2
    res.resolve(cfg, "test-domain", Descriptor.of(("key1", "a")))
    assert res.misses == 4


def test_keys_byte_identical_to_generator():
    mgr = Manager()
    yaml = """
domain: d
descriptors:
  - key: sec
    rate_limit: {unit: second, requests_per_unit: 4}
  - key: minute
    rate_limit: {unit: minute, requests_per_unit: 4}
  - key: day
    rate_limit: {unit: day, requests_per_unit: 4}
  - key: multi
    descriptors:
      - key: sub
        rate_limit: {unit: hour, requests_per_unit: 4}
"""
    cfg = make_config(mgr, yaml, name="config.keys")
    gen = CacheKeyGenerator(prefix="pfx:")
    res = ResolutionCache(prefix="pfx:", lane_dtype=LANE_DTYPE)
    now = 1_700_000_123
    descs = [
        Descriptor.of(("sec", "v")),
        Descriptor.of(("minute", "")),
        Descriptor.of(("day", "x")),
        Descriptor.of(("multi", ""), ("sub", "s")),
    ]
    for d in descs:
        rule = cfg.get_limit("d", d)
        ck = gen.generate("d", d, rule, now)
        e = res.resolve(cfg, "d", d)
        win = e.rs.window(now)
        key_bytes = e.stem_bytes + win.suffix
        assert key_bytes == ck.key.encode("utf-8")
        assert e.rs.per_second == ck.per_second
        assert len(e.stem_bytes) == ck.stem_blen
        # The record carries the rule's lane fields and the key's length.
        rec = np.frombuffer(
            record(win.head, len(key_bytes), win.tail), dtype=LANE_DTYPE
        )[0]
        assert int(rec["limits"]) == 4
        assert int(rec["len"]) == len(ck.key.encode("utf-8"))
        assert int(rec["expiry"]) == win.start + e.rs.divider


def test_window_state_rolls_over():
    mgr = Manager()
    cfg = make_config(mgr)
    res = ResolutionCache(LANE_DTYPE)
    e = res.resolve(cfg, "test-domain", Descriptor.of(("shady", "s")))
    w1 = e.rs.window(1000)
    assert w1 is e.rs.window(1000)  # memoized within the window
    w2 = e.rs.window(1001)  # SECOND unit: new window each second
    assert w2 is not w1
    assert w2.suffix == b"1001" and e.stem_bytes.endswith(b"_")
    rec = np.frombuffer(record(w2.head, 9, w2.tail), dtype=LANE_DTYPE)[0]
    assert int(rec["len"]) == 9
    assert int(rec["expiry"]) == 1002
    # The window is the RULE's: a second key of the rule shares it.
    e2 = res.resolve(cfg, "test-domain", Descriptor.of(("shady", "t")))
    assert e2.rs is e.rs and e2 is not e


# -- service-level invalidation ---------------------------------------


class FakeRuntime:
    def __init__(self, files):
        self.files = dict(files)
        self.callbacks = []

    def snapshot(self):
        data = dict(self.files)

        class Snap:
            def keys(self):
                return sorted(data)

            def get(self, key):
                return data.get(key, "")

        return Snap()

    def add_update_callback(self, fn):
        self.callbacks.append(fn)

    def fire(self):
        for fn in self.callbacks:
            fn()


def make_service(engine, clock, mgr, runtime_files=None, **cache_kwargs):
    cache = TpuRateLimitCache(engine, clock, **cache_kwargs)
    runtime = FakeRuntime(runtime_files or {"config.basic": BASIC_YAML})
    svc = RateLimitService(runtime, cache, mgr, clock=clock)
    return svc, cache, runtime


def test_service_uses_resolver_and_counts_hits(engine):
    clock = PinnedTimeSource(1234)
    mgr = Manager()
    svc, cache, _ = make_service(engine, clock, mgr)
    req = RateLimitRequest("test-domain", [Descriptor.of(("key1", "value1"))], 0)
    svc.should_rate_limit(req)
    svc.should_rate_limit(req)
    assert cache.resolver.misses == 1
    assert cache.resolver.hits == 1


def test_failed_reload_keeps_warm_cache(engine):
    clock = PinnedTimeSource(1234)
    mgr = Manager()
    svc, cache, runtime = make_service(engine, clock, mgr)
    d = Descriptor.of(("key1", "value1"))
    req = RateLimitRequest("test-domain", [d], 0)
    svc.should_rate_limit(req)
    cfg_before = svc.get_current_config()
    entry_before = cache.resolver.resolve(cfg_before, "test-domain", d)

    runtime.files["config.basic"] = "domain: [broken"
    runtime.fire()  # reload fails; old config AND generation survive
    assert svc.stats.config_load_error.value() == 1
    cfg_after = svc.get_current_config()
    assert cfg_after is cfg_before

    misses_before = cache.resolver.misses
    svc.should_rate_limit(req)
    assert cache.resolver.misses == misses_before  # still warm
    assert (
        cache.resolver.resolve(cfg_after, "test-domain", d) is entry_before
    )


def test_successful_reload_serves_new_rule_and_preserves_stats_identity(engine):
    clock = PinnedTimeSource(1234)
    mgr = Manager()
    svc, cache, runtime = make_service(engine, clock, mgr)
    d = Descriptor.of(("key1", "value1"))
    req = RateLimitRequest("test-domain", [d], 0)
    svc.should_rate_limit(req)
    rule_before = svc.get_current_config().get_limit("test-domain", d)

    # No-op reload: same YAML, new generation.
    runtime.fire()
    assert svc.stats.config_load_success.value() == 2
    entry = cache.resolver.resolve(
        svc.get_current_config(), "test-domain", d
    )
    # Stats identity: the Manager interns per-rule stats by key, so a
    # reload hands the new rule the SAME counter objects.
    assert entry.rs.rule.stats is rule_before.stats

    # Real change: stale limit never served after the generation flip.
    runtime.files["config.basic"] = BASIC_YAML.replace(
        "requests_per_unit: 10", "requests_per_unit: 3"
    )
    runtime.fire()
    [st] = svc.should_rate_limit(req).statuses
    assert st.current_limit.requests_per_unit == 3


# -- decision parity: resolved fast path vs uncached path -------------


def run_scenario(svc, clock):
    """A scripted mixed workload exercising shadow, unlimited,
    override, no-rule and window-rollover behavior; returns the
    flattened (overall_code, per-descriptor code/remaining/duration)
    transcript."""
    out = []
    descs = [
        Descriptor.of(("key1", "value1")),
        Descriptor.of(("wild", "anything")),
        Descriptor.of(("unlim", "u")),
        Descriptor.of(("shady", "s")),
        Descriptor.of(("norule", "x")),
        Descriptor.of(("key1", "value1"), limit=LimitOverride(2, Unit.MINUTE)),
    ]
    for step in range(8):
        resp = svc.should_rate_limit(
            RateLimitRequest("test-domain", descs, 0)
        )
        out.append(int(resp.overall_code))
        for st in resp.statuses:
            out.append(
                (
                    int(st.code),
                    st.limit_remaining,
                    st.duration_until_reset,
                    None
                    if st.current_limit is None
                    else (
                        st.current_limit.requests_per_unit,
                        int(st.current_limit.unit),
                    ),
                )
            )
        if step == 3:
            clock.advance(1)  # rolls the SECOND shadow window
        if step == 5:
            clock.advance(60)  # rolls the MINUTE windows
    return out


def test_resolved_path_decisions_identical_to_uncached():
    clock_a = PinnedTimeSource(1_700_000_000)
    clock_b = PinnedTimeSource(1_700_000_000)
    eng_a = CounterEngine(num_slots=1 << 10, buckets=(8, 32))
    eng_b = CounterEngine(num_slots=1 << 10, buckets=(8, 32))
    mgr_a, mgr_b = Manager(), Manager()
    svc_a, cache_a, _ = make_service(eng_a, clock_a, mgr_a)
    svc_b, cache_b, _ = make_service(
        eng_b, clock_b, mgr_b, resolution_cache_entries=0
    )
    assert cache_a.resolver is not None
    assert cache_b.resolver is None
    got = run_scenario(svc_a, clock_a)
    want = run_scenario(svc_b, clock_b)
    assert got == want
    assert cache_a.resolver.hits > 0


def test_resolved_path_multilane_parity():
    clock_a = PinnedTimeSource(1_700_000_000)
    clock_b = PinnedTimeSource(1_700_000_000)
    lanes_a = [CounterEngine(num_slots=256, buckets=(8, 32)) for _ in range(2)]
    lanes_b = [CounterEngine(num_slots=256, buckets=(8, 32)) for _ in range(2)]
    mgr_a, mgr_b = Manager(), Manager()
    svc_a, cache_a, _ = make_service(lanes_a, clock_a, mgr_a)
    svc_b, cache_b, _ = make_service(
        lanes_b, clock_b, mgr_b, resolution_cache_entries=0
    )
    got = run_scenario(svc_a, clock_a)
    want = run_scenario(svc_b, clock_b)
    assert got == want
    # Same stem must land on the same lane in both modes (a split
    # would double-count a key), so per-lane live-key counts match.
    cache_a.flush(), cache_b.flush()
    for la, lb in zip(cache_a.lanes, cache_b.lanes):
        assert la.stat_live_keys == lb.stat_live_keys


# -- /metrics exposure ------------------------------------------------


def test_cache_counters_exposed_on_metrics(engine):
    from ratelimit_tpu.observability import prometheus

    clock = PinnedTimeSource(1234)
    mgr = Manager()
    svc, cache, _ = make_service(engine, clock, mgr)
    cache.register_stats(mgr.store)
    req = RateLimitRequest("test-domain", [Descriptor.of(("key1", "value1"))], 0)
    svc.should_rate_limit(req)
    svc.should_rate_limit(req)
    text = prometheus.render(mgr.store)
    assert "# TYPE ratelimit_tpu_resolution_cache_hits counter" in text
    assert "ratelimit_tpu_resolution_cache_hits 1" in text
    assert "ratelimit_tpu_resolution_cache_misses 1" in text
    assert "ratelimit_tpu_resolution_cache_clears 0" in text
    assert "ratelimit_tpu_stem_cache_clears 0" in text
    assert "ratelimit_tpu_resolution_cache_entries 1" in text


# -- byte identity: keys and lane records vs the construction replaced --

FAMILY_YAML = """
domain: fam
descriptors:
  - key: minute
    rate_limit: {unit: minute, requests_per_unit: 7}
  - key: hour
    rate_limit: {unit: hour, requests_per_unit: 8}
  - key: day
    rate_limit: {unit: day, requests_per_unit: 9}
  - key: second
    rate_limit: {unit: second, requests_per_unit: 3}
  - key: slide
    rate_limit: {unit: minute, requests_per_unit: 10, algorithm: sliding_window}
  - key: tb
    rate_limit: {unit: second, requests_per_unit: 5, algorithm: gcra}
  - key: shady_slide
    rate_limit: {unit: minute, requests_per_unit: 11, algorithm: sliding_window, shadow: true}
  - key: shady_tb
    shadow_mode: true
    rate_limit: {unit: hour, requests_per_unit: 12, algorithm: gcra, shadow: true}
  - key: quiet
    shadow_mode: true
    rate_limit: {unit: minute, requests_per_unit: 2}
  - key: unlim
    rate_limit: {unlimited: true}
  - key: nested
    descriptors:
      - key: sub
        rate_limit: {unit: hour, requests_per_unit: 4}
        descriptors:
          - key: leaf
            rate_limit: {unit: day, requests_per_unit: 6}
"""

# family -> (entries of one descriptor given a value, override or None)
FAMILIES = {
    "fixed_minute": (lambda v: [("minute", v)], None),
    "fixed_hour": (lambda v: [("hour", v)], None),
    "fixed_day": (lambda v: [("day", v)], None),
    "per_second": (lambda v: [("second", v)], None),
    "nested_two_entries": (lambda v: [("nested", v), ("sub", v + "s")], None),
    "three_entries": (lambda v: [("nested", v), ("sub", "s"), ("leaf", v)], None),
    "algo_enforced_sliding": (lambda v: [("slide", v)], None),
    "algo_enforced_gcra": (lambda v: [("tb", v)], None),
    "algo_shadow_sliding": (lambda v: [("shady_slide", v)], None),
    "algo_shadow_gcra_shadow_mode": (lambda v: [("shady_tb", v)], None),
    "shadow_mode": (lambda v: [("quiet", v)], None),
    "unlimited": (lambda v: [("unlim", v)], None),
    "no_rule": (lambda v: [("nothing", v)], None),
    "override": (lambda v: [("minute", v)], LimitOverride(3, Unit.HOUR)),
}
VALUES = ["", "a", "user_123456", "ünï-çødé-値", "x" * 1100]


def lane_record(expiry, hits, limit, key_len, shadow, divider, algo) -> bytes:
    """One LANE_DTYPE record, built the way the cache built its
    per-key template before the window memo moved to the rule."""
    arr = np.empty(1, dtype=LANE_DTYPE)
    arr[0] = (expiry, hits, limit, key_len, shadow, divider, algo)
    return arr.tobytes()


def oracle_packs(cfg, gen, prefix, domain, desc, now, hits, banks, ps_bank, n_lanes):
    """{bank label: (key bytes, record bytes)} a descriptor must put on
    the wire to the engines, from CacheKeyGenerator and the parent's
    record construction.  Labels: "lane<i>", "per_second", "algo_<name>"."""
    from ratelimit_tpu.utils.time import unit_to_divider, window_start

    rule = cfg.get_limit(domain, desc)
    if rule is None or rule.unlimited:
        return {}
    stem_b = build_stem(prefix, domain, desc.entries).encode("utf-8")
    unit = rule.limit.unit
    divider = unit_to_divider(unit)
    w = window_start(now, unit)
    limit = rule.limit.requests_per_unit
    shadow = 1 if rule.shadow_mode else 0
    hits = min(hits, 0xFFFFFFFF)
    algo = rule.algorithm if rule.algorithm in banks else "fixed_window"
    out = {}
    if algo != "fixed_window":
        out["algo_" + algo] = (
            stem_b,
            lane_record(w + 2 * divider, hits, limit, len(stem_b), shadow,
                        divider, get_algorithm(algo).algo_id),
        )
        if not rule.algo_shadow:
            return out
    ck = gen.generate(domain, desc, rule, now)
    key_b = ck.key.encode("utf-8")
    assert key_b[: ck.stem_blen] == stem_b
    if ps_bank and ck.per_second:
        label = "per_second"
    else:
        label = f"lane{crc32(stem_b) % n_lanes if n_lanes > 1 else 0}"
    out[label] = (key_b, lane_record(w + divider, hits, limit, len(key_b), shadow, 0, 0))
    return out


def served_packs(cache, cfg, domain, desc, hits):
    """The same, as the served front half packs it."""
    req = RateLimitRequest(domain, [desc], hits)
    items = cache._prepare_resolved(req, cfg)[0]
    out = {}
    for bank, _engine, item in items:
        assert item.pack.count == 1
        out[cache._bank_labels[bank]] = (item.pack.key_blob, item.pack.meta.tobytes())
    return out


@pytest.fixture(scope="module")
def family_caches():
    """(label, cache, clock): one lane, two lanes with a per-second
    bank, three lanes — the same keys must cost the same bytes on each
    and route by the stem's hash."""
    from tests.test_algorithms import make_algo_banks

    out = []
    for label, n_lanes, per_second in (("1lane", 1, False), ("2lanes_ps", 2, True), ("3lanes", 3, False)):
        clock = PinnedTimeSource(1_700_000_000)
        lanes = [CounterEngine(num_slots=1 << 8, buckets=(8,)) for _ in range(n_lanes)]
        cache = TpuRateLimitCache(
            lanes if n_lanes > 1 else lanes[0],
            clock,
            per_second_engine=CounterEngine(num_slots=1 << 8, buckets=(8,)) if per_second else None,
            cache_key_prefix="pfx:",
            algorithm_banks=make_algo_banks(1 << 8),
        )
        out.append((label, cache, clock))
    yield out
    for _label, cache, _clock in out:
        cache.close()


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_bytes_identical_to_parent_construction(family, family_caches):
    """Key bytes and lane-record bytes (primary and algorithm pack) of
    every rule family equal the oracle's: first seen (miss), seen again
    (hit), with another addend, across window rollovers, after a
    generation change, and under every lane count."""
    entries_of, override = FAMILIES[family]
    mgr = Manager()
    cfg1 = make_config(mgr, FAMILY_YAML, name="config.fam")
    cfg2 = make_config(
        mgr,
        FAMILY_YAML.replace("requests_per_unit: ", "requests_per_unit: 1"),
        name="config.fam",
    )
    rng = random.Random(family)
    compared = 0
    for label, cache, clock in family_caches:
        gen = CacheKeyGenerator("pfx:")
        res = cache.resolver
        clears0, lookups0 = res.clears, res.hits + res.misses
        n_lanes = len(cache.lanes)
        ps_bank = cache.per_second_engine is not None
        t = 1_700_000_000 + rng.randrange(86_400)
        # now, now again (hit), next second, next minute, next hour,
        # next day, then the same walk under the reloaded config.
        steps = [(cfg1, 0), (cfg1, 0), (cfg1, 1), (cfg1, 60), (cfg1, 3600),
                 (cfg2, 0), (cfg2, 0), (cfg2, 86_400), (cfg2, 1)]
        for cfg, advance in steps:
            t += advance
            clock.now = t
            for v in VALUES:
                desc = Descriptor.of(*entries_of(v), limit=override)
                for hits in (1, 3):
                    want = oracle_packs(
                        cfg, gen, "pfx:", "fam", desc, t, hits,
                        cache.algorithm_banks, ps_bank, n_lanes,
                    )
                    got = served_packs(cache, cfg, "fam", desc, hits)
                    assert got == want, (family, label, v[:12], t, hits)
                    compared += len(want)
        if override is None:
            assert res.clears > clears0  # cfg2 dropped cfg1's table
            assert res.hits + res.misses == lookups0 + len(steps) * len(VALUES) * 2
        else:
            # Overrides never reach the cache.
            assert res.hits + res.misses == lookups0
    assert (compared == 0) == (family in ("unlimited", "no_rule"))


# -- what a cached key keeps on the collector's lists ------------------


def test_cached_key_heap_is_flat():
    """At most 2 collector-tracked objects a cached key beyond the
    map's own key (here: 1, the entry; the hot-key handle is the
    second where the sketch is on), and no numpy array."""
    mgr = Manager()
    cfg = make_config(mgr)
    res = ResolutionCache(LANE_DTYPE)
    now = 1_700_000_000
    n = 10_000
    descs = [Descriptor.of(("wild", f"acct_{i}")) for i in range(n)]
    # The rule's own state, its window and the first table growth are
    # not per key: pay them before the count.
    e = res.resolve(cfg, "test-domain", Descriptor.of(("wild", "warm")))
    e.rs.window(now)
    gc.collect()
    before = len(gc.get_objects())
    arrays_before = sum(isinstance(o, np.ndarray) for o in gc.get_objects())
    kept = []
    for d in descs:
        e = res.resolve(cfg, "test-domain", d)
        win = e.rs.window(now)
        key_bytes = e.stem_bytes + win.suffix
        kept.append(len(key_bytes) + len(record(win.head, len(key_bytes), win.tail)))
    del descs, d, e, win, key_bytes  # the requests are over: only the cache holds on
    gc.collect()
    objs = gc.get_objects()
    after = len(objs)
    arrays_after = sum(isinstance(o, np.ndarray) for o in objs)
    del objs
    assert len(res) == n + 1
    # The map's own key, a tuple of strings, is off the lists too after
    # the collection it survived.
    assert (after - before) / n <= 1.01
    assert arrays_after == arrays_before
    assert all(not gc.is_tracked(k) for k in list(res._live[1])[:100])


# -- capacity: second chance -------------------------------------------


def test_hot_set_survives_cold_scan():
    mgr = Manager()
    cfg = make_config(mgr)
    cap = 4096
    res = ResolutionCache(LANE_DTYPE, capacity=cap)
    hot = [Descriptor.of(("wild", f"hot_{i}")) for i in range(1000)]
    for d in hot:
        res.resolve(cfg, "test-domain", d)
    hits0, misses0 = res.hits, res.misses
    hot_hits = hot_lookups = 0
    # A cold scan of 3x capacity, one hot lookup every third cold key:
    # each hot key is touched ~4 times, ~once between two passes of the
    # hand.
    for i in range(3 * cap):
        res.resolve(cfg, "test-domain", Descriptor.of(("wild", f"cold_{i}")))
        assert len(res) <= cap
        if i % 3 == 0:
            h = res.hits
            res.resolve(cfg, "test-domain", hot[(i // 3) % len(hot)])
            hot_hits += res.hits - h
            hot_lookups += 1
    assert hot_hits / hot_lookups >= 0.95
    assert res.clears == 0
    assert len(res) == cap
    # Every insert past capacity evicted exactly one entry.
    assert res.evictions == (res.misses - misses0) + 1000 - cap
    assert res.hits - hits0 == hot_hits
    # The scan's own tail is what it evicted: the last cold keys are in.
    h = res.hits
    res.resolve(cfg, "test-domain", Descriptor.of(("wild", f"cold_{3 * cap - 1}")))
    assert res.hits == h + 1


def test_eviction_counter_and_gauge_exposed_on_metrics(engine):
    from ratelimit_tpu.observability import prometheus

    clock = PinnedTimeSource(1234)
    mgr = Manager()
    svc, cache, _ = make_service(engine, clock, mgr, resolution_cache_entries=2)
    cache.register_stats(mgr.store)
    for v in ("a", "b", "c", "d"):
        svc.should_rate_limit(
            RateLimitRequest("test-domain", [Descriptor.of(("wild", v))], 0)
        )
    text = prometheus.render(mgr.store)
    assert "# TYPE ratelimit_tpu_resolution_cache_evictions counter" in text
    assert "ratelimit_tpu_resolution_cache_evictions 2" in text
    assert "ratelimit_tpu_resolution_cache_clears 0" in text
    assert "ratelimit_tpu_resolution_cache_entries 2" in text


def test_concurrent_misses_keep_ring_and_map_together():
    """Misses insert under the cache's lock: however the RPC threads
    interleave (hits take no lock), the ring and the map hold the same
    entries and never more than capacity."""
    import sys
    import threading

    mgr = Manager()
    cfg = make_config(mgr)
    cap = 64
    res = ResolutionCache(LANE_DTYPE, capacity=cap)
    errors = []

    def worker(seed: int) -> None:
        rng = random.Random(seed)
        try:
            for _ in range(1000):
                d = Descriptor.of(("wild", f"k{rng.randrange(4 * cap)}"))
                e = res.resolve(cfg, "test-domain", d)
                assert e.stem_bytes == f"test-domain_wild_k{d.entries[0].value[1:]}_".encode()
        except Exception as exc:  # noqa: BLE001 - carried to the main thread
            errors.append(exc)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)  # interleave inside the miss path
    try:
        threads = [threading.Thread(target=worker, args=(s,)) for s in range(6)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    finally:
        sys.setswitchinterval(old)
    assert not errors, errors[0]
    entries = res._live[1]
    assert len(entries) == len(res._ring) == cap
    assert {id(e) for e in res._ring} == {id(e) for e in entries.values()}
    assert all(entries[e.key] is e for e in res._ring)
    assert res.clears == 0 and res.evictions > 0
