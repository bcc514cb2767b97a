"""`edge-per-ip` (chipbench/configs/edge-per-ip.json) at 390 of its
addresses on the CPU: the source's two rules on the one key
`remote_address` (a banned address at 0 a second, matched first; every
other address at 10 a second), one descriptor a request as
`flood5of100k1-poisson` draws them, sent as the load generator sends
them — serialized bytes over a gRPC connection — through the served
path (handler -> service -> resolution -> over-limit cache ->
dispatcher -> CounterEngine) under a pinned clock, on both slot tables.

Three sides get every sequence, and their answers (code,
`limit_remaining`, limit) are held equal one by one:

  on    the served path with the host's over-limit cache
        (LOCAL_CACHE_SIZE_IN_BYTES > 0: limiter/local_cache.py);
  off   the same path without it;
  plain backends/memory_cache.py over limiter/base.py, no cache: a dict
        of counters — and beside it the benchmark's own reference
        (chipbench/reference.py, which imports nothing of the program).

  (a) a flood across second boundaries: an entry set in second w
      answers nothing in w + 1;
  (b) the banned address: OVER_LIMIT from its first hit, also when it
      is the flooding one;
  (c) a cache of 2 entries under 5 flooding addresses: an evicted
      over-limit key goes back to the device and is still OVER_LIMIT;
  (d) 32 threads on one address inside one pinned second: exactly 10 OK;
  (e) a shadow rule whose key is cached answers OK with the full
      `limit_remaining` (upstream's fixed_cache_impl.go:57-67);
  (f) every case again with the two new counters stubbed to raise on
      any read: no answer depends on them;
  (g) BENCHMARK.json's new entries find their files, the cell's
      rehearsal is `correct` and both controls are not, and the new
      metrics read the change and are silent where the parent has
      nothing for them."""

import json
import os
import subprocess
import sys
import threading

import grpc
import numpy as np
import pytest

from chipbench import layers, traffic, wire
from chipbench.deploy import Deployment, load_json
from chipbench.reference import OK, OVER_LIMIT, Ledger
from ratelimit_tpu.api import Descriptor, RateLimitRequest
from ratelimit_tpu.backends.engine import CounterEngine
from ratelimit_tpu.backends.memory_cache import MemoryRateLimitCache
from ratelimit_tpu.backends.tpu_cache import TpuRateLimitCache
from ratelimit_tpu.limiter.local_cache import APPROX_ENTRY_BYTES, LocalCache
from ratelimit_tpu.server.grpc_server import create_grpc_server
from ratelimit_tpu.server.health import HealthChecker
from ratelimit_tpu.service.ratelimit import RateLimitService
from ratelimit_tpu.stats.manager import Manager
from ratelimit_tpu.utils.time import PinnedTimeSource

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG, MIX, CELL = "edge-per-ip", "flood5of100k1-poisson", "edge-per-ip.paced"
SEEDS = [2147483741, 17, 20261002]
TABLES = [pytest.param(True, id="native"), pytest.param(False, id="python")]
COUNTERS = [pytest.param(False, id="counters"), pytest.param(True, id="counters-raise")]
T0 = 1_790_000_000
LIMIT = 10
BANNED = 0  # key number of the banned address: the first family's one leaf
CACHE_BYTES = 1 << 20
TPU = "ratelimit.tpu."
LC = "ratelimit.localcache."
H = "ratelimit_server.ShouldRateLimit."


class Runtime:
    """The runtime loader's surface, holding one rule file."""

    def __init__(self, files: dict):
        self.files = files

    def snapshot(self):
        return self

    def keys(self):
        return sorted(self.files)

    def get(self, key):
        return self.files.get(key, "")

    def add_update_callback(self, fn):
        pass


class RaisesOnRead:
    """A counter that can be written and never read: whatever looks at
    its value — the scrape included — fails the test."""

    def add(self, delta):
        pass

    def inc(self):
        pass

    def _read(self, *args):
        raise AssertionError("something read a counter that nothing but the scrape may read")

    value = __int__ = __index__ = __bool__ = __eq__ = __lt__ = __le__ = __gt__ = __ge__ = _read
    __add__ = __radd__ = __sub__ = __rsub__ = __hash__ = __repr__ = __str__ = _read


def deployment(seed: int, keys: int = 390, shadow: bool = False, limit_offset: int = 0) -> Deployment:
    config = load_json("configs", CONFIG)
    banned, address = config["families"]
    assert (banned["path"], banned["limits"], banned["unit"]) == ([["remote_address", 1]], [0], "second")
    assert (address["key"], address["unit"], address["limit"]) == ("remote_address", "second", LIMIT)
    address["keys"] = keys
    if shadow:
        address["shadow"] = True
    return Deployment(config, seed, limit_offset=limit_offset)


class Plain:
    """backends/memory_cache.py under the service, no local cache."""

    def __init__(self, dep: Deployment, clock):
        self.dep = dep
        self.service = RateLimitService(
            Runtime({"config.e": dep.yaml(0)}), MemoryRateLimitCache(time_source=clock), Manager(), clock=clock
        )

    def ask(self, k: int) -> tuple:
        request = RateLimitRequest(self.dep.domain_name(0), [Descriptor.of(*self.dep.entries(int(k)))], 1)
        (s,) = self.service.should_rate_limit(request).statuses
        return (int(s.code), s.limit_remaining, s.current_limit.requests_per_unit)

    def close(self) -> None:
        pass


class Served:
    """The program's gRPC server over the device bank, with the
    over-limit cache of `cache_bytes` (None: off)."""

    def __init__(self, dep: Deployment, clock, native: bool, cache_bytes, stub: bool = False, workers: int = 4):
        self.dep = dep
        self.local = None
        if cache_bytes:
            # The cache's own clock follows the pinned one: a TTL is a
            # whole window, as served (runner.py gives it time.monotonic).
            self.local = LocalCache(cache_bytes, clock=lambda: float(clock.now))
        self.engine = CounterEngine(num_slots=1 << 14, native_table=native)
        self.cache = TpuRateLimitCache(
            self.engine, time_source=clock, batch_window_us=200, local_cache=self.local
        )
        self.stub = stub
        if stub:
            self.cache.stat_local_decisions = RaisesOnRead()
            self.cache.stat_requests_no_launch = RaisesOnRead()
        manager = Manager()
        self.store = manager.store
        self.cache.register_stats(self.store)
        if self.local is not None:
            self.local.register_stats(self.store)
        service = RateLimitService(Runtime({"config.e": dep.yaml(0)}), self.cache, manager, clock=clock)
        self.server = create_grpc_server(
            service, HealthChecker(), self.store, host="127.0.0.1", port=0, max_workers=workers
        )
        self.server.start()
        self.channel = grpc.insecure_channel(f"127.0.0.1:{self.server.bound_port}")
        self.call = self.channel.unary_unary(
            wire.METHOD, response_deserializer=wire.rls_pb2.RateLimitResponse.FromString
        )
        self.sent = 0

    def ask(self, k: int) -> tuple:
        """One request, as chipbench/run.py's Caller sends and reads it."""
        self.sent += 1
        resp = self.call(traffic.make_request(self.dep, 0, [k]), timeout=60)
        (s,) = resp.statuses
        return (s.code, s.limit_remaining, s.current_limit.requests_per_unit)

    def stat(self, name: str) -> int:
        return self.store.snapshot()[name]

    def histogram(self, name: str) -> dict:
        return self.store.histograms()[H + name]

    def close(self) -> None:
        self.channel.close()
        self.server.stop(None)
        self.cache.close()


class Three:
    """The three sides under one pinned clock, and the benchmark's
    reference beside them."""

    def __init__(self, seed: int, native: bool, stub: bool, cache_bytes=CACHE_BYTES, **dep_kwargs):
        self.dep = deployment(seed, **dep_kwargs)
        self.clock = PinnedTimeSource(T0)
        self.on = Served(self.dep, self.clock, native, cache_bytes, stub=stub)
        self.off = Served(self.dep, self.clock, native, None)
        self.plain = Plain(self.dep, self.clock)
        self.ledger = Ledger(self.dep)
        self.answers = []
        self.compared = self.mismatches = 0
        self.first = None

    def ask(self, k: int) -> tuple:
        """The same request to all three; equal, or the test ends here."""
        got = self.on.ask(k)
        assert got == self.off.ask(k) == self.plain.ask(k), (k, len(self.answers), self.clock.now)
        now = self.clock.now
        c, m, why = self.ledger.expect(np.array([k]), now, now, [got])
        self.compared, self.mismatches, self.first = self.compared + c, self.mismatches + m, self.first or why
        self.answers.append((now, int(k), got))
        return got

    def close(self) -> None:
        for side in (self.on, self.off, self.plain):
            side.close()

    def host_answered(self) -> int:
        """Requests of the `on` side that the cache answered, by the
        cache's own count (read off the object: the store's snapshot
        would read the stubbed counters)."""
        return self.on.local.hit_count

    def check_counters(self) -> None:
        """`local_decisions` and `requests_no_launch` against what was
        sent: one descriptor a request, so both equal the cache's hits;
        `response_ms.no_launch` holds the same requests."""
        on, off = self.on, self.off
        if on.stub:
            return
        hits = self.host_answered()
        assert on.stat(TPU + "local_decisions") == on.stat(TPU + "requests_no_launch") == hits
        assert on.stat(LC + "lookupCount") == on.stat(H + "descriptors") == on.sent
        assert on.histogram("response_ms.no_launch")["count"] == hits
        assert on.histogram("response_ms")["count"] == on.sent
        # Without the cache every request launches.
        assert off.stat(TPU + "local_decisions") == off.stat(TPU + "requests_no_launch") == 0
        assert off.histogram("response_ms.no_launch")["count"] == 0
        assert LC + "hitCount" not in off.store.snapshot()


@pytest.fixture
def three():
    made = []

    def make(*args, **kwargs):
        made.append(Three(*args, **kwargs))
        return made[-1]

    yield make
    for t in made:
        t.close()


@pytest.mark.parametrize("stub", COUNTERS)
@pytest.mark.parametrize("native", TABLES)
@pytest.mark.parametrize("seed", SEEDS)
def test_a_flood_matches_with_the_cache_on_off_and_the_reference(three, seed, native, stub):
    """(a) The cell's own requests at rehearsal size — 391 addresses,
    int(391 x 0.00005) -> 1 flooding with nine tenths of the requests —
    over 4 pinned seconds of 60 requests: the flooding address crosses
    10 in every second, and its entry of second w answers nothing in
    w + 1 (its first 10 hits there are OK again, from the device)."""
    t = three(seed, native, stub)
    mix = load_json("traffic", MIX)
    seconds, per_second = 4, 60
    _, keys = traffic.plan(mix, t.dep, seed, seconds * per_second)
    assert keys.shape == (seconds * per_second, 1)
    flooding = int(np.bincount(keys[:, 0]).argmax())
    assert (keys[:, 0] == flooding).mean() > 0.8 and flooding != BANNED
    for i, k in enumerate(keys[:, 0]):
        t.clock.now = T0 + 1 + i // per_second
        t.ask(int(k))

    assert t.mismatches == 0, t.first
    assert t.compared == len(keys)
    book = t.ledger.check_log()
    assert (book["over_admitted"], book["miscounted"]) == (0, 0), book["example"]
    hits_before = 0
    for sec in range(seconds):
        mine = [got for now, k, got in t.answers if now == T0 + 1 + sec and k == flooding]
        assert len(mine) > LIMIT + 20
        assert mine[:LIMIT] == [(OK, LIMIT - 1 - j, LIMIT) for j in range(LIMIT)]
        assert set(mine[LIMIT:]) == {(OVER_LIMIT, 0, LIMIT)}
        # The hit that crossed was decided by the device and set the
        # entry; every later one of the second was answered by it.
        hits_before += len(mine) - LIMIT - 1
    banned = sum(1 for _, k, _ in t.answers if k == BANNED)
    assert t.host_answered() >= hits_before > seconds * 20
    assert t.host_answered() <= hits_before + banned
    assert len(t.on.local) <= seconds * (2 if banned else 1)  # an entry an address-second over its limit
    t.check_counters()


@pytest.mark.parametrize("stub", COUNTERS)
@pytest.mark.parametrize("native", TABLES)
@pytest.mark.parametrize("flooding", [False, True], ids=["among-others", "flooding"])
def test_the_banned_address_is_over_limit_from_its_first_hit(three, native, flooding, stub):
    """(b) `requests_per_unit: 0`: the one rule shape no cell had
    served.  Its first hit of a second reaches the device (after 1 >
    limit 0) and sets the cache entry, the rest of the second the host
    answers; never OK, in either way of being sent."""
    t = three(SEEDS[0], native, stub)
    rng = np.random.default_rng([45, flooding])
    share = 0.9 if flooding else 0.1
    per_second = 40
    for i in range(3 * per_second):
        t.clock.now = T0 + 1 + i // per_second
        k = BANNED if rng.random() < share else int(rng.integers(1, t.dep.kpd))
        t.ask(k)

    assert t.mismatches == 0, t.first
    mine = [(now, got) for now, k, got in t.answers if k == BANNED]
    assert len(mine) > (60 if flooding else 3)
    assert {got for _, got in mine} == {(OVER_LIMIT, 0, 0)}
    assert all(got[0] == OK for _, k, got in t.answers if k != BANNED)  # nobody else reaches 10
    seconds = len({now for now, _ in mine})
    assert t.host_answered() == len(mine) - seconds  # one device decision a second it was seen in
    assert t.ledger.check_log()["over_admitted"] == 0
    t.check_counters()


@pytest.mark.parametrize("stub", COUNTERS)
@pytest.mark.parametrize("native", TABLES)
def test_an_evicted_over_limit_key_goes_back_to_the_device_and_stays_over(three, native, stub):
    """(c) LOCAL_CACHE_SIZE_IN_BYTES=128 is 2 entries; 5 addresses
    flood in turn, 25 hits each in one second: every set pushes out the
    oldest entry, so most over-limit hits find no entry, reach the
    device, and are OVER_LIMIT there all the same."""
    t = three(SEEDS[1], native, stub, cache_bytes=2 * APPROX_ENTRY_BYTES)
    assert t.on.local.max_entries == 2
    addresses = [11, 23, 57, 190, 333]
    t.clock.now = T0 + 1
    for _ in range(25):
        for k in addresses:
            t.ask(k)

    assert t.mismatches == 0, t.first
    for k in addresses:
        mine = [got for _, key, got in t.answers if key == k]
        assert mine == [(OK, LIMIT - 1 - j, LIMIT) for j in range(LIMIT)] + [(OVER_LIMIT, 0, LIMIT)] * 15
    over = 5 * 15
    local = t.on.local
    assert local.evacuate_count > 5 and len(local) == 2
    # In turn over 5 addresses with room for 2: an address's entry is
    # gone before its next hit, so no over-limit hit was answered by
    # the host and each went back to the device, which set it again.
    assert t.host_answered() == 0
    assert local.evacuate_count == over - 2
    t.check_counters()
    if not stub:
        rule = "ratelimit.service.rate_limit." + t.dep.domain_name(0) + ".remote_address."
        assert t.on.stat(rule + "over_limit") == over
        assert t.on.stat(rule + "over_limit_with_local_cache") == 0


@pytest.mark.parametrize("stub", COUNTERS)
@pytest.mark.parametrize("native", TABLES)
def test_thirty_two_threads_on_one_address_admit_exactly_ten(native, stub):
    """(d) 32 callers, 4 requests each, one address, one pinned second,
    the server's 32 RPC threads: whoever is decided by the device and
    whoever by the cache, exactly 10 are OK, with 9..0 remaining once
    each — with the cache and without."""
    dep, clock = deployment(SEEDS[2]), PinnedTimeSource(T0 + 1)
    for cache_bytes in (CACHE_BYTES, None):
        side = Served(dep, clock, native, cache_bytes, stub=stub and bool(cache_bytes), workers=32)
        try:
            payload = traffic.make_request(dep, 0, [77])
            answers, lock = [], threading.Lock()
            start = threading.Barrier(32)

            def caller(i):
                channel = grpc.insecure_channel(
                    f"127.0.0.1:{side.server.bound_port}", options=[("grpc.use_local_subchannel_pool", 1)]
                )
                call = channel.unary_unary(
                    wire.METHOD, response_deserializer=wire.rls_pb2.RateLimitResponse.FromString
                )
                call(traffic.make_request(dep, 0, [100 + i]), timeout=60)  # connected before the start
                start.wait(60)
                mine = []
                for _ in range(4):
                    (s,) = call(payload, timeout=60).statuses
                    mine.append((s.code, s.limit_remaining, s.current_limit.requests_per_unit))
                with lock:
                    answers.extend(mine)
                channel.close()

            threads = [threading.Thread(target=caller, args=(i,)) for i in range(32)]
            for th in threads:
                th.start()
            for th in threads:
                th.join(120)
                assert not th.is_alive()
            ok = sorted(a for a in answers if a[0] == OK)
            assert ok == [(OK, j, LIMIT) for j in range(LIMIT)]
            assert len(answers) == 128 and answers.count((OVER_LIMIT, 0, LIMIT)) == 118
            if cache_bytes:
                assert 0 < side.local.hit_count <= 117 and len(side.local) == 1
                if not stub:
                    assert side.stat(TPU + "local_decisions") == side.local.hit_count
                    assert side.stat(TPU + "requests_no_launch") == side.local.hit_count
                    assert side.histogram("response_ms.no_launch")["count"] == side.local.hit_count
        finally:
            side.close()


@pytest.mark.parametrize("stub", COUNTERS)
@pytest.mark.parametrize("native", TABLES)
def test_a_cached_key_of_a_shadow_rule_is_answered_ok_with_the_full_limit(three, native, stub):
    """(e) The address rule in shadow mode: past 10 the device answers
    OK (shadow) and the key is cached; the host then skips the counter
    and answers OK with `limit_remaining` = the limit, as upstream's
    fixed_cache_impl.go:57-67 does — the code equals the other sides',
    the remaining does not (they count on: 0), which is why the
    deployment's own rules carry no shadow rule."""
    dep, clock = deployment(SEEDS[0], shadow=True), PinnedTimeSource(T0 + 1)
    on = Served(dep, clock, native, CACHE_BYTES, stub=stub)
    off = Served(dep, clock, native, None)
    plain = Plain(dep, clock)
    try:
        rows = [(on.ask(5), off.ask(5), plain.ask(5)) for _ in range(LIMIT + 6)]
    finally:
        for side in (on, off, plain):
            side.close()
    for j, (a, b, c) in enumerate(rows):
        assert a[0] == b[0] == c[0] == OK and b == c == (OK, max(0, LIMIT - 1 - j), LIMIT)
        # Hit 11 crosses on the device and sets the entry; 12.. are skipped.
        assert a == (b if j <= LIMIT else (OK, LIMIT, LIMIT))
    assert on.local.hit_count == 5
    if not stub:
        assert on.stat(TPU + "local_decisions") == on.stat(TPU + "requests_no_launch") == 5


def test_a_raised_limit_and_a_lifted_ban_show_in_the_log():
    """What `--control server` does to this deployment, in process: the
    server is given every limit raised by one (11 a second, the ban 1),
    the reference keeps the configuration's.  The log comparison must
    show an over-admitted window of the address rule and an admitted
    hit of the banned address: the checks are not vacuous for either
    rule shape."""
    clock = PinnedTimeSource(T0 + 1)
    raised = Served(deployment(SEEDS[0], limit_offset=1), clock, True, CACHE_BYTES)
    ledger = Ledger(deployment(SEEDS[0]))
    mismatches = 0
    try:
        for k in [5] * 14 + [BANNED] * 3:
            _, m, _ = ledger.expect(np.array([k]), clock.now, clock.now, [raised.ask(k)])
            mismatches += m
    finally:
        raised.close()
    window = T0 + 1
    assert ledger.admitted[(5, window)] == LIMIT + 1 and ledger.admitted[(BANNED, window)] == 1
    book = ledger.check_log()
    assert book["over_admitted"] == 2 and book["miscounted"] == 2
    assert mismatches == 14 + 3  # every answer carries the raised limit


# -- the manifest, the cell's rehearsal, the new metrics ----------------------


def test_the_configuration_states_source_guarantee_and_defaults():
    config = load_json("configs", CONFIG)
    for part in ("envoyproxy/ratelimit README Example 3", "edge_proxy_per_ip", "remote_address", "10/second",
                 "0/second", "Local Cache", "LOCAL_CACHE_SIZE_IN_BYTES"):
        assert part in config["source"], part
    assert len(config["source"]) <= 200
    assert config["reduced"] == []
    assert config["server_env"] == {"TPU_WARMUP": "1", "LOCAL_CACHE_SIZE_IN_BYTES": str(CACHE_BYTES)}
    assert CACHE_BYTES // APPROX_ENTRY_BYTES == 16384
    assert {"readme", "addresses", "local_cache_size", "descriptors_per_request", "flood", "server", "load"} <= set(
        config["assumed"]
    )
    assert "limit" not in config["assumed"]  # 10 a second and the ban at 0 are the source's
    for part in ("exact admission", "a banned address none", "an OK answer always comes from the device bank",
                 "over-limit cache", "limit_remaining 0"):
        assert part in config["guarantee"], part
    assert config["families"] == [
        {"name": "banned", "path": [["remote_address", 1]], "limits": [0], "unit": "second"},
        {"name": "remote_address", "key": "remote_address", "unit": "second", "limit": LIMIT, "keys": 100000,
         "load": True},
    ]
    assert config["domains"] == {"count": 1, "rehearse_count": 1, "prefix": "e"}
    assert (config["load_per_request"], config["load_connections"]) == (4096, 1)
    assert config["replay"] == {"requests": 800, "pool": 16}
    # Both rules on one key, the value-specific one first, as the source has them.
    text = deployment(SEEDS[0]).yaml(0)
    assert text.index("value: v0") < text.index("requests_per_unit: 10")
    assert text.count("- key: remote_address") == 2 and "requests_per_unit: 0\n" in text
    mix = load_json("traffic", MIX)
    assert (mix["loop"], mix["connections"], mix["descriptors_per_request"]) == ("open", 4, 1)
    assert mix["domain_pick"] == {"dist": "uniform"}
    assert mix["key_pick"] == {"dist": "hotset", "hot_share": 0.9, "hot_fraction": 0.00005}
    assert int(100001 * mix["key_pick"]["hot_fraction"]) == 5
    assert (mix["warm_s"], mix["drain_s"], mix["rpc_timeout_s"]) == (3.0, 5.0, 20.0)
    assert mix["rate_rps"] % 10 == 0
    assert mix["rate_rps"] == RATE


RATE = 500  # half the measured knee of 1,000 (PERF.md section 4); ISSUE 45 expected 350-750

NEW_METRICS = {
    "no_launch_request_share.paced": ("%", "higher", "service + resolution"),
    "local_cache_entries.paced": ("count", "lower", "service + resolution"),
    "no_launch_response_ms.paced": ("ms", "lower", "gRPC handler"),
}
# PR 27's seven start at `mixed-1m.paced`; this cell runs each of their
# layers (a 2^20-slot table taking a lease an address-second, slot GC
# every 5 s, the 30 s snapshot, ~110 one-lane launches a second).
FROM_MIXED_1M = (
    "slot_fill_share.paced", "slot_evictions.paced", "gc_pause_ms.paced", "snapshot_hold_ms.paced",
    "snapshot_timeouts.paced", "device_submit_p99_us.paced", "readback_p99_us.paced",
)
# PR 35's two read `tenants-zipf.paced`'s rule load alone.
NOT_HERE = {"config_load_us_per_rule.paced", "config_parse_share.paced"}


def test_the_cell_is_in_the_manifest_and_reports_every_paced_metric():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    config = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert config == {
        "name": CONFIG, "source": load_json("configs", CONFIG)["source"],
        "file": f"chipbench/configs/{CONFIG}.json", "reduced": [], "why": config["why"],
    }
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    # One chip: nothing in the cell exists only across chips.
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, MIX, 1)
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert f"{RATE} requests/s (half the knee)" in cell["why"]
    for part in ("1 descriptor/request", "5 flooding addresses", "over-limit cache", "1-lane launches"):
        assert part in cell["why"], part
    for entry in bench["configs"] + bench["workloads"] + bench["per_layer"]:
        for key in ("why", "source", "layer"):
            text = entry.get(key, "x")
            assert 1 <= len(text) <= 200 and text.isprintable(), (entry["name"], key, len(text))
    p50 = next(m for m in bench["end_to_end"] if m["name"] == "p50_ms")
    assert p50["workloads"].count(CELL) == 1 and "workloads" not in bench["end_to_end"][1]
    names = [m["name"] for m in bench["per_layer"]]
    first = names.index(next(iter(NEW_METRICS)))
    mine = bench["per_layer"][first:first + len(NEW_METRICS)]
    assert [m["name"] for m in mine] == list(NEW_METRICS)
    for m in mine:
        assert set(m) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert (m["unit"], m["better"], m["layer"]) == NEW_METRICS[m["name"]]
        assert (m["source"], m["moves"], m["workloads"][0]) == ("program_counter", "p50_ms", CELL)
        assert os.path.exists(os.path.join(ROOT, "chipbench", "layer_metrics", m["name"] + ".json"))
    older = bench["per_layer"][:first]
    assert {m["layer"] for m in mine} <= {m["layer"] for m in older}  # no layer invented
    for m in older:
        assert (CELL in m["workloads"]) == (m["name"] not in NOT_HERE), m["name"]
        assert m["workloads"].count(CELL) <= 1
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024


@pytest.mark.parametrize("name", FROM_MIXED_1M)
def test_an_accepted_metric_whose_layer_the_cell_runs_lists_it(name):
    """Slot leases, slot GC, the snapshot and the launches' device
    calls all run in this cell: the metrics that read them list it,
    after the cells they had, through readers every server feeds."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        (m,) = [m for m in json.load(f)["per_layer"] if m["name"] == name]
    assert m["moves"] == "p50_ms" and m["workloads"].count(CELL) == 1
    assert m["workloads"].index(CELL) > m["workloads"].index("bulk-recipients.paced")
    assert load_json("layer_metrics", name)["reader"]["kind"] in ("level", "delta", "launches")


def test_a_fallback_answer_is_a_launched_request_to_counter_and_histogram_alike():
    """One predicate for both: a request that queued a work item is
    `launched` whatever answered it — here the DEVICE_FAILURE_MODE
    fallback after an injected device error, where no launch signalled
    (legs' signal stamp 0) — so neither `requests_no_launch` nor
    `response_ms.no_launch` takes it; a request the backend left
    unmarked feeds no histogram either."""
    from ratelimit_tpu.cluster.faults import DeviceFaultInjector
    from ratelimit_tpu.server.grpc_server import ServerReporter

    dep, clock, inj = deployment(SEEDS[0]), PinnedTimeSource(T0), DeviceFaultInjector()
    cache = TpuRateLimitCache(
        inj.wrap_engine("lane0", CounterEngine(num_slots=256, buckets=(8,))), time_source=clock,
        batch_window_us=100, kernel_deadline_s=0.25, device_failure_mode="host", fault_interval_s=0,
        local_cache=LocalCache(CACHE_BYTES, clock=lambda: float(clock.now)),
    )
    manager = Manager()
    service = RateLimitService(Runtime({"config.e": dep.yaml(0)}), cache, manager, clock=clock)
    reporter = ServerReporter(manager.store)
    try:
        inj.raise_error("lane0")
        request = RateLimitRequest(dep.domain_name(0), [Descriptor.of(*dep.entries(7))], 1)
        (status,) = service.should_rate_limit(request).statuses
        assert int(status.code) == OK and cache.fault_domain.stat_fallback_decisions == 1
        assert request.launched is True and request.legs[1] == 0
        assert cache.stat_requests_no_launch.value() == cache.stat_local_decisions.value() == 0
        for launched, count in ((request.launched, 0), (None, 0), (False, 1)):
            reporter.observe_phases(0.0, 0.001, 0.002, 0.003, launched)
            assert manager.store.histograms()[H + "response_ms.no_launch"]["count"] == count
        assert manager.store.histograms()[H + "response_ms"]["count"] == 3
    finally:
        inj.heal()
        cache.close()


@pytest.mark.parametrize("control, correct", [(None, True), ("server", False), ("reference", False)])
def test_the_cells_rehearsal_is_correct_and_its_controls_are_not(control, correct):
    """`python3 -m chipbench.run --workload edge-per-ip.paced
    --rehearse`: the whole harness on the CPU at 391 addresses —
    server with the cache on, key load, four generator workers, window,
    replay, log comparison."""
    args = ["--workload", CELL, "--seed", str(2**31 + 45), "--seconds", "4", "--trace", "0", "--rehearse"]
    if control:
        args += ["--control", control]
    out = subprocess.run(
        [sys.executable, "-m", "chipbench.run", *args], cwd=ROOT, capture_output=True, text=True, timeout=600,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    result = json.loads(out.stdout.splitlines()[-1])
    assert result["correct"] is correct, out.stdout[-2000:]
    assert result["attempted"] > 0 and result["failed"] == 0 and not result["metrics"]
    checks = dict(
        line.split()[1].split("=") for line in out.stdout.splitlines()
        if line.startswith("check ") and "limit=" in line
    )
    assert set(checks) == {
        "load_answers_wrong", "replay_mismatches", "log_over_admitted_windows",
        "log_miscounted_windows", "device_path_breaks",
    }
    assert checks["device_path_breaks"] == "0"
    assert all(v == "0" for v in checks.values()) is correct
    (compared,) = [ln for ln in out.stdout.splitlines() if ln.startswith("check compared:")]
    assert int(compared.split("replay decisions ")[1].split(",")[0]) > 800
    if control == "server":
        # The raised limit shows as windows with 11 OK answers (and the
        # lifted ban as one with 1), not only as wrong `limit` fields.
        assert int(checks["log_over_admitted_windows"]) > 0


def _obs(change: bool, cache_on: bool = True) -> dict:
    """The two /stats.json fetches of a traced run: the parent has the
    cache's gauges (they are upstream parity, older than this PR) and
    lacks the two counters and the histogram."""
    def stats(n):
        flat = {H + "descriptors": 1000 * n}
        if cache_on:
            flat.update({
                LC + "hitCount": 800 * n, LC + "missCount": 200 * n, LC + "lookupCount": 1000 * n,
                LC + "evacuateCount": 3 * n, LC + "entryCount": 60 * (n - 1),
            })
        hist = {H + "response_ms": {"count": 1000 * n, "total_ms": 900.0 * n}}
        if change:
            flat.update({TPU + "local_decisions": 790 * n, TPU + "requests_no_launch": 780 * n})
            hist[H + "response_ms.no_launch"] = {"count": 780 * n, "total_ms": 234.0 * n}
        return {"stats": flat, "histograms": hist}

    return {"stats_a": stats(1), "stats_b": stats(3)}


@pytest.mark.parametrize(
    "name, on_change, on_parent",
    [
        ("no_launch_request_share.paced", 78.0, None),
        ("local_cache_entries.paced", 120, 120),
        ("no_launch_response_ms.paced", 0.3, None),
    ],
)
def test_new_metric_reads_the_change_and_raises_nothing_on_the_parent(name, on_change, on_parent):
    spec = load_json("layer_metrics", name)
    assert set(spec) == {"what", "reader"}
    assert layers.read(spec["reader"], _obs(change=True)) == pytest.approx(on_change)
    got = layers.read(spec["reader"], _obs(change=False))
    assert got is None if on_parent is None else got == pytest.approx(on_parent)
    # A server with the cache off (every other cell's) has no gauges.
    if name.startswith("local_cache_"):
        assert layers.read(spec["reader"], _obs(change=True, cache_on=False)) is None
    assert layers.read(spec["reader"], {}) is None  # nothing gathered: nothing read, nothing raised
