"""`sharded-recipients` (chipbench/configs/sharded-recipients.json):
upstream's Redis-Cluster deployment on `BACKEND_TYPE=tpu-sharded`, at a
small size on the 8-device virtual CPU mesh of tests/conftest.py —

  (a) the configuration's one per-DAY key-only rule through a full
      `ratelimit_tpu.runner.Runner` with `backend_type="tpu-sharded"`
      under a pinned clock, 64-descriptor requests as
      `hot10pct64-poisson` draws them, sent as serialized bytes over
      gRPC, answer for answer (code, `limit_remaining`, limit) against
      backends/memory_cache.py AND against chipbench/reference.py's
      `Ledger`: keys crossing their cap of 5, a key several times
      inside one request, a DAY boundary;
  (b) a request all of whose slots one chip owns and one spread
      evenly over the chips: the same answers, the same `shape` and
      `padded_lanes` — the bucket, as on one chip — whatever the skew,
      and no routing counter on any bank;
  (c) the shares add up: for seeded batches (duplicate slots, fresh
      lanes, a saturating total, out-of-table probes, every readback
      dtype) meshes of 1, 2, 4 and 8 equal the one-chip `CounterEngine`
      bit for bit, the counters on the devices included;
  (d) an injected device fault on a sharded bank -> quarantine ->
      restart through the DEFAULT engine factory -> counters restored,
      later answers right;
  (e) the launch protocol: the one-chip engine's — one `rl.launch.pack`
      with nothing inside it, the readback copy asked for inside the
      device-call bracket and from ONE chip, nothing between readback
      and decide — and the program carries the one-chip program's
      name, compiles for a described `v5e:2x2`, donates the counters
      and holds exactly one all-reduce;
  (f) BENCHMARK.json's entries find their files, the cell's rehearsal
      is `correct` and both controls are not, and PR 49's three
      routing metrics read the PR 49 program's counters, read nothing
      on this tree and raise nothing."""

import contextlib
import glob
import json
import os
import re
import subprocess
import sys
import threading
import time

import grpc
import jax
import numpy as np
import pytest

from chipbench import layers, traffic, wire
from chipbench.deploy import Deployment, load_json
from chipbench.reference import OK, OVER_LIMIT, Ledger
from ratelimit_tpu.api import Code, Descriptor, RateLimitRequest
from ratelimit_tpu.backends.engine import DEFAULT_BUCKETS, CounterEngine, HostBatch
from ratelimit_tpu.backends.fault_domain import default_engine_factory
from ratelimit_tpu.backends.memory_cache import MemoryRateLimitCache
from ratelimit_tpu.backends.tpu_cache import TpuRateLimitCache
from ratelimit_tpu.cluster.faults import DeviceFaultInjector
from ratelimit_tpu.config.loader import ConfigFile, load_config
from ratelimit_tpu.observability import spans as span_names
from ratelimit_tpu.observability.spans import SPANS
from ratelimit_tpu.parallel import ShardedCounterEngine, make_mesh
from ratelimit_tpu.runner import Runner
from ratelimit_tpu.service.ratelimit import RateLimitService
from ratelimit_tpu.settings import Settings
from ratelimit_tpu.stats.manager import Manager
from ratelimit_tpu.utils.time import PinnedTimeSource

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG, MIX, CELL = "sharded-recipients", "hot10pct64-poisson", "sharded-recipients.paced"
TWIN_CONFIG, TWIN_CELL = "bulk-recipients", "bulk-recipients.paced"
SEEDS = [2147483777, 19, 20261004]
T0 = 1_790_000_000
DAY = 86_400
LIMIT = 5
CHIPS = 8  # the virtual mesh of tests/conftest.py
BANK = "ratelimit.tpu.bank0."

pytestmark = pytest.mark.skipif(
    "xla_force_host_platform_device_count=8" not in os.environ.get("XLA_FLAGS", ""),
    reason="needs the 8-device virtual CPU mesh of tests/conftest.py",
)


def deployment(seed: int, keys: int) -> Deployment:
    config = load_json("configs", CONFIG)
    (family,) = config["families"]
    assert (family["unit"], family["limit"]) == ("day", LIMIT)
    family["keys"] = keys
    return Deployment(config, seed)


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


class Served:
    """The configuration at `keys` keys behind the program's own
    Runner as `BACKEND_TYPE=tpu-sharded` wires it (runner._make_engine:
    a mesh over every device), and beside it backends/memory_cache.py
    under the same service and the benchmark's plain reference."""

    def __init__(self, seed: int, tmp_path, keys: int = 1024):
        self.dep = dep = deployment(seed, keys)
        self.mix = load_json("traffic", MIX)
        self.clock = PinnedTimeSource(T0)
        dep.write_runtime(str(tmp_path))
        settings = Settings(
            host="127.0.0.1", port=0, grpc_host="127.0.0.1", grpc_port=0,
            debug_host="127.0.0.1", debug_port=0, use_statsd=False,
            backend_type="tpu-sharded", tpu_num_slots=1 << 14,
            runtime_path=str(tmp_path), runtime_subdirectory="ratelimit",
            tpu_checkpoint_interval_s=0,
        )
        assert tuple(settings.tpu_batch_buckets) == DEFAULT_BUCKETS
        self.runner = Runner(settings, time_source=self.clock)
        self.runner.start()
        self.engine = self.runner.cache.engine
        self.store = self.runner.stats_manager.store
        self.memory = RateLimitService(
            Runtime({"config.s": dep.yaml(0)}), MemoryRateLimitCache(time_source=self.clock),
            Manager(), clock=self.clock,
        )
        self.channel = grpc.insecure_channel(f"127.0.0.1:{self.runner.grpc_server.bound_port}")
        self.call = self.channel.unary_unary(
            wire.METHOD, response_deserializer=wire.rls_pb2.RateLimitResponse.FromString
        )
        self.ledger = Ledger(dep)
        self.compared = self.mismatches = self.differ_from_memory = 0
        self.first = None

    def ask(self, keys) -> list:
        """One request as chipbench/run.py's Caller sends and reads it,
        judged against the reference and against memory_cache."""
        keys = np.asarray(keys)
        resp = self.call(traffic.make_request(self.dep, 0, keys), timeout=120)
        got = [(s.code, s.limit_remaining, s.current_limit.requests_per_unit) for s in resp.statuses]
        assert len(got) == len(keys)
        now = self.clock.now
        c, m, why = self.ledger.expect(keys, now, now, got)
        self.compared, self.mismatches, self.first = self.compared + c, self.mismatches + m, self.first or why
        request = RateLimitRequest(
            self.dep.domain_name(0), [Descriptor.of(*self.dep.entries(int(k))) for k in keys], 1
        )
        want = [
            (int(s.code), s.limit_remaining, s.current_limit.requests_per_unit)
            for s in self.memory.should_rate_limit(request).statuses
        ]
        self.differ_from_memory += sum(g != w for g, w in zip(got, want))
        self.first = self.first or next(
            (f"key {k}: answered {g}, memory_cache {w}" for k, g, w in zip(keys, got, want) if g != w), None
        )
        return got

    def stat(self, name: str) -> int:
        return self.store.snapshot()[BANK + name]

    def close(self) -> None:
        self.channel.close()
        self.runner.stop()


@pytest.fixture
def served(tmp_path):
    made = []

    def make(seed, **kwargs):
        made.append(Served(seed, tmp_path, **kwargs))
        return made[-1]

    yield make
    for s in made:
        s.close()


# -- (a) the served sharded path = memory_cache = the reference ---------------


@pytest.mark.parametrize("seed", SEEDS)
def test_the_served_sharded_path_matches_memory_cache_and_the_reference(served, seed):
    """40 requests of 64 over 200 keys against a cap of 5 (12.8 hits a
    key, so most cross it), then a key with 2 remaining 2, 5 and 7
    times inside one request, then the DAY boundary: every counter
    starts again, on the chips as in both references."""
    s = served(seed, keys=200)
    assert isinstance(s.engine, ShardedCounterEngine) and s.engine.model.num_banks == CHIPS
    _, keys = traffic.plan(s.mix, s.dep, seed, 40)
    assert keys.shape == (40, 64)
    over = 0
    for row in keys[:30]:
        over += sum(code == OVER_LIMIT for code, _, _ in s.ask(row))
    hits = np.bincount(keys[:30].reshape(-1), minlength=s.dep.kpd)
    assert over == int(np.maximum(hits - LIMIT, 0).sum()) > 0
    assert int((hits > LIMIT).sum()) > s.dep.kpd // 3

    s.clock.advance(DAY)  # a new day: every key has its 5 again
    day2 = int(T0 + DAY)
    assert s.clock.now == day2 and day2 // DAY == T0 // DAY + 1
    # Duplicates inside a request, the cap crossed inside it: answers
    # in request order.
    rng = np.random.default_rng([seed, 1])
    for times in (2, 5, 7):
        key = int(rng.integers(0, s.dep.kpd))
        spent = s.ledger.hits.get((key, day2 // DAY), 0)
        for _ in range(max(0, LIMIT - 2 - spent)):
            s.ask([key])
        others = rng.choice(np.setdiff1d(np.arange(s.dep.kpd), [key]), 64 - times, replace=False)
        request = np.concatenate([others, np.full(times, key)])
        rng.shuffle(request)
        got = s.ask(request)
        mine = [got[i] for i in np.flatnonzero(request == key)]
        want = [(OK, 1, LIMIT), (OK, 0, LIMIT)] + [(OVER_LIMIT, 0, LIMIT)] * (times - 2)
        assert mine == want[:times]
    for row in keys[30:]:
        s.ask(row)

    assert s.mismatches == 0 and s.differ_from_memory == 0, s.first
    assert s.compared >= keys.size
    book = s.ledger.check_log()
    assert (book["over_admitted"], book["miscounted"]) == (0, 0), book["example"]
    # The first day's counters are gone from no chip: they sit in other
    # slots (a window's number is in the cache key), and the second
    # day's were counted from zero.
    first_day = {k for (k, w) in s.ledger.hits if w == T0 // DAY}
    second_day = {k for (k, w) in s.ledger.hits if w == day2 // DAY}
    assert len(first_day & second_day) > 100
    assert s.stat("dedup_groups") == s.engine.stat_groups_launched <= s.stat("padded_lanes")


# -- (b) one chip's slots, and an even spread ---------------------------------


def test_a_request_on_one_chip_and_one_spread_evenly_are_exact_and_counted(served):
    """The slot table hands out slots densely in arrival order, so the
    i-th new key sits on chip i % 8.  64 keys of one chip and 8 keys of
    each chip are the same launch to the host: 64 groups in a bucket of
    64, whichever chips own them."""
    s = served(SEEDS[0], keys=1024)
    load = np.arange(1024)
    for lo in range(0, 1024, 256):
        assert s.ask(load[lo : lo + 256]) == [(OK, LIMIT - 1, LIMIT)] * 256
    before = {n: s.stat(n) for n in ("padded_lanes", "dedup_groups")}
    assert before == {"padded_lanes": 1024, "dedup_groups": 1024}

    chip = 5
    one_chip = load[chip::CHIPS][:64]
    assert s.ask(one_chip) == [(OK, LIMIT - 2, LIMIT)] * 64
    after = {n: s.stat(n) for n in before}
    assert after["dedup_groups"] - before["dedup_groups"] == 64
    assert after["padded_lanes"] - before["padded_lanes"] == 64

    even = np.concatenate([load[c::CHIPS][64:72] for c in range(CHIPS)])
    np.random.default_rng(7).shuffle(even)
    assert s.ask(even) == [(OK, LIMIT - 2, LIMIT)] * 64
    last = {n: s.stat(n) for n in before}
    assert last["dedup_groups"] - after["dedup_groups"] == 64
    assert last["padded_lanes"] - after["padded_lanes"] == 64
    # Two shapes in all: the load's and the two requests' one.
    assert s.engine._proven_shapes == {(256, "uint8"), (64, "uint8")}

    assert s.mismatches == 0 and s.differ_from_memory == 0, s.first
    # The hits are on the chips that own the slots: one_chip's 64 on
    # chip 5 alone, even's 8 on each.
    counts = np.asarray(s.engine._counts)
    assert counts.shape == (CHIPS, (1 << 14) // CHIPS)
    assert (counts == 2).sum(axis=1).tolist() == [8 + 64 * (i == chip) for i in range(CHIPS)]
    # A mesh bank exports a one-chip bank's counters and no other.
    assert not [n for n in s.store.snapshot() if n.startswith(BANK) and ("rout" in n or ".chip" in n)]
    # Where it runs is said where an operator looks: the start line and
    # /debug/faults.
    assert s.runner._where_it_runs().endswith(f" mesh_devices=lane0of1:{CHIPS}")
    bank, *algorithm_banks = s.runner.cache.fault_domain.summary()["banks"]
    assert bank["mesh_devices"] == CHIPS and len(bank["state_devices"]) == CHIPS
    assert not any("mesh_devices" in b for b in algorithm_banks)  # one-chip engines


# -- (c) the shares add up -----------------------------------------------------


def seeded_batches(seed: int, num_slots: int, steps: int = 6):
    """Batches with duplicate slots, fresh lanes (a tenth, some on
    counters already counted up: the reset shows), every readback
    dtype (uint8 / uint16 / raw uint32), a saturating total — once
    clamped on the host (a group's total past u32), once on the device
    (a counter near u32 max hit again) — and an out-of-table lane."""
    rng = np.random.default_rng([seed, 49])
    out = []
    for step in range(steps):
        n = int(rng.integers(40, 300))
        slots = rng.integers(0, num_slots, n).astype(np.int32)
        slots[rng.integers(0, n, n // 4)] = slots[rng.integers(0, n, n // 4)]  # duplicates
        limits = rng.integers(1, (200, 60_000, 3_000_000_000)[step % 3], n).astype(np.uint32)
        hits = rng.integers(1, 4, n).astype(np.uint32)
        fresh = rng.random(n) < 0.1
        if step >= steps - 2:
            hits[:3] = 0xFFFFFFF0  # totals past u32: clamped, saturating
            slots[:3] = 7 % num_slots
            hits[3] = 0xFFFFFF00  # its slot's counter + this wraps u32 on the device: pinned at max
            slots[3] = 11 % num_slots
            fresh &= (slots != slots[0]) & (slots != slots[3])  # they stay: the second batch adds onto u32 max
            slots[-1] = num_slots + 5  # out of the table
        out.append(HostBatch(slots=slots, hits=hits, limits=limits, fresh=fresh, shadow=rng.random(n) < 0.1))
    return out


@pytest.mark.parametrize("seed", SEEDS[:2])
@pytest.mark.parametrize("chips", [1, 2, 4, 8])
def test_a_mesh_of_1_2_4_8_equals_the_one_chip_engine_bit_for_bit(chips, seed):
    num_slots = 1 << 12
    one = CounterEngine(num_slots=num_slots)
    mesh = ShardedCounterEngine(make_mesh(chips), num_slots=num_slots)
    assert mesh.model.num_banks == chips and len(mesh.placement()["state_devices"]) == chips
    groups = 0
    for batch in seeded_batches(seed, num_slots):
        want, got = one.step(batch), mesh.step(batch)
        for field in want.__dataclass_fields__:
            np.testing.assert_array_equal(getattr(got, field), getattr(want, field), err_msg=field)
        groups += len(np.unique(batch.slots))
    np.testing.assert_array_equal(mesh.export_counts(), one.export_counts())
    assert one.export_counts().sum() > 0
    assert one.export_counts()[[7, 11]].tolist() == [0xFFFFFFFF] * 2  # saturated, not wrapped
    # What a snapshot, a checkpoint or a handoff takes (export_state)
    # is in the slot table's order on a mesh too, and goes back as it came.
    np.testing.assert_array_equal(mesh.export_state()["counts"], one.export_state()["counts"])
    mesh.import_state(mesh.export_state())
    np.testing.assert_array_equal(mesh.export_counts(), one.export_counts())
    # What the host shipped and the watchdog proved: the one-chip
    # engine's, launch for launch — every readback dtype among them.
    assert mesh.stat_groups_launched == one.stat_groups_launched == groups
    assert mesh.stat_padded_lanes == one.stat_padded_lanes
    assert mesh._proven_shapes == one._proven_shapes
    assert {dt for _, dt in mesh._proven_shapes} == {"uint8", "uint16", ""}
    # Warm-up probes out of the table, all of them: chip 0 answers
    # each `after = hits`, and no chip's bank moves.
    probes = HostBatch(
        slots=np.arange(num_slots, num_slots + 40, dtype=np.int32), hits=np.arange(40, dtype=np.uint32),
        limits=np.full(40, 100, np.uint32), fresh=np.zeros(40, bool), shadow=np.zeros(40, bool),
    )
    want, got = one.step(probes), mesh.step(probes)
    np.testing.assert_array_equal(got.afters, np.arange(40))
    np.testing.assert_array_equal(got.codes, want.codes)
    np.testing.assert_array_equal(mesh.export_counts(), one.export_counts())


def test_a_mesh_bank_exports_a_one_chip_banks_counters_and_no_routing_counter():
    def names(engine):
        manager = Manager()
        cache = TpuRateLimitCache(engine, time_source=PinnedTimeSource(T0))
        try:
            cache.register_stats(manager.store)
            return {n for n in manager.store.snapshot() if n.startswith(BANK)}
        finally:
            cache.close()

    one = names(CounterEngine(num_slots=256, buckets=(8,)))
    mesh = names(ShardedCounterEngine(make_mesh(4), num_slots=256, buckets=(8,)))
    assert BANK + "padded_lanes" in one and mesh == one
    assert not [n for n in mesh if "rout" in n or ".chip" in n]


@pytest.mark.parametrize("skew", ["one_chip", "even", "random"])
def test_a_mesh_launch_has_the_one_chip_engines_shape_whatever_the_skew(skew):
    """`shape` — the compiled program a launch runs, the watchdog's
    key — and `padded_lanes` depend on how many groups a launch holds
    and not on which chips own them: the warm-up proves every shape
    serving can meet, on a mesh as on one chip."""
    from ratelimit_tpu.backends.tpu_cache import warmup_engine

    chips, num_slots, buckets = 4, 1 << 10, (8, 32, 64)
    one = CounterEngine(num_slots=num_slots, buckets=buckets)
    mesh = ShardedCounterEngine(make_mesh(chips), num_slots=num_slots, buckets=buckets)
    warmup_engine(one)
    warmup_engine(mesh)
    proven = {(b, dt) for b in buckets for dt in ("uint8", "uint16", "")}
    assert mesh._proven_shapes == one._proven_shapes == proven
    assert mesh.stat_padded_lanes == one.stat_padded_lanes == 3 * sum(buckets)
    rng = np.random.default_rng(50)
    for n in (3, 8, 9, 30, 33, 64):
        slots = {
            "one_chip": np.arange(n) * chips + 1,  # all on chip 1
            "even": np.arange(n),  # chip i % 4
            "random": rng.choice(num_slots, n, replace=False),
        }[skew].astype(np.int32)
        batch = HostBatch(
            slots=slots, hits=np.ones(n, np.uint32), limits=np.full(n, 5, np.uint32),
            fresh=np.zeros(n, bool), shadow=np.zeros(n, bool),
        )
        tokens = [engine.step_submit(batch) for engine in (one, mesh)]
        (_, _, _, _, lift_one, shape_one), = tokens[0][3]
        (afters, _, _, _, lift_mesh, shape_mesh), = tokens[1][3]
        assert shape_mesh == shape_one == (one._bucket(n), "uint8") and shape_mesh in proven
        assert lift_one is None and lift_mesh is None and afters.is_fully_replicated
        want, got = one.step_complete(tokens[0]), mesh.step_complete(tokens[1])
        np.testing.assert_array_equal(got.afters, want.afters)
        assert mesh.stat_padded_lanes == one.stat_padded_lanes
    assert mesh._proven_shapes == proven
    np.testing.assert_array_equal(mesh.export_counts(), one.export_counts())


# -- (d) fault -> quarantine -> restart through the default factory ----------

YAML = """
domain: d
descriptors:
  - key: to_number
    rate_limit:
      unit: day
      requests_per_unit: 20
"""


def test_a_faulted_mesh_bank_restarts_through_the_default_factory_with_its_counters():
    """snapshot -> injected hang -> host fallback keeps counting ->
    the supervisor rebuilds the engine with default_engine_factory (the
    same mesh) -> the mirror's counters go back onto the chips -> each
    key admits EXACTLY its limit across the whole episode."""
    inj = DeviceFaultInjector()
    mesh = make_mesh(4)
    first = ShardedCounterEngine(mesh, num_slots=1 << 10, buckets=(8, 32))
    manager = Manager()
    cache = TpuRateLimitCache(
        inj.wrap_engine("lane0", first), time_source=PinnedTimeSource(T0), batch_window_us=100,
        kernel_deadline_s=0.2, device_failure_mode="host", fault_interval_s=0,
        fault_restart_backoff_s=0.05, fault_snapshot_interval_s=1000.0, fault_probe_timeout_s=30.0,
    )
    cache.register_stats(manager.store)
    rules = load_config([ConfigFile("config.c", YAML)], manager)
    fd = cache.fault_domain
    assert fd.engine_factory is default_engine_factory
    keys = [f"n{i}" for i in range(12)]
    admitted = dict.fromkeys(keys, 0)

    def hit_all():
        for k in keys:
            d = Descriptor.of(("to_number", k))
            rule = rules.get_limit("d", d)
            admitted[k] += cache.do_limit(RateLimitRequest("d", [d], 1), [rule])[0].code is Code.OK

    try:
        for _ in range(5):
            hit_all()
        assert fd.snapshot_now() == 1
        inj.hang("lane0")
        for _ in range(5):
            hit_all()
        assert fd.is_quarantined(0) and fd.stat_fallback_decisions > 0
        inj.heal()
        deadline = time.monotonic() + 60
        while fd.is_quarantined(0) and time.monotonic() < deadline:
            time.sleep(0.06)
            fd.tick()
        assert not fd.is_quarantined(0) and fd.stat_restarts == 1
        rebuilt = cache.engine
        assert isinstance(rebuilt, ShardedCounterEngine) and rebuilt is not first
        assert rebuilt.model.mesh is mesh and rebuilt.model.num_slots == first.model.num_slots
        assert rebuilt.buckets == first.buckets == (8, 32)
        # The 10 hits a key of before and during the fault are on the chips again.
        # (The supervisor's three probe keys hold 1 each.)
        counts = rebuilt.export_counts()
        assert sorted(counts[counts > 0].tolist()) == [1] * fd.probe_count + [10] * len(keys)
        for _ in range(15):
            hit_all()
        assert admitted == dict.fromkeys(keys, 20)
        cache.flush()
        counts = rebuilt.export_counts()
        assert sorted(counts[counts > 1].tolist()) == [25] * len(keys)
        # The bank's counters follow the restart: they read the new engine.
        snap = manager.store.snapshot()
        assert snap[BANK + "dedup_groups"] == rebuilt.stat_groups_launched > 0
        assert snap[BANK + "padded_lanes"] == rebuilt.stat_padded_lanes >= snap[BANK + "dedup_groups"]
        (bank,) = fd.summary()["banks"]
        assert (bank["state"], bank["restarts"], bank["mesh_devices"]) == ("closed", 1, 4)
    finally:
        inj.heal()
        cache.close()


# -- (e) the launch protocol ---------------------------------------------------


def test_the_readback_copy_is_asked_for_inside_the_device_call_bracket(monkeypatch):
    """One round trip a launch, as every other engine (PR 26) and by
    the same code (CounterEngine._device_submit, inherited whole): the
    packed numpy goes to the jitted step as it is, and
    copy_to_host_async() is called on its result before the bracket
    closes — the completer then finds the copy on its way.  The result
    is whole on every chip, so one chip's copy is the answer."""
    assert ShardedCounterEngine._device_submit is CounterEngine._device_submit
    assert ShardedCounterEngine.warmup_probe_slots is CounterEngine.warmup_probe_slots
    engine = ShardedCounterEngine(make_mesh(4), num_slots=1 << 10, buckets=(8, 32))
    events = []
    real_call, real_step = engine._device_call, engine.model.step_counters_unique_packed

    class Result:
        def __init__(self, array):
            self.array = array

        def copy_to_host_async(self):
            events.append("copy_to_host_async")
            self.array.copy_to_host_async()

        def is_ready(self):
            return self.array.is_ready()

        def __array__(self, *args, **kwargs):
            return np.asarray(self.array)

    def step(counts, dt, packed):
        assert type(packed) is np.ndarray and packed.shape == (4, 32) and packed.dtype == np.int32
        assert packed[0].tolist() == list(range(20)) + list(range(1 << 10, (1 << 10) + 12))  # global ids, as on one chip
        events.append("step")
        counts, afters = real_step(counts, dt, packed)
        assert afters.shape == (32,) and afters.is_fully_replicated and len(afters.sharding.device_set) == 4
        return counts, Result(afters)

    @contextlib.contextmanager
    def bracket(watch, shape, *leg):
        events.append(("open", shape, *leg))
        with real_call(watch, shape, *leg):
            yield
        events.append(("close", shape, *leg))

    monkeypatch.setattr(engine, "_device_call", bracket)
    monkeypatch.setattr(engine.model, "step_counters_unique_packed", step)
    batch = HostBatch(
        slots=np.arange(20, dtype=np.int32), hits=np.ones(20, np.uint32), limits=np.full(20, 5, np.uint32),
        fresh=np.zeros(20, bool), shadow=np.zeros(20, bool),
    )
    decisions = engine.step(batch)
    assert decisions.afters.tolist() == [1] * 20
    shape = (32, "uint8")  # 20 slots: the bucket of 32, as on one chip
    assert events == [
        ("open", shape), "step", "copy_to_host_async", ("close", shape),
        ("open", shape, span_names.COMPLETE_READBACK), ("close", shape, span_names.COMPLETE_READBACK),
    ]
    assert engine.stat_padded_lanes == 32 and engine.stat_groups_launched == 20
    assert np.asarray(engine._counts)[:, :5].tolist() == [[1] * 5] * 4  # 5 slots on each chip's bank


def test_the_mesh_program_has_the_one_chip_programs_name_in_a_device_trace():
    """A device plane names a program `jit_<function>`: the mesh step's
    is jit_step_counters_unique_packed on every chip, the one-chip
    engine's own name, which kernel_step_us.paced's pattern
    `step_counters` finds."""
    engine = ShardedCounterEngine(make_mesh(4), num_slots=1 << 10, buckets=(8,))
    engine.step(
        HostBatch(
            slots=np.arange(4, dtype=np.int32), hits=np.ones(4, np.uint32), limits=np.full(4, 5, np.uint32),
            fresh=np.zeros(4, bool), shadow=np.zeros(4, bool),
        )
    )
    (fn,) = engine.model._unique_packed_fns.values()
    packed = jax.ShapeDtypeStruct((4, 8), np.int32)
    counts = jax.ShapeDtypeStruct(engine._counts.shape, engine._counts.dtype)
    text = fn.lower(counts, packed).as_text()
    assert "jit_step_counters_unique_packed" in text and "jit_body" not in text and "routed" not in text
    one = CounterEngine(num_slots=1 << 10, buckets=(8,)).model
    assert "jit_step_counters_unique_packed" in type(one).step_counters_unique_packed.lower(
        one, jax.ShapeDtypeStruct((1 << 10,), np.uint32), "uint8", packed
    ).as_text()
    spec = load_json("layer_metrics", "kernel_step_us.paced")["reader"]
    trace = {"modules": [["jit_step_counters_unique_packed", 84e-6, 4]], "device_planes": 4}
    assert layers.read(spec, {"trace": trace}) == pytest.approx(21.0)


@pytest.fixture(scope="module")
def v5e_2x2():
    """A four-chip TPU v5e host, described and not attached: the TPU's
    compiler is installed here (on-chip-measurement guide, section 2).
    The persistent compile cache is off meanwhile: an entry written
    for a chip that is not there cannot be read back."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no compiler here, or its library is taken
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("width, dtype", [(8, "uint8"), (32, "uint8"), (32, "uint16"), (4096, "")])
def test_the_mesh_step_compiles_for_four_real_chips_with_exactly_one_all_reduce(v5e_2x2, width, dtype):
    """The cell's program at its real size — 2^20 slots striped over a
    2x2 of v5e, 2^18 a chip, the launch's whole bucket on every chip —
    as the TPU's compiler takes it: one program a chip under a
    `step_counters` name, the counters donated in place, and ONE thing
    that crosses chips: the all-reduce that makes the answer whole."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from ratelimit_tpu.parallel import ShardedFixedWindowModel

    mesh = Mesh(np.array(v5e_2x2.devices), ("banks",))
    model = ShardedFixedWindowModel(1 << 20, mesh)
    assert (model.num_banks, model.slots_per_bank) == (4, 1 << 18)
    with pytest.raises(Exception):  # builds the jitted step; there is nothing to run it on
        model.step_counters_unique_packed(None, dtype, None)
    counts = jax.ShapeDtypeStruct((4, 1 << 18), np.uint32, sharding=NamedSharding(mesh, P("banks", None)))
    packed = jax.ShapeDtypeStruct((4, width), np.int32, sharding=NamedSharding(mesh, P()))
    compiled = model._unique_packed_fns[dtype].lower(counts, packed).compile()
    text = compiled.as_text()
    assert text.startswith("HloModule jit_step_counters_unique_packed")
    assert "input_output_alias" in text.splitlines()[0]
    collectives = re.findall(
        r" = \S+ ((?:all-reduce|all-gather|all-to-all|collective-permute|reduce-scatter|collective-broadcast)[a-z-]*)\(",
        text,
    )
    assert collectives == ["all-reduce"], collectives
    (afters,) = [o for o in compiled.output_shardings if o.is_fully_replicated]
    assert afters.spec == P()


def _read_spans(trace_dir) -> dict:
    """{span name: [(line, start_ns, end_ns)]} of the rl.* events."""
    try:
        from jaxlib._profile_data import ProfileData
    except ImportError:
        from jax.profiler import ProfileData
    (path,) = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    out = {}
    for plane in ProfileData.from_file(path).planes:
        for i, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith("rl.") and not e.name.startswith("rl.clock."):
                    out.setdefault(e.name, []).append(
                        ((plane.name, i), int(e.start_ns), int(e.start_ns) + int(e.duration_ns))
                    )
    return out


def test_a_capture_holds_one_pack_and_no_route_or_unroute_span(tmp_path):
    """A mesh bank's launch in a real capture is a one-chip bank's:
    rl.launch ⊃ assign, pack, device_call on the collector; readback,
    then decide, on the completer — and no span speaks of routing."""
    from ratelimit_tpu.backends.dispatcher import BatchDispatcher, Lane, WorkItem

    assert not [n for n in span_names.SPAN_NAMES if "route" in n]
    assert not hasattr(span_names, "LAUNCH_ROUTE") and not hasattr(span_names, "COMPLETE_UNROUTE")
    engine = ShardedCounterEngine(make_mesh(4), num_slots=1 << 10, buckets=(8,))
    d = BatchDispatcher(engine, batch_window_us=100)
    got = []

    def launch(prefix):
        item = WorkItem(
            now=T0, lanes=[Lane(f"{prefix}{i}", T0 + DAY, LIMIT, False, 1) for i in range(6)],
            apply=lambda decisions: got.append(np.asarray(decisions.codes).tolist()), defer_apply=True,
        )
        d.submit(item)
        item.wait(60)

    launch("warm")  # the shape's compilation is over before the capture: spans open at its end are lost
    capture = threading.Thread(target=SPANS.capture, args=(str(tmp_path), 0.6))
    capture.start()
    try:
        deadline = time.monotonic() + 10
        while not SPANS.capturing and time.monotonic() < deadline:
            time.sleep(0.005)
        assert SPANS.capturing
        launch("k")
        assert got == [[OK] * 6] * 2
    finally:
        capture.join(60)
        d.stop()
    spans = _read_spans(str(tmp_path))
    assert not [n for n in spans if "route" in n]
    (pack,), (assign,) = spans["rl.launch.pack"], spans["rl.launch.assign"]
    (call,), (launch,) = spans["rl.launch.device_call"], spans["rl.launch"]
    assert assign[0] == pack[0] == call[0] == launch[0]
    assert launch[1] <= assign[1] and assign[2] <= pack[1] and call[2] <= launch[2]
    assert pack[2] <= call[1]  # packing is over before the device call opens
    (readback,), (decide,) = spans["rl.complete.readback"], spans["rl.complete.decide"]
    assert readback[0] == decide[0] != launch[0]
    assert call[1] <= readback[2] <= decide[1]
    # Nothing stands between the readback and the decide on the completer.
    between = [
        n for n, events in spans.items() for line, start, end in events
        if line == readback[0] and readback[2] <= start and end <= decide[1]
    ]
    assert between == []


# -- (f) the manifest, the cell's rehearsal, the new metrics -----------------


def test_the_configuration_is_its_twin_but_for_the_backend_and_the_prefix():
    config, twin = load_json("configs", CONFIG), load_json("configs", TWIN_CONFIG)
    for key in ("families", "load_per_request", "load_connections", "replay", "reduced"):
        assert config[key] == twin[key], key
    assert config["families"] == [
        {"name": "to_number", "key": "to_number", "unit": "day", "limit": LIMIT, "keys": 500000, "load": True}
    ]
    assert config["server_env"] == {"TPU_WARMUP": "1", "BACKEND_TYPE": "tpu-sharded"}
    assert twin["server_env"] == {"TPU_WARMUP": "1"}
    assert config["domains"] == {"count": 1, "rehearse_count": 1, "prefix": "s"}
    for part in ("envoyproxy/ratelimit README", "REDIS_TYPE=cluster", "driver_impl.go:108-126", "to_number per DAY"):
        assert part in config["source"]
    assert len(config["source"]) <= 200 and config["source"] != twin["source"]
    for part in ("exact admission", "request order", "device mesh", "one replica", "no fallback"):
        assert part in config["guarantee"], part
    assert {"readme", "nodes", "table", "keys_limit_request_size", "server", "load"} <= set(config["assumed"])


NEW_METRICS = {
    "route_us.paced": ("us", "lower"),
    "unroute_us.paced": ("us", "lower"),
    "routed_balance_share.paced": ("%", "higher"),
}


def test_the_cell_is_in_the_manifest_beside_its_twin():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert entry == {
        "name": CONFIG, "source": load_json("configs", CONFIG)["source"],
        "file": f"chipbench/configs/{CONFIG}.json", "reduced": [], "why": entry["why"],
    }
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    twin = next(w for w in bench["workloads"] if w["name"] == TWIN_CELL)
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    # The two cells differ in the configuration (its BACKEND_TYPE) and the chips alone.
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, MIX, 4)
    assert (twin["config"], twin["traffic"], twin["chips"]) == (TWIN_CONFIG, MIX, 1)
    for part in ("90 requests/s", "64 descriptors/request", "4 chips", "only across chips", TWIN_CELL):
        assert part in cell["why"], part
    # One four-chip cell of six: within "at most half, and one always may".
    assert [w["name"] for w in bench["workloads"] if w["chips"] == 4] == [CELL]
    for e in bench["configs"] + bench["workloads"] + bench["per_layer"]:
        for key in ("why", "source", "layer"):
            text = e.get(key, "x")
            assert 1 <= len(text) <= 200 and text.isprintable(), (e["name"], key, len(text))
    p50 = next(m for m in bench["end_to_end"] if m["name"] == "p50_ms")
    assert p50["workloads"].count(CELL) == 1 and "workloads" not in bench["end_to_end"][1]
    names = [m["name"] for m in bench["per_layer"]]
    first = names.index(next(iter(NEW_METRICS)))
    mine = bench["per_layer"][first : first + len(NEW_METRICS)]
    assert [m["name"] for m in mine] == list(NEW_METRICS)
    for m in mine:
        assert set(m) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert (m["unit"], m["better"]) == NEW_METRICS[m["name"]]
        assert (m["source"], m["layer"], m["moves"]) == ("program_counter", "engine (host)", "p50_ms")
        assert m["workloads"][0] == CELL and TWIN_CELL not in m["workloads"]
        assert os.path.exists(os.path.join(ROOT, "chipbench", "layer_metrics", m["name"] + ".json"))
    # Every accepted metric its twin reports, but the roofline share,
    # whose byte count reckons one unrouted launch (PERF.md section 7).
    for m in bench["per_layer"][:first]:
        listed = CELL in m["workloads"]
        assert listed == (TWIN_CELL in m["workloads"] and m["name"] != "serve_step_paced_roofline"), m["name"]
        if listed:
            assert m["workloads"].index(CELL) > m["workloads"].index(TWIN_CELL) and m["workloads"].count(CELL) == 1
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024


@pytest.mark.parametrize("control, correct", [(None, True), ("server", False), ("reference", False)])
def test_the_cells_rehearsal_is_correct_and_its_controls_are_not(control, correct):
    """`python3 -m chipbench.run --workload sharded-recipients.paced
    --rehearse`: the whole harness on the CPU at 1,953 keys, the server
    child on the virtual mesh (XLA_FLAGS rides the environment)."""
    args = ["--workload", CELL, "--seed", str(2**31 + 49), "--seconds", "4", "--trace", "0", "--rehearse"]
    if control:
        args += ["--control", control]
    out = subprocess.run(
        [sys.executable, "-m", "chipbench.run", *args], cwd=ROOT, capture_output=True, text=True, timeout=600,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    assert "backend=tpu-sharded platform=cpu" in out.stderr and f"mesh_devices=lane0of1:{CHIPS}" in out.stderr
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


def _obs(pr49: bool) -> dict:
    """The two /stats.json fetches of a traced run: from PR 49's
    program on a mesh bank (the parent side of this PR's check: its
    routing counters beside the bank's older ones), or from a program
    that keeps none — this tree, PR 49's parent, an unsharded bank."""
    def stats(n):
        flat = {BANK + "dedup_groups": 670 * n, BANK + "padded_lanes": 1280 * n}
        if pr49:
            flat.update({
                BANK + "routed_launches": 10 * n, BANK + "route_ns": 400_000 * n,
                BANK + "unroute_ns": 150_000 * n, BANK + "routed_busiest_lanes": 880 * n,
            })
        return {"stats": flat, "histograms": {}}

    return {"stats_a": stats(1), "stats_b": stats(3)}


@pytest.fixture(scope="module")
def this_trees_mesh_bank():
    """What a mesh bank of this tree really exports: two fetches, ten
    launches of 67 groups between them."""
    manager = Manager()
    cache = TpuRateLimitCache(
        ShardedCounterEngine(make_mesh(4), num_slots=1 << 10, buckets=(8, 128)), time_source=PinnedTimeSource(T0)
    )
    try:
        cache.register_stats(manager.store)
        fetches = [{"stats": dict(manager.store.snapshot()), "histograms": {}}]
        batch = HostBatch(
            slots=np.arange(67, dtype=np.int32), hits=np.ones(67, np.uint32), limits=np.full(67, 5, np.uint32),
            fresh=np.zeros(67, bool), shadow=np.zeros(67, bool),
        )
        for _ in range(10):
            cache.engine.step(batch)
        fetches.append({"stats": dict(manager.store.snapshot()), "histograms": {}})
    finally:
        cache.close()
    return {"stats_a": fetches[0], "stats_b": fetches[1]}


@pytest.mark.parametrize(
    "name, on_pr49",
    [("route_us.paced", 40.0), ("unroute_us.paced", 15.0), ("routed_balance_share.paced", 100 * 670 / 880)],
)
def test_pr49s_routing_metric_reads_nothing_on_this_tree_and_raises_nothing(name, on_pr49, this_trees_mesh_bank):
    """The three readers stay in the benchmark (a `benchmark` PR takes
    them out: PERF.md section 7): they read the parent's counters in
    this PR's check and find nothing to read here."""
    spec = load_json("layer_metrics", name)
    assert set(spec) == {"what", "reader"} and spec["reader"]["kind"] == "ratio"
    assert layers.read(spec["reader"], _obs(pr49=True)) == pytest.approx(on_pr49)
    assert layers.read(spec["reader"], _obs(pr49=False)) is None
    assert layers.read(spec["reader"], this_trees_mesh_bank) is None
    assert layers.read(spec["reader"], {}) is None  # nothing gathered: nothing read, nothing raised
    # lane_fill_share.paced still reads, on both: groups over the lanes shipped.
    fill = load_json("layer_metrics", "lane_fill_share.paced")["reader"]
    assert layers.read(fill, _obs(pr49=True)) == pytest.approx(100 * 670 / 1280)
    assert layers.read(fill, this_trees_mesh_bank) == pytest.approx(100 * 67 / 128)
