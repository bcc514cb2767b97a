"""`uniform-10k-persecond` (chipbench/configs/uniform-10k-persecond.json)
at a fraction of its keys on the CPU: the configuration's one per-SECOND
key-only rule, seeded `uniform4-poisson` traffic through the served
backend (TpuRateLimitCache and its normal dispatcher) under a pinned
clock, answer for answer against the benchmark's plain reference
(chipbench/reference.py), on both slot tables —

  (a) across consecutive second boundaries with the limit crossed in
      most seconds;
  (b) on a table smaller than the key-windows the run opens, with the
      expiry jitter at its default (300 s) and the clock carried past
      301 s: the slot GC frees leases, freed slots go to other keys,
      and their `fresh` lane must reset the count — one stale count is
      a mismatch;
  (c) the slot-churn counters (dedup groups, slot GC, arena) move as
      the run's own arithmetic says;
  (d) BENCHMARK.json's new entries find their files, and the new
      metrics read the change and are silent where the parent lacks
      the counters."""

import json
import os
import random

import numpy as np
import pytest

from chipbench import layers, traffic
from chipbench.deploy import Deployment, load_json
from chipbench.reference import OVER_LIMIT, Ledger
from ratelimit_tpu.api import Descriptor, RateLimitRequest
from ratelimit_tpu.backends.engine import CounterEngine
from ratelimit_tpu.backends.tpu_cache import TpuRateLimitCache
from ratelimit_tpu.config.loader import ConfigFile, load_config
from ratelimit_tpu.settings import Settings
from ratelimit_tpu.stats.manager import Manager
from ratelimit_tpu.utils.time import PinnedTimeSource

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG, MIX, CELL = "uniform-10k-persecond", "uniform4-poisson", "uniform-10k-persecond.paced"
SEEDS = [2147483659, 11, 20260929, 3000000019]
TABLES = [pytest.param(True, id="native"), pytest.param(False, id="python")]
T0 = 1_790_000_000
JITTER = Settings().expiration_jitter_max_seconds  # the default: 300
BANK = "ratelimit.tpu.bank0."


class Served:
    """The configuration's family at `keys` keys behind the served
    backend, and the reference beside it."""

    def __init__(self, seed: int, keys: int, num_slots: int, native: bool, jitter: int = 0):
        config = load_json("configs", CONFIG)
        (family,) = config["families"]
        assert (family["unit"], family["limit"]) == ("second", 2)
        family["keys"] = keys
        self.dep = Deployment(config, seed)
        self.mix = load_json("traffic", MIX)
        self.cfg = load_config([ConfigFile("config.u", self.dep.yaml(0))], Manager())
        self.domain = self.dep.domain_name(0)
        self.clock = PinnedTimeSource(T0)
        self.engine = CounterEngine(num_slots=num_slots, native_table=native)
        self.cache = TpuRateLimitCache(
            self.engine, time_source=self.clock, batch_window_us=100,
            expiration_jitter_max_seconds=jitter, jitter_rand=random.Random(seed),
        )
        self.dispatcher = self.cache._dispatchers[id(self.engine)]
        self.store = Manager().store
        self.cache.register_stats(self.store)
        self.ledger = Ledger(self.dep)
        self.compared = self.mismatches = self.over_limit = self.groups = 0
        self.first = None

    def ask(self, keys) -> None:
        descriptors = [Descriptor.of(*self.dep.entries(int(k))) for k in keys]
        limits = [self.cfg.get_limit(self.domain, d) for d in descriptors]
        statuses = self.cache.do_limit(RateLimitRequest(self.domain, descriptors, 1), limits)
        got = [
            (int(s.code), s.limit_remaining, s.current_limit.requests_per_unit)
            for s in statuses
        ]
        now = self.clock.now
        c, m, why = self.ledger.expect(np.asarray(keys), now, now, got)
        self.compared, self.mismatches, self.first = self.compared + c, self.mismatches + m, self.first or why
        self.over_limit += sum(code == OVER_LIMIT for code, _, _ in got)
        self.groups += len(set(int(k) for k in keys))  # one launch a request: its dedup groups

    def stat(self, name: str) -> int:
        return self.store.snapshot()[BANK + name]


@pytest.mark.parametrize("native", TABLES)
@pytest.mark.parametrize("seed", SEEDS)
def test_matches_the_reference_across_second_boundaries(seed, native):
    """(a) 8 consecutive seconds, 5 hits a key-second against a limit
    of 2: every boundary opens every key's window anew."""
    s = Served(seed, keys=100, num_slots=1 << 12, native=native)
    seconds, per_second = 8, 125
    try:
        load = s.dep.load_keys()
        s.ask(load)  # the set-up's load: every key once
        _, keys = traffic.plan(s.mix, s.dep, seed, seconds * per_second)
        crossed = []
        for sec in range(seconds):
            s.clock.now = T0 + 1 + sec
            before = s.over_limit
            for i in range(sec * per_second, (sec + 1) * per_second):
                s.ask(keys[i])
            crossed.append(s.over_limit > before)
        s.dispatcher.flush()
    finally:
        s.cache.close()

    assert s.mismatches == 0, s.first
    assert s.compared == len(load) + keys.size
    book = s.ledger.check_log()
    assert (book["over_admitted"], book["miscounted"]) == (0, 0), book["example"]
    assert book["exact_windows"] == book["windows"] == len(s.ledger.hits)
    assert sum(crossed) == seconds  # the limit was crossed in every second
    # Every key was hit on both sides of at least 6 boundaries, and
    # every first hit of a key-second was a rollover.
    windows = {}
    for gid, w in s.ledger.hits:
        windows.setdefault(gid, set()).add(w)
    assert min(len(ws) for ws in windows.values()) >= 7
    assert s.engine.stat_window_rollovers == len(s.ledger.hits)
    assert s.engine.stat_groups_launched == s.groups
    assert s.engine.slot_table.evictions == 0


@pytest.mark.parametrize("native", TABLES)
@pytest.mark.parametrize("seed", SEEDS)
def test_slots_freed_by_gc_are_reused_with_fresh_counts(seed, native):
    """(b) + (c): 340 pinned seconds at the default jitter on 2,048
    slots.  ~8 of 10 keys open a window each second and lease its slot
    1..301 s, so ~1,300 leases stand at a time, ~2,800 are opened in
    all, and every slot handed out after the first 2,048 inserts is one
    the GC freed."""
    num_slots, seconds, per_second = 1 << 11, 340, 5
    s = Served(seed, keys=10, num_slots=num_slots, native=native, jitter=JITTER)
    assert JITTER == 300
    # The collector's slot GC on every collect cycle, not every 5 s of
    # the test's real time: the clock here is pinned, the tick is not.
    s.dispatcher.gc_interval_s = 0.0
    s.dispatcher._next_gc_monotonic = 0.0
    try:
        _, keys = traffic.plan(s.mix, s.dep, seed, seconds * per_second)
        for i in range(len(keys)):
            s.clock.now = T0 + i // per_second
            s.ask(keys[i])
        s.dispatcher.flush()  # the collector's last cycle, its GC included, is done
        opened = len(s.ledger.hits)  # key-windows: all known, the clock is pinned
        live = len(s.engine.slot_table)
        stats = {
            name: s.stat(name)
            for name in (
                "window_rollovers", "dedup_groups", "slot_gc.runs", "slot_gc.freed",
                "slot_gc.total_us", "arena.compactions", "arena.bytes", "live_keys",
            )
        }
    finally:
        s.cache.close()

    assert s.mismatches == 0, s.first
    assert s.compared == keys.size
    book = s.ledger.check_log()
    assert (book["over_admitted"], book["miscounted"]) == (0, 0), book["example"]
    assert s.over_limit > seconds  # the limit is crossed all run long
    # The table is smaller than what the run opened, and never full:
    # no lease was evicted, none was freed by assign's own GC, so all
    # that left went through the collector's.
    assert opened > num_slots and s.engine.slot_table.evictions == 0
    assert stats["window_rollovers"] == opened
    assert stats["dedup_groups"] == s.groups
    assert stats["slot_gc.freed"] == opened - live > 0
    assert stats["live_keys"] == live < num_slots
    assert stats["slot_gc.runs"] >= len(keys)  # a launch a request, a GC a cycle
    assert stats["slot_gc.total_us"] > 0
    # Every lease still held ends after the clock, none beyond 301 s.
    now = T0 + seconds - 1
    expiries = s.engine.slot_table.export_packed().expiries
    assert now < expiries.min() and expiries.max() <= now + 1 + JITTER
    if native:
        # > 716 inserts pass the 1,024-entry map's load trigger.
        assert stats["arena.compactions"] >= 1
        assert 0 < stats["arena.bytes"] < 64 * opened  # ~45 B a key
    else:
        assert (stats["arena.compactions"], stats["arena.bytes"]) == (0, 0)


def test_the_configuration_states_source_guarantee_and_defaults():
    config = load_json("configs", CONFIG)
    assert "BASELINE.json configs[1]" in config["source"]
    assert config["reduced"] == [] and config["server_env"] == {"TPU_WARMUP": "1"}
    assert {"limit", "descriptors_per_request", "server", "load"} <= set(config["assumed"])
    assert "exact admission" in config["guarantee"]
    with open(os.path.join(ROOT, "BASELINE.json")) as f:
        assert json.load(f)["configs"][1] in config["source"]
    mix = load_json("traffic", MIX)
    assert (mix["loop"], mix["connections"], mix["descriptors_per_request"]) == ("open", 4, 4)
    assert mix["key_pick"] == mix["domain_pick"] == {"dist": "uniform"}
    assert mix["rate_rps"] % 10 == 0
    assert (mix["warm_s"], mix["drain_s"], mix["rpc_timeout_s"]) == (3.0, 5.0, 20.0)


NEW_METRICS = ["rollover_share.paced", "slot_gc_us.paced", "slot_gc_freed.paced", "arena_compactions.paced"]


def test_the_cell_is_in_the_manifest_and_reports_every_paced_metric():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    (entry,) = [c for c in bench["configs"] if c["name"] == CONFIG]
    assert entry["reduced"] == [] and entry["file"] == f"chipbench/configs/{CONFIG}.json"
    (cell,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, MIX, 1)
    assert f"{load_json('traffic', MIX)['rate_rps']} requests/s" in cell["why"]
    # (PR 36 appended `bulk-recipients` after it.)
    assert bench["workloads"][2] is cell and bench["configs"][2] is entry
    p50 = next(m for m in bench["end_to_end"] if m["name"] == "p50_ms")
    assert p50["workloads"][2] == CELL
    # Every metric of the served path; the start-up metrics (they move
    # `setup_s`, PR 35) list only the cell whose rule load they read.
    # (A later cell's own metrics, which list none of the cells there were then, are that cell's test's.)
    then = {w["name"] for w in bench["workloads"][:3]}
    paced = [m for m in bench["per_layer"] if m["moves"] == "p50_ms" and then & set(m["workloads"])]
    for m in paced:
        assert CELL in m["workloads"], m["name"]
    first = [m["name"] for m in paced].index(NEW_METRICS[0])
    mine = paced[first : first + len(NEW_METRICS)]  # PR 36's six and PR 41's six come after them
    assert [m["name"] for m in mine] == NEW_METRICS and first == len(paced) - len(NEW_METRICS) - 6 - 6
    for m in mine:
        assert (m["source"], m["layer"]) == ("program_counter", "engine (host)")


def _obs(change: bool) -> dict:
    """The two /stats.json fetches of a traced run, from the parent
    (no dedup_groups, slot_gc.* or arena.* counters) or the change."""
    def stats(n):
        doc = {BANK + "window_rollovers": 950 * n, BANK + "live_keys": 1000 * n}
        if change:
            doc.update({
                BANK + "dedup_groups": 1000 * n, BANK + "slot_gc.runs": 2 * n,
                BANK + "slot_gc.freed": 300 * n, BANK + "slot_gc.total_us": 700 * n,
                BANK + "arena.compactions": n, BANK + "arena.bytes": 40000 * n,
            })
        return {"stats": doc}

    return {"stats_a": stats(1), "stats_b": stats(3)}


@pytest.mark.parametrize(
    "name, on_change",
    [
        ("rollover_share.paced", 95.0),
        ("slot_gc_us.paced", 1400),
        ("slot_gc_freed.paced", 600),
        ("arena_compactions.paced", 2),
    ],
)
def test_new_metric_reads_the_change_and_is_silent_on_the_parent(name, on_change):
    spec = load_json("layer_metrics", name)
    assert set(spec) == {"what", "reader"}
    assert layers.read(spec["reader"], _obs(change=False)) is None
    assert layers.read(spec["reader"], _obs(change=True)) == pytest.approx(on_change)
