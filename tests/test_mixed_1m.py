"""`mixed-1m` (chipbench/configs/mixed-1m.json) at a small size on the
CPU: the configuration's own families — DAY / HOUR / MINUTE / SECOND
key-only rules and a shadow rule — on a slot table loaded ≥ 95% full,
seeded hot-set traffic through the served backend (TpuRateLimitCache
and its normal dispatcher), answer for answer against the benchmark's
plain reference (chipbench/reference.py) across a SECOND and a MINUTE
boundary.  And: every entry of BENCHMARK.json finds its files."""

import json
import os

import numpy as np
import pytest

from chipbench import traffic
from chipbench.deploy import Deployment, load_json
from chipbench.reference import Ledger
from ratelimit_tpu.api import Descriptor, RateLimitRequest
from ratelimit_tpu.backends.engine import CounterEngine
from ratelimit_tpu.backends.tpu_cache import TpuRateLimitCache
from ratelimit_tpu.config.loader import ConfigFile, load_config
from ratelimit_tpu.stats.manager import Manager
from ratelimit_tpu.utils.time import PinnedTimeSource

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# The configuration's families at 1/64 of its keys: 15,600 loaded keys
# fill 95.2% of 2^14 slots, and with every MINUTE, SECOND and shadow
# key live as well 16,368 fit — so a single eviction is a fault.
NUM_SLOTS = 1 << 14
SMALL_KEYS = {
    "acct_day": 7800, "acct_hour": 7800, "acct_minute": 384,
    "acct_second": 256, "trial": 128,
}
MINUTE = (1_790_000_000 // 60 + 1) * 60  # a minute boundary (unix s)
PER_SECOND = 250  # requests stamped with each second


def small_config() -> dict:
    config = load_json("configs", "mixed-1m")
    for fam in config["families"]:
        fam["keys"] = SMALL_KEYS[fam["name"]]
    return config


@pytest.mark.parametrize("seed", [2147483659, 11, 20260928, 3000000019])
def test_mixed_1m_matches_the_reference_across_window_boundaries(seed):
    dep = Deployment(small_config(), seed)
    mix = load_json("traffic", "hot1pct4-poisson")
    cfg = load_config([ConfigFile("config.m", dep.yaml(0))], Manager())
    domain = dep.domain_name(0)
    clock = PinnedTimeSource(MINUTE - 3)
    engine = CounterEngine(num_slots=NUM_SLOTS)
    cache = TpuRateLimitCache(engine, time_source=clock, batch_window_us=100)
    ledger = Ledger(dep)
    compared = mismatches = 0
    first = None

    def ask(keys):
        nonlocal compared, mismatches, first
        descriptors = [Descriptor.of(*dep.entries(int(k))) for k in keys]
        limits = [cfg.get_limit(domain, d) for d in descriptors]
        statuses = cache.do_limit(RateLimitRequest(domain, descriptors, 1), limits)
        got = [
            (int(s.code), s.limit_remaining, s.current_limit.requests_per_unit)
            for s in statuses
        ]
        c, m, why = ledger.expect(np.asarray(keys), clock.now, clock.now, got)
        compared, mismatches, first = compared + c, mismatches + m, first or why

    try:
        load = dep.load_keys()
        for lo in range(0, len(load), 1024):
            ask(load[lo : lo + 1024])
        assert len(engine.slot_table) / NUM_SLOTS >= 0.95
        n = 6 * PER_SECOND
        _, keys = traffic.plan(mix, dep, seed, n)
        for i in range(n):
            clock.now = MINUTE - 3 + i // PER_SECOND  # ... M-1 | M, M+1 ...
            ask(keys[i])
    finally:
        cache.close()

    assert mismatches == 0, first
    assert compared == len(load) + n * keys.shape[1]
    book = ledger.check_log()
    assert (book["over_admitted"], book["miscounted"]) == (0, 0), book["example"]
    assert engine.slot_table.evictions == 0
    # The boundaries were crossed by keys that count: some key of each
    # short-window family was hit on both sides of its boundary.
    windows = {}
    for gid, w in ledger.hits:
        windows.setdefault(gid, set()).add(w)
    fam_of = dep.family_of(np.array(sorted(windows)))
    crossed = {
        dep.families[f].name
        for gid, f in zip(sorted(windows), fam_of.tolist())
        if len(windows[gid]) > 1
    }
    assert {"acct_second", "acct_minute"} <= crossed
    hit = {dep.families[f].name for f in fam_of.tolist()}
    assert hit == set(SMALL_KEYS)


@pytest.mark.parametrize("group", ["configs", "workloads", "per_layer"])
def test_every_benchmark_entry_finds_its_files(group):
    """What chipbench/run.py looks up by the names in BENCHMARK.json:
    a configuration's file, a cell's configuration and traffic mix, a
    per-layer metric's reader file and the cells it lists."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    configs = {c["name"] for c in bench["configs"]}
    cells = {w["name"] for w in bench["workloads"]}
    assert bench[group]
    for entry in bench[group]:
        if group == "configs":
            with open(os.path.join(ROOT, entry["file"])) as f:
                config = json.load(f)
            assert entry["file"] == f"chipbench/configs/{entry['name']}.json"
            assert config["name"] == entry["name"]
            assert config["reduced"] == entry["reduced"]
        elif group == "workloads":
            assert entry["config"] in configs
            load_json("configs", entry["config"])
            assert load_json("traffic", entry["traffic"])["loop"] in ("open", "closed")
        else:
            assert "kind" in load_json("layer_metrics", entry["name"])["reader"]
            assert set(entry["workloads"]) <= cells


def _obs(change: bool) -> dict:
    """Observations as run.py gathers them in a traced run, from the
    parent (PR 26: no grab span, no timeout counter) or the change."""
    def faults(n):
        total = {"rl.bg.gc": 40.0 * n, "rl.bg.snapshot": 100.0 * n}
        doc = {"snapshots": 3 * n, "background": {"total_ms": total}}
        if change:
            total["rl.bg.snapshot.grab"] = 90.0 * n
            doc["snapshot_timeouts"] = 0
        return doc

    def stats(n):
        bank = "ratelimit.tpu.bank0."
        return {"stats": {bank + "live_keys": 1000 * n, bank + "num_slots": 4000, bank + "evictions": 0}}

    launches = [{"device_submit_us": 100.0 * i, "readback_us": 50.0 * i} for i in range(1, 102)]
    return {
        "stats_a": stats(1), "stats_b": stats(3), "faults_a": faults(1), "faults_b": faults(3),
        "launches": launches,
    }


@pytest.mark.parametrize(
    "name, on_parent, on_change",
    [
        ("slot_fill_share.paced", 75.0, 75.0),
        ("slot_evictions.paced", 0, 0),
        ("gc_pause_ms.paced", 80.0, 80.0),
        ("snapshot_hold_ms.paced", None, 180.0),
        ("snapshot_timeouts.paced", None, 0),
        ("device_submit_p99_us.paced", 10000.0, 10000.0),
        ("readback_p99_us.paced", 5000.0, 5000.0),
    ],
)
def test_new_metric_reads_the_change_and_is_silent_where_the_parent_lacks_it(name, on_parent, on_change):
    """The driver runs this PR's benchmark files over the parent too: a
    metric whose span or counter the parent lacks reports nothing there
    and does not raise."""
    from chipbench import layers

    spec = load_json("layer_metrics", name)
    assert set(spec) == {"what", "reader"}
    got_parent = layers.read(spec["reader"], _obs(change=False))
    got_change = layers.read(spec["reader"], _obs(change=True))
    assert got_parent == (None if on_parent is None else pytest.approx(on_parent))
    assert got_change == pytest.approx(on_change)
