"""`bulk-recipients` (chipbench/configs/bulk-recipients.json) at 200 of
its keys on the CPU: the configuration's one per-DAY key-only rule,
64-descriptor requests as `hot10pct64-poisson` draws them, sent as the
load generator sends them — serialized bytes over a gRPC connection —
through the served path (handler -> service -> resolution -> dispatcher
-> CounterEngine) under a pinned clock, answer for answer (code,
`limit_remaining`, limit) against the benchmark's plain reference
(chipbench/reference.py, which imports nothing of the program), on
both slot tables —

  (a) 64 a request until most keys have crossed their cap, also with a
      resolution cache smaller than one request's keys;
  (b) the same key 2, 5 and 7 times inside one request with 2 remaining:
      the cap is crossed inside the request, answers in request order;
      answers decided from the pre-request count must mismatch;
  (c) 63 / 64 / 65 distinct keys (bucket 64 -> 128), two 64-descriptor
      requests coalesced into one launch, one request of 4,097 (past
      TPU_BATCH_LIMIT and the engine's largest bucket);
  (d) `ShouldRateLimit.descriptors` and `bank0.padded_lanes` add up to
      what was sent and to the buckets the launches ran at;
  (e) BENCHMARK.json's new entries find their files, the cell's
      rehearsal is `correct` and both controls are not, and the new
      metrics read the change and are silent where the parent has
      nothing for them."""

import json
import os
import subprocess
import sys
import threading
import time

import grpc
import numpy as np
import pytest

from chipbench import layers, traffic, wire
from chipbench.deploy import Deployment, load_json
from chipbench.reference import OK, OVER_LIMIT, Ledger
from ratelimit_tpu.backends.engine import DEFAULT_BUCKETS, CounterEngine
from ratelimit_tpu.backends.tpu_cache import TpuRateLimitCache
from ratelimit_tpu.observability import make_launch_recorder
from ratelimit_tpu.server.grpc_server import create_grpc_server
from ratelimit_tpu.server.health import HealthChecker
from ratelimit_tpu.service.ratelimit import RateLimitService
from ratelimit_tpu.settings import Settings
from ratelimit_tpu.stats.manager import Manager
from ratelimit_tpu.utils.time import PinnedTimeSource

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG, MIX, CELL = "bulk-recipients", "hot10pct64-poisson", "bulk-recipients.paced"
SEEDS = [2147483693, 13, 20260930, 3000000041]
TABLES = [pytest.param(True, id="native"), pytest.param(False, id="python")]
T0 = 1_790_000_000
LIMIT = 5
BANK = "ratelimit.tpu.bank0."
DESCRIPTORS = "ratelimit_server.ShouldRateLimit.descriptors"
BATCH_LIMIT = Settings().tpu_batch_limit  # the default: 4096


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


def bucket_of(groups: int) -> int:
    return next(b for b in DEFAULT_BUCKETS if groups <= b)


def padded(requests) -> int:
    """Lanes the launch of `requests` (coalesced) runs at: the engine
    cuts the launch's lanes into chunks of its largest bucket, dedups
    each, and pads each chunk's groups to a bucket."""
    lanes = np.concatenate([np.asarray(r) for r in requests])
    top = DEFAULT_BUCKETS[-1]
    return sum(bucket_of(len(set(lanes[i : i + top].tolist()))) for i in range(0, len(lanes), top))


class Served:
    """The configuration's family at `keys` keys behind the program's
    gRPC server, and the reference beside it."""

    def __init__(self, seed: int, native: bool, keys: int = 200, resolution_entries: int = 1 << 16):
        config = load_json("configs", CONFIG)
        (family,) = config["families"]
        assert (family["unit"], family["limit"]) == ("day", LIMIT)
        family["keys"] = keys
        self.dep = Deployment(config, seed)
        self.mix = load_json("traffic", MIX)
        self.clock = PinnedTimeSource(T0)
        self.engine = CounterEngine(num_slots=1 << 14, native_table=native)
        assert self.engine.buckets == DEFAULT_BUCKETS
        self.cache = TpuRateLimitCache(
            self.engine, time_source=self.clock, batch_window_us=200, batch_limit=BATCH_LIMIT,
            resolution_cache_entries=resolution_entries,
        )
        self.dispatcher = self.cache._dispatchers[id(self.engine)]
        self.launches = make_launch_recorder(4096)
        self.cache.attach_launch_recorder(self.launches)
        manager = Manager()
        self.store = manager.store
        self.cache.register_stats(self.store)
        service = RateLimitService(
            Runtime({"config.b": self.dep.yaml(0)}), self.cache, manager, clock=self.clock
        )
        self.server = create_grpc_server(
            service, HealthChecker(), self.store, host="127.0.0.1", port=0, max_workers=4
        )
        self.server.start()
        self.channel = grpc.insecure_channel(f"127.0.0.1:{self.server.bound_port}")
        self.call = self.channel.unary_unary(
            wire.METHOD, response_deserializer=wire.rls_pb2.RateLimitResponse.FromString
        )
        self.ledger = Ledger(self.dep)
        self.compared = self.mismatches = self.sent = 0
        self.first = None

    def send(self, keys) -> list:
        """One request, as chipbench/run.py's Caller sends and reads it."""
        self.sent += len(keys)
        resp = self.call(traffic.make_request(self.dep, 0, keys), timeout=60)
        return [(s.code, s.limit_remaining, s.current_limit.requests_per_unit) for s in resp.statuses]

    def judge(self, keys, got) -> None:
        assert len(got) == len(keys)
        c, m, why = self.ledger.expect(np.asarray(keys), T0, T0, got)
        self.compared, self.mismatches, self.first = self.compared + c, self.mismatches + m, self.first or why

    def ask(self, keys) -> list:
        got = self.send(keys)
        self.judge(keys, got)
        return got

    def stat(self, name: str) -> int:
        return self.store.snapshot()[name]

    def records(self):
        self.dispatcher.flush()
        return self.launches.snapshot()

    def close(self) -> None:
        self.channel.close()
        self.server.stop(None)
        self.cache.close()


@pytest.fixture
def served(request):
    made = []

    def make(*args, **kwargs):
        made.append(Served(*args, **kwargs))
        return made[-1]

    yield make
    for s in made:
        s.close()


@pytest.mark.parametrize("resolution_entries", [1 << 16, 48], ids=["cache65536", "cache48"])
@pytest.mark.parametrize("native", TABLES)
@pytest.mark.parametrize("seed", SEEDS)
def test_64_descriptor_requests_match_the_reference(served, seed, native, resolution_entries):
    """(a) + (d): 40 requests of 64 over 200 keys against a cap of 5 —
    12.8 hits a key, a hot key 35 — so most keys cross; with 48 cache
    entries no request's 64 lookups fit and second-chance eviction
    runs inside every one."""
    s = served(seed, native, resolution_entries=resolution_entries)
    _, keys = traffic.plan(s.mix, s.dep, seed, 40)
    assert keys.shape == (40, 64)
    over = 0
    for row in keys:
        over += sum(code == OVER_LIMIT for code, _, _ in s.ask(row))
    records = s.records()

    assert s.mismatches == 0, s.first
    assert s.compared == keys.size
    book = s.ledger.check_log()
    assert (book["over_admitted"], book["miscounted"]) == (0, 0), book["example"]
    hits = np.bincount(keys.reshape(-1), minlength=s.dep.kpd)
    crossed = int((hits > LIMIT).sum())
    assert crossed > s.dep.kpd // 2  # most keys crossed their cap
    assert over == int(np.maximum(hits - LIMIT, 0).sum()) > 0
    # One launch a request (the caller waits for each answer); every
    # launch's dedup groups pad to bucket 64, and duplicates by chance
    # leave some lanes of it empty.
    groups = [len(set(row.tolist())) for row in keys]
    assert [int(r["lanes"]) for r in records] == [64] * len(keys)
    assert [int(r["dedup_groups"]) for r in records] == groups
    assert s.stat(BANK + "dedup_groups") == s.engine.stat_groups_launched == sum(groups)
    assert s.stat(BANK + "padded_lanes") == s.engine.stat_padded_lanes == 64 * len(keys)
    assert s.stat(DESCRIPTORS) == s.sent == keys.size
    cache = {
        n: s.stat("ratelimit.tpu.resolution_cache." + n) for n in ("hits", "misses", "evictions", "entries")
    }
    assert cache["hits"] + cache["misses"] == keys.size
    if resolution_entries == 48:  # the table stays at its capacity: a miss there replaces an entry
        assert cache["entries"] == 48
        assert keys.size // 4 < cache["evictions"] <= cache["misses"] - 48
    else:  # (the first request's 64 probes are all misses: the table is not the config's yet)
        assert (cache["entries"], cache["evictions"]) == (len(np.unique(keys)), 0)
        assert cache["misses"] <= cache["entries"] + 64


def mutant_answers(ledger: Ledger, keys) -> list:
    """What a server would answer that decided every descriptor of a
    request from its key's count BEFORE the request: duplicates inside
    one request all see the same `after`."""
    out = []
    for k in keys:
        after = ledger.hits.get((int(k), T0 // 86400), 0) + 1
        out.append((OK if after <= LIMIT else OVER_LIMIT, max(0, LIMIT - after), LIMIT))
    return out


@pytest.mark.parametrize("times", [2, 5, 7])
@pytest.mark.parametrize("native", TABLES)
@pytest.mark.parametrize("seed", SEEDS[:2])
def test_a_cap_crossed_inside_one_request_is_answered_in_request_order(served, seed, native, times):
    """(b) A key with 2 remaining, `times` times among the 64
    descriptors of one request: the first two are admitted (1, then 0
    remaining), every later one is OVER_LIMIT, wherever they stand."""
    s = served(seed, native)
    rng = np.random.default_rng([seed, times])
    key = int(rng.integers(0, s.dep.kpd))
    for _ in range(LIMIT - 2):
        assert s.ask([key])[0][0] == OK
    others = rng.choice(np.setdiff1d(np.arange(s.dep.kpd), [key]), 64 - times, replace=False)
    request = np.concatenate([others, np.full(times, key)])
    rng.shuffle(request)
    wrong = Ledger(s.dep)
    wrong.hits, wrong.admitted = dict(s.ledger.hits), dict(s.ledger.admitted)
    _, mutant_mismatches, _ = wrong.expect(request, T0, T0, mutant_answers(s.ledger, request))

    got = s.ask(request)

    assert s.mismatches == 0, s.first
    mine = [got[i] for i in np.flatnonzero(request == key)]
    want = [(OK, 1, LIMIT), (OK, 0, LIMIT)] + [(OVER_LIMIT, 0, LIMIT)] * (times - 2)
    assert mine == want[:times]
    assert all(got[i] == (OK, LIMIT - 1, LIMIT) for i in np.flatnonzero(request != key))
    # The comparison has the power to see the fault: the same request
    # answered from the pre-request count differs at every duplicate
    # after the first.
    assert mutant_mismatches == times - 1
    record = s.records()[-1]
    assert (int(record["lanes"]), int(record["dedup_groups"])) == (64, 64 - times + 1)


@pytest.mark.parametrize("native", TABLES)
@pytest.mark.parametrize("n, bucket", [(63, 64), (64, 64), (65, 128)])
def test_the_bucket_follows_the_distinct_keys_of_a_request(served, native, n, bucket):
    """(c) 63 and 64 distinct keys run at 64 lanes, 65 at 128."""
    s = served(SEEDS[0], native)
    keys = np.random.default_rng(n).choice(s.dep.kpd, n, replace=False)
    got = s.ask(keys)
    again = s.ask(keys[::-1])
    records = s.records()

    assert s.mismatches == 0, s.first
    assert got == [(OK, LIMIT - 1, LIMIT)] * n and again == [(OK, LIMIT - 2, LIMIT)] * n
    assert [(int(r["lanes"]), int(r["dedup_groups"])) for r in records] == [(n, n)] * 2
    assert s.stat(BANK + "padded_lanes") == 2 * bucket == 2 * padded([keys])
    assert s.stat(DESCRIPTORS) == 2 * n


@pytest.mark.parametrize("native", TABLES)
@pytest.mark.parametrize("seed", SEEDS)
def test_two_64_descriptor_requests_coalesce_into_one_launch(served, seed, native):
    """(c) Two callers whose requests wait in the intake together ride
    one launch: 128 lanes, the keys they share deduplicated across the
    two requests and counted in arrival order."""
    s = served(seed, native)
    _, keys = traffic.plan(s.mix, s.dep, seed, 2)
    shared = set(keys[0].tolist()) & set(keys[1].tolist())
    assert shared  # the hot set: some numbers are on both lists
    s.ask([0])  # the shapes' first launch is over before the hold
    hold, held = threading.Event(), threading.Event()

    def on_collector():
        held.set()
        hold.wait(30)

    blocker = threading.Thread(target=s.dispatcher.run_on_thread, args=(on_collector,))
    blocker.start()
    assert held.wait(30)
    answers = [None, None]

    def caller(i):
        answers[i] = s.send(keys[i])

    callers = [threading.Thread(target=caller, args=(i,)) for i in range(2)]
    for i, t in enumerate(callers):
        t.start()
        deadline = time.monotonic() + 30
        while len(s.dispatcher._buf) < i + 1 and time.monotonic() < deadline:
            time.sleep(0.001)
        assert len(s.dispatcher._buf) == i + 1  # request i is queued, in order
    hold.set()
    for t in callers + [blocker]:
        t.join(30)
        assert not t.is_alive()
    for i in range(2):
        s.judge(keys[i], answers[i])
    record = s.records()[-1]

    assert s.mismatches == 0, s.first
    assert s.compared == 1 + keys.size
    groups = len(set(keys.reshape(-1).tolist()))
    assert (int(record["items"]), int(record["lanes"]), int(record["dedup_groups"])) == (2, 128, groups)
    assert groups < 128 and padded([keys[0], keys[1]]) == 128
    assert s.stat(BANK + "padded_lanes") == 8 + 128
    assert s.stat(DESCRIPTORS) == 1 + 128


@pytest.mark.parametrize("native", TABLES)
@pytest.mark.parametrize("keys", [200, 6000], ids=["200keys", "6000keys"])
def test_a_request_past_the_batch_limit_is_exact(served, native, keys):
    """(c) 4,097 descriptors in one request: past TPU_BATCH_LIMIT, and
    one more than the engine's largest bucket, so the launch runs as
    two device steps.  Over 200 keys every key stands ~20 times in it
    (its cap crossed inside the request, one key's duplicates on both
    sides of the cut); over 6,000 nearly all are distinct.  Then the
    same request again, against what the first left."""
    n = BATCH_LIMIT + 1
    assert n == DEFAULT_BUCKETS[-1] + 1 == 4097
    s = served(SEEDS[1], native, keys=keys)
    request = np.random.default_rng([keys, n]).integers(0, keys, n)
    first = s.ask(request)
    second = s.ask(request)
    records = s.records()

    assert s.mismatches == 0, s.first
    assert s.compared == 2 * n
    book = s.ledger.check_log()
    assert (book["over_admitted"], book["miscounted"]) == (0, 0), book["example"]
    hits = np.bincount(request, minlength=keys)
    assert sum(code == OK for code, _, _ in first) == int(np.minimum(hits, LIMIT).sum())
    assert sum(code == OK for code, _, _ in second) == int(np.clip(LIMIT - hits, 0, hits).sum())
    assert request[-1] in request[:-1] or keys == 6000  # a key on both sides of the cut
    assert [int(r["lanes"]) for r in records] == [n, n]
    assert s.stat(BANK + "padded_lanes") == 2 * padded([request])
    assert padded([request]) == bucket_of(len(set(request[:-1].tolist()))) + 8
    assert s.stat(DESCRIPTORS) == 2 * n


# -- the manifest, the cell's rehearsal, the new metrics ----------------------


def test_the_configuration_states_source_guarantee_and_defaults():
    config = load_json("configs", CONFIG)
    for part in ("envoyproxy/ratelimit README, messaging example", "to_number", "rls.proto", "BASELINE.json"):
        assert part in config["source"]
    assert len(config["source"]) <= 200
    assert config["reduced"] == [] and config["server_env"] == {"TPU_WARMUP": "1"}
    assert {"limit", "descriptors_per_request", "keys", "rules_left_out", "server", "load"} <= set(config["assumed"])
    assert "exact admission" in config["guarantee"] and "request order" in config["guarantee"]
    assert config["families"] == [
        {"name": "to_number", "key": "to_number", "unit": "day", "limit": LIMIT, "keys": 500000, "load": True}
    ]
    assert config["domains"] == {"count": 1, "rehearse_count": 1, "prefix": "b"}
    assert (config["load_per_request"], config["load_connections"]) == (4096, 1)
    with open(os.path.join(ROOT, "BASELINE.json")) as f:
        assert "batch=1..4096" in json.load(f)["metric"]
    mix = load_json("traffic", MIX)
    assert (mix["loop"], mix["connections"], mix["descriptors_per_request"]) == ("open", 4, 64)
    assert mix["domain_pick"] == {"dist": "uniform"}
    assert mix["key_pick"] == {"dist": "hotset", "hot_share": 0.5, "hot_fraction": 0.1}
    assert mix["rate_rps"] % 10 == 0
    # Half the measured knee of 190 (PERF.md section 4): 2,700 requests in
    # the 30 s window, where ISSUE 36 expected 3,000 or more of a knee of 200-400.
    assert mix["rate_rps"] == 90
    assert (mix["warm_s"], mix["drain_s"], mix["rpc_timeout_s"]) == (3.0, 5.0, 20.0)


NEW_METRICS = {
    "lanes_per_launch.paced": ("lanes", "higher", "program_span", "dispatcher"),
    "lane_fill_share.paced": ("%", "higher", "program_counter", "engine (host)"),
    "prepare_us_per_descriptor.paced": ("us", "lower", "program_counter", "service + resolution"),
    "apply_us_per_descriptor.paced": ("us", "lower", "program_counter", "service + resolution"),
    "decode_us.paced": ("us", "lower", "program_counter", "gRPC handler"),
    "serialize_us.paced": ("us", "lower", "program_counter", "gRPC handler"),
}


def test_the_cell_is_in_the_manifest_and_reports_every_paced_metric():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    # Anchored by name: a later PR's cell, configuration or metric of its own touches nothing here.
    config = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert config == {
        "name": CONFIG, "source": load_json("configs", CONFIG)["source"],
        "file": f"chipbench/configs/{CONFIG}.json", "reduced": [], "why": config["why"],
    }
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, MIX, 1)
    # Nobody may read its roofline share as a kernel result: the `why`
    # states the rate, the knee, the lanes a launch, the idle device.
    assert f"{load_json('traffic', MIX)['rate_rps']} requests/s" in cell["why"]
    for part in ("knee", "lanes a launch", "idle > 99%"):
        assert part in cell["why"], part
    # The driver refuses the whole file, before any run, over one `why`,
    # `source` or `layer` past 200 printable characters.
    for entry in bench["configs"] + bench["workloads"] + bench["per_layer"]:
        for key in ("why", "source", "layer"):
            text = entry.get(key, "x")
            assert 1 <= len(text) <= 200 and text.isprintable(), (entry["name"], key, len(text))
    cells = [w["name"] for w in bench["workloads"]]
    cells = cells[:cells.index(CELL) + 1]  # the cells there were when this one came
    p50 = next(m for m in bench["end_to_end"] if m["name"] == "p50_ms")
    assert p50["workloads"][:len(cells)] == cells and "workloads" not in bench["end_to_end"][1]
    paced = [m for m in bench["per_layer"] if m["moves"] == "p50_ms" and set(m["workloads"]) & set(cells)]
    for m in paced:
        assert [c for c in m["workloads"] if c in cells][-1] == CELL, m["name"]
    # PR 36's six, where it appended them (later PRs append after).
    first = [m["name"] for m in bench["per_layer"]].index(next(iter(NEW_METRICS)))
    mine = bench["per_layer"][first:first + len(NEW_METRICS)]
    assert [m["name"] for m in mine] == list(NEW_METRICS)
    for m in mine:
        assert (m["unit"], m["better"], m["source"], m["layer"]) == NEW_METRICS[m["name"]]
        assert m["workloads"][:len(cells)] == cells and m["moves"] == "p50_ms"
    for m in bench["per_layer"]:
        if m["moves"] == "setup_s":  # the rule load's two: PR 35's one cell
            assert m["workloads"] == cells[:1]


@pytest.mark.parametrize("control, correct", [(None, True), ("server", False), ("reference", False)])
def test_the_cells_rehearsal_is_correct_and_its_controls_are_not(control, correct):
    """`python3 -m chipbench.run --workload bulk-recipients.paced
    --rehearse`: the whole harness on the CPU at 1,953 keys — server,
    key load, four generator workers, window, replay, log comparison."""
    args = ["--workload", CELL, "--seed", str(2**31 + 36), "--seconds", "4", "--trace", "0", "--rehearse"]
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


H = "ratelimit_server.ShouldRateLimit."


def _obs(change: bool) -> dict:
    """The two /stats.json fetches and the launch records of a traced
    run, from the parent (no `descriptors`, no `padded_lanes`; the
    launch record's `lanes` and the phase histograms are older than
    this PR) or the change."""
    def stats(n):
        flat = {BANK + "dedup_groups": 630 * n}
        if change:
            flat.update({BANK + "padded_lanes": 700 * n, DESCRIPTORS: 640 * n})
        hist = {
            H + "prepare_ms": {"count": 10 * n, "total_ms": 8.0 * n},
            H + "apply_ms": {"count": 10 * n, "total_ms": 1.6 * n},
            H + "phase.decode_ms": {"count": 10 * n, "total_ms": 2.0 * n},
            H + "phase.serialize_ms": {"count": 10 * n, "total_ms": 0.5 * n},
        }
        return {"stats": flat, "histograms": hist}

    return {
        "stats_a": stats(1), "stats_b": stats(3),
        "launches": [{"lanes": 64, "items": 1}, {"lanes": 128, "items": 2}, {"lanes": 64, "items": 1}],
    }


@pytest.mark.parametrize(
    "name, on_change, on_parent",
    [
        ("lanes_per_launch.paced", 256 / 3, 256 / 3),
        ("lane_fill_share.paced", 90.0, None),
        ("prepare_us_per_descriptor.paced", 12.5, None),
        ("apply_us_per_descriptor.paced", 2.5, None),
        ("decode_us.paced", 200.0, 200.0),
        ("serialize_us.paced", 50.0, 50.0),
    ],
)
def test_new_metric_reads_the_change_and_raises_nothing_on_the_parent(name, on_change, on_parent):
    spec = load_json("layer_metrics", name)
    assert set(spec) == {"what", "reader"}
    assert layers.read(spec["reader"], _obs(change=True)) == pytest.approx(on_change)
    got = layers.read(spec["reader"], _obs(change=False))
    assert got is None if on_parent is None else got == pytest.approx(on_parent)
    assert layers.read(spec["reader"], {}) is None  # nothing gathered: nothing read, nothing raised
