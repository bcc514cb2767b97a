"""Profile one dispatcher iteration phase-by-phase (host serving cost).

Round-2 verdict weak #2: the 76M dec/s headline measures the device
kernel; the host path feeding it (lane assembly, slot assignment,
dedup, padding, transfer, decide, status assembly) was unprofiled and
plausibly the real ceiling.  This script times each phase of a
4096-lane dispatcher iteration on the CPU platform
so the serial host cost per batch is a measured number, not a guess.

Phases of the round-3 packed pipeline:
  RPC threads : LanePack build (parallel across handler threads)
  collector   : pack concat -> fused C++ assign+dedup -> packed
                (4, N) int32 single-transfer -> jit launch
  completer   : readback -> vectorized decide -> tolist -> per-item
                status assembly

Round-6 addition: the descriptor-resolution front half (rule lookup +
key generation + routing + lane packing) measured through the REAL
service/cache seams (service._construct_limits_to_check +
tpu_cache._prepare), warm, with the resolution cache on vs off — the
cost the one-dict-hit fast path (limiter/resolution.py) attacks.

Run:  JAX_PLATFORMS=cpu python benchmarks/profile_host_path.py
Writes benchmarks/results/host_path.json.

Quick mode (CI smoke, `make bench-host`):
      JAX_PLATFORMS=cpu python benchmarks/profile_host_path.py --quick
runs only the resolution section with few iterations, asserts the
resolution cache reports a nonzero hit rate after warmup and that the
fast path STAYS engaged (no misses during the measured phase), prints
one JSON line, and exits non-zero on violation.  Writes no artifact.

Hot-key sketch mode:
      JAX_PLATFORMS=cpu python benchmarks/profile_host_path.py --hotkeys
measures the per-request cost of the Space-Saving hot-key feed
(observability/hotkeys.py) against the acceptance budget — <= ~2us/
request with the sketch enabled, ~0 with HOTKEYS_TOP_K=0 — split into
the front-half bump (steady state and eviction-churn worst case) and
the post-decision outcome attribution.  Writes
benchmarks/results/hotkeys_overhead.json (cited by PERF_NOTES.md).

Flight recorder mode:
      JAX_PLATFORMS=cpu python benchmarks/profile_host_path.py --flight
measures the per-request cost of the decision flight recorder + SLO
rollup stamping (observability/{flight,slo}.py) against the acceptance
budget — <= ~1us/request steady-state with the ring enabled, ~0 with
FLIGHT_RECORDER_SIZE=0 — split into the backend note branch (the
_prepare_resolved leg) and the handler-side record+observe stamp, and
verifies decisions are identical with the recorder on vs off.  Writes
benchmarks/results/flight_overhead.json.

Event journal + correlation mode:
      JAX_PLATFORMS=cpu python benchmarks/profile_host_path.py --events
measures the fleet-observability additions against the acceptance
budget — <= ~0.5us/request with the journal attached and the corr-id
path enabled, ~0 disabled — split into the serving front half with the
journal attached (which must be FREE: events stamp lifecycle
transitions, never requests), the per-request corr-id leg of the gRPC
handler (mint/parse + ring note), and the per-transition emit cost,
and verifies decisions are identical with the plane on vs off.  Writes
benchmarks/results/events_overhead.json.

Launch recorder + time-series mode:
      JAX_PLATFORMS=cpu python benchmarks/profile_host_path.py --launches
measures the per-request cost of the launch flight recorder + tsdb
sampler (observability/{launches,timeseries}.py) against the
acceptance budget — <= 0.5us/request amortized with the recorder
enabled, ~0 with LAUNCH_RECORDER_SIZE=0 — split into the RPC-thread
submit stamp, the per-launch collector/completer bookkeeping
(amortized over a coalesce ratio MEASURED through a real dispatcher),
and the sampler tick, and verifies decisions are identical with the
recorder on vs off.  Writes benchmarks/results/launches_overhead.json.
"""

from __future__ import annotations

import json
import os
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np  # noqa: E402

from ratelimit_tpu.backends.dispatcher import (  # noqa: E402
    Lane,
    LanePack,
    WorkItem,
    complete_items,
    submit_items,
)
from ratelimit_tpu.backends.engine import CounterEngine  # noqa: E402

BATCH = 4096
REQUESTS = 1024  # 4 lanes per request
DUP_KEYS = 512  # keyspace smaller than batch -> duplicates, real dedup work
ITERS = 30


def make_items(engine, it_seed: int, apply=lambda d: None):
    """REQUESTS WorkItems x 4 lanes with a reused keyspace, packed on
    the 'RPC thread' (here: inline) the way tpu_cache._make_item
    does in serving."""
    rng = np.random.default_rng(it_seed)
    items = []
    now = 1_700_000_000
    key_ids = rng.integers(0, DUP_KEYS, BATCH)
    k = 0
    for _ in range(REQUESTS):
        lanes = [
            Lane(
                key=f"domain_key_value{key_ids[k + j]}_1700000000",
                expiry=now + 60,
                limit=1000,
                shadow=False,
                hits=1,
            )
            for j in range(4)
        ]
        k += 4
        it = WorkItem(now=now, lanes=lanes, apply=apply)
        it.get_pack()  # pre-pack, as the serving path does
        items.append(it)
    return items


def timed(fn, *args, reps=ITERS):
    best = []
    for _ in range(reps):
        t0 = time.perf_counter()
        out = fn(*args)
        best.append(time.perf_counter() - t0)
    arr = np.array(best[2:])  # drop warmups
    return float(np.median(arr)), out


def profile_resolution(results, quick: bool = False):
    """Serving front half (rule lookup + key gen + routing + packing),
    resolved vs uncached, through the real seams.  Returns (ok, info):
    ok is the quick-mode assertion verdict (cache engaged + fast path
    stays engaged)."""
    from ratelimit_tpu.api import Descriptor, RateLimitRequest  # noqa: E402
    from ratelimit_tpu.backends.tpu_cache import TpuRateLimitCache  # noqa: E402
    from ratelimit_tpu.service import RateLimitService  # noqa: E402
    from ratelimit_tpu.stats.manager import Manager  # noqa: E402
    from ratelimit_tpu.utils.time import PinnedTimeSource  # noqa: E402

    n_reqs = 128 if quick else REQUESTS
    reps = 6 if quick else ITERS
    yaml = (
        "domain: domain\n"
        "descriptors:\n"
        "  - key: key\n"
        "    rate_limit:\n"
        "      unit: hour\n"
        "      requests_per_unit: 1000\n"
    )

    class _Runtime:
        def __init__(self, files):
            self._files = files

        def snapshot(self):
            files = self._files

            class Snap:
                def keys(self):
                    return sorted(files)

                def get(self, key):
                    return files.get(key, "")

            return Snap()

        def add_update_callback(self, fn):
            pass

    import gc

    gc.collect()  # don't time other sections' garbage

    def build(resolution_entries):
        clock = PinnedTimeSource(1_700_000_000)
        # No device work happens in _prepare, so a small engine is fine.
        engine = CounterEngine(num_slots=1 << 16)
        cache = TpuRateLimitCache(
            engine, clock, resolution_cache_entries=resolution_entries
        )
        svc = RateLimitService(
            _Runtime({"config.bench": yaml}), cache, Manager(), clock=clock
        )
        return svc, cache

    rng = np.random.default_rng(7)
    key_ids = rng.integers(0, DUP_KEYS, n_reqs * 4)
    reqs = []
    for r in range(n_reqs):
        descs = [
            Descriptor.of(("key", f"value{key_ids[r * 4 + j]}"))
            for j in range(4)
        ]
        reqs.append(RateLimitRequest("domain", descs, 0))

    def front_fast(svc, cache):
        # The fused one-pass front half (service hot path: rule lookup
        # + keys + routing + packing in do_limit_resolved's _prepare_
        # resolved).  Recycle the WorkItem events the way _execute does
        # after its waits (steady-state serving keeps the pool warm;
        # the front half alone never reaches that code).
        pool = cache._event_pool
        config = svc.get_current_config()
        for req in reqs:
            items, *_ = cache._prepare_resolved(req, config)
            if len(pool) < 1024:
                for _bank, _eng, item in items:
                    pool.append(item.event)

    def front_uncached(svc, cache):
        pool = cache._event_pool
        for req in reqs:
            limits, _unl = svc._construct_limits_to_check(req)
            items, *_ = cache._prepare(req, limits)
            if len(pool) < 1024:
                for _bank, _eng, item in items:
                    pool.append(item.event)

    svc_fast, cache_fast = build(1 << 16)
    svc_slow, cache_slow = build(0)

    front_fast(svc_fast, cache_fast)  # warm: populate the cache
    front_uncached(svc_slow, cache_slow)
    misses_after_warmup = cache_fast.resolver.misses
    t_fast, _ = timed(front_fast, svc_fast, cache_fast, reps=reps)
    t_slow, _ = timed(front_uncached, svc_slow, cache_slow, reps=reps)
    res = cache_fast.resolver

    scale = REQUESTS / n_reqs  # report per-1024-request batch
    results["resolution_uncached_per_batch"] = t_slow * scale
    results["resolution_resolved_per_batch"] = t_fast * scale
    results["resolution_speedup"] = t_slow / t_fast if t_fast else 0.0
    results["resolution_cache_hits"] = res.hits
    results["resolution_cache_misses"] = res.misses

    hit_rate = res.hits / max(1, res.hits + res.misses)
    stayed_engaged = res.misses == misses_after_warmup
    ok = hit_rate > 0.5 and stayed_engaged
    info = {
        "requests": n_reqs,
        "uncached_us_per_req": t_slow / n_reqs * 1e6,
        "resolved_us_per_req": t_fast / n_reqs * 1e6,
        "speedup": results["resolution_speedup"],
        "hits": res.hits,
        "misses": res.misses,
        "hit_rate": hit_rate,
        "fast_path_stayed_engaged": stayed_engaged,
    }
    return ok, info


def profile_hotkeys():
    """Per-request cost of the hot-key sketch feed, measured through
    the real serving seams (same harness as profile_resolution).

    Three configurations share one request set (n_reqs x 4
    descriptors over DUP_KEYS distinct stems):

    - ``disabled``:     HOTKEYS_TOP_K=0 (the ~0-cost baseline);
    - ``steady``:       capacity >= keyspace — pure handle-bump path;
    - ``churn``:        capacity << keyspace — every request's stems
                        keep getting evicted, so the locked track()
                        registration path runs constantly (worst
                        case; production top-K traffic is steady).

    The outcome-attribution leg (_note_hotkey_outcomes, which runs
    after the device step) is timed separately on a completed
    request's real statuses.
    """
    from ratelimit_tpu.api import Descriptor, RateLimitRequest  # noqa: E402
    from ratelimit_tpu.backends.tpu_cache import TpuRateLimitCache  # noqa: E402
    from ratelimit_tpu.service import RateLimitService  # noqa: E402
    from ratelimit_tpu.stats.manager import Manager  # noqa: E402
    from ratelimit_tpu.utils.time import PinnedTimeSource  # noqa: E402

    n_reqs = 256
    reps = 12
    yaml = (
        "domain: domain\n"
        "descriptors:\n"
        "  - key: key\n"
        "    rate_limit:\n"
        "      unit: hour\n"
        "      requests_per_unit: 1000\n"
    )

    class _Runtime:
        def __init__(self, files):
            self._files = files

        def snapshot(self):
            files = self._files

            class Snap:
                def keys(self):
                    return sorted(files)

                def get(self, key):
                    return files.get(key, "")

            return Snap()

        def add_update_callback(self, fn):
            pass

    def build(top_k):
        clock = PinnedTimeSource(1_700_000_000)
        engine = CounterEngine(num_slots=1 << 16)
        cache = TpuRateLimitCache(engine, clock, hotkeys_top_k=top_k)
        svc = RateLimitService(
            _Runtime({"config.bench": yaml}), cache, Manager(), clock=clock
        )
        return svc, cache

    rng = np.random.default_rng(7)
    key_ids = rng.integers(0, DUP_KEYS, n_reqs * 4)
    reqs = []
    for r in range(n_reqs):
        descs = [
            Descriptor.of(("key", f"value{key_ids[r * 4 + j]}"))
            for j in range(4)
        ]
        reqs.append(RateLimitRequest("domain", descs, 0))

    def front(svc, cache):
        pool = cache._event_pool
        config = svc.get_current_config()
        for req in reqs:
            items, *_ = cache._prepare_resolved(req, config)
            if len(pool) < 1024:
                for _bank, _eng, item in items:
                    pool.append(item.event)

    import gc

    gc.collect()
    results = {"requests": n_reqs, "descriptors_per_request": 4}
    times = {}
    for name, top_k in (
        ("disabled", 0),
        ("steady", 2 * DUP_KEYS),
        ("churn", 32),
    ):
        svc, cache = build(top_k)
        front(svc, cache)  # warm caches (and the sketch handles)
        t, _ = timed(front, svc, cache, reps=reps)
        times[name] = t
        results[f"front_{name}_us_per_req"] = t / n_reqs * 1e6

    results["sketch_steady_overhead_us_per_req"] = (
        (times["steady"] - times["disabled"]) / n_reqs * 1e6
    )
    results["sketch_churn_overhead_us_per_req"] = (
        (times["churn"] - times["disabled"]) / n_reqs * 1e6
    )

    # Outcome attribution on real statuses (the post-decision leg).
    svc, cache = build(2 * DUP_KEYS)
    config = svc.get_current_config()
    req = reqs[0]
    (items, statuses, categories, _keys, limits, _unl, hits_addend, now, hot,
     _shadow) = cache._prepare_resolved(req, config)
    statuses = cache._execute(
        limits, items, statuses, categories, hits_addend, now,
        len(req.descriptors),
    )
    t_note, _ = timed(
        lambda: cache._note_hotkey_outcomes(hot, statuses, limits, 1),
        reps=200,
    )
    results["outcome_attribution_us_per_req"] = t_note * 1e6
    results["total_steady_us_per_req"] = (
        results["sketch_steady_overhead_us_per_req"] + t_note * 1e6
    )

    path = os.path.join(
        os.path.dirname(__file__), "results", "hotkeys_overhead.json"
    )
    with open(path, "w") as f:
        json.dump(results, f, indent=2)
    print(json.dumps(results, indent=2))
    print(f"wrote {path}")
    return results


def profile_flight():
    """Per-request cost of the flight recorder + SLO rollup stamping,
    measured through the real serving seams (same harness as
    profile_hotkeys), plus decision parity with the ring on vs off.

    Legs:

    - ``note``:   the backend's _prepare_resolved branch that deposits
                  (stem hash, bank) into the recorder's thread-local —
                  flight attached vs not;
    - ``stamp``:  the handler-side leg (FlightRecorder.record + the
                  per-domain SloEngine.observe), enabled vs the
                  disabled ``if recorder is None`` guard;
    - ``parity``: do_limit_resolved decisions compared field-by-field
                  between a flight-on and a flight-off cache over the
                  same request stream.
    """
    from ratelimit_tpu.api import Descriptor, RateLimitRequest  # noqa: E402
    from ratelimit_tpu.backends.tpu_cache import TpuRateLimitCache  # noqa: E402
    from ratelimit_tpu.observability import SloEngine, make_flight_recorder  # noqa: E402
    from ratelimit_tpu.service import RateLimitService  # noqa: E402
    from ratelimit_tpu.stats.manager import Manager  # noqa: E402
    from ratelimit_tpu.utils.time import PinnedTimeSource  # noqa: E402

    n_reqs = 256
    reps = 12
    yaml = (
        "domain: domain\n"
        "descriptors:\n"
        "  - key: key\n"
        "    rate_limit:\n"
        "      unit: hour\n"
        "      requests_per_unit: 1000\n"
    )

    class _Runtime:
        def __init__(self, files):
            self._files = files

        def snapshot(self):
            files = self._files

            class Snap:
                def keys(self):
                    return sorted(files)

                def get(self, key):
                    return files.get(key, "")

            return Snap()

        def add_update_callback(self, fn):
            pass

    def build(flight_size):
        clock = PinnedTimeSource(1_700_000_000)
        engine = CounterEngine(num_slots=1 << 16)
        cache = TpuRateLimitCache(engine, clock)
        cache.flight = make_flight_recorder(flight_size)
        svc = RateLimitService(
            _Runtime({"config.bench": yaml}), cache, Manager(), clock=clock
        )
        return svc, cache

    rng = np.random.default_rng(7)
    key_ids = rng.integers(0, DUP_KEYS, n_reqs * 4)
    reqs = []
    for r in range(n_reqs):
        descs = [
            Descriptor.of(("key", f"value{key_ids[r * 4 + j]}"))
            for j in range(4)
        ]
        reqs.append(RateLimitRequest("domain", descs, 0))

    def front(svc, cache):
        pool = cache._event_pool
        config = svc.get_current_config()
        for req in reqs:
            items, *_ = cache._prepare_resolved(req, config)
            if len(pool) < 1024:
                for _bank, _eng, item in items:
                    pool.append(item.event)

    import gc

    gc.collect()
    results = {"requests": n_reqs, "descriptors_per_request": 4}

    # Leg 1: the backend note branch (front half, flight on vs off).
    # The front half is ~10us/req, so an A-B diff of two medians
    # drowns a ~0.3us delta in run-to-run noise; interleave the two
    # configurations and take best-of instead (the stable floor of
    # each path on this machine).
    times = {"on": [], "off": []}
    built = {"on": build(1 << 12), "off": build(0)}
    for name, (svc, cache) in built.items():
        front(svc, cache)  # warm the resolution cache
    for _ in range(4 * reps):
        for name, (svc, cache) in built.items():
            t0 = time.perf_counter()
            front(svc, cache)
            times[name].append(time.perf_counter() - t0)
    t_on, t_off = min(times["on"]), min(times["off"])
    results["front_flight_off_us_per_req"] = t_off / n_reqs * 1e6
    results["front_flight_on_us_per_req"] = t_on / n_reqs * 1e6
    results["note_overhead_us_per_req"] = (t_on - t_off) / n_reqs * 1e6

    # Leg 2: the handler-side stamp (record + SLO observe) vs the
    # disabled None-guard path — the exact code shape of the gRPC
    # handler's post-serialize block.
    recorder = make_flight_recorder(1 << 12)
    slo = SloEngine(Manager())
    slo.set_domains(["domain"])

    # Note deposits are costed in leg 1 (they happen in the backend's
    # front half); here a fresh note per iteration would double-count,
    # so the loop records noteless — one thread-local reset short of
    # the fully-noted path.
    def stamp_enabled():
        for _req in reqs:
            recorder.record("domain", 1, 1, 0.73)
            slo.observe("domain", False, 0.73)

    none_recorder = None

    def stamp_disabled():
        for _req in reqs:
            if none_recorder is not None:
                none_recorder.record("domain", 1, 1, 0.73)

    stamp_enabled()
    t_on, _ = timed(stamp_enabled, reps=reps)
    t_off, _ = timed(stamp_disabled, reps=reps)
    results["stamp_enabled_us_per_req"] = t_on / n_reqs * 1e6
    results["stamp_disabled_us_per_req"] = t_off / n_reqs * 1e6
    results["stamp_overhead_us_per_req"] = (t_on - t_off) / n_reqs * 1e6
    results["total_overhead_us_per_req"] = (
        results["note_overhead_us_per_req"]
        + results["stamp_overhead_us_per_req"]
    )

    # Leg 3: decision parity — the recorder must never change a
    # decision.  Full do_limit_resolved over the same stream, every
    # status field compared.
    svc_on, cache_on = build(1 << 12)
    svc_off, cache_off = build(0)
    identical = True
    for req in reqs:
        st_on, lim_on, unl_on = cache_on.do_limit_resolved(
            req, svc_on.get_current_config()
        )
        st_off, lim_off, unl_off = cache_off.do_limit_resolved(
            req, svc_off.get_current_config()
        )
        a = [
            (s.code, s.limit_remaining, s.duration_until_reset)
            for s in st_on
        ]
        b = [
            (s.code, s.limit_remaining, s.duration_until_reset)
            for s in st_off
        ]
        if a != b or unl_on != unl_off:
            identical = False
            break
    results["decisions_identical_on_off"] = identical

    path = os.path.join(
        os.path.dirname(__file__), "results", "flight_overhead.json"
    )
    with open(path, "w") as f:
        json.dump(results, f, indent=2)
    print(json.dumps(results, indent=2))
    print(f"wrote {path}")
    if not identical:
        print("FAIL: decisions differ with recorder on vs off")
        sys.exit(1)
    return results


def profile_events():
    """Per-request cost of the fleet-observability plane
    (observability/events.py + the corr-id leg of flight.py), against
    the acceptance budget — <= ~0.5us/request with the journal attached
    and FLIGHT_CORR_ENABLED, ~0 with both off — plus decision parity.

    Legs:

    - ``front``:  the serving front half with the journal attached to
                  the cache vs not.  The journal has ZERO hot-path
                  branches (events stamp lifecycle transitions, never
                  requests), so this must measure ~0 — the leg exists
                  to keep that claim a number, not a comment;
    - ``corr``:   the per-request corr-id work the gRPC handler does
                  when FLIGHT_CORR_ENABLED — parse the inbound hex id
                  (or mint one proxy-side), stamp it into the flight
                  ring's thread-local note — vs the disabled guard;
    - ``emit``:   the per-TRANSITION emit cost (ring store + tally),
                  for scale: transitions are rare, so this never rides
                  a request;
    - ``parity``: do_limit_resolved decisions field-identical with the
                  plane on vs off.
    """
    from ratelimit_tpu.api import Descriptor, RateLimitRequest  # noqa: E402
    from ratelimit_tpu.backends.tpu_cache import TpuRateLimitCache  # noqa: E402
    from ratelimit_tpu.observability import (  # noqa: E402
        make_flight_recorder,
        mint_corr,
        parse_corr,
    )
    from ratelimit_tpu.observability.events import EventJournal  # noqa: E402
    from ratelimit_tpu.service import RateLimitService  # noqa: E402
    from ratelimit_tpu.stats.manager import Manager  # noqa: E402
    from ratelimit_tpu.utils.time import PinnedTimeSource  # noqa: E402

    n_reqs = 256
    reps = 12
    yaml = (
        "domain: domain\n"
        "descriptors:\n"
        "  - key: key\n"
        "    rate_limit:\n"
        "      unit: hour\n"
        "      requests_per_unit: 1000\n"
    )

    class _Runtime:
        def __init__(self, files):
            self._files = files

        def snapshot(self):
            files = self._files

            class Snap:
                def keys(self):
                    return sorted(files)

                def get(self, key):
                    return files.get(key, "")

            return Snap()

        def add_update_callback(self, fn):
            pass

    def build(with_events):
        clock = PinnedTimeSource(1_700_000_000)
        engine = CounterEngine(num_slots=1 << 16)
        cache = TpuRateLimitCache(engine, clock)
        if with_events:
            cache.events = EventJournal(size=1024)
        svc = RateLimitService(
            _Runtime({"config.bench": yaml}), cache, Manager(), clock=clock
        )
        return svc, cache

    rng = np.random.default_rng(7)
    key_ids = rng.integers(0, DUP_KEYS, n_reqs * 4)
    reqs = []
    for r in range(n_reqs):
        descs = [
            Descriptor.of(("key", f"value{key_ids[r * 4 + j]}"))
            for j in range(4)
        ]
        reqs.append(RateLimitRequest("domain", descs, 0))

    def front(svc, cache):
        pool = cache._event_pool
        config = svc.get_current_config()
        for req in reqs:
            items, *_ = cache._prepare_resolved(req, config)
            if len(pool) < 1024:
                for _bank, _eng, item in items:
                    pool.append(item.event)

    import gc

    gc.collect()
    results = {"requests": n_reqs, "descriptors_per_request": 4}

    # Leg 1: front half with the journal attached vs not — interleaved
    # best-of A/B (profile_flight's recipe) since the true delta is 0
    # (the journal is never read on the serving path).  Alternate the
    # A/B order each round so scheduler drift can't bias one side.
    built = {"on": build(True), "off": build(False)}
    for name, (svc, cache) in built.items():
        front(svc, cache)  # warm the resolution cache
    times = {"on": [], "off": []}
    for i in range(8 * reps):
        order = ("on", "off") if i % 2 == 0 else ("off", "on")
        for name in order:
            svc, cache = built[name]
            t0 = time.perf_counter()
            front(svc, cache)
            times[name].append(time.perf_counter() - t0)
    t_on, t_off = min(times["on"]), min(times["off"])
    results["front_journal_off_us_per_req"] = t_off / n_reqs * 1e6
    results["front_journal_on_us_per_req"] = t_on / n_reqs * 1e6
    results["journal_overhead_us_per_req"] = (t_on - t_off) / n_reqs * 1e6

    # Leg 2: the per-request corr-id leg, enabled vs the disabled
    # guard — the exact shape of the gRPC handler's intake block
    # (server/grpc_server.py): one inbound-header parse (replica) or
    # mint (proxy), one thread-local ring note.
    flight = make_flight_recorder(1 << 12)
    inbound = "deadbeefcafef00d"

    def corr_enabled():
        note = flight.note_corr
        for _req in reqs:
            corr = parse_corr(inbound)
            if corr == 0:
                corr = mint_corr()
            note(corr)

    corr_off = False

    def corr_disabled():
        sink = 0
        for _req in reqs:
            if corr_off:
                sink = mint_corr()
        return sink

    corr_enabled()
    t_on = min(timed(corr_enabled, reps=reps)[0] for _ in range(3))
    t_off = min(timed(corr_disabled, reps=reps)[0] for _ in range(3))
    results["corr_enabled_us_per_req"] = t_on / n_reqs * 1e6
    results["corr_disabled_us_per_req"] = t_off / n_reqs * 1e6
    results["corr_overhead_us_per_req"] = (t_on - t_off) / n_reqs * 1e6
    results["total_overhead_us_per_req"] = (
        results["journal_overhead_us_per_req"]
        + results["corr_overhead_us_per_req"]
    )
    results["budget_us_per_req"] = 0.5
    results["within_budget"] = results["total_overhead_us_per_req"] <= 0.5

    # Leg 3: per-transition emit cost, for scale (never per-request).
    journal = EventJournal(size=4096)
    n_emits = 4096

    def emits():
        emit = journal.emit
        for i in range(n_emits):
            emit("bank_quarantine", bank=0, kind="bench", role="lane")

    emits()
    t_emit, _ = timed(emits, reps=reps)
    results["emit_us_per_event"] = t_emit / n_emits * 1e6

    # Leg 4: decision parity with the plane attached.
    svc_on, cache_on = built["on"]
    svc_off, cache_off = built["off"]
    cache_on.flight = make_flight_recorder(1 << 12)
    identical = True
    for req in reqs:
        st_on, _l1, unl_on = cache_on.do_limit_resolved(
            req, svc_on.get_current_config()
        )
        st_off, _l2, unl_off = cache_off.do_limit_resolved(
            req, svc_off.get_current_config()
        )
        a = [
            (s.code, s.limit_remaining, s.duration_until_reset)
            for s in st_on
        ]
        b = [
            (s.code, s.limit_remaining, s.duration_until_reset)
            for s in st_off
        ]
        if a != b or unl_on != unl_off:
            identical = False
            break
    results["decisions_identical_on_off"] = identical

    path = os.path.join(
        os.path.dirname(__file__), "results", "events_overhead.json"
    )
    with open(path, "w") as f:
        json.dump(results, f, indent=2)
    print(json.dumps(results, indent=2))
    print(f"wrote {path}")
    if not identical or not results["within_budget"]:
        print("FAIL: events/corr overhead or parity budget violated")
        sys.exit(1)
    return results


def profile_launches():
    """Per-request cost of the launch flight recorder + time-series
    sampler (observability/{launches,timeseries}.py) against the
    acceptance budget — <= 0.5us/request amortized with the recorder
    enabled, ~0 with LAUNCH_RECORDER_SIZE=0.

    An end-to-end A/B over do_limit cannot resolve this budget: one
    batched launch round-trips in ~400us on the CPU platform, ~800x
    the number under test.  So the seams that pay the cost are
    measured directly (the flight leg's approach) and real dispatch
    is reserved for what it CAN prove:

    - ``stamp``     the per-item submit-ns stamp in
                    BatchDispatcher.submit (on) vs the ``launches is
                    None`` branch (off) — the only RPC-thread cost;
    - ``coalesce``  a REAL BatchDispatcher + recorder driven with
                    bursts under an open batch window: the measured
                    items-per-launch that amortizes the per-launch
                    bookkeeping (and a live end-to-end smoke of the
                    stamping seams);
    - ``launch``    everything the enabled path adds per LAUNCH on
                    the collector/completer threads (launch-start
                    stamp, oldest-submit/corr scan, dedup-stat read,
                    meta append/popleft, complete stamp, ring
                    record), replayed at the measured batch size;
    - ``sampler``   one TimeSeriesStore.tick() with the default
                    series registered, amortized at TSDB_INTERVAL_S=5
                    and a nominal 10k req/s;
    - ``parity``    decisions through two real batched caches
                    (recorder attached vs not) compared field by
                    field — the recorder must never change an answer.
    """
    from collections import deque

    from ratelimit_tpu.api import Descriptor, RateLimitRequest
    from ratelimit_tpu.backends.dispatcher import BatchDispatcher
    from ratelimit_tpu.backends.tpu_cache import TpuRateLimitCache
    from ratelimit_tpu.config.loader import ConfigFile, load_config
    from ratelimit_tpu.observability.launches import (
        OUTCOME_OK,
        make_launch_recorder,
    )
    from ratelimit_tpu.observability.timeseries import (
        TimeSeriesStore,
        register_default_series,
    )
    from ratelimit_tpu.stats.manager import Manager
    from ratelimit_tpu.utils.time import PinnedTimeSource

    reps = 60
    results = {"budget_us_per_req": 0.5}
    mono = time.monotonic_ns

    # Leg 1: the submit-seam stamp (RPC thread, per item) — the exact
    # code shapes of BatchDispatcher.submit with a recorder attached
    # vs not.  Interleaved A/B (flight leg 1): a ~0.1us delta needs
    # both sides to see the same machine drift.
    items = make_items(None, 7)

    def stamp_enabled():
        for it in items:
            it.submit_ns = mono()

    none_recorder = None

    def stamp_disabled():
        for it in items:
            if none_recorder is not None:
                it.submit_ns = mono()

    times = {"on": [], "off": []}
    stamp_enabled(), stamp_disabled()  # warm
    for _ in range(4 * reps):
        t0 = time.perf_counter()
        stamp_enabled()
        times["on"].append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        stamp_disabled()
        times["off"].append(time.perf_counter() - t0)
    n = len(items)
    stamp_on = min(times["on"]) / n * 1e6
    stamp_off = min(times["off"]) / n * 1e6
    results["submit_stamp_us_per_item_enabled"] = stamp_on
    results["submit_stamp_us_per_item_disabled"] = stamp_off

    # Leg 2: measured coalescing through a REAL dispatcher + recorder.
    # Bursts of 8 items under an open 50ms window, flushed: the
    # collector drains each burst into one launch, so the recorder's
    # own coalesce_ratio() is the amortization denominator — and the
    # leg live-checks the stamping seams end to end (fields populated,
    # outcome ok).
    burst = 8
    engine = CounterEngine(num_slots=1 << 14)
    d = BatchDispatcher(engine, batch_window_us=50_000, batch_limit=4096)
    lr = make_launch_recorder(1 << 12)
    d.launches = lr
    try:
        ditems = make_items(engine, 11)[:256]
        for g in range(0, len(ditems), burst):
            for it in ditems[g : g + burst]:
                d.submit(it)
            d.flush()
            for it in ditems[g : g + burst]:
                it.wait(10.0)
    finally:
        d.stop()
    coalesce = lr.coalesce_ratio() or 1.0
    launches = lr.snapshot()
    ok = launches[launches["outcome"] == OUTCOME_OK]
    results["coalesce_items_per_launch_measured"] = coalesce
    results["launches_recorded"] = int(lr.stamped())
    seams_live = bool(
        len(ok)
        and int(ok["items"].sum()) == len(ditems)
        and (ok["launch_ns"] > 0).all()
        and (ok["queue_wait_ns"] > 0).all()
        and (ok["dedup_groups"] > 0).all()
    )
    results["seams_live"] = seams_live

    # Leg 3: per-launch bookkeeping — everything _launch() and the
    # completer's batch branch add when enabled, replayed over a
    # batch of the measured coalesce size against a real ring.
    lr2 = make_launch_recorder(1 << 12)
    rec = lr2.record
    meta_q = deque()
    batch = items[: max(1, round(coalesce))]
    for it in batch:
        it.submit_ns = mono()
        it.corr = 0x1234

    class _Eng:
        stat_dedup_groups = 6

    eng = _Eng()
    n_launches = 512

    def per_launch_ops():
        for _ in range(n_launches):
            # collector side (_launch)
            t0 = mono()
            oldest = corr = 0
            for it in batch:
                s = it.submit_ns
                if s and (oldest == 0 or s < oldest):
                    oldest = s
                    corr = it.corr
            queue_wait = t0 - oldest if oldest else 0
            meta_q.append(
                (
                    len(batch),
                    len(batch),
                    int(getattr(eng, "stat_dedup_groups", 0)),
                    queue_wait,
                    mono() - t0,
                    corr,
                )
            )
            # completer side (_complete_loop batch branch)
            t1 = mono()
            m = meta_q.popleft()
            rec(0, 0, m[0], m[1], m[2], m[3], m[4], mono() - t1, OUTCOME_OK, m[5])

    per_launch_ops()
    t_launch, _ = timed(per_launch_ops, reps=reps)
    per_launch_us = t_launch / n_launches * 1e6
    results["per_launch_bookkeeping_us"] = per_launch_us

    # Leg 4: the sampler tick with the default series registered,
    # amortized at the default 5s interval and a DELIBERATELY low
    # 10k req/s (less traffic = worse per-request amortization).
    mgr = Manager()
    ts = TimeSeriesStore(5.0, 3600.0)
    register_default_series(ts, mgr.store, launches=lr)
    ts.tick()
    t_tick, _ = timed(ts.tick, reps=reps)
    tick_us = t_tick * 1e6
    sampler_us_per_req = tick_us / (5.0 * 10_000.0)
    results["tsdb_tick_us"] = tick_us
    results["tsdb_us_per_req_at_10k_rps"] = sampler_us_per_req

    # Totals.  Enabled = RPC-thread stamp + per-launch bookkeeping
    # amortized over the measured coalesce + the sampler's share;
    # disabled = the None-guard branch alone (ring + sampler are off).
    results["total_overhead_us_per_req_enabled"] = (
        stamp_on + per_launch_us / coalesce + sampler_us_per_req
    )
    results["total_overhead_us_per_req_disabled"] = stamp_off

    # Leg 5: decision parity — recorder attached vs not over the same
    # request stream through two real batched caches.
    yaml = (
        "domain: d\n"
        "descriptors:\n"
        "  - key: k\n"
        "    rate_limit:\n"
        "      unit: minute\n"
        "      requests_per_unit: 100\n"
    )

    def build(with_recorder):
        clock = PinnedTimeSource(1_700_000_000)
        cache = TpuRateLimitCache(
            CounterEngine(num_slots=4096),
            time_source=clock,
            batch_window_us=200,
        )
        if with_recorder:
            cache.attach_launch_recorder(make_launch_recorder(1 << 12))
        mgr = Manager()
        cfg = load_config([ConfigFile("config.bench", yaml)], mgr)
        return cache, cfg

    cache_on, cfg_on = build(True)
    cache_off, cfg_off = build(False)
    rng = np.random.default_rng(13)
    vals = rng.integers(0, 32, 256)
    identical = True
    try:
        for v in vals:
            desc = Descriptor.of(("k", f"value{v}"))
            req = RateLimitRequest("d", [desc], 1)
            s_on = cache_on.do_limit(req, [cfg_on.get_limit("d", desc)])
            s_off = cache_off.do_limit(req, [cfg_off.get_limit("d", desc)])
            a = [
                (s.code, s.limit_remaining, s.duration_until_reset)
                for s in s_on
            ]
            b = [
                (s.code, s.limit_remaining, s.duration_until_reset)
                for s in s_off
            ]
            if a != b:
                identical = False
                break
    finally:
        cache_on.close()
        cache_off.close()
    results["decisions_identical_on_off"] = identical
    results["within_budget"] = (
        results["total_overhead_us_per_req_enabled"] <= 0.5
        and results["total_overhead_us_per_req_disabled"] <= 0.05
    )

    path = os.path.join(
        os.path.dirname(__file__), "results", "launches_overhead.json"
    )
    with open(path, "w") as f:
        json.dump(results, f, indent=2)
    print(json.dumps(results, indent=2))
    print(f"wrote {path}")
    if not identical or not seams_live or not results["within_budget"]:
        print("FAIL: launch-recorder parity/seams/budget violated")
        sys.exit(1)
    return results


def profile_overload():
    """Per-request cost of the overload-control hot path
    (overload/controller.py), measured through the real serving seams
    (same harness as profile_flight), against the acceptance budget —
    <= ~1.5us/request with the controllers ENABLED and idle, ~0 with
    the layer absent (the runner builds no controller at defaults).

    Legs:

    - ``promo``:  the promotion-cache branch in _prepare_resolved —
                  attached-and-empty PromotionCache vs None (the
                  common case: promotion enabled, nothing currently
                  promoted);
    - ``admit``:  OverloadController.admit() per request with every
                  loop enabled and nothing tripped (one dict probe +
                  compares + tuple) — the service-side leg;
    - ``shed``:   admit() while actively shedding (the refusal path
                  must be CHEAPER than serving, or shedding cannot
                  relieve anything);
    - ``parity``: decisions field-identical with the idle controller
                  + empty promotion attached vs absent.
    """
    from ratelimit_tpu.api import Descriptor, RateLimitRequest  # noqa: E402
    from ratelimit_tpu.backends.tpu_cache import TpuRateLimitCache  # noqa: E402
    from ratelimit_tpu.overload import OverloadController, PromotionCache  # noqa: E402
    from ratelimit_tpu.service import RateLimitService  # noqa: E402
    from ratelimit_tpu.stats.manager import Manager  # noqa: E402
    from ratelimit_tpu.utils.time import PinnedTimeSource  # noqa: E402

    n_reqs = 256
    reps = 12
    yaml = (
        "domain: domain\n"
        "priority: 2\n"
        "descriptors:\n"
        "  - key: key\n"
        "    rate_limit:\n"
        "      unit: hour\n"
        "      requests_per_unit: 1000\n"
    )

    class _Runtime:
        def __init__(self, files):
            self._files = files

        def snapshot(self):
            files = self._files

            class Snap:
                def keys(self):
                    return sorted(files)

                def get(self, key):
                    return files.get(key, "")

            return Snap()

        def add_update_callback(self, fn):
            pass

    def build():
        clock = PinnedTimeSource(1_700_000_000)
        engine = CounterEngine(num_slots=1 << 16)
        cache = TpuRateLimitCache(engine, clock)
        svc = RateLimitService(
            _Runtime({"config.bench": yaml}), cache, Manager(), clock=clock
        )
        return svc, cache

    rng = np.random.default_rng(7)
    key_ids = rng.integers(0, DUP_KEYS, n_reqs * 4)
    reqs = []
    for r in range(n_reqs):
        descs = [
            Descriptor.of(("key", f"value{key_ids[r * 4 + j]}"))
            for j in range(4)
        ]
        reqs.append(RateLimitRequest("domain", descs, 0))

    def front(svc, cache):
        pool = cache._event_pool
        config = svc.get_current_config()
        for req in reqs:
            items, *_ = cache._prepare_resolved(req, config)
            if len(pool) < 1024:
                for _bank, _eng, item in items:
                    pool.append(item.event)

    import gc

    gc.collect()
    results = {"requests": n_reqs, "descriptors_per_request": 4}

    # Leg 1: the promotion-cache branch in the resolved front half —
    # interleaved best-of A/B like profile_flight (the delta is well
    # under run-to-run median noise).
    built = {"off": build(), "on": build()}
    built["on"][1].promotion = PromotionCache(ttl_s=2.0, capacity=1024)
    for name, (svc, cache) in built.items():
        front(svc, cache)  # warm the resolution cache
    times = {"on": [], "off": []}
    for _ in range(4 * reps):
        for name, (svc, cache) in built.items():
            t0 = time.perf_counter()
            front(svc, cache)
            times[name].append(time.perf_counter() - t0)
    t_on, t_off = min(times["on"]), min(times["off"])
    results["front_promo_off_us_per_req"] = t_off / n_reqs * 1e6
    results["front_promo_on_us_per_req"] = t_on / n_reqs * 1e6
    results["promo_overhead_us_per_req"] = (t_on - t_off) / n_reqs * 1e6

    # Leg 2: admit() enabled-idle vs the absent-controller None guard
    # (the service hot path's exact shape).
    ctrl = OverloadController(
        shed_enabled=True,
        promote_enabled=True,
        backpressure_enabled=True,
        backpressure_max_wait_s=0.0,
    )
    ctrl.set_priorities({"domain": 2})

    def admit_enabled():
        admit = ctrl.admit
        for _req in reqs:
            reason, gate = admit("domain")
            if gate is not None:  # pragma: no cover - gate idle
                gate.release()

    none_ctrl = None

    def admit_disabled():
        for _req in reqs:
            if none_ctrl is not None:
                none_ctrl.admit("domain")

    admit_enabled()
    t_on, _ = timed(admit_enabled, reps=reps)
    t_off, _ = timed(admit_disabled, reps=reps)
    results["admit_enabled_us_per_req"] = t_on / n_reqs * 1e6
    results["admit_disabled_us_per_req"] = t_off / n_reqs * 1e6
    results["admit_overhead_us_per_req"] = (t_on - t_off) / n_reqs * 1e6
    results["total_overhead_us_per_req"] = (
        results["promo_overhead_us_per_req"]
        + results["admit_overhead_us_per_req"]
    )

    # Leg 3: the refusal path while actively shedding.
    ctrl._floor = 1
    ctrl._recompute_shed_locked()
    t_shed, _ = timed(
        lambda: [ctrl.admit("stranger") for _ in reqs], reps=reps
    )
    results["admit_shedding_us_per_req"] = t_shed / n_reqs * 1e6
    ctrl._floor = 0
    ctrl._recompute_shed_locked()

    # Leg 4: decision parity with the idle layer attached.
    svc_off, cache_off = built["off"]
    svc_on, cache_on = built["on"]
    svc_on.overload = ctrl
    identical = True
    for req in reqs:
        st_on, _lim, unl_on = cache_on.do_limit_resolved(
            req, svc_on.get_current_config()
        )
        st_off, _lim2, unl_off = cache_off.do_limit_resolved(
            req, svc_off.get_current_config()
        )
        a = [
            (s.code, s.limit_remaining, s.duration_until_reset)
            for s in st_on
        ]
        b = [
            (s.code, s.limit_remaining, s.duration_until_reset)
            for s in st_off
        ]
        if a != b or unl_on != unl_off:
            identical = False
            break
    results["decisions_identical_idle_on_off"] = identical
    results["budget_us_per_req"] = 1.5
    results["within_budget"] = (
        results["total_overhead_us_per_req"] <= 1.5
    )

    path = os.path.join(
        os.path.dirname(__file__), "results", "overload_overhead.json"
    )
    with open(path, "w") as f:
        json.dump(results, f, indent=2)
    print(json.dumps(results, indent=2))
    print(f"wrote {path}")
    if not identical:
        print("FAIL: decisions differ with idle overload layer attached")
        sys.exit(1)
    return results


def profile_watchdog():
    """Healthy-path cost of the device fault domain
    (backends/fault_domain.py), against the acceptance budget —
    <= 0.5us/request with the watchdog ENABLED and every bank closed,
    and decisions identical enabled vs disabled.

    Legs:

    - ``ops``:    the exact extra per-item work _execute does when the
                  domain is armed and healthy — the quarantine check,
                  the swap-safe engine resolve, and the kernel-deadline
                  timeout clamp — measured as a closure against an
                  empty-loop baseline (the dispatcher's ms-scale batch
                  window would drown the ns-scale delta in an
                  end-to-end A/B);
    - ``parity``: the same request stream through two REAL batched
                  caches (dispatcher + device step), fault domain
                  armed vs absent — every decision field must match.
    """
    from ratelimit_tpu.api import Descriptor, RateLimitRequest  # noqa: E402
    from ratelimit_tpu.backends.tpu_cache import TpuRateLimitCache  # noqa: E402
    from ratelimit_tpu.config.loader import ConfigFile, load_config  # noqa: E402
    from ratelimit_tpu.stats.manager import Manager  # noqa: E402
    from ratelimit_tpu.utils.time import PinnedTimeSource  # noqa: E402

    yaml = (
        "domain: domain\n"
        "descriptors:\n"
        "  - key: key\n"
        "    rate_limit:\n"
        "      unit: hour\n"
        "      requests_per_unit: 1000\n"
    )

    def build(armed):
        clock = PinnedTimeSource(1_700_000_000)
        cache = TpuRateLimitCache(
            CounterEngine(num_slots=1 << 12, buckets=(8, 64)),
            clock,
            batch_window_us=100,
            kernel_deadline_s=0.25 if armed else 0.0,
            fault_interval_s=0 if armed else None,  # no thread: ops only
            fault_snapshot_interval_s=1e9,
        )
        mgr = Manager()
        config = load_config([ConfigFile("config.bench", yaml)], mgr)
        return cache, config

    results = {}

    # Leg 1 — the armed-path ops, per item (one bank item per request
    # in the common case).
    cache_on, config_on = build(armed=True)
    fd = cache_on.fault_domain
    n = 200_000
    dispatch_timeout = 120.0

    def armed_ops():
        is_q = fd.is_quarantined
        eng_at = fd.engine_at
        kd = fd.kernel_deadline_s
        sink = None
        for _ in range(n):
            if not is_q(0):
                sink = eng_at(0)
            timeout = dispatch_timeout
            if kd < timeout:
                timeout = kd
        return sink, timeout

    def baseline_ops():
        sink = None
        for _ in range(n):
            sink = None
            timeout = dispatch_timeout
        return sink, timeout

    armed_ops()
    baseline_ops()
    t_on = min(timed(armed_ops, reps=7)[0] for _ in range(3))
    t_off = min(timed(baseline_ops, reps=7)[0] for _ in range(3))
    results["armed_ops_us_per_item"] = (t_on - t_off) / n * 1e6
    results["budget_us_per_req"] = 0.5
    results["within_budget"] = results["armed_ops_us_per_item"] <= 0.5

    # Leg 2 — decision parity through the real dispatcher path.
    cache_off, config_off = build(armed=False)
    rng = np.random.default_rng(11)
    identical = True
    for i in range(400):
        req = RateLimitRequest(
            "domain",
            [Descriptor.of(("key", f"v{rng.integers(0, 32)}"))],
            1,
        )
        st_on, _l1, _u1 = cache_on.do_limit_resolved(req, config_on)
        st_off, _l2, _u2 = cache_off.do_limit_resolved(req, config_off)
        a = [
            (s.code, s.limit_remaining, s.duration_until_reset)
            for s in st_on
        ]
        b = [
            (s.code, s.limit_remaining, s.duration_until_reset)
            for s in st_off
        ]
        if a != b:
            identical = False
            break
    results["decisions_identical_armed_vs_off"] = identical
    results["quarantined_banks_after"] = fd.quarantined_count()
    cache_on.close()
    cache_off.close()

    path = os.path.join(
        os.path.dirname(__file__), "results", "watchdog_overhead.json"
    )
    with open(path, "w") as f:
        json.dump(results, f, indent=2)
    print(json.dumps(results, indent=2))
    print(f"wrote {path}")
    if not identical or not results["within_budget"]:
        print("FAIL: watchdog overhead/parity budget violated")
        sys.exit(1)
    return results


def main():
    if "--launches" in sys.argv:
        profile_launches()
        sys.exit(0)
    if "--watchdog" in sys.argv:
        profile_watchdog()
        sys.exit(0)
    if "--overload" in sys.argv:
        profile_overload()
        sys.exit(0)
    if "--events" in sys.argv:
        profile_events()
        sys.exit(0)
    if "--flight" in sys.argv:
        profile_flight()
        sys.exit(0)
    if "--hotkeys" in sys.argv:
        profile_hotkeys()
        sys.exit(0)
    if "--quick" in sys.argv:
        results = {}
        ok, info = profile_resolution(results, quick=True)
        print(json.dumps({"quick": True, "ok": ok, **info}))
        sys.exit(0 if ok else 1)

    engine = CounterEngine(num_slots=1 << 20)
    results = {}

    # Round-6: the descriptor-resolution front half, resolved vs
    # uncached, through the real service/cache seams.  Runs FIRST so
    # the dispatcher sections' allocation churn can't contaminate it.
    _, res_info = profile_resolution(results)

    # Warm the XLA shapes first.
    items = make_items(engine, 0)
    tok = submit_items(engine, items)
    complete_items(engine, items, tok)

    # RPC-side: pack construction for 1024 requests x 4 lanes
    # (parallel across handler threads in serving).
    def build_packs():
        its = make_items(engine, 1)
        return its

    t_make, its = timed(build_packs)
    results["make_items_rpc_side"] = t_make

    # Collector phase: submit_items = concat + fused assign/dedup +
    # packed transfer + launch.  (Measured with pre-packed items, as
    # in serving.)
    t_submit, tok = timed(lambda: submit_items(engine, its))
    complete_items(engine, its, tok)
    results["submit_total"] = t_submit

    # Sub-phases of the collector.
    packs = [it.get_pack() for it in its]

    def concat():
        from ratelimit_tpu.backends.dispatcher import LANE_DTYPE

        blob = b"".join(p.key_blob for p in packs)
        meta = np.concatenate([p.meta_u8 for p in packs]).view(LANE_DTYPE)
        return blob, meta

    t_concat, (blob, meta) = timed(concat)
    results["pack_concat"] = t_concat

    blob_arr = np.frombuffer(blob, dtype=np.uint8)
    now = 1_700_000_000
    table = engine.slot_table
    if hasattr(table, "assign_dedup_packed"):
        lens = meta["len"].astype(np.int64)
        expiries = np.ascontiguousarray(meta["expiry"])
        hits = np.ascontiguousarray(meta["hits"])
        limits = np.ascontiguousarray(meta["limits"])
        t_fused, _ = timed(
            lambda: table.assign_dedup_packed(
                blob_arr, lens, now, expiries, hits, limits
            )
        )
        results["fused_assign_dedup_cpp"] = t_fused

    # Full collector+completer through the real dispatcher functions.
    def round_trip():
        token = submit_items(engine, its)
        return complete_items(engine, its, token)

    t_rt, _ = timed(round_trip)
    results["submit_plus_complete"] = t_rt
    results["complete_total"] = t_rt - t_submit

    # Status assembly measured through a realistic apply: the real
    # serving apply (tpu_cache._apply_decisions) does stat adds + one
    # DescriptorStatus per lane from list-backed decisions.
    from ratelimit_tpu.api import Code, DescriptorStatus

    _CODE = {c.value: c for c in Code}

    class _Stat:
        __slots__ = ("v",)

        def __init__(self):
            self.v = 0

        def add(self, x):
            self.v += x

    stats = [_Stat() for _ in range(4)]
    statuses = [None] * 4

    def apply(d):
        # 4 lanes per item, list-backed decisions.
        over, near, within, shadow = stats
        for j in range(4):
            v = d.over_limit[j]
            if v:
                over.add(v)
            v = d.near_limit[j]
            if v:
                near.add(v)
            v = d.within_limit[j]
            if v:
                within.add(v)
            v = d.shadow_mode[j]
            if v:
                shadow.add(v)
            statuses[j] = DescriptorStatus(
                code=_CODE[d.codes[j]],
                current_limit=None,
                limit_remaining=d.limit_remaining[j],
                duration_until_reset=60,
            )

    its_apply = make_items(engine, 3, apply=apply)
    tok = submit_items(engine, its_apply)
    complete_items(engine, its_apply, tok)  # warm

    def rt_apply():
        token = submit_items(engine, its_apply)
        return complete_items(engine, its_apply, token)

    t_rta, _ = timed(rt_apply)
    results["submit_plus_complete_with_status_assembly"] = t_rta
    results["status_assembly"] = t_rta - t_rt

    # Round-4 serving split (defer_apply=True): the completer only
    # parks per-item decision slices + signals; status assembly runs
    # on the waiting RPC threads (item.wait -> apply), where it
    # parallelizes across the handler pool and overlaps the next
    # batch.  Measure both legs separately.
    its_defer = make_items(engine, 4, apply=apply)
    for it in its_defer:
        it.defer_apply = True
    tok = submit_items(engine, its_defer)
    complete_items(engine, its_defer, tok)  # warm
    for it in its_defer:
        it.wait(5)
        it.event.clear()

    def rt_defer():
        token = submit_items(engine, its_defer)
        return complete_items(engine, its_defer, token)

    t_rtd, _ = timed(rt_defer)
    # timed() left one completed round parked; drain + measure the
    # RPC-side leg (serial here; spread over handler threads in
    # serving).  The lists-from-views conversion happens inside apply
    # via tolist on each item's slice.
    def drain_waits():
        for it in its_defer:
            it.wait(5)
            it.event.clear()
        return None

    t_wait, _ = timed(
        lambda: (rt_defer(), drain_waits())[1], reps=10
    )
    results["serving_completer_per_batch"] = t_rtd - results["submit_total"]
    results["deferred_assembly_rpc_side"] = max(0.0, t_wait - t_rtd)

    collector = results["submit_total"]
    completer = results["serving_completer_per_batch"]
    assembly = results["deferred_assembly_rpc_side"]
    results["collector_serial_per_batch"] = collector
    results["completer_per_batch"] = completer
    results["max_batches_per_sec_collector"] = 1.0 / collector
    # Two capacity numbers, both honest: the pipelined bound assumes
    # the collector, completer and RPC handler threads each have their
    # own core (the deferred-assembly leg spreads over the handler
    # pool); the 1-core bound sums every leg — the assembly work moved
    # off the completer, it did not disappear.
    results["implied_decisions_per_sec_pipelined"] = BATCH / max(
        collector, completer
    )
    results["implied_decisions_per_sec_one_core"] = BATCH / (
        collector + completer + assembly
    )

    out = {
        "batch": BATCH,
        "requests": REQUESTS,
        "dup_keys": DUP_KEYS,
        "note": (
            "round-4 pipeline: LanePack on RPC threads, fused C++ "
            "assign+dedup, single (4,N) int32 transfer, fused C++ "
            "decide+reconstruct (native/decide.cpp), deferred status "
            "assembly on RPC threads (defer_apply); round-6: "
            "descriptor-resolution cache front half (resolution_* "
            "keys, per 1024-request/4096-lane batch); 1-core host, "
            "CPU platform"
        ),
        "phases_seconds": results,
        "resolution": res_info,
    }
    path = os.path.join(
        os.path.dirname(__file__), "results", "host_path.json"
    )
    with open(path, "w") as f:
        json.dump(out, f, indent=2)
    for k, v in results.items():
        if isinstance(v, float) and v < 1:
            print(f"{k:45s} {v*1e6:12.1f} us")
        else:
            print(f"{k:45s} {v:12.3f}" if isinstance(v, float) else f"{k:45s} {v:12d}")
    print(f"wrote {path}")


if __name__ == "__main__":
    main()
