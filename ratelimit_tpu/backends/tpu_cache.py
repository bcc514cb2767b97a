"""TpuRateLimitCache: the RateLimitCache implementation over the
device counter engine.

Structurally mirrors the reference's Redis backend DoLimit
(src/redis/fixed_cache_impl.go:33-113), with the pipelined
INCRBY+EXPIRE round trip replaced by one batched device step:

1. ``hits_addend = max(1, request.hits_addend)``;
2. generate window-aligned cache keys + TotalHits stats;
3. host over-limit cache short-circuit (shadow-aware: a shadow rule
   with a cached over-limit key skips the counter entirely and falls
   through to an OK/within-limit status, matching
   fixed_cache_impl.go:57-67's ``continue``);
4. per-second limits route to a dedicated engine bank when configured
   (dual-Redis analog, fixed_cache_impl.go:77-87);
5. engine-bound lanes run either inline (batch_window_us=0) or through
   the micro-batching dispatcher (one device launch shared by
   concurrent RPCs — the radix implicit-pipelining analog,
   settings.go:71-77);
6. statuses assembled with duration-until-reset; first over-limit
   transitions populate the host cache with TTL = full window
   (base_limiter.go:103-115).

Backend failures surface as service.CacheError (the RedisError panic
analog, driver_impl.go:60-64) so the service boundary can count them.
"""

from __future__ import annotations

import random
import threading
import time
from typing import List, Optional, Sequence, Union
from zlib import crc32

import numpy as np

from ..api import Code, DescriptorStatus, RateLimitRequest
from ..config import RateLimitRule
from ..models.registry import ALGORITHMS
from ..observability import HotKeySketch, TRACER
from ..limiter.cache_key import CacheKey, CacheKeyGenerator, EMPTY_KEY
from ..limiter.local_cache import LocalCache
from ..limiter.resolution import (
    ResolutionCache,
    flat_key,
    record,
)
from ..stats.manager import Counter
from ..utils.time import (
    TimeSource,
    RealTimeSource,
    reset_seconds_cached,
    unit_to_divider,
    window_start,
)
from .dispatcher import (
    LANE_DTYPE,
    BatchDispatcher,
    LanePack,
    WorkItem,
    run_items,
)
from .engine import CounterEngine, HostDecisions, register_slot_stats

# Device code -> api Code without an enum __call__ per lane.
_CODE_BY_VALUE = {c.value: c for c in Code}
_OVER_VALUE = int(Code.OVER_LIMIT)

_CAT_NONE = 0  # no matching rule: OK, no stats
_CAT_ENGINE = 1  # goes to the counter engine
_CAT_LOCAL = 2  # host cache says over-limit: short-circuit
_CAT_SKIP = 3  # shadow rule + cached over-limit: skip counter, OK

# The resolution map's probe for a request whose config is not the
# table's generation: finds nothing, so every descriptor goes to miss().
_NO_ENTRY = {}.get


def warmup_engine(engine) -> None:
    """Pre-compile one engine's (bucket, readback-dtype) kernel shapes
    with inert batches — DISTINCT IN-TABLE slots with hits=0 and
    fresh=False, which scatter-add zero (or set a counter to its own
    value on the unique path), so counter state and the slot table are
    untouched.  A mesh bank's shapes are the same (bucket, dtype) set:
    its step takes the whole bucket on every chip.

    Module-level so the fault-domain supervisor can warm a freshly
    rebuilt engine OFF the serving path before probing/re-admitting it
    (a cold engine's first post-swap batches would otherwise stall
    their RPCs for each shape's XLA compilation)."""
    from .engine import HostBatch

    for bucket in engine.buckets:
        # One probe per readback dtype (u8 / u16 / u32 caps).
        # Distinct in-table slots so the engine's dedup pass keeps all
        # `bucket` lanes (CounterEngine.warmup_probe_slots).
        probe_slots = engine.warmup_probe_slots(bucket)
        width = len(probe_slots)
        for probe_limit in (100, 60_000, 3_000_000_000):
            batch = HostBatch(
                slots=probe_slots,
                hits=np.zeros(width, np.uint32),
                limits=np.full(width, probe_limit, np.uint32),
                fresh=np.zeros(width, bool),
                shadow=np.zeros(width, bool),
            )
            engine.step(batch)


def _engine_failure(exc):
    """Build the dead-engine CacheError OFF the _execute wait loop —
    the f-string (and the deferred import) runs only when an RPC is
    already failing, never per healthy iteration (tpu-lint
    hot-path-cost)."""
    from ..service import CacheError

    return CacheError(f"counter engine failure: {exc}")


class TpuRateLimitCache:
    def __init__(
        self,
        engine: Union[CounterEngine, Sequence[CounterEngine]],
        time_source: Optional[TimeSource] = None,
        per_second_engine: Optional[CounterEngine] = None,
        local_cache: Optional[LocalCache] = None,
        expiration_jitter_max_seconds: int = 0,
        cache_key_prefix: str = "",
        jitter_rand: Optional[random.Random] = None,
        batch_window_us: int = 0,
        batch_limit: int = 4096,
        dispatch_timeout_s: float = 120.0,
        pipeline_depth: int = 2,
        unhealthy_after: int = 3,
        resolution_cache_entries: int = 1 << 16,
        hotkeys_top_k: int = 0,
        algorithm_banks: Optional[dict] = None,
        kernel_deadline_s: float = 0.0,
        device_failure_mode: str = "host",
        fault_clock=None,
        fault_restart_backoff_s: float = 2.0,
        fault_snapshot_interval_s: float = 30.0,
        fault_interval_s: Optional[float] = None,
        fault_probe_timeout_s: Optional[float] = None,
        fault_restart_warmup: bool = True,
        engine_factory=None,
        thread_clocks: bool = False,
    ):
        """`engine` may be a LIST of engines: N independent host LANES,
        each with its own slot table, dispatcher thread pair, and
        device stream.  Keys hash-split across lanes (crc32 of the full
        cache key), the in-process mirror of the cluster tier's
        rendezvous split — on an M-core host the N serial collector
        legs run on N cores, so host throughput scales toward the
        device kernel instead of capping at one collector thread (the
        concurrency the reference gets free from goroutine-per-RPC +
        Redis pipelining, driver_impl.go:94-99).  See docs/HOST_LANES.md."""
        lanes = (
            list(engine)
            if isinstance(engine, (list, tuple))
            else [engine]
        )
        if not lanes:
            raise ValueError("need at least one engine lane")
        self.lanes: List[CounterEngine] = lanes
        self.engine = lanes[0]  # lane 0 (compat surface)
        self.per_second_engine = per_second_engine
        # Algorithm-table banks (models/registry.py): one dedicated
        # engine per non-default limiter algorithm (sliding-window,
        # GCRA).  Rules carrying ``algorithm: <name>`` route their
        # lanes here — as the CANDIDATE when ``shadow: true`` (the
        # fixed-window lanes keep enforcing and decision divergence is
        # counted below), as the ENFORCING bank otherwise.  Algorithms
        # with no bank fold back to fixed-window at resolution time.
        self.algorithm_banks: dict = {
            name: eng
            for name, eng in (algorithm_banks or {}).items()
            if eng is not None
        }
        for name in self.algorithm_banks:
            if name not in ALGORITHMS:
                raise ValueError(f"unknown algorithm bank {name!r}")
        self._algo_order = sorted(self.algorithm_banks)
        n_base = len(lanes) + (1 if per_second_engine is not None else 0)
        self._algo_bank_index = {
            name: n_base + i for i, name in enumerate(self._algo_order)
        }
        # Tracer bank labels, by bank index (see _execute).
        self._bank_labels = [f"lane{i}" for i in range(len(lanes))]
        if per_second_engine is not None:
            self._bank_labels.append("per_second")
        self._bank_labels.extend("algo_" + n for n in self._algo_order)
        # Lazily-grown labels for bank indexes PAST the static table
        # (override banks); _bank_label fills it on first sight so the
        # format never runs inside the _execute submit loop.
        self._extra_bank_labels = {}
        # Shadow-rollout divergence tallies per algorithm:
        # [agree, diverge] plain ints bumped on the RPC thread
        # (stats-only GIL races accepted, like the resolver tallies);
        # exported as ratelimit.tpu.shadow.<algo>.{agree,diverge}.
        self._shadow_counts = {name: [0, 0] for name in self._algo_order}
        self.time_source = time_source or RealTimeSource()
        self.local_cache = local_cache
        self.key_generator = CacheKeyGenerator(cache_key_prefix)
        # Cluster counter-handoff bookkeeping (cluster/handoff.py
        # export_from_cache/import_into_cache write it; /debug/cluster
        # and the ratelimit.cluster.* counter family read it).  The
        # import is jax- and grpc-free (hashing + numpy only).
        from ..cluster.handoff import HandoffLog

        self.handoff_log = HandoffLog()
        # Descriptor-resolution fast path (limiter/resolution.py): the
        # service resolves each descriptor through this once per config
        # generation; do_limit then reuses the memoized key, lane route
        # and LANE_DTYPE template instead of re-running the per-request
        # pipeline.  0 disables it (A/B benchmarking knob).
        self.resolver = (
            ResolutionCache(
                LANE_DTYPE,
                prefix=cache_key_prefix,
                capacity=resolution_cache_entries,
                algorithms=frozenset(self.algorithm_banks),
            )
            if resolution_cache_entries > 0
            else None
        )
        # Hot-key sketch (observability/hotkeys.py): Space-Saving
        # top-K over interned descriptor stems, fed by the resolution
        # fast path below (one counter bump per descriptor on a
        # pre-resolved handle).  0 disables; requires the resolver
        # (the handle lives on its entries).
        self.hotkeys = (
            HotKeySketch(hotkeys_top_k)
            if hotkeys_top_k > 0 and self.resolver is not None
            else None
        )
        # Near-limit threshold ratio for the sketch's outcome shares
        # (mirrors the engines' decide threshold).
        self._near_ratio = float(
            getattr(lanes[0].model, "near_ratio", 0.8)
        )
        # Flight recorder (observability/flight.py), attached by the
        # runner when FLIGHT_RECORDER_SIZE > 0: the resolution fast
        # path deposits the decisive descriptor's (stem hash, bank)
        # into its thread-local note, and the transport layer stamps
        # the ring record after serialize.  None = disabled (the
        # per-request cost is one attribute load + branch).
        self.flight = None
        # Lifecycle event journal (observability/events.py), attached
        # by the runner when EVENT_JOURNAL_SIZE > 0: handoff
        # export/import (cluster/handoff.py) and the fault domain's
        # quarantine/restart transitions stamp the fleet timeline.
        # Emission is transition-only — never per request.
        self.events = None
        # Hot-key promotion cache (overload/controller.py), attached
        # by the runner when OVERLOAD_PROMOTE_ENABLED: stems the
        # sketch marked repeat offenders carry a short-TTL host-side
        # OVER_LIMIT decision checked in _prepare_resolved, so they
        # skip the device entirely (the reference's freecache
        # OVER_LIMIT cache, sketch-driven).  None = disabled (one
        # attribute load + branch per descriptor).
        self.promotion = None
        # Launch flight recorder (observability/launches.py), attached
        # by the runner via attach_launch_recorder when
        # LAUNCH_RECORDER_SIZE > 0: every bank dispatcher stamps one
        # ring record per device batch at its submit/complete seams,
        # and quarantine fallbacks stamp through the fault domain.
        # None = disabled (one attribute load + branch per launch).
        self.launches = None
        self.expiration_jitter_max_seconds = int(expiration_jitter_max_seconds)
        self.jitter_rand = jitter_rand or random.Random()
        # Liveness backstop for dispatcher waits; generous because the
        # first batch through a new (bucket, dtype) shape pays XLA
        # compilation (~seconds to tens of seconds on big meshes) —
        # see warmup() to pre-pay that before serving.
        self.dispatch_timeout_s = float(dispatch_timeout_s)
        # The reference wraps its jitter rand in a mutex because
        # rand.Rand isn't goroutine-safe (utils/time.go:28-48); same.
        self._jitter_lock = threading.Lock()
        # Recycled WorkItem events (threading.Event construction is
        # ~1.8us — the single largest fixed cost of an all-resolved
        # request).  Plain list: append/pop are GIL-atomic.  Events
        # are recycled ONLY after a successful wait() (the completer's
        # set() has a happens-before edge to the waiter and never
        # touches the event again); timed-out/failed items keep
        # theirs, so a late set() can't leak into a new item.
        # Take via _pool_event() ONLY: `pool.pop() if pool else ...`
        # raced — another RPC thread can drain the last entry between
        # the truthiness check and the pop, raising IndexError on the
        # hot path (found by tpu-lint's shared-state pass).
        self._event_pool: List[threading.Event] = []

        # Inline mode (batch_window_us=0) runs the engine step on the
        # RPC caller thread; a per-engine lock serializes access to the
        # SlotTable and the donated counts buffer, which the dispatcher
        # thread otherwise owns exclusively.
        self._inline_locks = {id(e): threading.Lock() for e in self.lanes}
        if per_second_engine is not None:
            self._inline_locks[id(per_second_engine)] = threading.Lock()
        for eng in self.algorithm_banks.values():
            self._inline_locks[id(eng)] = threading.Lock()

        # Dispatcher construction knobs, kept for warm restarts: the
        # fault-domain supervisor rebuilds a quarantined bank's
        # dispatcher with exactly the serving parameters
        # (_make_dispatcher).
        self._batch_window_us = int(batch_window_us)
        self._batch_limit = int(batch_limit)
        self._pipeline_depth = pipeline_depth
        self._unhealthy_after = unhealthy_after
        self._stamp_clock = fault_clock
        # DEBUG_PROFILING=1: the dispatcher threads read their CPU
        # clock at both ends of a device-call bracket (CallWatch.cpu_clock).
        self._thread_clocks = bool(thread_clocks)
        self._dispatchers: dict = {}
        if batch_window_us > 0:
            for idx, lane in enumerate(self.lanes):
                self._dispatchers[id(lane)] = self._make_dispatcher(
                    lane,
                    name=(
                        "tpu-dispatcher"
                        if len(self.lanes) == 1
                        else f"tpu-dispatcher-lane{idx}"
                    ),
                )
            if per_second_engine is not None:
                self._dispatchers[id(per_second_engine)] = (
                    self._make_dispatcher(
                        per_second_engine, name="tpu-dispatcher-persecond"
                    )
                )
            for name in self._algo_order:
                eng = self.algorithm_banks[name]
                self._dispatchers[id(eng)] = self._make_dispatcher(
                    eng, name="tpu-dispatcher-" + name
                )

        # Device-path fault domain (backends/fault_domain.py): the
        # watchdog/quarantine/warm-restart envelope around the banks.
        # KERNEL_DEADLINE_S=0 (the library default) builds none — the
        # serving path is then byte-identical to a build without the
        # layer; the runner turns it on by default.  The failure mode
        # is validated (and kept) even without a domain: the
        # caller-deadline path answers with it.
        from .fault_domain import FAILURE_MODES

        if device_failure_mode not in FAILURE_MODES:
            raise ValueError(
                f"DEVICE_FAILURE_MODE must be one of "
                f"{sorted(FAILURE_MODES)}, got {device_failure_mode!r}"
            )
        self.device_failure_mode = device_failure_mode
        self.stat_deadline_answers = 0
        # What the host answered alone: descriptors decided on the RPC
        # thread (_CAT_LOCAL, _CAT_SKIP) and requests that queued no
        # work item.  Written once a request at the end of _execute,
        # read by the scrape alone (register_stats): no decision, count
        # or expiry depends on them.
        self.stat_local_decisions = Counter("local_decisions")
        self.stat_requests_no_launch = Counter("requests_no_launch")
        self._health = None
        self._health_hook = None
        self.fault_domain = None
        if kernel_deadline_s > 0 and self._dispatchers:
            from .fault_domain import DeviceFaultDomain

            self.fault_domain = DeviceFaultDomain(
                self,
                kernel_deadline_s,
                failure_mode=device_failure_mode,
                clock=fault_clock,
                restart_backoff_s=fault_restart_backoff_s,
                snapshot_interval_s=fault_snapshot_interval_s,
                interval_s=fault_interval_s,
                engine_factory=engine_factory,
                probe_timeout_s=fault_probe_timeout_s,
                restart_warmup=fault_restart_warmup,
            )
            self.fault_domain.start()

    # -- RateLimitCache seam --------------------------------------------

    def _prepare(
        self,
        request: RateLimitRequest,
        limits: Sequence[Optional[RateLimitRule]],
    ):
        """The host-side front half of do_limit — key generation,
        local-cache check, bank routing, lane packing — with no device
        work.  Split out so benchmarks/profile_host_path.py can time
        exactly this leg (the cost the resolution fast path attacks);
        do_limit runs it then submits/waits.

        Returns (items, statuses, categories, keys, hits_addend, now)
        where items is [(bank, engine, WorkItem)]."""
        n = len(request.descriptors)
        assert n == len(limits)
        hits_addend = max(1, request.hits_addend)
        now = self.time_source.unix_now()

        # Plain list: serving requests are a handful of descriptors,
        # where list ops beat numpy scalar writes by ~10x.
        categories = [_CAT_NONE] * n
        n_lanes = len(self.lanes)
        # Index lists per engine bank: one per lane, plus per-second.
        rows_by_lane: List[List[int]] = [[] for _ in range(n_lanes)]
        per_second_rows: List[int] = []
        # Pre-encoded keys (lane routing hashes the utf-8 STEM so a
        # key keeps its lane across windows and the cached/uncached
        # paths agree); only materialized on the multi-lane path so
        # single-lane serving pays nothing — _make_item re-encodes
        # there as before.
        enc_keys: Optional[List[Optional[bytes]]] = (
            [None] * n if n_lanes > 1 else None
        )
        local_cache = self.local_cache

        # Key generation + TotalHits (base_limiter.go:45-60).
        keys = []
        for desc, rule in zip(request.descriptors, limits):
            key = self.key_generator.generate(request.domain, desc, rule, now)
            keys.append(key)
            if rule is not None and not rule.unlimited:
                rule.stats.total_hits.add(hits_addend)

        for i, (key, rule) in enumerate(zip(keys, limits)):
            if key.key == "":
                continue
            if local_cache is not None and local_cache.contains(key.key):
                # Shadow rules skip the counter but never short-
                # circuit to OVER_LIMIT (fixed_cache_impl.go:57-67).
                categories[i] = _CAT_SKIP if rule.shadow_mode else _CAT_LOCAL
                continue
            categories[i] = _CAT_ENGINE
            if self.per_second_engine is not None and key.per_second:
                per_second_rows.append(i)
            elif n_lanes == 1:
                rows_by_lane[0].append(i)
            else:
                b = key.key.encode("utf-8")
                enc_keys[i] = b
                stem = b[: key.stem_blen] if key.stem_blen else b
                rows_by_lane[crc32(stem) % n_lanes].append(i)

        statuses: List[Optional[DescriptorStatus]] = [None] * n

        pairs = [
            (lane, rows) for lane, rows in zip(self.lanes, rows_by_lane)
        ]
        pairs.append((self.per_second_engine, per_second_rows))
        items: List[tuple] = []  # (bank, engine, WorkItem)
        for bank, (engine, rows) in enumerate(pairs):
            if not rows:
                continue
            item = self._make_item(
                rows, keys, limits, hits_addend, now, statuses, enc_keys
            )
            items.append((bank, engine, item))
        return items, statuses, categories, keys, hits_addend, now

    def _prepare_resolved(self, request: RateLimitRequest, config):
        """The one-dict-hit front half (limiter/resolution.py): rule
        lookup, key, TotalHits, local-cache check, bank routing AND
        per-bank pack assembly fused into a single pass over the
        descriptors.  Each engine-bound descriptor contributes three
        list appends — row index, key bytes (the key's stem + the
        rule's window suffix), record bytes (the rule's window
        template around the key's length) — and the per-bank packer
        just joins them.  ``_construct_limits_to_check``,
        CacheKeyGenerator.generate and _make_item's per-lane loop all
        collapse here.

        Returns (items, statuses, categories, keys, limits,
        is_unlimited, hits_addend, now, hot) — ``hot`` is the per-row
        hot-key entry list (None when the sketch is disabled); ``keys``
        holds a CacheKey only where something reads one (override rows,
        and every limited row when the local over-limit cache is on)."""
        resolver = self.resolver
        descriptors = request.descriptors
        domain = request.domain
        n = len(descriptors)
        hits_addend = max(1, request.hits_addend)
        hits_clamped = min(hits_addend, 0xFFFFFFFF)
        now = self.time_source.unix_now()

        limits: list = [None] * n
        is_unlimited = [False] * n
        keys: list = [EMPTY_KEY] * n
        categories = [_CAT_NONE] * n
        n_lanes = len(self.lanes)
        # Algorithm-table routing state, allocated lazily: the common
        # all-fixed-window request pays one int-truthiness branch per
        # descriptor and nothing else.
        algo_accs: Optional[dict] = None  # name -> (rows, enc, tpl)
        shadow_accs: Optional[dict] = None  # name -> (rows, enc, tpl)
        shadow_rows: Optional[list] = None  # (i, name, algo_id)
        raw_over: Optional[list] = None  # enforced pre-shadow over-ness
        cand_over: Optional[list] = None  # candidate over-ness
        cand_code: Optional[list] = None  # candidate would-be code
        # Per-bank accumulators: (row indices, key bytes, record bytes),
        # lanes first, per-second bank last.  The single-bank common
        # case routes through bound appends with no bank indirection.
        banks = [([], [], []) for _ in range(n_lanes)]
        ps_bank = ([], [], []) if self.per_second_engine is not None else None
        single_bank = n_lanes == 1 and ps_bank is None
        if single_bank:
            rows0, enc0, tp0 = banks[0]
            add_row = rows0.append
            add_enc = enc0.append
            add_tpl = tp0.append
        local_cache = self.local_cache
        promotion = self.promotion
        # Promotion miss fast path: membership on the raw entries dict
        # (one GIL-atomic op per descriptor); only HITS pay the
        # contains() call (expiry check + counting).
        promo_entries = promotion.entries if promotion is not None else None
        # Hot-key sketch feed: one counter bump per limited descriptor
        # on the handle pinned to its ResolvedDescriptor; track() (the
        # locked, structural path) only runs on first sight of a stem
        # or after a sketch eviction killed the handle.  Overrides
        # (request-supplied limits) bypass the resolver and are not
        # tracked.  ``hot`` rides back so do_limit_resolved can fold
        # the request's over/near-limit outcomes into the entries.
        hk = self.hotkeys
        hot: Optional[list] = [None] * n if hk is not None else None
        hk_observed = 0  # batched into hk.observed after the loop
        # Flight-recorder note: the FIRST limited descriptor is the
        # request's decisive identity in the ring (stem hash + bank).
        # One branch per descriptor until noted, then free.
        fl = self.flight
        fl_pending = fl is not None
        # Inlined resolve() hit path: the generation is checked once a
        # request (the table belongs to one; under another every probe
        # falls through to miss(), which sorts it out), then one dict
        # probe per descriptor on the flat string key, with the hit
        # tally batched into one attribute add per request.
        generation, entries_map, _ = resolver._live
        entries_get = (
            entries_map.get if generation == config.generation else _NO_ENTRY
        )
        miss = resolver.miss
        resolution_hits = 0
        overrides: Optional[list] = None
        # TotalHits adds batched by rule identity: consecutive
        # descriptors sharing a rule (the common wildcard pattern) pay
        # one counter lock instead of one each.
        prev_rule = None
        prev_hits = 0
        for i, desc in enumerate(descriptors):
            if desc.limit is not None:
                # Request-supplied override: uncached leg, handled in
                # the (rare) second pass below.
                if overrides is None:
                    overrides = []
                overrides.append(i)
                continue
            entries = desc.entries
            # flat_key(), its two common shapes spelled out.
            if len(entries) == 1:
                a = entries[0]
                ck = (domain, a.key, a.value)
            elif len(entries) == 2:
                a, b = entries
                ck = (domain, a.key, a.value, b.key, b.value)
            else:
                ck = flat_key(domain, entries)
            rd = entries_get(ck)
            if rd is not None:
                rd.ref = True  # tpu-lint: disable=shared-state -- idempotent second-chance bit
                resolution_hits += 1
            else:
                rd = miss(config, domain, desc, ck)
            rs = rd.rs
            rule = rs.rule
            if rule is None:
                continue  # no matching rule: CAT_NONE, empty key
            if rs.unlimited:
                is_unlimited[i] = True
                continue  # limits[i] stays None (service contract)
            limits[i] = rule
            # Hot-loop hoists (tpu-lint hot-path-cost): each of these
            # chains is probed several times per descriptor below —
            # load once per iteration instead of per use.
            algo_id = rs.algo_id
            algorithm = rs.algorithm
            per_second = rs.per_second
            stem_bytes = rd.stem_bytes
            if fl_pending:
                fl_pending = False
                if algo_id and not rs.algo_shadow:
                    note_bank = self._algo_bank_index[algorithm]
                elif ps_bank is not None and per_second:
                    note_bank = n_lanes
                else:
                    note_bank = rd.lane(n_lanes)
                fl.note(rd.stem_hash, note_bank)
            if hk is not None:
                e = rd.hot
                if e is None or e.key is None:
                    e = hk.track(rd.stem)
                    rd.hot = e
                e.hits += hits_addend
                hk_observed += hits_addend
                hot[i] = e
            if rule is prev_rule:
                prev_hits += hits_addend
            else:
                if prev_rule is not None:
                    prev_rule.stats.total_hits.add(prev_hits)
                prev_rule = rule
                prev_hits = hits_addend
            # Inline window-hit check (the overwhelmingly common case);
            # window() handles the rollover rebuild.
            win = rs.win
            if win is None or win.start != now - now % rs.divider:
                win = rs.window(now)
            if algo_id:
                # The algorithm bank's pack pieces: the stable-stem key
                # and its record.
                klen = len(stem_bytes)
                algo_tpl = record(win.algo_head, klen, win.algo_tail)
                if not rs.algo_shadow:
                    # Rule ENFORCES a non-default algorithm: route to
                    # its dedicated bank.  The host over-limit cache is
                    # skipped — these kernels refill capacity
                    # continuously, so a full-window OVER_LIMIT verdict
                    # has no valid TTL.
                    categories[i] = _CAT_ENGINE
                    if local_cache is not None:
                        keys[i] = CacheKey(
                            stem_bytes.decode("utf-8"), False, klen
                        )
                    if algo_accs is None:
                        algo_accs = {}
                    acc = algo_accs.get(algorithm)
                    if acc is None:
                        acc = algo_accs[algorithm] = ([], [], [])
                    acc[0].append(i)
                    acc[1].append(stem_bytes)
                    acc[2].append(algo_tpl)
                    continue
            if promo_entries is not None:
                stem = rd.stem
                if stem in promo_entries and promotion.contains(stem):
                    # Hot-key promotion (overload/controller.py): the
                    # sketch marked this stem a repeat offender; serve
                    # the short-TTL host decision and skip the device.
                    # Shadow rules stay non-enforcing here exactly like
                    # the host over-limit cache below.
                    categories[i] = (
                        _CAT_SKIP if rule.shadow_mode else _CAT_LOCAL
                    )
                    continue
            key_bytes = stem_bytes + win.suffix
            if local_cache is not None:
                # The one consumer of the key as text (here and in
                # _apply_decisions): built on this branch only.
                key = keys[i] = CacheKey(
                    key_bytes.decode("utf-8"), per_second, len(stem_bytes)
                )
                if local_cache.contains(key.key):
                    # Shadow rules skip the counter but never short-
                    # circuit to OVER_LIMIT (fixed_cache_impl.go:57-67).
                    categories[i] = (
                        _CAT_SKIP if rule.shadow_mode else _CAT_LOCAL
                    )
                    continue
            categories[i] = _CAT_ENGINE
            if algo_id:
                # Shadow rollout: the candidate kernel evaluates the
                # same descriptor on its own bank while fixed-window
                # enforcement proceeds below; divergence is tallied
                # after both complete (_note_shadow_outcomes).
                if shadow_accs is None:
                    shadow_accs = {}
                    shadow_rows = []
                    raw_over = [False] * n
                    cand_over = [None] * n
                    cand_code = [None] * n
                sa = shadow_accs.get(algorithm)
                if sa is None:
                    sa = shadow_accs[algorithm] = ([], [], [])
                sa[0].append(i)
                sa[1].append(stem_bytes)
                sa[2].append(algo_tpl)
                shadow_rows.append((i, algorithm, algo_id))
            tpl = record(win.head, len(key_bytes), win.tail)
            if single_bank:
                add_row(i)
                add_enc(key_bytes)
                add_tpl(tpl)
                continue
            if ps_bank is not None and per_second:
                bank = ps_bank
            else:
                bank = banks[rd.lane(n_lanes)]
            bank[0].append(i)
            bank[1].append(key_bytes)
            bank[2].append(tpl)
        if prev_rule is not None:
            prev_rule.stats.total_hits.add(prev_hits)
        if resolution_hits:
            resolver.hits += resolution_hits
        if hk_observed:
            hk.observed += hk_observed

        if overrides is not None:
            self._route_overrides(
                overrides,
                request,
                config,
                limits,
                is_unlimited,
                keys,
                categories,
                banks,
                ps_bank,
                hits_addend,
                hits_clamped,
                now,
            )

        statuses: List[Optional[DescriptorStatus]] = [None] * n
        items: List[tuple] = []  # (bank, engine, WorkItem)
        for bank_idx in range(n_lanes):
            rows, enc, tparts = banks[bank_idx]
            if rows:
                items.append(
                    (
                        bank_idx,
                        self.lanes[bank_idx],
                        self._make_packed_item(
                            rows, keys, limits, hits_addend, now, statuses,
                            enc, tparts, raw_over,
                        ),
                    )
                )
        if ps_bank is not None and ps_bank[0]:
            rows, enc, tparts = ps_bank
            items.append(
                (
                    n_lanes,
                    self.per_second_engine,
                    self._make_packed_item(
                        rows, keys, limits, hits_addend, now, statuses,
                        enc, tparts, raw_over,
                    ),
                )
            )
        if algo_accs is not None:
            # Enforcing algorithm banks: normal items — statuses/stats
            # assemble exactly like lane items, from the generic
            # engine's decide.
            for name, (rows, enc, tparts) in algo_accs.items():
                items.append(
                    (
                        self._algo_bank_index[name],
                        self.algorithm_banks[name],
                        self._make_packed_item(
                            rows, keys, limits, hits_addend, now, statuses,
                            enc, tparts, raw_over,
                        ),
                    )
                )
        if shadow_accs is not None:
            # Shadow candidates: side-channel items that record the
            # candidate kernel's would-be outcome and touch NOTHING
            # else (no statuses, no rule stats, no local cache).
            for name, (rows, enc, tparts) in shadow_accs.items():
                items.append(
                    (
                        self._algo_bank_index[name],
                        self.algorithm_banks[name],
                        self._make_candidate_item(
                            rows, hits_addend, now, enc, tparts,
                            cand_over, cand_code,
                        ),
                    )
                )
        shadow_info = (
            (shadow_rows, raw_over, cand_over, cand_code)
            if shadow_rows
            else None
        )
        return (
            items, statuses, categories, keys, limits, is_unlimited,
            hits_addend, now, hot, shadow_info,
        )

    def _route_overrides(
        self,
        overrides: List[int],
        request: RateLimitRequest,
        config,
        limits,
        is_unlimited,
        keys,
        categories,
        banks,
        ps_bank,
        hits_addend: int,
        hits_clamped: int,
        now: int,
    ) -> None:
        """Uncached leg for request-supplied override descriptors: the
        legacy get_limit + key-generator pipeline, routed into the same
        per-bank accumulators as the fast path (same stem hash, so an
        override and its configured twin share a lane)."""
        n_lanes = len(self.lanes)
        local_cache = self.local_cache
        scratch = np.empty(1, dtype=LANE_DTYPE)
        expiry_by_unit: dict = {}
        for i in overrides:
            desc = request.descriptors[i]
            rule = config.get_limit(request.domain, desc)
            if rule is not None and rule.unlimited:
                is_unlimited[i] = True
                continue
            limits[i] = rule
            key = self.key_generator.generate(request.domain, desc, rule, now)
            keys[i] = key
            if key.key == "":
                continue
            rule.stats.total_hits.add(hits_addend)
            if local_cache is not None and local_cache.contains(key.key):
                categories[i] = _CAT_SKIP if rule.shadow_mode else _CAT_LOCAL
                continue
            categories[i] = _CAT_ENGINE
            b = key.key.encode("utf-8")
            if ps_bank is not None and key.per_second:
                bank = ps_bank
            elif n_lanes == 1:
                bank = banks[0]
            else:
                stem = b[: key.stem_blen] if key.stem_blen else b
                bank = banks[crc32(stem) % n_lanes]
            unit = rule.limit.unit
            e = expiry_by_unit.get(unit)
            if e is None:
                e = expiry_by_unit[unit] = window_start(
                    now, unit
                ) + unit_to_divider(unit)
            scratch[0] = (
                e,
                hits_clamped,
                rule.limit.requests_per_unit,
                len(b),
                1 if rule.shadow_mode else 0,
                0,  # divider: overrides always enforce fixed-window
                0,  # algo: fixed_window
            )
            bank[0].append(i)
            bank[1].append(b)
            bank[2].append(scratch.tobytes())

    def _make_dispatcher(self, engine, name: str) -> BatchDispatcher:
        """One dispatcher with THE serving parameters — construction
        and warm-restart (fault_domain._try_restart) must agree."""
        return BatchDispatcher(
            engine,
            self._batch_window_us,
            self._batch_limit,
            name=name,
            pipeline_depth=self._pipeline_depth,
            unhealthy_after=self._unhealthy_after,
            stamp_clock=self._stamp_clock,
            cpu_clock=self._thread_clocks,
        )

    def attach_launch_recorder(self, recorder) -> None:
        """Wire the launch flight recorder into every bank dispatcher
        and the fault domain's fallback path (runner.start; _swap_bank
        re-applies it to warm-restarted dispatchers)."""
        self.launches = recorder
        if self.fault_domain is not None:
            self.fault_domain.launches = recorder
        self._wire_launch_recorder()

    def _bank_algo_id(self, bank: int) -> int:
        """models/registry algo_id serving at `bank` (engines() order):
        counter lanes and the per-second bank run fixed-window models;
        algorithm banks carry their registry id."""
        n_base = len(self.lanes) + (
            1 if self.per_second_engine is not None else 0
        )
        if bank < n_base:
            return 0
        return ALGORITHMS[self._algo_order[bank - n_base]].algo_id

    def _wire_launch_recorder(self) -> None:
        """Point every live dispatcher at the recorder with its bank's
        identity (stamped into each launch record)."""
        for bank, eng in enumerate(self.engines()):
            d = self._dispatchers.get(id(eng))
            if d is not None:
                d.launch_bank = bank
                d.launch_algo = self._bank_algo_id(bank)
                d.launches = self.launches

    def _swap_bank(self, bank: int, new_engine, new_dispatcher) -> None:
        """Install a warm-restarted engine + dispatcher at `bank`
        (called by the fault-domain supervisor with the bank's
        fallback lock held).  Bank indices and labels are stable; the
        batch-shape histograms and the health binding carry over to
        the new dispatcher; the old (dead) dispatcher leaves the
        routing dict so stale submissions fast-fail."""
        old = self.engines()[bank]
        n_lanes = len(self.lanes)
        if bank < n_lanes:
            self.lanes[bank] = new_engine
            if bank == 0:
                self.engine = new_engine
        elif self.per_second_engine is not None and bank == n_lanes:
            self.per_second_engine = new_engine
        else:
            base = n_lanes + (1 if self.per_second_engine is not None else 0)
            name = self._algo_order[bank - base]
            self.algorithm_banks[name] = new_engine
        old_d = self._dispatchers.pop(id(old), None)
        self._inline_locks[id(new_engine)] = threading.Lock()
        if old_d is not None:
            new_dispatcher.batch_lanes_hist = old_d.batch_lanes_hist
            new_dispatcher.batch_items_hist = old_d.batch_items_hist
        if self.launches is not None:
            new_dispatcher.launches = self.launches
            new_dispatcher.launch_bank = bank
            new_dispatcher.launch_algo = self._bank_algo_id(bank)
        self._dispatchers[id(new_engine)] = new_dispatcher
        if self._health_hook is not None:
            states, states_lock, make_on_state = self._health_hook
            with states_lock:
                if old_d is not None:
                    states.pop(id(old_d), None)
                states[id(new_dispatcher)] = True
            new_dispatcher.on_state = make_on_state(id(new_dispatcher))

    def do_limit(
        self,
        request: RateLimitRequest,
        limits: Sequence[Optional[RateLimitRule]],
    ) -> List[DescriptorStatus]:
        items, statuses, categories, keys, hits_addend, now = self._prepare(
            request, limits
        )
        return self._execute(
            limits, items, statuses, categories, hits_addend, now,
            len(request.descriptors),
            deadline=request.deadline,
            request=request,
        )

    def do_limit_resolved(self, request: RateLimitRequest, config):
        """The descriptor-resolution fast path: the service hands the
        whole request + its config snapshot here; rule lookup rides the
        resolution cache and the response legs come back together.

        Returns (statuses, limits, is_unlimited) — the same values the
        service's legacy _construct_limits_to_check + do_limit pair
        produces, decision-identical."""
        (
            items,
            statuses,
            categories,
            keys,
            limits,
            is_unlimited,
            hits_addend,
            now,
            hot,
            shadow_info,
        ) = self._prepare_resolved(request, config)
        statuses = self._execute(
            limits, items, statuses, categories, hits_addend, now,
            len(request.descriptors),
            deadline=request.deadline,
            request=request,
        )
        if hot is not None:
            self._note_hotkey_outcomes(hot, statuses, limits, hits_addend)
        if shadow_info is not None:
            self._note_shadow_outcomes(*shadow_info)
        return statuses, limits, is_unlimited

    def _note_shadow_outcomes(
        self, shadow_rows, raw_over, cand_over, cand_code
    ) -> None:
        """Tally shadow-rollout divergence: for every shadowed
        descriptor that reached the engines, compare the candidate
        kernel's would-be over-ness against the enforced fixed-window
        one (both PRE-shadow_mode, so a rule that also suppresses
        OVER_LIMIT responses still measures real algorithm
        divergence), bump the per-algorithm agree/diverge counters,
        and deposit the first candidate's (code, algo) into the
        flight-recorder note so the ring record carries BOTH codes."""
        counts = self._shadow_counts
        fl = self.flight
        noted = fl is None
        for i, name, algo_id in shadow_rows:
            co = cand_over[i]
            if co is None:
                continue  # candidate never evaluated (shouldn't happen)
            pair = counts[name]
            if co == raw_over[i]:
                pair[0] += 1
            else:
                pair[1] += 1
            if not noted:
                noted = True
                fl.note_shadow(int(cand_code[i]), algo_id)

    def _note_hotkey_outcomes(
        self, hot, statuses, limits, hits_addend: int
    ) -> None:
        """Fold this request's decisions into its hot-key entries:
        over-limit hits by status code, near-limit hits by the decide
        threshold (``after > floor(limit * near_ratio)``, recovered
        from limit_remaining for OK statuses).  Request-granular — a
        hits_addend spanning the threshold attributes wholly, which is
        exact enough for a sketch whose estimates already carry error
        bounds.  Lock-free bumps; see observability/hotkeys.py."""
        ratio = self._near_ratio
        over = Code.OVER_LIMIT
        for i, e in enumerate(hot):
            if e is None:
                continue
            st = statuses[i]
            if st.code is over:
                e.over_limit += hits_addend
            else:
                lim = st.current_limit
                if lim is not None:
                    rpu = lim.requests_per_unit
                    # after > limit * ratio (float compare; matches the
                    # decide threshold for every practically reachable
                    # limit — exactness to the device's float32 floor
                    # is not a sketch property).
                    if rpu - st.limit_remaining > rpu * ratio:
                        e.near_limit += hits_addend

    def _bank_label(self, bank: int) -> str:
        """Trace label for a bank index past the static table (override
        banks): format once, memoize, so the submit loop in _execute
        never builds a string per iteration (tpu-lint hot-path-cost)."""
        label = self._extra_bank_labels.get(bank)
        if label is None:
            label = self._extra_bank_labels[bank] = f"bank{bank}"  # tpu-lint: disable=shared-state -- GIL-atomic memo write; two threads formatting the same index is benign
        return label

    def _execute(
        self,
        limits,
        prep_items,
        statuses,
        categories,
        hits_addend: int,
        now: int,
        n: int,
        deadline: Optional[float] = None,
        request: Optional[RateLimitRequest] = None,
    ) -> List[DescriptorStatus]:
        """The device half: submit every bank's WorkItem, wait —
        bounded by the dispatch timeout and the caller's remaining RPC
        deadline (`deadline`, absolute time.monotonic seconds) — then
        fill the non-engine categories.  `request`, when given, takes
        the request's legs away with it (`RateLimitRequest.legs`).

        Quarantined banks never reach the device: their items answer
        from the DEVICE_FAILURE_MODE fallback (fault_domain
        .run_fallback).  A wait that finds its bank's device call
        stuck past KERNEL_DEADLINE_S records a hang fault
        (quarantining the bank) and answers the same way; a wait cut
        short by the CALLER's deadline answers per the failure mode
        WITHOUT faulting the bank.  With no fault domain
        (kernel_deadline_s=0) device errors raise CacheError exactly
        as before."""
        n_lanes = len(self.lanes)
        # When this request's trace is recording, the items' always-on
        # stamps (submit here; launched/signal on the dispatcher
        # threads; woke in wait()) become spans after the waits — see
        # _record_item_spans.
        span = TRACER.current()
        fd = self.fault_domain
        # One thread-local read per REQUEST (not per item): the launch
        # recorder joins a slow launch back to the request rings via
        # the submitting thread's sticky correlation id.  0 when either
        # ring is off — items then keep corr=0 and no store happens.
        req_corr = (
            self.flight.current_corr()
            if self.launches is not None and self.flight is not None
            else 0
        )
        pending: List[tuple] = []  # (bank, dispatcher|None, item) to wait on
        done: List[WorkItem] = []  # answered items (events recyclable)
        # Hot-loop hoist (tpu-lint hot-path-cost): the bound method
        # once, not one attribute probe per answered item.
        done_append = done.append
        inline: List[tuple] = []
        # Submit all banks first, then wait: the banks' device steps
        # overlap (the reference likewise pipelines both Redis clients
        # before the first PipeDo, fixed_cache_impl.go:77-95).
        for bank, engine, item in prep_items:
            if fd is not None:
                if fd.is_quarantined(bank) and fd.run_fallback(bank, item):
                    self._note_fallback()
                    done_append(item)
                    continue
                engine = fd.engine_at(bank)  # swap-safe resolve
            d = self._dispatchers.get(id(engine))
            if d is None:
                inline.append((bank, engine, item))
                continue
            if req_corr:
                item.corr = req_corr
            try:
                d.submit(item)
            except Exception as e:
                if fd is None:
                    # Dead dispatcher: fail THIS rpc immediately (the
                    # reference's RedisError-on-dead-pool analog) —
                    # never burn the wait timeout.
                    raise _engine_failure(e) from e
                from .fault_domain import classify_fault

                fd.record_fault(bank, classify_fault(e), e)
                done_append(self._fall_back(fd, bank, item, deadline))
                continue
            pending.append((bank, d, item))
        for bank, engine, item in inline:
            item.submit_ns = time.monotonic_ns()
            with self._inline_locks[id(engine)]:
                run_items(engine, [item])
            pending.append((bank, None, item))
        # prepare_ms ends here: everything this request sends to a
        # device bank is queued.
        submitted_ns = time.monotonic_ns()
        for bank, d, item in pending:
            timeout = self.dispatch_timeout_s
            caller_bound = False
            if deadline is not None:
                remaining = deadline - time.monotonic()
                if remaining < timeout:
                    timeout = max(0.0, remaining)
                    caller_bound = True
            try:
                if fd is not None and d is not None:
                    # Watched wait: wake every KERNEL_DEADLINE_S and
                    # ask the dispatcher whether its device call is
                    # stuck, so the first request to hit a hang also
                    # reports it.  A slow but MOVING bank (compiling a
                    # new shape, deep queue, snapshot on the collector)
                    # is not a hang: it keeps the dispatch timeout.
                    self._wait_watched(item, d, timeout, fd)
                else:
                    item.wait(timeout)
            except TimeoutError as e:
                from .fault_domain import FAULT_HANG, KernelDeadlineExceeded

                if caller_bound and not isinstance(e, KernelDeadlineExceeded):
                    # The CALLER's deadline expired first: answer per
                    # DEVICE_FAILURE_MODE without faulting the bank —
                    # it may be healthy, just slower than this RPC can
                    # wait (mirrors the cluster retry discipline,
                    # test_retry_never_sleeps_past_caller_deadline).
                    done_append(self._answer_failure_mode(item))
                    continue
                if fd is not None:
                    fd.record_fault(bank, FAULT_HANG, e)
                    done_append(self._fall_back(fd, bank, item, deadline))
                    continue
                raise _engine_failure(e) from e
            except Exception as e:
                if fd is not None:
                    from .fault_domain import classify_fault

                    fd.record_fault(bank, classify_fault(e), e)
                    done_append(self._fall_back(fd, bank, item, deadline))
                    continue
                raise _engine_failure(e) from e
            done_append(item)
        # All answered items' events are settled: the completers' (or
        # fallback path's) set() calls happened-before here and
        # nothing touches these events again, so they are safe to
        # clear and recycle (see _event_pool).  Timed-out originals
        # were replaced by clones and keep their events out of the
        # pool — a stuck completer may still signal them later.
        pool = self._event_pool
        if len(pool) < 1024:
            for item in done:
                item.event.clear()
                # Plain-list append/EAFP-pop are each one GIL-atomic
                # op (no check-then-act; see _pool_event); the 1024
                # bound is advisory — an overshoot wastes an Event.
                pool.append(item.event)  # tpu-lint: disable=shared-state -- GIL-atomic list ops; pop is EAFP in _pool_event
        if request is not None:
            # The request's legs for the handler's histograms
            # (ServerReporter.observe_legs): everything queued, then —
            # of the item whose launch was signalled LAST, the one the
            # answer waited for — the completer's signal and the
            # moment this thread was running again.  0, 0 where no
            # item rode a launch (fallback answers, cache-only
            # requests).
            signal_ns = woke_ns = 0
            for _bank, _engine, item in prep_items:
                stamps = item.launch
                if stamps is not None and stamps.signal_ns > signal_ns:
                    signal_ns, woke_ns = stamps.signal_ns, item.woke_ns
            request.legs = (submitted_ns, signal_ns, woke_ns)
            request.launched = bool(prep_items)
        if span is not None:
            self._record_item_spans(span, prep_items)

        # Non-engine categories.
        reset_cache: dict = {}
        n_local = 0
        for i in range(n):
            if statuses[i] is not None:
                continue
            rule = limits[i]
            cat = categories[i]
            if cat == _CAT_NONE:
                # No matching rule (base_limiter.go:78-81).
                statuses[i] = DescriptorStatus(code=Code.OK)
                continue
            duration = self._reset_seconds(rule, now, reset_cache)
            n_local += 1
            if cat == _CAT_LOCAL:
                rule.stats.over_limit.add(hits_addend)
                rule.stats.over_limit_with_local_cache.add(hits_addend)
                statuses[i] = DescriptorStatus(
                    code=Code.OVER_LIMIT,
                    current_limit=rule.limit,
                    limit_remaining=0,
                    duration_until_reset=duration,
                )
            else:  # _CAT_SKIP: shadow + cached over-limit -> plain OK
                rule.stats.within_limit.add(hits_addend)
                statuses[i] = DescriptorStatus(
                    code=Code.OK,
                    current_limit=rule.limit,
                    limit_remaining=rule.limit.requests_per_unit,
                    duration_until_reset=duration,
                )
        self.stat_local_decisions.add(n_local)  # tpu-lint: disable=shared-state -- stats.manager.Counter: add() takes the counter's own lock
        if not prep_items:
            self.stat_requests_no_launch.inc()
        return statuses  # type: ignore[return-value]

    def _fall_back(
        self, fd, bank: int, item: WorkItem, deadline: Optional[float]
    ) -> WorkItem:
        """Answer a just-faulted bank's item from the failure-mode
        fallback, on a clone (the original's event may still be
        signalled by a stuck completer); returns the answered clone.

        Should the bank have been re-admitted while this request
        queued for the mirror, the restarted device bank gets ONE try
        under the same bounds as the main wait — the caller's
        `deadline`, the dispatch timeout, the watchdog — and a fault
        there is recorded like any other.  Whatever then remains
        unanswered is answered per DEVICE_FAILURE_MODE: nothing
        escapes from here as an error."""
        clone = self._clone_item(item)
        if fd.run_fallback(bank, clone):
            self._note_fallback()
            return clone
        d = self._dispatchers.get(id(fd.engine_at(bank)))
        timeout = self.dispatch_timeout_s
        caller_bound = False
        if deadline is not None:
            remaining = deadline - time.monotonic()
            if remaining < timeout:
                timeout = max(0.0, remaining)
                caller_bound = True
        try:
            if d is None:  # a restart always installs a dispatcher
                raise RuntimeError(f"bank {bank} re-admitted, no dispatcher")
            d.submit(clone)
            self._wait_watched(clone, d, timeout, fd)
            return clone
        except Exception as e:
            from .fault_domain import KernelDeadlineExceeded, classify_fault

            caller_expired = (
                caller_bound
                and isinstance(e, TimeoutError)
                and not isinstance(e, KernelDeadlineExceeded)
            )
            if not caller_expired:  # the bank's fault, not the caller's hurry
                fd.record_fault(bank, classify_fault(e), e)
                again = self._clone_item(item)
                if fd.run_fallback(bank, again):
                    self._note_fallback()
                    return again
        return self._answer_failure_mode(item)

    @staticmethod
    def _wait_watched(item: WorkItem, d, timeout: float, fd) -> None:
        """``item.wait(timeout)`` that also watches dispatcher `d`:
        raises KernelDeadlineExceeded as soon as d's in-progress
        device call has been stuck past the kernel deadline
        (BatchDispatcher.stuck_age — the watchdog's own test)."""
        kd = fd.kernel_deadline_s
        end = time.monotonic() + timeout
        while not item.event.wait(min(kd, max(0.0, end - time.monotonic()))):
            if time.monotonic() >= end:
                break
            stuck = d.stuck_age()
            if stuck > kd:
                raise fd.hang_error(stuck)
        item.wait(0.0)  # answered: apply / raise its error; else TimeoutError

    def bind_health(self, health) -> None:
        """Wire backend liveness into the health checker: dispatcher
        death or N consecutive device-step failures flip /healthcheck
        and grpc.health.v1 to NOT_SERVING; a later success flips back
        (the reference's Redis pool active-connection health,
        driver_impl.go:31-52 + settings.go:91-92)."""
        import logging

        log = logging.getLogger("ratelimit.health")
        self._health = health

        # Per-dispatcher health, aggregated: the service is SERVING only
        # while EVERY bank's dispatcher is healthy — one bank recovering
        # must not mask the other still being dead.
        states = {id(d): True for d in self._dispatchers.values()}
        states_lock = threading.Lock()

        def make_on_state(key: int):
            def on_state(healthy: bool, reason: str) -> None:
                fd = self.fault_domain
                if fd is not None:
                    # The fault domain owns device-path failure: the
                    # replica keeps SERVING through the failure-mode
                    # fallback, so a dead/failing dispatcher reports
                    # DEGRADED instead of NOT_SERVING (the watchdog
                    # quarantines it; the supervisor restarts it).
                    if healthy:
                        log.info("tpu backend healthy again: %s", reason)
                        if (
                            fd.quarantined_count() == 0
                            and hasattr(health, "set_degraded")
                        ):
                            health.set_degraded(False, reason)
                    else:
                        log.error("tpu backend degraded: %s", reason)
                        if hasattr(health, "set_degraded"):
                            health.set_degraded(True, reason)
                    return
                # health.ok()/fail() happen INSIDE the lock so state
                # transitions from concurrent dispatcher threads land
                # in order — a stale ok() may never overtake a newer
                # fail().
                with states_lock:
                    states[key] = healthy
                    if healthy:
                        log.info("tpu backend healthy again: %s", reason)
                        if all(states.values()):
                            health.ok()
                    else:
                        log.error("tpu backend unhealthy: %s", reason)
                        health.fail()

            return on_state

        self._health_hook = (states, states_lock, make_on_state)
        for d in self._dispatchers.values():
            d.on_state = make_on_state(id(d))

    def queue_hwm_drain(self) -> int:
        """Deepest per-tick intake drain across every bank's
        dispatcher, reset on read — the queue-saturation detector's
        input (observability/detectors.py)."""
        return max(
            (d.queue_hwm_drain() for d in self._dispatchers.values()),
            default=0,
        )

    def flush(self) -> None:
        """Drain the dispatcher queues (deterministic test hook; the
        reference's memcached Flush analog, cache_impl.go:176-178;
        the graceful-drain leg of runner.stop).  Dead (quarantined)
        dispatchers are skipped — their queues were already
        fast-failed into the fallback."""
        for d in list(self._dispatchers.values()):
            if d.dead is not None:
                continue
            d.flush()

    def close(self) -> None:
        fd, self.fault_domain = self.fault_domain, None
        if fd is not None:
            fd.stop()
        dispatchers, self._dispatchers = list(self._dispatchers.values()), {}
        for d in dispatchers:
            # A dead dispatcher may have a STUCK collector/completer
            # (hang fault) that can never be joined; don't burn the
            # full join timeout on it.
            d.stop(timeout=0.5 if d.dead is not None else 10.0)

    # Batch-size histogram ladder: powers of two up to the default
    # batch limit (these histograms count lanes/items, not ms).
    _BATCH_BOUNDS = tuple(float(1 << i) for i in range(13))

    def register_stats(self, store, scope: str = "ratelimit.tpu") -> None:
        """Live gauges for each bank (slot-table occupancy/evictions/
        fill, dispatcher queue depth + high-water marks, in-flight
        launches, batch-shape histograms, window rollovers) — the
        analog of the reference's redis pool gauges
        (driver_impl.go:17-29) — plus the resolution/stem cache
        counters and the hot-key sketch family, so a key-cardinality
        blowup (clears climbing, hit rate collapsing) or an
        approaching slot-table exhaustion (fill_pct, evictions) is
        visible on /metrics instead of silent."""
        kg = self.key_generator
        store.counter_fn(scope + ".stem_cache_clears", lambda: kg.clears)
        store.gauge_fn(scope + ".stem_cache.entries", lambda: len(kg))
        res = self.resolver
        if res is not None:
            store.counter_fn(
                scope + ".resolution_cache.hits", lambda: res.hits
            )
            store.counter_fn(
                scope + ".resolution_cache.misses", lambda: res.misses
            )
            store.counter_fn(
                scope + ".resolution_cache.clears", lambda: res.clears
            )
            store.counter_fn(
                scope + ".resolution_cache.evictions", lambda: res.evictions
            )
            store.gauge_fn(
                scope + ".resolution_cache.entries", lambda: len(res)
            )
        if self.hotkeys is not None:
            self.hotkeys.register_stats(store, scope + ".hotkeys")
        # Cluster handoff family (fixed literal scope: these are
        # cluster-tier counters, not backend-tier — the name the
        # INCIDENT_RUNBOOK and dashboards key on).
        self.handoff_log.register_stats(store, "ratelimit.cluster")
        # Shadow-rollout divergence family (docs/ALGORITHMS.md): one
        # agree/diverge counter pair per configured algorithm bank —
        # bounded by the algorithm table, not by traffic.
        for name in self._algo_order:
            pair = self._shadow_counts[name]
            store.counter_fn(
                scope + ".shadow." + name + ".agree", lambda p=pair: p[0]
            )
            store.counter_fn(
                scope + ".shadow." + name + ".diverge", lambda p=pair: p[1]
            )
        # What the host answered alone (_execute): with
        # ShouldRateLimit.descriptors and response_ms's count they give
        # the share of descriptors and of requests no launch carried.
        store.counter_fn(
            scope + ".local_decisions", self.stat_local_decisions.value
        )
        store.counter_fn(
            scope + ".requests_no_launch", self.stat_requests_no_launch.value
        )
        # Fault-domain family + the caller-deadline answer counter
        # (the latter exists even without a domain — the deadline path
        # answers per DEVICE_FAILURE_MODE regardless).
        store.counter_fn(
            scope + ".fault.deadline_answers",
            lambda: self.stat_deadline_answers,
        )
        if self.fault_domain is not None:
            self.fault_domain.register_stats(store, scope + ".fault")
        for idx, engine in enumerate(self.engines()):
            base = f"{scope}.bank{idx}"
            # Closures resolve the engine BY INDEX per scrape
            # (self._engine_at): a supervised warm restart replaces
            # the engine object, and the gauges must follow.
            register_slot_stats(
                store, base, lambda i=idx: self._engine_at(i)
            )
            # Over ratelimit.tpu.launch.rate: the share of launches
            # whose device trip the hand-off hid (engine.py).
            store.counter_fn(
                base + ".readback_ready",
                lambda i=idx: self._engine_at(i).stat_readback_ready,
            )
            d = self._dispatchers.get(id(engine))
            if d is not None:
                store.gauge_fn(
                    base + ".dispatch_queue",
                    lambda i=idx: self._disp_stat(i, "queue_depth"),
                )
                store.gauge_fn(
                    base + ".dispatch_queue_hwm",
                    lambda i=idx: self._disp_stat(i, "queue_depth_hwm"),
                )
                store.gauge_fn(
                    base + ".inflight_launches",
                    lambda i=idx: self._disp_stat(i, "inflight"),
                )
                store.gauge_fn(
                    base + ".inflight_hwm",
                    lambda i=idx: self._disp_stat(i, "inflight_hwm"),
                )
                # Batch-shape histograms, observed once per launch on
                # the collector thread (dispatcher._launch): lanes per
                # device batch and work items per batch — the data for
                # tuning TPU_BATCH_WINDOW_US / TPU_BATCH_LIMIT /
                # TPU_NUM_LANES from dashboards.
                d.batch_lanes_hist = store.histogram(
                    base + ".batch_lanes", self._BATCH_BOUNDS
                )
                d.batch_items_hist = store.histogram(
                    base + ".batch_items", self._BATCH_BOUNDS
                )

    def _engine_at(self, idx: int):
        """Swap-safe engine accessor for scrape closures: a warm
        restart replaces the engine OBJECT at a bank; index-based
        reads follow the replacement."""
        return self.engines()[idx]

    def _disp_stat(self, idx: int, method: str) -> int:
        """Swap-safe dispatcher gauge read; 0 while a bank is between
        dispatchers (quarantined, mid-restart)."""
        d = self._dispatchers.get(id(self.engines()[idx]))
        return 0 if d is None else getattr(d, method)()

    def engines(self):
        """All live counter banks: lanes first in lane order, then the
        per-second bank, then the algorithm banks in sorted-name order
        (checkpoint surface; bank indices must be stable across
        restarts — a changed TPU_NUM_LANES restores keys into the
        wrong lane, where they age out via gc while their counters
        restart, the same amnesia envelope as a cluster membership
        change; checkpoint roles additionally pin each algorithm
        bank's name)."""
        out = list(self.lanes)
        if self.per_second_engine is not None:
            out.append(self.per_second_engine)
        out.extend(self.algorithm_banks[n] for n in self._algo_order)
        return out

    def run_exclusive(self, engine, fn) -> None:
        """Run `fn()` with exclusive access to `engine`'s slot table
        and counts: on the dispatcher thread when batching is on,
        under the inline lock otherwise."""
        d = self._dispatchers.get(id(engine))
        if d is not None:
            d.run_on_thread(fn)
        else:
            with self._inline_locks[id(engine)]:
                fn()

    def warmup(self) -> None:
        """Pre-compile every (bucket, readback-dtype) kernel shape so
        the first real RPC never pays XLA compilation.  Call before
        serving starts — it steps the engines directly, from this
        thread, so the fault domain's supervisor is paused meanwhile:
        a step donates the counts, and a snapshot due in that moment
        (the first falls due one watchdog tick after construction)
        read them donated — `Array has been deleted` -> exception
        fault -> restart (witnessed on the chip: PERF.md section 6,
        PR 26).  Nothing is served yet, so nothing goes unwatched."""
        fd = self.fault_domain
        if fd is not None:
            fd.stop()
        try:
            for engine in self.engines():
                warmup_engine(engine)
        finally:
            if fd is not None:
                fd.start()

    # -- internals -------------------------------------------------------

    def _clone_item(self, item: WorkItem) -> WorkItem:
        """A fallback twin of `item`: same pack and apply closure, but
        a FRESH event — the original's may still be signalled later by
        a stuck completer, and a recycled event that fires twice would
        corrupt a later request."""
        return WorkItem(
            now=item.now,
            lanes=(),
            pack=item.get_pack(),
            apply=item.apply,
            defer_apply=True,
        )

    def _answer_failure_mode(self, item: WorkItem) -> WorkItem:
        """Caller-deadline expiry on a HEALTHY (just slow) bank:
        synthesize the DEVICE_FAILURE_MODE answer — deny answers
        OVER_LIMIT, allow (and host, which has no mirror to consult
        outside quarantine) answers OK — with zero stat deltas."""
        from .host_engine import STATIC_ALLOW, STATIC_DENY

        clone = self._clone_item(item)
        eng = (
            STATIC_DENY if self.device_failure_mode == "deny" else STATIC_ALLOW
        )
        run_items(eng, [clone])
        clone.wait(5.0)
        self.stat_deadline_answers += 1  # tpu-lint: disable=shared-state -- GIL-atomic stats counter, scrape-only reader
        self._note_fallback()
        return clone

    def _note_fallback(self) -> None:
        """Mark this thread's in-flight request as fallback-answered:
        its flight-ring record stamps FLIGHT_CODE_FALLBACK."""
        fl = self.flight
        if fl is not None:
            fl.note_fallback()

    def _record_item_spans(self, span, prep_items) -> None:
        """Turn each item's always-on stamps (submit, launched, signal,
        woke — time.monotonic_ns, see dispatcher.LaunchStamps) into
        child spans — ``backend.dispatch`` (intake queue + collect +
        batch assembly, host-side), ``kernel.step`` (device launch
        through readback+decide) and ``wake`` (the completer's signal
        until this thread ran again) — on the waiting RPC thread, after
        the completion event's happens-before edge made the dispatcher
        threads' stamps visible.  Failed steps leave stamps at 0;
        record what exists."""
        # Spans live on perf_counter; the stamps are CLOCK_MONOTONIC.
        # One offset, read here, moves them across.
        off = time.perf_counter() - time.monotonic_ns() * 1e-9
        labels = self._bank_labels
        record_span = TRACER.record_span
        for bank, _engine, item in prep_items:
            stamps = item.launch
            if stamps is None or not stamps.launched_ns or not item.submit_ns:
                continue
            attrs = {
                # Banks past the static label table (override banks)
                # format their label in _bank_label.
                "bank": (
                    labels[bank]
                    if bank < len(labels)
                    else self._bank_label(bank)
                ),
                "lanes": item.n_lanes,
            }
            launched = stamps.launched_ns * 1e-9 + off
            record_span(
                "backend.dispatch",
                item.submit_ns * 1e-9 + off,
                launched,
                attrs=attrs,
                parent=span,
            )
            if stamps.signal_ns:
                signal = stamps.signal_ns * 1e-9 + off
                record_span(
                    "kernel.step", launched, signal, attrs=attrs, parent=span
                )
                if item.woke_ns:
                    record_span(
                        "wake",
                        signal,
                        item.woke_ns * 1e-9 + off,
                        attrs=attrs,
                        parent=span,
                    )

    def _make_item(
        self,
        rows: List[int],
        keys,
        limits,
        hits_addend: int,
        now: int,
        statuses: List[Optional[DescriptorStatus]],
        enc_keys: Optional[List[Optional[bytes]]] = None,
    ) -> WorkItem:
        """Pack this request's engine-bound lanes into arrays HERE, on
        the RPC thread: the dispatcher's serial collector then only
        concatenates packs (dispatcher.submit_items), so per-lane
        Python cost parallelizes across RPC handler threads instead of
        bottlenecking the device queue.  (The resolution fast path
        skips this entirely — _make_packed_item joins pre-serialized
        template records instead.)"""
        n_rows = len(rows)
        jitters = self._draw_jitters(rows)
        enc: List[bytes] = []
        hits_clamped = min(hits_addend, 0xFFFFFFFF)
        expiry_by_unit: dict = {}
        meta = np.empty(n_rows, dtype=LANE_DTYPE)
        for j, i in enumerate(rows):
            rule = limits[i]
            unit = rule.limit.unit
            e = expiry_by_unit.get(unit)
            if e is None:
                e = expiry_by_unit[unit] = window_start(
                    now, unit
                ) + unit_to_divider(unit)
            # Multi-lane routing already encoded the key; reuse it.
            b = (
                enc_keys[i]
                if enc_keys is not None and enc_keys[i] is not None
                else keys[i].key.encode("utf-8")
            )
            enc.append(b)
            meta[j] = (
                e,
                0,  # hits stamped for all rows below
                rule.limit.requests_per_unit,
                len(b),
                1 if rule.shadow_mode else 0,
                0,  # divider: legacy path serves fixed-window only
                0,  # algo: fixed_window
            )
        meta["hits"] = hits_clamped
        if jitters is not None:
            meta["expiry"] += np.asarray(jitters, dtype=np.int64)
        pack = LanePack(key_blob=b"".join(enc), meta=meta)
        return self._finish_item(
            rows, keys, limits, hits_addend, now, statuses, pack
        )

    def _pool_event(self) -> threading.Event:
        """One recycled (or fresh) Event.  EAFP on purpose: the old
        ``pool.pop() if pool else Event()`` raced — a concurrent RPC
        thread could drain the last entry between the truthiness check
        and the pop, turning a hot-path request into an IndexError
        (tests/test_unique_fastpath.py pins the empty-looking-pool
        case)."""
        try:
            return self._event_pool.pop()
        except IndexError:
            return threading.Event()

    def _make_packed_item(
        self,
        rows: List[int],
        keys,
        limits,
        hits_addend: int,
        now: int,
        statuses: List[Optional[DescriptorStatus]],
        enc: List[bytes],
        tparts: List[bytes],
        raw_over: Optional[list] = None,
    ) -> WorkItem:
        """Resolution-fast-path packer: the per-bank accumulators
        already hold the memoized key bytes and 24-byte template
        records, so the pack is two joins and two zero-copy views.
        Templates pre-stamp hits=1 (the common addend; override rows
        wrote the real value), so the field write is only paid when a
        request carries a different addend."""
        buf = bytearray(b"".join(tparts))
        meta = np.frombuffer(buf, dtype=LANE_DTYPE)
        # Both views share `buf`; handing meta_u8 to LanePack skips
        # its view()+safety-check construction cost.
        meta_u8 = np.frombuffer(buf, dtype=np.uint8)
        hits_clamped = min(hits_addend, 0xFFFFFFFF)
        if hits_clamped != 1:
            meta["hits"] = hits_clamped
        jitters = self._draw_jitters(rows)
        if jitters is not None:
            meta["expiry"] += np.asarray(jitters, dtype=np.int64)
        pack = LanePack(key_blob=b"".join(enc), meta=meta, meta_u8=meta_u8)
        return self._finish_item(
            rows, keys, limits, hits_addend, now, statuses, pack, raw_over
        )

    def _draw_jitters(self, rows) -> Optional[List[int]]:
        if self.expiration_jitter_max_seconds <= 0:
            return None
        # Spread slot reclamation like the reference spreads Redis
        # TTLs (fixed_cache_impl.go:71-74); one lock acquisition
        # per request, not per lane.
        with self._jitter_lock:
            return [
                self.jitter_rand.randrange(self.expiration_jitter_max_seconds)
                for _ in rows
            ]

    def _make_candidate_item(
        self,
        rows: List[int],
        hits_addend: int,
        now: int,
        enc: List[bytes],
        tparts: List[bytes],
        cand_over: list,
        cand_code: list,
    ) -> WorkItem:
        """Shadow-candidate packer: same pre-serialized template join
        as _make_packed_item, but the apply records ONLY the candidate
        kernel's would-be outcome (pre-shadow_mode over-ness + code)
        into the request-local side channel — no statuses, no rule
        stats, no local cache, so a shadowed rule's enforced responses
        stay byte-identical to plain fixed-window."""
        buf = bytearray(b"".join(tparts))
        meta = np.frombuffer(buf, dtype=LANE_DTYPE)
        meta_u8 = np.frombuffer(buf, dtype=np.uint8)
        hits_clamped = min(hits_addend, 0xFFFFFFFF)
        if hits_clamped != 1:
            meta["hits"] = hits_clamped
        pack = LanePack(key_blob=b"".join(enc), meta=meta, meta_u8=meta_u8)
        over_value = _OVER_VALUE

        def apply(decisions: HostDecisions) -> None:
            codes = decisions.codes.tolist()
            shadow = decisions.shadow_mode.tolist()
            for j, i in enumerate(rows):
                c = int(codes[j])
                cand_code[i] = c
                cand_over[i] = c == over_value or shadow[j] > 0

        event = self._pool_event()
        return WorkItem(
            now=now,
            lanes=(),
            pack=pack,
            apply=apply,
            defer_apply=True,
            event=event,
        )

    def _finish_item(
        self, rows, keys, limits, hits_addend, now, statuses, pack,
        raw_over: Optional[list] = None,
    ) -> WorkItem:
        def apply(decisions: HostDecisions) -> None:
            self._apply_decisions(
                rows, keys, limits, hits_addend, now, decisions, statuses,
                raw_over,
            )

        event = self._pool_event()
        # defer_apply: status assembly runs on THIS RPC thread inside
        # item.wait(), not on the dispatcher's completer — it was the
        # completer's largest serial leg (host_path.json).
        return WorkItem(
            now=now,
            lanes=(),
            pack=pack,
            apply=apply,
            defer_apply=True,
            event=event,
        )

    def _apply_decisions(
        self,
        rows: List[int],
        keys,
        limits,
        hits_addend: int,
        now: int,
        decisions: HostDecisions,
        statuses: List[Optional[DescriptorStatus]],
        raw_over: Optional[list] = None,
    ) -> None:
        # One tolist() per field up front (on THIS thread — the RPC
        # waiter under defer_apply): per-lane reads below become plain
        # list indexing on ints, ~10x cheaper than numpy scalar
        # extraction across a 4096-lane batch (host_path.json).  Stat
        # adds skip zero deltas (most lanes touch exactly one stat).
        reset_cache: dict = {}
        codes = decisions.codes.tolist()
        remaining = decisions.limit_remaining.tolist()
        over = decisions.over_limit.tolist()
        near = decisions.near_limit.tolist()
        within = decisions.within_limit.tolist()
        shadow = decisions.shadow_mode.tolist()
        set_lc = decisions.set_local_cache.tolist()
        local_cache = self.local_cache
        for j, i in enumerate(rows):
            rule = limits[i]
            stats = rule.stats
            if raw_over is not None:
                # Pre-shadow_mode over-ness, for the shadow-rollout
                # divergence comparison (_note_shadow_outcomes).
                raw_over[i] = codes[j] == _OVER_VALUE or shadow[j] > 0
            v = over[j]
            if v:
                stats.over_limit.add(int(v))
            v = near[j]
            if v:
                stats.near_limit.add(int(v))
            v = within[j]
            if v:
                stats.within_limit.add(int(v))
            v = shadow[j]
            if v:
                stats.shadow_mode.add(int(v))
            if local_cache is not None and set_lc[j]:
                local_cache.set(
                    keys[i].key, unit_to_divider(rule.limit.unit)
                )
            statuses[i] = DescriptorStatus(
                code=_CODE_BY_VALUE[int(codes[j])],
                current_limit=rule.limit,
                limit_remaining=int(remaining[j]),
                duration_until_reset=self._reset_seconds(rule, now, reset_cache),
            )

    @staticmethod
    def _reset_seconds(rule: RateLimitRule, now: int, cache: dict) -> int:
        return reset_seconds_cached(rule.limit.unit, now, cache)
