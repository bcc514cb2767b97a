"""Write-behind cache mode: decide on host, commit hits to the device
asynchronously — the memcached-backend analog (SURVEY.md row #12).

The reference's memcached mode reads current values, decides client-
side, and increments in a background goroutine pool (reference
src/memcached/cache_impl.go:58-174: GetMulti -> decide -> runAsync
increaseAsync, with Flush() as the deterministic test hook :176-178).
Its incr->add->incr race dance (:144-168) exists because memcached is
a SHARED external store: concurrent processes race on the same key.

The TPU-native inversion: each process owns its counters (the cluster
tier routes every key to exactly one owner — cluster/router.py), so
the host can fold its own in-flight hits into the decision and stay
EXACT while the device commit runs behind:

    decision basis = last device readback + pending uncommitted hits

The RPC path never waits on the device: do_limit reads/updates the
host view under a lock, answers, and enqueues the device commit on
the same micro-batching dispatcher the sync backend uses.  Device
readbacks reconcile the view (apply: device value replaces the
readback component, pending drains).  ``flush()`` drains the
dispatcher — everything enqueued before it is committed AND
reconciled after it returns (the AutoFlushForIntegrationTests
pattern, memcached/cache_impl.go:54,129-131).

Async envelope (documented deviations from the sync backend):
- Device-side slot eviction (table full) resets counters the host
  view still carries; the view reconciles at the next readback of
  that key.  Until then decisions are STRICTER (they remember hits
  the device forgave) — the safe direction for a limiter.  The
  reference's memcached mode has the mirror-image envelope (decisions
  LAG concurrent increments, over-admitting).
- Checkpoint-restore rebuilds the view from the restored slot table +
  counters (``on_restored``), so restored limits enforce immediately.
- A failed device commit drains its pending hits from the view
  (WorkItem.on_error): those hits never landed, so decisions fall
  back to the last device-confirmed values instead of permanently
  over-counting.
- The view is cardinality-capped at 4x the device table: past the
  cap, expired windows prune first, then soonest-expiring entries
  evict (the same forgiveness direction as device-table eviction).
- No per-second dual bank: the reference's memcached backend has no
  second-instance split either (that is a Redis-only feature,
  fixed_cache_impl.go:77-87); SECOND-unit limits share the one bank.
"""

from __future__ import annotations

import random
import threading
from typing import Dict, List, Optional, Sequence

import numpy as np

from ..api import Code, DescriptorStatus, RateLimitRequest
from ..config import RateLimitRule
from ..limiter.base import decide_batch
from ..limiter.cache_key import CacheKeyGenerator
from ..limiter.local_cache import LocalCache
from ..utils.time import (
    RealTimeSource,
    TimeSource,
    reset_seconds_cached,
    unit_to_divider,
    window_start,
)
from .dispatcher import LANE_DTYPE, BatchDispatcher, LanePack, WorkItem
from .engine import CounterEngine, register_slot_stats
from .tpu_cache import _CODE_BY_VALUE

# Prune the host view of expired windows every N reconciled batches.
_PRUNE_EVERY = 256


class WriteBehindRateLimitCache:
    """RateLimitCache with async device commits (memcached-mode
    latency envelope: the request path is pure host work)."""

    def __init__(
        self,
        engine: CounterEngine,
        time_source: Optional[TimeSource] = None,
        local_cache: Optional[LocalCache] = None,
        expiration_jitter_max_seconds: int = 0,
        cache_key_prefix: str = "",
        jitter_rand: Optional[random.Random] = None,
        batch_window_us: int = 200,
        batch_limit: int = 4096,
        unhealthy_after: int = 3,
        pipeline_depth: int = 2,
    ):
        self.engine = engine
        self.time_source = time_source or RealTimeSource()
        self.local_cache = local_cache
        self.key_generator = CacheKeyGenerator(cache_key_prefix)
        self.expiration_jitter_max_seconds = int(expiration_jitter_max_seconds)
        self.jitter_rand = jitter_rand or random.Random()
        self._jitter_lock = threading.Lock()

        # key -> [device_count, pending_hits, expiry].  device_count is
        # the value from the last reconciled readback; pending_hits are
        # enqueued but not yet reconciled.  Both mutate under _view_lock
        # (RPC threads on decide, the dispatcher completer on apply).
        self._view: Dict[str, list] = {}
        self._view_lock = threading.Lock()
        self._batches_reconciled = 0
        # Host-memory bound: the device table self-bounds at num_slots,
        # the host dict must too (high-cardinality DAY-unit traffic
        # would otherwise grow it for a full day).
        self._max_view_keys = max(4 * engine.model.num_slots, 1 << 14)

        # The same two-stage dispatcher as the sync backend — the only
        # difference is nobody blocks on item.wait().
        self._dispatcher = BatchDispatcher(
            engine,
            batch_window_us=max(1, batch_window_us),
            batch_limit=batch_limit,
            name="tpu-writebehind",
            pipeline_depth=pipeline_depth,
            unhealthy_after=unhealthy_after,
        )

    # -- RateLimitCache seam --------------------------------------------

    def do_limit(
        self,
        request: RateLimitRequest,
        limits: Sequence[Optional[RateLimitRule]],
    ) -> List[DescriptorStatus]:
        n = len(request.descriptors)
        assert n == len(limits)
        hits_addend = max(1, request.hits_addend)
        now = self.time_source.unix_now()

        keys = []
        for desc, rule in zip(request.descriptors, limits):
            key = self.key_generator.generate(request.domain, desc, rule, now)
            keys.append(key)
            if rule is not None and not rule.unlimited:
                rule.stats.total_hits.add(hits_addend)

        statuses: List[Optional[DescriptorStatus]] = [None] * n
        rows: List[int] = []  # engine-bound lanes
        reset_cache: dict = {}
        for i, (key, rule) in enumerate(zip(keys, limits)):
            if key.key == "":
                statuses[i] = DescriptorStatus(code=Code.OK)
                continue
            if self.local_cache is not None and self.local_cache.contains(
                key.key
            ):
                duration = self._reset_seconds(rule, now, reset_cache)
                if rule.shadow_mode:
                    # Shadow + cached over-limit: skip the counter,
                    # answer OK (fixed_cache_impl.go:57-67 semantics).
                    rule.stats.within_limit.add(hits_addend)
                    statuses[i] = DescriptorStatus(
                        code=Code.OK,
                        current_limit=rule.limit,
                        limit_remaining=rule.limit.requests_per_unit,
                        duration_until_reset=duration,
                    )
                else:
                    rule.stats.over_limit.add(hits_addend)
                    rule.stats.over_limit_with_local_cache.add(hits_addend)
                    statuses[i] = DescriptorStatus(
                        code=Code.OVER_LIMIT,
                        current_limit=rule.limit,
                        limit_remaining=0,
                        duration_until_reset=duration,
                    )
                continue
            rows.append(i)

        if not rows:
            return statuses  # type: ignore[return-value]

        m = len(rows)
        jitters = None
        if self.expiration_jitter_max_seconds > 0:
            with self._jitter_lock:
                jitters = [
                    self.jitter_rand.randrange(
                        self.expiration_jitter_max_seconds
                    )
                    for _ in rows
                ]

        befores = np.empty(m, dtype=np.int64)
        limits_arr = np.empty(m, dtype=np.int64)
        shadow_arr = np.empty(m, dtype=bool)
        enc: List[bytes] = []
        meta = np.empty(m, dtype=LANE_DTYPE)
        expiry_by_unit: dict = {}
        lane_keys: List[str] = []
        expiries: List[int] = []

        # Pass 1, lock-free: packing work (encode, expiry math, meta
        # records) parallelizes across RPC threads exactly like the
        # sync path's _make_item.
        for j, i in enumerate(rows):
            rule = limits[i]
            unit = rule.limit.unit
            e = expiry_by_unit.get(unit)
            if e is None:
                e = expiry_by_unit[unit] = window_start(
                    now, unit
                ) + unit_to_divider(unit)
            if jitters is not None:
                e += jitters[j]
            k = keys[i].key
            limits_arr[j] = rule.limit.requests_per_unit
            shadow_arr[j] = rule.shadow_mode
            b = k.encode("utf-8")
            enc.append(b)
            lane_keys.append(k)
            expiries.append(e)
            meta[j] = (e, hits_addend, limits_arr[j], len(b), 0, 0, 0)

        # Pass 2, under the lock: ONLY the decide basis + pending
        # update.  Duplicates inside the request see each other's hits
        # (pipeline-order semantics, like the sync path's prefixes).
        with self._view_lock:
            view = self._view
            for j, k in enumerate(lane_keys):
                entry = view.get(k)
                if entry is None:
                    entry = view[k] = [0, 0, expiries[j]]
                befores[j] = entry[0] + entry[1]
                entry[1] += hits_addend
            if len(view) > self._max_view_keys:
                self._shrink_view_locked(now)

        hits_arr = np.full(m, hits_addend, dtype=np.int64)
        d = decide_batch(
            limits=limits_arr,
            befores=befores,
            afters=befores + hits_arr,
            hits=hits_arr,
            near_ratio=self.engine.model.near_ratio,
            shadow_mask=shadow_arr,
            local_cache_mask=np.zeros(m, dtype=bool),
        )

        codes = d.codes.tolist()
        remaining = d.limit_remaining.tolist()
        over = d.over_limit.tolist()
        near = d.near_limit.tolist()
        within = d.within_limit.tolist()
        shadow_stat = d.shadow_mode.tolist()
        set_lc = d.set_local_cache.tolist()
        for j, i in enumerate(rows):
            rule = limits[i]
            stats = rule.stats
            if over[j]:
                stats.over_limit.add(over[j])
            if near[j]:
                stats.near_limit.add(near[j])
            if within[j]:
                stats.within_limit.add(within[j])
            if shadow_stat[j]:
                stats.shadow_mode.add(shadow_stat[j])
            if self.local_cache is not None and set_lc[j]:
                self.local_cache.set(
                    keys[i].key, unit_to_divider(rule.limit.unit)
                )
            statuses[i] = DescriptorStatus(
                code=_CODE_BY_VALUE[int(codes[j])],
                current_limit=rule.limit,
                limit_remaining=int(remaining[j]),
                duration_until_reset=self._reset_seconds(
                    rule, now, reset_cache
                ),
            )

        # Enqueue the device commit; nobody waits on it (the write-
        # behind point).  apply() reconciles the host view from the
        # device's authoritative afters.
        lane_hits = hits_addend

        def apply(decisions) -> None:
            self._reconcile(lane_keys, lane_hits, decisions)

        def on_error(exc: BaseException) -> None:
            # The commit never landed: drain its pending hits so the
            # view falls back to the device-confirmed values instead
            # of over-counting for the rest of the window.
            import logging

            logging.getLogger("ratelimit.writebehind").warning(
                "device commit failed, draining %d lanes: %r",
                len(lane_keys),
                exc,
            )
            with self._view_lock:
                for k in lane_keys:
                    entry = self._view.get(k)
                    if entry is not None:
                        entry[1] = max(0, entry[1] - lane_hits)

        item = WorkItem(
            now=now,
            lanes=(),
            pack=LanePack(key_blob=b"".join(enc), meta=meta),
            apply=apply,
            on_error=on_error,
        )
        try:
            self._dispatcher.submit(item)
        except Exception as e:
            # The item never reached the queue, so on_error will never
            # fire for it — drain THIS call's pending hits here (same
            # loop) or the view over-counts these keys until their
            # window expires.
            on_error(e)
            from ..service import CacheError

            raise CacheError(f"counter engine failure: {e}") from e
        return statuses  # type: ignore[return-value]

    def _reconcile(self, lane_keys: List[str], lane_hits: int, decisions):
        """Dispatcher-completer callback: fold the device's afters back
        into the view and drain this batch's pending hits."""
        # One tolist() up front: the per-lane reads below become plain
        # list indexing instead of numpy scalar extraction (~10x on a
        # 4096-lane batch), and this runs on the completer thread.
        afters = decisions.afters.tolist()
        now = self.time_source.unix_now()
        with self._view_lock:
            for j, k in enumerate(lane_keys):
                entry = self._view.get(k)
                if entry is None:
                    continue  # pruned (window rolled over mid-flight)
                entry[0] = int(afters[j])
                entry[1] = max(0, entry[1] - lane_hits)
            self._batches_reconciled += 1
            if self._batches_reconciled % _PRUNE_EVERY == 0:
                dead = [
                    k for k, e in self._view.items() if e[2] <= now
                ]
                for k in dead:
                    del self._view[k]

    def _shrink_view_locked(self, now: int) -> None:
        """Called under _view_lock when the view exceeds its cap:
        prune expired windows first; if still over, evict soonest-
        expiring entries down to 90% of the cap (the same forgiveness
        direction as the device slot table's evict-soonest policy)."""
        view = self._view
        dead = [k for k, e in view.items() if e[2] <= now]
        for k in dead:
            del view[k]
        if len(view) <= self._max_view_keys:
            return
        target = int(self._max_view_keys * 0.9)
        by_expiry = sorted(view.items(), key=lambda kv: kv[1][2])
        for k, _ in by_expiry[: len(view) - target]:
            del view[k]

    def on_restored(self) -> None:
        """Checkpoint-restore hook (CheckpointManager.restore):
        rebuild the view from the restored slot table + counters so
        restored limits enforce immediately (an empty view would
        over-admit a full limit's worth per key until the first
        reconcile)."""
        counts = self.engine.export_counts()
        with self._view_lock:
            self._view = {
                key: [int(counts[slot]), 0, expiry]
                for key, slot, expiry in self.engine.slot_table.export_packed().tuples()
            }

    # -- lifecycle / parity surface -------------------------------------

    def flush(self) -> None:
        """Drain: everything enqueued before this call is committed to
        the device AND reconciled into the view (Flush analog,
        memcached/cache_impl.go:176-178)."""
        self._dispatcher.flush()

    def close(self) -> None:
        self._dispatcher.stop()

    def bind_health(self, health) -> None:
        import logging

        log = logging.getLogger("ratelimit.health")

        def on_state(healthy: bool, reason: str) -> None:
            if healthy:
                log.info("tpu backend healthy again: %s", reason)
                health.ok()
            else:
                log.error("tpu backend unhealthy: %s", reason)
                health.fail()

        self._dispatcher.on_state = on_state

    def register_stats(self, store, scope: str = "ratelimit.tpu") -> None:
        base = scope + ".bank0"
        # The same slot-table surface as tpu_cache's banks.
        register_slot_stats(store, base, lambda: self.engine)
        store.gauge_fn(
            base + ".dispatch_queue", lambda: self._dispatcher.queue_depth()
        )
        store.gauge_fn(
            base + ".dispatch_queue_hwm",
            lambda: self._dispatcher.queue_depth_hwm(),
        )
        store.gauge_fn(
            scope + ".host_view_keys", lambda: len(self._view)
        )

    def engines(self):
        return [self.engine]

    def run_exclusive(self, engine, fn) -> None:
        self._dispatcher.run_on_thread(fn)

    def warmup(self) -> None:
        from .tpu_cache import TpuRateLimitCache

        TpuRateLimitCache.warmup(self)  # same probe logic, one bank

    @property
    def per_second_engine(self):  # checkpoint surface parity
        return None

    @staticmethod
    def _reset_seconds(rule: RateLimitRule, now: int, cache: dict) -> int:
        return reset_seconds_cached(rule.limit.unit, now, cache)
