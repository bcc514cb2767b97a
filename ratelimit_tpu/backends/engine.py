"""CounterEngine: host orchestration around the device model.

Owns the counter table (a donated device buffer), the host slot table,
and batch padding/bucketing.  One engine is one counter bank; the
backend may run a second engine for per-second limits (the dual-Redis
analog, reference fixed_cache_impl.go:77-87).
"""

from __future__ import annotations

import contextlib
import logging
import time
from dataclasses import dataclass
from typing import Callable, List, Optional, Sequence

import jax
import numpy as np

from ..models.fixed_window import DeviceBatch, FixedWindowModel
from ..observability import spans as _spans
from ..observability.spans import SPANS
from ..utils.threads import ThreadClock
from .slot_table import PackedEntries

logger = logging.getLogger("ratelimit.engine")

# Pad batches up to one of these sizes so XLA compiles a handful of
# shapes instead of one per batch length (SURVEY.md section 2 SP row:
# batch-axis bucketing to fixed kernel shapes).
DEFAULT_BUCKETS = (8, 16, 32, 64, 128, 256, 512, 1024, 2048, 4096)


@dataclass
class HostBatch:
    """Unpadded batch assembled on the host (numpy, batch order)."""

    slots: np.ndarray  # int32
    hits: np.ndarray  # uint32
    limits: np.ndarray  # uint32
    fresh: np.ndarray  # bool
    shadow: np.ndarray  # bool
    # Per-lane window length in seconds; only generic-algorithm models
    # (models/registry.py) consume it.  None -> dividers of 1 reach the
    # device (inert for warmup probes with hits=0).
    dividers: Optional[np.ndarray] = None  # uint32


@dataclass
class HostDecisions:
    """Device decisions pulled back to host numpy, unpadded."""

    codes: np.ndarray
    limit_remaining: np.ndarray
    befores: np.ndarray
    afters: np.ndarray
    over_limit: np.ndarray
    near_limit: np.ndarray
    within_limit: np.ndarray
    shadow_mode: np.ndarray
    set_local_cache: np.ndarray


class CallWatch:
    """The kernel watchdog's view of one thread's device calls: when
    the call in progress began, or None while there is none — or while
    it runs a kernel shape that has never completed, whose first call
    is XLA compilation (seconds on a TPU), not a hang.  The engine
    brackets exactly its device interactions with begin/end
    (CounterEngine._device_call), so host work — slot assignment, a
    table rehash, the decide pass — never runs on the deadline's
    clock.  Nor does a full garbage collection: it stops every Python
    thread, this one too, for up to a quarter of a second at a million
    keys, and the device call it fell into returns the moment it ends
    — so ``age`` leaves out what ``SPANS.gc_pause_ns`` grew by since
    the call began (``rl.bg.gc`` brackets the same collections).  A
    call that is really stuck stays open with no collection running,
    and its age grows as before.  One writer (the thread making the
    calls); read lock-free by the watchdog and by waiting RPCs
    (BatchDispatcher.stuck_age).

    ``last_leg`` is the thread's last COMPLETED leg — (span name,
    monotonic_ns it ended, its duration in ns) — which a hang fault
    copies beside the open background work: what the thread had just
    done when its device call stopped returning.

    The bracket's other clocks stand beside it: ``wall0_ns``
    (``time.monotonic_ns`` as the call began; 0 = no bracket open,
    armed or not), ``clock``, the thread's ThreadClock once it has
    called ``bind()``, and ``last_gil_ns``, the GIL-return time of the
    thread's last native call (-1: none).  From them a hang fault reads
    what the open bracket is made of (``ledger``): a call late while
    its thread was on the CPU was the host's; one that is all off the
    CPU, with the GIL back in microseconds just before, was the
    runtime's or the device's.  They judge nothing: ``age`` is the
    deadline's one clock.

    The thread itself reads its CPU clock (``cpu0_ns``, for the
    bracket's on-CPU sums: CounterEngine._device_call) only where
    ``cpu_clock`` is set — the traced run, DEBUG_PROFILING=1 — and why
    not always: on the chip's host (gVisor) one read is a trap into the
    sentry, 6 us in a tight loop and some 40 us between a served
    launch's other work — four a launch cost +4% of ``p50_ms`` in 12 of
    12 same-seed pairs (PERF.md section 6, PR 41).  The witness does
    not need it: the watchdog looks once (``glance``) at a bracket it
    finds open at a tick it already makes — one bracket in a hundred,
    off the request's path — and reads the thread's clock itself."""

    __slots__ = (
        "since", "gc_ns", "last_leg", "_now", "cpu_clock",
        "wall0_ns", "cpu0_ns", "clock", "last_gil_ns", "_seen",
    )

    def __init__(self, now: Callable[[], float], cpu_clock: bool = False):
        self.since: Optional[float] = None
        self.gc_ns = 0  # SPANS.gc_pause_ns() when the open call began
        self.last_leg: Optional[tuple] = None
        self._now = now
        self.cpu_clock = bool(cpu_clock)
        self.wall0_ns = 0
        self.cpu0_ns = 0
        self.clock: Optional[ThreadClock] = None
        self.last_gil_ns = -1
        # (wall0_ns of the bracket looked at, monotonic_ns, the thread's
        # CPU clock then): the watchdog's glance.
        self._seen = (0, 0, 0)

    def bind(self) -> None:
        """Called once by the thread this watch belongs to."""
        self.clock = ThreadClock()  # tpu-lint: disable=shared-state -- one CallWatch per dispatcher thread, bound once by that thread

    def begin(self, armed: bool) -> None:
        # gc_ns (and the ledger's two) before since: a reader that sees
        # this call's `since` sees them too.
        self.gc_ns = SPANS.gc_pause_ns()  # tpu-lint: disable=shared-state -- one CallWatch per dispatcher thread: single writer, lock-free readers
        if self.cpu_clock:
            self.cpu0_ns = time.thread_time_ns()  # tpu-lint: disable=shared-state -- same single-writer stamp
        self.wall0_ns = time.monotonic_ns()  # tpu-lint: disable=shared-state -- same single-writer stamp
        self.since = self._now() if armed else None  # tpu-lint: disable=shared-state -- same single-writer stamp

    def end(self) -> None:
        self.since = None  # tpu-lint: disable=shared-state -- same single-writer stamp
        self.wall0_ns = 0  # tpu-lint: disable=shared-state -- same single-writer stamp

    def glance(self) -> None:
        """The watchdog's, at a tick it already makes: a bracket it
        finds open and has not looked at yet gets ONE reading of its
        thread's CPU clock — the baseline ``ledger`` subtracts should
        the call turn out stuck.  A healthy bracket lasts under a
        millisecond and a tick comes 8 times a second: one bracket in a
        hundred is ever looked at, by the watchdog's thread, off the
        request's path; a call stuck for a deadline has had a tick land
        in it."""
        wall0, clock = self.wall0_ns, self.clock
        if wall0 and clock is not None and self._seen[0] != wall0:
            cpu = clock.ns()
            if cpu is not None:
                self._seen = (wall0, time.monotonic_ns(), cpu)  # tpu-lint: disable=shared-state -- the watchdog thread's own slot

    def ledger(self, watchdog_late_ms: Optional[float] = None) -> dict:
        """The open bracket so far, read from OUTSIDE the thread (the
        watchdog, inside record_fault only), in ms: ``wall_ms`` since
        the call began, and of the ``observed_ms`` since the watchdog's
        glance at this bracket, ``on_cpu_ms`` (what the stuck thread's
        CPU clock gained) + ``off_cpu_ms`` (the rest: blocked, or
        runnable and not running — this host's kernel does not tell the
        two apart).  {} with no bracket open or no bound thread."""
        wall0, clock = self.wall0_ns, self.clock
        if not wall0 or clock is None:
            return {}
        now_ns = time.monotonic_ns()
        out = {"wall_ms": round((now_ns - wall0) / 1e6, 3)}
        seen, cpu_now = self._seen, clock.ns()
        if seen[0] == wall0 and cpu_now is not None:
            span = (now_ns - seen[1]) / 1e6
            on_cpu = max(0, cpu_now - seen[2]) / 1e6
            out["observed_ms"] = round(span, 3)
            out["on_cpu_ms"] = round(on_cpu, 3)
            out["off_cpu_ms"] = round(max(0.0, span - on_cpu), 3)
        if self.last_gil_ns >= 0:
            out["last_gil_return_us"] = round(self.last_gil_ns / 1e3, 1)
        if watchdog_late_ms is not None:
            out["watchdog_late_ms"] = watchdog_late_ms
        return out

    def age(self, now: float) -> tuple:
        """(seconds the open call has been running, seconds of them
        inside full collections); (0.0, 0.0) with no call open."""
        since = self.since
        if since is None:
            return 0.0, 0.0
        in_gc = (SPANS.gc_pause_ns() - self.gc_ns) / 1e9
        return now - since, max(0.0, in_gc)


def device_report() -> dict:
    """Where this process's kernels run, as JAX reports it — the
    where-it-ran half of the runner's start line and /debug/faults.
    JAX falls back to CPU with only a warning when an accelerator
    fails to initialise, so the SETTING (BACKEND_TYPE=tpu) proves
    nothing; this does."""
    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "device_count": len(devices),
    }


def _pick_table_cls(native: Optional[bool]):
    """Slot-table implementation choice: C++ (one FFI call per batch)
    with automatic fallback to the Python oracle."""
    from .slot_table import SlotTable

    if native is False:
        return SlotTable
    from . import native_slot_table

    if native_slot_table.available():
        return native_slot_table.NativeSlotTable
    if native is True:
        raise RuntimeError("native slot table requested but unavailable")
    logger.warning(
        "native slot table unavailable: this bank runs on the Python "
        "table (same decisions, several times the host cost per batch)"
    )
    return SlotTable


def _refresh_table_cls():
    """Slot table for stable-stem algorithms (sliding-window/GCRA):
    the Python table with refresh-on-touch expiry, so a continuously
    hot key's slot — and the window/TAT state it carries — survives
    indefinitely instead of being reclaimed ``divider`` seconds after
    FIRST sight.  (The native table has no refresh path; these banks
    trade its fused assign for state longevity.)"""
    import functools

    from .slot_table import SlotTable

    return functools.partial(SlotTable, refresh_expiry=True)


@dataclass
class _Dedup:
    """Host-side duplicate-slot aggregation for one device chunk.

    The slot table hands every same-key lane the same slot; combining
    them before the device step (group totals + per-lane exclusive
    prefixes, Redis-pipeline order) lets the device run the unique-slot
    fast path (models/fixed_window.py step_counters_unique) and
    reproduces per-lane results exactly on readback.
    """

    uniq_slots: np.ndarray  # int32[g] sorted unique slots
    inv: np.ndarray  # intp[count] lane -> group
    totals: np.ndarray  # uint64[g] group hit totals
    prefix: np.ndarray  # uint64[count] exclusive same-slot prefix, batch order
    fresh: np.ndarray  # bool[g] any lane fresh
    limit_max: np.ndarray  # uint32[g] max limit in group (saturation cap)
    # uint32[g] group window length, or None.  Same slot = same key =
    # same rule, so the per-group max is just the shared divider; only
    # generic-algorithm models consume it (see _dedup_chunk).
    divider_max: Optional[np.ndarray] = None

    def totals_u32(self) -> np.ndarray:
        """Group totals CLAMPED (not wrapped) into the saturating u32
        counter domain the device runs in — a past-u32 total makes the
        device saturate the counter at u32 max, which the host
        reconstruction treats as fully-over (_decide_host)."""
        return np.minimum(self.totals, 0xFFFFFFFF).astype(np.uint32)


def _dedup_chunk(
    slots: np.ndarray,
    hits: np.ndarray,
    limits: np.ndarray,
    fresh: np.ndarray,
    dividers: Optional[np.ndarray] = None,
) -> _Dedup:
    uniq, inv = np.unique(slots, return_inverse=True)
    inv = inv.reshape(-1)
    g = len(uniq)
    h64 = hits.astype(np.uint64)
    totals = np.zeros(g, dtype=np.uint64)
    np.add.at(totals, inv, h64)
    fresh_g = np.zeros(g, dtype=bool)
    np.logical_or.at(fresh_g, inv, fresh)
    limit_max = np.zeros(g, dtype=np.uint32)
    np.maximum.at(limit_max, inv, limits)
    divider_max = None
    if dividers is not None:
        divider_max = np.zeros(g, dtype=np.uint32)
        np.maximum.at(divider_max, inv, dividers.astype(np.uint32))
    if g == len(slots):  # no duplicates: identity prefixes
        prefix = np.zeros(len(slots), dtype=np.uint64)
    else:
        order = np.argsort(inv, kind="stable")
        inv_s = inv[order]
        h_s = h64[order]
        cs = np.cumsum(h_s) - h_s  # global exclusive prefix
        seg_start = np.empty(len(inv_s), dtype=bool)
        seg_start[0] = True
        seg_start[1:] = inv_s[1:] != inv_s[:-1]
        base = cs[seg_start]  # one per group, group-id order
        prefix = np.empty(len(slots), dtype=np.uint64)
        prefix[order] = cs - base[inv_s]
    return _Dedup(
        uniq_slots=uniq.astype(np.int32),
        inv=inv,
        totals=totals,
        prefix=prefix,
        fresh=fresh_g,
        limit_max=limit_max,
        divider_max=divider_max,
    )


def _decode_keys(blob, lens: np.ndarray) -> List[str]:
    """Split a length-prefixed utf-8 key blob back into strings (the
    non-fused fallback path; the native table never needs this)."""
    if isinstance(blob, np.ndarray):
        blob = blob.tobytes()
    keys = []
    off = 0
    for ln in lens.tolist():
        keys.append(blob[off : off + ln].decode("utf-8"))
        off += ln
    return keys


_NATIVE_DECIDE = None  # resolved on first use: False, or the fn


def _native_decide_fn():
    """The C++ fused decide kernel, or None (resolved once)."""
    global _NATIVE_DECIDE
    if _NATIVE_DECIDE is None:
        from . import native_slot_table

        _NATIVE_DECIDE = (
            native_slot_table.decide_reconstruct
            if native_slot_table.available()
            else False
        )
    return _NATIVE_DECIDE or None


def _decide_host(
    afters_padded: np.ndarray,
    hits_u32: np.ndarray,
    limits_u32: np.ndarray,
    shadow: np.ndarray,
    near_ratio: float,
    dedup: Optional["_Dedup"] = None,
    stamp=None,
) -> HostDecisions:
    """Threshold state machine on host numpy, from device `afters`
    (`stamp`: the completing thread's native_slot_table.ReturnStamp,
    which the native pass leaves its GIL-return time in).

    The device returned one (possibly saturated) `after` per UNIQUE
    slot; per-lane values are rebuilt as
        before_lane = (after_group - group_total) + lane_prefix
    in exact uint64 arithmetic — the device counter is SATURATING (it
    clamps at u32 max instead of wrapping, see update_unique), so the
    subtraction never underflows in the unsaturated case.  Two
    saturation regimes:

    - narrow readback clamp (at group-max-limit + group-total): only
      engages when the true group 'before' exceeds the group-max
      limit, leaving reconstructed before == limit — every lane lands
      in the fully-over branch, whose outputs depend only on
      before >= limit (the step_counters_compact argument);
    - u32-max counter saturation (a key lapped past 2^32 hits in one
      window): after_group reads back as u32 max; every lane is
      treated as fully-over — decision-exact for every limit BELOW
      u32 max (stat attribution rounds toward over_limit for this
      astronomically hot key).  At the degenerate limit == u32 max
      the saturated counter reads exactly at-limit and keeps
      answering OK — the counter cannot count higher, which is also
      where a limit that large stops being a limit."""
    from ..limiter.base import decide_batch

    if dedup is not None:
        native = _native_decide_fn()
        if native is not None:
            # Fused C pass: reconstruction + threshold machine in one
            # call (native/decide.cpp), differential-locked to the
            # numpy path below by tests/test_native_decide.py.
            from ..api import Code

            g = len(dedup.uniq_slots)
            (
                codes, remaining, befores, afters,
                over, near, within, shadow_d, set_lc,
            ) = native(
                afters_padded[:g],
                dedup.totals,
                dedup.inv,
                dedup.prefix,
                hits_u32,
                limits_u32,
                shadow,
                near_ratio,
                int(Code.OK),
                int(Code.OVER_LIMIT),
                stamp,
            )
            return HostDecisions(
                codes=codes,
                limit_remaining=remaining,
                befores=befores,
                afters=afters,
                over_limit=over,
                near_limit=near,
                within_limit=within,
                shadow_mode=shadow_d,
                set_local_cache=set_lc,
            )

    U32_MAX = np.uint64(0xFFFFFFFF)
    count = len(hits_u32)
    hits = hits_u32.astype(np.int64)
    if dedup is None:  # afters already per-lane (general device path)
        afters = afters_padded[:count].astype(np.int64)
        befores = afters - hits
    else:
        g = len(dedup.uniq_slots)
        afters_g = afters_padded[:g].astype(np.uint64)
        saturated = afters_g >= U32_MAX
        before_g = np.where(
            saturated,
            U32_MAX,
            afters_g - np.minimum(dedup.totals, afters_g),
        )
        befores_u64 = before_g[dedup.inv] + dedup.prefix
        afters_u64 = np.minimum(
            befores_u64 + hits_u32.astype(np.uint64), U32_MAX
        )
        befores = np.minimum(befores_u64, U32_MAX).astype(np.int64)
        afters = afters_u64.astype(np.int64)
    d = decide_batch(
        limits=limits_u32,
        befores=befores,
        afters=afters,
        hits=hits,
        near_ratio=near_ratio,
        shadow_mask=shadow,
        local_cache_mask=np.zeros(count, dtype=bool),
    )
    return HostDecisions(
        codes=d.codes,
        limit_remaining=d.limit_remaining,
        befores=befores,
        afters=afters,
        over_limit=d.over_limit,
        near_limit=d.near_limit,
        within_limit=d.within_limit,
        shadow_mode=d.shadow_mode,
        set_local_cache=d.set_local_cache.astype(bool),
    )


def decide_generic(
    model,
    fetched: np.ndarray,
    hits_u32: np.ndarray,
    limits_u32: np.ndarray,
    shadow: np.ndarray,
    dedup: _Dedup,
    now: int,
) -> HostDecisions:
    """Host half of the generic algorithm protocol: the model rebuilds
    per-lane effective (before, after) counts from its device readback,
    then the SHARED threshold state machine (limiter.base.decide_batch)
    produces codes/stat deltas — near-limit and partial-hit attribution
    are identical across every algorithm by construction.  Generic
    algorithms never feed the host over-limit cache (their capacity
    refills continuously, so an OVER_LIMIT verdict is not valid for the
    remainder of any window) — set_local_cache stays False.

    Module-level (not a CounterEngine method) because the host mirror
    engine (backends/host_engine.py) runs the same reconstruction on
    its numpy replay of the kernel — the fallback path's decisions must
    come from the same arithmetic as the device path's."""
    from ..limiter.base import decide_batch

    befores, afters = model.lane_counts(
        fetched, dedup, hits_u32, limits_u32, now
    )
    count = len(hits_u32)
    d = decide_batch(
        limits=limits_u32,
        befores=befores,
        afters=afters,
        hits=hits_u32.astype(np.int64),
        near_ratio=model.near_ratio,
        shadow_mask=shadow,
        local_cache_mask=np.zeros(count, dtype=bool),
    )
    return HostDecisions(
        codes=d.codes,
        limit_remaining=d.limit_remaining,
        befores=befores,
        afters=afters,
        over_limit=d.over_limit,
        near_limit=d.near_limit,
        within_limit=d.within_limit,
        shadow_mode=d.shadow_mode,
        set_local_cache=np.zeros(count, dtype=bool),
    )


# A bank's slot-table family: name under ratelimit.tpu.bank<i> ->
# the plain int its collector keeps (CounterEngine.stat_*).
_SLOT_COUNTERS = (
    # Evictions are monotonic; paired with the num_slots gauge, "about
    # to exhaust TPU_NUM_SLOTS" is a dashboard trend, not a surprise.
    ("evictions", "stat_evictions"),
    ("window_rollovers", "stat_window_rollovers"),
    ("dedup_groups", "stat_groups_launched"),
    ("padded_lanes", "stat_padded_lanes"),
    ("slot_gc.runs", "stat_slot_gc_runs"),
    ("slot_gc.freed", "stat_slot_gc_freed"),
    ("arena.compactions", "stat_arena_compactions"),
)
_SLOT_GAUGES = (
    ("live_keys", "stat_live_keys"),
    ("arena.bytes", "stat_arena_bytes"),
)
# What each device-call bracket was made of, all launches summed (ns):
# the bracket's wall time beside the on-CPU time of the thread that
# stood in it (wall - cpu: off the CPU; both sums advance only in the
# traced run, CallWatch.cpu_clock, together or not at all) — once over
# every launch, once over the readbacks of the launches readback_ready
# counts, whose copy had arrived: what those waited for was not the
# device — and the GIL-return times of the one native call a launch
# makes on each thread (native_slot_table.ReturnStamp; count 0 on the
# Python table).  Plain sums: where the CPU clock ticks coarsely (10 ms
# under gVisor) one launch's reading is 0 or a whole tick, and a reader
# subtracts deltas of sums, never one launch's two readings.
_LEG_COUNTERS = (
    ("device_submit.wall_ns", "total_submit_wall_ns"),
    ("device_submit.cpu_ns", "total_submit_cpu_ns"),
    ("readback.wall_ns", "total_readback_wall_ns"),
    ("readback.cpu_ns", "total_readback_cpu_ns"),
    ("readback_ready.wall_ns", "total_ready_wall_ns"),
    ("readback_ready.cpu_ns", "total_ready_cpu_ns"),
    ("assign_gil.total_ns", "total_assign_gil_ns"),
    ("assign_gil.count", "count_assign_gil"),
    ("decide_gil.total_ns", "total_decide_gil_ns"),
    ("decide_gil.count", "count_decide_gil"),
)


def register_slot_stats(store, base: str, engine_of: Callable) -> None:
    """Register a bank's slot-table family under `base`
    (ratelimit.tpu.bank<i>): occupancy and capacity, evictions, window
    rollovers over dedup groups launched, the collector's slot GC, the
    native table's arena, and what the device-call brackets were made
    of (_LEG_COUNTERS).  `engine_of()` gives the bank's engine at
    each scrape (a warm restart replaces the object, by one of the same
    kind).  The values are snapshots written by the table-owning
    thread: observers never call into the (unsynchronized) native
    table."""
    for name, attr in _SLOT_COUNTERS + _LEG_COUNTERS:
        store.counter_fn(
            base + "." + name, lambda a=attr: getattr(engine_of(), a)
        )
    for name, attr in _SLOT_GAUGES:
        store.gauge_fn(
            base + "." + name, lambda a=attr: getattr(engine_of(), a)
        )
    store.counter_fn(
        base + ".slot_gc.total_us",
        lambda: engine_of().stat_slot_gc_ns // 1000,
    )
    store.gauge_fn(
        base + ".num_slots", lambda: engine_of().model.num_slots
    )
    store.gauge_fn(
        base + ".slot_fill_pct",
        lambda: (
            100
            * engine_of().stat_live_keys
            // max(1, engine_of().model.num_slots)
        ),
    )


class CounterEngine:
    def __init__(
        self,
        num_slots: int = 1 << 20,
        near_ratio: float = 0.8,
        buckets: Sequence[int] = DEFAULT_BUCKETS,
        device: Optional[jax.Device] = None,
        model=None,
        native_table: Optional[bool] = None,
    ):
        """`model` defaults to a single-chip FixedWindowModel.  A
        custom model must provide EITHER a SATURATING unique-slot
        serving path (step_counters_unique_packed or
        step_counters_unique + step_counters_unique_compact) OR the
        generic algorithm-table protocol (models/registry.py):
        ``step_serve_packed(state, packed, now)`` on device plus
        ``lane_counts(out, dedup, hits, limits, now)`` on host — the
        engine then dispatches through the generic path and runs the
        shared threshold state machine (limiter.base.decide_batch).
        For mesh models use parallel.ShardedCounterEngine, whose model
        brings the packed serving path over the mesh.
        `native_table`: None = use the C++ slot table when it
        builds/loads, True = require it, False = pure Python; generic
        models with stable-stem keys (windowed_keys=False) always get
        the Python table with refresh-on-touch expiry."""
        self.model = model if model is not None else FixedWindowModel(
            num_slots, near_ratio
        )
        # Generic algorithm-table protocol marker: the model owns both
        # the device step and the host lane reconstruction.
        self._generic = hasattr(self.model, "lane_counts")
        if (
            not self._generic
            and type(self)._device_submit is CounterEngine._device_submit
            and not (
                hasattr(self.model, "step_counters_unique_packed")
                or hasattr(self.model, "step_counters_unique")
            )
        ):
            raise TypeError(
                "model must provide a saturating unique-slot serving "
                "path (step_counters_unique[_packed]) or the generic "
                "step_serve_packed/lane_counts protocol; the modular "
                "update() path is not safe for serving — for mesh "
                "models use parallel.ShardedCounterEngine"
            )
        if self._generic and not getattr(self.model, "windowed_keys", True):
            self._table_cls = _refresh_table_cls()
        else:
            self._table_cls = _pick_table_cls(native_table)
        self.slot_table = self._table_cls(self.model.num_slots)
        self.buckets = tuple(sorted(buckets))
        self.max_batch = self.buckets[-1]
        self._device = device
        counts = self.model.init_state()
        if device is not None:
            counts = jax.device_put(counts, device)
        self._counts = counts
        # Gauge snapshot, updated only by the thread that owns the slot
        # table (step_submit); read lock-free from stats/HTTP threads
        # (plain int attribute reads are atomic under the GIL), so
        # observers never call into the un-synchronized native table.
        self.stat_live_keys = 0
        self.stat_evictions = 0
        # Unique slots across the LAST submitted batch's dedup groups:
        # the launch recorder's dedup_groups field (same single-toucher
        # discipline — written at the end of each submit, read by the
        # dispatcher collector immediately after submit returns).
        self.stat_dedup_groups = 0
        # The legs of the LAST submit and the LAST completion, in ns,
        # measured where they happen (the launch record's assign_ns /
        # device_submit_ns / readback_ns / decide_ns): the first two
        # written by the submitting thread and read by it right after
        # submit_packed returns, the last two by the completing thread
        # likewise after step_complete.
        self.stat_assign_ns = 0
        self.stat_device_submit_ns = 0
        self.stat_readback_ns = 0
        self.stat_decide_ns = 0
        # Beside them, same writers, same readers: the on-CPU time of
        # the thread inside each device-call bracket
        # (time.thread_time_ns; -1 unless the watch's cpu_clock is set:
        # the traced run) and the GIL-return time of the launch's
        # native assign / decide (-1: the Python table, no native
        # pass) — the record's device_submit_cpu_ns / readback_cpu_ns /
        # assign_gil_ns / decide_gil_ns.  Then their sums over all
        # launches, exported as counters (_LEG_COUNTERS).
        self.stat_device_submit_cpu_ns = -1
        self.stat_readback_cpu_ns = -1
        self.stat_assign_gil_ns = -1
        self.stat_decide_gil_ns = -1
        self.total_submit_wall_ns = 0
        self.total_submit_cpu_ns = 0
        self.total_readback_wall_ns = 0
        self.total_readback_cpu_ns = 0
        self.total_ready_wall_ns = 0
        self.total_ready_cpu_ns = 0
        self.total_assign_gil_ns = 0
        self.count_assign_gil = 0
        self.total_decide_gil_ns = 0
        self.count_decide_gil = 0
        self._decide_stamp = None  # the completing thread's ReturnStamp
        # Launches whose device step had finished (is_ready(), asked
        # once before the wait) when the completer took them up: over
        # the launch count, the share of launches whose device trip
        # the hand-off hid.  Monotonic; the completing thread's own.
        self.stat_readback_ready = 0
        # Fresh slot sightings = window rollovers: a key entering a
        # new window is a new cache key whose first batch appearance
        # carries fresh=1 (the lazy-expiry seam).  Counted per dedup
        # GROUP so one rolled-over key counts once per batch, however
        # many lanes repeat it.  Monotonic; exported as a counter.
        self.stat_window_rollovers = 0
        # Dedup groups launched, all launches summed: what
        # stat_window_rollovers is a share of (one device lane a
        # group).  Monotonic; exported as a counter.
        self.stat_groups_launched = 0
        # The bucket each device step ran at, all launches summed:
        # stat_groups_launched over it is the share of device lanes
        # that carried a group, the rest being padding.  Monotonic;
        # exported as a counter.
        self.stat_padded_lanes = 0
        # The collector's periodic slot GC (dispatcher._collect_loop;
        # not the one assign runs itself on an empty free list): runs,
        # leases it freed, time it took — monotonic counters.  And the
        # native table's arena as of the last launch or GC: rehashes
        # since the table was made, bytes held (the Python table has
        # no arena and reads 0).
        self.stat_slot_gc_runs = 0
        self.stat_slot_gc_freed = 0
        self.stat_slot_gc_ns = 0
        self.stat_arena_compactions = 0
        self.stat_arena_bytes = 0
        # Kernel shapes — (bucket, readback dtype) and the like — one
        # of whose launches has been read back: compiled, loaded and
        # known to finish.  Only calls of these arm the kernel
        # deadline (_device_call); step_complete grows the set.
        self._proven_shapes: set = set()

    def placement(self) -> dict:
        """Where this bank lives (runner start line, /debug/faults):
        slot-table implementation, the devices holding its state, and
        how many kernel shapes it has compiled and completed.  Safe
        from any thread: one attribute read each, and an array's
        sharding outlives its donation."""
        return {
            "slot_table": (
                "native"
                if hasattr(self.slot_table, "assign_dedup_packed")
                else "python"
            ),
            "state_devices": sorted(
                f"{d.platform}:{d.id}"
                for d in self._counts.sharding.device_set
            ),
            "shapes_compiled": len(self._proven_shapes),
        }

    # -- host-side key handling -----------------------------------------

    def warmup_probe_slots(self, bucket: int) -> np.ndarray:
        """In-table slots whose device shape for a `bucket`-lane batch
        is the WORST case this engine can serve (used by
        TpuRateLimitCache.warmup to precompile every serving shape).
        Single-chip: `bucket` distinct slots (wrapping only on tables
        smaller than the bucket, where the collapsed shape IS the
        worst achievable)."""
        ns = self.model.num_slots
        return (np.arange(bucket, dtype=np.int64) % ns).astype(np.int32)

    def assign_slot(self, key: str, now: int, expiry: int):
        return self.slot_table.assign(key, now, expiry)

    def gc(self, now: int) -> int:
        t0 = time.monotonic_ns()
        freed = self.slot_table.gc(now)
        self.stat_slot_gc_runs += 1
        self.stat_slot_gc_freed += freed
        self._read_table_stats()
        self.stat_slot_gc_ns += time.monotonic_ns() - t0
        return freed

    def _read_table_stats(self) -> None:
        """The slot table's own numbers into the plain ints the scrape
        side reads lock-free.  The engine has a single toucher (the
        dispatcher collector owns it; inline mode serializes via
        tpu_cache._inline_locks), and only it calls into the table."""
        table = self.slot_table
        self.stat_live_keys = len(table)  # tpu-lint: disable=shared-state -- collector-owned engine
        self.stat_evictions = table.evictions  # tpu-lint: disable=shared-state -- collector-owned engine
        self.stat_arena_compactions = table.compactions  # tpu-lint: disable=shared-state -- collector-owned engine
        self.stat_arena_bytes = table.arena_bytes  # tpu-lint: disable=shared-state -- collector-owned engine

    # -- device step ----------------------------------------------------

    def _bucket(self, n: int) -> int:
        for b in self.buckets:
            if n <= b:
                return b
        return self.max_batch

    def step(self, batch: HostBatch, now: int = 0) -> HostDecisions:
        """Run one padded device step per <=max_batch chunk."""
        return self.step_complete(self.step_submit(batch, now))

    def step_submit(self, batch: HostBatch, now: int = 0):
        """Launch the device work for `batch` WITHOUT waiting for the
        readback; returns an opaque token for step_complete.

        Split so the dispatcher can pipeline: launch batch N+1 while
        batch N's device->host transfer is still in flight (the counts
        donation chain serializes the compute correctly on device).
        Must be called from the thread that owns this engine.

        This entry takes pre-assigned slots (warmup, tests, oracle
        comparisons); the serving path is `submit_packed`, which fuses
        slot assignment + dedup into one native call.  ``now`` is the
        batch clock — only generic-algorithm models (whose kernels do
        their own window/TAT math) consume it.
        """
        n = len(batch.slots)
        chunks = []
        for start in range(0, n, self.max_batch):
            count = min(n - start, self.max_batch)
            end = start + count
            # Host-side duplicate-slot aggregation: same-key lanes
            # collapse to one device lane (group total + per-lane
            # prefixes) so the device always runs the unique-slot fast
            # path (7.5x — benchmarks/PERF_NOTES.md); lanes are rebuilt
            # in _decide_host.
            dedup = _dedup_chunk(
                batch.slots[start:end],
                batch.hits[start:end],
                batch.limits[start:end],
                batch.fresh[start:end],
                None
                if batch.dividers is None
                else batch.dividers[start:end],
            )
            afters_dev, reassemble, shape = self._device_submit(
                dedup, now, None
            )
            chunks.append(
                (afters_dev, start, count, dedup, reassemble, shape)
            )
            # Engine stats are plain ints on purpose (see
            # _read_table_stats): one toucher, lock-free readers.
            self.stat_window_rollovers += int(np.count_nonzero(dedup.fresh))  # tpu-lint: disable=shared-state -- collector-owned engine
            self.stat_padded_lanes += shape[0]  # tpu-lint: disable=shared-state -- collector-owned engine
        self._read_table_stats()
        self.stat_dedup_groups = sum(len(c[3].uniq_slots) for c in chunks)  # tpu-lint: disable=shared-state -- collector-owned engine
        self.stat_groups_launched += self.stat_dedup_groups  # tpu-lint: disable=shared-state -- collector-owned engine
        return (batch.hits, batch.limits, batch.shadow, chunks, now)

    def submit_packed(
        self,
        now: int,
        key_blob,
        meta: np.ndarray,
        watch: Optional[CallWatch] = None,
    ):
        """Serving fast path: assign slots AND dedup in one native call
        per chunk, then launch the device step (no wait).  `watch`, the
        dispatcher's, sees each launch begin and end (_device_call).

        Keys arrive pre-encoded as a length-prefixed utf-8 blob and
        per-lane scalars as one LANE_DTYPE record array (both built on
        the RPC threads — see dispatcher.LanePack), so the dispatcher's
        serial path never walks lanes in Python.  Returns the same
        token shape as step_submit.
        """
        n = len(meta)
        key_lens = meta["len"].astype(np.int64)
        expiries = np.ascontiguousarray(meta["expiry"])
        hits = np.ascontiguousarray(meta["hits"])
        limits = np.ascontiguousarray(meta["limits"])
        shadow = meta["shadow"].astype(bool)
        # Generic models need per-lane window lengths on device; the
        # fixed-window paths never read them (and the fused native
        # assign below predates the field).
        dividers = (
            np.ascontiguousarray(meta["divider"]) if self._generic else None
        )
        chunks = []
        table = self.slot_table
        fused = hasattr(table, "assign_dedup_packed")
        blob_arr = (
            np.frombuffer(key_blob, dtype=np.uint8)
            if isinstance(key_blob, (bytes, bytearray))
            else key_blob
        )
        # Chunks of one submission share pin scope: a key assigned in
        # chunk 1 must never be evicted for a chunk-2 lane (they are in
        # flight against the same device pass).
        multi_fused = fused and n > self.max_batch
        if multi_fused:
            offs = np.zeros(n + 1, dtype=np.int64)
            np.cumsum(key_lens, out=offs[1:])
            table.begin_batch()
        # Phase 1 — assign + dedup EVERY chunk before any device
        # launch: slot-table exhaustion must error the batch before a
        # single hit is committed to the counters (the old path's
        # assign-whole-batch-then-step ordering; a mid-batch failure
        # after partial commits would double-count on client retry).
        dedups: List[tuple] = []
        self.stat_device_submit_ns = 0  # tpu-lint: disable=shared-state -- collector-owned engine
        self.stat_device_submit_cpu_ns = -1  # tpu-lint: disable=shared-state -- collector-owned engine
        assign_gil = -1
        t_assign = time.monotonic_ns()
        try:
            with SPANS.span(_spans.LAUNCH_ASSIGN):
                if fused:
                    for start in range(0, n, self.max_batch):
                        count = min(n - start, self.max_batch)
                        end = start + count
                        bl = (
                            blob_arr[offs[start] : offs[end]]
                            if multi_fused
                            else blob_arr
                        )
                        inv, uniq, totals, prefix, freshg, limitmax = (
                            table.assign_dedup_packed(
                                bl,
                                key_lens[start:end],
                                now,
                                expiries[start:end],
                                hits[start:end],
                                limits[start:end],
                            )
                        )
                        gil = table.returned.gil_ns
                        assign_gil = max(assign_gil, 0) + gil
                        self.total_assign_gil_ns += gil  # tpu-lint: disable=shared-state -- collector-owned engine
                        self.count_assign_gil += 1  # tpu-lint: disable=shared-state -- collector-owned engine
                        dedup = _Dedup(
                            uniq_slots=uniq,
                            inv=inv,
                            totals=totals,
                            prefix=prefix,
                            fresh=freshg,
                            limit_max=limitmax,
                        )
                        dedups.append((start, count, dedup))
                else:
                    keys = _decode_keys(key_blob, key_lens)
                    slots64, fresh = table.assign_batch(keys, now, expiries)
                    slots = slots64.astype(np.int32)
                    for start in range(0, n, self.max_batch):
                        count = min(n - start, self.max_batch)
                        end = start + count
                        dedup = _dedup_chunk(
                            slots[start:end],
                            hits[start:end],
                            limits[start:end],
                            fresh[start:end],
                            None if dividers is None else dividers[start:end],
                        )
                        dedups.append((start, count, dedup))
        finally:
            if multi_fused:
                table.end_batch()
        t_assigned = time.monotonic_ns()
        self.stat_assign_ns = t_assigned - t_assign  # tpu-lint: disable=shared-state -- collector-owned engine
        self.stat_assign_gil_ns = assign_gil  # tpu-lint: disable=shared-state -- collector-owned engine
        if watch is not None:
            watch.last_leg = (
                _spans.LAUNCH_ASSIGN, t_assigned, t_assigned - t_assign
            )
            watch.last_gil_ns = assign_gil
        # Phase 2 — launch the device step per chunk.
        for start, count, dedup in dedups:
            afters_dev, reassemble, shape = self._device_submit(
                dedup, now, watch
            )
            chunks.append(
                (afters_dev, start, count, dedup, reassemble, shape)
            )
            self.stat_window_rollovers += int(np.count_nonzero(dedup.fresh))
            self.stat_padded_lanes += shape[0]
        self._read_table_stats()
        self.stat_dedup_groups = sum(
            len(d.uniq_slots) for _, _, d in dedups
        )
        self.stat_groups_launched += self.stat_dedup_groups
        return (hits, limits, shadow, chunks, now)

    def step_complete(
        self, token, watch: Optional[CallWatch] = None
    ) -> HostDecisions:
        """Block on the readback for a step_submit token and run the
        host threshold state machine.  Thread-agnostic (the only
        engine state it touches is the proven-shape set, which it
        grows).  `watch` sees each readback wait begin and end."""
        hits, limits, shadow, chunks, now = token
        self.stat_readback_ns = 0  # tpu-lint: disable=shared-state -- one completing thread per engine; the submitting thread never touches these two
        self.stat_readback_cpu_ns = -1  # tpu-lint: disable=shared-state -- same single completing thread
        decide_ns = 0
        decide_gil = -1
        stamp = self._decide_stamp
        if stamp is None and not self._generic and _native_decide_fn():
            from .native_slot_table import ReturnStamp

            stamp = self._decide_stamp = ReturnStamp()  # tpu-lint: disable=shared-state -- same single completing thread
        if not chunks:
            self.stat_decide_ns = 0  # tpu-lint: disable=shared-state -- same single completing thread
            empty = np.zeros(0, dtype=np.int32)
            return HostDecisions(*([empty] * 8), empty.astype(bool))
        outs: List[HostDecisions] = []
        ready = True
        for afters_dev, start, count, dedup, reassemble, shape in chunks:
            ready = ready and afters_dev.is_ready()
            with self._device_call(watch, shape, _spans.COMPLETE_READBACK):
                # The one intended device sync of serving: the wait for
                # the copy the launch asked for (_device_submit).
                fetched = np.asarray(afters_dev)
            self._proven_shapes.add(shape)  # tpu-lint: disable=shared-state -- set.add/`in` are GIL-atomic; a racing reader only sees a shape as cold once more
            if reassemble is not None:
                fetched = reassemble(fetched)
            end = start + count
            t_decide = time.monotonic_ns()
            with SPANS.span(_spans.COMPLETE_DECIDE):
                if self._generic:
                    out = self._decide_generic(
                        fetched,
                        hits[start:end],
                        limits[start:end],
                        shadow[start:end],
                        dedup,
                        now,
                    )
                else:
                    out = _decide_host(
                        fetched,
                        hits[start:end],
                        limits[start:end],
                        shadow[start:end],
                        self.model.near_ratio,
                        dedup,
                        stamp,
                    )
                    if stamp is not None:
                        gil = stamp.gil_ns
                        decide_gil = max(decide_gil, 0) + gil
                        self.total_decide_gil_ns += gil  # tpu-lint: disable=shared-state -- same single completing thread
                        self.count_decide_gil += 1  # tpu-lint: disable=shared-state -- same single completing thread
            outs.append(out)
            t_decided = time.monotonic_ns()
            decide_ns += t_decided - t_decide
            if watch is not None:
                watch.last_leg = (
                    _spans.COMPLETE_DECIDE, t_decided, t_decided - t_decide
                )
        self.stat_decide_ns = decide_ns  # tpu-lint: disable=shared-state -- same single completing thread
        self.stat_decide_gil_ns = decide_gil  # tpu-lint: disable=shared-state -- same single completing thread
        if watch is not None:
            watch.last_gil_ns = decide_gil
        # One launch, all of whose chunks had arrived: the count and,
        # in the traced run, that launch's readback brackets whole.
        self.stat_readback_ready += ready  # tpu-lint: disable=shared-state -- same single completing thread
        if ready and self.stat_readback_cpu_ns >= 0:
            self.total_ready_wall_ns += self.stat_readback_ns  # tpu-lint: disable=shared-state -- same single completing thread
            self.total_ready_cpu_ns += self.stat_readback_cpu_ns  # tpu-lint: disable=shared-state -- same single completing thread
        if len(outs) == 1:
            return outs[0]
        return HostDecisions(
            *(
                np.concatenate([getattr(o, f) for o in outs])
                for f in HostDecisions.__dataclass_fields__
            )
        )

    @contextlib.contextmanager
    def _device_call(
        self,
        watch: Optional[CallWatch],
        shape: tuple,
        leg: str = _spans.LAUNCH_DEVICE_CALL,
    ):
        """Bracket one device interaction — the launch of kernel
        `shape` (`leg` rl.launch.device_call), or the readback of one
        (rl.complete.readback) — for the kernel watchdog:
        KERNEL_DEADLINE_S's clock runs only inside, and only when the
        shape has completed before (see CallWatch).  The same bracket
        is the span of that name and the launch record's
        device_submit_ns / readback_ns: the watchdog's clock, the trace
        and the record time one interval.  Where the watch's
        ``cpu_clock`` is set (the traced run) the thread's on-CPU clock
        stands beside the wall stamp at each end (the record's
        device_submit_cpu_ns / readback_cpu_ns, and the bank's
        ``.wall_ns`` / ``.cpu_ns`` sums, which advance together or not
        at all): two more clock reads a bracket, by the thread that
        does the work.  They stand OUTSIDE the wall stamps (first
        before t0, last after t1), so the bracket everything else reads
        does not hold them; the on-CPU figure is high by about one
        clock read a bracket for it."""
        profile = watch is not None and watch.cpu_clock
        if watch is not None:
            watch.begin(shape in self._proven_shapes)
            t0 = watch.wall0_ns
        else:
            t0 = time.monotonic_ns()
        try:
            with SPANS.span(leg):
                yield
        finally:
            t1 = time.monotonic_ns()
            cpu = time.thread_time_ns() - watch.cpu0_ns if profile else -1
            if watch is not None:
                watch.end()
                watch.last_leg = (leg, t1, t1 - t0)
            if leg is _spans.COMPLETE_READBACK:
                self.stat_readback_ns += t1 - t0  # tpu-lint: disable=shared-state -- the completing thread's own field (step_complete)
                if profile:
                    self.stat_readback_cpu_ns = max(self.stat_readback_cpu_ns, 0) + cpu  # tpu-lint: disable=shared-state -- same
                    self.total_readback_wall_ns += t1 - t0  # tpu-lint: disable=shared-state -- same
                    self.total_readback_cpu_ns += cpu  # tpu-lint: disable=shared-state -- same
            else:
                self.stat_device_submit_ns += t1 - t0  # tpu-lint: disable=shared-state -- the submitting thread's own field
                if profile:
                    self.stat_device_submit_cpu_ns = max(self.stat_device_submit_cpu_ns, 0) + cpu  # tpu-lint: disable=shared-state -- same
                    self.total_submit_wall_ns += t1 - t0  # tpu-lint: disable=shared-state -- same
                    self.total_submit_cpu_ns += cpu  # tpu-lint: disable=shared-state -- same

    def _decide_generic(
        self,
        fetched: np.ndarray,
        hits_u32: np.ndarray,
        limits_u32: np.ndarray,
        shadow: np.ndarray,
        dedup: _Dedup,
        now: int,
    ) -> HostDecisions:
        return decide_generic(
            self.model, fetched, hits_u32, limits_u32, shadow, dedup, now
        )

    def _device_submit(
        self, dedup: _Dedup, now: int, watch: Optional[CallWatch]
    ):
        """Launch the device step for one deduped chunk; returns
        (device afters handle, reassemble-fn or None, shape).
        `reassemble`, when set, maps the fetched device array to one
        (possibly saturated) `after` per unique slot — no engine in
        the tree sets it (ROADMAP D-queue).  `shape` names the
        compiled program the chunk ran: its bucket plus whatever else
        selects one (_device_call's key)."""
        g = len(dedup.uniq_slots)
        padded = self._bucket(g)
        ns = self.model.num_slots

        if self._generic:
            # Generic algorithm path: ONE int32[5, padded] transfer —
            # rows (slots, hits-bits, limits-bits, fresh,
            # divider-bits) — plus the batch clock; the model owns
            # state layout, kernel math and host reconstruction.
            # Padding uses DISTINCT out-of-table slots with divider=1,
            # limit=1, hits=0 so pad lanes are inert.
            with SPANS.span(_spans.LAUNCH_PACK):
                pk = np.empty((5, padded), dtype=np.int32)
                pk[0, :g] = dedup.uniq_slots
                pk[1, :g] = dedup.totals_u32().view(np.int32)
                pk[2, :g] = dedup.limit_max.view(np.int32)
                pk[3, :g] = dedup.fresh
                if dedup.divider_max is not None:
                    pk[4, :g] = dedup.divider_max.view(np.int32)
                else:
                    pk[4, :g] = 1
                if padded > g:
                    pk[0, g:] = np.arange(
                        ns, ns + (padded - g), dtype=np.int64
                    )
                    pk[1, g:] = 0
                    pk[2, g:] = 1
                    pk[3, g:] = 0
                    pk[4, g:] = 1
            shape = (padded,)
            with self._device_call(watch, shape):
                # np.int32, not the Python int: a weak-typed scalar
                # would be another jit signature.
                self._counts, out_dev = self.model.step_serve_packed(
                    self._counts, pk, np.int32(now)
                )
                out_dev.copy_to_host_async()
            return out_dev, None, shape
        # Dtype choice uses the UNWRAPPED uint64 totals; totals past
        # u32 max are CLAMPED for the device (not wrapped), matching
        # the saturating counter arithmetic — the device stores u32
        # max and the host treats the group as fully-over
        # (_decide_host's saturation branch).
        cap = int(dedup.totals.max(initial=0)) + int(
            dedup.limit_max.max(initial=1)
        )
        dt = "uint8" if cap <= 0xFF else ("uint16" if cap <= 0xFFFF else "")

        # Serving fast path: the device returns only `afters` (the
        # minimal sufficient statistic); the threshold state machine
        # reruns vectorized on host from (afters, hits, limits) —
        # bit-identical to the on-device DeviceDecisions path, which
        # tests/test_counter_model.py locks against both.  When every
        # group's limit+total fits in uint8/uint16, the saturated
        # narrow readback shrinks the device->host transfer 4x/2x (see
        # FixedWindowModel.step_counters_compact for the exactness
        # argument).
        if hasattr(self.model, "step_counters_unique_packed"):
            # Packed transfer: ONE (4, padded) int32 host array, handed
            # to the jitted step as numpy — the dispatch carries it to
            # the counters' device; a device_put of its own first is a
            # second trip through JAX's Python for every launch.  Rows:
            # slots, hits (u32 bit-pattern), limits (u32 bit-pattern),
            # fresh.  Padding uses DISTINCT out-of-table slots
            # (num_slots + i) so the unique_indices scatter promise
            # holds.
            with SPANS.span(_spans.LAUNCH_PACK):
                pk = np.empty((4, padded), dtype=np.int32)
                pk[0, :g] = dedup.uniq_slots
                pk[1, :g] = dedup.totals_u32().view(np.int32)
                pk[2, :g] = dedup.limit_max.view(np.int32)
                pk[3, :g] = dedup.fresh
                if padded > g:
                    pk[0, g:] = np.arange(
                        ns, ns + (padded - g), dtype=np.int64
                    )
                    pk[1, g:] = 0
                    pk[2, g:] = 1
                    pk[3, g:] = 0
            shape = (padded, dt)
            with self._device_call(watch, shape):
                self._counts, afters_dev = (
                    self.model.step_counters_unique_packed(
                        self._counts, dt, pk
                    )
                )
                afters_dev.copy_to_host_async()
            return afters_dev, None, shape

        # Unpacked unique path (models with step_counters_unique but
        # no packed entry): five separate leaves.  There is NO modular
        # fallback here — serving requires a saturating unique path
        # (update()'s scatter-add wraps, which would reset enforcement
        # for lapped keys; see update_unique), so models without one
        # are rejected at engine construction.
        sl = np.arange(ns, ns + padded, dtype=np.int64).astype(np.int32)
        hi = np.zeros(padded, dtype=np.uint32)
        li = np.ones(padded, dtype=np.uint32)
        fr = np.zeros(padded, dtype=bool)
        sh = np.zeros(padded, dtype=bool)
        sl[:g] = dedup.uniq_slots
        hi[:g] = dedup.totals_u32()
        li[:g] = dedup.limit_max
        fr[:g] = dedup.fresh

        device_batch = DeviceBatch(
            slots=sl, hits=hi, limits=li, fresh=fr, shadow=sh
        )
        shape = (padded, dt)
        with self._device_call(watch, shape):
            if dt:
                self._counts, afters_dev = (
                    self.model.step_counters_unique_compact(
                        self._counts, dt, device_batch
                    )
                )
            else:
                self._counts, afters_dev = self.model.step_counters_unique(
                    self._counts, device_batch
                )
            afters_dev.copy_to_host_async()
        return afters_dev, None, shape

    def reset(self) -> None:
        """Drop all counters and key assignments (tests)."""
        counts = self.model.init_state()
        if self._device is not None:
            counts = jax.device_put(counts, self._device)
        self._counts = counts  # tpu-lint: disable=shared-state -- reset() is a test/exclusive-access hook; serving never races it
        self.slot_table = self._table_cls(self.model.num_slots)  # tpu-lint: disable=shared-state -- same exclusive-access contract

    # -- checkpoint surface (backends/checkpoint.py) --------------------

    @property
    def algorithm(self) -> str:
        """The model's algorithm-table name (models/registry.py);
        stamped into checkpoints so a restore can never feed one
        kernel's state rows to a different kernel."""
        return getattr(self.model, "algo", "fixed_window")

    def export_state(self) -> dict:
        """Named copy of the per-slot device state.  Fixed-window:
        ``{"counts": uint32[num_slots]}``; generic models expose one
        row per ``model.state_rows`` name.  The counts go out through
        export_counts as import_state takes them in through
        import_counts: a mesh bank overrides the pair to speak GLOBAL
        slot order — the slot table's — not its devices' layout."""
        rows = getattr(self.model, "state_rows", None)
        if rows is None:
            return {"counts": self.export_counts()}
        arr = np.asarray(jax.device_get(self._counts))
        if arr.ndim == 1:
            return {"counts": arr.reshape(-1)}
        return {name: arr[i].copy() for i, name in enumerate(rows)}

    def import_state(self, state: dict) -> None:
        """Inverse of export_state; validates names and shapes."""
        rows = getattr(self.model, "state_rows", None)
        if rows is None or rows == ("counts",):
            self.import_counts(state["counts"])
            return
        ns = self.model.num_slots
        stacked = np.empty((len(rows), ns), dtype=np.uint32)
        for i, name in enumerate(rows):
            arr = np.asarray(state[name], dtype=np.uint32).reshape(-1)
            if arr.shape[0] != ns:
                raise ValueError(
                    f"state row {name!r} size {arr.shape[0]} != "
                    f"num_slots {ns}"
                )
            stacked[i] = arr
        put = jax.numpy.asarray(stacked)
        if self._device is not None:
            put = jax.device_put(put, self._device)
        self._counts = put

    # -- live key-range handoff (cluster/handoff.py) --------------------

    def export_keys(self, pred, drop: bool = True):
        """Export the live keys matching ``pred(key) -> bool`` for a
        counter handoff: returns ``(state, entries)`` where ``state``
        is one column-subset array per export_state row (column i is
        key i's per-slot state) and ``entries`` is ``[(key, expiry),
        ...]``.  With ``drop`` (the default) the exported keys leave
        THIS engine — their slots are zeroed and released — so a key
        that re-homes back later can never resurrect stale state (the
        stable-stem algorithm banks keep slots alive indefinitely
        while hot, so leaving them would not be inert there).

        Must run with exclusive engine access (cache.run_exclusive),
        like every slot-table touch."""
        packed = self.slot_table.export_packed()
        keys = packed.keys()
        mask = np.fromiter((pred(k) for k in keys), dtype=bool, count=len(keys))
        sel = packed.select(mask)
        # Writable copies: device readbacks can come back read-only.
        state = {
            name: np.array(arr, copy=True)
            for name, arr in self.export_state().items()
        }
        out = {name: arr[sel.slots].copy() for name, arr in state.items()}
        if drop and len(sel):
            for arr in state.values():
                arr[sel.slots] = 0
            self.import_state(state)
            self.restore_slot_table(packed.select(~mask))
        sel_keys = [k for k, m in zip(keys, mask.tolist()) if m]
        return out, list(zip(sel_keys, sel.expiries.tolist()))

    def restore_slot_table(self, entries: PackedEntries) -> None:
        """Replace the slot table by one rebuilt from packed entries
        (checkpoint restore, handoff), keeping this bank's table kind
        and, for algorithm banks, its refresh-on-touch lease policy
        (_refresh_table_cls)."""
        table_cls = type(self.slot_table)
        if getattr(self.slot_table, "refresh_expiry", False):
            self.slot_table = table_cls.from_packed(
                self.model.num_slots, entries, refresh_expiry=True
            )
        else:
            self.slot_table = table_cls.from_packed(
                self.model.num_slots, entries
            )

    def import_keys(self, state: dict, entries, now: int) -> dict:
        """Inverse of export_keys, into THIS engine's table: assign a
        local slot per key and land its state columns.  A key already
        live locally (requests raced the handoff window) MERGES
        instead of overwriting: fixed-window ``counts`` add
        (saturating — both sides counted disjoint hits), every other
        row takes the element-wise max (GCRA's later TAT and
        sliding-window's newer window are the stricter/fresher side —
        the conservative direction; a merge may briefly over-deny,
        never over-admit).  Entries whose lease already expired at
        ``now`` are dropped — a stale import cannot resurrect expired
        counters.  Returns {imported, merged, dropped}.

        Must run with exclusive engine access (cache.run_exclusive)."""
        res = {"imported": 0, "merged": 0, "dropped": 0}
        if not entries:
            return res
        full = {
            name: np.array(arr, copy=True)
            for name, arr in self.export_state().items()
        }
        for i, (key, expiry) in enumerate(entries):
            if int(expiry) <= now:
                res["dropped"] += 1
                continue
            slot, fresh = self.slot_table.assign(key, now, int(expiry))
            for name, arr in full.items():
                col = state[name][i]
                if fresh:
                    arr[slot] = col
                elif name == "counts":
                    arr[slot] = min(int(arr[slot]) + int(col), 0xFFFFFFFF)
                else:
                    arr[slot] = max(arr[slot], col)
            res["imported" if fresh else "merged"] += 1
        self.import_state(full)
        return res

    def export_counts(self) -> np.ndarray:
        """Flat uint32 copy of the counter table."""
        return np.asarray(jax.device_get(self._counts)).reshape(-1)

    def import_counts(self, counts: np.ndarray) -> None:
        arr = np.asarray(counts, dtype=np.uint32).reshape(-1)
        if arr.shape[0] != self.model.num_slots:
            raise ValueError(
                f"counts size {arr.shape[0]} != num_slots {self.model.num_slots}"
            )
        put = jax.numpy.asarray(arr)
        if self._device is not None:
            put = jax.device_put(put, self._device)
        self._counts = put
