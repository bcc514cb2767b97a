"""Device-path fault domain: watchdog, bank quarantine with host
fallback, and supervised warm restart.

PR 9 gave the CLUSTER tier a failure envelope (replica circuits,
degraded routing, counter handoff); this module gives each replica's
OWN device path the same treatment.  Before it, a hung kernel launch
stalled every RPC on its lane for ``dispatch_timeout_s`` (120 s by
default) with no recovery, a dead bank stayed dead until a process
restart, and a restart forgave every open window.  Following the
crash-only discipline (Candea & Fox: recovery must be a tested code
path, not an operator runbook), the fault domain makes device failure
a first-class, bounded, self-healing outcome:

- **Watchdog + deadlines.**  A supervisor thread (injectable
  MonotonicClock, deterministic ``tick()`` seam like the PR 5
  detectors) scans every bank's dispatcher: a device call stuck past
  ``KERNEL_DEADLINE_S`` (dispatcher liveness stamps), a dead
  dispatcher thread, or repeated step exceptions classify into
  ``hang`` / ``exception`` / ``device_lost`` faults
  (``ratelimit.tpu.fault.*`` counters).  The deadline binds only
  calls whose kernel shape has completed before: a first-seen shape
  is XLA compilation, not a hang (``BatchDispatcher.stuck_age``).
  Waiting RPCs poll the same stamps (backends/tpu_cache.py
  ``_execute``), so the FIRST request to hit a hang also reports it —
  detection never waits for the next tick.

- **Quarantine + host fallback.**  A faulted bank's dispatcher is
  killed (its queue fast-fails) and its lanes re-route per
  ``DEVICE_FAILURE_MODE``: ``host`` (default) serves them from a
  numpy mirror engine (backends/host_engine.py) seeded with the
  bank's last periodic snapshot — the SAME algorithm semantics,
  counting continues; ``allow``/``deny`` answer statically with zero
  stat deltas.  Fallback-answered requests stamp
  ``FLIGHT_CODE_FALLBACK`` into the flight ring.

- **Supervised warm restart.**  After a backoff the supervisor builds
  a fresh engine + dispatcher for the bank, probes it with synthetic
  traffic (half-open, like the PR 9 replica circuit), imports the
  host mirror's counters (export_keys/import_keys — the cluster
  handoff protocol, intra-process), and atomically swaps it in.
  Restart loss is bounded by one snapshot interval; under mode
  ``host`` the only lost hits are those between the last snapshot and
  the fault.

Health: a quarantined-but-serving replica is DEGRADED, not down — the
fault domain reports through :meth:`HealthChecker.set_degraded` and
only flips NOT_SERVING when no fallback can answer.
"""

from __future__ import annotations

import json
import logging
import threading
import time
from typing import Callable, List, Optional

import numpy as np

from ..observability.launches import OUTCOME_FALLBACK
from ..observability.spans import (
    BG_SNAPSHOT,
    BG_WATCHDOG_TICK,
    SLOW_TICK_NS,
    SPANS,
)
from ..utils.time import REAL_MONOTONIC, MonotonicClock
from .host_engine import STATIC_ALLOW, STATIC_DENY, HostEngine

logger = logging.getLogger("ratelimit.faults")

FAULT_HANG = "hang"
FAULT_EXCEPTION = "exception"
FAULT_DEVICE_LOST = "device_lost"
FAULT_KINDS = (FAULT_HANG, FAULT_EXCEPTION, FAULT_DEVICE_LOST)

MODE_ALLOW = "allow"
MODE_DENY = "deny"
MODE_HOST = "host"
FAILURE_MODES = frozenset({MODE_ALLOW, MODE_DENY, MODE_HOST})

#: Substrings marking an exception as the device itself going away
#: (vs. a bug in a step): jax/XLA runtime errors and the PJRT
#: device-lost vocabulary.
_DEVICE_LOST_MARKERS = (
    "device lost",
    "device_lost",
    "devicelost",
    "xlaruntimeerror",
    "xla runtime",
    "failed to enqueue",
    "internal: device",
)


class KernelDeadlineExceeded(TimeoutError):
    """A device call of an already-proven kernel shape has been stuck
    past KERNEL_DEADLINE_S (BatchDispatcher.stuck_age) — as opposed to
    a plain TimeoutError, which only says some wait ran out."""


def classify_fault(exc: BaseException) -> str:
    """Map an exception from the device path onto the fault taxonomy:
    hang (timeouts), device_lost (the device/runtime went away), or
    exception (everything else — a bug or bad input in a step)."""
    if isinstance(exc, TimeoutError):
        return FAULT_HANG
    text = f"{type(exc).__name__}: {exc}".lower()
    cause = exc.__cause__
    if cause is not None:
        text += f" {type(cause).__name__}: {cause}".lower()
    if any(m in text for m in _DEVICE_LOST_MARKERS):
        return FAULT_DEVICE_LOST
    return FAULT_EXCEPTION


def default_engine_factory(bank: int, old_engine):
    """Rebuild a bank's engine from its predecessor's shape: same
    algorithm model (fresh state), same slot budget, buckets and
    device — or, for a bank striped over a mesh
    (parallel.ShardedCounterEngine), the same mesh."""
    from ..models.registry import get_algorithm
    from .engine import CounterEngine

    mesh = getattr(old_engine.model, "mesh", None)
    if mesh is not None:
        from ..parallel import ShardedCounterEngine

        return ShardedCounterEngine(
            mesh,
            num_slots=old_engine.model.num_slots,
            near_ratio=old_engine.model.near_ratio,
            buckets=tuple(old_engine.buckets),
        )
    algo = getattr(old_engine, "algorithm", "fixed_window")
    model = get_algorithm(algo).make_model(
        old_engine.model.num_slots, old_engine.model.near_ratio
    )
    return CounterEngine(
        model=model,
        buckets=tuple(old_engine.buckets),
        device=getattr(old_engine, "_device", None),
    )


class BankRecord:
    """Per-bank fault-domain state.  ``state`` transitions
    closed -> quarantined -> half_open -> closed; the hot path reads
    it lock-free (string identity check), all transitions happen under
    the domain lock."""

    __slots__ = (
        "bank",
        "role",
        "state",
        "lock",
        "fallback",
        "snapshot",
        "next_snapshot",
        "fault_kind",
        "fault_error",
        "last_hang",
        "quarantined_at",
        "next_restart",
        "backoff_s",
        "restarts",
        "fallback_decisions",
        "fallback_evented",
    )

    def __init__(self, bank: int, role: str):
        self.bank = bank
        self.role = role
        self.state = "closed"
        # Serializes the host mirror (fallback decisions, snapshot
        # seeding, the final export before re-admission).
        self.lock = threading.Lock()
        self.fallback: Optional[HostEngine] = None
        self.snapshot: Optional[tuple] = None  # (state dict, entries)
        self.next_snapshot = 0.0
        self.fault_kind: Optional[str] = None
        self.fault_error: Optional[str] = None
        # The bank's LAST hang fault with what it found beside it
        # (record_fault): {"error", "at_mono_ns", "during": the open
        # background work and both dispatcher threads' device-call
        # state}.  Kept after re-admission — a restart takes two
        # seconds, an operator longer; the journal holds every one.
        self.last_hang: Optional[dict] = None
        self.quarantined_at: Optional[float] = None
        self.next_restart = 0.0
        self.backoff_s = 0.0
        self.restarts = 0
        self.fallback_decisions = 0
        # One bank_fallback journal event per quarantine EPISODE (the
        # per-decision count is a counter, not a timeline entry).
        self.fallback_evented = False


class DeviceFaultDomain:
    """The fault domain around one TpuRateLimitCache's device banks."""

    def __init__(
        self,
        cache,
        kernel_deadline_s: float,
        failure_mode: str = MODE_HOST,
        clock: Optional[MonotonicClock] = None,
        restart_backoff_s: float = 2.0,
        max_restart_backoff_s: float = 60.0,
        snapshot_interval_s: float = 30.0,
        interval_s: Optional[float] = None,
        engine_factory: Optional[Callable] = None,
        probe_count: int = 3,
        probe_timeout_s: Optional[float] = None,
        restart_warmup: bool = True,
    ):
        if failure_mode not in FAILURE_MODES:
            raise ValueError(
                f"DEVICE_FAILURE_MODE must be one of "
                f"{sorted(FAILURE_MODES)}, got {failure_mode!r}"
            )
        if kernel_deadline_s <= 0:
            raise ValueError("kernel_deadline_s must be positive")
        self.cache = cache
        self.kernel_deadline_s = float(kernel_deadline_s)
        self.failure_mode = failure_mode
        self._clock = clock or REAL_MONOTONIC
        self.restart_backoff_s = float(restart_backoff_s)
        self.max_restart_backoff_s = float(max_restart_backoff_s)
        self.snapshot_interval_s = float(snapshot_interval_s)
        # Watchdog cadence: at least twice per deadline so "quarantined
        # within one watchdog deadline" holds even with no traffic.
        self.interval_s = (
            float(interval_s)
            if interval_s is not None
            else min(max(self.kernel_deadline_s / 2.0, 0.05), 1.0)
        )
        self.engine_factory = engine_factory or default_engine_factory
        self.restart_warmup = bool(restart_warmup)
        self.probe_count = int(probe_count)
        self.probe_timeout_s = (
            float(probe_timeout_s)
            if probe_timeout_s is not None
            else max(5.0, 20.0 * self.kernel_deadline_s)
        )
        from .checkpoint import bank_roles

        engines = cache.engines()
        roles = bank_roles(cache)
        #: bank index -> CURRENT engine (kept in sync across swaps so
        #: the hot path resolves swap-safely without rebuilding
        #: cache.engines() per request).
        self._engines: List = list(engines)
        self._records: List[BankRecord] = [
            BankRecord(i, roles[i]) for i in range(len(engines))
        ]
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        # Counters (plain ints bumped under the GIL; scraped as
        # counter_fns like every other backend family).
        self.stat_faults = {k: 0 for k in FAULT_KINDS}
        self.stat_fallback_decisions = 0
        self.stat_restarts = 0
        self.stat_probe_failures = 0
        self.stat_snapshots = 0
        self.stat_snapshot_timeouts = 0  # tokens the collector did not serve in time
        self.stat_gc_excused = 0  # ticks a call was past the deadline only by a collection
        # The watchdog's own lateness (_loop): how late each wake-up
        # it already makes ran against interval_s, summed; how many
        # ran SLOW_TICK_NS or more late; the last one's.  A process
        # that stood still — every thread, this one too — shows here
        # and in no span.
        self.stat_tick_late_ns = 0
        self.stat_ticks_late = 0
        self.last_tick_late_ns = 0
        # Lifecycle event journal (observability/events.py), wired by
        # the runner when EVENT_JOURNAL_SIZE > 0: quarantine entry,
        # first fallback decision of an episode, half-open probes and
        # restart outcomes land on the fleet timeline.  All emissions
        # are transition-path only — never per request.
        self.events = None
        # Launch flight recorder (observability/launches.py), wired via
        # cache.attach_launch_recorder: fallback answers are single-
        # item host-side "launches" and stamp OUTCOME_FALLBACK records
        # so the /debug/launches timeline shows a quarantined bank's
        # traffic instead of going dark.
        self.launches = None

    # -- hot-path surface (backends/tpu_cache.py _execute) --------------

    def is_quarantined(self, bank: int) -> bool:
        return self._records[bank].state != "closed"

    def engine_at(self, bank: int):
        """Swap-safe engine resolve for `bank` (one list index)."""
        return self._engines[bank]

    def run_fallback(self, bank: int, item) -> bool:
        """Answer one bank-bound WorkItem from the failure-mode
        fallback: the host mirror (mode ``host``, under the bank's
        fallback lock) or a static allow/deny synthesizer.  The item
        must carry an UNTOUCHED event (the cache clones items whose
        original event may still be signalled by a stuck completer).

        Returns False — the item untouched — when the bank was
        re-admitted while this request waited for the mirror (a
        restart holds the fallback lock for the whole counter import,
        seconds at a million keys, and every request for the bank
        queues behind it): the mirror is gone and the caller sends the
        item to the device bank instead."""
        from .dispatcher import run_items

        rec = self._records[bank]
        mode = self.failure_mode
        lr = self.launches
        t0 = time.monotonic_ns() if lr is not None else 0
        if mode == MODE_DENY:
            run_items(STATIC_DENY, [item])
        elif mode == MODE_ALLOW:
            run_items(STATIC_ALLOW, [item])
        else:
            with rec.lock:
                mirror = rec.fallback
                if mirror is not None:
                    run_items(mirror, [item])
            if mirror is None:
                return False
        if lr is not None:
            # One OUTCOME_FALLBACK record per fallback answer: a
            # single-item host-side "launch" with the whole duration
            # in complete_ns (there is no device submit leg).
            lr.record(
                bank,
                0,
                item.n_lanes,
                1,
                0,
                0,
                0,
                time.monotonic_ns() - t0,
                OUTCOME_FALLBACK,
                item.corr,
            )
        # The event is already set; wait() applies the deferred slices
        # on THIS thread exactly like a healthy dispatcher completion.
        item.wait(5.0)
        rec.fallback_decisions += 1  # tpu-lint: disable=shared-state -- GIL-atomic stats counter, scrape-only reader
        self.stat_fallback_decisions += 1  # tpu-lint: disable=shared-state -- GIL-atomic stats counter, scrape-only reader
        if self.events is not None and not rec.fallback_evented:
            # First fallback decision of THIS quarantine episode: one
            # timeline entry marking "traffic is now answered by the
            # fallback" (per-decision volume stays in the counters).
            # A racing second emitter is benign — two entries, not a
            # wrong timeline.
            rec.fallback_evented = True  # tpu-lint: disable=shared-state -- GIL-atomic episode flag; duplicate event is benign
            self.events.emit(
                "bank_fallback", bank=bank, mode=self.failure_mode
            )
        return True

    # -- fault intake ----------------------------------------------------

    def record_fault(
        self, bank: int, kind: str, exc: Optional[BaseException] = None
    ) -> None:
        """Quarantine `bank` (idempotent): count + classify the fault,
        seed the host mirror from the last snapshot, kill the bank's
        dispatcher so queued RPCs fast-fail into the fallback, and
        schedule the supervised restart."""
        rec = self._records[bank]
        engine = self._engines[bank]
        during = None
        if kind == FAULT_HANG:
            # Taken FIRST, while whatever the device call is waiting
            # behind may still be open.  The step takes microseconds on
            # the device; a call "stuck" for seconds beside an open
            # snapshot, capture or call token says the host was late,
            # not the device.
            at_ns = time.monotonic_ns()
            during = {"background": SPANS.open_work(at_ns)}
            d = self.cache._dispatchers.get(id(engine))
            if d is not None:
                # With each open bracket's ledger: what the scheduler
                # did to its thread so far, read here from outside it.
                during["threads"] = d.watch_report(
                    watchdog_late_ms=round(self.last_tick_late_ns / 1e6, 3)
                )
        with self._lock:
            if rec.state != "closed":
                return
            self.stat_faults[kind] = self.stat_faults.get(kind, 0) + 1
            now = self._clock.now()
            if self.failure_mode == MODE_HOST:
                host = HostEngine(
                    num_slots=engine.model.num_slots,
                    near_ratio=engine.model.near_ratio,
                    algorithm=getattr(engine, "algorithm", "fixed_window"),
                )
                if rec.snapshot is not None:
                    try:
                        host.import_snapshot(*rec.snapshot)
                    except Exception:
                        logger.exception(
                            "bank %d: seeding host mirror from snapshot "
                            "failed; mirror starts fresh",
                            bank,
                        )
                rec.fallback = host
            rec.fault_kind = kind
            rec.fault_error = repr(exc) if exc is not None else None
            if during is not None:
                rec.last_hang = {
                    "error": rec.fault_error,
                    "at_mono_ns": at_ns,
                    "during": during,
                }
            rec.quarantined_at = now
            rec.backoff_s = self.restart_backoff_s
            rec.next_restart = now + rec.backoff_s
            rec.fallback_evented = False  # new episode, new timeline entry
            if self.events is not None:
                # Stamp the episode marker BEFORE the state flip is
                # visible: request threads emit bank_fallback the
                # moment they observe "quarantined", and the timeline
                # contract (docs/OBSERVABILITY.md) is quarantine ->
                # fallback -> restart in seq/timestamp order.
                self.events.emit(
                    "bank_quarantine",
                    bank=bank,
                    role=rec.role,
                    kind=kind,
                    error=rec.fault_error,
                    failure_mode=self.failure_mode,
                    **({"during": during} if during is not None else {}),
                )
            rec.state = "quarantined"
        d = self.cache._dispatchers.get(id(engine))
        if d is not None and d.dead is None:
            d.kill(
                RuntimeError(
                    f"bank {bank} ({rec.role}) quarantined: {kind} fault"
                )
            )
        self._report_health()
        logger.error(
            "device bank %d (%s) quarantined: %s fault (%s); failure "
            "mode %s, restart in %.1fs%s",
            bank,
            rec.role,
            kind,
            rec.fault_error,
            self.failure_mode,
            rec.backoff_s,
            # The witness goes to the log too: a run that ends before
            # anyone asks /debug/faults keeps its ledger there.
            f"; during {json.dumps(during)}" if during is not None else "",
        )

    def snapshot_entries(self) -> int:
        """Slot-table entries the banks' current snapshots hold."""
        snaps = [rec.snapshot for rec in self._records]
        return sum(len(snap[1]) for snap in snaps if snap is not None)

    def quarantined_count(self) -> int:
        return sum(1 for r in self._records if r.state != "closed")

    def mirror_snapshot(self, bank: int):
        """A consistent (state, entries) copy of a quarantined bank's
        host mirror, or None when the failure mode carries no mirror —
        the on-disk checkpointer's source while the bank is down
        (checkpoint.CheckpointManager.checkpoint)."""
        rec = self._records[bank]
        with rec.lock:
            if rec.fallback is None:
                return None
            return (
                rec.fallback.export_state(),
                rec.fallback.slot_table.export_packed(),
            )

    def _report_health(self) -> None:
        health = getattr(self.cache, "_health", None)
        if health is None or not hasattr(health, "set_degraded"):
            return
        n = self.quarantined_count()
        if n:
            health.set_degraded(
                True, f"{n} device bank(s) quarantined, serving via "
                f"{self.failure_mode} fallback"
            )
        else:
            health.set_degraded(False, "all device banks closed")

    # -- watchdog / supervisor ------------------------------------------

    def tick(self, now: Optional[float] = None) -> None:
        """One watchdog+supervisor pass: detect hung/dead dispatchers,
        take due snapshots, attempt due restarts.  Deterministic seam
        for tests (drive it with a FakeMonotonicClock); the background
        thread calls it every ``interval_s``."""
        if now is None:
            now = self._clock.now()
        for bank, rec in enumerate(self._records):
            if rec.state == "closed":
                self._watch_bank(bank, rec, now)
            elif rec.state == "quarantined" and now >= rec.next_restart:
                self._try_restart(bank, rec, now)

    def _watch_bank(self, bank: int, rec: BankRecord, now: float) -> None:
        engine = self._engines[bank]
        d = self.cache._dispatchers.get(id(engine))
        if d is None:
            return
        if d.dead is not None:
            self.record_fault(bank, classify_fault(d.dead), d.dead)
            return
        d.glance()
        stuck = d.stuck_age(now)
        if stuck > self.kernel_deadline_s:
            self.record_fault(bank, FAULT_HANG, self.hang_error(stuck))
            return
        if d.stuck_age(now, excuse_gc=False) > self.kernel_deadline_s:
            # Open past the deadline only by the length of a full
            # collection, which held this thread as well: not a hang.
            self.stat_gc_excused += 1  # tpu-lint: disable=shared-state -- GIL-atomic stats counter, single supervisor writer
        if self.snapshot_interval_s > 0 and now >= rec.next_snapshot:
            self._snapshot_bank(bank, rec, d, now)

    def hang_error(self, stuck_s: float) -> "KernelDeadlineExceeded":
        """The exception a hang fault is recorded with (watchdog tick
        and RPC wait alike)."""
        return KernelDeadlineExceeded(
            f"device call stuck {stuck_s:.3f}s "
            f"(> kernel deadline {self.kernel_deadline_s:.3f}s)"
        )

    def snapshot_now(self, bank: Optional[int] = None) -> int:
        """Force an immediate snapshot of one bank (or all closed
        banks); returns how many were taken.  The chaos harness uses
        this to pin the restart-loss envelope exactly."""
        taken = 0
        now = self._clock.now()
        for i, rec in enumerate(self._records):
            if bank is not None and i != bank:
                continue
            if rec.state != "closed":
                continue
            d = self.cache._dispatchers.get(id(self._engines[i]))
            if d is None:
                continue
            before = self.stat_snapshots
            self._snapshot_bank(i, rec, d, now)
            taken += self.stat_snapshots - before
        return taken

    def _snapshot_bank(self, bank: int, rec: BankRecord, d, now: float):
        """Async periodic snapshot (state copy on the dispatcher
        thread, like CheckpointManager.checkpoint) — the seed for the
        host mirror, bounding restart loss to one interval.  A timeout
        here is NOT treated as a fault (a deep-but-moving queue can
        legitimately delay the token); the stuck-stamp check catches
        real hangs."""
        from .checkpoint import snapshot_engine

        engine = self._engines[bank]
        grabbed = {}

        def grab():
            grabbed["snap"] = snapshot_engine(engine, bank)

        try:
            # From asking for the token to holding the copy: the wait
            # behind the collector's queue AND the grab on it (which is
            # rl.call_token there).
            with SPANS.background(BG_SNAPSHOT, bank):
                d.run_on_thread(
                    grab, timeout=max(1.0, 4.0 * self.kernel_deadline_s)
                )
        except TimeoutError:
            self.stat_snapshot_timeouts += 1  # tpu-lint: disable=shared-state -- GIL-atomic stats counter, single supervisor writer
            logger.warning(
                "bank %d: snapshot token not served in time (queue "
                "backlog?); retrying next interval",
                bank,
            )
            rec.next_snapshot = now + self.snapshot_interval_s
            return
        except Exception as e:
            self.record_fault(bank, classify_fault(e), e)
            return
        snap = grabbed.get("snap")
        if snap is not None:
            rec.snapshot = snap
            rec.next_snapshot = now + self.snapshot_interval_s
            self.stat_snapshots += 1  # tpu-lint: disable=shared-state -- GIL-atomic stats counter, single supervisor writer

    def _try_restart(self, bank: int, rec: BankRecord, now: float) -> None:
        """One supervised warm-restart attempt: fresh engine + probe
        (half-open) -> import the host mirror's counters -> swap."""
        engine = self._engines[bank]
        try:
            new_engine = self.engine_factory(bank, engine)
            if self.restart_warmup:
                # Pre-compile the serving shapes OFF the serving path:
                # a cold engine's first post-swap batches would stall
                # their RPCs for each shape's XLA compilation.
                from .tpu_cache import warmup_engine

                warmup_engine(new_engine)
        except Exception as factory_exc:
            logger.exception(
                "bank %d: engine factory failed; staying quarantined",
                bank,
            )
            self._backoff(rec, now)
            if self.events is not None:
                self.events.emit(
                    "bank_restart_failed",
                    bank=bank,
                    stage="factory",
                    error=repr(factory_exc),
                    next_attempt_in_s=round(rec.backoff_s, 3),
                )
            return
        new_disp = self.cache._make_dispatcher(
            new_engine, name=f"tpu-dispatcher-restart{bank}-{rec.restarts}"
        )
        rec.state = "half_open"
        if self.events is not None:
            self.events.emit(
                "bank_half_open", bank=bank, attempt=rec.restarts + 1
            )
        ok = False
        try:
            ok = self._probe(bank, rec, new_engine, new_disp)
        except Exception:
            logger.exception("bank %d: restart probe crashed", bank)
        if not ok:
            self.stat_probe_failures += 1  # tpu-lint: disable=shared-state -- GIL-atomic stats counter, single supervisor writer
            rec.state = "quarantined"
            self._backoff(rec, now)
            new_disp.kill(RuntimeError("restart probe failed"))
            logger.error(
                "bank %d: restart probe failed; next attempt in %.1fs",
                bank,
                rec.backoff_s,
            )
            if self.events is not None:
                self.events.emit(
                    "bank_restart_failed",
                    bank=bank,
                    stage="probe",
                    next_attempt_in_s=round(rec.backoff_s, 3),
                )
            return
        # Probe passed: merge the mirror's counters and re-admit.  The
        # bank's fallback lock closes the window between export and
        # swap so no fallback decision is lost.
        with rec.lock:
            if rec.fallback is not None:
                state, entries = rec.fallback.export_keys(
                    lambda _k: True, drop=True
                )
                wall_now = self.cache.time_source.unix_now()

                def merge():
                    new_engine.import_keys(state, entries, wall_now)

                try:
                    new_disp.run_on_thread(merge, timeout=30.0)
                except Exception:
                    logger.exception(
                        "bank %d: importing mirror counters failed; "
                        "re-admitting with snapshot-only state",
                        bank,
                    )
            with self._lock:
                self.cache._swap_bank(bank, new_engine, new_disp)
                self._engines[bank] = new_engine
                rec.fallback = None
                rec.snapshot = None
                rec.next_snapshot = now  # re-seed on the next tick
                rec.fault_kind = None
                rec.fault_error = None
                rec.quarantined_at = None
                rec.backoff_s = 0.0
                rec.restarts += 1
                rec.state = "closed"
        self.stat_restarts += 1  # tpu-lint: disable=shared-state -- GIL-atomic stats counter, single supervisor writer
        self._report_health()
        logger.warning(
            "device bank %d (%s) re-admitted after supervised warm "
            "restart (restart #%d)",
            bank,
            rec.role,
            rec.restarts,
        )
        if self.events is not None:
            self.events.emit(
                "bank_restart", bank=bank, restarts=rec.restarts
            )

    def _backoff(self, rec: BankRecord, now: float) -> None:
        rec.backoff_s = min(
            max(rec.backoff_s * 2.0, self.restart_backoff_s),
            self.max_restart_backoff_s,
        )
        rec.next_restart = now + rec.backoff_s

    def _probe(self, bank: int, rec: BankRecord, engine, disp) -> bool:
        """Half-open probe: synthetic traffic through the NEW
        dispatcher must complete within the probe timeout and answer
        OK.  Probe keys live in a reserved namespace with a huge limit
        so they can never collide with (or deny) real traffic."""
        from ..models.registry import get_algorithm
        from .dispatcher import LANE_DTYPE, LanePack, WorkItem

        spec = get_algorithm(getattr(engine, "algorithm", "fixed_window"))
        generic = spec.name != "fixed_window"
        wall_now = self.cache.time_source.unix_now()
        for i in range(self.probe_count):
            key = f"__fault_probe__/{bank}/{rec.restarts}/{i}"
            kb = key.encode("utf-8")
            meta = np.zeros(1, dtype=LANE_DTYPE)
            meta[0] = (
                wall_now + 120,  # expiry
                1,  # hits
                1_000_000,  # limit: the probe must never deny itself
                len(kb),
                0,  # shadow
                60 if generic else 0,  # divider
                spec.algo_id,
            )
            got = {}

            def apply(decisions, got=got):
                got["codes"] = np.asarray(decisions.codes).tolist()

            item = WorkItem(
                now=wall_now,
                lanes=(),
                pack=LanePack(key_blob=kb, meta=meta),
                apply=apply,
                defer_apply=True,
            )
            try:
                disp.submit(item)
                item.wait(self.probe_timeout_s)
            except Exception as e:
                logger.warning(
                    "bank %d: probe %d failed: %r", bank, i, e
                )
                return False
            if got.get("codes") != [1]:  # api.Code.OK
                logger.warning(
                    "bank %d: probe %d answered %s, not OK",
                    bank,
                    i,
                    got.get("codes"),
                )
                return False
        return True

    # -- lifecycle / observability --------------------------------------

    def start(self) -> None:
        if self._thread is not None or self.interval_s <= 0:
            return
        self._stop.clear()  # start after a stop: TpuRateLimitCache.warmup
        self._thread = threading.Thread(
            target=self._loop, name="device-supervisor", daemon=True
        )
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        t, self._thread = self._thread, None
        if t is not None:
            t.join(timeout=5)

    def _loop(self) -> None:
        interval_ns = int(self.interval_s * 1e9)
        due_ns = time.monotonic_ns() + interval_ns
        while not self._stop.wait(self.interval_s):
            late_ns = max(0, time.monotonic_ns() - due_ns)
            self.last_tick_late_ns = late_ns  # tpu-lint: disable=shared-state -- GIL-atomic stats, single supervisor writer
            self.stat_tick_late_ns += late_ns  # tpu-lint: disable=shared-state -- same
            if late_ns >= SLOW_TICK_NS:
                self.stat_ticks_late += 1  # tpu-lint: disable=shared-state -- same
            try:
                with SPANS.background(BG_WATCHDOG_TICK):
                    self.tick()
            except Exception:
                logger.exception("device-supervisor tick failed")
            due_ns = time.monotonic_ns() + interval_ns

    def register_stats(self, store, scope: str = "ratelimit.tpu.fault"):
        """The bounded fault family: per-kind fault counters, fallback
        decisions, restarts/probe failures/snapshots, and the
        quarantined-bank gauge."""
        for kind in FAULT_KINDS:
            store.counter_fn(
                scope + "." + kind, lambda k=kind: self.stat_faults[k]
            )
        store.counter_fn(
            scope + ".fallback_decisions",
            lambda: self.stat_fallback_decisions,
        )
        store.counter_fn(scope + ".restarts", lambda: self.stat_restarts)
        store.counter_fn(
            scope + ".probe_failures", lambda: self.stat_probe_failures
        )
        store.counter_fn(scope + ".snapshots", lambda: self.stat_snapshots)
        store.counter_fn(
            scope + ".snapshot_timeouts", lambda: self.stat_snapshot_timeouts
        )
        store.gauge_fn(scope + ".snapshot_entries", self.snapshot_entries)
        store.counter_fn(scope + ".gc_excused", lambda: self.stat_gc_excused)
        store.counter_fn(
            scope + ".tick_late_ms",
            lambda: self.stat_tick_late_ns // 1_000_000,
        )
        store.counter_fn(scope + ".ticks_late", lambda: self.stat_ticks_late)
        store.gauge_fn(
            scope + ".quarantined_banks", lambda: self.quarantined_count()
        )

    def summary(self) -> dict:
        """The /debug/faults JSON body."""
        from .engine import device_report

        now = self._clock.now()
        banks = []
        for rec in self._records:
            b = {
                "bank": rec.bank,
                "role": rec.role,
                "state": rec.state,
                "restarts": rec.restarts,
                "fallback_decisions": rec.fallback_decisions,
                "has_snapshot": rec.snapshot is not None,
                **self._engines[rec.bank].placement(),
            }
            if rec.last_hang is not None:
                b["last_hang"] = rec.last_hang
            if rec.state != "closed":
                b["fault_kind"] = rec.fault_kind
                b["fault_error"] = rec.fault_error
                if rec.quarantined_at is not None:
                    b["quarantined_for_s"] = round(
                        now - rec.quarantined_at, 3
                    )
                b["next_restart_in_s"] = round(
                    max(0.0, rec.next_restart - now), 3
                )
                if rec.fallback is not None:
                    b["mirror_live_keys"] = rec.fallback.stat_live_keys
            banks.append(b)
        return {
            "device": device_report(),
            "kernel_deadline_s": self.kernel_deadline_s,
            "failure_mode": self.failure_mode,
            "snapshot_interval_s": self.snapshot_interval_s,
            "faults": dict(self.stat_faults),
            "fallback_decisions": self.stat_fallback_decisions,
            "restarts": self.stat_restarts,
            "probe_failures": self.stat_probe_failures,
            "snapshots": self.stat_snapshots,
            "snapshot_timeouts": self.stat_snapshot_timeouts,
            "snapshot_entries": self.snapshot_entries(),
            "gc_excused": self.stat_gc_excused,
            "tick_late_ms": round(self.stat_tick_late_ns / 1e6, 3),
            "ticks_late": self.stat_ticks_late,
            "quarantined_banks": self.quarantined_count(),
            # What runs beside serving (observability/spans.py): open
            # now, and per activity the time and count since start.
            "background": SPANS.summary(),
            "banks": banks,
        }
