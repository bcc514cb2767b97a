"""Program spans on the profiler's clock, and the record of background
work.

Two jobs, one name table:

- **Spans.**  ``SPANS.span(name)`` at the seams where the host does a
  launch's work, on the thread that does it.  While a capture runs
  (``SPANS.capture``, behind ``GET /debug/xla_trace``) each is a
  ``jax.profiler.TraceAnnotation`` and lands on its thread's line of
  the ``/host:CPU`` plane of the same ``.xplane.pb`` that holds the
  device's ``XLA Ops`` — so an idle gap on the device can be laid to
  what the host was doing in it (chipbench/host_spans.py).  With no
  capture running, ``span`` returns one shared no-op context manager:
  no object is built, nothing is allocated.  The thread's ROLE is in
  the name (OS thread names do not survive into the xplane); the bank
  and the launch id ride as stats.

- **Background work.**  ``SPANS.background(name)`` wraps what runs a
  few times a minute beside serving — snapshot, checkpoint, incident
  capture, the collector's call tokens — and the periodic ticks.  It
  is a span like the others, and besides: while open it stands in the
  open-work map, which a hang fault copies into its entry
  (``DeviceFaultDomain.record_fault`` -> ``during``); when it ends it
  adds its duration to a per-name total (``/debug/faults``
  ``background.total_ms``) and leaves one ``background_work`` event in
  the lifecycle journal.  Ticks run many times a second, so a tick
  reaches the journal only when it took ``SLOW_TICK_NS`` or more.
  The interpreter's full (generation-2) garbage collections are
  background work too (``watch_gc``): they stop every Python thread
  for as long as they take, on whichever thread tripped them — which
  may be one that holds this module's lock or the journal's, so that
  path takes neither (``_on_gc``).

The capture is light: Python tracer off, host tracer at the lowest
level that still carries ``TraceAnnotation`` (1).  Right after the
profiler starts and right before it stops, the capture emits an instant
span named ``rl.clock.<time.monotonic_ns()>``: the launch records, the
journal and any client's stamps are CLOCK_MONOTONIC, the xplane's
events are relative to the session's start, and the two marks join them.

The profiler session is one per process, and so is ``SPANS`` (the
``TRACER`` precedent); the module imports nothing of JAX until a
capture starts.
"""

from __future__ import annotations

import collections
import contextlib
import gc
import operator
import threading
import time
from typing import Dict, List, Optional

__all__ = [
    "SPANS",
    "ProgramSpans",
    "SPAN_NAMES",
    "BACKGROUND_NAMES",
    "TICK_NAMES",
]

# -- the one table of names (docs/OBSERVABILITY.md and PERF.md copy it) ----

# collector thread (BatchDispatcher._collect / _launch / _collect_loop)
COLLECT_IDLE = "rl.collect.idle"  # blocked for work: nothing to launch
COLLECT_WINDOW = "rl.collect.window"  # waiting out the batch window
LAUNCH = "rl.launch"  # all of _launch; stats: bank, launch_id
LAUNCH_ASSIGN = "rl.launch.assign"  # native slot assign + dedup
LAUNCH_PACK = "rl.launch.pack"  # numpy pack of the device batch
LAUNCH_DEVICE_CALL = "rl.launch.device_call"  # the dispatch: carries the packed lanes, asks for the readback copy
GC = "rl.gc"  # slot-table gc
CALL_TOKEN = "rl.call_token"  # run_on_thread: snapshot / checkpoint grabs
# completer thread (_complete_loop / complete_items / step_complete)
COMPLETE_IDLE = "rl.complete.idle"  # blocked on the completion queue
COMPLETE_READBACK = "rl.complete.readback"  # the wait for the copy the launch asked for
COMPLETE_DECIDE = "rl.complete.decide"  # host threshold machine
COMPLETE_SIGNAL = "rl.complete.signal"  # scatter + event.set() loop
# background threads
BG_SNAPSHOT = "rl.bg.snapshot"  # fault-domain snapshot: token asked -> copy held
BG_SNAPSHOT_GRAB = "rl.bg.snapshot.grab"  # the collector's share of a snapshot or checkpoint grab: device read + packed copy
BG_CHECKPOINT = "rl.bg.checkpoint"  # one bank: children grab / write
BG_CHECKPOINT_GRAB = "rl.bg.checkpoint.grab"
BG_CHECKPOINT_WRITE = "rl.bg.checkpoint.write"
BG_INCIDENT_CAPTURE = "rl.bg.incident_capture"
BG_DETECTOR_TICK = "rl.bg.detector_tick"
BG_TSDB_TICK = "rl.bg.tsdb_tick"
BG_WATCHDOG_TICK = "rl.bg.watchdog_tick"
BG_GC = "rl.bg.gc"  # a generation-2 collection: every Python thread waits
CLOCK_PREFIX = "rl.clock."  # rl.clock.<monotonic_ns>, twice a capture

#: What ``background()`` accepts: the bounded family behind the
#: per-name totals (names are minted here, never from traffic).
TICK_NAMES = (BG_DETECTOR_TICK, BG_TSDB_TICK, BG_WATCHDOG_TICK, BG_GC)
BACKGROUND_NAMES = (
    BG_SNAPSHOT,
    BG_SNAPSHOT_GRAB,
    BG_CHECKPOINT,
    BG_INCIDENT_CAPTURE,
    CALL_TOKEN,
) + TICK_NAMES
SPAN_NAMES = (
    COLLECT_IDLE,
    COLLECT_WINDOW,
    LAUNCH,
    LAUNCH_ASSIGN,
    LAUNCH_PACK,
    LAUNCH_DEVICE_CALL,
    GC,
    COMPLETE_IDLE,
    COMPLETE_READBACK,
    COMPLETE_DECIDE,
    COMPLETE_SIGNAL,
    BG_CHECKPOINT_GRAB,
    BG_CHECKPOINT_WRITE,
) + BACKGROUND_NAMES

#: A periodic tick reaches the journal only when it took this long: a
#: watchdog ticks 8 times a second, and the journal holds 1024 events.
SLOW_TICK_NS = 10_000_000

_NOOP = contextlib.nullcontext()
_START_NS = operator.itemgetter(3)  # of an open-work row


class ProgramSpans:
    """See the module docstring.  ``journal`` (observability/events.py)
    is wired by the runner; None just skips the journal events."""

    def __init__(self):
        # jax.profiler.TraceAnnotation while a capture runs, else None:
        # the one attribute the hot path loads.
        self._annotation = None
        self.journal = None
        self._lock = threading.Lock()
        # (name, thread ident) -> (name, bank, thread name, start_ns)
        self._open: Dict[tuple, tuple] = {}
        self._total_ns = {n: 0 for n in BACKGROUND_NAMES}
        self._count = {n: 0 for n in BACKGROUND_NAMES}
        # The open full collection (_on_gc: lock-free, collections
        # never overlap), and the finished ones the journal has not
        # had yet: (thread name, start_ns, duration_ns).
        self._gc_span = _NOOP
        self._gc_start_ns = 0
        self._gc_done: collections.deque = collections.deque(maxlen=64)

    # -- spans -------------------------------------------------------------

    @property
    def capturing(self) -> bool:
        return self._annotation is not None

    def span(self, name: str, bank: int = -1, launch_id: int = -1):
        """A context manager for one span.  Named parameters, not
        ``**stats``: a keyword call then builds no dict, and with no
        capture running the whole call is one load, one test, one
        return of the shared no-op."""
        annotation = self._annotation
        if annotation is None:
            return _NOOP
        if launch_id >= 0:
            return annotation(name, bank=bank, launch_id=launch_id)
        if bank >= 0:
            return annotation(name, bank=bank)
        return annotation(name)

    # -- background work ----------------------------------------------------

    @contextlib.contextmanager
    def background(self, name: str, bank: int = -1):
        """One background activity: span + open-work entry + per-name
        total + (when it ends) one journal event."""
        if name not in self._total_ns or name == BG_GC:  # _on_gc's alone
            raise ValueError(f"unknown background work {name!r}")
        key = self._begin(name, bank)
        try:
            with self.span(name, bank):
                yield
        finally:
            self._end(key)

    def _begin(self, name: str, bank: int) -> tuple:
        thread = threading.current_thread()
        key = (name, thread.ident)
        row = (name, bank, thread.name, time.monotonic_ns())
        with self._lock:
            self._open[key] = row
        return key

    def _end(self, key: tuple) -> None:
        end = time.monotonic_ns()
        with self._lock:
            row = self._open.pop(key, None)
            if row is None:
                return
            name, bank, thread_name, start = row
            dur = end - start
            self._total_ns[name] += dur
            self._count[name] += 1
        journal = self.journal
        if journal is None:
            return
        if name not in TICK_NAMES or dur >= SLOW_TICK_NS:
            _emit(journal, name, bank, thread_name, start, dur)
        # The collections that ended since the last activity did: no
        # lock of ours is held here, and none of the journal's.
        done = self._gc_done
        while done:
            try:
                thread_name, start, dur = done.popleft()  # tpu-lint: disable=shared-state -- deque append/popleft are GIL-atomic; the gc callback that appends may take no lock
            except IndexError:  # another thread's _end took it
                break
            _emit(journal, BG_GC, -1, thread_name, start, dur)

    def watch_gc(self, on: bool = True) -> None:
        """Record the interpreter's generation-2 collections as
        background work ``rl.bg.gc`` (runner start / stop).  A full
        collection walks every tracked object — 100k rules make
        millions — and holds every Python thread while it does, so a
        device call that returned in microseconds can look stuck for
        as long.  The callback runs on every collection; the young
        generations return at once."""
        if self._on_gc in gc.callbacks:
            gc.callbacks.remove(self._on_gc)
        if on:
            gc.callbacks.append(self._on_gc)

    def _on_gc(self, phase: str, info: dict) -> None:
        """Takes NO lock, here or in the journal: a collection starts
        on whichever thread trips it, at any allocation — also one made
        inside ``_begin`` / ``_end`` / ``open_work`` / ``journal.emit``
        under their (non-reentrant) locks, where taking the same lock
        again would wedge that thread and everyone after it.
        Collections never overlap (the interpreter runs one at a time,
        callbacks included), so plain attributes hold the open one and
        plain stores add to the totals; the journal event waits in
        ``_gc_done`` for the next ordinary ``_end`` (the watchdog's
        tick ends 8 times a second)."""
        if info["generation"] != 2:
            return
        if phase == "start":
            self._gc_start_ns = time.monotonic_ns()
            self._gc_span = self.span(BG_GC)
            self._gc_span.__enter__()
            return
        self._gc_span.__exit__(None, None, None)
        self._gc_span = _NOOP
        start, self._gc_start_ns = self._gc_start_ns, 0
        if not start:  # installed in the middle of a collection
            return
        dur = time.monotonic_ns() - start
        self._total_ns[BG_GC] += dur
        self._count[BG_GC] += 1
        if dur >= SLOW_TICK_NS:
            self._gc_done.append(
                (threading.current_thread().name, start, dur)
            )

    def gc_pause_ns(self) -> int:
        """Nanoseconds this process has spent in full collections so
        far, the open one included — lock-free, like ``_on_gc``.  The
        kernel watchdog takes it when a device call begins and again
        when it judges the call (engine.CallWatch): every Python
        thread waits while a collection runs, the one that would have
        returned from the device call too, so that time is the
        interpreter's and not the device's."""
        total, start = self._total_ns[BG_GC], self._gc_start_ns
        return total + (time.monotonic_ns() - start if start else 0)

    def open_work(self, now_ns: Optional[int] = None) -> List[dict]:
        """What background work is open right now, oldest first — what
        a hang fault records as ``during``."""
        if now_ns is None:
            now_ns = time.monotonic_ns()
        with self._lock:
            rows = sorted(self._open.values(), key=_START_NS)
        return [
            {
                "what": name,
                "bank": bank,
                "thread": thread,
                "start_mono_ns": start,
                "open_s": round((now_ns - start) / 1e9, 3),
            }
            for name, bank, thread, start in rows
        ]

    def summary(self) -> dict:
        """The ``background`` object of ``GET /debug/faults``."""
        with self._lock:
            total = dict(self._total_ns)
            count = dict(self._count)
        return {
            "open": self.open_work(),
            "total_ms": {n: round(v / 1e6, 3) for n, v in total.items()},
            "count": count,
        }

    # -- the capture ----------------------------------------------------------

    def clock_mark(self) -> None:
        """An instant span whose name carries CLOCK_MONOTONIC."""
        with self.span(CLOCK_PREFIX + str(time.monotonic_ns())):
            pass

    def capture(self, trace_dir: str, seconds: float) -> dict:
        """Trace ``seconds`` of this process into ``trace_dir`` with the
        light options; returns how long the profiler's start and stop
        each blocked.  One capture at a time (the caller's gate)."""
        import jax

        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 1
        t0 = time.monotonic_ns()
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        t1 = time.monotonic_ns()
        self._annotation = jax.profiler.TraceAnnotation
        try:
            self.clock_mark()
            time.sleep(seconds)
            self.clock_mark()
        finally:
            self._annotation = None
            t2 = time.monotonic_ns()
            jax.profiler.stop_trace()
            t3 = time.monotonic_ns()
        return {
            "start_trace_ms": round((t1 - t0) / 1e6, 3),
            "stop_trace_ms": round((t3 - t2) / 1e6, 3),
        }


def _emit(journal, name, bank, thread_name, start_ns, dur_ns) -> None:
    journal.emit(
        "background_work",
        what=name,
        bank=bank,
        thread=thread_name,
        start_mono_ns=start_ns,
        duration_ms=round(dur_ns / 1e6, 3),
    )


SPANS = ProgramSpans()
