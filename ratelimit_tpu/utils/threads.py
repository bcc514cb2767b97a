"""Background-thread crash visibility.

A daemon thread that dies from an uncaught exception (sampler,
dispatcher, write-behind flusher, SRV watcher) prints a traceback to
stderr and vanishes — the service limps on degraded and nothing
fails.  ``threading.excepthook`` (3.8+) is the seam: the runner
installs a hook that LOGS the crash loudly, and the test bootstrap
(tests/conftest.py) installs a recording hook so any test whose
background thread dies FAILS instead of passing silently.

The hook CHAINS: the previous hook still runs, so stacking the
recorder on top of the logger (or pytest's own machinery) loses
nothing.

Also here: :class:`ThreadClock`, one thread's on-CPU clock as any
thread of the process can read it.
"""

from __future__ import annotations

import logging
import threading
import time
from typing import Callable, List, Optional


class ThreadClock:
    """The CONSTRUCTING thread's on-CPU clock (CLOCK_THREAD_CPUTIME_ID:
    ns the thread has run, not waited), kept by its clock id so that
    ANOTHER thread can read it: ``ns()``.  The thread itself reads
    ``time.thread_time_ns()``, the same clock.  What a wall-clock
    bracket holds beyond its thread's on-CPU time the thread spent off
    the CPU: asleep on the GIL, on the runtime or the device, or
    runnable and not running.  Passive: a read is a clock read; nothing
    here wakes, samples or locks.  ``ns()`` is None where the platform
    has no per-thread clock id, or the thread is gone."""

    __slots__ = ("_id",)

    def __init__(self):
        try:
            self._id: Optional[int] = time.pthread_getcpuclockid(
                threading.get_ident()
            )
        except (AttributeError, OSError):
            self._id = None

    def ns(self) -> Optional[int]:
        if self._id is None:
            return None
        try:
            return time.clock_gettime_ns(self._id)
        except OSError:
            return None


class ThreadExceptionRecorder:
    """Collects (thread name, exception) pairs from crashed threads.

    ``drain()`` returns and clears the record — tests that
    DELIBERATELY crash a background thread drain it to acknowledge;
    anything left at check time is a failure.
    """

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._records: List[tuple] = []

    def record(self, thread_name: str, exc: BaseException) -> None:
        with self._lock:
            self._records.append((thread_name, exc))

    def drain(self) -> List[tuple]:
        with self._lock:
            out, self._records = self._records, []
            return out

    def pending(self) -> List[tuple]:
        with self._lock:
            return list(self._records)


def install_thread_excepthook(
    on_exception: Optional[Callable[[str, BaseException], None]] = None,
    logger_name: str = "ratelimit.threads",
) -> Callable:
    """Install a chaining ``threading.excepthook``: log the crash at
    ERROR (daemon-thread tracebacks otherwise go to bare stderr and
    get lost in service logs), invoke ``on_exception(thread_name,
    exc)`` if given, then run the PREVIOUS hook.  Returns the
    installed hook (tests compare identity)."""
    previous = threading.excepthook
    log = logging.getLogger(logger_name)

    def hook(args: "threading.ExceptHookArgs") -> None:
        if args.exc_type is SystemExit:
            return  # mirrors the default hook: SystemExit is silent
        name = args.thread.name if args.thread is not None else "?"
        log.error(
            "background thread %r died: %r",
            name,
            args.exc_value,
            exc_info=(args.exc_type, args.exc_value, args.exc_traceback),
        )
        if on_exception is not None:
            try:
                on_exception(name, args.exc_value)
            except Exception:  # the hook must never raise
                log.exception("thread excepthook callback failed")
        # Chain CUSTOM hooks only: re-running the default hook would
        # print the same traceback to stderr a second time.
        if previous is not None and previous not in (
            hook,
            threading.__excepthook__,
        ):
            previous(args)

    threading.excepthook = hook
    return hook
