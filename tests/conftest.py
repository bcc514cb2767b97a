"""Test bootstrap: force JAX onto a virtual 8-device CPU mesh.

Tests never require real TPU hardware; sharded-engine tests use
8 virtual CPU devices (mirrors how the reference tests run against
local redis processes instead of production clusters).
Must run before anything imports jax.

Two failure-visibility layers ride along (docs/STATIC_ANALYSIS.md):

- ``TPU_SANITIZE=1`` activates the runtime lock sanitizer BEFORE any
  application module allocates a lock; lock-order cycles or blocking
  calls under a held lock observed anywhere in the run fail the whole
  session (``make sanitize``).
- ``threading.excepthook`` records background-thread crashes; the
  autouse fixture fails the OWNING test instead of letting a dead
  sampler/dispatcher thread pass silently.  Tests that deliberately
  crash a thread call ``thread_exceptions.drain()`` to acknowledge.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# The sanitizer must patch threading.Lock/RLock before ANY application
# import allocates module-level locks (trace._rand_lock et al.), so
# this block precedes every ratelimit_tpu import — including the
# transitive ones below.  Pure stdlib: importing it pulls in no jax.
from ratelimit_tpu.analysis import sanitizer as _sanitizer  # noqa: E402

if _sanitizer.enabled_by_env():
    _sanitizer.install(
        raise_on_violation=os.environ.get("TPU_SANITIZE_RAISE", "")
        not in ("", "0")
    )

import pytest  # noqa: E402

from ratelimit_tpu.stats.manager import Manager  # noqa: E402
from ratelimit_tpu.utils.threads import (  # noqa: E402
    ThreadExceptionRecorder,
    install_thread_excepthook,
)
from ratelimit_tpu.utils.time import PinnedTimeSource  # noqa: E402

# Historical alias: the pinned clock is now first-class in
# ratelimit_tpu.utils.time (injected through the Runner's clock seam).
FakeTimeSource = PinnedTimeSource

#: Session-wide recorder: a background thread dying during ANY test
#: must fail THAT test (reference repos get this from `go test`'s
#: panic propagation; Python daemon threads just print and vanish).
THREAD_EXCEPTIONS = ThreadExceptionRecorder()
install_thread_excepthook(THREAD_EXCEPTIONS.record)


@pytest.fixture
def clock():
    return FakeTimeSource(1234)


@pytest.fixture
def stats_manager():
    return Manager()


@pytest.fixture
def thread_exceptions():
    """Handle to the crash recorder: tests that deliberately kill a
    background thread drain it to acknowledge the crash."""
    return THREAD_EXCEPTIONS


@pytest.fixture(autouse=True)
def _fail_on_thread_exceptions():
    """Any UNACKNOWLEDGED background-thread crash fails the test that
    owned it."""
    THREAD_EXCEPTIONS.drain()  # a prior test's leftovers are not ours
    yield
    crashed = THREAD_EXCEPTIONS.drain()
    if crashed:
        lines = ", ".join(f"{name}: {exc!r}" for name, exc in crashed)
        pytest.fail(
            f"background thread(s) died during this test: {lines} "
            "(use the thread_exceptions fixture and drain() if the "
            "crash is deliberate)"
        )


def pytest_sessionfinish(session, exitstatus):
    """Under TPU_SANITIZE=1, lock-order cycles or blocking-under-lock
    observed ANYWHERE in the run fail the session."""
    if _sanitizer.enabled_by_env():
        s = _sanitizer.get()
        if s.violations():
            print("\n" + s.format_report())
            session.exitstatus = 1
