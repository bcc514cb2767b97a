"""Host mirror engine (backends/host_engine.py): the fallback must
speak the device kernels' exact semantics.

Parity is asserted against the REAL CounterEngine on the same traffic
(duplicate keys, shadow lanes, multiple steps): decision fields are
identical for fixed-window (the device's narrow readback clamps raw
befores in the fully-over branch, which is decision-invariant by the
step_counters_compact argument), and fully identical for the generic
kernels (their readback is never clamped).
"""

import numpy as np
import pytest

from ratelimit_tpu.backends.dispatcher import LANE_DTYPE
from ratelimit_tpu.backends.engine import CounterEngine
from ratelimit_tpu.backends.host_engine import (
    STATIC_ALLOW,
    STATIC_DENY,
    HostEngine,
    StaticFallbackEngine,
)
from ratelimit_tpu.models.registry import get_algorithm

DECISION_FIELDS = (
    "codes",
    "limit_remaining",
    "over_limit",
    "near_limit",
    "within_limit",
    "shadow_mode",
    "set_local_cache",
)


def _meta(rows):
    """rows: [(key, hits, limit, shadow, divider, algo_id)] -> blob+meta."""
    enc = [k.encode() for k, *_ in rows]
    meta = np.zeros(len(rows), LANE_DTYPE)
    for j, ((_k, hits, limit, shadow, divider, algo), b) in enumerate(
        zip(rows, enc)
    ):
        meta[j] = (2_000_000_000, hits, limit, len(b), shadow, divider, algo)
    return b"".join(enc), meta


def _run(engine, now, blob, meta):
    return engine.step_complete(engine.submit_packed(now, blob, meta.copy()))


def test_fixed_window_decision_parity():
    rng = np.random.default_rng(7)
    dev = CounterEngine(num_slots=128, buckets=(32,))
    host = HostEngine(num_slots=128)
    for step in range(10):
        rows = [
            (
                f"k{rng.integers(0, 12)}",
                int(rng.integers(1, 4)),
                int(rng.integers(1, 25)),
                int(rng.integers(0, 2)),
                0,
                0,
            )
            for _ in range(30)
        ]
        blob, meta = _meta(rows)
        d1 = _run(dev, 1000, blob, meta)
        d2 = _run(host, 1000, blob, meta)
        for f in DECISION_FIELDS:
            np.testing.assert_array_equal(
                np.asarray(getattr(d1, f)),
                np.asarray(getattr(d2, f)),
                err_msg=f"step {step} field {f}",
            )


@pytest.mark.parametrize("algo", ["sliding_window", "gcra"])
def test_generic_kernel_full_parity(algo):
    rng = np.random.default_rng(13)
    spec = get_algorithm(algo)
    dev = CounterEngine(
        num_slots=128, buckets=(32,), model=spec.make_model(128, 0.8)
    )
    host = HostEngine(num_slots=128, algorithm=algo)
    lims = [2, 3, 7, 11, 13, 17, 30, 45, 59, 60]  # most do not divide the window
    for step in range(10):
        rows = [
            (
                f"k{rng.integers(0, 10)}",
                int(rng.integers(1, 3)),
                int(lims[rng.integers(0, len(lims))]),
                0,
                60,
                spec.algo_id,
            )
            for _ in range(24)
        ]
        blob, meta = _meta(rows)
        now = 1_700_000_040 + 13 * step
        d1 = _run(dev, now, blob, meta)
        d2 = _run(host, now, blob, meta)
        for f in DECISION_FIELDS + ("befores", "afters"):
            np.testing.assert_array_equal(
                np.asarray(getattr(d1, f)),
                np.asarray(getattr(d2, f)),
                err_msg=f"{algo} step {step} field {f}",
            )


def test_mirror_counters_import_into_device_engine():
    """The warm-restart merge: counts accumulated on the mirror keep
    limiting after export_keys -> device import_keys."""
    host = HostEngine(num_slots=64)
    rows = [("hot", 1, 10, 0, 0, 0)] * 7
    blob, meta = _meta(rows)
    _run(host, 1000, blob, meta)  # 7 hits on "hot"
    state, entries = host.export_keys(lambda _k: True, drop=True)
    assert len(entries) == 1 and len(host.slot_table) == 0

    dev = CounterEngine(num_slots=64, buckets=(8,))
    res = dev.import_keys(state, entries, now=1000)
    assert res == {"imported": 1, "merged": 0, "dropped": 0}
    # 7 already counted; 3 more admit, the 11th is over.
    rows = [("hot", 1, 10, 0, 0, 0)] * 4
    blob, meta = _meta(rows)
    d = _run(dev, 1000, blob, meta)
    assert list(np.asarray(d.codes)) == [1, 1, 1, 2]


def test_import_snapshot_seeds_mirror():
    src = HostEngine(num_slots=64)
    blob, meta = _meta([("a", 5, 10, 0, 0, 0), ("b", 2, 10, 0, 0, 0)])
    _run(src, 1000, blob, meta)
    snap = (src.export_state(), src.slot_table.export_packed())

    mirror = HostEngine(num_slots=64)
    assert mirror.import_snapshot(*snap) == 2
    # "a" has 5 counted: 5 more admit, the 11th is over.
    blob, meta = _meta([("a", 1, 10, 0, 0, 0)] * 6)
    d = _run(mirror, 1000, blob, meta)
    assert list(np.asarray(d.codes)) == [1, 1, 1, 1, 1, 2]


def test_snapshot_num_slots_mismatch_refused():
    src = HostEngine(num_slots=64)
    mirror = HostEngine(num_slots=32)
    with pytest.raises(ValueError, match="num_slots"):
        mirror.import_snapshot(src.export_state(), src.slot_table.export_packed())


def test_static_allow_answers_ok_with_zero_stats():
    blob, meta = _meta([("x", 1, 42, 0, 0, 0), ("y", 3, 7, 1, 0, 0)])
    d = STATIC_ALLOW.step_complete(STATIC_ALLOW.submit_packed(0, blob, meta))
    assert list(np.asarray(d.codes)) == [1, 1]
    assert list(np.asarray(d.limit_remaining)) == [42, 7]
    for f in ("over_limit", "near_limit", "within_limit", "shadow_mode"):
        assert not np.asarray(getattr(d, f)).any(), f
    assert not np.asarray(d.set_local_cache).any()


def test_static_deny_answers_over_limit_except_shadow():
    blob, meta = _meta([("x", 1, 42, 0, 0, 0), ("y", 1, 7, 1, 0, 0)])
    d = STATIC_DENY.step_complete(STATIC_DENY.submit_packed(0, blob, meta))
    # Shadow rules never enforce, even under fail-closed deny.
    assert list(np.asarray(d.codes)) == [2, 1]
    assert list(np.asarray(d.limit_remaining)) == [0, 0]
    for f in ("over_limit", "near_limit", "within_limit", "shadow_mode"):
        assert not np.asarray(getattr(d, f)).any(), f


def test_static_engines_are_stateless():
    eng = StaticFallbackEngine(allow=False)
    blob, meta = _meta([("x", 1, 5, 0, 0, 0)])
    for _ in range(3):
        d = eng.step_complete(eng.submit_packed(0, blob, meta))
        assert list(np.asarray(d.codes)) == [2]
    assert eng.stat_decisions == 3
