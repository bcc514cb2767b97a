"""Snapshots and checkpoints as arrays (slot_table.PackedEntries): the
native and the Python table against the tuple oracle, through a file
and back.  (The watchdog's rule about collections and the snapshot
counters: tests/test_fault_domain.py.)"""

import numpy as np
import pytest

from ratelimit_tpu.api import Code, Descriptor, RateLimitRequest
from ratelimit_tpu.backends import native_slot_table
from ratelimit_tpu.backends.checkpoint import (
    restore_engine,
    snapshot_engine,
    write_snapshot,
)
from ratelimit_tpu.backends.engine import CounterEngine
from ratelimit_tpu.backends.slot_table import PackedEntries, SlotTable
from ratelimit_tpu.backends.tpu_cache import TpuRateLimitCache
from ratelimit_tpu.config.loader import ConfigFile, load_config
from ratelimit_tpu.stats.manager import Manager
from ratelimit_tpu.utils.time import PinnedTimeSource

needs_native = pytest.mark.skipif(
    not native_slot_table.available(), reason="native slot table not built"
)
TABLES = [pytest.param(True, marks=needs_native, id="native"), pytest.param(False, id="python")]

YAML = """
domain: d
descriptors:
  - key: k
    rate_limit:
      unit: hour
      requests_per_unit: 20
"""
# utf-8 of 1, 2, 3 and 4 bytes a character, and an empty value
VALUES = ["plain", "é", "ключ", "日本語", "🙂x", "a_b-c.9", ""]


def _drive(native: bool):
    """An engine behind the served cache after hits on VALUES (value i
    hit i + 1 times): (cache, engine)."""
    rule = load_config([ConfigFile("config.c", YAML)], Manager()).get_limit(
        "d", Descriptor.of(("k", "x"))
    )
    engine = CounterEngine(num_slots=64, buckets=(8,), native_table=native)
    cache = TpuRateLimitCache(engine, time_source=PinnedTimeSource(7200))
    for i, value in enumerate(VALUES):
        req = RateLimitRequest("d", [Descriptor.of(("k", value))], 1)
        for _ in range(i + 1):
            assert cache.do_limit(req, [rule])[0].code is Code.OK
    return cache, engine


@pytest.mark.parametrize("native", TABLES)
def test_packed_snapshot_round_trip_matches_the_tuple_oracle(native, tmp_path):
    """packed export -> snapshot -> write_snapshot -> restore: the same
    keys, slots, expiries and counts as the Python table's tuples."""
    oracle_cache, oracle = _drive(native=False)
    cache, engine = _drive(native)
    try:
        want = sorted(oracle.slot_table.entries())
        assert len(want) == len(VALUES)
        state, packed = snapshot_engine(engine)
        assert isinstance(packed, PackedEntries)
        assert sorted(packed.tuples()) == want
        path = str(tmp_path / "bank0.npz")
        write_snapshot(path, 64, state, packed, "lane0of1")
        fresh = CounterEngine(num_slots=64, buckets=(8,), native_table=native)
        assert restore_engine(fresh, path, "lane0of1")
        assert type(fresh.slot_table) is type(engine.slot_table)
        assert sorted(fresh.slot_table.export_packed().tuples()) == want
        np.testing.assert_array_equal(fresh.export_counts(), oracle.export_counts())
        by_key = {k: int(fresh.export_counts()[s]) for k, s, _ in want}
        assert sorted(by_key.values()) == list(range(1, len(VALUES) + 1))
        # A known key keeps its slot, a new one gets a free slot.
        slot, is_fresh = fresh.slot_table.assign(want[0][0], 7200, want[0][2])
        assert (slot, is_fresh) == (want[0][1], False)
    finally:
        cache.close()
        oracle_cache.close()


def test_packed_entries_adapters_and_shape_check():
    tuples = [("ключ", 3, 100), ("", 0, 50), ("日本", 7, 75)]
    packed = PackedEntries.from_tuples(tuples)
    assert len(packed) == 3 and packed.tuples() == tuples
    assert packed.select(np.array([True, False, True])).tuples() == [tuples[0], tuples[2]]
    assert len(PackedEntries.from_tuples([])) == 0
    table = SlotTable.from_packed(8, packed, refresh_expiry=True)
    assert sorted(table.export_packed().tuples()) == sorted(tuples)
    assert table.refresh_expiry
    with pytest.raises(ValueError, match="disagree"):  # a blob one byte short
        PackedEntries(packed.key_blob[:-1], packed.key_lens, packed.slots, packed.expiries)
    with pytest.raises(ValueError, match="disagree"):
        PackedEntries(packed.key_blob, packed.key_lens, packed.slots[:2], packed.expiries)


@pytest.mark.parametrize("native", TABLES)
def test_corrupt_key_lengths_refuse_the_restore(native, tmp_path):
    """Lengths that overrun the blob would send C++ reading past it:
    the file is refused and the engine starts fresh."""
    cache, engine = _drive(native)
    try:
        state, packed = snapshot_engine(engine)
    finally:
        cache.close()
    bad = object.__new__(PackedEntries)
    for name in ("key_blob", "slots", "expiries"):
        object.__setattr__(bad, name, getattr(packed, name))
    object.__setattr__(bad, "key_lens", packed.key_lens + 1000)
    path = str(tmp_path / "bank0.npz")
    write_snapshot(path, 64, state, bad, "lane0of1")
    fresh = CounterEngine(num_slots=64, buckets=(8,), native_table=native)
    assert not restore_engine(fresh, path, "lane0of1")
    assert len(fresh.slot_table) == 0
