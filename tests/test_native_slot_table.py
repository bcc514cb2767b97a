"""Native (C++) slot table vs the Python oracle.

The Python SlotTable in backends/slot_table.py is the behavioral spec;
the native table must match it operation-for-operation, including
eviction order, gc, batch pinning, and checkpoint export/import.
"""

import numpy as np
import pytest

from ratelimit_tpu.backends import native_slot_table
from ratelimit_tpu.backends.slot_table import PackedEntries, SlotTable

pytestmark = pytest.mark.skipif(
    not native_slot_table.available(), reason="no C++ toolchain"
)


def make_pair(n=16):
    return SlotTable(n), native_slot_table.NativeSlotTable(n)


def test_basic_assign_and_duplicate():
    py, nat = make_pair()
    for table in (py, nat):
        slots, fresh = table.assign_batch(["a", "b", "a"], 0, [10, 20, 10])
        assert list(fresh) == [True, True, False]
        assert slots[0] == slots[2] != slots[1]
        assert len(table) == 2


def test_differential_random_workload():
    rng = np.random.default_rng(17)
    py, nat = make_pair(32)
    now = 0
    for step in range(300):
        now += int(rng.integers(0, 3))
        n = int(rng.integers(1, 12))
        keys = [f"k{int(rng.integers(0, 60))}_{now // 10}" for _ in range(n)]
        expiries = [now + int(rng.integers(1, 30)) for _ in range(n)]
        s1, f1 = py.assign_batch(keys, now, expiries)
        s2, f2 = nat.assign_batch(keys, now, expiries)
        np.testing.assert_array_equal(f1, f2, err_msg=f"step {step} fresh")
        np.testing.assert_array_equal(s1, s2, err_msg=f"step {step} slots")
        assert len(py) == len(nat)
        if rng.random() < 0.2:
            assert py.gc(now) == nat.gc(now)
    assert py.evictions == nat.evictions


def test_existing_keys_pinned_against_mid_batch_eviction():
    """A slot handed out for an EXISTING key earlier in a batch must
    not be evicted for a later fresh key in the same batch (it would
    alias two live keys inside one device step)."""
    for table in make_pair(2):
        # Fill: a (expires soonest), b.
        table.assign_batch(["a", "b"], 0, [10, 20])
        # One batch touches existing 'a' then needs a slot for 'c':
        # 'b' must be evicted, never 'a'.
        slots, fresh = table.assign_batch(["a", "c"], 0, [10, 30])
        assert slots[0] != slots[1]
        live = {k for k, _, _ in table.export_packed().tuples()}
        assert live == {"a", "c"}

    # Same guarantee through the cross-call begin/end protocol.
    for table in make_pair(2):
        table.assign_batch(["a", "b"], 0, [10, 20])
        table.begin_batch()
        try:
            sa, _ = table.assign("a", 0, 10)
            sc, _ = table.assign("c", 0, 30)
        finally:
            table.end_batch()
        assert sa != sc
        assert {k for k, _, _ in table.export_packed().tuples()} == {"a", "c"}


def test_exhaustion_matches():
    py, nat = make_pair(2)
    for table in (py, nat):
        with pytest.raises(RuntimeError, match="slot table exhausted"):
            table.assign_batch(["a", "b", "c"], 0, [100, 100, 100])


def test_export_import_roundtrip():
    py, nat = make_pair(16)
    for table in (py, nat):
        table.assign_batch(["x", "y", "z"], 0, [30, 10, 20])
    assert sorted(py.entries()) == sorted(nat.export_packed().tuples())

    restored = native_slot_table.NativeSlotTable.from_packed(16, nat.export_packed())
    assert sorted(restored.export_packed().tuples()) == sorted(py.entries())
    # Known key keeps its slot; new key gets a free one.
    s, f = restored.assign_batch(["x", "new"], 0, [30, 40])
    old = dict((k, v) for k, v, _ in nat.export_packed().tuples())
    assert s[0] == old["x"] and not f[0]
    assert f[1]


def test_engine_uses_native_when_available():
    from ratelimit_tpu.backends.engine import CounterEngine

    engine = CounterEngine(num_slots=64, native_table=True)
    assert isinstance(engine.slot_table, native_slot_table.NativeSlotTable)
    engine_py = CounterEngine(num_slots=64, native_table=False)
    assert isinstance(engine_py.slot_table, SlotTable)


def test_gc_respects_batch_pins():
    """ADVICE r1 (medium): gc() during assign_batch must not reclaim a
    slot already handed out earlier in the same batch when that lane's
    key expires at the batch's `now` (window boundary inside one
    dispatcher batch, zero jitter)."""
    for table in make_pair(1):
        # k_90's window ends exactly at now=100; k_100 then needs a
        # slot.  gc() must skip the pinned k_90 -> exhaustion, never
        # two lanes aliasing slot 0.
        with pytest.raises(RuntimeError, match="slot table exhausted"):
            table.assign_batch(["k_90", "k_100"], 100, [100, 110])

    # Positive case: an UNpinned expired key is still reclaimed while
    # the pinned expired key survives.
    for table in make_pair(2):
        table.assign_batch(["old"], 0, [50])  # expires long before now
        slots, fresh = table.assign_batch(["k_90", "k_100"], 100, [100, 110])
        assert slots[0] != slots[1]
        assert list(fresh) == [True, True]
        assert {k for k, _, _ in table.export_packed().tuples()} == {"k_90", "k_100"}

    # Explicit gc() between batches keeps reclaiming as before.
    py, nat = make_pair(4)
    for table in (py, nat):
        table.assign_batch(["a", "b"], 0, [10, 20])
        assert table.gc(15) == 1
        assert {k for k, _, _ in table.export_packed().tuples()} == {"b"}


def test_import_skips_duplicate_keys():
    """ADVICE r1 (low): a snapshot with duplicate keys must not leak
    slots (slot marked used but mapping dropped/overwritten)."""
    entries = [("dup", 0, 100), ("dup", 1, 200), ("other", 2, 300)]
    py = SlotTable.from_entries(8, entries)
    nat = native_slot_table.NativeSlotTable.from_packed(
        8, PackedEntries.from_tuples(entries)
    )
    for table in (py, nat):
        live = sorted(table.export_packed().tuples())
        assert live == [("dup", 0, 100), ("other", 2, 300)]
        assert len(table) == 2
        # slot 1 must be free again: 6 fresh keys fit (8 - 2 live).
        keys = [f"n{i}" for i in range(6)]
        slots, fresh = table.assign_batch(keys, 0, [400] * 6)
        assert all(fresh)
        assert len(set(map(int, slots))) == 6
        assert 1 in set(map(int, slots))


def _pack(keys):
    enc = [k.encode("utf-8") for k in keys]
    blob = np.frombuffer(b"".join(enc), dtype=np.uint8)
    lens = np.fromiter((len(b) for b in enc), np.int64, len(enc))
    return blob, lens


def test_fused_assign_dedup_matches_numpy_oracle():
    """The fused C++ assign+dedup (one walk, round-3 host-path fast
    path) must reproduce assign_batch + engine._dedup_chunk exactly:
    same slots, sorted group order, totals, pipeline-order prefixes,
    freshness, and max-limits — across duplicates, evictions, and
    multi-call sequences."""
    from ratelimit_tpu.backends.engine import _dedup_chunk

    rng = np.random.default_rng(23)
    fused = native_slot_table.NativeSlotTable(24)
    oracle = native_slot_table.NativeSlotTable(24)
    now = 0
    for step in range(120):
        now += int(rng.integers(0, 3))
        n = int(rng.integers(1, 16))
        keys = [f"k{int(rng.integers(0, 40))}_{now // 8}" for _ in range(n)]
        expiries = np.asarray(
            [now + int(rng.integers(1, 20)) for _ in range(n)], np.int64
        )
        hits = rng.integers(1, 9, n).astype(np.uint32)
        limits = rng.integers(1, 1000, n).astype(np.uint32)
        blob, lens = _pack(keys)

        inv, uniq, totals, prefix, freshg, limitmax = (
            fused.assign_dedup_packed(blob, lens, now, expiries, hits, limits)
        )
        slots, fresh = oracle.assign_batch(keys, now, list(expiries))
        want = _dedup_chunk(slots.astype(np.int32), hits, limits, fresh)

        np.testing.assert_array_equal(uniq, want.uniq_slots)
        np.testing.assert_array_equal(inv, want.inv)
        np.testing.assert_array_equal(totals, want.totals)
        np.testing.assert_array_equal(prefix, want.prefix)
        np.testing.assert_array_equal(freshg, want.fresh)
        np.testing.assert_array_equal(limitmax, want.limit_max)
        # Per-lane slots reconstruct exactly from groups.
        np.testing.assert_array_equal(uniq[inv], slots)
        assert len(fused) == len(oracle)
        assert fused.evictions == oracle.evictions


def test_fused_assign_dedup_exhaustion():
    t = native_slot_table.NativeSlotTable(2)
    keys = ["a", "b", "c"]
    blob, lens = _pack(keys)
    with pytest.raises(RuntimeError, match="slot table exhausted"):
        t.assign_dedup_packed(
            blob,
            lens,
            0,
            np.full(3, 100, np.int64),
            np.ones(3, np.uint32),
            np.ones(3, np.uint32),
        )


def test_steady_state_churn_compacts_arena():
    """Review finding (round 3): steady-state expiry churn (gc
    tombstones a key, the next window reinserts it) reuses tombstone
    probe slots, so the load-based rehash trigger never fires — the
    dead-byte trigger must compact the arena or it grows without
    bound (and would eventually wrap the u32 key offsets)."""
    t = native_slot_table.NativeSlotTable(4096)
    keys = [f"churnkey_with_a_realistic_length_{i:05d}" for i in range(2048)]
    key_bytes = sum(len(k) for k in keys)
    peak = 0
    for window in range(40):
        now = window * 100
        expiries = [now + 50] * len(keys)
        slots, _ = t.assign_batch(keys, now, expiries)
        assert len(set(map(int, slots))) == len(keys)
        t.gc(now + 60)  # whole window expires
        assert len(t) == 0
        peak = max(peak, t.arena_bytes)
    # 40 windows x ~78KB of keys: unbounded growth would reach
    # ~40x key_bytes (~3MB).  The compaction trigger (dead > 1MB and
    # dead > half the arena) caps the peak around the 1MB threshold —
    # ~14x key_bytes here — so anything under 20x proves compaction
    # fired and bounded the arena.
    assert peak < 20 * key_bytes, (peak, key_bytes)
