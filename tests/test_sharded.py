"""Sharded (multi-bank) engine vs the single-chip model.

Runs on the virtual 8-device CPU mesh from conftest; asserts the
bank-sharded shard_map step is bit-identical to the single-chip jitted
step (same decisions, same counter table) across random batches with
duplicate slots, fresh resets, shadow rules, and padding lanes.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ratelimit_tpu.backends.engine import CounterEngine, HostBatch
from ratelimit_tpu.models.fixed_window import DeviceBatch, FixedWindowModel
from ratelimit_tpu.parallel import ShardedCounterEngine, ShardedFixedWindowModel, make_mesh


NUM_SLOTS = 64  # tiny: forces heavy duplicate-slot traffic


def _random_batch(rng, n, num_slots):
    slots = rng.integers(0, num_slots + 1, size=n).astype(np.int32)
    hits = rng.integers(1, 5, size=n).astype(np.uint32)
    limits = rng.integers(1, 12, size=n).astype(np.uint32)
    fresh = rng.random(n) < 0.15
    shadow = rng.random(n) < 0.2
    return DeviceBatch(
        slots=jnp.asarray(slots),
        hits=jnp.asarray(hits),
        limits=jnp.asarray(limits),
        fresh=jnp.asarray(fresh),
        shadow=jnp.asarray(shadow),
    )


@pytest.mark.parametrize("n_devices", [1, 4, 8])
def test_sharded_matches_single_chip(n_devices):
    mesh = make_mesh(n_devices)
    sharded = ShardedFixedWindowModel(NUM_SLOTS, mesh)
    assert sharded.num_slots == NUM_SLOTS  # 64 divides 1/4/8
    single = FixedWindowModel(NUM_SLOTS)

    s_counts = sharded.init_state()
    counts = single.init_state()
    rng = np.random.default_rng(7)

    for step in range(6):
        batch = _random_batch(rng, 32, NUM_SLOTS)
        s_counts, s_dec = sharded.step(s_counts, batch)
        counts, dec = single.step(counts, batch)

        for field in dec._fields:
            a = np.asarray(getattr(s_dec, field))
            b = np.asarray(getattr(dec, field))
            np.testing.assert_array_equal(
                a.astype(np.int64), b.astype(np.int64), err_msg=f"step {step} {field}"
            )
        # Device layout is bank-major with modulo striping: global
        # slot s lives at [s % nb, s // nb], so transpose recovers
        # global order.
        np.testing.assert_array_equal(
            np.asarray(s_counts).T.reshape(-1), np.asarray(counts)
        )


def test_sharded_rounds_up_slot_count():
    mesh = make_mesh(8)
    m = ShardedFixedWindowModel(100, mesh)
    assert m.num_slots == 104  # ceil(100/8)*8
    assert m.slots_per_bank == 13


def test_sharded_engine_matches_engine():
    mesh = make_mesh(8)
    se = ShardedCounterEngine(mesh, num_slots=NUM_SLOTS, buckets=(8, 32))
    e = CounterEngine(num_slots=NUM_SLOTS, buckets=(8, 32))
    rng = np.random.default_rng(3)

    for _ in range(4):
        n = int(rng.integers(1, 70))  # crosses the max_batch chunking
        slots = rng.integers(0, NUM_SLOTS, size=n).astype(np.int32)
        hb = HostBatch(
            slots=slots,
            hits=rng.integers(1, 4, size=n).astype(np.uint32),
            limits=rng.integers(1, 10, size=n).astype(np.uint32),
            fresh=np.zeros(n, dtype=bool),
            shadow=rng.random(n) < 0.3,
        )
        d1 = se.step(hb)
        d2 = e.step(hb)
        for field in ("codes", "limit_remaining", "befores", "afters",
                      "over_limit", "near_limit", "within_limit",
                      "shadow_mode", "set_local_cache"):
            np.testing.assert_array_equal(
                np.asarray(getattr(d1, field)).astype(np.int64),
                np.asarray(getattr(d2, field)).astype(np.int64),
                err_msg=field,
            )


def test_counts_actually_sharded():
    mesh = make_mesh(8)
    m = ShardedFixedWindowModel(1 << 10, mesh)
    counts = m.init_state()
    # One shard per device, each holding exactly its bank.
    assert len(counts.addressable_shards) == 8
    assert counts.addressable_shards[0].data.shape == (1, m.slots_per_bank)


def test_mesh_engine_ships_the_whole_bucket_to_every_chip():
    """The mesh bank launches what the one-chip engine launches: one
    packed bucket, replicated — ownership is decided on the device
    (PR 50; dividing the lanes on the host cost the launch more than
    the chips ever saved).  The answer comes back whole on every chip,
    so nothing reassembles it on the host."""
    mesh = make_mesh(8)
    se = ShardedCounterEngine(mesh, num_slots=1 << 10, buckets=(8, 32, 128, 256))
    rng = np.random.default_rng(9)
    n = 256
    hb = HostBatch(
        slots=rng.choice(1 << 10, size=n, replace=False).astype(np.int32),
        hits=np.ones(n, dtype=np.uint32),
        limits=np.full(n, 10, dtype=np.uint32),
        fresh=np.zeros(n, dtype=bool),
        shadow=np.zeros(n, dtype=bool),
    )
    token = se.step_submit(hb)
    _hits, _limits, _shadow, chunks, _now = token
    afters_dev, _start, _count, _dedup, reassemble, shape = chunks[0]
    assert afters_dev.shape == (n,) and afters_dev.is_fully_replicated
    assert len(afters_dev.sharding.device_set) == 8
    assert reassemble is None and shape == (n, "uint8")
    d = se.step_complete(token)
    np.testing.assert_array_equal(d.afters, np.ones(n))


def test_mesh_engine_heavy_duplicates_and_skew():
    """All lanes hash to one bank + heavy same-key duplication: the
    mesh step must still match the single-chip engine decision for
    decision."""
    mesh = make_mesh(8)
    se = ShardedCounterEngine(mesh, num_slots=NUM_SLOTS, buckets=(8, 32))
    e = CounterEngine(num_slots=NUM_SLOTS, buckets=(8, 32))
    rng = np.random.default_rng(21)
    spb = se.model.slots_per_bank
    for step in range(5):
        n = 40
        # Slots only in bank 0 (max skew): under modulo striping a
        # slot s is bank-0-owned iff s % num_banks == 0, so multiples
        # of num_banks pin the whole batch to one bank.  Small value
        # range -> many duplicates.
        nb = se.model.num_banks
        slots = (
            rng.integers(0, max(spb // 2, 2), size=n).astype(np.int64) * nb
        ).astype(np.int32)
        fresh = np.zeros(n, dtype=bool)
        if step == 0:
            seen: set = set()
            for i, s in enumerate(slots):
                if s not in seen:
                    seen.add(s)
                    fresh[i] = True
        hb = HostBatch(
            slots=slots,
            hits=rng.integers(1, 4, size=n).astype(np.uint32),
            limits=np.full(n, 9, dtype=np.uint32),
            fresh=fresh,
            shadow=rng.random(n) < 0.2,
        )
        d1, d2 = se.step(hb), e.step(hb)
        for field in ("codes", "limit_remaining", "over_limit",
                      "near_limit", "within_limit", "shadow_mode",
                      "set_local_cache"):
            np.testing.assert_array_equal(
                np.asarray(getattr(d1, field)).astype(np.int64),
                np.asarray(getattr(d2, field)).astype(np.int64),
                err_msg=f"step {step} {field}",
            )
        np.testing.assert_array_equal(
            se.export_counts(), e.export_counts()
        )


def test_mesh_engine_oob_probe_lanes():
    """Distinct out-of-table slots (padding, probes): chip 0 answers
    them like the single-chip path (before=0)."""
    mesh = make_mesh(4)
    se = ShardedCounterEngine(mesh, num_slots=NUM_SLOTS, buckets=(8,))
    ns = se.model.num_slots
    n = 8
    hb = HostBatch(
        slots=np.arange(ns, ns + n, dtype=np.int64).astype(np.int32),
        hits=np.zeros(n, dtype=np.uint32),
        limits=np.full(n, 100, dtype=np.uint32),
        fresh=np.zeros(n, dtype=bool),
        shadow=np.zeros(n, dtype=bool),
    )
    d = se.step(hb)
    assert (d.codes == 1).all()
    np.testing.assert_array_equal(d.afters, np.zeros(n))


def test_warmup_compiles_every_mesh_shape():
    """Every (bucket, readback-dtype) shape of the mesh step gets
    compiled at startup — the same set as the one-chip engine's,
    whatever chip owns the probes' slots — and the probes leave
    counters and the slot table untouched."""
    from ratelimit_tpu.backends.tpu_cache import TpuRateLimitCache

    mesh = make_mesh(8)
    buckets = (8, 32)
    se = ShardedCounterEngine(mesh, num_slots=1 << 10, buckets=buckets)
    cache = TpuRateLimitCache(se)

    seen = []  # (dtype, bucket)
    orig = se.model.step_counters_unique_packed

    def spy(counts, out_dtype, packed):
        seen.append((out_dtype, int(np.asarray(packed).shape[1])))
        return orig(counts, out_dtype, packed)

    se.model.step_counters_unique_packed = spy
    cache.warmup()

    assert sorted(seen) == sorted(
        (dt, bucket) for bucket in buckets for dt in ("uint8", "uint16", "")
    )
    assert se._proven_shapes == {(b, dt) for dt, b in seen}
    # Probes are inert: no counters touched, no keys assigned.
    assert not se.export_counts().any()
    assert len(se.slot_table) == 0
