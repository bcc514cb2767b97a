"""Pluggable limiter algorithms (models/registry.py; docs/ALGORITHMS.md).

Covers: device-kernel parity against the numpy oracles (sliding-window
and GCRA), the boundary-burst scenario on synthetic time (fixed-window
admits ~2x at a window edge while sliding-window and GCRA hold the
configured rate), shadow-mode rollout (enforcement byte-identical to
fixed-window, divergence counters populated, dual codes in flight
records), config validation (unknown ``algorithm:``, ``shadow: true``
on the default, algorithm under ``unlimited``), failed reloads keeping
the old algorithm table, slot-table refresh-on-touch expiry, the
missing-bank fold-back, checkpoint roundtrips of the widened per-slot
state, and the /metrics shadow family.
"""

import numpy as np
import pytest

from ratelimit_tpu.api import Code, Descriptor, RateLimitRequest
from ratelimit_tpu.backends import CounterEngine, TpuRateLimitCache
from ratelimit_tpu.backends.slot_table import SlotTable
from ratelimit_tpu.config import ConfigError, ConfigFile, load_config
from ratelimit_tpu.models.registry import ALGORITHMS, get_algorithm
from ratelimit_tpu.service import RateLimitService
from ratelimit_tpu.stats.manager import Manager
from ratelimit_tpu.utils.time import PinnedTimeSource

OK, OVER = int(Code.OK), int(Code.OVER_LIMIT)

ALGO_YAML = """
domain: algo
descriptors:
  - key: fx
    rate_limit: {unit: minute, requests_per_unit: 10}
  - key: slide
    rate_limit: {unit: minute, requests_per_unit: 10, algorithm: sliding_window}
  - key: tb
    rate_limit: {unit: minute, requests_per_unit: 10, algorithm: gcra}
  - key: shady
    rate_limit: {unit: minute, requests_per_unit: 10, algorithm: sliding_window, shadow: true}
  - key: shady_tb
    rate_limit: {unit: minute, requests_per_unit: 10, algorithm: gcra, shadow: true}
"""

# A minute boundary with room on both sides.
EDGE = 1_700_000_040 - (1_700_000_040 % 60) + 60


class FakeRuntime:
    def __init__(self, files):
        self.files = dict(files)
        self.callbacks = []

    def snapshot(self):
        data = dict(self.files)

        class Snap:
            def keys(self):
                return sorted(data)

            def get(self, key):
                return data.get(key, "")

        return Snap()

    def add_update_callback(self, fn):
        self.callbacks.append(fn)

    def fire(self):
        for fn in self.callbacks:
            fn()


def make_algo_banks(num_slots=1 << 10):
    return {
        name: CounterEngine(
            buckets=(8, 32),
            model=get_algorithm(name).make_model(num_slots, 0.8),
        )
        for name in ("sliding_window", "gcra")
    }


def make_service(clock, yaml=ALGO_YAML, banks=True, **cache_kwargs):
    engine = CounterEngine(num_slots=1 << 10, buckets=(8, 32))
    cache = TpuRateLimitCache(
        engine,
        clock,
        algorithm_banks=make_algo_banks() if banks else None,
        **cache_kwargs,
    )
    runtime = FakeRuntime({"config.algo": yaml})
    svc = RateLimitService(runtime, cache, Manager(), clock=clock)
    return svc, cache, runtime


def burst(svc, key, n, domain="algo"):
    codes = []
    for _ in range(n):
        resp = svc.should_rate_limit(
            RateLimitRequest(domain, [Descriptor.of((key, "u"))], 0)
        )
        codes.append(int(resp.statuses[0].code))
    return codes


# -- device kernels vs numpy oracles ----------------------------------


def _packed(slots, hits, limits, fresh, divider, padded, ns):
    import jax.numpy as jnp

    g = len(slots)
    pk = np.empty((5, padded), np.int32)
    pk[0, :g] = slots
    pk[0, g:] = ns + np.arange(padded - g)
    pk[1, :g] = np.asarray(hits, np.uint32).view(np.int32)
    pk[1, g:] = 0
    pk[2, :g] = np.asarray(limits, np.uint32).view(np.int32)
    pk[2, g:] = 1
    pk[3, :g] = np.asarray(fresh, np.int32)
    pk[3, g:] = 0
    pk[4, :g] = np.asarray(divider, np.uint32).view(np.int32)
    pk[4, g:] = 1
    return jnp.asarray(pk)


def test_sliding_kernel_matches_numpy_oracle():
    """Randomized multi-step parity: the jitted sliding-window
    kernel's state and readback must match reference_step exactly
    (both weigh the previous window in exact integer arithmetic)."""
    import jax.numpy as jnp

    ns = 256
    model = get_algorithm("sliding_window").make_model(ns, 0.8)
    state = model.init_state()
    ref = np.zeros((3, ns), np.uint32)
    rng = np.random.default_rng(7)
    now = 1_700_000_000
    seen = set()
    for step in range(20):
        g = int(rng.integers(1, 9))
        slots = rng.choice(ns, size=g, replace=False).astype(np.int32)
        hits = rng.integers(1, 5, g).astype(np.uint32)
        limits = rng.integers(1, 30, g).astype(np.uint32)
        divider = np.full(g, 60, np.uint32)
        fresh = np.array([s not in seen for s in slots], bool)
        seen.update(int(s) for s in slots)
        state, out = model.step_serve_packed(
            state, _packed(slots, hits, limits, fresh, divider, 8, ns),
            jnp.asarray(now, jnp.int32),
        )
        ref_out = model.reference_step(
            ref, slots, hits, limits, fresh, divider, now
        )
        got = np.asarray(out)
        np.testing.assert_array_equal(got[0, :g], ref_out[0])
        np.testing.assert_array_equal(got[1, :g], ref_out[1])
        np.testing.assert_array_equal(np.asarray(state), ref)
        now += int(rng.integers(0, 45))


def test_floor_muldiv_is_the_exact_integer_floor():
    """floor(a * b / d) over the whole uint32 range and every window
    unit, against plain 64-bit arithmetic.  The f32 form this replaced
    missed the exact floor in ~200 per million cases on the host and —
    differently — on a TPU, whose f32 divide is not correctly rounded
    (PERF.md, PR 21): one admission's difference between the device
    and its host mirror."""
    import jax
    import jax.numpy as jnp

    from ratelimit_tpu.ops import floor_muldiv

    rng = np.random.default_rng(11)
    n = 1 << 16
    divider = rng.choice(np.array([1, 60, 3600, 86400], np.uint32), n)
    prev = rng.integers(0, 1 << 32, n, dtype=np.uint64).astype(np.uint32)
    prev[:64] = 0xFFFFFFFF  # saturated counters
    prev[64 : n // 2] = rng.integers(0, 5000, n // 2 - 64)  # everyday counts
    rem = (rng.integers(0, 1 << 40, n) % (divider.astype(np.int64) + 1)).astype(
        np.uint32
    )  # 0..divider inclusive
    got = np.asarray(
        jax.jit(floor_muldiv)(
            jnp.asarray(prev), jnp.asarray(rem), jnp.asarray(divider)
        )
    )
    want = (
        prev.astype(np.uint64) * rem.astype(np.uint64) // divider.astype(np.uint64)
    ).astype(np.uint32)
    np.testing.assert_array_equal(got, want)


def test_gcra_kernel_is_exact_rational_gcra():
    """Randomized multi-step parity, three ways: the jitted kernel's
    budgets and state equal reference_step bit for bit, and both equal
    GCRA carried in exact rationals (``fractions.Fraction`` TAT,
    ``B = limit - ceil((TAT - now)+ / T)``) — for limits that do not
    divide the window, every window unit, idle gaps from 0 s to days,
    multi-cell hits and limits up to 2**31.  The f32 kernel this
    replaced agreed with its own oracle on 89% of such lanes."""
    import math
    from fractions import Fraction

    import jax.numpy as jnp

    ns = 512
    model = get_algorithm("gcra").make_model(ns, 0.8)
    state = model.init_state()
    ref = np.zeros((2, ns), np.uint32)
    rng = np.random.default_rng(7)
    tat = [Fraction(0)] * ns
    slot_limit = rng.integers(1, 2000, ns).astype(np.uint32)
    slot_limit[:16] = rng.integers(1 << 20, 1 << 31, 16)
    slot_divider = rng.choice(np.array([1, 60, 3600, 86400], np.uint32), ns)
    now = 1_700_000_000
    seen = set()
    for step in range(60):
        g = 64
        slots = rng.choice(ns, size=g, replace=False).astype(np.int32)
        hits = rng.integers(0, 5, g).astype(np.uint32)
        if step % 5 == 0:
            hits = hits * rng.integers(1, 300, g).astype(np.uint32)
        limits, divider = slot_limit[slots], slot_divider[slots]
        fresh = np.array([s not in seen for s in slots], bool)
        seen.update(int(s) for s in slots)
        state, out = model.step_serve_packed(
            state, _packed(slots, hits, limits, fresh, divider, g, ns),
            jnp.asarray(now, jnp.int32),
        )
        budgets = model.reference_step(
            ref, slots, hits, limits, fresh, divider, now
        )
        np.testing.assert_array_equal(np.asarray(out), budgets)
        np.testing.assert_array_equal(np.asarray(state), ref)
        for s, h, lim, d, b in zip(slots, hits, limits, divider, budgets):
            t_emit = Fraction(int(d), int(lim))
            used = math.ceil(max(tat[s] - now, 0) / t_emit)
            assert b == max(int(lim) - used, 0), (step, s, lim, d)
            if min(int(h), b) > 0:
                tat[s] = max(tat[s], Fraction(now)) + min(int(h), b) * t_emit
        now += int(rng.choice([0, 0, 1, 1, 2, 7, 61, 977, 4000, 90000, 200000]))


# -- the boundary-burst scenario --------------------------------------


def test_fixed_window_admits_2x_at_edge_new_algorithms_hold():
    """The headline correctness scenario on synthetic time: burst the
    full limit just before a window edge, then again just after.
    Fixed windows admit ~2x the configured rate inside the straddling
    interval; sliding-window and GCRA hold it."""
    clock = PinnedTimeSource(EDGE - 5)
    svc, cache, _ = make_service(clock)

    admitted = {}
    for key in ("fx", "slide", "tb"):
        pre = burst(svc, key, 10)
        assert pre == [OK] * 10, (key, pre)  # fresh keys admit the limit
    clock.advance(10)  # cross the minute edge, 5s into the new window
    for key in ("fx", "slide", "tb"):
        post = burst(svc, key, 10)
        admitted[key] = sum(1 for c in post if c == OK)

    # Fixed window: a brand-new window admits the full limit again —
    # 20 admitted inside a 15-second interval (the 2x boundary burst).
    assert admitted["fx"] == 10
    # Sliding window: floor(10 * 55/60) = 9 of the previous window
    # still weighs in, so exactly 1 more fits.
    assert admitted["slide"] == 1
    # GCRA: the burst pushed TAT a full period out; 10 elapsed seconds
    # refill one 6-second emission cell — the configured rate, not a
    # re-opened window.
    assert admitted["tb"] == 1

    # ...and capacity keeps coming back smoothly, one cell per
    # emission interval, not all at once.
    clock.advance(7)  # 12s past the edge
    assert burst(svc, "tb", 2) == [OK, OVER]


def test_gcra_steady_rate_between_windows():
    """GCRA refills continuously: after an idle stretch the full burst
    returns; under a steady drip it admits exactly 1 per interval."""
    clock = PinnedTimeSource(EDGE)
    svc, _, _ = make_service(clock)
    assert burst(svc, "tb", 11).count(OK) == 10
    clock.advance(120)  # two full periods idle: burst capacity is back
    assert burst(svc, "tb", 11).count(OK) == 10


def test_sliding_window_decay_readmits_gradually():
    clock = PinnedTimeSource(EDGE - 1)
    svc, _, _ = make_service(clock)
    assert burst(svc, "slide", 10) == [OK] * 10
    clock.advance(31)  # 30s into the next window: wprev = floor(10*.5)
    codes = burst(svc, "slide", 6)
    assert codes.count(OK) == 5, codes  # 5 slots freed by decay


# -- shadow-mode rollout ----------------------------------------------


def test_shadow_enforcement_byte_identical_to_fixed_window():
    """A shadowed rule's responses must be exactly what a plain
    fixed-window rule would produce — across bursts, window edges and
    the local-cache path."""
    plain_yaml = ALGO_YAML.replace(
        ", algorithm: sliding_window, shadow: true", ""
    ).replace(", algorithm: gcra, shadow: true", "")
    clock_a = PinnedTimeSource(EDGE - 5)
    clock_b = PinnedTimeSource(EDGE - 5)
    svc_a, cache_a, _ = make_service(clock_a)
    svc_b, cache_b, _ = make_service(clock_b, yaml=plain_yaml, banks=False)

    transcript_a, transcript_b = [], []
    for svc, clock, transcript in (
        (svc_a, clock_a, transcript_a),
        (svc_b, clock_b, transcript_b),
    ):
        for step in range(3):
            for key in ("shady", "shady_tb"):
                for _ in range(8):
                    resp = svc.should_rate_limit(
                        RateLimitRequest(
                            "algo", [Descriptor.of((key, "x"))], 0
                        )
                    )
                    st = resp.statuses[0]
                    transcript.append(
                        (
                            int(resp.overall_code),
                            int(st.code),
                            st.limit_remaining,
                            st.duration_until_reset,
                        )
                    )
            clock.advance(7)
    assert transcript_a == transcript_b
    # ...and the shadow evaluation really ran on the side.
    counts = cache_a._shadow_counts
    total = sum(a + d for a, d in counts.values())
    assert total == 48, counts


def test_shadow_divergence_counters():
    """Right after a window edge the candidate kernels disagree with
    fixed-window (which forgives the whole burst): divergence must be
    counted per algorithm, agreement before the edge too."""
    clock = PinnedTimeSource(EDGE - 5)
    svc, cache, _ = make_service(clock)
    burst(svc, "shady", 10)
    burst(svc, "shady_tb", 10)
    pre = {k: tuple(v) for k, v in cache._shadow_counts.items()}
    assert pre["sliding_window"] == (10, 0)
    assert pre["gcra"] == (10, 0)

    clock.advance(10)  # cross the edge: fixed admits, candidates mostly say no
    codes = burst(svc, "shady", 10)
    assert codes == [OK] * 10  # enforcement is still fixed-window
    # Candidate sliding-window admits exactly 1 (decay left one slot),
    # so 1 more agreement and 9 divergences.
    assert tuple(cache._shadow_counts["sliding_window"]) == (11, 9)
    codes = burst(svc, "shady_tb", 10)
    assert codes == [OK] * 10
    # Candidate GCRA refilled exactly 1 cell in the elapsed 10s.
    assert tuple(cache._shadow_counts["gcra"]) == (11, 9)


def test_shadow_dual_codes_in_flight_record():
    """The flight-recorder note carries the candidate's would-be code
    + algorithm id; a transport-layer record() stamp lands both."""
    from ratelimit_tpu.observability import make_flight_recorder

    clock = PinnedTimeSource(EDGE - 5)
    svc, cache, _ = make_service(clock)
    flight = make_flight_recorder(64)
    cache.flight = flight

    burst(svc, "shady", 10)
    clock.advance(10)
    burst(svc, "shady", 1)  # candidate's one decayed slot goes here
    resp = svc.should_rate_limit(
        RateLimitRequest("algo", [Descriptor.of(("shady", "u"))], 0)
    )
    # Simulate the gRPC handler's post-serialize stamp (same thread).
    flight.record("algo", int(resp.overall_code), 1, 0.5)
    rec = flight.snapshot_dicts()[0]
    assert rec["code"] == OK  # enforced: fixed-window admits
    assert rec["shadow_code"] == OVER  # candidate: sliding rejects
    assert rec["shadow_algorithm"] == "sliding_window"

    # Non-shadow requests carry no dual-code fields.
    resp = svc.should_rate_limit(
        RateLimitRequest("algo", [Descriptor.of(("fx", "u"))], 0)
    )
    flight.record("algo", int(resp.overall_code), 1, 0.5)
    assert "shadow_code" not in flight.snapshot_dicts()[0]


def test_shadow_metrics_family_rendered():
    from ratelimit_tpu.observability import prometheus

    clock = PinnedTimeSource(EDGE - 5)
    svc, cache, _ = make_service(clock)
    mgr = Manager()
    cache.register_stats(mgr.store)
    burst(svc, "shady", 3)
    text = prometheus.render(mgr.store)
    assert "# TYPE ratelimit_tpu_shadow_sliding_window_agree counter" in text
    assert "ratelimit_tpu_shadow_sliding_window_agree 3" in text
    assert "ratelimit_tpu_shadow_sliding_window_diverge 0" in text
    assert "ratelimit_tpu_shadow_gcra_agree 0" in text


# -- config validation ------------------------------------------------


def _load(yaml):
    return load_config([ConfigFile("config.x", yaml)], Manager())


def test_unknown_algorithm_rejected():
    with pytest.raises(ConfigError) as e:
        _load(
            """
domain: d
descriptors:
  - key: k
    rate_limit: {unit: minute, requests_per_unit: 5, algorithm: leaky_bucket}
"""
        )
    assert "invalid rate limit algorithm 'leaky_bucket'" in str(e.value)
    assert "gcra" in str(e.value)  # the error lists the known table


def test_shadow_on_default_algorithm_rejected():
    for rl in (
        "{unit: minute, requests_per_unit: 5, shadow: true}",
        "{unit: minute, requests_per_unit: 5, algorithm: fixed_window, shadow: true}",
    ):
        with pytest.raises(ConfigError) as e:
            _load(
                f"""
domain: d
descriptors:
  - key: k
    rate_limit: {rl}
"""
            )
        assert "shadow: true requires a non-default algorithm" in str(e.value)


def test_algorithm_under_unlimited_rejected():
    with pytest.raises(ConfigError) as e:
        _load(
            """
domain: d
descriptors:
  - key: k
    rate_limit: {unlimited: true, algorithm: gcra}
"""
        )
    assert "should not specify rate limit algorithm when unlimited" in str(
        e.value
    )


def test_valid_algorithms_load_and_dump():
    cfg = _load(ALGO_YAML.replace("domain: algo", "domain: d"))
    rule = cfg.get_limit("d", Descriptor.of(("tb", "x")))
    assert rule.algorithm == "gcra" and not rule.algo_shadow
    rule = cfg.get_limit("d", Descriptor.of(("shady", "x")))
    assert rule.algorithm == "sliding_window" and rule.algo_shadow
    dump = cfg.dump()
    assert "algorithm: gcra" in dump
    assert "algorithm: sliding_window (shadow)" in dump


def test_failed_reload_keeps_old_algorithm_table():
    """Extends the PR 3 failed-reload contract: a bad push (here an
    unknown algorithm name) keeps the old config, the old generation,
    the warm resolution cache AND the old rule->algorithm routing."""
    clock = PinnedTimeSource(EDGE - 5)
    svc, cache, runtime = make_service(clock)
    assert burst(svc, "tb", 11).count(OK) == 10  # GCRA enforcing

    runtime.files["config.algo"] = ALGO_YAML.replace(
        "algorithm: gcra}", "algorithm: nonsense}"
    )
    runtime.fire()  # reload fails
    assert svc.stats.config_load_error.value() == 1

    misses_before = cache.resolver.misses
    clock.advance(6)  # one GCRA emission interval refills one cell
    codes = burst(svc, "tb", 2)
    assert codes == [OK, OVER]  # still GCRA semantics, same bank state
    assert cache.resolver.misses == misses_before  # cache stayed warm


def test_missing_bank_folds_to_fixed_window():
    """A rule naming an algorithm the backend has no bank for keeps
    limiting with fixed-window semantics instead of erroring."""
    clock = PinnedTimeSource(EDGE - 5)
    svc, cache, _ = make_service(clock, banks=False)
    assert burst(svc, "slide", 11).count(OK) == 10
    clock.advance(10)
    # Fixed-window fallback: the new window admits the limit again.
    assert burst(svc, "slide", 10) == [OK] * 10
    assert cache._shadow_counts == {}


# -- slot-table refresh + checkpoint ----------------------------------


def test_slot_table_refresh_expiry():
    t = SlotTable(4, refresh_expiry=True)
    slot, fresh = t.assign("k", now=0, expiry=10)
    assert fresh
    t.assign("k", now=8, expiry=18)  # touch extends the lease
    assert t.gc(now=11) == 0  # original expiry passed; lease held
    assert len(t) == 1
    assert t.gc(now=19) == 1  # extended lease expired

    plain = SlotTable(4)
    plain.assign("k", now=0, expiry=10)
    plain.assign("k", now=8, expiry=18)  # no refresh by default
    assert plain.gc(now=11) == 1


def test_algorithm_bank_uses_refresh_table_and_survives_windows():
    """A continuously hot GCRA key must keep its slot (and TAT) across
    many window lengths — the refresh-on-touch expiry at work."""
    clock = PinnedTimeSource(EDGE)
    svc, cache, _ = make_service(clock)
    bank = cache.algorithm_banks["gcra"]
    assert bank.slot_table.refresh_expiry
    burst(svc, "tb", 10)
    for _ in range(40):  # 240s = 4 windows, touched every 6s
        clock.advance(6)
        assert burst(svc, "tb", 1) == [OK]  # exactly the refill rate
        assert burst(svc, "tb", 1) == [OVER]  # ...and nothing more
    assert bank.stat_evictions == 0


def test_checkpoint_roundtrip_algorithm_state(tmp_path):
    """The widened per-slot state (GCRA's tat rows, sliding-window's
    three rows) checkpoints and restores bit-exactly; a kernel
    mismatch refuses the restore."""
    from ratelimit_tpu.backends.checkpoint import (
        restore_engine,
        save_engine,
    )

    clock = PinnedTimeSource(EDGE)
    svc, cache, _ = make_service(clock)
    burst(svc, "tb", 7)
    burst(svc, "slide", 5)

    for name in ("gcra", "sliding_window"):
        bank = cache.algorithm_banks[name]
        path = str(tmp_path / f"{name}.npz")
        save_engine(bank, path, role="algo_" + name)
        fresh = CounterEngine(
            buckets=(8, 32), model=get_algorithm(name).make_model(1 << 10, 0.8)
        )
        assert restore_engine(fresh, path, role="algo_" + name)
        for row, arr in bank.export_state().items():
            np.testing.assert_array_equal(
                fresh.export_state()[row], arr, err_msg=(name, row)
            )
        assert fresh.slot_table.entries() == bank.slot_table.entries()
        assert fresh.slot_table.refresh_expiry

        # Kernel mismatch: GCRA state must never restore into a
        # sliding-window (or fixed-window) engine.
        other = "sliding_window" if name == "gcra" else "gcra"
        wrong = CounterEngine(
            buckets=(8, 32),
            model=get_algorithm(other).make_model(1 << 10, 0.8),
        )
        assert not restore_engine(wrong, path, role="algo_" + name)

    # Same kernel, older state layout (GCRA kept its TAT as seconds +
    # a 2^-32 fraction before PR 21): refused, not misread.
    from ratelimit_tpu.backends.checkpoint import write_snapshot

    bank = cache.algorithm_banks["gcra"]
    old = {
        "tat_sec": np.zeros(1 << 10, np.uint32),
        "tat_frac": np.zeros(1 << 10, np.uint32),
    }
    path = str(tmp_path / "gcra_old_layout.npz")
    write_snapshot(
        path, 1 << 10, old, bank.slot_table.export_packed(), "algo_gcra", "gcra"
    )
    fresh = CounterEngine(
        buckets=(8, 32), model=get_algorithm("gcra").make_model(1 << 10, 0.8)
    )
    assert not restore_engine(fresh, path, role="algo_gcra")


def test_checkpoint_roles_include_algorithm_banks(tmp_path):
    from ratelimit_tpu.backends.checkpoint import CheckpointManager

    clock = PinnedTimeSource(EDGE)
    svc, cache, _ = make_service(clock)
    mgr = CheckpointManager(cache, str(tmp_path), interval_s=3600)
    assert mgr._bank_roles() == [
        "lane0of1",
        "algo_gcra",
        "algo_sliding_window",
    ]


def test_restored_gcra_bank_keeps_limiting(tmp_path):
    """End-to-end restart envelope: checkpoint mid-burst, restore into
    a fresh cache, and the restored TAT still rejects the next hit."""
    from ratelimit_tpu.backends.checkpoint import CheckpointManager

    clock = PinnedTimeSource(EDGE)
    svc, cache, _ = make_service(clock)
    burst(svc, "tb", 10)  # burst capacity fully spent
    CheckpointManager(cache, str(tmp_path), interval_s=3600).checkpoint()

    svc2, cache2, _ = make_service(PinnedTimeSource(EDGE + 1))
    restored = CheckpointManager(
        cache2, str(tmp_path), interval_s=3600
    ).restore()
    assert restored == 3  # lane + both algorithm banks
    assert burst(svc2, "tb", 1) == [OVER]


# -- registry sanity ---------------------------------------------------


def test_registry_contract():
    assert set(ALGORITHMS) == {"fixed_window", "sliding_window", "gcra"}
    ids = [spec.algo_id for spec in ALGORITHMS.values()]
    assert len(ids) == len(set(ids))  # stable distinct flight ids
    assert ALGORITHMS["fixed_window"].windowed_keys
    assert not ALGORITHMS["gcra"].windowed_keys
    with pytest.raises(KeyError):
        get_algorithm("nope")
