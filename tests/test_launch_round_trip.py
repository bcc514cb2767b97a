"""One device round trip a launch (backends/engine.py): the packed
lanes go into the jitted step as numpy, the readback copy is asked for
inside the launch's bracket, and the completer only collects it.

Held here: the numpy input gives what a ``jnp.asarray`` input gave, bit
for bit, for every bucket x readback dtype and for both generic banks;
serving adds no jit signature after warm-up; the watchdog still sees
one bracket around the launch and one around the readback; and
``stat_readback_ready`` counts what was ready when it was taken up."""

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ratelimit_tpu.backends.dispatcher import LANE_DTYPE
from ratelimit_tpu.backends.engine import (
    DEFAULT_BUCKETS,
    CallWatch,
    CounterEngine,
    HostBatch,
    _decide_host,
    decide_generic,
)
from ratelimit_tpu.backends.tpu_cache import warmup_engine
from ratelimit_tpu.models.registry import get_algorithm
from ratelimit_tpu.observability import spans as span_names

NS = 1 << 13  # holds the largest bucket's 4,096 distinct slots
DTYPES = {"uint8": 100, "uint16": 60_000, "": 3_000_000_000}


class _Tap:
    """Stands in front of one jitted step of a model instance: passes
    the call through and keeps what went in and the handle that came
    out."""

    def __init__(self, model, name):
        self.calls = []
        self._step = getattr(model, name)
        setattr(model, name, self)

    def __call__(self, state, *args):
        state, out = self._step(state, *args)
        self.calls.append((args, out))
        return state, out


def _decisions_equal(got, want):
    for f in type(got).__dataclass_fields__:
        np.testing.assert_array_equal(getattr(got, f), getattr(want, f), f)


def _batch(rng, bucket, limit, generic=False):
    """`bucket` distinct in-table slots: the launch fills its bucket."""
    slots = rng.choice(NS, size=bucket, replace=False).astype(np.int32)
    return HostBatch(
        slots=slots,
        hits=rng.integers(0, 4, bucket).astype(np.uint32),
        limits=np.full(bucket, limit, np.uint32),
        fresh=rng.random(bucket) < 0.25,
        shadow=rng.random(bucket) < 0.1,
        dividers=(
            rng.choice(np.array([1, 60, 3600], np.uint32), bucket)
            if generic
            else None
        ),
    )


# -- (a) numpy in == jnp.asarray in, bit for bit ------------------------


@pytest.fixture(scope="module")
def fixed():
    engine = CounterEngine(num_slots=NS, native_table=False)
    return engine, _Tap(engine.model, "step_counters_unique_packed")


@pytest.mark.parametrize("dt", list(DTYPES))
@pytest.mark.parametrize("bucket", DEFAULT_BUCKETS)
def test_numpy_launch_equals_device_put_launch(fixed, bucket, dt):
    engine, tap = fixed
    model = engine.model
    rng = np.random.default_rng([bucket, len(dt)])
    for _ in range(2):  # the second step finds the first one's counts
        ref_counts = jnp.asarray(engine.export_counts())
        batch = _batch(rng, bucket, DTYPES[dt])
        del tap.calls[:]
        token = engine.step_submit(batch)
        got = engine.step_complete(token)
        ((got_dt, pk), afters_dev), = tap.calls
        assert got_dt == dt and type(pk) is np.ndarray
        assert pk.shape == (4, bucket) and pk.dtype == np.int32
        ref_counts, ref_afters = type(model).step_counters_unique_packed(
            model, ref_counts, dt, jnp.asarray(pk)
        )
        ref_afters = jax.device_get(ref_afters)
        afters = np.asarray(afters_dev)
        assert afters.dtype == ref_afters.dtype == np.dtype(dt or "uint32")
        np.testing.assert_array_equal(afters, ref_afters)
        np.testing.assert_array_equal(
            engine.export_counts(), np.asarray(ref_counts)
        )
        dedup = token[3][0][3]
        _decisions_equal(
            got,
            _decide_host(
                ref_afters, batch.hits, batch.limits, batch.shadow,
                model.near_ratio, dedup,
            ),
        )


@pytest.fixture(scope="module", params=["sliding_window", "gcra"])
def generic(request):
    model = get_algorithm(request.param).make_model(NS, 0.8)
    engine = CounterEngine(model=model)
    return engine, _Tap(model, "step_serve_packed")


def _state(engine) -> np.ndarray:
    rows = engine.export_state()
    return np.stack([rows[name] for name in engine.model.state_rows])


@pytest.mark.parametrize("bucket", DEFAULT_BUCKETS)
def test_generic_numpy_launch_equals_device_put_and_oracle(generic, bucket):
    engine, tap = generic
    model = engine.model
    rng = np.random.default_rng([bucket, 5])
    now = 1_700_000_000 + bucket
    for _ in range(2):
        oracle_state = _state(engine).copy()
        ref_state = jnp.asarray(oracle_state)
        batch = _batch(rng, bucket, 500, generic=True)
        del tap.calls[:]
        token = engine.step_submit(batch, now)
        got = engine.step_complete(token)
        ((pk, now_arg), out_dev), = tap.calls
        assert type(pk) is np.ndarray and pk.shape == (5, bucket)
        # A Python int would be weak-typed: another jit signature.
        assert type(now_arg) is np.int32 and now_arg == now
        ref_state, ref_out = type(model).step_serve_packed(
            model, ref_state, jnp.asarray(pk), jnp.asarray(now, jnp.int32)
        )
        ref_out = jax.device_get(ref_out)
        np.testing.assert_array_equal(np.asarray(out_dev), ref_out)
        np.testing.assert_array_equal(_state(engine), np.asarray(ref_state))
        dedup = token[3][0][3]
        oracle_out = model.reference_step(
            oracle_state,
            dedup.uniq_slots,
            dedup.totals_u32(),
            dedup.limit_max,
            dedup.fresh,
            dedup.divider_max,
            now,
        )
        g = len(dedup.uniq_slots)
        np.testing.assert_array_equal(
            np.asarray(out_dev)[..., :g], np.asarray(oracle_out)
        )
        np.testing.assert_array_equal(_state(engine), oracle_state)
        _decisions_equal(
            got,
            decide_generic(
                model, ref_out, batch.hits, batch.limits, batch.shadow,
                dedup, now,
            ),
        )
        now += int(rng.integers(1, 90))


# -- (b) serving adds no jit signature after warm-up ---------------------


def _serve(engine, now, keys):
    enc = [k.encode() for k in keys]
    meta = np.zeros(len(keys), LANE_DTYPE)
    for j, b in enumerate(enc):
        meta[j] = (now + 60, 1, 50, len(b), 0, 60, 0)
    return engine.step_complete(engine.submit_packed(now, b"".join(enc), meta))


@pytest.mark.parametrize("algo", ["fixed_window", "sliding_window", "gcra"])
def test_served_launches_add_no_jit_signature_after_warmup(algo):
    if algo == "fixed_window":
        engine = CounterEngine(num_slots=256, buckets=(8, 32))
        step = type(engine.model).step_counters_unique_packed
    else:
        model = get_algorithm(algo).make_model(256, 0.8)
        engine = CounterEngine(model=model, buckets=(8, 32))
        step = type(model).step_serve_packed
    warmup_engine(engine)
    warm = step._cache_size()
    rng = np.random.default_rng(3)
    for i in range(24):
        n = int(rng.integers(1, 30))
        keys = [f"k{int(k)}" for k in rng.choice(200, n, replace=False)]
        d = _serve(engine, 1_700_000_000 + 37 * i, keys)
        assert len(d.codes) == n
    assert step._cache_size() == warm


# -- (c) the watchdog's brackets -----------------------------------------


class _Watch(CallWatch):
    def __init__(self):
        super().__init__(now=lambda: 0.0)
        self.seen = []

    def begin(self, armed):
        self.seen.append(("begin", armed))
        super().begin(armed)

    def end(self):
        self.seen.append(("end", self.last_leg))
        super().end()


def test_watch_sees_one_bracket_a_launch_and_one_a_readback():
    engine = CounterEngine(num_slots=256, buckets=(8,))
    enc, meta = b"ab", np.zeros(1, LANE_DTYPE)
    meta[0] = (2_000_000_000, 1, 50, 2, 0, 0, 0)
    for armed in (False, True):  # a cold shape is not on the clock
        watch = _Watch()
        token = engine.submit_packed(1_000, enc, meta.copy(), watch)
        assert [s[0] for s in watch.seen] == ["begin", "end"]
        assert watch.seen[0] == ("begin", armed)
        assert watch.last_leg[0] == span_names.LAUNCH_DEVICE_CALL
        # Proven only by a completed readback, not by the copy request.
        assert (engine._proven_shapes != set()) == armed
        engine.step_complete(token, watch)
        assert [s[0] for s in watch.seen] == ["begin", "end"] * 2
        assert watch.seen[2] == ("begin", armed)
        assert watch.last_leg[0] == span_names.COMPLETE_DECIDE
        assert engine._proven_shapes == {(8, "uint8")}
        assert engine.stat_device_submit_ns > 0
        assert engine.stat_readback_ns > 0


# -- (d) stat_readback_ready ----------------------------------------------


class _Blocked:
    """A step result that is not ready until released: what the
    completer finds when the device is still at work."""

    def __init__(self, arr):
        self._arr = arr
        self.released = threading.Event()
        self.waited_for = threading.Event()
        self.copy_asked = False

    def copy_to_host_async(self):
        self.copy_asked = True

    def is_ready(self):
        return self.released.is_set()

    def __array__(self, dtype=None, copy=None):
        self.waited_for.set()
        assert self.released.wait(10.0)
        return np.asarray(self._arr)


def test_readback_ready_counts_only_what_was_ready_when_taken_up():
    engine = CounterEngine(num_slots=256, buckets=(8,))
    batch = HostBatch(
        slots=np.arange(3, dtype=np.int32),
        hits=np.ones(3, np.uint32),
        limits=np.full(3, 10, np.uint32),
        fresh=np.zeros(3, bool),
        shadow=np.zeros(3, bool),
    )
    token = engine.step_submit(batch)
    jax.block_until_ready(token[3][0][0])
    engine.step_complete(token)
    assert engine.stat_readback_ready == 1

    step = engine.model.step_counters_unique_packed
    held = []

    def blocked_step(counts, dt, pk):
        counts, afters = step(counts, dt, pk)
        held.append(_Blocked(afters))
        return counts, held[-1]

    engine.model.step_counters_unique_packed = blocked_step
    token = engine.step_submit(batch)
    assert held[0].copy_asked  # asked for inside the launch
    done = []
    t = threading.Thread(
        target=lambda: done.append(engine.step_complete(token))
    )
    t.start()
    assert held[0].waited_for.wait(10.0)  # asked is_ready(), now waits
    held[0].released.set()
    t.join(10.0)
    assert not t.is_alive() and len(done) == 1
    np.testing.assert_array_equal(done[0].afters, [2, 2, 2])
    assert engine.stat_readback_ready == 1  # not this one


# -- warm-up pauses the fault domain's supervisor ------------------------


def test_supervisor_snapshots_all_through_warmups_raise_no_fault():
    """A warm-up step donates the counts; a snapshot that reads them in
    that moment finds `Array has been deleted` -> exception fault ->
    restart (it did on the chip: PERF.md section 6, PR 26).  Here the
    supervisor ticks and snapshots as fast as it can, before, between
    and after three warm-ups: none during one, so no fault."""
    from ratelimit_tpu.backends.tpu_cache import TpuRateLimitCache
    from ratelimit_tpu.utils.time import PinnedTimeSource

    engine = CounterEngine(num_slots=256, buckets=(8, 32))
    cache = TpuRateLimitCache(
        engine,
        time_source=PinnedTimeSource(1_000),
        batch_window_us=200,
        kernel_deadline_s=5.0,
        fault_interval_s=0.0005,
        fault_snapshot_interval_s=0.0005,
    )
    fd = cache.fault_domain
    try:
        for _ in range(3):
            cache.warmup()
            after = fd.stat_snapshots
            deadline = time.monotonic() + 10.0
            while fd.stat_snapshots == after:  # the supervisor is back
                assert time.monotonic() < deadline
                time.sleep(0.001)
        report = fd.summary()
    finally:
        cache.close()
    assert len(engine._proven_shapes) == 6
    assert sum(report["faults"].values()) == 0 and report["restarts"] == 0
