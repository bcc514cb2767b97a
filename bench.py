"""Kernel-layer benchmark: the device step of every kernel in the
algorithm table, scanned on one chip, each verified bit for bit
against a numpy replay.

This is ONE LAYER's measurement, not the system's: no request is
served, no dispatcher or gRPC handler runs in the timed sections.  The
served path (RPC -> dispatcher -> device -> response) has not been
measured; ROADMAP S0 replaces this file with the benchmark that does.

What is measured: the serving device step — the TPU-native replacement
for the reference's Redis INCRBY+EXPIRE round trip
(reference src/redis/fixed_cache_impl.go:33-113) — at the largest
serving bucket (4096 lanes), steady state, for the fixed-window kernel
and (shorter sections) the pluggable sliding-window and GCRA kernels
(models/registry.py, docs/ALGORITHMS.md).

Protocol (see benchmarks/PERF_NOTES.md for the measurements that shaped
it):

- The serving engine dedups same-key lanes host-side (the slot table
  walks every key anyway), so the device step's contract is UNIQUE
  slots per batch (models/fixed_window.py step_counters_unique); the
  bench feeds it disjoint 4096-slot slices of a random permutation of
  the 1M-slot space, i.e. the hardest case: every lane a distinct
  random key.
- Inputs are generated on device at setup, untimed.
- Each dispatch scans STEPS_PER_CALL batches (the dispatcher likewise
  keeps the device queue full); CALLS dispatches are enqueued
  back-to-back (enqueue is async) and the timed section ends when the
  per-call digests + the final step's saturated per-lane readback
  (the exact serving payload, u16) are fetched.
- Every step's full decision payload is computed and folded into the
  digest, which is verified afterwards against a host numpy replay of
  all CALLS x STEPS_PER_CALL batches, so no device work can be
  dead-code-eliminated and the counters must be bit-exact.  Each
  kernel's verdict rides the final record's "digests" field; any
  "fail" exits non-zero.

Runs on a TPU only: the platform fields come from jax.devices()[0],
and anything else exits non-zero before a number is produced (a CPU
rate must never be written under a device metric's name).

Prints the result as the FINAL JSON line:
  {"metric": ..., "value": N, "unit": ..., "platform": ...,
   "device_kind": ..., "devices": N, "digests": {...}, ...}
"""

from __future__ import annotations

import json
import sys
import time

import numpy as np

#: One layer's unit: decisions a lax.scan of the device step retires,
#: never the served path's decisions/s.
UNIT = "kernel-scan decisions/s/chip"
BATCH = 4096
NUM_SLOTS = 1 << 20
STEPS_PER_CALL = 256  # one full permutation of the slot space
CALLS = 128
LIMIT_MAX = 1000
# The per-algorithm sections are shorter: they exist to RECORD each
# kernel's throughput beside the headline, not to re-anchor it.
ALGO_STEPS_PER_CALL = 64
ALGO_CALLS = 16


def _fail(e: AssertionError) -> str:
    """A digest verdict for a kernel that disagreed with its numpy
    replay: the first line of the assertion, so the record says where."""
    return "fail: " + (str(e).strip().splitlines() or ["mismatch"])[0][:200]


def _bench_algorithm(name: str) -> tuple:
    """Timed steady-state section for one generic-algorithm kernel
    (models/registry.py step_serve_packed protocol): device-resident
    int32[5, BATCH] packed batches over unique slots, scanned
    STEPS_PER_CALL at a time, digest-folded so nothing is dead code,
    then verified against the model's numpy reference_step replay —
    state and readback bit-exact (both kernels are integer arithmetic
    throughout).
    Returns (decisions/sec, digest verdict)."""
    import functools
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from ratelimit_tpu.models.registry import get_algorithm

    model = get_algorithm(name).make_model(NUM_SLOTS, 0.8)
    state = model.init_state()
    k = ALGO_STEPS_PER_CALL
    now_host = 1_700_000_040  # window-aligned: divider 60 divides it

    key = jax.random.key(17)
    k_perm, k_hits, k_lim = jax.random.split(key, 3)
    perm = jax.random.permutation(k_perm, NUM_SLOTS).astype(jnp.int32)
    slots = perm[: k * BATCH].reshape(k, BATCH)
    hits = jax.random.randint(k_hits, (k, BATCH), 1, 4, jnp.int32)
    limits = jax.random.randint(k_lim, (k, BATCH), 1, LIMIT_MAX, jnp.int32)
    packed = jnp.stack(
        [
            slots,
            hits,
            limits,
            jnp.zeros((k, BATCH), jnp.int32),  # fresh: lazy reset path
            jnp.full((k, BATCH), 60, jnp.int32),  # divider
        ],
        axis=1,
    )  # (k, 5, BATCH)
    now = jnp.asarray(now_host, jnp.int32)

    @functools.partial(jax.jit, donate_argnums=0)
    def run_pipeline(state, packed):
        def body(st, pk):
            st, out = model.step_serve_packed(st, pk, now)
            return st, jnp.sum(
                out.astype(jnp.uint32), dtype=jnp.uint32
            )  # modular digest; replayed on host

        state, digests = jax.lax.scan(body, state, packed)
        return state, jnp.sum(digests, dtype=jnp.uint32)

    state, digest = run_pipeline(state, packed)  # compile+warm
    warm_digest = int(jax.device_get(digest))

    start = time.perf_counter()
    outs = []
    for _ in range(ALGO_CALLS):
        state, digest = run_pipeline(state, packed)
        outs.append(digest)
    fetched = jax.device_get(outs)
    elapsed = time.perf_counter() - start

    # --- verification (untimed): numpy replay of every batch ----------
    h_slots = np.asarray(jax.device_get(slots))
    h_hits = np.asarray(jax.device_get(hits)).astype(np.uint32)
    h_limits = np.asarray(jax.device_get(limits)).astype(np.uint32)
    rows = len(model.state_rows)
    ref = np.zeros((rows, NUM_SLOTS), np.uint32)
    fresh = np.zeros(BATCH, bool)
    divider = np.full(BATCH, 60, np.uint32)
    digests = np.zeros(1 + ALGO_CALLS, np.uint32)
    for call in range(1 + ALGO_CALLS):
        acc = np.uint32(0)
        for s in range(k):
            out = model.reference_step(
                ref, h_slots[s], h_hits[s], h_limits[s], fresh, divider,
                now_host,
            )
            flat = (
                np.concatenate([o.reshape(-1) for o in out])
                if isinstance(out, tuple)
                else out.reshape(-1)
            )
            acc = np.uint32(
                acc + np.uint32(flat.astype(np.uint32).sum(dtype=np.uint32))
            )
        digests[call] = acc
    try:
        assert warm_digest == int(digests[0]), (
            "warmup digest", warm_digest, int(digests[0]),
        )
        for i, d in enumerate(fetched):
            assert int(d) == int(digests[1 + i]), ("digest call", i)
        final_state = np.asarray(jax.device_get(state))
        np.testing.assert_array_equal(final_state, ref, err_msg=name)
        verdict = "pass"
    except AssertionError as e:
        verdict = _fail(e)

    return BATCH * k * ALGO_CALLS / elapsed, verdict


def _bench_launches() -> dict:
    """Drive a real BatchDispatcher with a launch recorder attached
    (bursts of 8 under an open 50ms window, flushed per burst) and
    return the ring-derived digest — launches, coalescing, phase
    p99s — for the BENCH record's ``launches`` section."""
    from ratelimit_tpu.backends.dispatcher import (
        BatchDispatcher,
        Lane,
        WorkItem,
    )
    from ratelimit_tpu.backends.engine import CounterEngine
    from ratelimit_tpu.observability.launches import (
        OUTCOME_OK,
        make_launch_recorder,
    )

    engine = CounterEngine(num_slots=1 << 12)
    d = BatchDispatcher(engine, batch_window_us=50_000, batch_limit=4096)
    lr = make_launch_recorder(1 << 10)
    try:
        # Warm the jit cache BEFORE attaching the recorder, so the
        # ring digests steady-state launches, not the XLA compile.
        warm = WorkItem(
            now=1_700_000_000,
            lanes=[
                Lane(
                    key="bench_warm_0",
                    expiry=1_700_000_060,
                    limit=1000,
                    shadow=False,
                    hits=1,
                )
            ],
            apply=lambda dec: None,
        )
        d.submit(warm)
        d.flush()
        warm.wait(30.0)
        d.launches = lr
        for burst in range(64):
            items = [
                WorkItem(
                    now=1_700_000_000,
                    lanes=[
                        Lane(
                            key=f"bench_k{(burst * 8 + j) % 128}_0",
                            expiry=1_700_000_060,
                            limit=1000,
                            shadow=False,
                            hits=1,
                        )
                    ],
                    apply=lambda dec: None,
                )
                for j in range(8)
            ]
            for it in items:
                d.submit(it)
            d.flush()
            for it in items:
                it.wait(10.0)
    finally:
        d.stop()
    live = lr.snapshot()
    ok = live[live["outcome"] == OUTCOME_OK]
    return {
        "launches": int(lr.stamped()),
        "items": int(live["items"].sum()),
        "coalesce_items_per_launch": lr.coalesce_ratio(),
        "p99_launch_us": round(lr.p99_launch_ns() / 1e3, 1),
        "p99_complete_us": (
            round(float(np.percentile(ok["complete_ns"], 99)) / 1e3, 1)
            if len(ok)
            else 0.0
        ),
        "ok": int(len(ok)),
        "faults": int(len(live) - len(ok)),
    }


def main() -> int:
    import jax
    import jax.numpy as jnp

    from ratelimit_tpu.settings import configure_compile_cache

    configure_compile_cache()
    device = jax.devices()[0]
    if device.platform != "tpu":
        print(
            f"bench.py measures the chip; JAX runs on {device.platform!r} "
            f"({device.device_kind}) here — refusing to produce a number",
            file=sys.stderr,
        )
        return 2
    where = {
        "platform": device.platform,
        "device_kind": device.device_kind,
        "devices": len(jax.devices()),
    }

    from ratelimit_tpu.models.fixed_window import DeviceBatch, FixedWindowModel

    model = FixedWindowModel(NUM_SLOTS)
    counts = model.init_state()

    # --- device-side input generation (setup, untimed) ----------------
    key = jax.random.key(42)
    k_perm, k_hits, k_lim, k_fresh = jax.random.split(key, 4)
    perm = jax.random.permutation(k_perm, NUM_SLOTS).astype(jnp.int32)
    k = STEPS_PER_CALL
    stacked = DeviceBatch(
        slots=perm.reshape(k, BATCH),  # unique within (and across) steps
        hits=jax.random.randint(k_hits, (k, BATCH), 1, 4, jnp.uint32),
        limits=jax.random.randint(k_lim, (k, BATCH), 1, LIMIT_MAX, jnp.uint32),
        fresh=jax.random.bernoulli(k_fresh, 0.05, (k, BATCH)),
        shadow=jnp.zeros((k, BATCH), dtype=bool),
    )

    import functools

    @functools.partial(jax.jit, donate_argnums=0)
    def run_pipeline(counts, stacked):
        def body(carry, batch):
            counts, _ = carry
            # The serving fast path: unique-slot update + saturated
            # narrow readback (engine.py picks u8/u16 by limit cap;
            # limits here are <1000 -> u16).
            counts, afters = model.update_unique(counts, batch)
            cap = batch.limits + batch.hits.astype(jnp.uint32)
            sat = jnp.minimum(afters, cap).astype(jnp.uint16)
            # Per-step digest folds every lane's result so nothing is
            # dead code; uint32 wraparound is replayed on host.
            return (counts, sat), jnp.sum(sat.astype(jnp.uint32))

        init = (counts, jnp.zeros((BATCH,), dtype=jnp.uint16))
        (counts, last_sat), digests = jax.lax.scan(body, init, stacked)
        # last_sat is the final step's per-lane payload (the exact
        # serving readback shape), verified lane-for-lane on host.
        return counts, jnp.sum(digests), last_sat

    counts, digest, tail = run_pipeline(counts, stacked)  # compile+warm
    warm_digest = int(jax.device_get(digest))
    warm_tail = np.asarray(jax.device_get(tail))

    # --- timed steady state -------------------------------------------
    start = time.perf_counter()
    outs = []
    for _ in range(CALLS):
        counts, digest, tail = run_pipeline(counts, stacked)
        outs.append((digest, tail))
    fetched = jax.device_get(outs)  # one batched fetch of 4B+4B per call
    elapsed = time.perf_counter() - start

    decisions = BATCH * STEPS_PER_CALL * CALLS

    # --- verification (untimed): numpy replay of every batch ----------
    h_slots = np.asarray(jax.device_get(stacked.slots))
    h_hits = np.asarray(jax.device_get(stacked.hits))
    h_limits = np.asarray(jax.device_get(stacked.limits))
    h_fresh = np.asarray(jax.device_get(stacked.fresh))
    table = np.zeros(NUM_SLOTS, dtype=np.uint32)
    digests = np.zeros(1 + CALLS, dtype=np.uint32)
    tails = []
    for call in range(1 + CALLS):
        acc = np.uint32(0)
        for s in range(STEPS_PER_CALL):
            sl, hi, li, fr = h_slots[s], h_hits[s], h_limits[s], h_fresh[s]
            before = np.where(fr, np.uint32(0), table[sl])
            # Saturating add, mirroring the device counter domain
            # (update_unique clamps at u32 max instead of wrapping);
            # bench values never reach it, but the replay formula must
            # match the kernel's semantics exactly.
            after = np.minimum(
                before.astype(np.uint64) + hi, np.uint64(0xFFFFFFFF)
            ).astype(np.uint32)
            table[sl] = after
            sat = np.minimum(after, li + hi).astype(np.uint16)
            acc = np.uint32(acc + np.uint32(sat.astype(np.uint32).sum()))
        digests[call] = acc
        tails.append(sat)
    verdicts = {}
    try:
        assert warm_digest == int(digests[0]), "warmup digest mismatch"
        np.testing.assert_array_equal(warm_tail, tails[0])
        for i, (d, t) in enumerate(fetched):
            assert int(d) == int(digests[1 + i]), f"digest mismatch call {i}"
            np.testing.assert_array_equal(np.asarray(t), tails[1 + i])
        verdicts["fixed_window"] = "pass"
    except AssertionError as e:
        verdicts["fixed_window"] = _fail(e)

    decisions_per_sec = decisions / elapsed

    # --- launch flight recorder (observability/launches.py) -----------
    # A short serving-path leg through a REAL dispatcher with the
    # recorder attached: the BENCH record carries the ring-derived
    # coalescing + phase digest so the launch-shape trajectory is
    # tracked round over round alongside raw kernel throughput.
    launches = _bench_launches()
    print(json.dumps({"event": "launches_bench", **where, **launches}), flush=True)

    # --- pluggable-algorithm kernels (models/registry.py) -------------
    algorithms = {"fixed_window": round(decisions_per_sec, 1)}
    for algo in ("sliding_window", "gcra"):
        dps, verdicts[algo] = _bench_algorithm(algo)
        algorithms[algo] = round(dps, 1)
        print(
            json.dumps(
                {
                    "event": "algorithm_bench",
                    "algorithm": algo,
                    "value": round(dps, 1),
                    "unit": UNIT,
                    "digest": verdicts[algo],
                    **where,
                }
            ),
            flush=True,
        )

    print(
        json.dumps(
            {
                "metric": "fixed_window_kernel_scan_decisions_per_sec",
                "value": round(decisions_per_sec, 1),
                "unit": UNIT,
                **where,
                "digests": verdicts,
                "algorithms": algorithms,
                "launches": launches,
            }
        )
    )
    return 0 if all(v == "pass" for v in verdicts.values()) else 1


if __name__ == "__main__":
    sys.exit(main())
