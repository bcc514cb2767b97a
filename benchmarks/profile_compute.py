"""True device-compute cost via slope measurement.

Per-step timings are inferred from total (enqueue+fetch) time as a
function of scan length: slope = true per-step device cost. Fetch is a
tiny digest so readback is constant. Also probes whether the
host<->device link compresses (zeros vs random fetch) and whether
fetches batch.
"""

from __future__ import annotations

import time

import numpy as np

BATCH = 4096
NUM_SLOTS = 1 << 20


def main() -> None:
    import jax
    import jax.numpy as jnp

    from ratelimit_tpu.models.fixed_window import DeviceBatch, FixedWindowModel

    print(f"devices={jax.devices()}")
    model = FixedWindowModel(NUM_SLOTS)

    r = np.random.default_rng(7)

    def make(k):
        return DeviceBatch(
            slots=jnp.asarray(r.integers(0, NUM_SLOTS, (k, BATCH)), dtype=jnp.int32),
            hits=jnp.asarray(r.integers(1, 4, (k, BATCH)), dtype=jnp.uint32),
            limits=jnp.asarray(r.integers(1, 1000, (k, BATCH)), dtype=jnp.uint32),
            fresh=jnp.asarray(r.random((k, BATCH)) < 0.05),
            shadow=jnp.asarray(np.zeros((k, BATCH), dtype=bool)),
        )

    def runner(k):
        stacked = make(k)

        @jax.jit
        def run(counts, stacked):
            def body(counts, batch):
                counts, afters = model.update(counts, batch)
                return counts, jnp.sum(afters, dtype=jnp.uint32)

            counts, sums = jax.lax.scan(body, counts, stacked)
            return jnp.sum(sums)  # 4-byte digest

        return run, stacked

    results = {}
    for k in (64, 512, 2048):
        run, stacked = runner(k)
        counts = model.init_state()
        _ = jax.device_get(run(counts, stacked))  # compile+warm
        best = float("inf")
        for _ in range(3):
            counts = model.init_state()
            t0 = time.perf_counter()
            d = jax.device_get(run(counts, stacked))
            best = min(best, time.perf_counter() - t0)
        results[k] = best
        print(f"scan k={k:5d}: total {best*1e3:9.1f} ms  digest={int(d)}")

    k1, k2 = 64, 2048
    slope = (results[k2] - results[k1]) / (k2 - k1)
    print(
        f"per-step device cost: {slope*1e6:.2f} us/step "
        f"-> {BATCH/slope/1e6 if slope > 0 else float('inf'):.1f} M dec/s compute ceiling"
    )

    # Link compression probe: zeros vs random 8MiB.
    n = 2 << 20
    z = jnp.zeros((n,), jnp.uint32) + jnp.uint32(0)
    key = jax.random.key(0)
    rnd = jax.random.bits(key, (n,), jnp.uint32)
    for name, a in (("zeros", z), ("random", rnd)):
        jax.device_get(a)
        t0 = time.perf_counter()
        jax.device_get(a)
        dt = time.perf_counter() - t0
        print(f"fetch 8MiB {name}: {dt*1e3:8.1f} ms ({4*n/dt/1e6:7.1f} MB/s)")

    # Batched fetch: 8 x 1MiB as one device_get vs sequential.
    arrs = [jax.random.bits(jax.random.key(i), (1 << 18,), jnp.uint32) for i in range(8)]
    for a in arrs:
        jax.device_get(a)
    t0 = time.perf_counter()
    jax.device_get(arrs)
    t_batch = time.perf_counter() - t0
    t0 = time.perf_counter()
    for a in arrs:
        jax.device_get(a)
    t_seq = time.perf_counter() - t0
    print(f"8x1MiB fetch: batched {t_batch*1e3:.1f} ms, sequential {t_seq*1e3:.1f} ms")


if __name__ == "__main__":
    main()
