"""Slope-based component breakdown of the device step.

Measures each component as the slope of total time vs scan length
with a 4-byte digest fetch, so enqueue and readback cost cancel out
of the per-step number.
"""

from __future__ import annotations

import time

import numpy as np

BATCH = 4096
NUM_SLOTS = 1 << 20
KS = (64, 1024)


def main() -> None:
    import jax
    import jax.numpy as jnp

    from ratelimit_tpu.ops.prefix import per_slot_inclusive_prefix

    print(f"devices={jax.devices()} batch={BATCH} slots={NUM_SLOTS}")
    r = np.random.default_rng(7)

    def measure(body):
        times = {}
        for k in KS:
            slots = jnp.asarray(r.integers(0, NUM_SLOTS, (k, BATCH)), jnp.int32)
            hits = jnp.asarray(r.integers(1, 4, (k, BATCH)), jnp.uint32)
            fresh = jnp.asarray(r.random((k, BATCH)) < 0.05)
            counts0 = jnp.zeros((NUM_SLOTS,), jnp.uint32)

            @jax.jit
            def run(counts, slots, hits, fresh):
                def step(counts, xs):
                    counts, out = body(counts, *xs)
                    return counts, jnp.sum(out, dtype=jnp.uint32)

                counts, sums = jax.lax.scan(step, counts, (slots, hits, fresh))
                return jnp.sum(sums)

            jax.device_get(run(counts0, slots, hits, fresh))
            best = float("inf")
            for _ in range(3):
                t0 = time.perf_counter()
                jax.device_get(run(counts0, slots, hits, fresh))
                best = min(best, time.perf_counter() - t0)
            times[k] = best
        k1, k2 = KS
        return (times[k2] - times[k1]) / (k2 - k1)

    def c_noop(counts, s, h, f):
        return counts, h

    def c_fresh(counts, s, h, f):
        idx = jnp.where(f, s, NUM_SLOTS)
        return counts.at[idx].set(jnp.uint32(0), mode="drop"), h

    def c_gather(counts, s, h, f):
        return counts, counts.at[s].get(mode="fill", fill_value=0)

    def c_sort(counts, s, h, f):
        return counts, jnp.argsort(s, stable=True).astype(jnp.uint32)

    def c_prefix(counts, s, h, f):
        return counts, per_slot_inclusive_prefix(s, h)

    def c_scatter_add(counts, s, h, f):
        return counts.at[s].add(h, mode="drop"), h

    def c_scatter_add_unique(counts, s, h, f):
        return counts.at[s].add(h, mode="drop", unique_indices=True), h

    def c_full(counts, s, h, f):
        idx = jnp.where(f, s, NUM_SLOTS)
        counts = counts.at[idx].set(jnp.uint32(0), mode="drop")
        before = counts.at[s].get(mode="fill", fill_value=0)
        incl = per_slot_inclusive_prefix(s, h)
        afters = before + incl
        counts = counts.at[s].add(h, mode="drop")
        return counts, afters

    comps = [
        ("noop", c_noop),
        ("fresh zero scatter-set", c_fresh),
        ("gather before", c_gather),
        ("argsort", c_sort),
        ("prefix(sort+cumsum+segmin)", c_prefix),
        ("scatter-add", c_scatter_add),
        ("scatter-add unique hint", c_scatter_add_unique),
        ("full update", c_full),
    ]
    for name, body in comps:
        us = measure(body) * 1e6
        print(f"{name:28s} {us:9.2f} us/step  {BATCH/us if us>0 else 0:9.1f} M dec/s")


if __name__ == "__main__":
    main()
