"""Candidate-variant timing: sorted access, 2D row layout, matmul cumsum.

Slope method (KS wide apart, best-of-5) to beat fetch noise. Digest
folds both the scan outputs and the final table so no
component can be DCE'd.
"""

from __future__ import annotations

import time

import numpy as np

BATCH = 4096
NUM_SLOTS = 1 << 20
ROWS = NUM_SLOTS // 128
KS = (64, 4096)
REPS = 5


def main() -> None:
    import jax
    import jax.numpy as jnp

    print(f"devices={jax.devices()} batch={BATCH} slots={NUM_SLOTS}")
    r = np.random.default_rng(7)

    def measure(body, table_2d=False):
        times = {}
        for k in KS:
            slots = jnp.asarray(r.integers(0, NUM_SLOTS, (k, BATCH)), jnp.int32)
            hits = jnp.asarray(r.integers(1, 4, (k, BATCH)), jnp.uint32)
            fresh = jnp.asarray(r.random((k, BATCH)) < 0.05)
            shape = (ROWS, 128) if table_2d else (NUM_SLOTS,)
            counts0 = jnp.zeros(shape, jnp.uint32)

            @jax.jit
            def run(counts, slots, hits, fresh):
                def step(counts, xs):
                    counts, out = body(counts, *xs)
                    return counts, jnp.sum(out, dtype=jnp.uint32)

                counts, sums = jax.lax.scan(step, counts, (slots, hits, fresh))
                return jnp.sum(sums) + jnp.sum(counts.ravel()[:: NUM_SLOTS // 16])

            jax.device_get(run(counts0, slots, hits, fresh))
            best = float("inf")
            for _ in range(REPS):
                t0 = time.perf_counter()
                jax.device_get(run(counts0, slots, hits, fresh))
                best = min(best, time.perf_counter() - t0)
            times[k] = best
        k1, k2 = KS
        return (times[k2] - times[k1]) / (k2 - k1)

    # --- gather variants ---
    def g_random(counts, s, h, f):
        return counts, counts.at[s].get(mode="fill", fill_value=0)

    def g_sorted(counts, s, h, f):
        ss = jnp.sort(s)
        return counts, counts.at[ss].get(mode="fill", fill_value=0)

    def g_2d_rows(counts, s, h, f):
        rows = s >> 7
        lanes = s & 127
        rowvals = counts.at[rows].get(mode="fill", fill_value=0)  # (B,128)
        onehot = (
            jax.lax.broadcasted_iota(jnp.int32, (BATCH, 128), 1) == lanes[:, None]
        )
        vals = jnp.sum(jnp.where(onehot, rowvals, 0), axis=1, dtype=jnp.uint32)
        return counts, vals

    def g_2d_rows_sorted(counts, s, h, f):
        ss = jnp.sort(s)
        rows = ss >> 7
        lanes = ss & 127
        rowvals = counts.at[rows].get(mode="fill", fill_value=0)
        onehot = (
            jax.lax.broadcasted_iota(jnp.int32, (BATCH, 128), 1) == lanes[:, None]
        )
        vals = jnp.sum(jnp.where(onehot, rowvals, 0), axis=1, dtype=jnp.uint32)
        return counts, vals

    # --- scatter variants ---
    def s_add_random(counts, s, h, f):
        return counts.at[s].add(h, mode="drop"), h

    def s_add_sorted(counts, s, h, f):
        order = jnp.argsort(s, stable=True)
        return counts.at[s[order]].add(h[order], mode="drop"), h

    # --- cumsum variants ---
    def c_cumsum_1d(counts, s, h, f):
        return counts, jnp.cumsum(h, dtype=jnp.uint32)

    def c_cumsum_matmul(counts, s, h, f):
        # two-level blocked cumsum on the MXU: (32,128) view, exact in
        # f32 for sums < 2^24.
        x = h.astype(jnp.float32).reshape(32, 128)
        tri = (
            jax.lax.broadcasted_iota(jnp.int32, (128, 128), 0)
            <= jax.lax.broadcasted_iota(jnp.int32, (128, 128), 1)
        ).astype(jnp.float32)
        within = jax.lax.dot_general(
            x, tri, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )  # (32,128) within-row inclusive
        row_tot = within[:, -1]  # (32,)
        tri32 = (
            jax.lax.broadcasted_iota(jnp.int32, (32, 32), 0)
            < jax.lax.broadcasted_iota(jnp.int32, (32, 32), 1)
        ).astype(jnp.float32)
        carry = jax.lax.dot_general(
            row_tot[None, :], tri32, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )[0]  # exclusive row carries
        out = (within + carry[:, None]).reshape(BATCH).astype(jnp.uint32)
        return counts, out

    def c_argsort_only(counts, s, h, f):
        return counts, jnp.argsort(s, stable=True).astype(jnp.uint32)

    def c_sort_pairs(counts, s, h, f):
        ss, hh = jax.lax.sort([s, h], num_keys=1)
        return counts, hh

    comps = [
        ("gather random 1d", g_random, False),
        ("gather sorted 1d", g_sorted, False),
        ("gather 2d rowgather+select", g_2d_rows, True),
        ("gather 2d sorted rowgather", g_2d_rows_sorted, True),
        ("scatter-add random", s_add_random, False),
        ("scatter-add sorted", s_add_sorted, False),
        ("cumsum 1d", c_cumsum_1d, False),
        ("cumsum matmul 2-level", c_cumsum_matmul, False),
        ("argsort", c_argsort_only, False),
        ("lax.sort pairs", c_sort_pairs, False),
    ]
    for name, body, is2d in comps:
        us = measure(body, is2d) * 1e6
        print(f"{name:28s} {us:9.2f} us/step  {BATCH/us if us>0 else 0:9.1f} M dec/s")


if __name__ == "__main__":
    main()
