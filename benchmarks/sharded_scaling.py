"""Virtual-mesh check of the bank-sharded engine: what the striping
divides and what it does not.

The TABLE divides: each chip holds 1/num_banks of the counters, and
modulo striping spreads a launch's slots evenly over the banks (the
per-bank OWNED lanes below, counted from the slot ids).  The LANES do
not: since PR 50 ownership is decided on the device, every chip runs
the launch's whole bucket and the host packs one launch as on one chip
(routing the lanes on the host, rounds 2-5 and PR 49, cost a served
launch ~0.5 ms on four real chips to spare idle chips 0.4 us: PERF.md
section 5).  On a virtual CPU mesh wall-clock is not chip wall-clock,
so this reports structure only — owned lanes per bank and bit-identity
against the single-chip engine — and no timing.

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python benchmarks/sharded_scaling.py
"""

from __future__ import annotations

import json
import os
import sys

import numpy as np

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

BATCH = 1024
NUM_SLOTS = 1 << 16
STEPS = 20


def main() -> None:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8"
        ).strip()
    import jax

    jax.config.update("jax_platforms", "cpu")

    from ratelimit_tpu.backends.engine import CounterEngine, HostBatch
    from ratelimit_tpu.parallel import ShardedCounterEngine, make_mesh

    rng = np.random.default_rng(5)
    batches = []
    for _ in range(STEPS):
        batches.append(
            HostBatch(
                slots=rng.choice(NUM_SLOTS, BATCH, replace=False).astype(
                    np.int32
                ),
                hits=rng.integers(1, 4, BATCH).astype(np.uint32),
                limits=rng.integers(1, 200, BATCH).astype(np.uint32),
                fresh=rng.random(BATCH) < 0.05,
                shadow=np.zeros(BATCH, dtype=bool),
            )
        )

    ref = CounterEngine(num_slots=NUM_SLOTS)
    ref_decisions = [ref.step(b) for b in batches]

    rows = []
    for nd in (1, 2, 4, 8):
        engine = ShardedCounterEngine(make_mesh(nd), num_slots=NUM_SLOTS)
        bank_counts = []
        for i, b in enumerate(batches):
            # Lanes each bank OWNS in this step, from the slot ids.
            bank_counts.append(
                np.bincount(np.unique(b.slots) % nd, minlength=nd)
            )
            d = engine.step(b)
            np.testing.assert_array_equal(
                d.codes, ref_decisions[i].codes, err_msg=f"mesh {nd}"
            )
            np.testing.assert_array_equal(
                d.afters, ref_decisions[i].afters, err_msg=f"mesh {nd}"
            )
        np.testing.assert_array_equal(
            engine.export_counts(), ref.export_counts()
        )
        # Per-bank OWNED lane counts: each bank's share of the table's
        # traffic shrinks ~1/n and stays balanced (modulo striping).
        bc = np.asarray(bank_counts)  # (steps, nd)
        rows.append(
            {
                "banks": nd,
                "per_bank_owned_lanes_mean": [
                    round(float(x), 1) for x in bc.mean(axis=0)
                ],
                "per_bank_owned_lanes_max": [
                    int(x) for x in bc.max(axis=0)
                ],
                "bank_imbalance_max_over_mean": round(
                    float(bc.max() / max(bc.mean(), 1e-9)), 3
                ),
                "full_batch": BATCH,
            }
        )
        print(rows[-1], flush=True)

    out = os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        "results",
        "sharded_scaling.json",
    )
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w") as f:
        json.dump(rows, f, indent=1)
    print(f"wrote {out}")


if __name__ == "__main__":
    main()
