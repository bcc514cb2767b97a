"""Seeded randomized differential fuzzer for the native boundary.

Drives the C++ slot table (and the fused decide kernel) and the pure-
Python oracles through the same randomized workload and asserts
operation-for-operation parity — the dynamic complement of the
`native-abi-contract` static rule: the rule proves the signatures
agree, this proves the *behavior* does, and under `make
sanitize-native` every batch also runs with ASan+UBSan watching the
C++ side (docs/STATIC_ANALYSIS.md).

Adversarial surface, on top of plain workloads:

- keys with embedded NULs, non-ASCII (multi-byte utf-8), and
  100-300-char arena-straddling lengths;
- a capacity-pressure pair (4 slots) whose batches constantly evict
  (eviction-order parity is the hardest invariant);
- batch pinning via the begin/end protocol interleaved with single
  assigns;
- exhaustion: batches with more distinct live keys than slots must
  raise on BOTH sides;
- export_packed + from_packed checkpoint round-trips;
- the fused dedup call vs python assign + engine._dedup_chunk, and
  the decide kernel vs _decide_host with saturating device counters.

Exit 0 and a one-line summary when every batch is clean; the first
divergence raises with the seed and batch index (re-run with --seed
to reproduce).
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

from ratelimit_tpu.backends import native_slot_table as nst
from ratelimit_tpu.backends.slot_table import SlotTable

ADVERSARIAL_FRAGMENTS = [
    "a\x00b",  # embedded NUL
    "\x00lead",
    "ключ",  # multi-byte utf-8
    "限流-キー",
    "\U0001f512lock",
    "dom.v1|user=42|ip=10.0.0.1",
]


class KeyGen:
    def __init__(self, rng):
        self.rng = rng

    def one(self):
        r = self.rng.random()
        if r < 0.50:  # small hot space: duplicates + reuse across batches
            return f"k{int(self.rng.integers(0, 40))}"
        if r < 0.70:  # adversarial fragment, possibly repeated
            frag = ADVERSARIAL_FRAGMENTS[
                int(self.rng.integers(0, len(ADVERSARIAL_FRAGMENTS)))
            ]
            return frag + str(int(self.rng.integers(0, 8)))
        if r < 0.85:  # arena-straddling long key
            n = int(self.rng.integers(100, 301))
            return "L" + "x" * n + str(int(self.rng.integers(0, 6)))
        return f"cold{int(self.rng.integers(0, 10_000))}"

    def batch(self, n):
        return [self.one() for _ in range(n)]


def _eq(name, a, b, ctx):
    np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b), err_msg=f"{ctx}: {name}"
    )


class Harness:
    def __init__(self, seed, with_decide=True):
        self.rng = np.random.default_rng(seed)
        self.keys = KeyGen(self.rng)
        self.now = 0
        self.pairs = {"main": self._pair(48), "pressure": self._pair(4)}
        self.with_decide = with_decide
        if with_decide:
            # engine imports jax; keep it off the accelerator.
            os.environ.setdefault("JAX_PLATFORMS", "cpu")
            import ratelimit_tpu.backends.engine as eng

            self.eng = eng
        self.stats = {
            "assign": 0,
            "dedup": 0,
            "decide": 0,
            "pin": 0,
            "roundtrip": 0,
            "exhaustion": 0,
        }

    def _pair(self, slots):
        return [SlotTable(slots), nst.NativeSlotTable(slots)]

    # -- one fuzz batch ----------------------------------------------

    def step(self, i):
        rng = self.rng
        self.now += int(rng.integers(0, 4))
        label = "pressure" if rng.random() < 0.35 else "main"
        pair = self.pairs[label]
        ctx = f"batch {i} ({label}, now={self.now})"
        r = rng.random()
        if r < 0.08:
            self.check_exhaustion(label, ctx)
        elif r < 0.18:
            self.check_pinning(label, ctx)
        elif r < 0.26:
            self.check_roundtrip(pair, ctx)
        elif r < 0.55:
            self.check_assign(label, ctx)
        else:
            self.check_dedup(label, ctx)

    def _run_both(self, label, ctx, op):
        """op(table) on the python then the native table; a capacity
        overflow must hit BOTH or NEITHER.  After an agreed overflow
        the pair is rebuilt (the oracle raises mid-batch, so partial
        state is unspecified) and None is returned."""
        results, raised = [], []
        for table in self.pairs[label]:
            try:
                results.append(op(table))
                raised.append(False)
            except RuntimeError:
                results.append(None)
                raised.append(True)
        assert raised[0] == raised[1], f"{ctx}: exhaustion parity {raised}"
        if raised[0]:
            self.pairs[label] = self._pair(self.pairs[label][1].num_slots)
            self.stats["exhaustion"] += 1
            return None
        return results

    def check_assign(self, label, ctx):
        n = int(self.rng.integers(1, 14))
        keys = self.keys.batch(n)
        exp = [self.now + int(self.rng.integers(1, 40)) for _ in range(n)]
        res = self._run_both(
            label, ctx, lambda t: t.assign_batch(keys, self.now, exp)
        )
        if res is None:
            return
        (s1, f1), (s2, f2) = res
        py, nat = self.pairs[label]
        _eq("slots", s1, s2, ctx)
        _eq("fresh", f1, f2, ctx)
        assert len(py) == len(nat), ctx
        assert py.evictions == nat.evictions, ctx
        if self.rng.random() < 0.25:
            assert py.gc(self.now) == nat.gc(self.now), f"{ctx}: gc"
        self.stats["assign"] += 1

    def check_dedup(self, label, ctx):
        n = int(self.rng.integers(1, 14))
        keys = self.keys.batch(n)
        exp = np.asarray(
            [self.now + int(self.rng.integers(1, 40)) for _ in range(n)],
            dtype=np.int64,
        )
        hits = self.rng.integers(0, 7, n).astype(np.uint32)
        limits = self.rng.integers(1, 50, n).astype(np.uint32)
        blob, lens = nst._pack_keys(keys)

        def op(table):
            if isinstance(table, nst.NativeSlotTable):
                return table.assign_dedup_packed(
                    blob, lens, self.now, exp, hits, limits
                )
            return table.assign_batch(keys, self.now, exp)

        res = self._run_both(label, ctx, op)
        if res is None:
            return
        (slots_py, fresh_py), fused = res
        inv, uniq, totals, prefix, fresh_g, limit_max = fused
        oracle = self._dedup_oracle(slots_py, hits, limits, fresh_py)
        _eq("inv", oracle.inv, inv, ctx)
        _eq("uniq_slots", oracle.uniq_slots, uniq, ctx)
        _eq("totals", oracle.totals, totals, ctx)
        _eq("prefix", oracle.prefix, prefix[: len(slots_py)], ctx)
        _eq("fresh_g", oracle.fresh, fresh_g, ctx)
        _eq("limit_max", oracle.limit_max, limit_max, ctx)
        self.stats["dedup"] += 1
        if self.with_decide and self.rng.random() < 0.5:
            self.check_decide(oracle, hits, limits, ctx)

    def _dedup_oracle(self, slots, hits, limits, fresh):
        if self.with_decide:
            chunk = self.eng._dedup_chunk
        else:
            from ratelimit_tpu.backends.engine import _dedup_chunk as chunk
        return chunk(
            np.asarray(slots, dtype=np.int32),
            hits,
            limits,
            np.asarray(fresh, dtype=bool),
        )

    def check_decide(self, dedup, hits, limits, ctx):
        """Native fused decide vs the numpy oracle, with saturating
        device counters including near-u32-max lap cases."""
        eng = self.eng
        g = len(dedup.uniq_slots)
        before = self.rng.integers(0, 60, g).astype(np.uint64)
        lap = self.rng.random(g) < 0.1
        before[lap] = np.uint64(0xFFFFFFFF) - self.rng.integers(
            0, 3, int(lap.sum())
        ).astype(np.uint64)
        afters_g = np.minimum(
            before + dedup.totals, np.uint64(0xFFFFFFFF)
        ).astype(np.uint32)
        shadow = (self.rng.random(len(hits)) < 0.2).astype(bool)
        ratio = float(self.rng.choice([0.0, 0.5, 0.8, 1.0]))

        saved = eng._NATIVE_DECIDE
        try:
            eng._NATIVE_DECIDE = False
            want = eng._decide_host(afters_g, hits, limits, shadow, ratio, dedup)
            eng._NATIVE_DECIDE = None
            got = eng._decide_host(afters_g, hits, limits, shadow, ratio, dedup)
            assert eng._NATIVE_DECIDE is not False, "native decide not loaded"
        finally:
            eng._NATIVE_DECIDE = saved
        for f in (
            "codes",
            "limit_remaining",
            "befores",
            "afters",
            "over_limit",
            "near_limit",
            "within_limit",
            "shadow_mode",
        ):
            _eq(
                f,
                np.asarray(getattr(want, f), dtype=np.int64),
                np.asarray(getattr(got, f), dtype=np.int64),
                ctx,
            )
        _eq(
            "set_local_cache",
            np.asarray(want.set_local_cache, dtype=bool),
            np.asarray(got.set_local_cache, dtype=bool),
            ctx,
        )
        self.stats["decide"] += 1

    def check_pinning(self, label, ctx):
        """begin/end protocol with single assigns in between: the
        touched set must survive identically on both sides."""
        n = int(self.rng.integers(2, 6))
        keys = self.keys.batch(n)
        exp = [self.now + int(self.rng.integers(1, 40)) for _ in range(n)]

        def op(table):
            table.begin_batch()
            try:
                return [
                    table.assign(k, self.now, e) for k, e in zip(keys, exp)
                ]
            finally:
                table.end_batch()

        res = self._run_both(label, ctx, op)
        if res is None:
            return
        assert res[0] == [
            (int(s), bool(f)) for s, f in res[1]
        ], f"{ctx}: pinned assigns"
        py, nat = self.pairs[label]
        assert sorted(py.entries()) == sorted(nat.export_packed().tuples()), f"{ctx}: entries"
        self.stats["pin"] += 1

    def check_roundtrip(self, pair, ctx):
        py, nat = pair
        assert sorted(py.entries()) == sorted(nat.export_packed().tuples()), f"{ctx}: entries"
        clone = nst.NativeSlotTable.from_packed(nat.num_slots, nat.export_packed())
        assert sorted(clone.export_packed().tuples()) == sorted(nat.export_packed().tuples()), (
            f"{ctx}: from_entries round-trip"
        )
        self.stats["roundtrip"] += 1

    def check_exhaustion(self, label, ctx):
        """More distinct live keys than slots in one batch must raise
        on BOTH sides; the pair is rebuilt afterwards so both resume
        from identical (empty) state."""
        py, nat = self.pairs[label]
        cap = nat.num_slots
        keys = [f"xh{i}-{self.now}" for i in range(cap + 2)]
        exp = [self.now + 100] * len(keys)
        outcomes = []
        for table in (py, nat):
            try:
                table.assign_batch(keys, self.now, exp)
                outcomes.append("ok")
            except RuntimeError:
                outcomes.append("exhausted")
        assert outcomes[0] == outcomes[1] == "exhausted", f"{ctx}: {outcomes}"
        self.pairs[label] = self._pair(cap)
        self.stats["exhaustion"] += 1


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--batches", type=int, default=10_000)
    ap.add_argument("--seed", type=int, default=20260806)
    ap.add_argument(
        "--no-decide",
        action="store_true",
        help="skip the decide-kernel differential (no jax import)",
    )
    args = ap.parse_args(argv)

    if not nst.available():
        print("fuzz_native: native library unavailable; nothing to fuzz")
        return 1
    h = Harness(args.seed, with_decide=not args.no_decide)
    for i in range(args.batches):
        h.step(i)
        if i and i % 2000 == 0:
            print(f"fuzz_native: {i}/{args.batches} batches clean", flush=True)
    so = nst.loaded_path() or "?"
    parts = ", ".join(f"{k}={v}" for k, v in sorted(h.stats.items()))
    print(
        f"fuzz_native: {args.batches} batches clean, 0 divergences "
        f"(seed {args.seed}; {parts}; lib {os.path.basename(so)})"
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
