"""End-to-end serving sweep: batch window x batch limit.

Mirrors the reference's (disabled) BenchmarkParallelDoLimit
(reference test/redis/bench_test.go:22-97: parallel DoLimit against a
local Redis over a pipeline window {0,35,75,150,300}us x limit
{1..16} sweep, pool = GOMAXPROCS^2) — here the sweep drives the full
TpuRateLimitCache (keygen, dispatcher micro-batching, device step,
host decisions) from a thread pool and reports decisions/sec plus
request-latency percentiles per configuration.

    python benchmarks/sweep.py [--threads 16] [--requests 2000] \
        [--descriptors 4] [--json]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

WINDOWS_US = (0, 35, 75, 150, 300)
BATCH_LIMITS = (256, 1024, 4096)


def link_floor_ms() -> float:
    """Round-trip floor of the host<->device link: one tiny jitted step
    + readback, best of 5 — the part of every per-batch latency below
    that no host-side change can remove."""
    import jax
    import jax.numpy as jnp

    x = jnp.zeros((8,), jnp.uint32)
    f = jax.jit(lambda x: x + 1)
    np.asarray(f(x))
    best = float("inf")
    for _ in range(5):
        t0 = time.perf_counter()
        np.asarray(f(x))
        best = min(best, time.perf_counter() - t0)
    return best * 1e3


def engine_leg_breakdown(buckets=(1, 8, 64, 512, 1024, 4096)):
    """Latency of the DEVICE leg alone (engine.step: pad, launch,
    readback, host decide) per bucket size — separates the dispatcher
    window/queueing from the device round trip."""
    import jax  # noqa: F401

    from ratelimit_tpu.backends.engine import CounterEngine, HostBatch

    engine = CounterEngine(num_slots=1 << 18)
    rows = {}
    rng = np.random.default_rng(3)
    for n in buckets:
        hb = HostBatch(
            slots=rng.choice(1 << 18, n, replace=False).astype(np.int32),
            hits=np.ones(n, dtype=np.uint32),
            limits=np.full(n, 1000, dtype=np.uint32),
            fresh=np.zeros(n, dtype=bool),
            shadow=np.zeros(n, dtype=bool),
        )
        engine.step(hb)  # compile
        best = float("inf")
        for _ in range(5):
            t0 = time.perf_counter()
            engine.step(hb)
            best = min(best, time.perf_counter() - t0)
        rows[n] = round(best * 1e3, 3)
    return rows


def run_config(window_us, batch_limit, threads, requests, descriptors,
               qps=0):
    import jax  # noqa: F401  (device selection happens at import)

    from ratelimit_tpu.api import Descriptor, RateLimitRequest
    from ratelimit_tpu.backends.engine import CounterEngine
    from ratelimit_tpu.backends.tpu_cache import TpuRateLimitCache
    from ratelimit_tpu.config.loader import ConfigFile, load_config
    from ratelimit_tpu.stats.manager import Manager

    yaml_text = (
        "domain: bench\n"
        "descriptors:\n"
        "  - key: k\n"
        "    rate_limit:\n"
        "      unit: hour\n"
        "      requests_per_unit: 1000000\n"
    )
    mgr = Manager()
    cfg = load_config([ConfigFile("config.bench", yaml_text)], mgr)
    cache = TpuRateLimitCache(
        CounterEngine(num_slots=1 << 18),
        batch_window_us=window_us,
        batch_limit=batch_limit,
    )
    try:
        cache.warmup()
        rule_req = RateLimitRequest("bench", [Descriptor.of(("k", "w"))], 1)
        rule = cfg.get_limit("bench", rule_req.descriptors[0])

        reqs = []
        for i in range(requests):
            descs = [
                Descriptor.of(("k", f"v{(i * descriptors + j) % 997}"))
                for j in range(descriptors)
            ]
            reqs.append(RateLimitRequest("bench", descs, 1))
        rules = [rule] * descriptors

        latencies = np.zeros(requests)
        bench_start = [0.0]

        def worker(i):
            if qps > 0:
                # Open-loop pacing: arrivals at the target rate, so
                # latency is serving latency, not closed-loop queueing
                # under total saturation.
                due = bench_start[0] + i / qps
                delay = due - time.perf_counter()
                if delay > 0:
                    time.sleep(delay)
            t0 = time.perf_counter()
            cache.do_limit(reqs[i], rules)
            latencies[i] = time.perf_counter() - t0

        with ThreadPoolExecutor(max_workers=threads) as pool:
            start = time.perf_counter()
            bench_start[0] = start
            list(pool.map(worker, range(requests)))
            elapsed = time.perf_counter() - start

        return {
            "window_us": window_us,
            "batch_limit": batch_limit,
            "qps_target": qps,
            "decisions_per_sec": round(requests * descriptors / elapsed, 1),
            "p50_ms": round(float(np.percentile(latencies, 50)) * 1e3, 3),
            "p99_ms": round(float(np.percentile(latencies, 99)) * 1e3, 3),
        }
    finally:
        cache.close()


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--threads", type=int, default=16)
    p.add_argument("--requests", type=int, default=2000)
    p.add_argument("--descriptors", type=int, default=4)
    p.add_argument(
        "--windows", type=int, nargs="+", default=list(WINDOWS_US),
        help="batch windows (us); 0 = inline (no dispatcher)",
    )
    p.add_argument(
        "--limits", type=int, nargs="+", default=list(BATCH_LIMITS)
    )
    p.add_argument("--json", action="store_true")
    p.add_argument(
        "--out", default="", help="also write a JSON result file with metadata"
    )
    p.add_argument(
        "--qps", type=int, default=0,
        help="open-loop request pacing (0 = closed-loop saturation)",
    )
    p.add_argument(
        "--breakdown", action="store_true",
        help="also measure the device leg alone per bucket size",
    )
    args = p.parse_args(argv)

    import jax

    device = str(jax.devices()[0])
    floor_ms = link_floor_ms()
    if not args.json:
        print(f"device={device}  link round-trip floor={floor_ms:.1f}ms")

    breakdown = None
    if args.breakdown:
        breakdown = engine_leg_breakdown()
        if not args.json:
            print(f"device-leg ms per bucket: {breakdown}")

    rows = []
    for window in args.windows:
        for limit in args.limits:
            row = run_config(
                window, limit, args.threads, args.requests,
                args.descriptors, qps=args.qps,
            )
            rows.append(row)
            if not args.json:
                print(
                    f"window={row['window_us']:>4}us limit={row['batch_limit']:>5} "
                    f"-> {row['decisions_per_sec']:>12,.0f} dec/s  "
                    f"p50={row['p50_ms']:7.3f}ms p99={row['p99_ms']:7.3f}ms",
                    flush=True,
                )
    result = {
        "device": device,
        "link_floor_ms": round(floor_ms, 2),
        "threads": args.threads,
        "requests": args.requests,
        "descriptors": args.descriptors,
        "qps_target": args.qps,
        "device_leg_ms_per_bucket": breakdown,
        "rows": rows,
    }
    if args.json:
        print(json.dumps(result))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(result, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
