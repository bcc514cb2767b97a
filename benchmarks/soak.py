"""Serving soak: sustained closed-loop load; RSS must plateau.

Exercises the leak-prone serving machinery together — slot-table
expiry churn (SECOND-unit windows roll every second), the C++ map's
heap/arena, the keygen stem memo, dispatcher queues, stat tree — and
records the RSS trajectory.  Passing = RSS flat at steady state
(growth between the early and late sample windows under the bound;
the early ramp is the slot table / memo / allocator arenas filling to
capacity).

The RSS trajectory is sampled twice on purpose: the script's own
10s poll (the raw ``rss_samples`` rows) AND a live
observability.timeseries sampler thread running exactly as it does in
serving — the flat-ceiling assertion runs against BOTH, so a
regression in the tsdb path itself (a leak, a dead sampler, a torn
ring) fails the soak even when the raw poll looks flat.

Run:  JAX_PLATFORMS=cpu python benchmarks/soak.py \
          [--seconds 180] [--threads 4]
Writes benchmarks/results/soak_rss.json.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np  # noqa: E402

YAML = (
    "domain: soak\n"
    "descriptors:\n"
    "  - key: k\n"
    "    rate_limit:\n"
    "      unit: second\n"
    "      requests_per_unit: 50\n"
)


def rss_mb() -> float:
    with open("/proc/self/status") as f:
        for line in f:
            if line.startswith("VmRSS"):
                return int(line.split()[1]) / 1024
    return 0.0


def main(argv=None) -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--seconds", type=int, default=180)
    p.add_argument("--threads", type=int, default=4)
    p.add_argument("--growth-bound-mb", type=float, default=30.0)
    p.add_argument(
        "--backend", choices=("sync", "write-behind"), default="sync"
    )
    args = p.parse_args(argv)

    from ratelimit_tpu.api import Descriptor, RateLimitRequest
    from ratelimit_tpu.backends.engine import CounterEngine
    from ratelimit_tpu.backends.tpu_cache import TpuRateLimitCache
    from ratelimit_tpu.backends.write_behind import WriteBehindRateLimitCache
    from ratelimit_tpu.config.loader import ConfigFile, load_config
    from ratelimit_tpu.observability.timeseries import (
        TimeSeriesStore,
        register_default_series,
    )
    from ratelimit_tpu.stats.manager import Manager

    mgr = Manager()
    cfg = load_config([ConfigFile("c", YAML)], mgr)
    cache_cls = (
        WriteBehindRateLimitCache
        if args.backend == "write-behind"
        else TpuRateLimitCache
    )
    cache = cache_cls(
        CounterEngine(num_slots=1 << 16, buckets=(8, 32, 128)),
        batch_window_us=200,
    )
    cache.warmup()
    # Live time-series sampler, wired exactly as runner.start does
    # (default series incl. the rss_mb gauge), ticking on its own
    # thread for the whole soak; interval sized for >=24 live rows.
    ts_interval = max(2.0, args.seconds / 36.0)
    ts = TimeSeriesStore(ts_interval, retention_s=2.0 * args.seconds)
    register_default_series(ts, mgr.store, cache=cache)
    ts.start()
    stop = threading.Event()
    sent = [0]
    errors: list = []

    def worker(tid: int) -> None:
        i = 0
        try:
            while not stop.is_set():
                req = RateLimitRequest(
                    "soak", [Descriptor.of(("k", f"v{tid}_{i % 500}"))], 1
                )
                lim = [cfg.get_limit(req.domain, d) for d in req.descriptors]
                cache.do_limit(req, lim)
                sent[0] += 1
                i += 1
        except Exception as e:  # pragma: no cover
            errors.append(repr(e))

    threads = [
        threading.Thread(target=worker, args=(t,))
        for t in range(args.threads)
    ]
    for t in threads:
        t.start()
    samples = []
    t0 = time.monotonic()
    while time.monotonic() - t0 < args.seconds:
        time.sleep(10)
        samples.append(
            {
                "t_s": round(time.monotonic() - t0),
                "rss_mb": round(rss_mb(), 1),
                "requests": sent[0],
            }
        )
    stop.set()
    for t in threads:
        t.join(timeout=20)
    ts.stop()
    cache.flush()
    cache.close()
    assert not errors, errors

    early = float(np.mean([s["rss_mb"] for s in samples[2:5]]))
    late = float(np.mean([s["rss_mb"] for s in samples[-3:]]))

    # The live series is the second witness: the sampler thread must
    # have kept ticking, and ITS rss_mb trajectory must plateau too.
    snap = ts.snapshot()
    ts_rss = [v for v in snap["series"].get("rss_mb", []) if v is not None]
    assert len(ts_rss) >= 8, (
        f"tsdb sampler recorded only {len(ts_rss)} live rss rows "
        f"(interval {ts_interval:.1f}s over {args.seconds}s)"
    )
    k = max(2, len(ts_rss) // 8)
    ts_early = float(np.mean(ts_rss[1 : 1 + k]))
    ts_late = float(np.mean(ts_rss[-k:]))
    out = {
        "note": (
            f"{args.seconds}s closed-loop soak ({args.backend} backend), "
            f"{args.threads} threads, "
            "SECOND-unit windows (slot-table churn every second), "
            "1-core CPU platform, clean env; early ramp = slot table/"
            "memo/arenas filling to capacity, then plateau"
        ),
        "total_requests": sent[0],
        "requests_per_sec": round(sent[0] / args.seconds, 1),
        "rss_samples": samples,
        "rss_early_mb": round(early, 1),
        "rss_late_mb": round(late, 1),
        "growth_mb": round(late - early, 1),
        "timeseries": {
            "interval_s": round(ts_interval, 1),
            "live_rows": len(ts_rss),
            "rss_series_mb": [round(v, 1) for v in ts_rss],
            "rss_early_mb": round(ts_early, 1),
            "rss_late_mb": round(ts_late, 1),
            "growth_mb": round(ts_late - ts_early, 1),
            "summary": ts.summary(),
        },
    }
    suffix = "" if args.backend == "sync" else "_wb"
    path = os.path.join(
        os.path.dirname(__file__), "results", f"soak_rss{suffix}.json"
    )
    with open(path, "w") as f:
        json.dump(out, f, indent=2)
    print(
        json.dumps(
            {k: v for k, v in out.items() if k != "rss_samples"}, indent=1
        )
    )
    assert late - early < args.growth_bound_mb, (
        f"RSS grew {late - early:.1f}MB during soak"
    )
    assert ts_late - ts_early < args.growth_bound_mb, (
        f"live timeseries rss_mb grew {ts_late - ts_early:.1f}MB "
        "during soak"
    )
    print("SOAK PASSED")


if __name__ == "__main__":
    main()
