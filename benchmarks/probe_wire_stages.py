"""Quick wire-stage probe: where do the milliseconds above the
in-process path go?  (Iteration tool for the r5 wire work; the
committed artifact comes from closed_loop_p99.py.)

Run:  JAX_PLATFORMS=cpu python benchmarks/probe_wire_stages.py
"""

from __future__ import annotations

import os
import sys
import threading
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np  # noqa: E402

from closed_loop_p99 import BENCH_YAML, DESCRIPTORS, WINDOW_US  # noqa: E402


def pct(a, q):
    return round(float(np.percentile(np.asarray(a), q)) * 1e3, 3)


def main():
    import tempfile

    import grpc

    from ratelimit_tpu.runner import Runner
    from ratelimit_tpu.server import grpc_server as gsrv
    from ratelimit_tpu.settings import Settings
    from ratelimit_tpu.utils.time import PinnedTimeSource

    from ratelimit_tpu.server import pb  # noqa: F401
    from envoy.service.ratelimit.v3 import rls_pb2

    tmp = tempfile.TemporaryDirectory()
    root = tmp.name
    os.makedirs(os.path.join(root, "rl", "config"))
    with open(os.path.join(root, "rl", "config", "c.yaml"), "w") as f:
        f.write(BENCH_YAML)
    r = Runner(
        Settings(
            host="127.0.0.1", port=0, grpc_host="127.0.0.1", grpc_port=0,
            debug_host="127.0.0.1", debug_port=0, use_statsd=False,
            backend_type="tpu", tpu_num_slots=1 << 16,
            tpu_batch_window_us=WINDOW_US, tpu_batch_limit=1024,
            tpu_batch_buckets=[8, 32, 128, 1024],
            runtime_path=root, runtime_subdirectory="rl",
            local_cache_size_in_bytes=0, expiration_jitter_max_seconds=0,
            tpu_warmup=True,
        ),
        time_source=PinnedTimeSource(1_000_000),
    )
    r.start()

    stages = []
    lock = threading.Lock()

    def sink(recv, decoded, serviced, serialized):
        with lock:
            stages.append((recv, decoded, serviced, serialized))

    gsrv.set_stage_sink(sink)
    try:
        addr = f"127.0.0.1:{r.grpc_server.bound_port}"
        with grpc.insecure_channel(addr) as channel:
            method = channel.unary_unary(
                "/envoy.service.ratelimit.v3.RateLimitService/ShouldRateLimit",
                request_serializer=rls_pb2.RateLimitRequest.SerializeToString,
                response_deserializer=rls_pb2.RateLimitResponse.FromString,
            )
            reqs = []
            for i in range(2000):
                q = rls_pb2.RateLimitRequest(domain="bench", hits_addend=1)
                for j in range(DESCRIPTORS):
                    d = q.descriptors.add()
                    e = d.entries.add()
                    e.key, e.value = "k", f"r{i}d{j}"
                reqs.append(q)
            method(reqs[0], timeout=60)
            stages.clear()
            lat = []
            for q in reqs:
                t0 = time.perf_counter()
                method(q, timeout=60)
                lat.append((t0, time.perf_counter()))
        totals = [b - a for a, b in lat]
        decode = [d - a for a, d, _s, _z in stages]
        service = [s - d for _a, d, s, _z in stages]
        encode = [z - s for _a, _d, s, z in stages]
        handler = [z - a for a, _d, _s, z in stages]
        # Client->handler entry and serialized->client-return residual:
        # needs pairing (same order, closed loop C1).
        pre = [sa - t0 for (t0, _t1), (sa, _d, _s, _z) in zip(lat, stages)]
        post = [t1 - z for (_t0, t1), (_a, _d, _s, z) in zip(lat, stages)]
        for name, v in (
            ("total", totals), ("client_to_handler(pre)", pre),
            ("handler_decode", decode), ("handler_service", service),
            ("handler_encode_serialize", encode), ("handler_total", handler),
            ("handler_to_client(post)", post),
        ):
            print(f"{name:28s} p50={pct(v,50):7.3f}ms p99={pct(v,99):7.3f}ms")
    finally:
        gsrv.set_stage_sink(None)
        r.stop()
        tmp.cleanup()


if __name__ == "__main__":
    main()
