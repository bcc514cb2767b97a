"""Graceful drain on shutdown (runner.stop; docs/RESILIENCE.md):
after health flips NOT_SERVING, in-flight RPCs complete, the
dispatcher intake drains, and the final checkpoint snapshot lands on
disk — a SIGTERM'd replica forgives nothing.
"""

import threading
import time

import grpc
import pytest

from ratelimit_tpu.runner import Runner
from ratelimit_tpu.settings import Settings
from ratelimit_tpu.utils.time import PinnedTimeSource

from ratelimit_tpu.server import pb  # noqa: F401  (sys.path for generated)
from envoy.service.ratelimit.v3 import rls_pb2  # noqa: E402

YAML = """
domain: drain
descriptors:
  - key: key1
    rate_limit:
      unit: minute
      requests_per_unit: 100
"""


def _request(domain, pairs, hits=1):
    req = rls_pb2.RateLimitRequest(domain=domain, hits_addend=hits)
    d = req.descriptors.add()
    for k, v in pairs:
        e = d.entries.add()
        e.key = k
        e.value = v
    return req


def test_sigterm_drain_completes_inflight_and_snapshots(tmp_path):
    root = tmp_path / "runtime"
    config_dir = root / "ratelimit" / "config"
    config_dir.mkdir(parents=True)
    (config_dir / "basic.yaml").write_text(YAML)
    ckpt_dir = tmp_path / "ckpt"

    settings = Settings(
        host="127.0.0.1", port=0, grpc_host="127.0.0.1", grpc_port=0,
        debug_host="127.0.0.1", debug_port=0, use_statsd=False,
        backend_type="tpu", tpu_num_slots=1 << 10,
        # A wide batch window holds the RPC in flight long enough for
        # stop() to overlap it.
        tpu_batch_window_us=150_000, tpu_batch_buckets=[8],
        tpu_checkpoint_dir=str(ckpt_dir),
        tpu_checkpoint_interval_s=10_000.0,  # only the final snapshot
        runtime_path=str(root), runtime_subdirectory="ratelimit",
        local_cache_size_in_bytes=0, expiration_jitter_max_seconds=0,
    )
    r = Runner(settings, time_source=PinnedTimeSource(1_000_000))
    r.start()
    port = r.grpc_server.bound_port
    results = {}

    def rpc():
        with grpc.insecure_channel(f"127.0.0.1:{port}") as channel:
            try:
                resp = channel.unary_unary(
                    "/envoy.service.ratelimit.v3.RateLimitService"
                    "/ShouldRateLimit",
                    request_serializer=(
                        rls_pb2.RateLimitRequest.SerializeToString
                    ),
                    response_deserializer=(
                        rls_pb2.RateLimitResponse.FromString
                    ),
                )(_request("drain", [("key1", "x")]), timeout=30)
                results["code"] = resp.overall_code
            except grpc.RpcError as e:  # pragma: no cover - failure detail
                results["error"] = e

    # Let the RPC reach the dispatcher intake (it then parks in the
    # 150 ms batch window), then stop mid-flight.  Wait for the
    # intake itself, not for a fixed time: on a loaded host a thread
    # start plus a connect can take longer than any sleep chosen here,
    # and an RPC that arrives after stop() began is rightly refused.
    submitted = threading.Event()
    for d in r.cache._dispatchers.values():

        def submit(item, submit=d.submit):
            submitted.set()
            submit(item)

        d.submit = submit
    t = threading.Thread(target=rpc)
    t.start()
    assert submitted.wait(20)
    r.stop()
    t.join(timeout=20)
    assert not t.is_alive()

    # The in-flight RPC completed with a real decision (the backend
    # closed AFTER the drain), not an error.
    assert results.get("code") == rls_pb2.RateLimitResponse.OK, results

    # Health flipped before listeners died.
    assert not r.health.healthy

    # The final checkpoint landed and carries the drained decision.
    bank0 = ckpt_dir / "bank0.npz"
    assert bank0.exists()
    import numpy as np

    from ratelimit_tpu.backends.checkpoint import restore_engine
    from ratelimit_tpu.backends.engine import CounterEngine

    eng = CounterEngine(num_slots=1 << 10)
    assert restore_engine(eng, str(bank0), "lane0of1")
    counts = np.asarray(eng.export_counts())
    entries = eng.slot_table.export_packed().tuples()
    assert entries, "snapshot lost the drained key"
    assert sum(int(counts[s]) for _k, s, _e in entries) == 1
