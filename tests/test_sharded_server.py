"""The tpu-sharded backend through a FULL Runner on the 8-device
virtual CPU mesh — the reference's topology-matrix analog
(Makefile:74-102 spins local redis cluster/sentinel processes; here
the 'cluster' is the bank-sharded engine over 8 virtual devices).

Covers what tests/test_sharded.py (engine level) cannot: the Runner's
backend_type="tpu-sharded" wiring, the warm-up through the cache,
the dispatcher over a sharded engine, and wire-exact decisions."""

import grpc
import pytest

from ratelimit_tpu.runner import Runner
from ratelimit_tpu.settings import Settings
from ratelimit_tpu.utils.time import PinnedTimeSource

from ratelimit_tpu.server import pb  # noqa: F401
from envoy.service.ratelimit.v3 import rls_pb2  # noqa: E402

YAML = """
domain: sh
descriptors:
  - key: limited
    rate_limit:
      unit: minute
      requests_per_unit: 4
  - key: persec
    rate_limit:
      unit: second
      requests_per_unit: 2
"""


def _make_runner(tmp_path_factory, name, **overrides):
    """One construction site for the file's Runners: mesh-skip guard,
    config dir, shared Settings defaults, pinned clock (progression
    assertions must never straddle a real window rollover)."""
    import jax

    if overrides.get("backend_type", "tpu-sharded").startswith(
        "tpu-sharded"
    ) and len(jax.devices()) < 8:
        pytest.skip("needs the 8-device virtual CPU mesh")
    root = tmp_path_factory.mktemp(name)
    config_dir = root / "ratelimit" / "config"
    config_dir.mkdir(parents=True)
    (config_dir / "sh.yaml").write_text(YAML)
    base = dict(
        host="127.0.0.1",
        port=0,
        grpc_host="127.0.0.1",
        grpc_port=0,
        debug_host="127.0.0.1",
        debug_port=0,
        use_statsd=False,
        backend_type="tpu-sharded",
        tpu_num_slots=1 << 10,
        tpu_batch_window_us=200,
        tpu_batch_buckets=[8, 32],
        runtime_path=str(root),
        runtime_subdirectory="ratelimit",
        local_cache_size_in_bytes=0,
        expiration_jitter_max_seconds=0,
    )
    base.update(overrides)
    return Runner(
        Settings(**base), time_source=PinnedTimeSource(1_000_000)
    )


@pytest.fixture(scope="module")
def runner(tmp_path_factory):
    r = _make_runner(tmp_path_factory, "sharded-runtime")
    r.start()
    yield r
    r.stop()


def _call(runner, request_pb):
    with grpc.insecure_channel(
        f"127.0.0.1:{runner.grpc_server.bound_port}"
    ) as channel:
        method = channel.unary_unary(
            "/envoy.service.ratelimit.v3.RateLimitService/ShouldRateLimit",
            request_serializer=rls_pb2.RateLimitRequest.SerializeToString,
            response_deserializer=rls_pb2.RateLimitResponse.FromString,
        )
        return method(request_pb, timeout=60)


def _request(entries, hits=0):
    req = rls_pb2.RateLimitRequest(domain="sh", hits_addend=hits)
    d = req.descriptors.add()
    for k, v in entries:
        e = d.entries.add()
        e.key, e.value = k, v
    return req


def test_sharded_backend_is_wired(runner):
    from ratelimit_tpu.parallel import ShardedCounterEngine

    assert isinstance(runner.cache.engine, ShardedCounterEngine)
    assert runner.cache.engine.model.num_banks == 8


def test_progression_over_the_sharded_mesh(runner):
    """4/min limit, wire-exact over 8 banks: 4 OK then OVER."""
    OK = rls_pb2.RateLimitResponse.OK
    OVER = rls_pb2.RateLimitResponse.OVER_LIMIT
    codes, remaining = [], []
    for _ in range(6):
        resp = _call(runner, _request([("limited", "mesh")]))
        codes.append(resp.overall_code)
        remaining.append(resp.statuses[0].limit_remaining)
    assert codes == [OK] * 4 + [OVER] * 2
    assert remaining == [3, 2, 1, 0, 0, 0]


def test_many_keys_spread_across_banks(runner):
    """Distinct keys land on EVERY bank: bank ownership is modulo-
    striped (slot % num_banks), so the slot table's dense allocation
    spreads over the whole mesh from the first key."""
    OK = rls_pb2.RateLimitResponse.OK
    for i in range(40):
        resp = _call(runner, _request([("limited", f"spread{i}")]))
        assert resp.overall_code == OK
        assert resp.statuses[0].limit_remaining == 3
    runner.cache.flush()
    eng = runner.cache.engine
    counts = eng.export_counts()  # global slot order
    import numpy as np

    live = np.nonzero(counts)[0]
    banks_used = int(np.unique(live % eng.model.num_banks).size)
    # Modulo striping spreads DENSE slot allocation over the mesh:
    # 40+ live keys must touch every bank.
    assert banks_used == eng.model.num_banks


def test_per_second_unit_on_sharded_backend(runner):
    """SECOND-unit rules work on the sharded backend (single bank set:
    per-second routing only engages when a second engine exists)."""
    OK = rls_pb2.RateLimitResponse.OK
    OVER = rls_pb2.RateLimitResponse.OVER_LIMIT
    codes = [
        _call(runner, _request([("persec", "s")])).overall_code
        for _ in range(3)
    ]
    assert codes == [OK, OK, OVER]


def test_sharded_write_behind_backend(tmp_path_factory):
    """BACKEND_TYPE=tpu-sharded-write-behind composes the async host-
    decide mode with the bank-sharded mesh engine: wire-exact limit
    enforcement, async commits landing on the sharded table."""
    r = _make_runner(
        tmp_path_factory,
        "shwb-runtime",
        backend_type="tpu-sharded-write-behind",
    )
    r.start()
    try:
        from ratelimit_tpu.parallel import ShardedCounterEngine

        assert isinstance(r.cache.engine, ShardedCounterEngine)
        OK = rls_pb2.RateLimitResponse.OK
        OVER = rls_pb2.RateLimitResponse.OVER_LIMIT
        codes = [
            _call(r, _request([("limited", "wbmesh")])).overall_code
            for _ in range(6)
        ]
        assert codes == [OK] * 4 + [OVER] * 2
        r.cache.flush()
        assert int(r.cache.engine.export_counts().sum()) >= 6
    finally:
        r.stop()


def test_sharded_dual_bank_per_second(tmp_path_factory):
    """BACKEND_TYPE=tpu-sharded + TPU_PER_SECOND=true: BOTH banks are
    bank-sharded mesh engines (the dual-Redis analog composed with the
    cluster-in-a-host), wire-exact on both units — the three-way
    matrix cell the r3 verdict called out (next #8)."""
    r = _make_runner(
        tmp_path_factory,
        "shps-runtime",
        tpu_per_second=True,
        tpu_per_second_num_slots=1 << 10,
    )
    r.start()
    try:
        from ratelimit_tpu.parallel import ShardedCounterEngine

        assert isinstance(r.cache.engine, ShardedCounterEngine)
        assert isinstance(r.cache.per_second_engine, ShardedCounterEngine)
        OK = rls_pb2.RateLimitResponse.OK
        OVER = rls_pb2.RateLimitResponse.OVER_LIMIT
        # SECOND-unit rule rides the per-second mesh bank: 2/s.
        codes = [
            _call(r, _request([("persec", "dual")])).overall_code
            for _ in range(3)
        ]
        assert codes == [OK, OK, OVER]
        # MINUTE-unit rule rides the main mesh bank: 4/min.
        codes = [
            _call(r, _request([("limited", "dual")])).overall_code
            for _ in range(6)
        ]
        assert codes == [OK] * 4 + [OVER] * 2
        # The keys landed on DIFFERENT banks: per-second counters live
        # only in the per-second engine and vice versa.
        r.cache.flush()
        assert int(r.cache.per_second_engine.export_counts().sum()) == 3
        assert int(r.cache.engine.export_counts().sum()) == 6
    finally:
        r.stop()
