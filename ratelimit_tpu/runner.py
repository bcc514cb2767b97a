"""Process bootstrap: Settings -> backend -> service -> listeners.

The reference's runner wires stats, logging, the freecache local
cache, the gRPC/HTTP/debug servers, the backend cache (selected by
BACKEND_TYPE) and the service with its runtime config loader
(reference src/service_cmd/runner/runner.go:39-143,
src/server/server_impl.go:176-313).  Same shape here, with the TPU
counter engine as the default backend.

Run directly:  python -m ratelimit_tpu.runner
"""

from __future__ import annotations

import logging
import signal
import threading
from typing import Optional

from .config.runtime import RuntimeLoader
from .service import RateLimitService
from .settings import Settings, configure_compile_cache, new_settings
from .stats.manager import Manager
from .stats.statsd import StatsdExporter
from .utils.time import RealTimeSource

logger = logging.getLogger("ratelimit")

_LOG_LEVELS = {
    "TRACE": logging.DEBUG,
    "DEBUG": logging.DEBUG,
    "INFO": logging.INFO,
    "WARN": logging.WARNING,
    "WARNING": logging.WARNING,
    "ERROR": logging.ERROR,
}


def _make_engine(s: Settings, sharded: bool, num_slots: int):
    """One construction site for counter engines (single-chip or the
    bank-sharded mesh) so every backend branch shares the tuning
    knobs."""
    if sharded:
        from .parallel import ShardedCounterEngine, make_mesh

        return ShardedCounterEngine(
            make_mesh(),
            num_slots=num_slots,
            near_ratio=s.near_limit_ratio,
            buckets=tuple(s.tpu_batch_buckets),
        )
    from .backends.engine import CounterEngine

    return CounterEngine(
        num_slots=num_slots,
        near_ratio=s.near_limit_ratio,
        buckets=tuple(s.tpu_batch_buckets),
    )


def make_algorithm_banks(s: Settings):
    """Build the dedicated engine banks for the configured non-default
    limiter algorithms (models/registry.py; docs/ALGORITHMS.md), or
    None when TPU_ALGORITHM_BANKS is empty.  An unknown name fails
    startup — a typo'd bank list should never silently serve without
    the kernel it asked for."""
    names = [p.strip() for p in s.tpu_algorithm_banks.split(",") if p.strip()]
    if not names:
        return None
    from .backends.engine import CounterEngine
    from .models.registry import DEFAULT_ALGORITHM, get_algorithm

    banks = {}
    for name in names:
        spec = get_algorithm(name)  # raises KeyError on typos
        if spec.name == DEFAULT_ALGORITHM:
            continue  # the lanes ARE the fixed-window banks
        banks[spec.name] = CounterEngine(
            near_ratio=s.near_limit_ratio,
            buckets=tuple(s.tpu_batch_buckets),
            model=spec.make_model(
                s.tpu_algorithm_num_slots, s.near_limit_ratio
            ),
        )
    return banks or None


def lane_slot_split(total_slots: int, n_lanes: int) -> list:
    """Per-lane slot counts summing to `total_slots`: base = floor
    division, with the remainder distributed one slot each to the
    first lanes.  Every lane gets at least 1 slot (an empty engine
    table cannot serve), so for the degenerate total < n_lanes the
    sum exceeds the total rather than wedging a lane."""
    base, rem = divmod(max(0, int(total_slots)), n_lanes)
    return [
        max(1, base + (1 if i < rem else 0)) for i in range(n_lanes)
    ]


def create_limiter(s: Settings, stats_manager: Manager, local_cache, time_source):
    """BackendType switch (reference runner.go:50-74)."""
    backend = s.backend_type.lower()
    if backend == "memory":
        from .backends.memory_cache import MemoryRateLimitCache

        return MemoryRateLimitCache(
            time_source=time_source,
            local_cache=local_cache,
            near_ratio=s.near_limit_ratio,
            cache_key_prefix=s.cache_key_prefix,
            expiration_jitter_max_seconds=s.expiration_jitter_max_seconds,
        )
    if backend in ("tpu-write-behind", "tpu-sharded-write-behind") and int(
        s.tpu_num_lanes
    ) > 1:
        # Lanes exist only for the sync tpu backends (the write-behind
        # path decides on the host view; its dispatcher never gates
        # request latency).  A silently-ignored knob reads as "on".
        logger.warning(
            "TPU_NUM_LANES=%s is ignored by backend %r (lanes apply to "
            "tpu / tpu-sharded)",
            s.tpu_num_lanes,
            s.backend_type,
        )
    if backend in ("tpu-write-behind", "tpu-sharded-write-behind"):
        # Memcached-mode analog: decide on host, commit async
        # (reference memcached/cache_impl.go:58-174; see
        # backends/write_behind.py for the envelope).  The engine under
        # it is orthogonal: single-chip or the bank-sharded mesh.
        from .backends.write_behind import WriteBehindRateLimitCache

        return WriteBehindRateLimitCache(
            _make_engine(
                s, backend == "tpu-sharded-write-behind", s.tpu_num_slots
            ),
            time_source=time_source,
            local_cache=local_cache,
            expiration_jitter_max_seconds=s.expiration_jitter_max_seconds,
            cache_key_prefix=s.cache_key_prefix,
            batch_window_us=s.tpu_batch_window_us,
            batch_limit=s.tpu_batch_limit,
            unhealthy_after=s.tpu_unhealthy_after,
            pipeline_depth=s.tpu_pipeline_depth,
        )
    if backend in ("tpu", "tpu-sharded"):
        from .backends.tpu_cache import TpuRateLimitCache

        sharded = backend == "tpu-sharded"
        n_lanes = max(1, int(s.tpu_num_lanes))
        # TPU_NUM_SLOTS is the total budget: each lane serves ~1/N of
        # the hash-split keyspace from a ~1/N-sized table.  The
        # division remainder goes to the first lanes so the per-lane
        # sum equals the documented total (a floor division alone
        # silently drops up to n_lanes-1 slots of capacity).
        engines = [
            _make_engine(s, sharded, per_lane)
            for per_lane in lane_slot_split(s.tpu_num_slots, n_lanes)
        ]
        per_second_engine = (
            _make_engine(s, sharded, s.tpu_per_second_num_slots)
            if s.tpu_per_second
            else None
        )
        return TpuRateLimitCache(
            engines if n_lanes > 1 else engines[0],
            time_source=time_source,
            per_second_engine=per_second_engine,
            local_cache=local_cache,
            expiration_jitter_max_seconds=s.expiration_jitter_max_seconds,
            cache_key_prefix=s.cache_key_prefix,
            batch_window_us=s.tpu_batch_window_us,
            batch_limit=s.tpu_batch_limit,
            dispatch_timeout_s=s.tpu_dispatch_timeout_s,
            pipeline_depth=s.tpu_pipeline_depth,
            unhealthy_after=s.tpu_unhealthy_after,
            resolution_cache_entries=s.resolution_cache_entries,
            hotkeys_top_k=s.hotkeys_top_k,
            algorithm_banks=make_algorithm_banks(s),
            # Device-path fault domain (backends/fault_domain.py;
            # docs/RESILIENCE.md): on by default — a hung kernel
            # launch quarantines its bank within KERNEL_DEADLINE_S
            # instead of stalling RPCs for the dispatch timeout.
            kernel_deadline_s=s.kernel_deadline_s,
            device_failure_mode=s.device_failure_mode,
            fault_restart_backoff_s=s.device_restart_backoff_s,
            fault_snapshot_interval_s=s.tpu_checkpoint_interval_s,
            thread_clocks=s.debug_profiling,
            fault_interval_s=(
                s.device_watchdog_interval_s
                if s.device_watchdog_interval_s > 0
                else None
            ),
        )
    raise ValueError(f"Invalid setting for BackendType: {s.backend_type}")


class Runner:
    def __init__(
        self,
        settings: Optional[Settings] = None,
        time_source=None,
    ):
        # The clock seam: production uses the real clock; wire-level
        # tests inject a pinned TimeSource so window-progression
        # assertions can't straddle a minute rollover (the reference
        # pins its clock the same way, test/service/ratelimit_test.go:72-76).
        self.settings = settings or new_settings()
        self.time_source = time_source or RealTimeSource()
        self.stats_manager = Manager(extra_tags=self.settings.extra_tags)
        self._stopped = threading.Event()
        self.cache = None
        self.service = None
        self.runtime = None
        self.grpc_server = None
        self.http_server = None
        self.debug_server = None
        self.statsd = None
        self.health = None
        self.checkpointer = None
        self._trace_jsonl = None
        self.flight = None
        self.slo = None
        self.detectors = None
        self.overload = None
        self.events = None
        self.launches = None
        self.timeseries = None

    # -- lifecycle (runner.go:76-143) -----------------------------------

    def start(self) -> None:
        """Wire everything and start all listeners (non-blocking)."""
        s = self.settings
        logging.basicConfig(
            level=_LOG_LEVELS.get(s.log_level.upper(), logging.WARNING),
            format=(
                '{"@timestamp":"%(asctime)s","level":"%(levelname)s",'
                '"@message":"%(message)s"}'
                if s.log_format == "json"
                else "%(asctime)s %(levelname)s %(name)s %(message)s"
            ),
        )
        # A sampler/dispatcher/write-behind thread dying from an
        # uncaught exception must scream in the service log, not print
        # to bare stderr and vanish (utils/threads.py; the test
        # bootstrap stacks a recording hook on the same seam).
        from .utils.threads import install_thread_excepthook

        install_thread_excepthook()

        if s.backend_type.lower() != "memory":
            # Must land before the first jit compile (engine creation
            # below): restarts skip recompiling every (bucket, dtype)
            # serving kernel.
            configure_compile_cache()

        from .server.health import HealthChecker
        from .server.grpc_server import create_grpc_server
        from .server.http_server import (
            HttpServer,
            add_debug_routes,
            add_healthcheck,
            add_json_handler,
        )

        # Tracing policy + exporters (docs/OBSERVABILITY.md).  The
        # process-wide tracer is configured here, once, from Settings —
        # the serving layers reference it like they reference logging.
        from .observability import JsonlExporter, TRACER, log_exporter

        TRACER.configure(
            sample_rate=s.trace_sample_rate,
            sample_errors=s.trace_sample_errors,
            enabled=s.trace_sample_rate > 0 or s.trace_sample_errors,
            ring_size=s.trace_ring_size,
            slow_size=s.trace_slow_size,
        )
        TRACER.clear_exporters()
        if s.trace_export_jsonl:
            self._trace_jsonl = JsonlExporter(s.trace_export_jsonl)
            TRACER.add_exporter(self._trace_jsonl)
        if s.trace_log:
            TRACER.add_exporter(log_exporter)

        local_cache = None
        if s.local_cache_size_in_bytes > 0:
            from .limiter.local_cache import LocalCache

            local_cache = LocalCache(s.local_cache_size_in_bytes)
            local_cache.register_stats(self.stats_manager.store)

        time_source = self.time_source
        self.cache = create_limiter(s, self.stats_manager, local_cache, time_source)
        if hasattr(self.cache, "register_stats"):
            self.cache.register_stats(self.stats_manager.store)

        # Decision flight recorder + per-domain SLO engine
        # (observability/{flight,slo}.py; docs/OBSERVABILITY.md).  The
        # recorder attaches to the backend's note seam so ring records
        # carry the decisive descriptor's stem hash + bank; both stamp
        # on the RPC thread next to the per-phase histogram sink.
        from .observability import (
            AnomalyDetectors,
            ErrorRateDetector,
            LatencySpikeDetector,
            OverLimitSurgeDetector,
            QueueSaturationDetector,
            SloEngine,
            make_event_journal,
            make_flight_recorder,
            make_launch_recorder,
            make_timeseries,
            register_default_series,
        )

        store = self.stats_manager.store
        self.flight = make_flight_recorder(s.flight_recorder_size)
        if self.flight is not None:
            self.flight.register_stats(store)
            if hasattr(self.cache, "flight"):
                self.cache.flight = self.flight

        # Launch flight recorder (observability/launches.py;
        # docs/OBSERVABILITY.md "Launch recorder"): one ring record per
        # device batch, stamped on the dispatcher threads — the
        # per-launch analog of the decision ring above.  Only the TPU
        # backends have dispatchers to instrument.
        self.launches = make_launch_recorder(s.launch_recorder_size)
        if self.launches is not None:
            if hasattr(self.cache, "attach_launch_recorder"):
                self.cache.attach_launch_recorder(self.launches)
                self.launches.register_stats(store)
            else:
                # No dispatch path to record: keep the route absent
                # rather than serving an eternally-empty ring.
                self.launches = None

        # Lifecycle event journal (observability/events.py;
        # docs/OBSERVABILITY.md "Event journal").  One process-wide
        # timeline: the backend's fault domain, the handoff
        # export/import seams, the overload controller and the config
        # reloader all stamp transitions into the same ring.  Emitters
        # hold ``events=None`` when EVENT_JOURNAL_SIZE=0, so the
        # disabled path carries no journal branches at all.
        self.events = make_event_journal(
            s.event_journal_size, jsonl_path=s.event_journal_jsonl
        )
        if self.events is not None:
            self.events.register_stats(store)
            if hasattr(self.cache, "events"):
                self.cache.events = self.events
            fd = getattr(self.cache, "fault_domain", None)
            if fd is not None:
                fd.events = self.events
        # Background work (snapshot, checkpoint, incident capture, the
        # collector's call tokens) leaves its durations in the same
        # journal (observability/spans.py).
        from .observability.spans import SPANS

        SPANS.journal = self.events
        SPANS.watch_gc()
        self.slo = SloEngine(
            self.stats_manager,
            target=s.slo_target,
            window_s=s.slo_window_s,
            latency_threshold_ms=s.slo_latency_ms,
        )

        # Overload controller (overload/controller.py): built ONLY
        # when some OVERLOAD_* setting asks for it — the defaults-off
        # serving path carries no controller object at all, so
        # decisions stay byte-identical to a build without the layer.
        if (
            s.overload_shed_enabled
            or s.overload_promote_enabled
            or s.overload_backpressure_enabled
        ):
            from .overload import OverloadController

            self.overload = OverloadController(
                slo=self.slo,
                hotkeys=getattr(self.cache, "hotkeys", None),
                shed_enabled=s.overload_shed_enabled,
                shed_burn_threshold=s.shed_burn_threshold,
                shed_clear_ratio=s.shed_clear_ratio,
                shed_min_requests=s.shed_min_requests,
                promote_enabled=s.overload_promote_enabled,
                promote_ttl_s=s.promote_ttl_s,
                promote_over_share=s.promote_over_share,
                promote_min_hits=s.promote_min_hits,
                promote_capacity=s.promote_capacity,
                backpressure_enabled=s.overload_backpressure_enabled,
                backpressure_tokens=s.backpressure_tokens,
                backpressure_max_wait_s=s.backpressure_max_wait_s,
                backpressure_hold_s=s.backpressure_hold_s,
            )
            self.overload.events = self.events
            self.overload.register_stats(store)
            if self.overload.promotion is not None and hasattr(
                self.cache, "promotion"
            ):
                self.cache.promotion = self.overload.promotion

        # In-process time-series store (observability/timeseries.py;
        # docs/OBSERVABILITY.md "Time-series store"): bounded capacity
        # / latency history behind /debug/timeseries, incident
        # captures and the /fleet.json sparkline summaries.  Series
        # registration happens HERE, before the sampler starts.
        self.timeseries = make_timeseries(
            s.tsdb_interval_s, s.tsdb_retention_s
        )
        if self.timeseries is not None:
            register_default_series(
                self.timeseries,
                store,
                cache=self.cache,
                launches=self.launches,
                overload=self.overload,
                local_cache=local_cache,
            )
            self.timeseries.register_stats(store)
            self.timeseries.start()

        if s.tpu_warmup and hasattr(self.cache, "warmup"):
            logger.warning("warming up kernel shapes (TPU_WARMUP=true)...")
            self.cache.warmup()

        if s.tpu_checkpoint_dir and hasattr(self.cache, "engines"):
            from .backends.checkpoint import CheckpointManager

            self.checkpointer = CheckpointManager(
                self.cache, s.tpu_checkpoint_dir, s.tpu_checkpoint_interval_s
            )
            self.checkpointer.restore()
            self.checkpointer.start()

        self.runtime = RuntimeLoader(
            s.runtime_path,
            s.runtime_subdirectory,
            ignore_dot_files=s.runtime_ignore_dot_files,
        )
        self.service = RateLimitService(
            self.runtime,
            self.cache,
            self.stats_manager,
            runtime_watch_root=s.runtime_watch_root,
            clock=time_source,
            global_shadow_mode=s.global_shadow_mode,
            headers_enabled=s.rate_limit_response_headers_enabled,
            header_limit=s.header_ratelimit_limit,
            header_remaining=s.header_ratelimit_remaining,
            header_reset=s.header_ratelimit_reset,
            # Re-read env-derived settings on every config reload, like
            # the reference's settings.NewSettings() call in its reload
            # path (ratelimit.go:77-89) — integration tests flip
            # SHADOW_MODE/header env vars and expect a YAML touch to
            # pick them up.
            settings_reloader=new_settings,
        )
        # SLO domains follow the config: attach the engine, then adopt
        # the already-loaded snapshot (construction above reloaded
        # before the attribute existed).  The overload controller's
        # priority ladder follows the same pattern.
        self.service.slo = self.slo
        self.service.overload = self.overload
        self.service.events = self.events
        config = self.service.get_current_config()
        if config is not None:
            self.slo.set_domains(config.domains.keys())
            if self.overload is not None:
                self.overload.set_priorities(config.priorities)
        self.runtime.start()

        # Anomaly detectors + incident capture (detectors.py).  Always
        # constructed — /debug/incidents and the deterministic tick()
        # seam work even with the sampler off — but the thread only
        # runs when ANOMALY_INTERVAL_S > 0.
        self.detectors = AnomalyDetectors(
            store,
            [
                LatencySpikeDetector(
                    store.histogram(
                        "ratelimit_server.ShouldRateLimit.response_ms"
                    ),
                    factor=s.anomaly_spike_factor,
                    min_samples=s.anomaly_min_samples,
                ),
                OverLimitSurgeDetector(
                    self.slo,
                    factor=s.anomaly_spike_factor,
                    min_requests=s.anomaly_min_samples,
                ),
                QueueSaturationDetector(
                    getattr(self.cache, "queue_hwm_drain", lambda: 0),
                    threshold=s.anomaly_queue_depth,
                ),
                ErrorRateDetector(store),
            ],
            flight=self.flight,
            tracer=TRACER,
            slo=self.slo,
            incident_dir=s.incident_dir,
            incident_max=s.incident_max,
            interval_s=s.anomaly_interval_s,
            cooldown_s=s.anomaly_cooldown_s,
            overload=self.overload,
            events=self.events,
            timeseries=self.timeseries,
        )
        self.detectors.register_stats(store)
        self.detectors.start()

        self.health = HealthChecker()
        if hasattr(self.cache, "bind_health"):
            # Backend death -> NOT_SERVING + fast-fail RPCs (the Redis
            # active-connection health analog, driver_impl.go:31-52).
            self.cache.bind_health(self.health)

        credentials = None
        if bool(s.grpc_server_tls_cert) != bool(s.grpc_server_tls_key):
            # A half-configured pair must fail startup, never silently
            # serve rate-limit traffic in cleartext.
            raise ValueError(
                "GRPC_SERVER_TLS_CERT and GRPC_SERVER_TLS_KEY must be "
                "set together (got cert="
                f"{s.grpc_server_tls_cert!r}, key={s.grpc_server_tls_key!r})"
            )
        if s.grpc_server_tls_cert:
            # TLS / mTLS listener (the REDIS_TLS analog; see Settings).
            from .server.grpc_server import server_credentials

            credentials = server_credentials(
                s.grpc_server_tls_cert,
                s.grpc_server_tls_key,
                s.grpc_server_tls_ca,
            )
        self.grpc_server = create_grpc_server(
            self.service,
            self.health,
            store=self.stats_manager.store,
            host=s.grpc_host,
            port=s.grpc_port,
            max_connection_age_s=s.grpc_max_connection_age,
            max_connection_age_grace_s=s.grpc_max_connection_age_grace,
            max_workers=s.grpc_max_workers,
            credentials=credentials,
            auth_token=s.grpc_auth_token,
            flight=self.flight,
            slo=self.slo,
            corr_enabled=s.flight_corr_enabled,
        )
        self.grpc_server.start()

        self.http_server = HttpServer(s.host, s.port, name="api")
        add_json_handler(
            self.http_server, self.service, flight=self.flight, slo=self.slo
        )
        add_healthcheck(self.http_server, self.health)
        self.http_server.start()

        self.debug_server = HttpServer(s.debug_host, s.debug_port, name="debug")
        add_debug_routes(
            self.debug_server,
            self.stats_manager.store,
            self.service,
            profiling_enabled=s.debug_profiling,
            detectors=self.detectors,
            slo=self.slo,
            overload=self.overload,
            flight=self.flight,
            cluster_handoff_enabled=s.cluster_handoff_enabled,
            events=self.events,
            launches=self.launches,
            timeseries=self.timeseries,
        )
        add_healthcheck(self.debug_server, self.health)
        self.debug_server.start()

        if s.use_statsd:
            self.statsd = StatsdExporter(
                self.stats_manager.store,
                s.statsd_host,
                s.statsd_port,
                srv_record=s.statsd_srv,
                srv_refresh_s=s.statsd_srv_refresh_s,
            )
            self.statsd.start()

        if s.gc_tuning:
            # After all startup allocation (engines, kernels, config,
            # servers): move it out of the gc's scan set so serving-
            # path collections stay small.  See Settings.gc_tuning.
            import gc

            gc.collect()
            gc.freeze()

        logger.warning(
            "ratelimit serving: http=%s grpc=%s debug=%s backend=%s%s",
            self.http_server.bound_port,
            self.grpc_server.bound_port,
            self.debug_server.bound_port,
            s.backend_type,
            self._where_it_runs(),
        )

    def _where_it_runs(self) -> str:
        """Start-line suffix saying where the counters REALLY live:
        the platform JAX initialised (not the BACKEND_TYPE setting),
        each bank's slot-table implementation and, for banks striped
        over a mesh (tpu-sharded), how many devices each spans.  Empty
        for the host-only memory backend."""
        if not hasattr(self.cache, "engines"):
            return ""
        from .backends.checkpoint import bank_roles
        from .backends.engine import device_report

        dev = device_report()
        placed = [
            (role, engine.placement())
            for role, engine in zip(
                bank_roles(self.cache), self.cache.engines()
            )
        ]
        tables = ",".join(f"{role}:{p['slot_table']}" for role, p in placed)
        meshes = ",".join(
            f"{role}:{p['mesh_devices']}"
            for role, p in placed
            if "mesh_devices" in p
        )
        return (
            f" platform={dev['platform']} device_kind={dev['device_kind']!r}"
            f" devices={dev['device_count']} slot_tables={tables}"
            + (f" mesh_devices={meshes}" if meshes else "")
        )

    def run(self) -> None:
        """start() + install signal handlers + block until stopped
        (reference Run blocks in http.Serve, server_impl.go:139-152;
        SIGTERM flips health to NOT_SERVING first, health.go:28-35)."""
        self.start()

        def handle(signum, frame):
            logger.warning("received signal %s, shutting down", signum)
            if self.health is not None:
                self.health.fail()
            self.stop()

        for sig in (signal.SIGINT, signal.SIGTERM, signal.SIGHUP):
            signal.signal(sig, handle)
        self._stopped.wait()

    def stop(self) -> None:
        """Graceful drain + stop (reference Stop, runner.go:136-143 +
        handleGracefulShutdown, server_impl.go:302-313), in the
        crash-only order (docs/RESILIENCE.md "Graceful drain"):

        1. health flips NOT_SERVING (load balancers stop routing; the
           signal handler in run() already did this for SIGTERM —
           repeated here so direct stop() calls get the same order);
        2. the gRPC listener stops accepting NEW RPCs but grants
           in-flight ones a grace period to complete — their dispatch
           waits still have a live backend (the cache closes LAST);
        3. the dispatcher intake drains (flush) so every accepted
           decision is committed to the counters;
        4. the final checkpoint snapshots the fully-drained counters —
           a restart restores every window intact;
        5. only then do the remaining listeners and the backend stop.
        """
        if self.health is not None:
            self.health.fail()
        if self.grpc_server is not None:
            self.grpc_server.stop(grace=5).wait(timeout=10)
        if self.cache is not None and hasattr(self.cache, "flush"):
            try:
                self.cache.flush()
            except Exception:
                logger.exception("dispatcher drain failed during shutdown")
        if self.checkpointer is not None:
            self.checkpointer.stop(final_checkpoint=True)
        for srv in (self.http_server, self.debug_server):
            if srv is not None:
                srv.stop()
        if self.runtime is not None:
            self.runtime.stop()
        if self.detectors is not None:
            self.detectors.stop()
        if self.timeseries is not None:
            self.timeseries.stop()
        if self.statsd is not None:
            self.statsd.stop()
        if self.cache is not None and hasattr(self.cache, "close"):
            self.cache.close()
        if self._trace_jsonl is not None:
            from .observability import TRACER

            TRACER.clear_exporters()
            self._trace_jsonl.close()
            self._trace_jsonl = None
        from .observability.spans import SPANS

        SPANS.watch_gc(False)
        if self.events is not None:
            if SPANS.journal is self.events:
                SPANS.journal = None
            self.events.close()
        self._stopped.set()


def main() -> None:
    Runner().run()


if __name__ == "__main__":
    main()
