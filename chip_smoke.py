#!/usr/bin/env python
"""chip_smoke.py — does the server answer ShouldRateLimit with the
counters on the TPU?  The quickest proof that the system still starts
on the chip.

Drives the NORMAL entry point, `python -m ratelimit_tpu.runner`, as a
child process with default settings (BACKEND_TYPE=tpu, one lane, 2^20
fixed-window slots, 2^18-slot sliding-window and GCRA banks, fault
domain on, no warm-up) and talks to it only over real gRPC and the HTTP
debug port:

  g++ native/*.cpp               the slot table the server loads is built here
  start 1 (cold compile cache)   time to healthy, first answer
    closed form, 3 algorithms    fresh key under N/DAY hit N+5 times, for an
                                 N that divides the day and one that does not
    GCRA refill                  N/MINUTE, N not dividing 60, dripped for 12 s
                                 against GCRA in exact rationals
    load                         1,000,000 distinct live keys, 64 per request
    queries vs the host oracle   1 / 4 / 64 / 1536 descriptors per request,
                                 serial and concurrent (backends/memory_cache.py
                                 fed the same sequence)
    shadow rule, near-limit stat
    evidence the device did it   platform, state placement, native slot
                                 table, zero faults / fallbacks, launches
    SIGTERM -> exit code 0       the drain releases the chip
  start 2 (warm compile cache, TPU_WARMUP=true: all 50 serving shapes)
    closed form again, a few oracle queries, evidence, SIGTERM -> 0

The deployment is BASELINE.json config 4 (mixed SECOND / MINUTE / HOUR /
DAY units, a shadow rule, near-limit thresholds, key-only rules so every
distinct value is a key) plus one rule each on the sliding-window and
GCRA kernels; limits, key names and the request sequence come from
--seed.  Nothing is fetched.

One process per chip: this parent never imports jax (asserted at exit);
each server child starts after the previous one has exited.  Where the
serving process does not report platform "tpu" the smoke exits non-zero
and prints no result.  `--dry-run` is the sandbox rehearsal (tiny key
count, JAX_PLATFORMS=cpu): it checks the harness and the host path and
proves nothing about the chip.

On success the last two lines of stdout are JSON objects: the counts
of the run (also in chiprun_out/chip_smoke.json; a dry run writes
chip_smoke_dry_run.* instead, never over a chip run's evidence), then,
last, the verdict with exactly these keys and the device as the serving
process reports it from jax.devices():
  {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}
Wall times among the counts are set-up observations of one run, not
measurements.
"""

from __future__ import annotations

import argparse
import collections
import contextlib
import importlib.metadata
import json
import math
import os
import random
import re
import signal
import subprocess
import sys
import tempfile
import threading
import time
import urllib.request
from fractions import Fraction

import grpc

from ratelimit_tpu.api import Descriptor, RateLimitRequest
from ratelimit_tpu.backends import native_slot_table
from ratelimit_tpu.backends.memory_cache import MemoryRateLimitCache
from ratelimit_tpu.config.loader import ConfigFile, load_config
from ratelimit_tpu.server import pb  # noqa: F401  (puts the protos on sys.path)
from ratelimit_tpu.settings import compile_cache_dir
from ratelimit_tpu.stats.manager import Manager
from ratelimit_tpu.utils.time import PinnedTimeSource

from envoy.service.ratelimit.v3 import rls_pb2  # noqa: E402
from grpchealth.v1 import health_pb2  # noqa: E402

REPO = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(REPO, "chiprun_out")
DOMAIN = "smoke"
FULL_KEYS = 1_000_000
MIN_KEYS = 262_144  # the load may be cut to fit the time limit, never below this
DRY_RUN_KEYS = 3_000
PER_REQUEST = 64
LOAD_CLIENTS = 16  # gRPC threads of the load phase
BUDGET_S = 1100.0  # the contract allows 1200 s; the rest is margin
AFTER_LOAD_S = 400.0  # reserved for everything that follows the load
BUCKETS = (8, 16, 32, 64, 128, 256, 512, 1024, 2048, 4096)
SERVING_SHAPES = 50  # 10 buckets x 3 readback dtypes + 10 + 10 generic
OK, OVER = rls_pb2.RateLimitResponse.OK, rls_pb2.RateLimitResponse.OVER_LIMIT
UNIT_SECONDS = {"second": 1, "minute": 60, "hour": 3600, "day": 86400}
ALGORITHMS = ("fixed_window", "sliding_window", "gcra")
CHURN_UNITS = ("second", "minute", "hour")


class SmokeFailure(Exception):
    """A phase failed; the message is the one line the run ends with."""


# ---------------------------------------------------------------------------
# the deployment, from the seed
# ---------------------------------------------------------------------------


class Deployment:
    """Limit config + key naming for one seed."""

    def __init__(self, seed: int):
        rng = random.Random(seed)
        self.tag = f"{rng.getrandbits(32):08x}"
        self.limits = {
            "day": rng.randint(6, 12),
            "hour": rng.randint(6, 12),
            "minute": rng.randint(4, 9),
            "second": rng.randint(2, 5),
        }
        # N/DAY for the closed-form check: one N that divides the day
        # and one that does not (GCRA's emission interval 86400/N is
        # then no whole number of seconds, nor an exact float).
        self.exact_ns = (
            rng.choice((10, 12, 15, 16, 18, 20)),
            rng.choice((7, 11, 13, 14, 17, 19)),
        )
        # N/MINUTE for the GCRA refill check: N does not divide 60.
        self.drip_n = rng.choice((11, 13, 17, 19, 23))
        self.shadow_limit = 3

    def yaml(self) -> str:
        def rule(key: str, unit: str, limit: int, extra: str = "") -> str:
            return (
                f"  - key: {key}\n    rate_limit:\n      unit: {unit}\n"
                f"      requests_per_unit: {limit}\n{extra}"
            )

        rules = [rule(f"acct_{u}", u, lim) for u, lim in self.limits.items()]
        rules.append(
            rule("trial", "day", self.shadow_limit, "    shadow_mode: true\n")
        )
        for algo in ALGORITHMS:
            named = "" if algo == "fixed_window" else f"      algorithm: {algo}\n"
            rules += [rule(f"exact{n}_{algo}", "day", n, named) for n in self.exact_ns]
        rules.append(rule("drip_gcra", "minute", self.drip_n, "      algorithm: gcra\n"))
        return f"domain: {DOMAIN}\ndescriptors:\n" + "".join(rules)

    def write_runtime(self, root: str) -> None:
        cfg = os.path.join(root, "ratelimit", "config")
        os.makedirs(cfg)
        with open(os.path.join(cfg, "smoke.yaml"), "w") as f:
            f.write(self.yaml())


def settle_units(horizon_s: float) -> tuple:
    """The HOUR/DAY units whose window cannot roll within `horizon_s`
    — where the bulk keys and every oracle-compared key live, so no
    counter resets mid-run.  Waits out a UTC midnight closer than the
    horizon (both would roll)."""
    while True:
        now = time.time()
        safe = tuple(
            u for u in ("day", "hour")
            if UNIT_SECONDS[u] - now % UNIT_SECONDS[u] > horizon_s
        )
        if safe:
            return safe
        wait = 86400 - now % 86400 + 1
        print(f"UTC midnight in {wait:.0f}s: waiting so no DAY window rolls mid-run")
        time.sleep(wait)


# ---------------------------------------------------------------------------
# the server child
# ---------------------------------------------------------------------------


class Server:
    """One `python -m ratelimit_tpu.runner` child on ephemeral ports."""

    START_LINE = re.compile(
        r"ratelimit serving: http=(\d+) grpc=(\d+) debug=(\d+) (.*)"
    )

    def __init__(self, name: str, log_path: str, runtime_root: str, env_extra: dict):
        self.name = name
        self.log_path = log_path
        env = dict(os.environ)
        env.pop("TPU_NATIVE_SO", None)  # the library built here, no override
        env.update(
            RUNTIME_ROOT=runtime_root,
            RUNTIME_SUBDIRECTORY="ratelimit",
            HOST="127.0.0.1", GRPC_HOST="127.0.0.1", DEBUG_HOST="127.0.0.1",
            PORT="0", GRPC_PORT="0", DEBUG_PORT="0",
            USE_STATSD="false",  # no statsd daemon here; not on the path
            **env_extra,
        )
        self.t_spawn = time.monotonic()
        self._log = open(self.log_path, "wb")
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "ratelimit_tpu.runner"],
            cwd=REPO, env=env, stdout=self._log, stderr=subprocess.STDOUT,
        )
        self.grpc_port = self.debug_port = 0
        self.start_line = ""
        self.channel = None

    def log_tail(self, n: int = 30) -> str:
        with open(self.log_path, "rb") as f:
            return b"\n".join(f.read().splitlines()[-n:]).decode(errors="replace")

    def wait_healthy(self, timeout_s: float) -> float:
        """Block until the start line is logged and /healthcheck says
        OK; returns seconds since spawn."""
        end = time.monotonic() + timeout_s
        while time.monotonic() < end:
            if self.proc.poll() is not None:
                raise SmokeFailure(
                    f"{self.name}: server exited {self.proc.returncode} during "
                    f"start-up:\n{self.log_tail()}"
                )
            with open(self.log_path, "rb") as f:
                m = self.START_LINE.search(f.read().decode(errors="replace"))
            if m:
                self.grpc_port, self.debug_port = int(m.group(2)), int(m.group(3))
                self.start_line = m.group(0)
                if self.http("/healthcheck") == "OK":
                    self.channel = grpc.insecure_channel(
                        f"127.0.0.1:{self.grpc_port}"
                    )
                    return time.monotonic() - self.t_spawn
            time.sleep(0.1)
        raise SmokeFailure(
            f"{self.name}: not healthy after {timeout_s:.0f}s:\n{self.log_tail()}"
        )

    def http(self, path: str) -> str:
        url = f"http://127.0.0.1:{self.debug_port}{path}"
        with urllib.request.urlopen(url, timeout=30) as r:
            return r.read().decode()

    def json(self, path: str):
        return json.loads(self.http(path))

    def stop(self) -> None:
        """SIGTERM and require the graceful drain to exit 0 — the next
        process needs the chip."""
        if self.channel is not None:
            self.channel.close()
        self.proc.send_signal(signal.SIGTERM)
        try:
            rc = self.proc.wait(timeout=90)
        except subprocess.TimeoutExpired:
            raise SmokeFailure(
                f"{self.name}: still running 90s after SIGTERM:\n{self.log_tail()}"
            ) from None
        if rc != 0:
            raise SmokeFailure(
                f"{self.name}: exit code {rc} after SIGTERM:\n{self.log_tail()}"
            )

    def kill(self) -> None:
        if self.proc.poll() is None:
            self.proc.kill()
            self.proc.wait()
        self._log.close()


@contextlib.contextmanager
def serving(name: str, out_stem: str, runtime_root: str, env_extra: dict):
    """A Server that is dead when the block ends, however it ends; a
    failing block leaves the server's last log lines on stderr."""
    server = Server(name, f"{out_stem}_{name}.log", runtime_root, env_extra)
    try:
        yield server
    except BaseException:
        print(f"--- {name} log tail ---\n{server.log_tail()}", file=sys.stderr)
        raise
    finally:
        server.kill()


# ---------------------------------------------------------------------------
# gRPC client + the reference
# ---------------------------------------------------------------------------


def make_request(pairs) -> "rls_pb2.RateLimitRequest":
    req = rls_pb2.RateLimitRequest(domain=DOMAIN)
    for key, value in pairs:
        entry = req.descriptors.add().entries.add()
        entry.key, entry.value = key, value
    return req


class Client:
    def __init__(self, server: Server):
        self._call = server.channel.unary_unary(
            "/envoy.service.ratelimit.v3.RateLimitService/ShouldRateLimit",
            request_serializer=rls_pb2.RateLimitRequest.SerializeToString,
            response_deserializer=rls_pb2.RateLimitResponse.FromString,
        )
        self._health = server.channel.unary_unary(
            "/grpc.health.v1.Health/Check",
            request_serializer=health_pb2.HealthCheckRequest.SerializeToString,
            response_deserializer=health_pb2.HealthCheckResponse.FromString,
        )
        self.requests = 0
        self.decisions = 0
        self._lock = threading.Lock()

    def ask(self, pairs):
        resp = self._call(make_request(pairs), timeout=120)
        with self._lock:
            self.requests += 1
            self.decisions += len(pairs)
        if len(resp.statuses) != len(pairs):
            raise SmokeFailure(
                f"{len(pairs)} descriptors sent, {len(resp.statuses)} statuses back"
            )
        return resp

    def serving(self) -> bool:
        resp = self._health(health_pb2.HealthCheckRequest(), timeout=30)
        return resp.status == health_pb2.HealthCheckResponse.SERVING


class Oracle:
    """backends/memory_cache.py over the same YAML: the plain
    reference the fixed-window decisions are held to, one for one."""

    def __init__(self, deployment: Deployment):
        self.config = load_config(
            [ConfigFile("smoke.yaml", deployment.yaml())], Manager()
        )
        self.cache = MemoryRateLimitCache(
            time_source=PinnedTimeSource(int(time.time()))
        )

    def ask(self, pairs) -> list:
        descs = [Descriptor.of(p) for p in pairs]
        limits = [self.config.get_limit(DOMAIN, d) for d in descs]
        statuses = self.cache.do_limit(RateLimitRequest(DOMAIN, descs), limits)
        return [
            (int(s.code), s.limit_remaining, s.current_limit.requests_per_unit)
            for s in statuses
        ]


def observed(resp) -> list:
    return [
        (s.code, s.limit_remaining, s.current_limit.requests_per_unit)
        for s in resp.statuses
    ]


def compare(pairs, resp, want: list, where: str) -> None:
    got = observed(resp)
    if got != want:
        i = next(i for i, (g, w) in enumerate(zip(got, want)) if g != w)
        raise SmokeFailure(
            f"MISMATCH in {where}: descriptor {i} {pairs[i]} of a "
            f"{len(pairs)}-descriptor request answered (code, remaining, limit)="
            f"{got[i]}, reference says {want[i]}"
        )
    over = any(code == OVER for code, _, _ in want)
    if resp.overall_code != (OVER if over else OK):
        raise SmokeFailure(
            f"MISMATCH in {where}: overall_code {resp.overall_code} does not "
            f"follow from the statuses for {pairs[:2]}..."
        )


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------


def check_closed_form(client: Client, dep: Deployment, salt: str) -> None:
    """For each algorithm and each N: a fresh key under N/DAY hit N+5
    times must answer exactly N OK, then OVER_LIMIT, with
    limit_remaining counting down to 0.  Closed form — independent of
    every line under test."""
    for algo in ALGORITHMS:
        for n in dep.exact_ns:
            want = [(OK, n - 1 - i, n) for i in range(n)] + [(OVER, 0, n)] * 5
            pair = (f"exact{n}_{algo}", f"{dep.tag}-{salt}")
            got = [observed(client.ask([pair]))[0] for _ in want]
            if got != want:
                i = next(i for i, (g, w) in enumerate(zip(got, want)) if g != w)
                raise SmokeFailure(
                    f"MISMATCH in closed form, {algo}, {n}/DAY: hit {i + 1} of "
                    f"key {pair} answered (code, remaining, limit)={got[i]}, "
                    f"exact answer is {want[i]}; all answers: "
                    f"{[g[:2] for g in got]}"
                )


def check_gcra_refill(client: Client, dep: Deployment, salt: str) -> int:
    """GCRA's refill, through the served path, against GCRA carried in
    exact rationals: a fresh key under N/MINUTE (N does not divide 60)
    takes a burst of N+2 hits and then one hit every 0.9 s for 12 s, so
    cells come back at a fractional emission interval while every
    answer is checked — budget = N - ceil((TAT - now)+ / T), T = 60/N.

    The server stamps a request with its whole unix second.  A hit
    whose send and answer straddle a second has no known stamp, so
    hits are sent early in a second and a straddled attempt starts
    over on a new key.  Returns how many answers refilled cells made OK."""
    n, t_emit = dep.drip_n, Fraction(60, dep.drip_n)
    for attempt in range(3):
        pair = ("drip_gcra", f"{dep.tag}-{salt}-{attempt}")
        tat, refilled = Fraction(0), 0
        for i in range(n + 2 + 14):
            if i >= n + 2:
                time.sleep(0.9)
            into = time.time() % 1
            if into > 0.8:
                time.sleep(1.05 - into)
            sec = int(time.time())
            got = observed(client.ask([pair]))[0]
            if int(time.time()) != sec:
                break
            budget = n - math.ceil(max(tat - sec, 0) / t_emit)
            if got != ((OK, budget - 1, n) if budget > 0 else (OVER, 0, n)):
                raise SmokeFailure(
                    f"MISMATCH in GCRA refill, {n}/MINUTE: hit {i + 1} of key "
                    f"{pair} at second {sec} answered (code, remaining, limit)="
                    f"{got}, exact GCRA has a budget of {budget} cells "
                    f"(TAT - now = {float(max(tat - sec, 0)):.4f} s)"
                )
            if budget > 0:
                tat = max(tat, Fraction(sec)) + t_emit
                refilled += i >= n
        else:
            if not refilled:
                raise SmokeFailure("GCRA refill: no cell came back in 12 s")
            return refilled
    raise SmokeFailure("GCRA refill: three attempts straddled a second boundary")


def bulk_key(dep: Deployment, units: tuple, i: int):
    unit = units[i % len(units)]
    return (f"acct_{unit}", f"{dep.tag}-{i}"), dep.limits[unit]


def load_keys(
    client: Client, dep: Deployment, units: tuple, target: int,
    n_clients: int, deadline: float,
) -> int:
    """Hit `target` distinct HOUR/DAY keys once each, 64 per request,
    from `n_clients` threads; every 32nd request a thread also sends
    64 distinct SECOND/MINUTE/HOUR keys (window roll-over and slot
    reuse under the load; few enough that the 2^20-slot table never
    has to evict a live key).  Every key is new, so the exact answer is OK with
    limit - 1 remaining.  Stops early at `deadline` (the caller reports
    the cut).  Returns how many bulk keys were acknowledged."""
    n_requests = -(-target // PER_REQUEST)
    next_index = iter(range(n_requests))
    lock = threading.Lock()
    state = {"loaded": 0, "error": None}

    def expect_new(pairs, limits, where):
        compare(pairs, client.ask(pairs), [(OK, lim - 1, lim) for lim in limits], where)

    def worker():
        try:
            while state["error"] is None and time.monotonic() < deadline:
                with lock:
                    r = next(next_index, None)
                if r is None:
                    return
                lo = r * PER_REQUEST
                keyed = [
                    bulk_key(dep, units, i)
                    for i in range(lo, min(lo + PER_REQUEST, target))
                ]
                expect_new([k for k, _ in keyed], [lim for _, lim in keyed], "load")
                with lock:
                    state["loaded"] += len(keyed)
                if r % 32 == 0:
                    churn = [
                        (f"acct_{CHURN_UNITS[j % 3]}", f"{dep.tag}-c{r}-{j}")
                        for j in range(PER_REQUEST)
                    ]
                    expect_new(
                        churn,
                        [dep.limits[k[len("acct_"):]] for k, _ in churn],
                        "load (churn keys)",
                    )
        except Exception as e:  # noqa: BLE001 — carried to the main thread
            state["error"] = state["error"] or e

    threads = [threading.Thread(target=worker) for _ in range(n_clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if state["error"] is not None:
        raise state["error"]
    return state["loaded"]


def run_queries(
    client: Client, oracle: Oracle, dep: Deployment, units: tuple,
    rng: random.Random, loaded: int, salt: str, scale: float,
) -> None:
    """Query phases, each compared with the oracle one for one: 1, 4
    and 64 descriptors per request, serially and from 8 threads at
    once, then one 1536-descriptor request (a launch of >= 1024 lanes
    whatever the timing).  Keys repeat within and across requests, so
    counters cross their near-limit and over-limit thresholds and
    in-batch duplicates reach the dedup path; a third of each pool are
    keys the load already hit once (their slots must still hold 1)."""
    primed = set()

    def pool(name: str, size: int) -> list:
        keys = []
        for j in range(size):
            if j % 3 == 0 and len(primed) < loaded // 2:
                # Never the same loaded key in two pools: pools run
                # concurrently and the oracle replays them in turn.
                pair = None
                while pair is None or pair in primed:
                    pair, _ = bulk_key(dep, units, rng.randrange(loaded))
                primed.add(pair)
                oracle.ask([pair])  # the hit the load already made
            else:
                unit = units[j % len(units)]
                pair = (f"acct_{unit}", f"{dep.tag}-{salt}-{name}-{j}")
            keys.append(pair)
        return keys

    def plan(name: str, width: int, n_requests: int, pool_size: int) -> list:
        keys = pool(name, pool_size)
        return [rng.choices(keys, k=width) for _ in range(n_requests)]

    def n(count: int) -> int:
        return max(4, int(count * scale))

    serial = (
        plan("s1", 1, n(300), n(30))
        + plan("s4", 4, n(200), n(70))
        + plan("s64", 64, n(100), n(600))
    )
    for pairs in serial:
        compare(pairs, client.ask(pairs), oracle.ask(pairs), "serial queries")

    # Concurrent: each thread owns its keys, so each key's history is
    # one thread's sequence and the oracle can replay the threads one
    # after another.
    plans = []
    for t in range(8):
        mixed = (
            plan(f"c{t}w1", 1, n(60), n(8))
            + plan(f"c{t}w4", 4, n(40), n(16))
            + plan(f"c{t}w64", 64, n(30), n(200))
        )
        rng.shuffle(mixed)
        plans.append(mixed)
    answers = [[] for _ in plans]
    errors = []

    def worker(t: int) -> None:
        try:
            for pairs in plans[t]:
                answers[t].append(client.ask(pairs))
        except Exception as e:  # noqa: BLE001 — carried to the main thread
            errors.append(e)

    threads = [threading.Thread(target=worker, args=(t,)) for t in range(len(plans))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if errors:
        raise errors[0]
    for t, mixed in enumerate(plans):
        for pairs, resp in zip(mixed, answers[t]):
            compare(pairs, resp, oracle.ask(pairs), "concurrent queries")

    (wide,) = plan("wide", 1536, 1, 1200)
    compare(wide, client.ask(wide), oracle.ask(wide), "1536-descriptor query")


def check_shadow_and_near_limit(client: Client, server: Server, dep: Deployment) -> None:
    pair = ("trial", dep.tag)
    k = dep.shadow_limit
    want = [(OK, max(0, k - 1 - i), k) for i in range(2 * k)]
    got = [observed(client.ask([pair]))[0] for _ in want]
    if got != want:
        raise SmokeFailure(f"shadow rule: answered {got}, must be {want}")
    stats = server.json("/stats.json")["stats"]
    rule = f"ratelimit.service.rate_limit.{DOMAIN}."
    if stats.get(rule + "trial.shadow_mode") != k:
        raise SmokeFailure(
            f"shadow rule: shadow_mode stat is {stats.get(rule + 'trial.shadow_mode')}"
            f", {k} hits were over the limit"
        )
    # The fixed-window closed-form keys took N+5 hits each: the hits
    # between floor(0.8 N) and N are the near-limit ones.
    for n in dep.exact_ns:
        near = stats.get(f"{rule}exact{n}_fixed_window.near_limit")
        if near != n - (4 * n) // 5:
            raise SmokeFailure(
                f"near-limit stat of a {n}/DAY rule hit {n + 5} times is {near}, "
                f"must be {n - (4 * n) // 5}"
            )


def bucket_of(lanes: int) -> int:
    """The serving bucket a `lanes`-lane launch pads up to (lanes are
    counted before dedup, so this is an upper bound on its kernel)."""
    return next((b for b in BUCKETS if lanes <= b), BUCKETS[-1])


class LaunchWatch(threading.Thread):
    """Follows /debug/launches by cursor while the phases run (the
    ring holds 1024 launches; the load makes far more)."""

    def __init__(self, server: Server):
        super().__init__(daemon=True)
        self.server = server
        self.lanes = collections.Counter()  # bucket -> launches seen
        self.outcomes = collections.Counter()
        self.max_lanes = 0
        self.stamped = 0
        self._cursor = 0
        self._halt = threading.Event()

    def poll(self) -> None:
        body = self.server.json(f"/debug/launches?since={self._cursor}")
        self.stamped = body["stamped"]
        for rec in body["launches"]:
            self._cursor = rec["seq"]
            self.outcomes[rec["outcome"]] += 1
            if rec["outcome"] == "ok":
                self.max_lanes = max(self.max_lanes, rec["lanes"])
                self.lanes[bucket_of(rec["lanes"])] += 1

    def run(self) -> None:
        while not self._halt.wait(0.2):
            self.poll()

    def finish(self) -> None:
        self._halt.set()
        self.join()
        self.poll()


def device_evidence(server: Server, client: Client, watch: LaunchWatch, dry_run: bool) -> dict:
    """Everything that says the DEVICE did the work, from surfaces the
    server already has.  Anything off -> SmokeFailure."""
    faults = server.json("/debug/faults")
    stats = server.json("/stats.json")["stats"]
    device = faults["device"]
    problems = []
    platform = "cpu" if dry_run else "tpu"
    for bank in faults["banks"]:
        if bank["state"] != "closed" or bank["restarts"] or bank["fallback_decisions"]:
            problems.append(f"bank {bank['role']}: {bank}")
        if not all(d.startswith(platform + ":") for d in bank["state_devices"]):
            problems.append(f"bank {bank['role']} state lives on {bank['state_devices']}")
        if len(bank["state_devices"]) != 1:
            problems.append(f"bank {bank['role']} spans {bank['state_devices']}")
    if faults["banks"][0]["slot_table"] != "native":
        problems.append(
            f"fixed-window bank runs the {faults['banks'][0]['slot_table']} slot table"
        )
    zero = {
        name: stats.get("ratelimit.tpu.fault." + name)
        for name in (
            "hang", "exception", "device_lost", "fallback_decisions",
            "quarantined_banks", "restarts", "probe_failures", "deadline_answers",
        )
    }
    if any(zero.values()) or None in zero.values():
        problems.append(f"fault family is not all zero: {zero}")
    if watch.outcomes["ok"] == 0 or set(watch.outcomes) != {"ok"}:
        problems.append(f"launch outcomes seen: {dict(watch.outcomes)}")
    health = server.http("/healthcheck")
    if health != "OK" or not client.serving():
        problems.append(f"health: /healthcheck={health!r} grpc SERVING={client.serving()}")
    if problems:
        raise SmokeFailure("device evidence: " + "; ".join(problems))
    return {
        "device": device,
        "banks": {
            b["role"]: {k: b[k] for k in ("slot_table", "state_devices", "shapes_compiled")}
            for b in faults["banks"]
        },
        "shapes_compiled": sum(b["shapes_compiled"] for b in faults["banks"]),
        "faults": zero,
        "snapshots": faults["snapshots"],
        "launches_stamped": watch.stamped,
        "launches_seen": dict(watch.outcomes),
        "launch_lane_buckets_seen": {str(b): c for b, c in sorted(watch.lanes.items())},
        "max_launch_lanes": watch.max_lanes,
        "live_keys": {
            b["role"]: stats[f"ratelimit.tpu.bank{b['bank']}.live_keys"]
            for b in faults["banks"]
        },
        "evictions": stats["ratelimit.tpu.bank0.evictions"],
        "window_rollovers": stats["ratelimit.tpu.bank0.window_rollovers"],
        "resolution_cache": {
            k: stats["ratelimit.tpu.resolution_cache." + k]
            for k in ("hits", "misses", "clears")
        },
    }


def build_native_table() -> dict:
    """Compile native/*.cpp on THIS machine, now, through the loader's
    own builder (what `make native` calls).  Whatever .so came with
    the copy is replaced, so the "native" the server reports below is
    a library built here."""
    t = time.monotonic()
    if not native_slot_table._build():
        raise SmokeFailure(
            "native slot table: g++ could not build native/*.cpp on this machine"
        )
    return {
        "built_here": os.path.relpath(native_slot_table._SO, REPO),
        "build_wall_s": round(time.monotonic() - t, 1),
    }


def package_version(name: str):
    try:
        return importlib.metadata.version(name)
    except importlib.metadata.PackageNotFoundError:
        return None


def cache_entries(cache_dir: str) -> int:
    try:
        return len(os.listdir(cache_dir))
    except FileNotFoundError:
        return 0


def require_platform(server: Server, dry_run: bool) -> dict:
    device = server.json("/debug/faults")["device"]
    if not dry_run and device["platform"] != "tpu":
        raise SmokeFailure(
            f"the serving process runs on platform {device['platform']!r} "
            f"({device['device_kind']}), not on a TPU: nothing here was proven "
            "on the chip"
        )
    return device


# ---------------------------------------------------------------------------
# main
# ---------------------------------------------------------------------------


def begin_session(server: Server, dep: Deployment, salt: str, timeout_s: float, dry_run: bool):
    """Bring one server to its first answers: healthy, on the right
    platform, one request, then the closed-form check of all three
    algorithms (on an unwarmed server these are each bank's first
    kernel shapes, compiled under live RPCs — which the watchdog must
    not call a hang) and GCRA's refill."""
    healthy_s = server.wait_healthy(timeout_s)
    device = require_platform(server, dry_run)
    print(server.start_line, flush=True)
    client = Client(server)
    watch = LaunchWatch(server)
    watch.start()
    t = time.monotonic()
    client.ask([("acct_day", f"{dep.tag}-first")])
    facts = {
        "time_to_healthy_s": round(healthy_s, 2),
        "first_answer_s": round(time.monotonic() - t, 3),
    }
    check_closed_form(client, dep, salt)
    facts["gcra_refills_granted"] = check_gcra_refill(client, dep, salt)
    return client, watch, device, facts


def end_session(server: Server, client: Client, watch: LaunchWatch, facts: dict, dry_run: bool) -> dict:
    """Collect the device evidence, then SIGTERM -> exit code 0."""
    watch.finish()
    evidence = device_evidence(server, client, watch, dry_run)
    server.stop()
    return {**facts, "requests": client.requests, "decisions": client.decisions, **evidence}


def run(seed: int, dry: bool, out_stem: str) -> dict:
    t0 = time.monotonic()
    dep = Deployment(seed)
    rng = random.Random(seed + 1)
    cache_dir = compile_cache_dir()
    child_env = {"JAX_PLATFORMS": "cpu"} if dry else {}
    target = DRY_RUN_KEYS if dry else FULL_KEYS
    scale = 0.1 if dry else 1.0
    units = settle_units(BUDGET_S + 120)
    cache = {"dir": cache_dir, "entries_before": cache_entries(cache_dir)}
    result = {
        "seed": seed,
        "host_cores": os.cpu_count(),
        "versions": {p: package_version(p) for p in ("jax", "jaxlib", "libtpu")},
        "deployment": {
            "source": "BASELINE.json config 4 + sliding_window and gcra rules",
            "limits": dep.limits, "exact_n_per_day": dep.exact_ns,
            "gcra_refill_n_per_minute": dep.drip_n, "bulk_units": units,
        },
        "native_slot_table": build_native_table(),
        "compile_cache": cache,
        "reduced": [],
    }
    if dry:
        result["dry_run"] = "JAX_PLATFORMS=cpu rehearsal: proves nothing about the chip"
        result["reduced"].append(f"dry run: {target} keys, query phases x{scale}")

    with tempfile.TemporaryDirectory(prefix="chip_smoke_runtime_") as runtime_root:
        dep.write_runtime(runtime_root)

        # ---- start 1: cold compile cache, no warm-up -------------------
        with serving("start1", out_stem, runtime_root, child_env) as server:
            client, watch, device, facts = begin_session(server, dep, "start1", 300, dry)
            t = time.monotonic()
            loaded = load_keys(
                client, dep, units, target, LOAD_CLIENTS,
                deadline=t0 + BUDGET_S - AFTER_LOAD_S,
            )
            facts.update(keys_loaded=loaded, load_wall_s=round(time.monotonic() - t, 1))
            if loaded < min(target, MIN_KEYS):
                raise SmokeFailure(
                    f"load: only {loaded} of {target} keys acknowledged in "
                    f"{facts['load_wall_s']}s — under the {MIN_KEYS} floor"
                )
            if loaded < target:
                result["reduced"].append(
                    f"keys cut from {target} to {loaded}: the load had "
                    f"{facts['load_wall_s']}s of the {BUDGET_S:.0f}s budget"
                )
            live = server.json("/stats.json")["stats"]["ratelimit.tpu.bank0.live_keys"]
            if live < loaded:
                raise SmokeFailure(
                    f"load: {loaded} keys acknowledged but the slot-table gauge "
                    f"reads {live} live keys"
                )
            run_queries(client, Oracle(dep), dep, units, rng, loaded, "q1", scale)
            check_shadow_and_near_limit(client, server, dep)
            result["start1"] = end_session(server, client, watch, facts, dry)
        cache["entries_after_start1"] = cache_entries(cache_dir)
        if result["start1"]["max_launch_lanes"] < 1024:
            raise SmokeFailure(
                f"largest launch seen had {result['start1']['max_launch_lanes']} lanes"
            )
        if not cache["entries_after_start1"]:
            raise SmokeFailure(f"start 1 left no compile-cache entry in {cache_dir}")

        # ---- start 2: start 1's cache, every serving shape warmed ------
        warm_env = {**child_env, "TPU_WARMUP": "true"}
        with serving("start2", out_stem, runtime_root, warm_env) as server:
            client, watch, _, facts = begin_session(server, dep, "start2", 600, dry)
            run_queries(client, Oracle(dep), dep, units, rng, 0, "q2", 0.2 * scale)
            result["start2"] = end_session(server, client, watch, facts, dry)
        cache["entries_after_start2"] = cache_entries(cache_dir)
        if result["start2"]["shapes_compiled"] != SERVING_SHAPES:
            raise SmokeFailure(
                f"warm-up completed {result['start2']['shapes_compiled']} kernel "
                f"shapes, the default banks serve {SERVING_SHAPES}"
            )

    result["mismatches"] = 0  # any mismatch raised above
    result["wall_s"] = round(time.monotonic() - t0, 1)
    return {
        "ok": True,
        "device": {
            "platform": device["platform"],
            "kind": device["device_kind"],
            "count": device["device_count"],
        },
        **result,
    }


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--seed", type=int, default=21)
    p.add_argument(
        "--dry-run", action="store_true",
        help="sandbox rehearsal on JAX_PLATFORMS=cpu at a tiny size; proves "
        "nothing about the chip and is not what the driver runs",
    )
    args = p.parse_args(argv)
    os.makedirs(OUT_DIR, exist_ok=True)
    # A dry run never writes over a chip run's evidence.
    out_stem = os.path.join(
        OUT_DIR, "chip_smoke_dry_run" if args.dry_run else "chip_smoke"
    )
    if args.dry_run:
        print("DRY RUN on JAX_PLATFORMS=cpu: proves nothing about the chip")

    def out_of_time(signum, frame):
        raise SmokeFailure("time limit: 1190 s are up")

    # The contract's time limit, enforced from inside: SIGALRM lands in
    # the main thread as an exception and `serving` kills the child.
    signal.signal(signal.SIGALRM, out_of_time)
    signal.alarm(1190)
    try:
        result = run(args.seed, args.dry_run, out_stem)
    except (SmokeFailure, grpc.RpcError, OSError) as e:
        print(f"chip_smoke FAILED: {e}", file=sys.stderr)
        return 1
    finally:
        signal.alarm(0)
    if "jax" in sys.modules:
        # One process per chip: this one must never be able to hold it.
        print("chip_smoke FAILED: the parent process imported jax", file=sys.stderr)
        return 1
    counts = json.dumps(result)
    with open(out_stem + ".json", "w") as f:
        f.write(counts + "\n")
    print(counts)
    # The verdict is the last line and holds these two keys only.
    print(json.dumps({"ok": result["ok"], "device": result["device"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
