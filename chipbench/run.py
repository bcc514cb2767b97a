"""Run one cell of the benchmark once.

    python -m chipbench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A fresh process tree: this launcher (never imports JAX), the server
(`chipbench.served` = the program's own runner, the one process on the
chip) and the load-generator workers.  Set-up: start server and workers
together, build the key load while the server warms, load the cell's
keys, open the window after a few seconds of the cell's own traffic.
Then measure for --seconds, check the answers, SIGTERM the server, and
print one JSON line.  No TPU, or another device count than the cell
asks for: exit non-zero, no result.

  --rehearse           tiny sizes on JAX_PLATFORMS=cpu: counts and the
                       correctness verdict, never a metric
  --control server     must-fail probe: the server is given every limit
                       raised by one; `correct` has to come out false
  --control reference  must-fail probe: the reference's limits raised by one
  --series             also print completions and p50 per second of the window
  --rate R             offer R requests/s in an open-loop cell (the one-off
                       knee sweep; the driver never passes it)
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402

import grpc  # noqa: E402
import numpy as np  # noqa: E402

from . import layers, traffic  # noqa: E402
from .deploy import Deployment, load_json  # noqa: E402
from .reference import OK, Ledger  # noqa: E402
from .server import ROOT, RunFailure, Server, split_cores  # noqa: E402

N_WORKERS = 4
WORK_ROOT = os.path.join(ROOT, ".chipbench_work")
TRACE_FOR_S = 3.0  # the traced seconds, in the second half of the window


def say(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def benchmark_json() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def metrics_of(bench: dict, group: str, cell: str) -> list:
    return [m for m in bench[group] if cell in m.get("workloads", [cell])]


# ---------------------------------------------------------------------------
# the key load (set-up) and the replay (after the window): plain gRPC threads
# ---------------------------------------------------------------------------


class Caller:
    """Blocking unary calls on one connection of its own."""

    def __init__(self, port: int):
        from . import wire  # here, not at the top: needs the program on the path

        self.channel = grpc.insecure_channel(
            f"127.0.0.1:{port}", options=[("grpc.use_local_subchannel_pool", 1)]
        )
        self.call = self.channel.unary_unary(
            wire.METHOD, response_deserializer=wire.rls_pb2.RateLimitResponse.FromString
        )

    def ask(self, payload: bytes):
        """(wall at send, wall at receipt, [(code, remaining, limit)])."""
        t_send = time.time()
        resp = self.call(payload, timeout=120)
        t_recv = time.time()
        return t_send, t_recv, [
            (s.code, s.limit_remaining, s.current_limit.requests_per_unit)
            for s in resp.statuses
        ]

    def close(self) -> None:
        self.channel.close()


def build_load(dep: Deployment, per_request: int) -> list:
    """[(gids, payload)]: every loaded key once, one domain a request."""
    keys = dep.load_keys()
    out = []
    for d in range(dep.n_domains):
        for lo in range(0, len(keys), per_request):
            chunk = keys[lo : lo + per_request]
            out.append((d * dep.kpd + chunk, traffic.make_request(dep, d, chunk)))
    return out


def load_keys(dep: Deployment, port: int, requests: list, n_conn: int, ref_offset: int = 0) -> dict:
    """Hit every loaded key once from n_conn connections.  Every key is
    new, so the reference's answer is OK with limit - 1 remaining
    (`ref_offset` raises its limits: --control reference); the hits go
    into the run's log with their instants."""
    nxt = iter(range(len(requests)))
    lock = threading.Lock()
    rows = {"gid": [], "t_send": [], "t_recv": [], "wrong": 0, "error": None}

    def worker() -> None:
        caller = Caller(port)
        try:
            while rows["error"] is None:
                with lock:
                    i = next(nxt, None)
                if i is None:
                    return
                gids, payload = requests[i]
                t_send, t_recv, got = caller.ask(payload)
                d, k = np.divmod(gids, dep.kpd)
                lim = dep.limits_of(d, k) + ref_offset
                want_rem = lim - 1
                wrong = len(got) != len(gids) or any(
                    g != (OK, int(r), int(m)) for g, r, m in zip(got, want_rem, lim)
                )
                with lock:
                    rows["wrong"] += wrong
                    rows["gid"].append(gids)
                    rows["t_send"].append(np.full(len(gids), t_send))
                    rows["t_recv"].append(np.full(len(gids), t_recv))
        except Exception as e:  # noqa: BLE001 — carried to the main thread
            rows["error"] = rows["error"] or e
        finally:
            caller.close()

    threads = [threading.Thread(target=worker) for _ in range(n_conn)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if rows["error"] is not None:
        raise rows["error"]
    for key in ("gid", "t_send", "t_recv"):
        rows[key] = np.concatenate(rows[key]) if rows[key] else np.zeros(0)
    return rows


def replay_plan(dep: Deployment, spec: dict, seed: int, seen_gids: np.ndarray):
    """The ordered sequence: `requests` requests of 1..4 descriptors in
    one domain each, over a pool of keys drawn from the seed — half of
    them keys the run has already hit (their counters carry on), every
    family of the configuration among them — repeated until counters
    cross their limits."""
    rng = np.random.default_rng([int(seed), 3])
    pool_n = int(spec["pool"])
    d0 = int(seen_gids[0] // dep.kpd) if len(seen_gids) else 0
    seen_k = np.unique(seen_gids[seen_gids // dep.kpd == d0] % dep.kpd)
    pool = list(rng.choice(seen_k, min(len(seen_k), pool_n // 2), replace=False))
    per_family = max(2, (pool_n - len(pool)) // len(dep.families))
    for fam in dep.families:
        pool += list(fam.offset + rng.choice(fam.count, min(fam.count, per_family), replace=False))
    pool = np.array(pool, dtype=np.int64)
    out = []
    for _ in range(int(spec["requests"])):
        ks = pool[rng.integers(0, len(pool), int(rng.integers(1, 5)))]
        out.append((d0 * dep.kpd + ks, traffic.make_request(dep, d0, ks)))
    return out


# ---------------------------------------------------------------------------
# observers of a traced run
# ---------------------------------------------------------------------------


class LaunchWatch(threading.Thread):
    """Follows /debug/launches by cursor (the ring holds 1024)."""

    def __init__(self, server: Server):
        super().__init__(daemon=True)
        self.server = server
        self.records = []
        self._cursor = 0
        self._halt = threading.Event()

    def poll(self) -> None:
        body = self.server.json(f"/debug/launches?since={self._cursor}")
        for rec in body["launches"]:
            self._cursor = rec["seq"]
            self.records.append(rec)

    def run(self) -> None:
        while not self._halt.wait(0.25):
            self.poll()

    def finish(self) -> list:
        self._halt.set()
        self.join()
        self.poll()
        return self.records


def cache_entries() -> int:
    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(ROOT, ".jax_cache")
    try:
        return len(os.listdir(cache))
    except FileNotFoundError:
        return 0


def find_xplane(work: str):
    for root, _dirs, names in os.walk(work):
        for name in names:
            if name.endswith(".xplane.pb"):
                return os.path.join(root, name)
    return None


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------


class Run:
    """One cell, once: set_up -> window -> client_numbers -> check ->
    shut_down -> result.  `close` leaves no process behind, however the
    run ended."""

    def __init__(self, args):
        if not os.path.isdir(os.path.join(ROOT, "ratelimit_tpu")):
            raise RunFailure(f"the program (ratelimit_tpu/) is not in this checkout: {ROOT}")
        self.args = args
        self.bench = benchmark_json()
        self.cell = next((w for w in self.bench["workloads"] if w["name"] == args.workload), None)
        if self.cell is None:
            raise RunFailure(f"no workload {args.workload!r} in BENCHMARK.json")
        self.config = load_json("configs", self.cell["config"])
        self.mix = load_json("traffic", self.cell["traffic"])
        self.seed = int(args.seed)
        self.seconds = float(args.seconds)
        self.dep = Deployment(self.config, self.seed, args.rehearse, limit_offset=args.control == "server")
        self.work = os.path.join(WORK_ROOT, self.cell["name"])
        self.server = None
        self.workers = []
        self.obs = {"harness": {}}

    # -- set-up: everything before the window opens -----------------------

    def set_up(self) -> None:
        args, config = self.args, self.config
        shutil.rmtree(self.work, ignore_errors=True)
        os.makedirs(self.work)
        server_cpus, self.gen_cpus = split_cores()
        os.sched_setaffinity(0, self.gen_cpus)
        say(f"cores: server {server_cpus}, generator {self.gen_cpus}")
        runtime_root = os.path.join(self.work, "runtime")
        self.dep.write_runtime(runtime_root)
        env = dict(config.get("server_env", {}))
        if args.trace:
            # Stopping the profiler blocks device calls for seconds (2.6 s
            # on the chip, PR 23), which the default 0.25 s kernel watchdog
            # reads as a hang: quarantine, host fallback, restart.  A traced
            # run raises the deadline so that the trace shows serving.
            env.update(DEBUG_PROFILING="1", KERNEL_DEADLINE_S="30")
        if args.rehearse:
            env["JAX_PLATFORMS"] = "cpu"
        self.server = server = Server(self.work, runtime_root, env, server_cpus)
        for w in range(N_WORKERS):
            spec = {
                "config": self.cell["config"], "traffic": self.cell["traffic"], "seed": self.seed,
                "rehearse": args.rehearse, "worker": w, "n_workers": N_WORKERS,
                "seconds": self.seconds, "cpus": self.gen_cpus, "rate_rps": args.rate,
                "out": os.path.join(self.work, f"worker{w}.npz"),
            }
            spec_path = os.path.join(self.work, f"worker{w}.json")
            with open(spec_path, "w") as f:
                json.dump(spec, f)
            self.workers.append(
                subprocess.Popen(
                    [sys.executable, "-m", "chipbench.loadgen", spec_path], cwd=ROOT,
                    stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True,
                )
            )
        # The server takes tens of seconds to come up: build the load now.
        load_requests = build_load(self.dep, int(config["load_per_request"]))
        healthy_s = server.wait_healthy(300 if args.rehearse else 1100)
        self.device = device = server.json("/debug/faults")["device"]
        self.obs["device_kind"] = device["device_kind"]
        say(server.start_line)
        if not args.rehearse and (
            device["platform"] != "tpu" or device["device_count"] != self.cell["chips"]
        ):
            raise RunFailure(
                f"the server runs on {device['device_count']} x {device['platform']!r} "
                f"({device['device_kind']}); the cell asks for {self.cell['chips']} TPU chip(s)"
            )
        t = time.monotonic()
        self.loaded = load_keys(
            self.dep, server.grpc_port, load_requests, int(config["load_connections"]),
            ref_offset=args.control == "reference",
        )
        load_s = time.monotonic() - t
        for w in self.workers:
            if w.stdout.readline().strip() != "ready":
                raise RunFailure("a load-generator worker died before it was ready")
        self.watch = None
        if args.trace:
            self.obs["stats_a"] = server.json("/stats.json")
            self.obs["faults_a"] = server.json("/debug/faults")
            self.watch = LaunchWatch(server)
            self.watch.start()
        warm_s = float(self.mix["warm_s"]) + 0.5
        self.t0 = time.monotonic() + warm_s
        self.t_end = self.t0 + self.seconds
        for w in self.workers:
            w.stdin.write(f"go {server.grpc_port} {self.t0!r}\n")
            w.stdin.flush()
        self.setup_s = self.t0 - T_START
        say(
            f"set-up {self.setup_s:.1f}s: server healthy {healthy_s:.1f}, key load {load_s:.1f} "
            f"({len(self.loaded['gid'])} keys), warm-up traffic {warm_s:.1f}"
        )

    # -- the window ----------------------------------------------------------

    def window(self) -> None:
        """Sleep through the window, reading only /proc — except in a
        traced run, which reads the host's layers over the window's
        first half, undisturbed (t0 .. t_mid), then snapshots the
        counters and traces a few seconds of the second half: the
        profiler slows every Python thread, and its stop blocks device
        calls for seconds."""
        server, obs, seconds = self.server, self.obs, self.seconds
        time.sleep(max(0.0, self.t0 - time.monotonic()))
        cpu_a, entries_a = server.cpu_seconds(), cache_entries()
        self.t_mid = self.t_end
        if self.args.trace:
            self.t_mid = self.t0 + seconds / 2
            time.sleep(max(0.0, self.t_mid - time.monotonic()))
            self.cpu_calm_s = server.cpu_seconds() - cpu_a
            obs["stats_b"] = server.json("/stats.json")
            time.sleep(min(3.0, seconds / 10))
            self.trace_for = min(TRACE_FOR_S, seconds / 3)
            t_a = time.monotonic_ns()
            reply = server.http(f"/debug/xla_trace?seconds={self.trace_for}", timeout=300)
            traced = (t_a, time.monotonic_ns())
            say(reply.splitlines()[0])
        time.sleep(max(0.0, self.t_end - time.monotonic()))
        self.cpu_window_s = server.cpu_seconds() - cpu_a
        obs["harness"]["window_compiles"] = cache_entries() - entries_a
        for w in self.workers:
            rc = w.wait(timeout=seconds + 120)
            if rc != 0:
                raise RunFailure(f"a load-generator worker exited {rc}")
        if self.args.trace:
            records = self.watch.finish()
            obs["faults_b"] = server.json("/debug/faults")
            lo, hi = self.t0 * 1e9, self.t_mid * 1e9
            obs["launches"] = [r for r in records if lo <= r["ts_ns"] < hi and r["outcome"] == "ok"]
            obs["traced_launches"] = [r for r in records if traced[0] <= r["ts_ns"] < traced[1]]

    # -- what the client saw ---------------------------------------------------

    def client_numbers(self) -> None:
        mix, t0, t_end, seconds = self.mix, self.t0, self.t_end, self.seconds
        logs = [np.load(os.path.join(self.work, f"worker{w}.npz")) for w in range(N_WORKERS)]
        self.log = log = {
            k: np.concatenate([lg[k] for lg in logs])
            for k in ("idx", "sched", "sent", "done", "wall", "status", "codes")
        }
        self.answered = answered = log["status"] == 0
        per = log["codes"].shape[1]
        open_loop = mix["loop"] == "open"
        # An open loop measures what was DUE in the window, from its due
        # instant; a closed loop what was answered in it.
        stamp = log["sched"] if open_loop else log["done"]
        in_window = (stamp >= t0) & (stamp < t_end) & (open_loop | (log["status"] != 2))
        self.attempted = int(in_window.sum())
        self.failed = int((in_window & ~answered).sum())
        gave_up = t_end + float(mix.get("drain_s", 0.0))
        latency_ms = 1e3 * (np.where(answered, log["done"], gave_up) - log["sched"])
        lat = latency_ms[in_window]
        self.e2e = {"setup_s": self.setup_s}
        if open_loop:
            self.e2e["p50_ms"] = float(np.percentile(lat, 50))
        else:
            self.e2e["decisions_per_s"] = float((in_window & answered).sum() * per / seconds)
        # For the layers: the whole window, or in a traced run its
        # undisturbed first half.
        h = self.obs["harness"]
        calm = in_window & (stamp < self.t_mid)
        late_ms = 1e3 * (log["sent"] - log["sched"])[calm]
        h["client_mean_ms"] = float(latency_ms[calm].mean())
        h["client_p95_ms"] = float(np.percentile(latency_ms[calm], 95))
        h["client_p99_ms"] = float(np.percentile(latency_ms[calm], 99))
        h["loadgen_late_p99_ms"] = float(np.percentile(late_ms, 99)) if open_loop else None
        gen_cpu = sum(float(lg["cpu_s"]) for lg in logs)
        gen_span = max(float(lg["span_s"]) for lg in logs)
        h["loadgen_cpu_share"] = 100.0 * gen_cpu / (gen_span * len(self.gen_cpus))
        cpu_calm_s = self.cpu_calm_s if self.args.trace else self.cpu_window_s
        h["server_cpu_us_per_decision"] = 1e6 * cpu_calm_s / max(1, int((calm & answered).sum() * per))
        say(
            f"window: attempted {self.attempted}, failed {self.failed}; latency ms "
            f"p50 {np.percentile(lat, 50):.3f} p95 {np.percentile(lat, 95):.3f} "
            f"p99 {np.percentile(lat, 99):.3f} max {lat.max():.3f}; "
            f"send lateness ms p99 {np.percentile(late_ms, 99):.3f} max {late_ms.max():.3f}; "
            f"generator cpu {h['loadgen_cpu_share']:.1f}% of its cores, "
            f"server cpu {self.cpu_window_s:.1f}s"
        )
        if self.args.series:
            print_series(log, answered, t0, seconds, open_loop)

    # -- stop the server, then read what only a stopped server leaves ---------

    def shut_down(self) -> None:
        report = self.server.stop()
        for line in self.server.warnings():
            say("server: " + line)
        peaks = [p for p in report.get("peak_bytes_in_use", []) if p is not None]
        self.memory_peak_bytes = max(peaks) if peaks else None
        if not self.args.trace:
            return
        xplane = find_xplane(self.work)
        if xplane is None:
            raise RunFailure("the traced run left no .xplane.pb under " + self.work)
        out = subprocess.run(
            [sys.executable, "-m", "chipbench.trace_reduce", xplane, repr(self.trace_for)],
            cwd=ROOT, env={**os.environ, "JAX_PLATFORMS": "cpu"}, capture_output=True,
            text=True, timeout=300,
        )
        if out.returncode != 0:
            raise RunFailure("trace_reduce failed: " + out.stderr[-2000:])
        self.obs["trace"] = json.loads(out.stdout.splitlines()[-1])

    def close(self) -> None:
        for w in self.workers:
            if w.poll() is None:
                w.kill()
            w.wait()
        if self.server is not None:
            if self.server.proc.poll() is None:
                say("--- server log tail ---\n" + self.server.log_tail())
            self.server.kill()

    # -- the result line ---------------------------------------------------------

    def result(self, correct: bool) -> dict:
        args, obs, cell = self.args, self.obs, self.cell["name"]
        device = self.device
        result = {
            "correct": correct, "attempted": self.attempted, "failed": self.failed, "metrics": {},
            "device": {
                "platform": device["platform"], "kind": device["device_kind"],
                "count": device["device_count"], "memory_peak_bytes": self.memory_peak_bytes,
            },
        }
        group = "per_layer" if args.trace else "end_to_end"
        values = {}
        for m in metrics_of(self.bench, group, cell):
            if args.trace:
                values[m["name"]] = layers.read(load_json("layer_metrics", m["name"])["reader"], obs)
            else:
                values[m["name"]] = self.e2e[m["name"]]
        if args.rehearse:
            # A CPU run gives counts and the verdict, never a metric.
            result["rehearsal"] = "JAX_PLATFORMS=cpu at a tiny size: no metric is reported"
            say(f"rehearsal: would report {sorted(values)}")
            return result
        if args.trace:
            trace = obs["trace"]
            if not trace["device_planes"] or trace["busy_s"] <= 0:
                raise RunFailure("the traced seconds hold no operation on the device")
            result["device"].update(busy_s=trace["busy_s"], window_s=trace["window_s"])
            result["breakdown"] = {
                "device_ops": [[name, s] for name, s, _ in trace["ops"][:10]],
                "idle_gaps": [[name, s] for name, s in trace["idle_gaps"][:10]],
            }
        units = {m["name"]: m["unit"] for m in self.bench[group]}
        result["metrics"] = {
            name: {"value": v, "unit": units[name]} for name, v in values.items() if v is not None
        }
        return result


def run(args) -> dict:
    this = Run(args)
    try:
        this.set_up()
        this.window()
        this.client_numbers()
        correct = check_answers(this)  # outside the window and outside setup_s
        this.shut_down()
    finally:
        this.close()
    return this.result(correct)


def print_series(log, answered, t0, seconds, open_loop) -> None:
    """Completions and p50 per second of the window (stderr)."""
    stamp = log["sched"] if open_loop else log["done"]
    lat = 1e3 * (log["done"] - log["sched"])
    for s in range(int(seconds)):
        m = answered & (stamp >= t0 + s) & (stamp < t0 + s + 1)
        p50 = float(np.percentile(lat[m], 50)) if m.any() else float("nan")
        mx = float(lat[m].max()) if m.any() else float("nan")
        say(f"series second {s:3d}: completed {int(m.sum()):6d}  p50 {p50:9.3f} ms  max {mx:9.3f} ms")


def check_answers(this: Run) -> bool:
    """Decide `correct`; prints every number compared beside its limit."""
    args, dep, mix, seed, server = this.args, this.dep, this.mix, this.seed, this.server
    log, answered, loaded = this.log, this.answered, this.loaded
    ledger = Ledger(dep, limit_offset=args.control == "reference")
    n_total, _ = traffic.offered(mix, this.seconds, seed, args.rehearse, args.rate)
    domains, keys = traffic.plan(mix, dep, seed, n_total)
    per = keys.shape[1]
    gid = (domains[log["idx"]][:, None] * dep.kpd + keys[log["idx"]]).reshape(-1)
    t_send = np.repeat(log["wall"], per)
    span = np.where(answered, log["done"] - log["sent"], this.t_end + 30.0 - log["sent"])
    t_recv = t_send + np.repeat(span, per)
    ledger.add(gid, t_send, t_recv, log["codes"].reshape(-1) == OK, np.repeat(answered, per))

    plan = replay_plan(dep, this.config["replay"], seed, gid)
    needed = np.unique(np.concatenate([gid] + [g for g, _ in plan]))
    mine = np.isin(loaded["gid"], needed)
    n_mine = int(mine.sum())
    ledger.add(loaded["gid"][mine], loaded["t_send"][mine], loaded["t_recv"][mine], np.ones(n_mine, bool), np.ones(n_mine, bool))

    caller = Caller(server.grpc_port)
    compared = mismatches = 0
    first = None
    try:
        for gids, payload in plan:
            t_s, t_r, got = caller.ask(payload)
            c, m, why = ledger.expect(gids, t_s, t_r, got)
            compared, mismatches, first = compared + c, mismatches + m, first or why
    finally:
        caller.close()
    book = ledger.check_log()
    faults = server.json("/debug/faults")
    device_path_breaks = (
        sum(faults["faults"].values()) + faults["fallback_decisions"] + faults["restarts"]
        + faults["quarantined_banks"]
    )
    numbers = [
        ("load_answers_wrong", loaded["wrong"], 0),
        ("replay_mismatches", mismatches, 0),
        ("log_over_admitted_windows", book["over_admitted"], 0),
        ("log_miscounted_windows", book["miscounted"], 0),
        ("device_path_breaks", device_path_breaks, 0),
    ]
    for name, value, limit in numbers:
        print(f"check {name}={value} limit={limit}")
    print(
        f"check compared: replay decisions {compared}, key-windows {book['windows']} "
        f"(exact {book['exact_windows']}), load answers {len(loaded['gid'])}"
    )
    for why in (first, book["example"]):
        if why:
            print("check first difference: " + why)
    vacuous = compared == 0 or book["exact_windows"] == 0
    if vacuous:
        print("check compared nothing: not correct")
    return not vacuous and all(value <= limit for _, value, limit in numbers)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--rehearse", action="store_true")
    p.add_argument("--control", choices=("server", "reference"))
    p.add_argument("--series", action="store_true")
    p.add_argument("--rate", type=float, help="open loop: offer this rate, not the mix's (knee sweep only)")
    args = p.parse_args(argv)
    try:
        result = run(args)
    except (RunFailure, OSError, KeyError, subprocess.TimeoutExpired, grpc.RpcError) as e:
        say(f"chipbench FAILED: {e}")
        return 1
    if "jax" in sys.modules:
        say("chipbench FAILED: the launcher imported jax")
        return 1
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
