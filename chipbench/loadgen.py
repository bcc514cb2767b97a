"""One load-generator process: an asyncio gRPC client that offers its
share of a cell's traffic and logs every request.

Started by run.py as `python -m chipbench.loadgen <spec.json>` before
the server is up: it pins itself to the generator's cores, builds its
requests (and, for an open loop, the whole arrival schedule) from the
seed, prints `ready`, and waits on stdin for `go <grpc port> <t0>`,
where t0 is the window's opening on the machine-wide monotonic clock.
Traffic runs from t0 - warm_s to t0 + seconds; what completes (closed
loop) or was due (open loop) in [t0, t0 + seconds) is the measurement,
the rest is warm-up and is logged all the same for the answer check.

Open loop: nothing here waits on the server.  A sender coroutine spins
on the clock (the generator's cores are its own), fires each request at
its scheduled instant with no limit on requests in flight, latency runs from the scheduled instant, and how late each send
was is logged.  What is still unanswered `drain_s` after the window is
marked unanswered.

The log goes to `<out>.npz`: per request its pool index, scheduled /
sent / done instants (monotonic), wall clock at send, status (0
answered, 1 RPC error, 2 unanswered) and the answer codes.
"""

from __future__ import annotations

import asyncio
import json
import os
import sys
import time

import grpc
import numpy as np

ANSWERED, RPC_ERROR, UNANSWERED = 0, 1, 2


def worker_slice(n: int, worker: int, n_workers: int) -> np.ndarray:
    """The request numbers worker `worker` owns: every n_workers-th."""
    return np.arange(worker, n, n_workers)


class Log:
    def __init__(self, capacity: int, per: int):
        self.n = 0
        self.idx = np.zeros(capacity, dtype=np.int64)
        self.sched = np.zeros(capacity)
        self.sent = np.zeros(capacity)
        self.done = np.zeros(capacity)
        self.wall = np.zeros(capacity)
        self.status = np.full(capacity, UNANSWERED, dtype=np.int8)
        self.codes = np.zeros((capacity, per), dtype=np.uint8)

    def row(self) -> int:
        i = self.n
        if i >= len(self.idx):
            raise RuntimeError("request log full")
        self.n = i + 1
        return i

    def save(self, path: str, **extra) -> None:
        n = self.n
        np.savez(
            path, idx=self.idx[:n], sched=self.sched[:n], sent=self.sent[:n],
            done=self.done[:n], wall=self.wall[:n], status=self.status[:n],
            codes=self.codes[:n], **extra,
        )


async def _one(call, payload: bytes, log: Log, row: int, timeout: float, parse) -> None:
    try:
        raw = await call(payload, timeout=timeout)
    except grpc.aio.AioRpcError:
        log.done[row] = time.monotonic()
        log.status[row] = RPC_ERROR
        return
    log.done[row] = time.monotonic()
    codes = [s.code for s in parse(raw).statuses]
    log.codes[row, : len(codes)] = codes
    log.status[row] = ANSWERED


async def run_closed(spec, calls, payloads, log, parse) -> None:
    t_start = spec["t0"] - spec["warm_s"]
    t_end = spec["t0"] + spec["seconds"]
    cursor = iter(range(1 << 62))
    n_pool = len(payloads)

    async def client(c: int) -> None:
        call = calls[c % len(calls)]
        await asyncio.sleep(max(0.0, t_start - time.monotonic()))
        while time.monotonic() < t_end:
            j = next(cursor) % n_pool
            row = log.row()
            log.idx[row] = spec["owned"][j]
            log.wall[row] = time.time()
            log.sched[row] = log.sent[row] = time.monotonic()
            await _one(call, payloads[j], log, row, spec["rpc_timeout_s"], parse)

    await asyncio.gather(*(client(c) for c in range(spec["n_clients"])))


async def run_open(spec, calls, payloads, log, parse) -> None:
    due = spec["due"]  # monotonic instants, ascending, this worker's own
    tasks = []
    for j in range(len(due)):
        # Spin, yielding to the event loop each turn, rather than sleep:
        # a generator that sleeps between sends pays a wake-up on its own
        # core for every send and every answer (p50 +1.2 ms, send lateness
        # p99 2.5 ms against 0.11 ms spinning: my chip runs, PR 23).
        while time.monotonic() < due[j]:
            await asyncio.sleep(0)
        row = log.row()
        log.idx[row] = spec["owned"][j]
        log.sched[row] = due[j]
        log.wall[row] = time.time()
        log.sent[row] = time.monotonic()
        tasks.append(
            asyncio.ensure_future(
                _one(calls[j % len(calls)], payloads[j], log, row, spec["rpc_timeout_s"], parse)
            )
        )
    # Whatever is unanswered drain_s after the window stays UNANSWERED.
    cutoff = spec["t0"] + spec["seconds"] + spec["drain_s"]
    if tasks:
        await asyncio.wait(tasks, timeout=max(0.0, cutoff - time.monotonic()))
    for t in tasks:
        t.cancel()
    await asyncio.gather(*tasks, return_exceptions=True)


async def drive(spec, payloads, log) -> None:
    from . import wire

    channels = [
        grpc.aio.insecure_channel(
            f"127.0.0.1:{spec['port']}",
            options=[("grpc.use_local_subchannel_pool", 1)],
        )
        for _ in range(spec["n_channels"])
    ]
    calls = [ch.unary_unary(wire.METHOD) for ch in channels]  # bytes in, bytes out
    parse = wire.rls_pb2.RateLimitResponse.FromString
    try:
        await asyncio.gather(*(ch.channel_ready() for ch in channels))
        runner = run_closed if spec["loop"] == "closed" else run_open
        await runner(spec, calls, payloads, log, parse)
    finally:
        for ch in channels:
            await ch.close()


def main(argv) -> int:
    with open(argv[1]) as f:
        spec = json.load(f)
    if spec.get("cpus"):
        os.sched_setaffinity(0, spec["cpus"])
    from . import traffic
    from .deploy import Deployment, load_json

    mix = load_json("traffic", spec["traffic"])
    dep = Deployment(load_json("configs", spec["config"]), spec["seed"], spec["rehearse"])
    worker, n_workers = spec["worker"], spec["n_workers"]
    seconds, warm_s = spec["seconds"], float(mix.get("warm_s", 3.0))
    per = int(mix["descriptors_per_request"])
    spec.update(loop=mix["loop"], warm_s=warm_s, rpc_timeout_s=float(mix["rpc_timeout_s"]))
    n_total, offsets = traffic.offered(mix, seconds, spec["seed"], spec["rehearse"], spec.get("rate_rps"))
    if offsets is None:
        clients = max(n_workers, int(mix["clients"]) // (16 if spec["rehearse"] else 1))
        spec["n_clients"] = len(worker_slice(clients, worker, n_workers))
        capacity = 1 << (16 if spec["rehearse"] else 20)
    else:
        spec["drain_s"] = float(mix["drain_s"])
        capacity = n_total
    owned = worker_slice(n_total, worker, n_workers)
    domains, keys = traffic.plan(mix, dep, spec["seed"], n_total)
    payloads = [traffic.make_request(dep, domains[i], keys[i]) for i in owned]
    spec["owned"] = owned.tolist()
    spec["n_channels"] = max(1, int(mix["connections"]) // n_workers)
    log = Log(capacity, per)

    print("ready", flush=True)
    word, port, t0 = sys.stdin.readline().split()
    if word != "go":
        return 1
    spec["port"], spec["t0"] = int(port), float(t0)
    if offsets is not None:
        spec["due"] = (spec["t0"] - warm_s + offsets[owned]).tolist()
    cpu0, m0 = time.process_time(), time.monotonic()
    asyncio.run(drive(spec, payloads, log))
    log.save(spec["out"], cpu_s=time.process_time() - cpu0, span_s=time.monotonic() - m0)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
