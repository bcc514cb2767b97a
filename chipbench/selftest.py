"""The benchmark's own checks, on the CPU, no chip:

    python3 -m chipbench.selftest [--quick]

  trace      trace_reduce on a recorded TPU trace cut to 40 steps
             (testdata/), against numbers worked out by hand
  generator  same seed -> same plan, arrivals and bytes; another seed differs
  reference  an exact limiter passes the comparison; a reference with every
             limit raised by one, and a limiter that admits one too many, fail
  manifest   BENCHMARK.json and the files under chipbench/ agree
  runs       (not with --quick) whole rehearsal runs come out correct; with
             the timed path broken underneath (--control server: the server
             admits limit + 1) and with the reference's limits raised
             (--control reference) they come out not correct.  README.md's
             worked examples and the cell it holds back are written out as
             files and entries of their own in a copy of the tree, and run
"""

from __future__ import annotations

import json
import os
import re
import shutil
import subprocess
import sys

import numpy as np

from . import layers, traffic, trace_reduce
from .deploy import Deployment, load_json
from .reference import OK, OVER_LIMIT, Ledger
from .server import ROOT

HERE = os.path.dirname(os.path.abspath(__file__))
TRACE = os.path.join(HERE, "testdata", "tpu_v5e_saturated_40steps.xplane.pb")


def check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)
    print("ok  " + what)


def test_trace() -> None:
    busy, gaps = trace_reduce.union_seconds([(0, 10), (5, 15), (20, 30), (30, 31)])
    check(busy == 26e-9 and gaps == [(15, 5e-9)], "union of overlapping intervals: 26 ns busy, one 5 ns gap")
    check(trace_reduce.op_label("%fusion.1 = u32[1048576]{0:T(1024)} fusion(u32[1048576]{0} %p)") == "fusion.1_u32_1048576", "op label from HLO text")
    check(trace_reduce.module_label("jit_step(123)") == "jit_step", "program label drops the fingerprint")
    out = subprocess.run(
        [sys.executable, "-m", "chipbench.trace_reduce", TRACE], cwd=ROOT, capture_output=True,
        text=True, env={**os.environ, "JAX_PLATFORMS": "cpu"}, timeout=300,
    )
    check(out.returncode == 0, "trace_reduce reads the recorded .xplane.pb")
    t = json.loads(out.stdout.splitlines()[-1])
    check(t["device_planes"] == 1, "one device plane (the host plane is not one)")
    check(t["modules"] == [["jit_step_counters_unique_packed", t["modules"][0][1], 40]], "40 executions of the serving step")
    check(abs(t["modules"][0][1] - 0.00084672) < 1e-9, "their device time: 846.72 us")
    check(t["ops"][0][0] == "fusion.1_u32_1048576" and t["ops"][0][2] == 40, "the whole-table fusion runs once a step and leads the ops")
    check(abs(t["busy_s"] - 0.000835095) < 1e-9, "busy 835.095 us")
    check(abs(t["window_s"] - 0.292256635) < 1e-9, "window = the device's own span when no length is given")
    check(abs(t["busy_s"] + sum(g for _, g in t["idle_gaps"]) - t["window_s"]) < 1e-6, "busy + idle gaps = the span")
    obs = {"trace": t, "device_kind": "TPU v5 lite", "traced_launches": [{"lanes": 8}, {"lanes": 12}]}
    step = layers.read({"kind": "trace", "table": "modules", "pattern": "step_counters", "reduce": "mean_us"}, obs)
    check(abs(step - 21.168) < 1e-3, "kernel step 21.168 us")
    roof = layers.read({"kind": "roofline", "pattern": "step_counters", "bytes": "fixed_window_step"}, obs)
    want = 100 * (40 * (8 * 26 + 16 * 26) / 2 / 819e9) / 0.00084672
    check(abs(roof - want) < 1e-12 and roof < 1, f"roofline share {roof:.6f}% from shapes and the 819 GB/s peak")
    try:
        layers.peak("TPU v9", "hbm_bytes_per_s")
    except KeyError:
        check(True, "an unknown device kind is an error")
    else:
        check(False, "an unknown device kind is an error")


def test_generator() -> None:
    for cfg, mix_name in (("tenants-zipf", "zipf4-poisson"), ("mixed-1m", "hot1pct-closed256")):
        mix = load_json("traffic", mix_name)
        seed = 2**31 + 7
        runs = []
        for s in (seed, seed, seed + 1):
            dep = Deployment(load_json("configs", cfg), s, rehearse=True)
            n, offsets = traffic.offered(mix, 5.0, s, True)
            d, k = traffic.plan(mix, dep, s, n)
            payload = b"".join(traffic.make_request(dep, d[i], k[i]) for i in range(min(n, 64)))
            runs.append((n, None if offsets is None else offsets.tobytes(), d.tobytes(), k.tobytes(), payload, dep.yaml(0)))
        check(runs[0] == runs[1], f"{cfg}/{mix_name}: same seed, same arrivals, plan, bytes and rules")
        check(runs[0][2:5] != runs[2][2:5], f"{cfg}/{mix_name}: another seed, another plan")


def _simulate(dep: Deployment, extra_admit: int, limit_offset: int):
    """A limiter written inline (counts in a dict) answers a random
    sequence; the Ledger judges it.  extra_admit = how many hits above
    the limit the simulated server lets through."""
    rng = np.random.default_rng(5)
    counts: dict = {}
    ledger = Ledger(dep, limit_offset)
    now = 1_000_000_020.0  # mid-minute, so no window is straddled
    gids = rng.integers(0, 40, 3000) * 7 % dep.n_keys
    d, k = np.divmod(gids, dep.kpd)
    limits = dep.limits_of(d, k)
    shadow = dep.shadow_by_family[dep.family_of(k)]
    codes = np.zeros(len(gids), dtype=int)
    half = len(gids) // 2
    mismatches = 0
    for i, g in enumerate(gids.tolist()):
        counts[g] = counts.get(g, 0) + 1
        over = counts[g] > limits[i] + extra_admit and not shadow[i]
        codes[i] = OVER_LIMIT if over else OK
        if i >= half:  # second half: the ordered replay
            lim = int(limits[i]) + extra_admit
            got = [(int(codes[i]), max(0, lim - counts[g]), lim)]
            _, m, _ = ledger.expect([g], now, now + 0.001, got)
            mismatches += m
        elif i == half - 1:  # first half: unordered, in one go
            ledger.add(gids[:half], np.full(half, now), np.full(half, now + 0.5), codes[:half] == OK, np.ones(half, bool))
    return mismatches, ledger.check_log()


def test_reference() -> None:
    dep = Deployment(load_json("configs", "mixed-1m"), 3, rehearse=True)
    m, book = _simulate(dep, 0, 0)
    check(m == 0 and book["over_admitted"] == 0 and book["miscounted"] == 0 and book["exact_windows"] > 0, "an exact limiter passes replay and log comparison")
    m, book = _simulate(dep, 0, 1)
    check(m > 0 and book["miscounted"] > 0, f"must-fail: reference limits + 1 -> {m} replay mismatches, {book['miscounted']} miscounted windows")
    m, book = _simulate(dep, 1, 0)
    check(m > 0 and book["over_admitted"] > 0, f"must-fail: a limiter admitting limit + 1 -> {book['over_admitted']} over-admitted windows")


def test_manifest() -> None:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"] for w in bench["workloads"]}
    e2e = {m["name"] for m in bench["end_to_end"]}
    for c in bench["configs"]:
        check(os.path.exists(os.path.join(ROOT, c["file"])), f"config file {c['file']}")
    for w in bench["workloads"]:
        load_json("configs", w["config"]), load_json("traffic", w["traffic"])
    check(True, "every cell's configuration and mix load")
    for m in bench["per_layer"]:
        spec = load_json("layer_metrics", m["name"])
        if not (set(spec) == {"what", "reader"} and m["moves"] in e2e and set(m["workloads"]) <= cells and spec["reader"]["kind"]):
            check(False, f"layer metric {m['name']}: a reader file of its own, nothing of BENCHMARK.json repeated in it")
    check(True, f"{len(bench['per_layer'])} layer metrics: each has its reader file")


def _run(tree: str, *args) -> dict:
    out = subprocess.run(
        [sys.executable, "-m", "chipbench.run", *args, "--rehearse"], cwd=tree,
        capture_output=True, text=True, timeout=600,
    )
    if out.returncode != 0:
        raise AssertionError(f"run {args} exited {out.returncode}:\n{out.stderr[-3000:]}")
    return json.loads(out.stdout.splitlines()[-1])


def merged_tree() -> str:
    """A copy of the tree with README.md's blocks applied: each `file:`
    block written out as a new file, each `merge:` block added to a
    copy of BENCHMARK.json — what a later PR would commit."""
    tree = os.path.join(ROOT, ".chipbench_work", "selftest_tree")
    shutil.rmtree(tree, ignore_errors=True)
    shutil.copytree(HERE, os.path.join(tree, "chipbench"), ignore=shutil.ignore_patterns("__pycache__"))
    for name in ("ratelimit_tpu", "native"):
        os.symlink(os.path.join(ROOT, name), os.path.join(tree, name))
    with open(os.path.join(HERE, "README.md")) as f:
        readme = f.read()
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    blocks = re.findall(r"<!-- (file|merge): (\S+) -->\n```json\n(.*?)```", readme, re.S)
    check([k for k, _, _ in blocks].count("file") == 3, "README has three example files")
    check([k for k, _, _ in blocks].count("merge") == 2, "README has two BENCHMARK.json merges: the example cell and the cells held back")
    for kind, path, body in blocks:
        if kind == "file":
            check(not os.path.exists(os.path.join(ROOT, path)), f"{path} is a new file, no edit")
            with open(os.path.join(tree, path), "w") as f:
                f.write(body)
            continue
        add = json.loads(body)
        for key in ("configs", "workloads", "end_to_end", "per_layer"):
            bench[key] += add.get(key, [])
        for metric, cells in add.get("end_to_end_workloads", {}).items():
            next(m for m in bench["end_to_end"] if m["name"] == metric)["workloads"] += cells
    with open(os.path.join(tree, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return tree


def test_runs() -> None:
    tree = merged_tree()
    base = ("--workload", "mixed-1m.saturated", "--seed", "31", "--seconds", "4", "--trace", "0")
    r = _run(tree, *base)
    check(r["correct"] is True and r["failed"] == 0 and not r["metrics"], "a rehearsal run (every rule kind: a cell held back) is correct and reports no metric")
    r = _run(tree, *base, "--control", "server")
    check(r["correct"] is False, "timed path broken underneath (server admits limit + 1): correct is false")
    r = _run(tree, *base, "--control", "reference")
    check(r["correct"] is False, "reference limits + 1: correct is false")
    r = _run(tree, "--workload", "tenants-zipf.saturated", "--seed", "32", "--seconds", "4", "--trace", "1")
    check(r["correct"] is True and r["failed"] == 0, "a traced closed-loop rehearsal of the other cell held back is correct")
    r = _run(ROOT, "--workload", "tenants-zipf.paced", "--seed", str(2**31 + 11), "--seconds", "6", "--trace", "1")
    check(r["correct"] is True, "a traced open-loop rehearsal with a seed over 2**31 is correct")
    r = _run(tree, "--workload", "uniform-10k-second.paced", "--seed", "8", "--seconds", "4", "--trace", "1")
    check(r["correct"] is True, "README's example configuration, mix, metric and cell run in --rehearse")


def main(argv) -> int:
    tests = [test_trace, test_generator, test_reference, test_manifest]
    if "--quick" not in argv:
        tests += [test_runs]
    for t in tests:
        print(f"--- {t.__name__[5:]}")
        t()
    print("selftest passed")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
