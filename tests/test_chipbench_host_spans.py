"""chipbench/host_spans.py — the reduction from a light profiler trace
(device planes + the program's rl.* spans) to "what the host was doing
in each idle gap of the device": its own selftest cases, run here so
tier-1 guards the arithmetic every later PR's numbers rest on."""

import pytest

from chipbench import host_spans


@pytest.mark.parametrize(
    "case", [host_spans.selftest_split, host_spans.selftest_recorded]
)
def test_host_spans_selftest(case):
    case()


def test_cut_keeps_what_the_reductions_read():
    planes = host_spans.load(host_spans.TESTDATA)
    whole = host_spans.attribute(planes)
    again = host_spans.attribute(host_spans.cut(planes, 0.0, 3600.0))
    assert again == whole
    assert whole["clock"]["marks"] == 2


# ---------------------------------------------------------------------------
# the per-layer metrics PR 24 added: readers that exist, fed by what the
# program gained — in BENCHMARK.json only where silent on a program that
# lacks it
# ---------------------------------------------------------------------------

import json  # noqa: E402
import os  # noqa: E402
import re  # noqa: E402

from chipbench import layers  # noqa: E402
from chipbench.deploy import load_json  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# In BENCHMARK.json: read with the readers layers.py has, silent on an
# older program.
PR24 = ("pool_wait_ms", "prepare_us", "apply_us", "wake_us", "snapshot_ms")
# The launch legs: the `launches` reader indexes rec[field], so their
# `.paced` entries waited (chipbench/README-pr24.md) until the parent a
# PR is measured against was PR 24, whose every record carries them;
# PR 26 appended them, and `readback_ready_share.paced` with them.
LAUNCH_LEGS = ("assign_us", "device_submit_us", "readback_us", "decide_us", "handoff_us")
HELD = LAUNCH_LEGS + ("incident_stall_ms",)
PR26 = tuple(n + ".paced" for n in LAUNCH_LEGS) + ("readback_ready_share.paced",)
# PR 27 added the cell `mixed-1m.paced`: its name appended to every
# `.paced` metric's cells, and seven metrics of its own after them.
PR27 = (
    "slot_fill_share.paced", "slot_evictions.paced", "gc_pause_ms.paced", "snapshot_hold_ms.paced",
    "snapshot_timeouts.paced", "device_submit_p99_us.paced", "readback_p99_us.paced",
)
# PR 33 added the cell `uniform-10k-persecond.paced`: its name appended
# to every metric's cells, and four slot-churn metrics after them: three
# that every cell reports, and the arena's rehashes, which only its own
# traffic moves (0 in the other two on the chip).
PR33 = ("rollover_share.paced", "slot_gc_us.paced", "slot_gc_freed.paced", "arena_compactions.paced")
PACED = ["tenants-zipf.paced", "mixed-1m.paced", "uniform-10k-persecond.paced", "bulk-recipients.paced"]
# PR 35 appended two readings of the rule load: they move `setup_s`, in
# the one cell whose load is large enough to read.
PR35 = ("config_load_us_per_rule.paced", "config_parse_share.paced")
# PR 36 added the cell `bulk-recipients.paced` (64 descriptors a
# request): its name appended to every metric that moves `p50_ms`, and
# six readings of the per-descriptor path after PR 35's two, in all four cells.
PR36 = (
    "lanes_per_launch.paced", "lane_fill_share.paced", "prepare_us_per_descriptor.paced",
    "apply_us_per_descriptor.paced", "decode_us.paced", "serialize_us.paced",
)
# PR 41 appended six readings of what the device-call brackets are made
# of (on-CPU, the GIL's return, the watchdog's lateness), in all four
# cells.
PR41 = (
    "device_submit_cpu_share.paced", "readback_cpu_share.paced", "readback_ready_blocked_us.paced",
    "collector_gil_return_us.paced", "completer_gil_return_us.paced", "watchdog_late_ms.paced",
)
H = "ratelimit_server.ShouldRateLimit."


def _obs(with_new: bool, ready: bool = False) -> dict:
    """Observations as run.py gathers them: a program before PR 24 has
    response_ms, launch.rate, snapshots and the old launch fields only;
    one before PR 26 (`ready` false) no bank's readback_ready."""
    def stats(n):
        hist = {H + "response_ms": {"count": 10 * n, "total_ms": 40.0 * n}}
        if with_new:
            for leg, ms in (("pool_wait_ms", 5.0), ("prepare_ms", 4.0), ("wake_ms", 3.0), ("apply_ms", 2.0)):
                hist[H + leg] = {"count": 10 * n, "total_ms": ms * n}
        flat = {"ratelimit.tpu.launch.rate": 9 * n}
        if ready:
            flat.update({f"ratelimit.tpu.bank{b}.readback_ready": (3 - b) * n for b in range(3)})
        return {"histograms": hist, "stats": flat}

    def faults(n):
        doc = {"snapshots": 3 * n}
        if with_new:
            doc["background"] = {"total_ms": {"rl.bg.snapshot": 70.0 * n, "rl.bg.incident_capture": 0.0}}
        return doc

    launches = [{"launch_us": 1000.0 + i, "complete_us": 800.0} for i in range(4)]
    if with_new:
        for i, rec in enumerate(launches):
            rec.update({leg: 100.0 * (k + 1) + i for k, leg in enumerate(LAUNCH_LEGS)})
    return {
        "stats_a": stats(1), "stats_b": stats(3), "faults_a": faults(1), "faults_b": faults(2),
        "launches": launches,
    }


@pytest.mark.parametrize("suffix", [".paced", ""])
@pytest.mark.parametrize("name", PR24 + HELD)
def test_pr24_metric_reads_what_the_program_gained(name, suffix):
    spec = load_json("layer_metrics", name + suffix)
    assert set(spec) == {"what", "reader"}
    want = {
        "pool_wait_ms": 0.5, "prepare_us": 400.0, "wake_us": 300.0, "apply_us": 200.0,
        "assign_us": 101.5, "device_submit_us": 201.5, "readback_us": 301.5,
        "decide_us": 401.5, "handoff_us": 501.5, "incident_stall_ms": 0.0, "snapshot_ms": 70.0,
    }[name]
    assert layers.read(spec["reader"], _obs(with_new=True)) == pytest.approx(want)
    if name in LAUNCH_LEGS:
        # Why their entries wait: today's reader raises on an older
        # program's records, and the driver's traced run of the parent
        # reads every metric BENCHMARK.json lists.
        assert spec["reader"] == {"kind": "launches", "field": name, "reduce": "mean"}
        with pytest.raises(KeyError):
            layers.read(spec["reader"], _obs(with_new=False))
    else:
        assert layers.read(spec["reader"], _obs(with_new=False)) is None


def test_readback_ready_share_is_silent_on_a_program_without_the_counter():
    spec = load_json("layer_metrics", "readback_ready_share.paced")
    assert set(spec) == {"what", "reader"}
    # (3 + 2 + 1) x (3 - 1) ready of 9 x (3 - 1) launches.
    assert layers.read(spec["reader"], _obs(True, ready=True)) == pytest.approx(100 * 12 / 18)
    assert layers.read(spec["reader"], _obs(True)) is None  # the parent: PR 24
    assert layers.read(spec["reader"], _obs(False)) is None


@pytest.mark.parametrize("name", PR26)
def test_pr26_entry_reads_on_pr24s_records_and_on_the_change(name):
    """What let the launch legs land: on the parent (PR 24: the fields
    are in every record, the counter is not) no reader raises."""
    spec = load_json("layer_metrics", name)
    on_parent = layers.read(spec["reader"], _obs(True))
    on_change = layers.read(spec["reader"], _obs(True, ready=True))
    assert on_change is not None
    assert (on_parent is None) == (name == "readback_ready_share.paced")


def test_pr24_and_pr26_entries_are_appended_and_the_rest_wait_outside_the_benchmark():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    # This test holds what PRs 24-41 left, by the names of their cells:
    # a later cell's name in a list, and the metrics that list only
    # later cells, are that cell's own test's to hold.
    bench["per_layer"] = [
        dict(m, workloads=[c for c in m["workloads"] if c in PACED])
        for m in bench["per_layer"] if set(m["workloads"]) & set(PACED)
    ]
    assert [m["name"] for m in bench["per_layer"][-len(PR41):]] == list(PR41)
    for m in bench["per_layer"][-len(PR41):]:
        assert (m["moves"], m["source"], m["workloads"]) == ("p50_ms", "program_counter", PACED)
    bench["per_layer"] = bench["per_layer"][:-len(PR41)]
    assert [m["name"] for m in bench["per_layer"][-len(PR36):]] == list(PR36)
    for m in bench["per_layer"][-len(PR36):]:
        assert (m["moves"], m["workloads"]) == ("p50_ms", PACED)
    bench["per_layer"] = bench["per_layer"][:-len(PR36)]
    assert [m["name"] for m in bench["per_layer"][-len(PR35):]] == list(PR35)
    for m in bench["per_layer"][-len(PR35):]:
        assert (m["moves"], m["layer"], m["workloads"]) == ("setup_s", "whole server", PACED[:1])
    bench["per_layer"] = bench["per_layer"][:-len(PR35)]
    names = [m["name"] for m in bench["per_layer"]]
    assert names[-len(PR33):] == list(PR33)
    for m in bench["per_layer"][-len(PR33):]:
        mine = m["name"] == "arena_compactions.paced"
        assert m["moves"] == "p50_ms" and m["workloads"] == (PACED[2:] if mine else PACED)
    before33 = bench["per_layer"][:-len(PR33)]
    names = names[:-len(PR33)]
    n24, n26, n27 = len(PR24), len(PR26), len(PR27)
    assert names[-n24 - n26 - n27:-n26 - n27] == [n + ".paced" for n in PR24]
    assert names[-n26 - n27:-n27] == list(PR26)
    assert names[-n27:] == list(PR27)
    waiting = {n for n in LAUNCH_LEGS} | {"incident_stall_ms", "incident_stall_ms.paced"}
    assert not waiting & set(names)
    for m in before33[-n24 - n26 - n27:-n27]:
        assert m["moves"] == "p50_ms"
        assert m["workloads"] == PACED
    for m in before33[-n27:]:
        assert m["moves"] == "p50_ms" and m["workloads"] == PACED[1:]
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for n in PR26[:-1]:
        m = by_name[n]
        assert (m["unit"], m["better"], m["source"]) == ("us", "lower", "program_span")
        assert m["layer"] == ("dispatcher" if n == "handoff_us.paced" else "engine (host)")
    m = by_name["readback_ready_share.paced"]
    assert (m["unit"], m["better"], m["source"], m["layer"]) == ("%", "higher", "program_counter", "engine (host)")
    with open(os.path.join(ROOT, "chipbench", "README-pr24.md")) as f:
        (block,) = re.findall(r"<!-- merge: BENCHMARK.json -->\n```json\n(.*?)```", f.read(), re.S)
    twins = json.loads(block)["per_layer"]
    assert [t["name"] for t in twins] == list(PR24)
    paced = {m["name"]: m for m in bench["per_layer"]}
    for t in twins:
        p = paced[t["name"] + ".paced"]
        assert (t["unit"], t["better"], t["source"], t["layer"]) == (p["unit"], p["better"], p["source"], p["layer"])
        assert t["moves"] == "decisions_per_s"
