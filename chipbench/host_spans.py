"""What the host was doing while the device sat idle.

    python -m chipbench.host_spans <file.xplane.pb | planes.json>     prints JSON
    python -m chipbench.host_spans <file.xplane.pb> --cut <from_s> <for_s>
    python -m chipbench.host_spans --selftest

The program writes its own spans (`rl.*`, ratelimit_tpu/observability/
spans.py) into the profiler's trace, on the line of the thread that does
the work, beside the device's `XLA Ops`.  This lays every nanosecond of
every idle gap of 100 us or more on the device to ONE name, in this
order:

  1. a collector's open work leaf: rl.launch.assign, .pack, .device_call,
     rl.gc, rl.call_token — else the rest of an open rl.launch
  2. a completer's: rl.complete.readback, .decide, .signal
  3. rl.collect.window (a collector waits out the batch window)
  4. an open rl.bg.* (snapshot, checkpoint, incident capture, a tick)
  5. `no_request`: every collector the trace saw is in rl.collect.idle
  6. `unattributed`

so the seconds sum to the gap seconds exactly.  The thread's role is in
the span's name; which thread is which collector is the line it is on.

Out: `gap_s`, `gaps`, `during` ([[name, seconds]], largest first),
`shares` (% of gap_s: no_request, host_launch, host_complete, window,
background, unattributed), `launches` and `launches_per_s` (rl.launch
spans over the span of the device's events), `clock` (the rl.clock.<ns>
marks: CLOCK_MONOTONIC minus trace time, so launch records and journal
events can be laid on the trace).  A trace with no device plane, or with
no rl.* span (a program older than the spans), gives `{}`.

`--cut` prints the planes as JSON, cut to the lines this file reads and
to a stretch of the trace: how testdata/ was recorded.
"""

from __future__ import annotations

import json
import os
import sys

from .trace_reduce import OPS_LINE, read_xplane, union_seconds

MIN_GAP_S = 100e-6
COLLECTOR_WORK = ("rl.launch.assign", "rl.launch.pack", "rl.launch.device_call", "rl.gc", "rl.call_token", "rl.launch")
COMPLETER_WORK = ("rl.complete.readback", "rl.complete.decide", "rl.complete.signal")
WINDOW, IDLE, CLOCK = "rl.collect.window", "rl.collect.idle", "rl.clock."
BACKGROUND = (
    "rl.bg.incident_capture", "rl.bg.snapshot", "rl.bg.checkpoint.grab", "rl.bg.checkpoint.serialize",
    "rl.bg.checkpoint.write", "rl.bg.checkpoint", "rl.bg.gc", "rl.bg.detector_tick", "rl.bg.tsdb_tick",
    "rl.bg.watchdog_tick",
)
ORDER = COLLECTOR_WORK + COMPLETER_WORK + (WINDOW,) + BACKGROUND
SHARE_OF = {
    **{n: "host_launch" for n in COLLECTOR_WORK}, **{n: "host_complete" for n in COMPLETER_WORK},
    **{n: "background" for n in BACKGROUND}, WINDOW: "window", "no_request": "no_request",
    "unattributed": "unattributed",
}
TESTDATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "testdata", "tpu_v5e_paced_light_40launches.planes.json")


def attribute(planes: list) -> dict:
    """`planes` as trace_reduce.read_xplane gives them."""
    devices = [p for p in planes if any(line["name"] == OPS_LINE for line in p["lines"])]
    host_lines = [
        line["events"] for p in planes if p not in devices for line in p["lines"]
        if any(name.startswith("rl.") for name, _, _ in line["events"])
    ]
    if not devices or not host_lines:
        return {}
    # Sweep over every boundary: +1 / -1 on the name that opens or closes there.
    marks = []
    gap_ns = n_gaps = 0
    t_min = t_max = None
    for plane in devices:
        for line in plane["lines"]:
            if line["name"] != OPS_LINE or not line["events"]:
                continue
            _, gaps = union_seconds((s, s + d) for _, s, d in line["events"])
            for start, seconds in gaps:
                if seconds >= MIN_GAP_S:
                    dur = round(seconds * 1e9)
                    marks += [(start, 1, "gap"), (start + dur, -1, "gap")]
                    gap_ns, n_gaps = gap_ns + dur, n_gaps + 1
            lo = min(s for _, s, _ in line["events"])
            hi = max(s + d for _, s, d in line["events"])
            t_min, t_max = min(lo, t_min or lo), max(hi, t_max or hi)
    collectors = launches = 0
    clock = []
    for events in host_lines:
        names = {name for name, _, _ in events}
        is_collector = any(n.startswith(("rl.collect.", "rl.launch")) for n in names)
        collectors += is_collector
        for name, start, dur in events:
            if name.startswith(CLOCK):
                clock.append(int(name[len(CLOCK):]) - start)
            elif name in ORDER or name == IDLE:
                marks += [(start, 1, name), (start + dur, -1, name)]
                launches += name == "rl.launch" and t_min <= start < t_max
    marks.sort(key=lambda m: m[0])
    active = dict.fromkeys(ORDER + (IDLE, "gap"), 0)
    during = dict.fromkeys(ORDER + ("no_request", "unattributed"), 0)
    prev = marks[0][0] if marks else 0
    for t, step, name in marks:
        if t > prev and active["gap"]:
            owner = next((n for n in ORDER if active[n]), None)
            if owner is None:
                owner = "no_request" if active[IDLE] >= collectors > 0 else "unattributed"
            during[owner] += t - prev
        prev = t
        active[name] += step
    shares = dict.fromkeys(("no_request", "host_launch", "host_complete", "window", "background", "unattributed"), 0.0)
    for name, ns in during.items():
        shares[SHARE_OF[name]] += 100.0 * ns / gap_ns if gap_ns else 0.0
    span_s = (t_max - t_min) / 1e9 if t_max else 0.0
    return {
        "gap_s": gap_ns / 1e9 / len(devices), "gaps": n_gaps,
        "during": sorted(([n, ns / 1e9 / len(devices)] for n, ns in during.items() if ns), key=lambda r: -r[1]),
        "shares": shares, "launches": launches, "launches_per_s": launches / span_s if span_s else 0.0,
        "collector_lines": collectors,
        "clock": {"marks": len(clock), "monotonic_minus_trace_ns": clock[0] if clock else None, "drift_ns": clock[-1] - clock[0] if clock else None},
    }


def cut(planes: list, from_s: float, for_s: float) -> list:
    """The lines `attribute` and trace_reduce read, `for_s` seconds of
    them from `from_s` after the first device event (the clock marks
    are kept wherever they lie)."""
    starts = [s for p in planes for line in p["lines"] if line["name"] == OPS_LINE for _, s, _ in line["events"]]
    lo = min(starts) + int(from_s * 1e9)
    hi = lo + int(for_s * 1e9)
    out = []
    for p in planes:
        lines = []
        for line in p["lines"]:
            device = line["name"].startswith("XLA ")
            events = [
                e for e in line["events"]
                if (device or e[0].startswith("rl.")) and (lo <= e[1] < hi or e[0].startswith(CLOCK))
            ]
            if events:
                lines.append({"name": line["name"], "events": events})
        if lines:
            out.append({"name": p["name"], "lines": lines})
    return out


def load(path: str) -> list:
    if path.endswith(".json"):
        with open(path) as f:
            return json.load(f)
    return read_xplane(path)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)
    print("ok  " + what)


def selftest_split() -> None:
    # A trace built by hand, in ns.  The device is busy [0, 100), idle for 1 ms, busy again; the
    # second gap, of 50 ns, is under the floor.
    ms = 1_000_000
    device = {"name": "/device:TPU:0", "lines": [{"name": OPS_LINE, "events": [
        ["%fusion = u32[8]{0} fusion()", 0, 100], ["%fusion = u32[8]{0} fusion()", 100 + ms, 100],
        ["%fusion = u32[8]{0} fusion()", 250 + ms, 100],
    ]}]}
    collector = [  # idle 0.1 ms, then a launch of 0.4 ms (assign 0.1, pack 0.05, device_call 0.15), then idle
        ["rl.collect.idle", 100, 100_000], ["rl.launch", 100_100, 400_000], ["rl.launch.assign", 110_100, 100_000],
        ["rl.launch.pack", 220_100, 50_000], ["rl.launch.device_call", 300_100, 150_000],
        ["rl.collect.idle", 500_100, 400_000],
    ]
    completer = [["rl.complete.readback", 250_100, 100_000], ["rl.complete.decide", 600_100, 50_000]]
    background = [["rl.bg.snapshot", 550_100, 200_000], ["rl.clock.5000000", 0, 10], ["rl.clock.5001000", 2 * ms, 10]]
    host = {"name": "/host:CPU", "lines": [{"name": "python", "events": ev} for ev in (collector, completer, background)]}
    got = attribute([device, host])
    want = {
        "no_request": 100_000 + 50_000 + 150_000,  # idle before the launch, after it, and after the snapshot
        "rl.launch.assign": 100_000, "rl.launch.pack": 50_000, "rl.launch.device_call": 150_000,
        "rl.launch": 10_000 + 10_000 + 30_000 + 50_000,  # what its leaves leave of the launch
        # rl.complete.readback lies wholly under the collector's launch: a collector's work goes first
        "rl.complete.decide": 50_000,  # over the snapshot: a completer's work goes before background
        "rl.bg.snapshot": 150_000,  # over idle: background goes before no_request
        "unattributed": 100_000,  # the last 0.1 ms: no span of any thread
    }
    check(got["gaps"] == 1 and got["gap_s"] == 0.001, "one gap of 1 ms; the 50 ns gap is under the 100 us floor")
    check({n: round(s * 1e9) for n, s in got["during"]} == want, "a hand-built trace with known overlaps gives the known split")
    check(abs(sum(s for _, s in got["during"]) - got["gap_s"]) < 1e-12, "the split sums to the gap seconds")
    check(abs(sum(got["shares"].values()) - 100.0) < 1e-9 and got["shares"]["host_launch"] == 40.0, "shares sum to 100%; host_launch 40%")
    check(got["clock"] == {"marks": 2, "monotonic_minus_trace_ns": 5_000_000, "drift_ns": 1000 - 2 * ms}, "the two clock marks give the offset to CLOCK_MONOTONIC")
    check(attribute([device]) == {} and attribute([host]) == {}, "no rl.* span, or no device plane: nothing, and no error")


def selftest_recorded() -> None:
    # The light trace recorded on the chip (PR 24), cut to 40 launches.
    planes = load(TESTDATA)
    got = attribute(planes)
    check(got["launches"] == 40 and got["collector_lines"] == 1, "recorded trace: 40 launches on one collector line")
    check(abs(sum(s for _, s in got["during"]) - got["gap_s"]) < 1e-9, "recorded trace: attributed seconds sum to the gap seconds")
    from .trace_reduce import reduce_events
    reduced = reduce_events(planes)
    floor_gaps = sum(s for name, s in reduced["idle_gaps"] if name != "idle_gaps_under_100us")
    check(abs(floor_gaps - got["gap_s"]) < 1e-9, "recorded trace: the gap seconds are trace_reduce's idle gaps of 100 us and over")
    check(got["shares"]["unattributed"] <= 10.0, f"recorded trace: unattributed {got['shares']['unattributed']:.2f}% of the gap seconds, at most 10%")


def main(argv) -> int:
    if argv[1:] == ["--selftest"]:
        selftest_split()
        selftest_recorded()
        print("host_spans selftest passed")
        return 0
    planes = load(argv[1])
    if argv[2:3] == ["--cut"]:
        print(json.dumps(cut(planes, float(argv[3]), float(argv[4])), separators=(",", ":")))
    else:
        print(json.dumps(attribute(planes)))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
