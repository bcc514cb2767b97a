"""The profiler's trace (`*.xplane.pb`, as `jax.profiler` wrote it in the
server process) -> what the device did in the traced window.

    python -m chipbench.trace_reduce <file.xplane.pb> [seconds asked]    prints JSON

Run as a child of run.py once the server has exited (reading the file
needs jaxlib's ProfileData; the harness itself never imports JAX).

Out: `window_s` (the seconds the trace was asked for, or the span of
the device's own events where that is longer — the host threads' events
run on for seconds while the profiler stops, the device's do not),
`busy_s` (union of the intervals in which an operation ran on a
device, averaged over device planes), per-operation and per-program
device time, and the idle gaps between operations bucketed by length.
`reduce_events` is the arithmetic alone, on plain lists: the selftest
drives it on a recorded trace cut to a few hundred events.
"""

from __future__ import annotations

import json
import re
import sys

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
GAP_BUCKETS = (
    ("idle_gaps_under_100us", 0.0, 100e-6),
    ("idle_gaps_100us_to_1ms", 100e-6, 1e-3),
    ("idle_gaps_1ms_to_10ms", 1e-3, 10e-3),
    ("idle_gaps_over_10ms", 10e-3, float("inf")),
)
_HLO = re.compile(r"^%?([\w.\-]+) = \(?(\w+)\[([\d,]*)\]")


def op_label(name: str) -> str:
    """A device event's name as a short stable label.  The TPU plane
    names an op by its HLO text (`%fusion.1 = u32[1048576]{...}
    fusion(...)`): keep op, dtype and shape — `fusion.1_u32_1048576`."""
    m = _HLO.match(name)
    if m:
        dims = m.group(3).replace(",", "x") or "scalar"
        return f"{m.group(1)}_{m.group(2)}_{dims}"
    return re.sub(r"[^\w.\-]+", "_", name)[:80]


def module_label(name: str) -> str:
    """`jit_step(123456789)` -> `jit_step`: the fingerprint changes
    with the compiler, the name does not."""
    return re.sub(r"\(\d+\)$", "", name)


def union_seconds(intervals) -> tuple:
    """(busy seconds, [(gap start, gap seconds)]) of [(start_ns, end_ns)]."""
    busy = 0
    gaps = []
    cur_s = cur_e = None
    for s, e in sorted(intervals):
        if cur_e is None:
            cur_s, cur_e = s, e
        elif s <= cur_e:
            cur_e = max(cur_e, e)
        else:
            busy += cur_e - cur_s
            gaps.append((cur_e, (s - cur_e) / 1e9))
            cur_s, cur_e = s, e
    if cur_e is not None:
        busy += cur_e - cur_s
    return busy / 1e9, gaps


def reduce_events(planes: list, asked_s: float = 0.0) -> dict:
    """`planes`: [{"name", "lines": [{"name", "events": [[name, start_ns,
    dur_ns], ...]}]}].  Device planes are those with an XLA Ops line."""
    devices = [
        p for p in planes if any(line["name"] == OPS_LINE for line in p["lines"])
    ]
    spans = [
        (start, start + dur)
        for p in devices for line in p["lines"] for _, start, dur in line["events"]
    ]
    t_min = min((s for s, _ in spans), default=0)
    t_max = max((e for _, e in spans), default=0)
    ops: dict = {}
    modules: dict = {}
    busy_total = 0.0
    gap_s = {name: 0.0 for name, _, _ in GAP_BUCKETS}
    for plane in devices:
        for line in plane["lines"]:
            if line["name"] == OPS_LINE:
                busy, gaps = union_seconds((s, s + d) for _, s, d in line["events"])
                busy_total += busy
                for _, g in gaps:
                    for name, lo, hi in GAP_BUCKETS:
                        if lo <= g < hi:
                            gap_s[name] += g
                for name, _, dur in line["events"]:
                    acc = ops.setdefault(op_label(name), [0.0, 0])
                    acc[0] += dur / 1e9
                    acc[1] += 1
            elif line["name"] == MODULES_LINE:
                for name, _, dur in line["events"]:
                    acc = modules.setdefault(module_label(name), [0.0, 0])
                    acc[0] += dur / 1e9
                    acc[1] += 1
    n = max(1, len(devices))

    def ranked(table):
        return sorted(([k, v[0] / n, v[1]] for k, v in table.items()), key=lambda r: -r[1])

    return {
        "device_planes": len(devices),
        "window_s": max(float(asked_s), (t_max - t_min) / 1e9),
        "busy_s": busy_total / n,
        "ops": ranked(ops),
        "modules": ranked(modules),
        "idle_gaps": sorted(([k, v / n] for k, v in gap_s.items()), key=lambda r: -r[1]),
    }


def read_xplane(path: str) -> list:
    try:
        from jaxlib._profile_data import ProfileData
    except ImportError:
        from jax.profiler import ProfileData
    planes = []
    for plane in ProfileData.from_file(path).planes:
        lines = []
        for line in plane.lines:
            events = [[e.name, int(e.start_ns), int(e.duration_ns)] for e in line.events]
            lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return planes


def main(argv) -> int:
    asked_s = float(argv[2]) if len(argv) > 2 else 0.0
    print(json.dumps(reduce_events(read_xplane(argv[1]), asked_s)))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
