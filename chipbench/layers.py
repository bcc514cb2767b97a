"""Per-layer metrics: a few generic readers, chosen by each metric's
own file `layer_metrics/<name>.json`.

A reader takes the run's observations (`obs`) and returns a number, or
None where it finds nothing to read — the harness then leaves the
metric out of the line.  `obs` holds:

  stats_a/stats_b    GET /stats.json before the traffic and at mid-window,
                     just before the profiler starts
  faults_a/faults_b  GET /debug/faults before the traffic and after it
  launches           the /debug/launches records stamped in the window's
                     first half; traced_launches: in the traced seconds
  trace              trace_reduce's output for the traced seconds
  harness            what run.py measured itself (client log, /proc,
                     compile-cache entries), by key
  device_kind        as the server reports it

Reader kinds (the `reader` object of a metric file):

  {"kind": "delta", "endpoint": "stats"|"faults", "paths": [[...], ...]}
        after - before of the numbers at those JSON paths, summed
  {"kind": "ratio", "endpoint": ..., "num": [paths], "den": [paths], "scale": 100}
        sum of deltas over sum of deltas
  {"kind": "level", "endpoint": ..., "num": [paths], "den": [paths], "scale": 100}
        a gauge, not a counter: sum over sum as they stand at mid-window
  {"kind": "launches", "field": f, "reduce": "mean"|"p50"|"p99"|"sum"}
  {"kind": "trace", "table": "ops"|"modules", "pattern": regex,
   "reduce": "mean_us"|"total_s"|"count"}
  {"kind": "idle_share"}                    100 * (1 - busy_s / window_s)
  {"kind": "roofline", "pattern": regex, "bytes": name}
        100 * least time / device time of the programs matching
        `pattern`; least time = steps in the trace x mean bytes a
        launch of those seconds needs (a function below, from shapes)
        over the peak of peaks.json.  Memory-bound: the step does no arithmetic to speak of.
  {"kind": "harness", "key": k}
  {"kind": "difference", "a": reader, "b": reader}
"""

from __future__ import annotations

import json
import os
import re

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
BUCKETS = (8, 16, 32, 64, 128, 256, 512, 1024, 2048, 4096)  # default TPU_BATCH_BUCKETS


def peak(device_kind: str, key: str) -> float:
    with open(os.path.join(HERE, "peaks.json")) as f:
        peaks = json.load(f)
    if device_kind not in peaks:
        raise KeyError(f"device kind {device_kind!r} is not in chipbench/peaks.json")
    return float(peaks[device_kind][key])


def fixed_window_step_bytes(lanes: int) -> int:
    """Bytes one serving step of `lanes` lanes has to move, from its
    shapes (models/fixed_window.py step_counters_unique_packed): the
    launch pads to its bucket B; int32[4, B] packed input in (16 B a
    lane), one uint32 counter read and one written per lane (8 B), the
    saturated uint16 readback out (2 B)."""
    bucket = next((b for b in BUCKETS if lanes <= b), BUCKETS[-1])
    return bucket * (16 + 4 + 4 + 2)


STEP_BYTES = {"fixed_window_step": fixed_window_step_bytes}


def _dig(doc, path):
    for key in path:
        if not isinstance(doc, dict) or key not in doc:
            return None
        doc = doc[key]
    return doc if isinstance(doc, (int, float)) else None


def _delta(obs, endpoint, path):
    a, b = _dig(obs.get(endpoint + "_a"), path), _dig(obs.get(endpoint + "_b"), path)
    return None if a is None or b is None else b - a


def read(reader: dict, obs: dict):
    kind = reader["kind"]
    if kind == "delta":
        deltas = [_delta(obs, reader["endpoint"], p) for p in reader["paths"]]
        return None if None in deltas else sum(deltas)
    if kind == "ratio":
        num = [_delta(obs, reader["endpoint"], p) for p in reader["num"]]
        den = [_delta(obs, reader["endpoint"], p) for p in reader["den"]]
        if None in num or None in den or sum(den) == 0:
            return None
        return reader.get("scale", 1) * sum(num) / sum(den)
    if kind == "level":
        doc = obs.get(reader["endpoint"] + "_b")
        num, den = [_dig(doc, p) for p in reader["num"]], [_dig(doc, p) for p in reader["den"]]
        if None in num or None in den or sum(den) == 0:
            return None
        return reader.get("scale", 1) * sum(num) / sum(den)
    if kind == "launches":
        values = [rec[reader["field"]] for rec in obs.get("launches") or ()]
        if not values:
            return None
        how = reader["reduce"]
        if how == "mean":
            return float(np.mean(values))
        if how == "sum":
            return float(np.sum(values))
        return float(np.percentile(values, {"p50": 50, "p99": 99}[how]))
    trace = obs.get("trace")
    if kind == "trace":
        if not trace:
            return None
        rows = [r for r in trace[reader["table"]] if re.search(reader["pattern"], r[0])]
        seconds, count = sum(r[1] for r in rows), sum(r[2] for r in rows)
        if not count:
            return None
        how = reader["reduce"]
        return {"mean_us": 1e6 * seconds / count, "total_s": seconds, "count": count}[how]
    if kind == "idle_share":
        if not trace or not trace["device_planes"]:
            return None
        return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
    if kind == "roofline":
        launches = obs.get("traced_launches") or ()
        if not trace or not launches:
            return None
        rows = [r for r in trace["modules"] if re.search(reader["pattern"], r[0])]
        seconds, steps = sum(r[1] for r in rows), sum(r[2] for r in rows)
        if seconds <= 0:
            return None
        # Bytes a step as launched in those seconds needs on average,
        # times the steps the device trace itself counts.
        step_bytes = STEP_BYTES[reader["bytes"]]
        need = steps * float(np.mean([step_bytes(rec["lanes"]) for rec in launches]))
        return 100.0 * (need / peak(obs["device_kind"], "hbm_bytes_per_s")) / seconds
    if kind == "harness":
        return obs["harness"].get(reader["key"])
    if kind == "difference":
        a, b = read(reader["a"], obs), read(reader["b"], obs)
        return None if a is None or b is None else a - b
    raise ValueError(f"unknown reader kind {kind!r}")
