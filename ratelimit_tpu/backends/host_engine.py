"""Host-side mirror counter engine: the device path's fallback.

When a bank's device path faults (hung kernel launch, device-step
exception, device-lost — backends/fault_domain.py), its lanes re-route
here: a pure-numpy engine that evaluates the SAME algorithm semantics
as the device kernels.  The reference service treats backend failure
as a first-class, configurable outcome (envoyproxy/ratelimit's Redis
failure modes); ``DEVICE_FAILURE_MODE=host`` is the richest of ours —
instead of a blanket allow/deny, the quarantined bank keeps *counting*
on the host until the supervisor warm-restarts the device bank and
imports the mirror's counters back (export_keys/import_keys, the same
protocol the cluster handoff uses).

The numpy evaluators are the models' own oracles promoted to a serving
surface: fixed-window uses the saturating-counter replay bench.py
verifies digests against, sliding-window and GCRA call the models'
``reference_step`` (bit-exact twins of the device kernels — the same
f32 ops in the same order).  Decisions then ride the exact host
reconstruction the device path uses (engine._decide_host /
engine.decide_generic), so a fallback decision differs from the
device's only by whatever hits the device lost when it faulted.

``StaticFallbackEngine`` is the allow/deny half of the knob: it
synthesizes fixed-code decisions with ZERO stat deltas (no rule
counters move for traffic the backend never evaluated) and never
touches state.

Throughput envelope: one RPC's lanes per call under the bank's
fallback lock — numpy serves ~100k lanes/s/core, plenty for a
degraded bank while the supervisor restarts it.
"""

from __future__ import annotations

from typing import List, Optional, Tuple

import numpy as np

from ..api import Code
from ..models.registry import get_algorithm
from .slot_table import PackedEntries, SlotTable

_OK = int(Code.OK)
_OVER = int(Code.OVER_LIMIT)
_U32_MAX = np.uint64(0xFFFFFFFF)


def host_fixed_window_step(
    counts: np.ndarray,
    slots: np.ndarray,
    totals: np.ndarray,
    fresh: np.ndarray,
) -> np.ndarray:
    """The fixed-window counter update over UNIQUE slots, on numpy:
    zero fresh slots, saturating add (the device counter clamps at u32
    max instead of wrapping — models/fixed_window.py update_unique),
    return per-group afters.  This is the replay formula bench.py
    verifies the device digests against, promoted to a serving
    surface.  Mutates ``counts`` in place."""
    before = np.where(fresh, np.uint32(0), counts[slots]).astype(np.uint64)
    after = np.minimum(before + totals.astype(np.uint64), _U32_MAX).astype(
        np.uint32
    )
    counts[slots] = after
    return after


class HostEngine:
    """Numpy twin of :class:`~.engine.CounterEngine` for one bank.

    Implements the engine surface the dispatcher/cache touch —
    ``submit_packed``/``step_complete`` (synchronous: the "token" is
    the finished decisions), the slot table, gc, and the checkpoint/
    handoff protocol (export/import state and keys) — so a quarantined
    bank's WorkItems run through :func:`~.dispatcher.run_items`
    unchanged and the supervisor can stream its counters back into a
    restarted device engine.
    """

    def __init__(
        self,
        num_slots: int,
        near_ratio: float = 0.8,
        algorithm: str = "fixed_window",
        max_batch: int = 4096,
    ):
        spec = get_algorithm(algorithm)
        self.spec = spec
        # The model instance carries metadata + the numpy halves
        # (reference_step, lane_counts); no device arrays are created
        # (init_state is never called here).
        self.model = spec.make_model(num_slots, near_ratio)
        self._generic = hasattr(self.model, "lane_counts")
        self.slot_table = SlotTable(
            num_slots, refresh_expiry=not spec.windowed_keys
        )
        self.state = np.zeros((len(spec.state_rows), num_slots), np.uint32)
        self.max_batch = int(max_batch)
        self.buckets = (self.max_batch,)
        self.stat_live_keys = 0
        self.stat_evictions = 0
        self.stat_window_rollovers = 0
        self.stat_decisions = 0

    @property
    def algorithm(self) -> str:
        return self.spec.name

    # -- serving surface (dispatcher.run_items protocol) ----------------

    def submit_packed(self, now: int, key_blob, meta: np.ndarray):
        """Mirror of CounterEngine.submit_packed, evaluated eagerly:
        assign slots, dedup same-key lanes, run the numpy step, rebuild
        per-lane decisions.  Returns the finished HostDecisions as the
        token (step_complete is the identity)."""
        from .engine import (
            HostDecisions,
            _decide_host,
            _decode_keys,
            _dedup_chunk,
            decide_generic,
        )

        n = len(meta)
        key_lens = meta["len"].astype(np.int64)
        expiries = np.ascontiguousarray(meta["expiry"])
        hits = np.ascontiguousarray(meta["hits"])
        limits = np.ascontiguousarray(meta["limits"])
        shadow = meta["shadow"].astype(bool)
        dividers = (
            np.ascontiguousarray(meta["divider"]) if self._generic else None
        )
        keys = _decode_keys(key_blob, key_lens)
        slots64, fresh = self.slot_table.assign_batch(keys, now, expiries)
        slots = slots64.astype(np.int32)
        outs: List = []
        for start in range(0, n, self.max_batch):
            count = min(n - start, self.max_batch)
            end = start + count
            dedup = _dedup_chunk(
                slots[start:end],
                hits[start:end],
                limits[start:end],
                fresh[start:end],
                None if dividers is None else dividers[start:end],
            )
            self.stat_window_rollovers += int(np.count_nonzero(dedup.fresh))  # tpu-lint: disable=shared-state -- mirror has one toucher (the bank's fallback lock)
            if self._generic:
                divider_g = (
                    dedup.divider_max
                    if dedup.divider_max is not None
                    else np.ones(len(dedup.uniq_slots), np.uint32)
                )
                out = self.model.reference_step(
                    self.state,
                    dedup.uniq_slots.astype(np.int64),
                    dedup.totals_u32(),
                    dedup.limit_max,
                    dedup.fresh,
                    divider_g,
                    now,
                )
                fetched = (
                    np.stack(out) if isinstance(out, tuple) else np.asarray(out)
                )
                outs.append(
                    decide_generic(
                        self.model,
                        fetched,
                        hits[start:end],
                        limits[start:end],
                        shadow[start:end],
                        dedup,
                        now,
                    )
                )
            else:
                afters_g = host_fixed_window_step(
                    self.state[0],
                    dedup.uniq_slots,
                    dedup.totals_u32(),
                    dedup.fresh,
                )
                outs.append(
                    _decide_host(
                        afters_g,
                        hits[start:end],
                        limits[start:end],
                        shadow[start:end],
                        self.model.near_ratio,
                        dedup,
                    )
                )
        self.stat_live_keys = len(self.slot_table)
        self.stat_evictions = self.slot_table.evictions  # tpu-lint: disable=shared-state -- mirror has one toucher (the bank's fallback lock)
        self.stat_decisions += n  # tpu-lint: disable=shared-state -- mirror has one toucher (the bank's fallback lock)
        if len(outs) == 1:
            return outs[0]
        if not outs:
            empty = np.zeros(0, dtype=np.int32)
            return HostDecisions(*([empty] * 8), empty.astype(bool))
        return HostDecisions(
            *(
                np.concatenate([getattr(o, f) for o in outs])
                for f in HostDecisions.__dataclass_fields__
            )
        )

    def step_complete(self, token):
        """The token IS the decisions (the numpy step is synchronous)."""
        return token

    def gc(self, now: int) -> int:
        freed = self.slot_table.gc(now)
        self.stat_live_keys = len(self.slot_table)
        return freed

    # -- checkpoint / handoff surface -----------------------------------

    def export_state(self) -> dict:
        rows = self.spec.state_rows
        return {name: self.state[i].copy() for i, name in enumerate(rows)}

    def import_state(self, state: dict) -> None:
        ns = self.model.num_slots
        bad_row = bad_size = None
        for i, name in enumerate(self.spec.state_rows):
            arr = np.asarray(state[name], dtype=np.uint32).reshape(-1)
            if arr.shape[0] != ns:
                bad_row, bad_size = name, arr.shape[0]
                break
            self.state[i] = arr
        if bad_row is not None:
            # Formatted OUTSIDE the loop (hot-path-cost): the message
            # builds once on the cold error leg, never per row.
            raise ValueError(
                f"state row {bad_row!r} size {bad_size} != num_slots {ns}"
            )

    def import_snapshot(self, state: dict, entries: PackedEntries) -> int:
        """Seed the mirror from a bank's last pre-fault snapshot
        (backends/checkpoint.py snapshot_engine shape): state rows +
        the live entries, packed.  The quarantined bank then
        continues counting from where the device was at the snapshot —
        restart loss is bounded by the snapshot interval."""
        self.import_state({k: np.asarray(v) for k, v in state.items()})
        self.restore_slot_table(entries)
        self.stat_live_keys = len(self.slot_table)
        return len(entries)

    # Live key-range export/import and the slot table's rebuild from
    # packed entries: identical semantics to the device engine's
    # (merge-on-collision, drop-expired; the table keeps its kind and
    # lease policy) — reuse its implementation, which only touches
    # export_state/import_state and the slot table (all provided above).
    from .engine import CounterEngine as _CE

    restore_slot_table = _CE.restore_slot_table
    export_keys = _CE.export_keys
    import_keys = _CE.import_keys
    del _CE


class StaticFallbackEngine:
    """DEVICE_FAILURE_MODE allow|deny synthesizer: answers every lane
    with a fixed code, zero stat deltas (rule counters must not move
    for traffic the backend never evaluated), and no state.  Shadow
    rules never enforce: a deny answers them OK, like every other
    path."""

    def __init__(self, allow: bool):
        self.allow = bool(allow)
        self.stat_decisions = 0

    def submit_packed(self, now: int, key_blob, meta: np.ndarray):
        from .engine import HostDecisions

        n = len(meta)
        z = np.zeros(n, dtype=np.int64)
        zb = np.zeros(n, dtype=bool)
        limits = meta["limits"].astype(np.int64)
        if self.allow:
            codes = np.full(n, _OK, dtype=np.int32)
            remaining = limits
        else:
            shadow = meta["shadow"] != 0
            codes = np.where(shadow, _OK, _OVER).astype(np.int32)
            remaining = z
        self.stat_decisions += n  # tpu-lint: disable=shared-state -- GIL-atomic stats counter, scrape-only reader
        return HostDecisions(
            codes=codes,
            limit_remaining=remaining,
            befores=z,
            afters=z,
            over_limit=z,
            near_limit=z,
            within_limit=z,
            shadow_mode=z,
            set_local_cache=zb,
        )

    def step_complete(self, token):
        return token


#: Shared static synthesizers (stateless): the caller-deadline path
#: uses these even when no fault domain is built.
STATIC_ALLOW = StaticFallbackEngine(allow=True)
STATIC_DENY = StaticFallbackEngine(allow=False)
