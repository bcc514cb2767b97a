"""Counter-state checkpoint/restore.

The reference has no checkpointing: durable state is the counters in
Redis with TTL = window, and a restart just reconnects (SURVEY.md
section 5 "Checkpoint / resume").  The TPU engine keeps counters in
HBM, so a process restart would forgive every open window — this
module closes that gap: periodic atomic snapshots of (counter table,
slot table) per engine bank, restored on startup.

Restore correctness needs no window bookkeeping: cache keys embed
their window start, so restored keys whose window has passed simply
expire via the slot table's normal gc/expiry path, and a slot whose
key is gone is zeroed on reassignment (the batch `fresh` flag).  A
crash between snapshots forgives at most `interval_s` worth of hits —
the same failure envelope as Redis with async persistence.

Snapshots are taken on the dispatcher thread (the slot table owner)
via BatchDispatcher.run_on_thread, so they are consistent without a
global lock on the serving path.
"""

from __future__ import annotations

import json
import logging
import os
import threading
import time
from typing import Optional

import numpy as np

from ..observability import spans as _spans
from ..observability.spans import SPANS
from .slot_table import PackedEntries

logger = logging.getLogger("ratelimit.checkpoint")

FORMAT_VERSION = 1

# Restore-age guard: the longest fixed-window unit is a DAY, so no
# live counter can still be enforceable once a snapshot is older than
# that — restoring one would resurrect expired windows (and a stale
# handoff import could over-deny forever on stable-stem banks).
# Snapshots older than this are refused (skip-and-start-fresh).
MAX_RESTORE_AGE_S = 86400.0


def bank_roles(cache) -> list:
    """Topology names for each cache.engines() position: lanes by
    index/count, the per-second bank by name, algorithm banks by
    algorithm, plain banks otherwise.  The restore/handoff guard that
    keeps a topology change from feeding one bank's keys into a
    different-purpose engine (restore_engine; cluster/handoff.py uses
    the same names to route imported sections)."""
    engines = cache.engines()
    lanes = getattr(cache, "lanes", None)
    per_second = getattr(cache, "per_second_engine", None)
    algo_banks = getattr(cache, "algorithm_banks", None) or {}
    algo_by_id = {id(e): name for name, e in algo_banks.items()}
    roles = []
    for idx, e in enumerate(engines):
        if lanes is not None and idx < len(lanes) and e is lanes[idx]:
            roles.append(f"lane{idx}of{len(lanes)}")
        elif per_second is not None and e is per_second:
            roles.append("per_second")
        elif id(e) in algo_by_id:
            roles.append("algo_" + algo_by_id[id(e)])
        else:
            roles.append(f"bank{idx}")
    return roles


def snapshot_engine(engine, bank: int = -1) -> tuple:
    """Copy one bank's state: (state dict, packed entries).  The state
    dict is ``{"counts": ...}`` for fixed-window banks and one named
    row per kernel state array for algorithm banks (sliding-window's
    window/curr/prev, GCRA's tat_anchor/tat_cells — see
    models/registry.py state_rows); the entries are the slot table's
    four arrays (slot_table.PackedEntries).  This is the only part
    that needs exclusive access to the engine — a device read and an
    array copy, nothing per key, timed as ``rl.bg.snapshot.grab``:
    serialization and disk I/O happen afterwards on the caller's
    thread."""
    with SPANS.background(_spans.BG_SNAPSHOT_GRAB, bank):
        return engine.export_state(), engine.slot_table.export_packed()


def write_snapshot(
    path: str,
    num_slots: int,
    state,
    entries: PackedEntries,
    role: str = "",
    algorithm: str = "fixed_window",
) -> None:
    """Serialize + atomically write a snapshot (no pickle: keys are
    stored as concatenated utf-8 bytes + a length array — the packed
    entries as they are — so restore can run with allow_pickle=False
    on untrusted files).  `role` names
    the bank's position in the cache topology (e.g. "lane1of4",
    "per_second", "algo_gcra") so a topology change can't silently
    restore one bank's keys into a different-purpose engine whose
    slot count happens to match; `algorithm` likewise refuses to feed
    one kernel's state rows to a different kernel.  ``state`` may be
    a plain counts array (legacy callers) or the snapshot_engine
    dict."""
    if not isinstance(state, dict):
        state = {"counts": state}
    tmp = f"{path}.tmp.{os.getpid()}"
    meta = json.dumps(
        {
            "version": FORMAT_VERSION,
            "num_slots": num_slots,
            "role": role,
            "algorithm": algorithm,
            "state_rows": sorted(state),
            "saved_at": time.time(),
        }
    )
    arrays = {"state_" + name: arr for name, arr in state.items()}
    if list(state) == ["counts"]:
        # Fixed-window snapshots keep the historical layout so
        # pre-algorithm checkpoints and new ones are interchangeable.
        arrays = {"counts": state["counts"]}
    with SPANS.span(_spans.BG_CHECKPOINT_WRITE):
        with open(tmp, "wb") as f:
            np.savez_compressed(
                f,
                meta=np.frombuffer(meta.encode(), dtype=np.uint8),
                key_lens=entries.key_lens,
                key_blob=entries.key_blob,
                slots=entries.slots,
                expiries=entries.expiries,
                **arrays,
            )
        os.replace(tmp, path)


def save_engine(engine, path: str, role: str = "") -> None:
    """snapshot_engine + write_snapshot in one call (tests, shutdown).
    Callers on the serving path should copy under exclusivity and
    write outside it — see CheckpointManager.checkpoint."""
    state, entries = snapshot_engine(engine)
    write_snapshot(
        path, engine.model.num_slots, state, entries, role,
        getattr(engine, "algorithm", "fixed_window"),
    )


def restore_engine(
    engine,
    path: str,
    role: str = "",
    max_age_s: float = MAX_RESTORE_AGE_S,
    wall_now=time.time,
) -> bool:
    """Restore one engine bank from `path`; returns False (and leaves
    the engine fresh) if the snapshot is missing or incompatible.
    When both sides carry a bank `role`, a mismatch refuses the
    restore (logged skip-and-start-fresh, like the num_slots guard);
    snapshots from before roles existed restore as before.  A snapshot
    older than ``max_age_s`` (default: one day, the longest window
    unit) is refused — every counter in it has expired, and restoring
    would resurrect dead windows (0 disables the guard; ``wall_now``
    is the clock seam for tests)."""
    if not os.path.exists(path):
        return False
    try:
        with np.load(path, allow_pickle=False) as z:
            meta = json.loads(bytes(z["meta"]).decode())
            if meta.get("version") != FORMAT_VERSION:
                logger.warning("checkpoint %s: unknown version, skipping", path)
                return False
            age_s = wall_now() - meta.get("saved_at", 0)  # tpu-lint: disable=timing-discipline -- cross-restart age: wall stamps are all that survive a process boundary
            if max_age_s and age_s > max_age_s:
                logger.warning(
                    "checkpoint %s: snapshot is %.0fs old (> %.0fs, the "
                    "longest window unit) — refusing to resurrect "
                    "expired counters, starting fresh",
                    path,
                    age_s,
                    max_age_s,
                )
                return False
            saved_role = meta.get("role", "")
            if role and saved_role and saved_role != role:
                logger.warning(
                    "checkpoint %s: bank role %r != expected %r "
                    "(topology changed), skipping",
                    path,
                    saved_role,
                    role,
                )
                return False
            if meta.get("num_slots") != engine.model.num_slots:
                logger.warning(
                    "checkpoint %s: num_slots %s != engine %s, skipping",
                    path,
                    meta.get("num_slots"),
                    engine.model.num_slots,
                )
                return False
            saved_algo = meta.get("algorithm", "fixed_window")
            engine_algo = getattr(engine, "algorithm", "fixed_window")
            if saved_algo != engine_algo:
                logger.warning(
                    "checkpoint %s: algorithm %r != engine %r "
                    "(kernel state is not interchangeable), skipping",
                    path,
                    saved_algo,
                    engine_algo,
                )
                return False
            if "counts" in z.files:
                state = {"counts": z["counts"]}
            else:
                state = {
                    name[len("state_"):]: z[name]
                    for name in z.files
                    if name.startswith("state_")
                }
            rows = getattr(engine.model, "state_rows", None)
            if "counts" not in state and rows and set(state) != set(rows):
                logger.warning(
                    "checkpoint %s: state rows %s != the %s kernel's %s "
                    "(an older layout of its state), skipping",
                    path,
                    sorted(state),
                    engine_algo,
                    sorted(rows),
                )
                return False
            entries = PackedEntries(
                z["key_blob"].astype(np.uint8, copy=False),
                z["key_lens"].astype(np.int64, copy=False),
                z["slots"].astype(np.int64, copy=False),
                z["expiries"].astype(np.int64, copy=False),
            )
    except Exception as e:
        logger.warning("checkpoint %s unreadable (%s), starting fresh", path, e)
        return False

    engine.import_state({k: v.astype(np.uint32) for k, v in state.items()})
    engine.restore_slot_table(entries)
    logger.warning(
        "restored %d live keys from %s (saved %.0fs ago)",
        len(entries),
        path,
        time.time() - meta.get("saved_at", 0),  # tpu-lint: disable=timing-discipline -- cross-restart age: wall stamps are all that survive a process boundary
    )
    return True


class CheckpointManager:
    """Periodic background snapshots of a TpuRateLimitCache's banks."""

    def __init__(self, cache, directory: str, interval_s: float = 30.0):
        if interval_s <= 0:
            raise ValueError(
                f"checkpoint interval must be positive, got {interval_s} "
                "(leave TPU_CHECKPOINT_DIR empty to disable checkpointing)"
            )
        self.cache = cache
        self.directory = directory
        self.interval_s = interval_s
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        os.makedirs(directory, exist_ok=True)

    def _bank_path(self, idx: int) -> str:
        return os.path.join(self.directory, f"bank{idx}.npz")

    def _bank_roles(self) -> list:
        return bank_roles(self.cache)

    def restore(self) -> int:
        """Restore all banks; returns how many were restored."""
        restored = 0
        roles = self._bank_roles()
        for idx, engine in enumerate(self.cache.engines()):
            if restore_engine(engine, self._bank_path(idx), roles[idx]):
                restored += 1
        if restored and hasattr(self.cache, "on_restored"):
            # Backends with host-side decision state (write-behind's
            # view) rebuild it from the restored engine.
            self.cache.on_restored()
        return restored

    def checkpoint(self) -> None:
        """Snapshot all banks now.  Only the state COPY runs under
        engine exclusivity (dispatcher thread / inline lock); the
        expensive compression + disk write happen on this thread so
        serving stalls only for the memcpy, not the I/O.

        Quarantined banks (backends/fault_domain.py) have no live
        dispatcher to snapshot through; their HOST MIRROR — the state
        actually serving — is snapshotted instead, so a process
        restart during a quarantine episode still restores the
        mirror's counters.  Banks with no mirror (DEVICE_FAILURE_MODE
        allow/deny) keep their previous on-disk snapshot.  One broken
        bank must never starve the others of snapshots."""
        roles = self._bank_roles()
        fd = getattr(self.cache, "fault_domain", None)
        for idx, engine in enumerate(self.cache.engines()):
            # One bank, one piece of background work with a duration
            # (rl.bg.checkpoint; children grab / write).
            with SPANS.background(_spans.BG_CHECKPOINT, idx):
                self._checkpoint_bank(idx, engine, roles[idx], fd)

    def _checkpoint_bank(self, idx: int, engine, role: str, fd) -> None:
        if fd is not None and fd.is_quarantined(idx):
            snap = fd.mirror_snapshot(idx)
            if snap is None:
                return  # no mirror: the last snapshot stands
            state, entries = snap
        else:
            grabbed = {}

            def grab():
                grabbed["state"], grabbed["entries"] = snapshot_engine(engine, idx)

            try:
                with SPANS.span(_spans.BG_CHECKPOINT_GRAB, idx):
                    self.cache.run_exclusive(engine, grab)
            except Exception:
                # The bank faulted between the quarantine check and
                # the snapshot token (dead dispatcher): skip it this
                # round; the fault domain's mirror covers the next.
                logger.exception("bank %d snapshot skipped", idx)
                return
            state, entries = grabbed["state"], grabbed["entries"]
        write_snapshot(
            self._bank_path(idx),
            engine.model.num_slots,
            state,
            entries,
            role,
            getattr(engine, "algorithm", "fixed_window"),
        )

    def start(self) -> None:
        if self._thread is not None:
            return
        self._thread = threading.Thread(
            target=self._loop, name="checkpointer", daemon=True
        )
        self._thread.start()

    def stop(self, final_checkpoint: bool = True) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=30)
            self._thread = None
        if final_checkpoint:
            try:
                self.checkpoint()
            except Exception:
                logger.exception("final checkpoint failed")

    def _loop(self) -> None:
        while not self._stop.wait(self.interval_s):
            try:
                self.checkpoint()
            except Exception:
                logger.exception("periodic checkpoint failed")
