"""Descriptor-resolution cache: one dict hit from proto entries to
packed lanes.

The per-request Python pipeline — ``get_limit`` trie walk, key-stem
assembly, utf-8 encode, crc32 lane hash, per-lane ``LANE_DTYPE``
record construction — is window-independent for everything except the
window suffix and the hits addend.  A ``ResolutionCache`` memoizes it
in two tiers, so that what is kept per key is only what is the key's
own:

- **per rule, once** (:class:`ResolvedRule`, one per rule and config
  generation): the matched :class:`RateLimitRule` (or None /
  unlimited) with its stats handles, unit, divider, per-second flag,
  algorithm routing — and the **window memo**: for the current window
  the ASCII suffix ``str(window_start)`` and the lane record's bytes
  with expiry, ``hits = 1``, limit, shadow, divider and algo stamped,
  built with numpy once per (rule, window) and cut in two around the
  one per-key field, the key's byte length;
- **per key, flat** (:class:`ResolvedDescriptor`): the encoded utf-8
  key stem, its crc32 (lane route and flight-recorder hash), a
  reference to the rule's object, the hot-key handle, the second-
  chance bit.  A window's key bytes are ``stem_bytes + suffix``; its
  lane record is ``head + length + tail``, three byte strings joined
  per request.  One collector-tracked object a key (two with the
  sketch's handle), no numpy array, no per-window object: a full
  collection has a sixth of the parent's heap to walk, and a first-
  seen key allocates nothing that outlives the request but its entry.

The map's key is the flat string tuple ``(domain, k1, v1, k2, v2,
...)``: hashed and compared in C (a tuple of ``Entry`` dataclasses
runs a Python ``__hash__`` / ``__eq__`` per entry and probe), and —
holding only strings — dropped from the collector's lists at the first
collection it survives.

The reference memoizes only the cheap half of this (pooled
``bytes.Buffer`` key building, cache_key.go:17-29) and gets the rest
free from Go; here the full resolution is the measured host-path tax
(benchmarks/results/host_path.json) so the whole pipeline collapses
onto one dict hit.

Invalidation is the config **generation counter**: every
:class:`RateLimitConfig` carries a monotonically increasing
``generation`` (config/loader.py).  The table belongs to one
generation; the first miss under a newer one drops it whole (counted
in ``clears``) and starts the next, and a request still holding an
older config is answered uncached.  A FAILED reload keeps the old
config object AND its old generation (service/ratelimit.py keeps the
previous config on ConfigError), so the warm cache survives bad
pushes.  Request-supplied overrides (``descriptor.limit is not None``)
bypass the cache entirely.

Capacity is held by **second-chance eviction** (CLOCK): entries sit in
a ring in insertion order, a hit sets the entry's ``ref`` bit, and a
miss at capacity moves the hand past referenced entries (clearing
their bits) and replaces the first unreferenced one — amortized O(1),
counted in ``evictions``.  A scan of first-seen keys therefore evicts
its own tail and a hot set stays; nothing is cleared wholesale, so the
heap neither saws nor is freed in one storm.

Thread model: hits run concurrently on RPC handler threads with no
lock — ``(generation, entries, rules)`` is one tuple read in one
atomic load, a dict get is atomic under the GIL, the ``ref`` store is
idempotent, and the hit/miss tallies are plain ints whose rare lost
increments are an accepted stats-only race (the same trade the stem
cache makes).  A miss builds its entry unlocked and takes the cache's
lock only to insert (ring, hand and map move together); a racing
double-resolve returns the entry that got there first.  The window
memo is swapped whole, so concurrent readers see the old window's
state or the new one, never a mix.

This module is dependency-light on purpose: the lane record dtype is
injected by the backend (``lane_dtype=LANE_DTYPE``) so the limiter
layer never imports the device stack.
"""

from __future__ import annotations

import sys
import threading
from typing import Optional
from zlib import crc32

import numpy as np

from ..api import Descriptor, Unit
from ..models.registry import DEFAULT_ALGORITHM, get_algorithm
from ..utils.time import unit_to_divider
from .cache_key import build_stem

_MISSING_BANK_WARNED: set = set()


def _warn_missing_bank(algo: str) -> None:
    """One log line per (process, algorithm): a rule asked for an
    algorithm the backend has no engine bank for; it keeps limiting
    with the default kernel instead."""
    if algo in _MISSING_BANK_WARNED:
        return
    _MISSING_BANK_WARNED.add(algo)
    import logging

    logging.getLogger("ratelimit").warning(
        "rule requests algorithm %r but the backend has no bank for "
        "it; falling back to %s enforcement (enable the bank via "
        "TPU_ALGORITHM_BANKS)",
        algo,
        DEFAULT_ALGORITHM,
    )


def flat_key(domain: str, entries) -> tuple:
    """The map's key: ``(domain, k1, v1, k2, v2, ...)``.  One and two
    entries — nearly every descriptor — are spelled out; the serving
    loop (tpu_cache._prepare_resolved) inlines the same two shapes."""
    n = len(entries)
    if n == 1:
        a = entries[0]
        return (domain, a.key, a.value)
    if n == 2:
        a, b = entries
        return (domain, a.key, a.value, b.key, b.value)
    out = [domain]
    for e in entries:
        out.append(e.key)
        out.append(e.value)
    return tuple(out)


# The key-length field of a lane record, little-endian as LANE_DTYPE
# stores it, for every length a key is likely to have (``record``
# falls back to int.to_bytes past the table).
_LEN_FIELD_BYTES = 4
_LEN_LE = tuple(n.to_bytes(_LEN_FIELD_BYTES, "little") for n in range(1024))


def record(head: bytes, key_len: int, tail: bytes) -> bytes:
    """One lane record: a window's template with the key's byte length
    spliced in."""
    if key_len < 1024:
        return head + _LEN_LE[key_len] + tail
    return head + key_len.to_bytes(_LEN_FIELD_BYTES, "little") + tail


class Window:
    """Everything about one (rule, window) pair: the window's start,
    its ASCII key suffix, and the lane record with ``expiry`` pre-
    stamped to ``start + divider`` and ``hits`` to the common addend 1
    — cut into the bytes before (``head``) and after (``tail``) the
    key-length field, which is the key's.

    For rules running a non-default algorithm the window carries that
    bank's record too (``algo_head`` / ``algo_tail``): the rule's
    divider (the kernel's window / emission math needs it), the
    algorithm's id, and an expiry leasing the slot TWO windows past
    the current one — the algorithm banks' refresh-on-touch slot
    tables extend it while the key stays hot, so per-slot window / TAT
    state survives exactly as long as it matters.  An ENFORCING
    algorithm rule has only that record (its key is the bare stem: the
    kernels track windows per slot), a SHADOWING one has both.

    Immutable after construction; the owning rule swaps the whole
    object on window rollover."""

    __slots__ = ("start", "suffix", "head", "tail", "algo_head", "algo_tail")

    def __init__(self, start, suffix, head, tail, algo_head, algo_tail):
        self.start = start
        self.suffix = suffix
        self.head = head
        self.tail = tail
        self.algo_head = algo_head
        self.algo_tail = algo_tail


class ResolvedRule:
    """What a resolution owes to the rule alone, once per (rule,
    config generation): the rule, its routing flags, and the single-
    slot window memo (:class:`Window`)."""

    __slots__ = (
        "rule",
        "unlimited",
        "per_second",
        "unit",
        "divider",
        "algorithm",
        "algo_id",
        "algo_shadow",
        "win",
        "_cut",
    )

    def __init__(self, rule, cut, algorithms: frozenset):
        self.rule = rule
        self.unlimited = rule is not None and rule.unlimited
        # (record fields) -> (head, tail): the cache's, which owns the
        # lane dtype.
        self._cut = cut
        self.win: Optional[Window] = None
        if rule is not None and not rule.unlimited:
            self.unit = rule.limit.unit
            self.divider = unit_to_divider(self.unit)
            self.per_second = self.unit == Unit.SECOND
            # Algorithm-table routing (models/registry.py): resolved
            # once per rule so the serving loop reads plain attrs.
            # An algorithm the backend has NO bank for folds back to
            # the default — the rule keeps limiting (fixed-window)
            # instead of erroring every request it matches.
            algo = getattr(rule, "algorithm", DEFAULT_ALGORITHM)
            if algo != DEFAULT_ALGORITHM and algo not in algorithms:
                _warn_missing_bank(algo)
                algo = DEFAULT_ALGORITHM
            self.algorithm = algo
            self.algo_id = (
                0
                if algo == DEFAULT_ALGORITHM
                else get_algorithm(algo).algo_id
            )
            self.algo_shadow = self.algo_id != 0 and bool(
                getattr(rule, "algo_shadow", False)
            )
        else:
            self.unit = None
            self.divider = 0
            self.per_second = False
            self.algorithm = DEFAULT_ALGORITHM
            self.algo_id = 0
            self.algo_shadow = False

    def window(self, now: int) -> Window:
        """The memoized window state, rebuilt once per rollover.  With
        it a key is byte-identical to CacheKeyGenerator's: ``stem +
        str(window_start)`` for fixed-window rules (and the fixed-
        window primary of a rule SHADOWING an algorithm), the bare
        stem for rules ENFORCING one."""
        # Inline window_start(now, unit): the divider is resolved once
        # at construction, so the hot path skips the per-call Unit
        # coercion + divider lookup (measured ~1.5us/descriptor).
        divider = self.divider
        w = now - now % divider
        win = self.win
        if win is not None and win.start == w:
            return win
        rule = self.rule
        limit = rule.limit.requests_per_unit
        shadow = 1 if rule.shadow_mode else 0
        head = tail = algo_head = algo_tail = b""
        if self.algo_id:
            algo_head, algo_tail = self._cut(
                (
                    w + 2 * divider,  # expiry lease (refreshed on touch)
                    1,  # hits pre-stamped to the common addend
                    limit,
                    0,  # len: the key's
                    shadow,
                    divider,
                    self.algo_id,
                )
            )
        if not self.algo_id or self.algo_shadow:
            head, tail = self._cut(
                (
                    w + divider,  # expiry base (jitter stamped later)
                    1,  # hits pre-stamped to the common addend; the packer
                    #    only overwrites when the request carries hits != 1
                    limit,
                    0,  # len: the key's
                    shadow,
                    0,  # divider: fixed-window kernels never read it
                    0,  # algo: fixed_window
                )
            )
        win = Window(
            w, str(w).encode("ascii"), head, tail, algo_head, algo_tail
        )
        self.win = win  # tpu-lint: disable=shared-state -- whole-object swap: readers see the old or the new Window, never a mix (class docstring)
        return win


class ResolvedDescriptor:
    """One interned (domain, entries) resolution: what is the key's
    own, and a reference to its rule's :class:`ResolvedRule`."""

    __slots__ = ("key", "rs", "stem_bytes", "stem_hash", "hot", "ref")

    def __init__(self, key: tuple, rs: ResolvedRule, stem_bytes: bytes):
        self.key = key  # the map's key, for the eviction
        self.rs = rs
        self.stem_bytes = stem_bytes
        # One crc32 per resolution (cold path): the lane route and the
        # flight recorder's key-stem hash share it, so ring records
        # and lane hashing agree by construction.
        self.stem_hash = crc32(stem_bytes)
        # Hot-key sketch handle (observability/hotkeys.py), pinned by
        # the serving loop on first observation so the per-request
        # cost is one counter bump — None until tracked, and the
        # handle itself goes dead (key=None) on sketch eviction.
        self.hot = None
        # Second chance: set by every hit, cleared by the hand.
        self.ref = False

    @property
    def stem(self) -> str:
        """The key stem as text — for the consumers that key by it
        (hot-key sketch registration, promotion cache, the local
        over-limit cache's key); the serving path joins bytes."""
        return self.stem_bytes.decode("utf-8")

    def lane(self, n_lanes: int) -> int:
        """The host lane of this key among ``n_lanes``: crc32 of the
        utf-8 STEM, so a key keeps its lane across windows and the
        cached and uncached paths agree.  A changed lane count re-
        routes with the same amnesia envelope as a restart with a
        changed TPU_NUM_LANES."""
        return self.stem_hash % n_lanes if n_lanes > 1 else 0


_NO_GENERATION = -1


class ResolutionCache:
    """Per-service map from the flat ``(domain, k1, v1, ...)`` key to a
    :class:`ResolvedDescriptor`.  See module docstring for the
    invalidation, eviction and threading contract."""

    def __init__(
        self,
        lane_dtype,
        prefix: str = "",
        capacity: int = 1 << 16,
        algorithms: frozenset = frozenset(),
    ):
        self.prefix = prefix
        self.lane_dtype = lane_dtype
        len_type, self._len_at = lane_dtype.fields["len"][:2]
        if len_type.str not in ("<u4", "=u4") or sys.byteorder != "little":
            raise ValueError(
                "lane record's `len` must be a little-endian u4, "
                f"not {len_type.str!r}"
            )
        self.capacity = max(1, int(capacity))
        # Non-default algorithms the owning backend has banks for;
        # rules asking for anything else fold to the default kernel
        # (see ResolvedRule).
        self.algorithms = frozenset(algorithms)
        # (generation, entries, rules by id(rule)): one object, so a
        # lock-free reader never pairs one generation's number with
        # another's table.
        self._live: tuple = (_NO_GENERATION, {}, {})
        # Second-chance ring: the entries in insertion order, and the
        # hand.  Moved only under the lock.
        self._ring: list = []
        self._hand = 0
        self._lock = threading.Lock()
        # Stats-only tallies; benign GIL races accepted (see module
        # docstring).  Exported as counters via register_stats on the
        # owning backend.
        self.hits = 0
        self.misses = 0
        self.clears = 0
        self.evictions = 0

    def __len__(self) -> int:
        return len(self._live[1])

    def _cut(self, fields: tuple) -> tuple:
        """(head, tail) of the lane record holding ``fields`` (its
        ``len`` left 0): numpy builds it, once per (rule, window)."""
        arr = np.empty(1, dtype=self.lane_dtype)
        arr[0] = fields
        raw = arr.tobytes()
        at = self._len_at
        return raw[:at], raw[at + _LEN_FIELD_BYTES:]

    def resolve(self, config, domain: str, descriptor: Descriptor):
        """One dict hit on the hot path.  Returns None for
        request-supplied overrides (the caller falls back to the
        uncached ``get_limit`` + key-generator path); otherwise a
        :class:`ResolvedDescriptor` valid for ``config.generation``."""
        if descriptor.limit is not None:
            return None
        key = flat_key(domain, descriptor.entries)
        generation, entries, _ = self._live
        if generation == config.generation:
            e = entries.get(key)
            if e is not None:
                e.ref = True  # tpu-lint: disable=shared-state -- idempotent second-chance bit
                self.hits += 1
                return e
        return self.miss(config, domain, descriptor, key)

    def miss(self, config, domain: str, descriptor: Descriptor, key: tuple):
        """Resolve ``descriptor`` afresh and keep it — unless ``config``
        is older than the table's (a request that began before a
        reload): that one is answered and nothing kept."""
        self.misses += 1
        live = self._live
        if live[0] != config.generation:
            live = self._retarget(config.generation)
        rules = live[2] if live is not None else {}
        rule = config.get_limit(domain, descriptor)
        rs = rules.get(id(rule))
        if rs is None:
            # id(rule) stays the rule's: the ResolvedRule holds it.
            rs = rules.setdefault(
                id(rule),
                ResolvedRule(rule, self._cut, self.algorithms),
            )
        e = ResolvedDescriptor(
            key,
            rs,
            build_stem(self.prefix, domain, descriptor.entries).encode("utf-8"),
        )
        if live is None:
            return e
        with self._lock:
            if self._live is not live:
                return e  # a reload landed meanwhile: answered, not kept
            entries = live[1]
            other = entries.get(key)
            if other is not None:
                return other  # a racing miss of the same key got there first
            ring = self._ring
            capacity = self.capacity
            if len(ring) < capacity:
                ring.append(e)
            else:
                hand = self._hand
                victim = ring[hand]
                while victim.ref:
                    victim.ref = False
                    hand = hand + 1 if hand + 1 < capacity else 0
                    victim = ring[hand]
                del entries[victim.key]
                ring[hand] = e
                self._hand = hand + 1 if hand + 1 < capacity else 0
                self.evictions += 1
            entries[key] = e
        return e

    def _retarget(self, generation: int, force: bool = False) -> Optional[tuple]:
        """The live table for ``generation``: a fresh one if it is
        newer than the table's (or ``force``), None if it is older.
        A new dict, not ``clear()``: a reader that took the old table
        keeps probing the old generation's entries, which is what it
        asked for."""
        with self._lock:
            live = self._live
            if force or generation > live[0]:
                if live[1]:
                    self.clears += 1
                self._ring = []
                self._hand = 0
                self._live = live = (generation, {}, {})
            return live if live[0] == generation else None

    def clear(self) -> None:
        self._retarget(self._live[0], force=True)
