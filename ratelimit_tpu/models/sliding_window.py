"""Sliding-window rate limiting as a batched device kernel.

The two-window interpolation used by CDN-scale limiters: each slot
holds the request count of its CURRENT window and its PREVIOUS window,
and admission weighs the previous count by the un-elapsed fraction of
the current window:

    effective(now) = floor(prev * (divider - (now - w)) / divider) + curr

where ``w = now - now % divider`` is the current window start — in
exact integer arithmetic (``ops.floor_muldiv``): a TPU's f32 divide is
not correctly rounded, and one ulp across the floor() is one admission
more or fewer than the host oracle and the fault domain's host mirror
would grant (measured on a v5e, PR 21).  The
estimate assumes the previous window's traffic was uniform; its error
is bounded by one window's worth of skew, and — unlike fixed windows —
it can never admit 2x the configured rate across a boundary (the decay
term hands the new window a non-zero starting count).

Slot-state contract (the reason this kernel's keys differ from
fixed-window's): the cache key is the STABLE STEM, not stem+window —
the kernel tracks window rollover itself in per-slot state, so a slot
must survive rollovers.  Per-slot state is three uint32 rows:

    row 0: window_start   unix seconds of the slot's current window
    row 1: curr           count in the current window (saturating u32)
    row 2: prev           count in the previous window

On each batch the kernel ages state lazily per lane: same window ->
accumulate; adjacent window -> prev=curr, curr=0; older -> both zero
(idle keys decay to empty without any sweep).  ``fresh`` lanes (newly
assigned slots) zero all three rows first — identical to fixed-window
lazy expiry.

Serving protocol (backends/engine.py generic path): ``packed`` is ONE
int32[5, N] host->device transfer — rows (slots, hits-bits,
limits-bits, fresh, divider-bits) — plus the batch clock ``now``; the
kernel returns uint32[2, N]: per-slot (weighted-prev, curr-after).
The host rebuilds per-lane pipeline-order befores/afters from the
dedup prefixes and runs the SAME threshold state machine as
fixed-window (limiter.base.decide_batch), so near-limit attribution,
partial-hit semantics and shadow_mode all carry over unchanged.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.muldiv import floor_muldiv
from .registry import ALGO_SLIDING_WINDOW


class SlidingWindowModel:
    """Configuration + jittable step for the two-window table."""

    algo = ALGO_SLIDING_WINDOW
    #: Stable-stem keys: slots survive window rollovers (see module
    #: docstring); the owning engine uses refresh-on-touch expiry.
    windowed_keys = False
    state_rows = ("window_start", "curr", "prev")

    def __init__(self, num_slots: int, near_ratio: float = 0.8):
        self.num_slots = int(num_slots)
        self.near_ratio = float(near_ratio)

    def init_state(self) -> jax.Array:
        """Fresh state: all slots empty in window 0."""
        return jnp.zeros((3, self.num_slots), dtype=jnp.uint32)

    @functools.partial(jax.jit, static_argnums=0, donate_argnums=1)
    def step_serve_packed(
        self, state: jax.Array, packed: jax.Array, now: jax.Array
    ) -> Tuple[jax.Array, jax.Array]:
        """One serving step over UNIQUE slots (the engine dedups).

        Padding lanes use out-of-table slots (gathers fill 0, scatters
        drop) with divider=1 and hits=0, so they are inert.
        """
        slots = packed[0]
        hits = jax.lax.bitcast_convert_type(packed[1], jnp.uint32)
        fresh = packed[3] != 0
        divider = jax.lax.bitcast_convert_type(packed[4], jnp.uint32)
        now_u = now.astype(jnp.uint32)

        win = state[0].at[slots].get(mode="fill", fill_value=0)
        curr = state[1].at[slots].get(mode="fill", fill_value=0)
        prev = state[2].at[slots].get(mode="fill", fill_value=0)

        w = now_u - now_u % divider
        same = (win == w) & ~fresh
        # Unsigned w - divider wraps when w < divider; the wrapped
        # value can never equal a real stored window, so the compare
        # stays correct without a signed cast.
        adjacent = (win == w - divider) & ~fresh
        new_prev = jnp.where(
            same, prev, jnp.where(adjacent, curr, jnp.uint32(0))
        )
        base = jnp.where(same, curr, jnp.uint32(0))

        elapsed = now_u - w  # in [0, divider)
        wprev = floor_muldiv(new_prev, divider - elapsed, divider)

        # SATURATING add, mirroring the fixed-window counter domain
        # (models/fixed_window.py update_unique): one u32 add wraps at
        # most once, so after < base <=> wrap.
        after = base + hits
        after = jnp.where(after < base, jnp.uint32(0xFFFFFFFF), after)

        state = state.at[:, slots].set(
            jnp.stack([w, after, new_prev]),
            mode="drop",
            unique_indices=True,
        )
        return state, jnp.stack([wprev, after])

    # -- host halves (backends/engine.py generic protocol) --------------

    def lane_counts(
        self,
        out: np.ndarray,
        dedup,
        hits_u32: np.ndarray,
        limits_u32: np.ndarray,
        now: int,
    ) -> Tuple[np.ndarray, np.ndarray]:
        """Rebuild per-lane (before, after) effective counts from the
        per-GROUP device readback, in pipeline order: the weighted-prev
        term is batch-constant per group, so

            before_lane = wprev_g + (after_g - total_g) + prefix_lane

        in exact integer arithmetic.  A group saturated at u32 max is
        treated as fully-over, same as the fixed-window path."""
        g = len(dedup.uniq_slots)
        U32_MAX = np.uint64(0xFFFFFFFF)
        wprev_g = out[0, :g].astype(np.int64)
        after_g = out[1, :g].astype(np.uint64)
        saturated = after_g >= U32_MAX
        before_g = np.where(
            saturated, U32_MAX, after_g - np.minimum(dedup.totals, after_g)
        ).astype(np.int64)
        befores = (
            wprev_g[dedup.inv]
            + before_g[dedup.inv]
            + dedup.prefix.astype(np.int64)
        )
        afters = befores + hits_u32.astype(np.int64)
        return befores, afters

    def reference_step(
        self,
        state: np.ndarray,
        slots: np.ndarray,
        hits: np.ndarray,
        limits: np.ndarray,
        fresh: np.ndarray,
        divider: np.ndarray,
        now: int,
    ) -> Tuple[np.ndarray, np.ndarray]:
        """Numpy oracle of step_serve_packed over unique in-table
        slots (tests/bench verification, the fault domain's host
        mirror); mutates ``state`` in place and returns (wprev,
        after).  The weighted-prev term is the plain 64-bit
        multiply-then-divide the kernel's 32-bit ``floor_muldiv``
        must equal."""
        win = state[0, slots].copy()
        curr = state[1, slots].copy()
        prev = state[2, slots].copy()
        now_u = np.uint32(now)
        divider = divider.astype(np.uint32)
        w = now_u - now_u % divider
        fresh = fresh.astype(bool)
        same = (win == w) & ~fresh
        adjacent = (win == w - divider) & ~fresh
        new_prev = np.where(same, prev, np.where(adjacent, curr, 0)).astype(
            np.uint32
        )
        base = np.where(same, curr, 0).astype(np.uint32)
        elapsed = now_u - w
        wprev = (
            new_prev.astype(np.uint64)
            * (divider - elapsed).astype(np.uint64)
            // divider.astype(np.uint64)
        ).astype(np.uint32)
        after = np.minimum(
            base.astype(np.uint64) + hits.astype(np.uint64),
            np.uint64(0xFFFFFFFF),
        ).astype(np.uint32)
        state[0, slots] = w
        state[1, slots] = after
        state[2, slots] = new_prev
        return wprev, after
