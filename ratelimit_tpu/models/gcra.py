"""GCRA (token bucket) rate limiting as a batched device kernel.

The Generic Cell Rate Algorithm in its virtual-scheduling formulation:
each slot stores one theoretical-arrival-time (TAT).  With emission
interval ``T = divider / limit`` and burst tolerance
``tau = divider - T`` (an idle key may burst exactly ``limit`` cells),
a request of ``h`` cells at time ``now``:

    conforms  iff  TAT <= now + tau
    then           TAT' = max(TAT, now) + h * T

This is the continuous-refill policy: capacity returns one cell per
``T`` seconds instead of all at once at a window edge, so there is no
boundary burst at all.

Everything is exact integer arithmetic — no float, no rounding, on
the device and in the numpy oracle alike.  ``now`` is whole unix
seconds and every TAT is some earlier ``now`` plus a whole number of
emission intervals, so per-slot state is two uint32 rows

    row 0: tat_anchor   unix seconds the cell count is measured from
    row 1: tat_cells    TAT = tat_anchor + tat_cells * divider / limit

and with ``e = now - tat_anchor`` elapsed seconds the cells still
held against the limit are

    used = ceil((TAT - now)+ / T) = (tat_cells - floor(e * limit / divider))+

— one exact ``floor(a * b / d)`` (ops.floor_muldiv, 32-bit
only, no x64 on the device), never a division by ``limit``.  The f32
form this replaced differed from its own numpy oracle by one cell on
11% of lanes for limits that do not divide the window, and a TPU's f32
divide is not correctly rounded on top of that (measured on a v5e,
PERF.md PR 21): the host mirror the fault domain falls back to must
grant exactly what the device grants.

An update re-anchors by whole windows (``divider`` seconds = ``limit``
cells), so ``e < divider`` and ``tat_cells <= 2 * limit`` hold at
every write; a slot whose anchor is two windows old is therefore empty
whatever it holds — which also bounds how long cells counted under a
since-lowered limit can outlive a config reload.  Consumed CELLS carry
over a limit change, like a fixed-window count.  Limits are clamped to
2**31 - 1 (the budget readback is int32).

Batch semantics over duplicate lanes (the engine dedups same-key
lanes to one slot): admission is cell-granular against the group's
budget ``B = limit - ceil((TAT - now)+ / T)`` in pipeline order —
lane ``k`` is admitted iff its exclusive hit-prefix plus its own
``h`` fits in ``B``, and the device advances TAT by
``min(total_h, B)`` cells.  For ``hits_addend == 1`` (the common
case) this is exactly per-request GCRA; for multi-cell lanes
straddling the budget the advance errs toward over-counting —
the same safe direction as the fixed-window counter saturation.

Serving protocol (backends/engine.py generic path): ``packed`` is
int32[5, N] rows (slots, hits-bits, limits-bits, fresh,
divider-bits) plus the batch clock; the kernel returns int32[N]
per-group budgets.  The host maps budgets onto the shared threshold
state machine by synthesizing ``before = limit - B + prefix`` (cells
already consumed against the limit), so OVER/near-limit attribution
and shadow_mode ride limiter.base.decide_batch unchanged.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.muldiv import floor_muldiv
from .registry import ALGO_GCRA

_L_MAX = 2**31 - 1  # limit clamp: budgets read back as int32
_U32_MAX = 0xFFFFFFFF


class GcraModel:
    """Configuration + jittable step for the TAT table."""

    algo = ALGO_GCRA
    #: Stable-stem keys: the TAT must survive window rollovers (see
    #: module docstring); the owning engine uses refresh-on-touch
    #: expiry.
    windowed_keys = False
    state_rows = ("tat_anchor", "tat_cells")

    def __init__(self, num_slots: int, near_ratio: float = 0.8):
        self.num_slots = int(num_slots)
        self.near_ratio = float(near_ratio)

    def init_state(self) -> jax.Array:
        """Fresh state: every anchor at 0 (i.e. the distant past: any
        key's first sighting has full burst capacity)."""
        return jnp.zeros((2, self.num_slots), dtype=jnp.uint32)

    @functools.partial(jax.jit, static_argnums=0, donate_argnums=1)
    def step_serve_packed(
        self, state: jax.Array, packed: jax.Array, now: jax.Array
    ) -> Tuple[jax.Array, jax.Array]:
        """One serving step over UNIQUE slots (the engine dedups).

        Padding lanes use out-of-table slots (gathers fill 0, scatters
        drop) with divider=1, limit=1, hits=0, so they are inert.
        """
        slots = packed[0]
        hits = jax.lax.bitcast_convert_type(packed[1], jnp.uint32)
        limits = jnp.minimum(
            jax.lax.bitcast_convert_type(packed[2], jnp.uint32),
            jnp.uint32(_L_MAX),
        )
        fresh = packed[3] != 0
        divider = jax.lax.bitcast_convert_type(packed[4], jnp.uint32)
        now_u = now.astype(jnp.uint32)

        anchor = state[0].at[slots].get(mode="fill", fill_value=0)
        cells = state[1].at[slots].get(mode="fill", fill_value=0)
        cells = jnp.where(fresh, jnp.uint32(0), cells)

        # Elapsed seconds through the signed view, so a clock that
        # stepped back reads as 0 elapsed (the strict side), not 2^32.
        e = jnp.maximum(
            jax.lax.bitcast_convert_type(now_u - anchor, jnp.int32),
            jnp.int32(0),
        ).astype(jnp.uint32)
        k = e // divider  # whole windows elapsed: k * limit cells back
        whole = jnp.where(k == jnp.uint32(1), limits, jnp.uint32(0))
        refilled = whole + floor_muldiv(limits, e - k * divider, divider)
        # TAT <= now.  cells <= 2 * limit, so two whole windows empty
        # the bucket and k * limit never has to be formed.
        idle = (k >= jnp.uint32(2)) | (cells <= refilled)
        used = jnp.where(idle, jnp.uint32(0), cells - refilled)
        budget = jnp.where(limits > used, limits - used, jnp.uint32(0))

        adm = jnp.minimum(hits, budget)  # cells admitted
        upd = adm > jnp.uint32(0)
        # max(TAT, now) + adm * T, re-anchored by whole windows only.
        base = jnp.where(idle, jnp.uint32(0), cells - whole)
        new_cells = base + adm  # saturating, like every counter here
        new_cells = jnp.where(
            new_cells < base, jnp.uint32(_U32_MAX), new_cells
        )
        new_anchor = jnp.where(idle, now_u, anchor + k * divider)

        state = state.at[:, slots].set(
            jnp.stack(
                [
                    jnp.where(upd, new_anchor, anchor),
                    jnp.where(upd, new_cells, cells),
                ]
            ),
            mode="drop",
            unique_indices=True,
        )
        return state, budget.astype(jnp.int32)

    # -- host halves (backends/engine.py generic protocol) --------------

    def lane_counts(
        self,
        out: np.ndarray,
        dedup,
        hits_u32: np.ndarray,
        limits_u32: np.ndarray,
        now: int,
    ) -> Tuple[np.ndarray, np.ndarray]:
        """Map per-group budgets onto the shared (before, after)
        surface: ``before = limit - B + prefix`` is the cells already
        consumed against the limit in pipeline order, so
        ``after > limit  <=>  prefix + h > B`` — exactly the
        conformance test.  ``before`` can go slightly negative when a
        lane's limit sits below its group's max (mixed-limit groups
        only); decide_batch's comparisons remain correct."""
        g = len(dedup.uniq_slots)
        budgets = np.asarray(out).reshape(-1)[:g].astype(np.int64)
        befores = (
            limits_u32.astype(np.int64)
            - budgets[dedup.inv]
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
    ) -> np.ndarray:
        """Numpy oracle of step_serve_packed over unique in-table
        slots (tests/bench verification, the fault domain's host
        mirror); mutates ``state`` in place and returns the per-slot
        budgets.  The refill is the plain 64-bit multiply-then-divide
        the kernel's 32-bit arithmetic must equal."""
        now_u = np.uint32(now)
        anchor = state[0, slots].copy()
        cells = state[1, slots].astype(np.uint64)
        cells[fresh.astype(bool)] = 0
        limits = np.minimum(limits.astype(np.uint64), np.uint64(_L_MAX))
        divider = divider.astype(np.uint64)
        e = np.maximum((now_u - anchor).view(np.int32), 0).astype(np.uint64)
        k = e // divider
        idle = (k >= 2) | (cells <= e * limits // divider)
        used = np.where(idle, 0, cells - e * limits // divider)
        budget = np.where(limits > used, limits - used, 0)
        adm = np.minimum(hits.astype(np.uint64), budget)
        upd = adm > 0
        new_cells = np.minimum(
            np.where(idle, 0, cells - k * limits) + adm, np.uint64(_U32_MAX)
        )
        new_anchor = np.where(
            idle, now_u, anchor + (k * divider).astype(np.uint32)
        )
        state[0, slots] = np.where(upd, new_anchor, anchor)
        state[1, slots] = np.where(upd, new_cells, cells).astype(np.uint32)
        return budget.astype(np.int32)
