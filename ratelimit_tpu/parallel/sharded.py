"""Sharded fixed-window counter model: slot space split across a Mesh.

Design (TPU-first, not a translation of the reference's Redis cluster):

- The counter table is one logical uint32[num_banks * slots_per_bank]
  array laid out as (num_banks, slots_per_bank) and sharded over mesh
  axis ``banks`` with ``NamedSharding(P("banks", None))`` — each chip
  holds exactly its bank in HBM.
- Bank ownership is MODULO-STRIPED: global slot s belongs to bank
  ``s % num_banks`` at local position ``s // num_banks``.  The host
  slot table allocates slots densely (0, 1, 2, ...), so contiguous
  ranges would pile every early key onto bank 0 until it filled —
  striping spreads work evenly from the very first key (found by the
  round-3 sharded-server test: 40 keys, one bank).
- A batch is replicated to every chip.  Under ``shard_map`` each chip
  masks the batch to the slots it owns, runs the same branch-free
  fixed-window body as the single-chip model (models/fixed_window.py),
  and zeroes every lane it does not own.
- One ``psum`` over ``banks`` (rides ICI) recombines the per-lane
  answers: each lane is owned by exactly one chip, so the sum is a
  select.  No gather/scatter collectives, no host round trips.
- The serving step (``step_counters_unique_packed``) is the one-chip
  model's, entry for entry and body for body (each chip runs
  ``FixedWindowModel.update_unique`` on its bank): the host packs one
  ``int32[4, bucket]`` launch exactly as for one chip and never asks
  which chip owns a slot (why: ShardedCounterEngine's docstring).

This is the Redis-cluster key-slot analog (reference
src/redis/driver_impl.go:108-126: radix cluster routes each key by hash
slot) built the SPMD way: instead of routing requests to the owning
node over TCP, every chip sees every request and ownership is a mask.
The slot id already encodes the bank (slot % num_banks), so the
host-side SlotTable needs no changes.
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from jax import shard_map

from ..backends.engine import CounterEngine
from ..models.fixed_window import (
    DeviceBatch,
    DeviceDecisions,
    FixedWindowModel,
    decision_block,
)
from ..ops.prefix import per_slot_inclusive_prefix


def make_mesh(
    n_devices: Optional[int] = None, axis: str = "banks"
) -> Mesh:
    """1-D device mesh over the first `n_devices` local devices."""
    devices = jax.devices()
    if n_devices is not None:
        devices = devices[:n_devices]
    return Mesh(np.array(devices), (axis,))


class ShardedFixedWindowModel:
    """Fixed-window decisions over a bank-sharded counter table.

    ``num_slots`` is the GLOBAL slot count; it is rounded up to a
    multiple of the mesh size so every bank is equal-sized (XLA needs
    even sharding).  Slot ids from the host SlotTable index the global
    space; bank ownership is ``slot % num_banks`` (modulo striping,
    see the module docstring).
    """

    def __init__(self, num_slots: int, mesh: Mesh, near_ratio: float = 0.8):
        self.mesh = mesh
        self.axis = mesh.axis_names[0]
        self.num_banks = mesh.devices.size
        self.slots_per_bank = -(-int(num_slots) // self.num_banks)
        self.num_slots = self.slots_per_bank * self.num_banks
        self.near_ratio = float(near_ratio)
        # One chip's bank as the one-chip model sees a table: the
        # serving step runs ITS unique-slot body on local slot ids.
        self._bank_model = FixedWindowModel(self.slots_per_bank, near_ratio)

        counts_spec = NamedSharding(mesh, P(self.axis, None))
        repl = NamedSharding(mesh, P())
        self._step = self._build(self._bank_step)
        self._step_counters = self._build(self._bank_update)
        self._compact_fns: dict = {}
        self._unique_packed_fns: dict = {}
        self._counts_sharding = counts_spec
        self._batch_sharding = repl

    def _build(self, body):
        counts_spec = NamedSharding(self.mesh, P(self.axis, None))
        repl = NamedSharding(self.mesh, P())
        return jax.jit(
            shard_map(
                body,
                mesh=self.mesh,
                in_specs=(P(self.axis, None), P()),
                out_specs=(P(self.axis, None), P()),
            ),
            in_shardings=(counts_spec, repl),
            out_shardings=(counts_spec, repl),
            donate_argnums=0,
        )

    def init_state(self) -> jax.Array:
        """Fresh sharded counter table: (num_banks, slots_per_bank)."""
        return jax.device_put(
            jnp.zeros((self.num_banks, self.slots_per_bank), dtype=jnp.uint32),
            self._counts_sharding,
        )

    def step(
        self, counts: jax.Array, batch: DeviceBatch
    ) -> Tuple[jax.Array, DeviceDecisions]:
        return self._step(counts, batch)

    def step_counters(
        self, counts: jax.Array, batch: DeviceBatch
    ) -> Tuple[jax.Array, jax.Array]:
        """Counter update only; returns (counts, afters) — the serving
        fast path (see models/fixed_window.py step_counters)."""
        return self._step_counters(counts, batch)

    def step_counters_compact(
        self, counts: jax.Array, out_dtype: str, batch: DeviceBatch
    ) -> Tuple[jax.Array, jax.Array]:
        """Saturated narrow readback over the mesh (see
        FixedWindowModel.step_counters_compact for the exactness
        argument).  Non-owned lanes are already 0, so the psum of the
        narrow values still selects the single owner without wrap."""
        fn = self._compact_fns.get(out_dtype)
        if fn is None:

            def step_counters_compact(counts, batch, _dt=out_dtype):
                counts, afters, owned = self._bank_core(counts, batch)
                cap = batch.limits + batch.hits.astype(jnp.uint32)
                sat = jnp.minimum(afters, cap)
                sat = jnp.where(owned, sat, jnp.uint32(0)).astype(jnp.dtype(_dt))
                return counts, jax.lax.psum(sat, self.axis)

            fn = self._compact_fns[out_dtype] = self._build(
                step_counters_compact
            )
        return fn(counts, batch)

    # -- unique fast path: the serving step ------------------------------

    def step_counters_unique_packed(
        self, counts: jax.Array, out_dtype: str, packed: jax.Array
    ) -> Tuple[jax.Array, jax.Array]:
        """FixedWindowModel.step_counters_unique_packed over the mesh:
        the same packed int32[4, N] launch (GLOBAL slot ids, hits and
        limits as uint32 bit-patterns, fresh 0/1; padding = distinct
        out-of-table ids) goes to every chip, each chip updates the
        lanes whose slots it owns, and one psum of the uint32 afters
        makes the answer whole on every chip — so the host reads it
        back from one.  Bit for bit the one-chip step's answers and
        table.  The per-chip program carries this method's name, as
        the one-chip engine's does (``jit_step_counters_unique_packed``
        on every chip's plane of a device trace)."""
        fn = self._unique_packed_fns.get(out_dtype)
        if fn is None:

            def step_counters_unique_packed(counts, packed, _dt=out_dtype):
                hits = jax.lax.bitcast_convert_type(packed[1], jnp.uint32)
                limits = jax.lax.bitcast_convert_type(packed[2], jnp.uint32)
                counts, afters = self._bank_unique(
                    counts, packed[0], hits, packed[3] != 0
                )
                afters = jax.lax.psum(afters, self.axis)
                if _dt:
                    afters = jnp.minimum(afters, limits + hits).astype(
                        jnp.dtype(_dt)
                    )
                return counts, afters

            fn = self._build(step_counters_unique_packed)
            self._unique_packed_fns[out_dtype] = fn  # tpu-lint: disable=shared-state -- one GIL-atomic dict set a dtype, by the thread that owns the engine (CounterEngine._device_submit)
        return fn(counts, packed)

    def _bank_unique(self, counts, slots, hits, fresh):
        """Unique-slot update of THIS chip's bank from the whole
        launch; returns (counts, afters) with `afters` 0 on every lane
        another chip answers.  FixedWindowModel.update_unique on the
        bank, at local positions (bank = slot % num_banks, local =
        slot // num_banks); every lane this chip does not own goes to
        a distinct out-of-bank index (spb + lane), so it reads a
        virtual zero, scatters nowhere and the unique-scatter promise
        holds.  Chip 0 answers out-of-table lanes (padding, probes)
        with `after = hits`, as the one-chip step does."""
        nb = jnp.int32(self.num_banks)
        bank = jax.lax.axis_index(self.axis)
        in_table = (slots >= 0) & (slots < self.num_slots)
        owns = in_table & (slots % nb == bank)
        lane = jax.lax.broadcasted_iota(jnp.int32, slots.shape, 0)
        local = jnp.where(owns, slots // nb, self.slots_per_bank + lane)
        row, afters = self._bank_model.update_unique(
            counts[0],
            DeviceBatch(
                slots=local, hits=hits, limits=hits, fresh=fresh, shadow=fresh
            ),  # limits / shadow: unused by the update
        )
        answers = owns | (~in_table & (bank == 0))
        return row[None, :], jnp.where(answers, afters, jnp.uint32(0))

    # -- per-bank SPMD bodies (run on every chip under shard_map) -------

    def _bank_core(self, counts, batch: DeviceBatch):
        """Shared per-bank counter update; returns (counts, afters,
        owned) with `afters` valid only on owned lanes (0 elsewhere).
        Modulo-striped ownership: bank = slot % num_banks, local
        position = slot // num_banks."""
        # counts: uint32[1, slots_per_bank] — this chip's bank.
        spb = self.slots_per_bank
        nb = jnp.int32(self.num_banks)
        bank = jax.lax.axis_index(self.axis)

        local = batch.slots // nb
        in_table = (batch.slots >= 0) & (batch.slots < self.num_slots)
        owns_slot = in_table & (batch.slots % nb == bank)
        # Out-of-table lanes (padding) read a virtual zero counter and
        # scatter nowhere; bank 0 claims them so their decisions match
        # the single-chip model lane-for-lane.
        owned = owns_slot | (~in_table & (bank == 0))
        lslots = jnp.where(owns_slot, local, spb)  # spb = inert (drop/fill)

        row = counts[0]
        fresh_idx = jnp.where(batch.fresh & owns_slot, lslots, spb)
        row = row.at[fresh_idx].set(jnp.uint32(0), mode="drop")

        table_before = row.at[lslots].get(mode="fill", fill_value=0)

        # Pipeline-order duplicates: global computation, replicated on
        # every chip (slots are global ids so segments are identical).
        incl = per_slot_inclusive_prefix(batch.slots, batch.hits)
        afters = jnp.where(owned, table_before + incl, jnp.uint32(0))

        masked_hits = jnp.where(owns_slot, batch.hits, jnp.uint32(0))
        row = row.at[lslots].add(masked_hits, mode="drop")
        return row[None, :], afters, owned

    def _bank_update(self, counts, batch: DeviceBatch):
        counts, afters, _ = self._bank_core(counts, batch)
        return counts, jax.lax.psum(afters, self.axis)

    def _bank_step(self, counts, batch: DeviceBatch):
        counts, afters, owned = self._bank_core(counts, batch)
        full = decision_block(
            afters, batch.hits, batch.limits, batch.shadow, self.near_ratio
        )
        # Zero every lane this bank does not own, then psum: each lane
        # is owned by exactly one bank, so the sum is a select.
        partial = jax.tree_util.tree_map(
            lambda x: jnp.where(owned, x, jnp.zeros_like(x)).astype(
                jnp.int32 if x.dtype == jnp.bool_ else x.dtype
            ),
            full,
        )
        decisions = jax.tree_util.tree_map(
            lambda x: jax.lax.psum(x, self.axis), partial
        )
        return counts, decisions



class ShardedCounterEngine(CounterEngine):
    """CounterEngine over a bank-sharded model.

    Everything a launch runs is inherited — slot table, dedup, the one
    packed launch (CounterEngine._device_submit), the readback, the
    host-side decide: the model's step decides on the device which chip
    owns a lane, so the host never routes, `shape` is (bucket, dtype)
    as on one chip, and the replicated answer is copied to the host
    from one chip.  What the striping is for is the TABLE: each chip
    holds 1/num_banks of the counters (the Redis-cluster key-slot
    analog, driver_impl.go:108-126), so a table grows with the mesh.
    The lanes are not divided: every chip sees the launch's whole
    bucket, because 16x the lanes cost a chip +0.4 us while dividing
    them on the host cost the launch ~0.5 ms (PERF.md section 5,
    PR 49 -> 50).  This class adds the layout: where the bank lives
    and the global slot order of its checkpoint surface."""

    def __init__(
        self,
        mesh: Mesh,
        num_slots: int = 1 << 20,
        near_ratio: float = 0.8,
        buckets: Sequence[int] = (8, 16, 32, 64, 128, 256, 512, 1024, 2048, 4096),
    ):
        super().__init__(
            buckets=buckets,
            model=ShardedFixedWindowModel(num_slots, mesh, near_ratio),
        )

    def placement(self) -> dict:
        """The bank's placement plus the size of the mesh its table is
        striped over (runner start line, /debug/faults)."""
        return {**super().placement(), "mesh_devices": self.model.num_banks}

    def export_counts(self) -> np.ndarray:
        """Flat uint32 copy in GLOBAL slot order: bank b's local
        position l holds global slot l*num_banks + b (modulo
        striping), so the (nb, spb) device layout transposes back."""
        m = self.model
        arr = np.asarray(jax.device_get(self._counts)).reshape(
            m.num_banks, m.slots_per_bank
        )
        return arr.T.reshape(-1)

    def import_counts(self, counts) -> None:
        arr = np.asarray(counts, dtype=np.uint32).reshape(-1)
        m = self.model
        if arr.shape[0] != m.num_slots:
            raise ValueError(
                f"counts size {arr.shape[0]} != num_slots {m.num_slots}"
            )
        self._counts = jax.device_put(
            np.ascontiguousarray(
                arr.reshape(m.slots_per_bank, m.num_banks).T
            ),
            m._counts_sharding,
        )
