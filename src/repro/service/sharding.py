"""Sharded slot pool: one engine shard per mesh device.

The paper's synchronous SA wins because it scales with the device's
parallelism — but a single slot pool caps the serving engine at one
device's worth of chain blocks.  This module shards the pool over a 1-D
``(pool,)`` JAX device mesh (launch/mesh.py): :class:`EngineShard` pairs
one device with a private :class:`~repro.service.slots.SlotPool` and
:class:`~repro.service.slots.RidTable`, and the engine runs each shard's
dispatch groups as *independent device programs* — one per
``(shard, family, dim, N)`` — so shards anneal concurrently (JAX async dispatch
overlaps the launches) and compile counts stay bounded per device exactly
as they were for the single pool.

Why shards are private, not a ``shard_map`` over one global pool:

* **Tenant state is ragged.**  Slots hold heterogeneous ``(dim,)`` blocks
  and join different ``(dim, N)`` dispatch groups each tick; a collective
  program over the union would re-introduce the straggler coupling the
  continuous-batching design exists to avoid.
* **Migration wants checkpoints, not collectives.**  Russkov et al.
  (arXiv:2006.00561) redistribute replicas between accelerators by moving
  their state; our :class:`~repro.service.slots.SwappedJob` checkpoint is
  already bit-exact and placement-invariant (counter-based RNG on logical
  chain coordinates), so moving a job between shards is checkpoint-on-A /
  restore-on-B with zero trajectory perturbation — the scheduler treats
  cross-shard rebalancing exactly like preemption's swap-to-host, minus
  the queue round-trip.

Placement itself (which shard a request calls home) lives in the
scheduler (scheduler.py: ``place`` / ``plan_migrations``); this module
only knows about devices and per-shard state.
"""
from __future__ import annotations

import dataclasses
from typing import List

import jax

from repro.launch.mesh import slot_pool_mesh
from repro.service.slots import RidTable, SlotPool


def slot_pool_devices(n_shards: int) -> List[object]:
    """The devices backing ``n_shards`` engine shards.

    Uses the 1-D ``(pool,)`` mesh when enough physical devices exist
    (``XLA_FLAGS=--xla_force_host_platform_device_count=N`` provides them
    on CPU).  When oversubscribed, logical shards round-robin over the
    devices that do exist: placement, migration and accounting behave
    identically — only true parallel dispatch is lost — so the sharding
    logic stays testable on a single-device host.
    """
    if n_shards < 1:
        raise ValueError(f"n_shards must be >= 1, got {n_shards}")
    devices = jax.devices()
    if n_shards <= len(devices):
        return list(slot_pool_mesh(n_shards).devices.reshape(-1))
    return [devices[i % len(devices)] for i in range(n_shards)]


@dataclasses.dataclass
class EngineShard:
    """One device's slice of the serving state.

    A shard owns a private slot pool and rid table; rids (segment ids in
    the masked champion exchange) are shard-local, which keeps the
    segmented reduce identical to the single-pool engine.  Dispatch
    groups never span shards — each shard's groups compile and launch on
    its own device.

    The fleet is *elastic* (engine.py ``drain``/``resize``): a shard
    marked ``draining`` accepts no new placements while the engine
    checkpoint-evacuates its jobs onto the survivors, and is retired —
    removed from the fleet — once empty.  Shard ``index`` is therefore a
    stable identity, not a list position: retired indices are never
    reused, and shards added later get fresh indices.
    """

    index: int                  # stable shard id (never reused)
    device: object              # jax.Device the shard's programs run on
    pool: SlotPool
    rids: RidTable
    sweeps_done: int = 0        # block-sweeps on this shard (utilization
                                # numerator for per-shard occupancy)
    resident_ticks: int = 0     # engine ticks this shard was in the fleet
                                # (utilization denominator — shards may
                                # join/leave mid-run)
    draining: bool = False      # no new placements; evacuating to retire
    group_cache: dict = dataclasses.field(default_factory=dict)
                                # (family, dim, N) -> {"buf": device array,
                                # "n_padded": int}: the launch paths'
                                # double buffer.  When a group's
                                # membership is unchanged since its last
                                # launch (every slot still references this
                                # buffer at its packed rows), the host
                                # repack + transfer is skipped and the
                                # buffer is donated straight back to the
                                # next launch (engine._group_state)

    @property
    def jobs(self):
        """rid -> ActiveJob resident on this shard."""
        return self.rids.jobs

    def occupancy(self, ticks: int = 0) -> float:
        """Fraction of this shard's slot-ticks spent sweeping.  Uses the
        shard's own residency by default (elastic fleets: shards join and
        leave mid-run); pass ``ticks`` to override the denominator."""
        denom = ticks if ticks else self.resident_ticks
        return self.sweeps_done / (max(denom, 1) * self.pool.n_slots)


def make_shard(index: int, n_slots: int, chains_per_slot: int) -> EngineShard:
    """Build one shard on the device backing ``index`` (round-robin over
    the physical devices — the elastic-fleet grow path, where shards are
    added one at a time with fresh indices)."""
    devices = jax.devices()
    return EngineShard(index=index, device=devices[index % len(devices)],
                       pool=SlotPool(n_slots, chains_per_slot),
                       rids=RidTable(n_slots))


def make_shards(n_devices: int, n_slots: int,
                chains_per_slot: int) -> List[EngineShard]:
    """Build the engine's shard list: ``n_slots`` slots *per shard*."""
    return [EngineShard(index=i, device=dev,
                        pool=SlotPool(n_slots, chains_per_slot),
                        rids=RidTable(n_slots))
            for i, dev in enumerate(slot_pool_devices(n_devices))]
