"""Device-mesh sharding of the batched quorum state.

The reference scales by partitioning groups over 16 worker goroutines with
``clusterID % workers`` (``execengine.go:654-706``, ``server/partition.go:38``).
The TPU-native analog partitions the *group axis of the state tensors* over a
``jax.sharding.Mesh``: every kernel op in :mod:`.kernels` is row-wise over
groups, so GSPMD partitions the entire ``quorum_step`` program with **zero
collectives** — each chip steps its slice of groups independently, the same
embarrassing parallelism the reference exploits, but across chips over ICI
instead of goroutines.

Event batches are replicated (they are tiny: ``(K,)`` int32); each chip
applies only the scatter rows that land in its group slice — XLA handles
this natively for scatter-into-sharded-operand.
"""
from __future__ import annotations

from typing import Optional

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .state import QuorumState

GROUP_AXIS = "groups"


def make_mesh(devices=None, axis: str = GROUP_AXIS) -> Mesh:
    devices = devices if devices is not None else jax.devices()
    return Mesh(devices, (axis,))


def state_sharding(mesh: Mesh, axis: str = GROUP_AXIS) -> QuorumState:
    """A ``QuorumState`` of shardings: group axis split, peer axis local.

    Peer columns stay on-chip with their group row (quorum math reduces
    across peers — splitting peers would force cross-chip reductions for a
    7-wide axis; splitting groups costs nothing).
    """
    row = NamedSharding(mesh, P(axis))
    mat = NamedSharding(mesh, P(axis, None))
    cube = NamedSharding(mesh, P(axis, None, None))  # (G,S,P) read acks
    mats = (
        "match", "next", "voting", "present", "active", "votes",
        "read_index", "read_count",
        "kv_value", "kv_ent_index", "kv_ent_key", "kv_ent_val",
    )
    fields = {
        k: (cube if k == "read_acks" else mat if k in mats else row)
        for k in QuorumState._fields
    }
    return QuorumState(**fields)


def block_sharding(sharding):
    """The sharding of a packed state block (``state.StateBlocks``,
    ``(rows, G)``) of an engine whose leaves shard as ``sharding``: the
    leaves' group axis is the blocks' LAST, so the leaves' spec moves one
    axis to the right and the groups stay split exactly as before."""
    if isinstance(sharding, NamedSharding):
        return NamedSharding(sharding.mesh, P(None, *sharding.spec))
    return sharding


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def shard_state(st: QuorumState, mesh: Mesh, axis: str = GROUP_AXIS) -> QuorumState:
    sh = state_sharding(mesh, axis)
    return QuorumState(
        *(jax.device_put(v, s) for v, s in zip(st, sh))
    )
