"""Partition-axis sharding over a TPU device mesh.

The reference scales by cloning per-key processor graphs inside one JVM
(partition/PartitionRuntime.java:255-308) and has no distributed backend
(SURVEY.md §2.8/§5.8).  Here the partition axis of the NFA state tensors
([P, K] slots, [P, K, S, C] captures) and the [P, T] event lanes shard over
an ICI mesh: every device steps its own partition shard, no collectives on
the hot path.  The optional fused stats reduction (jit_engine_step
stats=True, used by parallel/distributed.DistributedPatternBank) is the one
collective — XLA lowers the sum over the sharded axis to an all-reduce over
ICI/DCN.  Multi-host scale-out uses the same program under jax.distributed
over DCN.
"""
from __future__ import annotations

from typing import Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops.nfa import CLOCK_KEY, NfaSpec, build_block_step, make_carry


def partition_mesh(devices: Optional[Sequence] = None,
                   axis: str = "p") -> Mesh:
    """1-D mesh over all (or given) devices; the partition axis maps onto it."""
    devs = np.asarray(devices if devices is not None else jax.devices())
    return Mesh(devs, (axis,))


def auto_mesh(axis: str = "p") -> Optional[Mesh]:
    """The engine-default mesh: all local devices when there is more than
    one, else None (single-chip execution needs no sharding machinery).
    The planner-built device runtimes (plan/planner.py) call this so a
    SiddhiManager user gets ICI-sharded execution wherever the hardware
    has it — the engine-integrated replacement for the reference's per-key
    clone scaling (partition/PartitionRuntime.java:255-308).

    `SIDDHI_TPU_MESH=off` forces single-device (operator escape hatch).

    Under jax.distributed (multi-host), the engine-default mesh is the
    LOCAL device set: SiddhiManager engines are shared-nothing per host
    (parallel/multihost.py routes keys between them), and a global mesh
    would demand lock-step dispatch across processes.  Explicit global
    meshes remain available (parallel/distributed.py)."""
    import os
    if os.environ.get("SIDDHI_TPU_MESH", "auto").lower() == "off":
        return None
    devs = jax.local_devices() if jax.process_count() > 1 \
        else jax.devices()
    if len(devs) <= 1:
        return None
    return partition_mesh(devs, axis)


def round_up_partitions(n_partitions: int, mesh: Optional[Mesh]) -> int:
    """Smallest lane count >= n_partitions divisible by the mesh size (the
    leading axis shards evenly; surplus lanes stay empty)."""
    if mesh is None:
        return n_partitions
    nd = int(mesh.devices.size)
    return -(-n_partitions // nd) * nd


def jit_engine_step(spec: NfaSpec, mesh: Mesh, axis: str = "p",
                    stats: bool = False, donate: bool = True):
    """jit of the raw NFA block step (ops/nfa.build_block_step) with the
    partition axis of carry, event block and match outputs sharded over
    `mesh` — the engine-integrated sharded hot path.  Partition lanes are
    fully independent, so the step itself has ZERO collectives.

    stats=True additionally returns {"matches", "dropped"} global sums
    FUSED into the same executable (one dispatch per block; the reduction
    over the sharded axis is the one collective) — the multi-host path
    (DistributedPatternBank) uses this so each block costs a single
    dispatch."""
    step = build_block_step(spec)

    def stepped(carry, block):
        new_carry, matches = step(carry, block)
        st = {"matches": jnp.sum(matches[0].astype(jnp.int32)),
              "dropped": jnp.sum(new_carry["dropped"])}
        return new_carry, matches, st

    proto_carry = make_carry(spec, 1)
    carry_sh = jax.tree_util.tree_map(
        lambda v: lead_axis_sharding(mesh, v, axis), proto_carry)
    block_sh = {name: NamedSharding(mesh, P(axis, None))
                for name in list(spec.attr_names) +
                ["__ts", "__stream", "__valid"] +
                ([CLOCK_KEY] if "deadline" in proto_carry else [])}

    def lead(nd):
        return NamedSharding(mesh, P(axis, *([None] * (nd - 1))))
    matches_sh = (lead(3), lead(5), lead(3), lead(3), lead(3))
    if not stats:
        return jax.jit(step, in_shardings=(carry_sh, block_sh),
                       out_shardings=(carry_sh, matches_sh),
                       donate_argnums=(0,) if donate else ())
    replicated = NamedSharding(mesh, P())
    stats_sh = {"matches": replicated, "dropped": replicated}
    return jax.jit(stepped, in_shardings=(carry_sh, block_sh),
                   out_shardings=(carry_sh, matches_sh, stats_sh),
                   donate_argnums=0)


def lead_axis_sharding(mesh: Mesh, v, axis: str = "p") -> NamedSharding:
    """Leading-dim-on-`axis` sharding for an array(-like) leaf."""
    return NamedSharding(mesh, P(axis, *([None] * (jnp.ndim(v) - 1))))


def shard_carry(carry: Dict[str, jnp.ndarray], mesh: Mesh,
                axis: str = "p") -> Dict[str, jnp.ndarray]:
    """Place NFA carry tensors with their leading partition dim sharded."""
    return {k: jax.device_put(v, lead_axis_sharding(mesh, v, axis))
            for k, v in carry.items()}


