"""Device mesh management.

Reference surface: PX worker/SQC topology — a query runs at DOP d across
nodes, each node hosting worker threads (sql/engine/px/ob_px_sub_coord.cpp,
ob_px_worker.h:229). The TPU mapping: one mesh axis "shard" enumerates the
execution shards (device = worker); multi-host slices extend the same mesh
over ICI/DCN and XLA routes the collectives (SURVEY.md §2.7). A second
optional axis "host" models the 2-level PARTITION_HASH/BC2HOST slave-mapping
methods (hierarchical exchanges).
"""

from __future__ import annotations

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

SHARD_AXIS = "shard"


def shard_map_compat(f, *, mesh, in_specs, out_specs,
                     check_replication=True):
    """The one spelling of shard_map in the engine and its tests: every
    SPMD program routes through here (`jax.shard_map`, whose replication
    check is `check_vma`)."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_replication)


def mesh_signature(mesh: Mesh) -> tuple:
    """Restart-stable identity of a mesh: axis sizes + axis names.

    Device ids deliberately excluded — a warm boot enumerates devices in
    the same order but with fresh client handles; what an exported SPMD
    program actually depends on is the axis geometry its shardings were
    lowered against. Joins the plan-artifact key (engine/plan_artifact)
    and the hydrate-time guard so a program exported on one mesh shape
    can never run with another's shardings."""
    return (
        tuple(int(mesh.shape[a]) for a in mesh.axis_names),
        tuple(str(a) for a in mesh.axis_names),
    )


# Multi-process runtimes (the DCN half of SURVEY §2.7's architectural
# translation: ICI within a slice = one process's devices, DCN across
# slices = jax.distributed's cross-process collectives — gloo on CPU,
# real DCN transport on TPU pods): call jax.distributed.initialize
# BEFORE importing anything from this package (package imports build jnp
# constants, which locks the backend) — after that jax.devices() is the
# GLOBAL list and the same shard_map PX programs run SPMD across
# processes, exactly like the reference's SQC dispatch spans observers
# (sql/engine/px/ob_px_rpc_processor.h:28). See
# tests/test_px_multiproc.py and __graft_entry__._mp_px_worker.


def make_mesh(n_devices: int | None = None, devices=None) -> Mesh:
    if devices is None:
        devices = jax.devices()
    if n_devices is not None:
        if len(devices) < n_devices:
            raise ValueError(
                f"mesh needs {n_devices} devices but only {len(devices)} "
                "are available; silently shrinking would break exchange "
                "capacity math"
            )
        devices = devices[:n_devices]
    return Mesh(np.array(devices), (SHARD_AXIS,))


def row_sharding(mesh: Mesh) -> NamedSharding:
    """Rows split across shards (granule assignment, static)."""
    return NamedSharding(mesh, P(SHARD_AXIS))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())
