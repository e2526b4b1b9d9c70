"""The store's mesh and placement: shard and replica axes, per-replica
sub-meshes, and the leading-axis-sharded placement of its index stacks.

Functions, not module constants — importing this module never touches jax
device state (callers that force virtual devices set XLA_FLAGS first).

Mesh semantics (DESIGN.md §5):
  shard   — one store shard per device; every index stack's leading axis
            is split over it.
  replica — each position holds a FULL copy of every shard; the store
            compiles its fan-out once per replica sub-mesh and routes
            whole dispatches to one replica.
"""
from __future__ import annotations

from typing import Any

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def make_mesh(shape, axis_names):
    """``jax.make_mesh`` with every axis ``Auto`` (sharding propagated by
    the compiler, as the shard_map programs expect)."""
    return jax.make_mesh(
        shape, axis_names,
        axis_types=(jax.sharding.AxisType.Auto,) * len(axis_names))


def make_store_mesh(num_shards: int | None = None, replicas: int = 1):
    """Mesh for the sharded KNN datastore (repro.store.ShardedKNNStore).

    ``replicas=1`` (default): the 1-D ``('shard',)`` mesh — one store
    shard per device, every local device unless ``num_shards`` picks a
    subset.  ``replicas>1``: a 2-D ``('replica', 'shard')`` mesh — each
    replica row holds a FULL copy of every shard (``replicas ×
    num_shards`` devices), so reads fan out round-robin across replicas
    and a replica loss is a routing decision, not data loss.
    ``num_shards`` then defaults to ``devices // replicas``.
    """
    n = len(jax.devices())
    assert replicas >= 1, f"replicas must be >= 1, got {replicas}"
    if replicas == 1:
        shards = n if num_shards is None else num_shards
        assert 1 <= shards <= n, f"need {shards} devices, have {n}"
        return make_mesh((shards,), ("shard",))
    shards = (n // replicas) if num_shards is None else num_shards
    assert shards >= 1, f"{n} devices cannot host {replicas} replicas"
    assert replicas * shards <= n, (
        f"need {replicas}x{shards} devices, have {n}")
    return make_mesh((replicas, shards), ("replica", "shard"))


def replica_submeshes(mesh, replica_axis: str = "replica") -> list:
    """Split a replicated store mesh into one sub-mesh per replica, each
    spanning that replica's devices over the remaining (shard) axes.  The
    store compiles its fan-out per sub-mesh and routes whole dispatches to
    one replica — there is no cross-replica collective on the query path,
    which is exactly what lets a dead replica be routed around."""
    names = list(mesh.axis_names)
    ax = names.index(replica_axis)
    shard_names = tuple(n for n in names if n != replica_axis)
    devs = np.moveaxis(mesh.devices, ax, 0)
    return [Mesh(devs[r], shard_names) for r in range(devs.shape[0])]


def submesh_compiler_options(mesh) -> dict | None:
    """Compiler options for a program that spans PART of a TPU slice.

    By default the TPU runtime opens every multi-chip program with a launch
    barrier across all chips of the slice ("Enhanced barrier buffer must
    include all devices in the slice"); a replica's program runs on only
    its own chips, and the barrier then halts the device.  Such programs
    compile without it.  Single-chip and whole-slice programs, and other
    platforms, keep the defaults (``None``)."""
    devs = mesh.devices.ravel()
    if devs[0].platform == "tpu" and 1 < devs.size < len(jax.devices()):
        return {"xla_tpu_use_enhanced_launch_barrier": False}
    return None


def store_stack_specs(tree, axes) -> Any:
    """Pytree of PartitionSpecs sharding every leaf's LEADING axis over the
    store's shard axes (the rest replicated) — the layout of the sharded
    KNN datastore's per-shard index stacks: leaf shapes are
    ``(num_shards, blocks, ...)``, one shard slice per device."""
    axes = tuple(axes) if isinstance(axes, (tuple, list)) else (axes,)
    return jax.tree.map(lambda leaf: P(axes, *([None] * (leaf.ndim - 1))), tree)


def store_put(tree, mesh: Mesh, axes):
    """Place a store stack pytree on the mesh, leading axis sharded."""
    specs = store_stack_specs(tree, axes)
    return jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), tree, specs)


def store_shard_update(arr, i: int, new_slice) -> "jax.Array":
    """Replace shard ``i``'s leading-axis slice of an already-placed store
    stack IN PLACE of a full re-placement: only the devices whose buffer
    covers row ``i`` receive new bytes (``device_put`` of the one-shard
    slice); every other device keeps its existing buffer, and the pieces
    reassemble into a new Array with the same sharding.  This is what
    makes mutation placement O(changed shard), not O(store).

    ``new_slice`` must already be padded to the stack's cross-shard
    maxima: shape ``(1,) + arr.shape[1:]``.  Callers that grew the global
    geometry (more blocks, wider list bound) must fall back to a full
    ``store_put`` — a stale-shaped buffer cannot be patched.
    """
    new_slice = np.asarray(new_slice)
    if new_slice.shape != (1,) + arr.shape[1:]:
        raise ValueError(
            f"slice shape {new_slice.shape} does not match stack row "
            f"{(1,) + arr.shape[1:]} — geometry changed, use store_put")
    bufs = []
    for s in arr.addressable_shards:
        sl = s.index[0]
        lo = 0 if sl.start is None else sl.start
        hi = arr.shape[0] if sl.stop is None else sl.stop
        if lo <= i < hi:
            local = new_slice if hi - lo == 1 else None
            if local is None:
                # device holds several shard rows: patch row i inside its
                # existing local buffer
                local = np.asarray(s.data).copy()
                local[i - lo] = new_slice[0]
            bufs.append(jax.device_put(
                np.asarray(local, dtype=arr.dtype), s.device))
        else:
            bufs.append(s.data)
    return jax.make_array_from_single_device_arrays(
        arr.shape, arr.sharding, bufs)
