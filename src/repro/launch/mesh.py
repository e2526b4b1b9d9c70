"""Production mesh construction.

A FUNCTION, not a module constant — importing this module never touches
jax device state (the dry-run sets XLA_FLAGS before any jax init).

Mesh semantics (DESIGN.md §5):
  pod   — slow inter-pod links (DCI); pure data parallelism, optionally
          int8-compressed gradient all-reduce.
  data  — intra-pod DP/FSDP axis (batch + parameter sharding).
  model — tensor/expert/sequence parallel axis.
"""
from __future__ import annotations

import jax


def make_mesh(shape, axis_names):
    """``jax.make_mesh`` with every axis ``Auto`` (sharding propagated by
    the compiler, as the shard_map programs and pjit'd steps expect)."""
    return jax.make_mesh(
        shape, axis_names,
        axis_types=(jax.sharding.AxisType.Auto,) * len(axis_names))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over the real local devices (tests / CPU examples)."""
    n = len(jax.devices())
    assert data * model <= n, f"need {data * model} devices, have {n}"
    return make_mesh((data, model), ("data", "model"))


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
    import numpy as np
    from jax.sharding import Mesh

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


def dp_axes(mesh) -> tuple:
    """Data-parallel axes: ('pod','data') on multi-pod, ('data',) otherwise."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)
