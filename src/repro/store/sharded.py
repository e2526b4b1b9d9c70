"""ShardedKNNStore — build-once-per-shard indexes, fan-out query with
on-device top-k reduction, delete/TTL tombstones, replica failover
(DESIGN.md §Sharded store, §10).

The paper's algorithms are single-machine; serving one big S to heavy
query traffic needs the standard distributed kNN-join decomposition
(Lu et al., "Efficient Processing of k Nearest Neighbor Joins using
MapReduce"): partition S row-wise, join every query block against every
partition, merge per-partition top-k.  Here that becomes:

* **Shard layout** — S is split into contiguous row ranges, one per
  position of a mesh axis (``store/mesh.make_store_mesh`` or any axis of
  an existing mesh).  Each shard builds its own device-resident
  :class:`~repro.core.engine.SparseKNNIndex` structures ONCE — the padded
  CSR blocks (BF), tile-inverted indexes (IIB) or threshold-independent
  superset indexes + tilemass (IIIB, in the GLOBAL datastore's
  dim-frequency-rank order so every shard prunes like the single-device
  build over the concatenated S).  The per-shard stacks are assembled
  into ``(num_shards, blocks, ...)`` arrays placed with the leading axis
  sharded (``store/mesh.store_stack_specs``) — shard i's stacks
  live on device i.

* **Replicas** — ``make_store_mesh(..., replicas=)`` adds a ``replica``
  axis; the store splits it into per-replica sub-meshes
  (``store/mesh.replica_submeshes``) and places the SAME stacks on each
  (the host mirror is the single source of truth; device replicas are a
  pure function of it).  Each fan-out dispatch routes to exactly one
  replica — half-open probes first, then live clean replicas round-robin
  (read scaling), dead replicas never — and a mid-dispatch
  ``ShardLostError``/``ReplicaLostError`` fails over to the next healthy
  replica WITHIN the same block, so callers see FULL results through a
  replica loss.  Health is a circuit breaker per replica
  (``runtime.fault.ReplicaHealth``); mutations write through to every
  non-dead replica and queue per-replica dirty shard sets for dead ones;
  :meth:`resync_replicas` is the anti-entropy pass that re-places the
  missed slices and re-admits the replica half-open;
  :meth:`verify_replicas` audits bit-parity.  With one replica all of
  this is inert and the PR 7 degraded/queued-behind-recovery semantics
  apply unchanged.

* **Fan-out query** — ``query(R)`` prepares each R block's device inputs
  once (``engine.prepare_r_block_inputs``; they depend only on R and on
  build-frozen global statistics) and replicates them into ONE jitted
  ``shard_map`` program: every shard runs the engine's scanned join over
  its local blocks (the same ``bf_scan_join``/``iib_scan_join``/
  ``iiib_scan_join`` dispatched on a single device), then the per-shard
  TopKStates are tree-reduced on device (``core.topk.tree_reduce_topk``,
  whose merge body is the shared ``insert_candidates`` epilogue of
  kernels/topk_merge).  One device dispatch and one host sync (the result
  pull) per R block — NOT per (R block, shard), and not per replica:
  there is no cross-replica collective — and zero query-time index
  builds.  Results are bit-identical to a single-device SparseKNNIndex
  over the concatenated S: shards hold ascending global-id ranges and the
  reduction always puts the lower shard on the tie-winning side, matching
  ``topk_update``'s first-offered-wins order.

* **Mutability** — ``add()`` appends a batch to the shard with the
  fewest live rows (balance policy), assigning fresh global ids and
  re-assembling only that shard's tail blocks; placement is INCREMENTAL
  (``store/mesh.store_shard_update``): while the padded stack
  geometry is unchanged, only the touched shard's slice ships
  host→device — ``StoreStats.placed_shards``/``placed_bytes`` make it
  observable — and only a grown geometry (more blocks, wider bound)
  re-places everything.  ``delete(ids)`` and TTL expiry (``add(...,
  ttl=)`` + ``expire(now)``) tombstone rows by per-row valid masks folded
  into the scan (one host→device mask upload, NO index rebuild);
  ``compact()`` — triggered automatically once a shard's dead fraction
  crosses ``auto_compact`` — is the real rebuild that reclaims
  tombstoned rows.  Global ids remain stable across all mutations (each
  shard carries an explicit id stack, which is why the scan joins take
  per-row ids rather than block offsets).  Once ``add()`` has landed a
  batch on a non-tail shard, global ids are no longer ascending in shard
  order, so versus a single-device index built in append order the
  scores stay exact but ids may differ where scores tie EXACTLY (tie
  preference follows shard order; BF's zero-overlap 0.0 scores are the
  common case — IIB/IIIB mask those to -inf).

IIIB's MinPruneScore threshold evolves shard-locally (each shard's scan
carries its own) — exactness is per-entry (Theorem 1 masks only entries
that provably cannot enter any top-k), so shard-local thresholds change
the work done, never the result.
"""
from __future__ import annotations

import dataclasses
import os
import time
from typing import Dict, List, Optional, Sequence, Set, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import iiib as iiib_mod
from repro.core import lsh as lsh_mod
from repro.core.bf import bf_scan_join
from repro.core.engine import (
    JoinResult,
    JoinSpec,
    JoinStats,
    SparseKNNIndex,
    _build_index_iib,
    _device_batch,
    _pad_block,
    _pad_feature_axis,
    _shape_stats,
    load_calibration,
    observe_thresholds,
    plan,
    prepare_r_block_inputs,
)
from repro.core.iib import iib_scan_join
from repro.core.iiib import iiib_scan_join
from repro.core.topk import TopKState, init_topk, tree_reduce_topk
from repro.obs import recorder as obs_recorder
from repro.obs import trace as obs_trace
from repro.runtime.fault import ReplicaHealth, ReplicaLostError, ShardLostError
from repro.sparse.format import SparseBatch, num_tiles
from repro.store.mesh import (
    make_store_mesh,
    replica_submeshes,
    store_put,
    store_shard_update,
    submesh_compiler_options,
)

P = jax.sharding.PartitionSpec


class StoreStats:
    """Store-lifetime work accounting (per-query numbers live in the
    JoinStats each ``query()`` returns).

    Since PR 10 every counter attribute is backed by a typed instrument in
    ``self.registry`` (repro.obs.registry) — the attribute API
    (``stats.queries += 1``, ``stats.saves``) is unchanged, but the same
    cells now feed the OpenMetrics text exposition (``stats.expose()``)
    next to the serving metrics, so the two views cannot drift."""

    # attribute → (instrument name, help)
    _COUNTERS = {
        "queries": ("store_queries", "query() calls"),
        "device_dispatches": ("store_device_dispatches",
                              "jitted fan-out launches (one per R block)"),
        "host_syncs": ("store_host_syncs", "result pulls (one per R block)"),
        "index_builds": ("store_index_builds",
                         "per-shard S-block index constructions"),
        "stack_uploads": ("store_stack_uploads",
                          "placement events (full OR incremental)"),
        "placed_shards": ("store_placed_shards",
                          "per-(replica, shard) slices shipped"),
        "placed_bytes": ("store_placed_bytes",
                         "bytes shipped host->device by placements"),
        "build_wall_s": ("store_build_wall_seconds",
                         "time inside build()/extend()"),
        "query_wall_s": ("store_query_wall_seconds", "time inside query()"),
        "deleted": ("store_rows_deleted", "rows tombstoned via delete()"),
        "expired": ("store_rows_expired", "rows tombstoned via TTL expiry"),
        "compactions": ("store_compactions",
                        "shard compactions (real rebuilds)"),
        "saves": ("store_saves", "checkpoint commits (save / save_dirty)"),
        "save_wall_s": ("store_save_wall_seconds", "time inside save()"),
        "shard_losses": ("store_shard_losses",
                         "shard copies marked lost by failures"),
        "degraded_queries": ("store_degraded_queries",
                             "queries served with shards missing"),
        "recoveries": ("store_recoveries",
                       "shards rebuilt from a checkpoint slice"),
        "recovery_wall_s": ("store_recovery_wall_seconds",
                            "time inside recover()"),
        "replica_losses": ("store_replica_losses",
                           "replicas marked dead (health transitions)"),
        "replica_failovers": ("store_replica_failovers",
                              "blocks served by a non-first-choice replica"),
        "resyncs": ("store_resyncs", "replica anti-entropy re-placements"),
        "resync_wall_s": ("store_resync_wall_seconds",
                          "time inside resync_replicas()"),
    }

    def __init__(self, registry=None):
        from repro.obs.registry import MetricRegistry

        object.__setattr__(self, "_inst", {})
        reg = registry or MetricRegistry()
        self.registry = reg
        for attr, (name, hlp) in self._COUNTERS.items():
            self._inst[attr] = reg.counter(name, hlp)
        # fan-out attempts routed to each replica (plain dict: labelled
        # per-replica counters stay host-side scratch)
        self.replica_dispatches: Dict[int, int] = {}

    def __getattr__(self, name):
        inst = object.__getattribute__(self, "__dict__").get("_inst", {}).get(name)
        if inst is not None:
            return inst.value
        raise AttributeError(
            f"{type(self).__name__!s} has no attribute {name!r}")

    def __setattr__(self, name, value):
        inst = self.__dict__.get("_inst", {}).get(name)
        if inst is not None:
            inst.set(value)
        else:
            object.__setattr__(self, name, value)

    def expose(self) -> str:
        """OpenMetrics-style text exposition of the store counters."""
        return self.registry.expose()


def fanout_program(algorithm: str, mesh, axes: Tuple[str, ...], *, rb: int,
                   k: int, dim: int, s_block: int, tile: int,
                   approx: bool = False):
    """The jitted ``shard_map`` program of one R block: shard-local
    scanned join → on-device tree reduction.  No cross-replica
    collective — each replica's program spans only its own devices
    (``mesh`` is one replica's sub-mesh), which is what lets a dead
    replica be routed around.

    ``approx`` compiles a variant whose locals prepend the band-lookup
    pass: the replicated R band keys membership-test each shard's
    ``lshk`` stack (``lsh.band_hits``) and the candidate mask ANDs
    into the shard's valid mask — still ONE dispatch per R block; the
    live-candidate counts ride back via ``all_gather``.  Arguments follow
    :meth:`ShardedKNNStore._fanout_args`."""
    alg, sb = algorithm, s_block
    nsh = int(np.prod([mesh.shape[a] for a in axes]))
    rep = P()
    shard = P(axes)
    state_spec = TopKState(scores=rep, ids=rep)

    if alg == "bf" and not approx:
        def local(bi, bv, bn, s_idx, s_val, s_nnz, s_ids, s_valid):
            br = SparseBatch(indices=bi, values=bv, nnz=bn, dim=dim)
            state = init_topk(rb, k)
            state = bf_scan_join(
                state, br, s_idx[0], s_val[0], s_nnz[0], s_ids[0], s_valid[0],
                dim=dim,
            )
            return tree_reduce_topk(state, axes, nsh)

        fn = jax.shard_map(
            local, mesh=mesh, check_vma=False,
            in_specs=(rep, rep, rep) + (shard,) * 5,
            out_specs=state_spec,
        )
    elif alg == "bf":
        def local(bi, bv, bn, rk, rr,
                  s_idx, s_val, s_nnz, s_ids, s_valid, s_lshk):
            br = SparseBatch(indices=bi, values=bv, nnz=bn, dim=dim)
            vm = jnp.logical_and(
                s_valid[0], lsh_mod.band_hits(rk, rr, s_lshk[0]))
            state = init_topk(rb, k)
            state = bf_scan_join(
                state, br, s_idx[0], s_val[0], s_nnz[0], s_ids[0], vm,
                dim=dim,
            )
            return (
                tree_reduce_topk(state, axes, nsh),
                jax.lax.all_gather(jnp.sum(vm), axes),
            )

        fn = jax.shard_map(
            local, mesh=mesh, check_vma=False,
            in_specs=(rep,) * 5 + (shard,) * 6,
            out_specs=(state_spec, rep),
        )
    elif alg == "iib" and not approx:
        def local(r_tiles, tiles, s_rows, s_vals, s_counts, s_ids, s_valid):
            state = init_topk(rb, k)
            state = iib_scan_join(
                state, r_tiles, tiles,
                s_rows[0], s_vals[0], s_counts[0], s_ids[0], s_valid[0],
                tile=tile, num_s=sb,
            )
            return tree_reduce_topk(state, axes, nsh)

        fn = jax.shard_map(
            local, mesh=mesh, check_vma=False,
            in_specs=(rep, rep) + (shard,) * 5,
            out_specs=state_spec,
        )
    elif alg == "iib":
        def local(r_tiles, tiles, rk, rr,
                  s_rows, s_vals, s_counts, s_ids, s_valid, s_lshk):
            vm = jnp.logical_and(
                s_valid[0], lsh_mod.band_hits(rk, rr, s_lshk[0]))
            state = init_topk(rb, k)
            state = iib_scan_join(
                state, r_tiles, tiles,
                s_rows[0], s_vals[0], s_counts[0], s_ids[0], vm,
                tile=tile, num_s=sb,
            )
            return (
                tree_reduce_topk(state, axes, nsh),
                jax.lax.all_gather(jnp.sum(vm), axes),
            )

        fn = jax.shard_map(
            local, mesh=mesh, check_vma=False,
            in_specs=(rep,) * 4 + (shard,) * 6,
            out_specs=(state_spec, rep),
        )
    elif not approx:
        def local(r_tiles, mwt, rv,
                  s_rows, s_vals, s_counts, s_mass, s_ids, s_valid):
            state = init_topk(rb, k)
            # each shard carries its OWN MinPruneScore — work-only
            # divergence from the sequential scan (see module docstring)
            state, thr, _, kept = iiib_scan_join(
                state, jnp.float32(-jnp.inf), r_tiles, mwt,
                s_rows[0], s_vals[0], s_counts[0], s_mass[0], s_ids[0],
                s_valid[0], rv, tile=tile, num_s=sb,
            )
            red = tree_reduce_topk(state, axes, nsh)
            return (
                red,
                jax.lax.all_gather(jnp.sum(kept), axes),
                jax.lax.all_gather(thr, axes),
            )

        fn = jax.shard_map(
            local, mesh=mesh, check_vma=False,
            in_specs=(rep,) * 3 + (shard,) * 6,
            out_specs=(state_spec, rep, rep),
        )
    else:
        def local(r_tiles, mwt, rv, rk, rr,
                  s_rows, s_vals, s_counts, s_mass, s_ids, s_valid, s_lshk):
            vm = jnp.logical_and(
                s_valid[0], lsh_mod.band_hits(rk, rr, s_lshk[0]))
            state = init_topk(rb, k)
            state, thr, _, kept = iiib_scan_join(
                state, jnp.float32(-jnp.inf), r_tiles, mwt,
                s_rows[0], s_vals[0], s_counts[0], s_mass[0], s_ids[0],
                vm, rv, tile=tile, num_s=sb,
            )
            red = tree_reduce_topk(state, axes, nsh)
            return (
                red,
                jax.lax.all_gather(jnp.sum(kept), axes),
                jax.lax.all_gather(thr, axes),
                jax.lax.all_gather(jnp.sum(vm), axes),
            )

        fn = jax.shard_map(
            local, mesh=mesh, check_vma=False,
            in_specs=(rep,) * 5 + (shard,) * 7,
            out_specs=(state_spec, rep, rep, rep),
        )
    return jax.jit(fn, compiler_options=submesh_compiler_options(mesh))


def _np_sparse_slice(idx, val, nnz, lo: int, hi: int, dim: int) -> SparseBatch:
    return SparseBatch(
        indices=jnp.asarray(idx[lo:hi]), values=jnp.asarray(val[lo:hi]),
        nnz=jnp.asarray(nnz[lo:hi]), dim=dim,
    )


class ShardedKNNStore:
    """Build-once-per-shard, query-many, mutable KNN datastore over a mesh.

    ``spec`` follows the engine's JoinSpec; open fields are resolved once,
    globally, so every shard uses the same algorithm and block geometry.
    ``axes`` names the mesh axis (or axes — they flatten into the shard
    ring) that S is partitioned over; defaults to a fresh 1-D ``('shard',)``
    mesh over the local devices (``replicas=`` forwards to
    ``make_store_mesh`` and adds the replica dimension).  A mesh axis
    named ``'replica'`` that is NOT in ``axes`` becomes the replication
    dimension.  ``replica_fail_threshold`` is the health tracker's
    consecutive-failure circuit-breaker threshold (a single shard-copy
    loss below it keeps the replica routable; a whole-replica loss kills
    it immediately).  ``use_kernel`` / ``warm_start`` are engine-only for
    now (the fused Pallas path and the sampled warm start assume a single
    resident device) and are rejected here.
    """

    def __init__(
        self,
        S: SparseBatch,
        spec: JoinSpec,
        mesh=None,
        axes: Optional[Sequence[str]] = None,
        num_shards: Optional[int] = None,
        auto_compact: float = 0.5,
        calibration=None,
        replicas: int = 1,
        replica_fail_threshold: int = 2,
        *,
        _row_ids: Optional[np.ndarray] = None,
        _alive: Optional[np.ndarray] = None,
        _deadline: Optional[np.ndarray] = None,
        _next_gid: Optional[int] = None,
        _frozen_rank: Optional[np.ndarray] = None,
        _shard_sizes: Optional[Sequence[int]] = None,
        _lsh_cfg: Optional[dict] = None,
    ):
        # The underscored keywords are the checkpoint-restore channel used
        # by :meth:`load`: per-row state (global ids, tombstone masks, TTL
        # deadlines, in concatenated shard order), the saved IIIB rank
        # (restored verbatim — recomputing would break bit-parity after
        # post-freeze mutations), and — when the loader's shard count
        # matches the save — the exact saved row split.
        t0 = time.perf_counter()
        if spec.use_kernel:
            raise ValueError("use_kernel is not supported by ShardedKNNStore yet")
        if spec.warm_start:
            raise ValueError("warm_start is not supported by ShardedKNNStore yet")
        if mesh is None:
            mesh = make_store_mesh(num_shards, replicas=replicas)
        self.mesh = mesh
        names = tuple(mesh.axis_names)
        if axes is None:
            if "replica" in names:
                axes = tuple(a for a in names if a != "replica")
            else:
                axes = (names[0],)
        self._axes = tuple(axes) if isinstance(axes, (tuple, list)) else (axes,)
        self.n_shards = int(np.prod([mesh.shape[a] for a in self._axes]))

        # replica dimension: one sub-mesh (and one placed stack set) per
        # replica; a single-replica store's "sub-mesh" is the mesh itself,
        # so the unreplicated path is byte-for-byte the old one
        if "replica" in names and "replica" not in self._axes:
            self._replica_meshes = replica_submeshes(mesh)
        else:
            self._replica_meshes = [mesh]
        self.n_replicas = len(self._replica_meshes)
        self.health = ReplicaHealth(
            self.n_replicas, fail_threshold=replica_fail_threshold)

        self.spec = spec
        self.dim = S.dim
        self.tile = spec.tile
        self.auto_compact = float(auto_compact)
        self.calibration = load_calibration(calibration)
        self.stats = StoreStats()

        n_s = S.num_vectors
        if n_s < self.n_shards:
            raise ValueError(f"S has {n_s} rows < {self.n_shards} shards")

        idx = np.asarray(S.indices)
        val = np.asarray(S.values)
        nnz = np.asarray(S.nnz)

        # resolve algorithm/geometry ONCE at store level (bit-parity with a
        # single-device build needs every shard on the same plan, including
        # the occupied-tile statistic the engine's own planning uses)
        f_mean = float(nnz.mean()) if n_s else 0.0
        p = plan((n_s, f_mean, self.dim), (n_s, f_mean, self.dim), spec,
                 occupied_tiles=self._occupied_tiles_of(idx),
                 calibration=self.calibration)
        self.algorithm = spec.algorithm or p.algorithm

        # contiguous balanced row ranges (ragged allowed: first n_s % shards
        # ranges get one extra row — np.array_split semantics); a restore
        # onto the SAME shard count reuses the exact saved split so block
        # geometry (and the dispatch shape) round-trips
        if _shard_sizes is not None and len(_shard_sizes) == self.n_shards:
            sizes = [int(s) for s in _shard_sizes]
            if sum(sizes) != n_s:
                raise ValueError("restored shard sizes do not cover S")
        else:
            sizes = [len(a) for a in np.array_split(np.arange(n_s), self.n_shards)]
        bounds = np.concatenate([[0], np.cumsum(sizes)])
        self.s_block = max(1, min(spec.s_block or p.s_block, min(sizes)))

        # IIIB superset order: the GLOBAL datastore's dim-frequency rank,
        # frozen into every shard (a shard-local rank would still be exact
        # but would not match the single-device parity reference)
        self._rank_np = None
        self._rank_dev = None
        if self.algorithm == "iiib":
            if _frozen_rank is not None:
                self._rank_np = np.asarray(_frozen_rank)
            else:
                freq = np.zeros(self.dim, np.int64)
                ok = idx < self.dim
                np.add.at(freq, np.where(ok, idx, 0).ravel(), ok.ravel())
                self._rank_np = iiib_mod.s_frequency_rank(freq)
            self._rank_dev = jnp.asarray(self._rank_np)

        # approximate tier: ONE LSHConfig (and projection) shared by every
        # shard and replica — identical band keys everywhere.  A restored
        # store takes the SAVED config (``_lsh_cfg``) so keys round-trip
        # even if the planner changes between versions.
        self._lsh: Optional[lsh_mod.LSHBands] = None
        if spec.accuracy == "approx":
            cfg = (lsh_mod.LSHConfig(**_lsh_cfg) if _lsh_cfg is not None
                   else lsh_mod.plan_lsh(spec.target_recall, seed=spec.seed))
            self._lsh = lsh_mod.LSHBands(cfg, self.dim)

        shard_spec = dataclasses.replace(
            spec, algorithm=self.algorithm, s_block=self.s_block
        )
        # per-shard engine indexes in streaming mode: host mirrors, block
        # metadata and tombstone bookkeeping — the DEVICE stacks are owned
        # by the store (assembled sharded over the mesh below)
        self.shards: List[SparseKNNIndex] = []
        self._gids: List[np.ndarray] = []
        # per-replica divergence tracking: shard copies whose device state
        # failed (_lost) or missed a write-through while dead (_replica_dirty)
        self._lost: List[Set[int]] = [set() for _ in range(self.n_replicas)]
        self._replica_dirty: List[Set[int]] = [
            set() for _ in range(self.n_replicas)]
        self._rr = 0                    # round-robin cursor over clean replicas
        self.fault_plan = None          # FaultPlan hook, consulted per dispatch
        for i in range(self.n_shards):
            lo, hi = int(bounds[i]), int(bounds[i + 1])
            shard = SparseKNNIndex.build(
                _np_sparse_slice(idx, val, nnz, lo, hi, self.dim), shard_spec,
                cache_device_blocks=False, frozen_rank=self._rank_np,
                calibration=self.calibration,
                lsh_cfg=self._lsh.cfg if self._lsh is not None else None,
            )
            if _alive is not None:
                shard._alive = np.asarray(_alive[lo:hi], bool).copy()
            if _deadline is not None:
                shard._deadline = np.asarray(_deadline[lo:hi], np.float64).copy()
            self.shards.append(shard)
            if _row_ids is not None:
                self._gids.append(np.asarray(_row_ids[lo:hi], np.int32).copy())
            else:
                self._gids.append(np.arange(lo, hi, dtype=np.int32))
        self._next_gid = n_s if _next_gid is None else int(_next_gid)

        # durability bookkeeping: which shards diverge from the last commit
        # (a fresh build has never been committed — everything is dirty)
        self._dirty: Set[int] = set(range(self.n_shards))
        self._dirty_rank = True
        self._last_save_dir: Optional[str] = None

        self._shard_arrays: List[Dict[str, np.ndarray]] = [
            self._assemble_shard(i) for i in range(self.n_shards)
        ]
        self._stacks: List[Optional[Dict[str, jax.Array]]] = (
            [None] * self.n_replicas)
        self._stacked_host: Optional[Dict[str, np.ndarray]] = None
        self._host_geometry: Optional[tuple] = None
        self._upload_stacks()
        self._query_fns: Dict[Tuple[int, int, bool], callable] = {}
        self.stats.build_wall_s += time.perf_counter() - t0

    # -- introspection -------------------------------------------------------

    @classmethod
    def build(cls, S: SparseBatch, spec: JoinSpec, **kw) -> "ShardedKNNStore":
        return cls(S, spec, **kw)

    @property
    def num_vectors(self) -> int:
        """Live rows across all shards."""
        return sum(s.live_rows for s in self.shards)

    @property
    def shard_rows(self) -> List[int]:
        """Per-shard live row counts (the balance policy's target)."""
        return [s.live_rows for s in self.shards]

    @property
    def num_blocks(self) -> int:
        return sum(s.num_blocks for s in self.shards)

    # -- stack assembly ------------------------------------------------------

    def _assemble_shard(self, i: int, from_block: int = 0) -> Dict[str, np.ndarray]:
        """One shard's stack slice as host arrays (block-stacked, not yet
        padded to the cross-shard maxima).  Tile-index construction counts
        into ``stats.index_builds`` — this is the per-shard analogue of the
        engine's ``_build_stacks`` and runs only at build/add/compact/
        refreeze time, never at query time.

        ``from_block`` retains the previously assembled prefix (the engine's
        tail-only rebuild semantics): ``add()`` passes the first block its
        ``extend()`` touched, so N chunked adds cost O(tail) index builds
        each, not O(shard).  A grown list bound pads the retained prefix
        (sentinel rows, zero values) — a pad is not a rebuild."""
        shard = self.shards[i]
        old = self._shard_arrays[i] if from_block > 0 else None
        out: Dict[str, np.ndarray] = {}
        sb = self.s_block
        if self.algorithm == "bf":
            f = shard._idx.shape[1]
            tail = shard._blocks[from_block:]
            parts = {
                "idx": [np.asarray(b.host.indices).astype(np.int32) for b in tail],
                "val": [np.asarray(b.host.values).astype(np.float32) for b in tail],
                "nnz": [np.asarray(b.host.nnz).astype(np.int32) for b in tail],
            }
            if old is not None:
                oi, ov = old["idx"][:from_block], old["val"][:from_block]
                if oi.shape[2] < f:
                    oi2, ov2 = _pad_feature_axis(
                        oi.reshape(-1, oi.shape[2]), ov.reshape(-1, ov.shape[2]),
                        f, self.dim,
                    )
                    oi = oi2.reshape(from_block, sb, f)
                    ov = ov2.reshape(from_block, sb, f)
                parts["idx"] = list(oi) + parts["idx"]
                parts["val"] = list(ov) + parts["val"]
                parts["nnz"] = list(old["nnz"][:from_block]) + parts["nnz"]
            out = {k: np.stack(v) for k, v in parts.items()}
        else:
            rank = shard._rank_dev if self.algorithm == "iiib" else None
            tail = shard._blocks[from_block:]
            m = max(blk.bound for blk in tail)
            if old is not None:
                m = max(m, old["rows"].shape[2])
            rows, vals, counts, mass = [], [], [], []
            if old is not None:
                orows, ovals = old["rows"][:from_block], old["vals"][:from_block]
                pad = m - orows.shape[2]
                if pad:
                    orows = np.concatenate(
                        [orows, np.full(orows.shape[:2] + (pad,), sb, orows.dtype)],
                        axis=2,
                    )
                    ovals = np.concatenate(
                        [ovals,
                         np.zeros(ovals.shape[:2] + (pad, self.tile), ovals.dtype)],
                        axis=2,
                    )
                rows, vals = list(orows), list(ovals)
                counts = list(old["counts"][:from_block])
                if self.algorithm == "iiib":
                    mass = list(old["mass"][:from_block])
            for blk in tail:
                ti = _build_index_iib(
                    _device_batch(blk.host), max_rows=m, tile=self.tile, rank=rank
                )
                self.stats.index_builds += 1
                blk.list_total = int(np.asarray(ti.counts).sum())
                rows.append(np.asarray(ti.rows))
                vals.append(np.asarray(ti.vals))
                counts.append(np.asarray(ti.counts))
                if self.algorithm == "iiib":
                    mass.append(blk.tilemass.astype(np.float32))
            out["rows"] = np.stack(rows)
            out["vals"] = np.stack(vals)
            out["counts"] = np.stack(counts)
            if self.algorithm == "iiib":
                out["mass"] = np.stack(mass)
        if self._lsh is not None:
            # band keys are per-row build state like the tilemass: the
            # retained prefix carries over, only tail blocks re-hash
            keys = [b.lshkeys for b in shard._blocks[from_block:]]
            if old is not None:
                keys = list(old["lshk"][:from_block]) + keys
            out["lshk"] = np.stack(keys)
        return out

    def _shard_ids_valid(self, i: int) -> Tuple[np.ndarray, np.ndarray]:
        """(B, s_block) global-id stack + valid mask of shard i (padding and
        tombstones folded in — the only arrays delete()/expire() touch).
        Replica-local losses are NOT folded here — :meth:`_replica_valid`
        zeroes the lost shards of one replica's copy at placement time, so
        a shard lost on one replica still answers from the others."""
        shard = self.shards[i]
        b, sb = shard.num_blocks, self.s_block
        ids = np.zeros(b * sb, np.int32)
        ids[: shard.n_s] = self._gids[i]
        valid = np.arange(b * sb) < shard.n_s
        valid[: shard.n_s] &= shard._alive
        return ids.reshape(b, sb), valid.reshape(b, sb)

    def _padded_geometry(self) -> tuple:
        """(b_max, width): the cross-shard padded stack geometry.  width is
        the feature bound (bf) or the inverted-list bound (iib/iiib).  While
        this is unchanged, a mutation's placement can be incremental."""
        b_max = max(s.num_blocks for s in self.shards)
        if self.algorithm == "bf":
            width = max(a["idx"].shape[2] for a in self._shard_arrays)
        else:
            width = max(a["rows"].shape[2] for a in self._shard_arrays)
        return (b_max, width)

    def _padded_shard(self, i: int, b_max: int, width: int) -> Dict[str, np.ndarray]:
        """Shard i's stack slice padded to the cross-shard maxima — one row
        of the stacked host mirror (and the unit ``store_shard_update``
        ships on the incremental placement path)."""
        sb = self.s_block
        a = self._shard_arrays[i]
        out: Dict[str, np.ndarray] = {}

        def pad_blocks(x: np.ndarray, fill) -> np.ndarray:
            pad = b_max - x.shape[0]
            if pad == 0:
                return x
            return np.concatenate(
                [x, np.full((pad,) + x.shape[1:], fill, x.dtype)]
            )

        if self.algorithm == "bf":
            idx2, val2 = a["idx"], a["val"]
            if idx2.shape[2] < width:
                flat_i = idx2.reshape(-1, idx2.shape[2])
                flat_v = val2.reshape(-1, val2.shape[2])
                flat_i, flat_v = _pad_feature_axis(flat_i, flat_v, width, self.dim)
                idx2 = flat_i.reshape(idx2.shape[0], sb, width)
                val2 = flat_v.reshape(val2.shape[0], sb, width)
            out["idx"] = pad_blocks(idx2, self.dim)
            out["val"] = pad_blocks(val2, 0.0)
            out["nnz"] = pad_blocks(a["nnz"], 0)
        else:
            rows, vals = a["rows"], a["vals"]
            pad = width - rows.shape[2]
            if pad:
                # a wider list bound is a pad, not a rebuild (sentinel
                # rows scatter into the discard slot, zero values)
                rows = np.concatenate(
                    [rows, np.full(rows.shape[:2] + (pad,), sb, rows.dtype)],
                    axis=2,
                )
                vals = np.concatenate(
                    [vals, np.zeros(vals.shape[:2] + (pad, self.tile), vals.dtype)],
                    axis=2,
                )
            out["rows"] = pad_blocks(rows, sb)
            out["vals"] = pad_blocks(vals, 0.0)
            out["counts"] = pad_blocks(a["counts"], 0)
            if self.algorithm == "iiib":
                out["mass"] = pad_blocks(a["mass"], 0.0)
        if self._lsh is not None:
            # pad blocks key 0: excluded by the valid mask, never by key
            out["lshk"] = pad_blocks(a["lshk"], 0)
        ids, valid = self._shard_ids_valid(i)
        out["ids"] = pad_blocks(ids, 0)
        out["valid"] = pad_blocks(valid, False)
        return out

    def _replica_valid(self, r: int) -> np.ndarray:
        """Replica r's valid mask: the host truth with r's lost shard
        copies zeroed (a degraded redrive on r must not read them)."""
        v = self._stacked_host["valid"]
        if not self._lost[r]:
            return v
        v = v.copy()
        for i in self._lost[r]:
            v[i] = False
        return v

    def _upload_stacks(self, shards: Optional[Set[int]] = None):
        """Place the per-shard slices on every replica.

        ``shards=None`` (build/recover/refreeze) re-stacks the host mirror
        and fully re-places each replica.  ``shards={...}`` (add/compact)
        is the incremental path: while the padded geometry is unchanged,
        only the named shards' rows are patched into the host mirror and
        shipped (``store_shard_update`` — per-shard buffers, not a full
        re-place); a geometry change falls back to the full path.  Dead
        replicas are skipped and accrue the touched shards in their dirty
        set — :meth:`resync_replicas` replays them."""
        geometry = self._padded_geometry()
        incremental = (
            shards is not None
            and self._stacked_host is not None
            and geometry == self._host_geometry
        )
        b_max, width = geometry
        if incremental:
            touched = sorted(set(shards))
            for i in touched:
                p = self._padded_shard(i, b_max, width)
                for k, v in p.items():
                    self._stacked_host[k][i] = v
            self._place(touched)
        else:
            padded = [
                self._padded_shard(i, b_max, width) for i in range(self.n_shards)
            ]
            self._stacked_host = {
                k: np.stack([p[k] for p in padded]) for k in padded[0]
            }
            self._host_geometry = geometry
            self._place(None)
        self._num_blocks_stacked = b_max
        self.stats.stack_uploads += 1
        self._refresh_plan_stats()
        # compiled query fns survive uploads: the program depends on stack
        # geometry only through argument shapes, which jax.jit keys on

    def _place(self, shards: Optional[Sequence[int]]):
        """Write-through to every replica: full placement (``shards=None``)
        or per-shard slice updates.  Dead replicas accrue dirty instead."""
        touched = set(range(self.n_shards)) if shards is None else set(shards)
        for r in range(self.n_replicas):
            if self.health.state(r) == ReplicaHealth.DEAD:
                self._replica_dirty[r] |= touched
                continue
            if shards is None or self._stacks[r] is None:
                self._place_replica_full(r)
            else:
                self._place_replica_shards(r, sorted(touched))

    def _place_replica_full(self, r: int):
        # host arrays straight into the sharded put: each device receives
        # only its own shard's slice (a device array made first would land
        # whole on the default device)
        tree = {k: v for k, v in self._stacked_host.items() if k != "valid"}
        tree["valid"] = self._replica_valid(r)
        self._stacks[r] = store_put(tree, self._replica_meshes[r], self._axes)
        self.stats.placed_shards += self.n_shards
        self.stats.placed_bytes += sum(
            int(v.size) * v.dtype.itemsize for v in tree.values())

    def _place_replica_shards(self, r: int, shards: Sequence[int]):
        st = dict(self._stacks[r])
        valid = self._replica_valid(r)
        for i in shards:
            for k, host in self._stacked_host.items():
                sl = valid[i:i + 1] if k == "valid" else host[i:i + 1]
                st[k] = store_shard_update(st[k], i, sl)
                self.stats.placed_bytes += (
                    int(np.prod(sl.shape)) * np.dtype(st[k].dtype).itemsize)
            self.stats.placed_shards += 1
        self._stacks[r] = st

    def _refresh_replica_valid(self, r: int):
        """Re-place ONLY replica r's valid mask (tombstones / lost folds)."""
        new_valid = store_put(
            self._replica_valid(r), self._replica_meshes[r], self._axes,
        )
        self._stacks[r] = dict(self._stacks[r], valid=new_valid)

    def _refresh_valid(self):
        """Tombstone fold: ONLY the valid mask re-uploads — no index arrays
        are touched, no tile index is rebuilt (``stats.index_builds`` is the
        observable).  Dead replicas are skipped (resync re-places the whole
        valid leaf anyway)."""
        b_max = self._num_blocks_stacked
        valid_parts = []
        for i in range(self.n_shards):
            _, valid = self._shard_ids_valid(i)
            pad = b_max - valid.shape[0]
            if pad:
                valid = np.concatenate([valid, np.zeros((pad, self.s_block), bool)])
            valid_parts.append(valid)
        self._stacked_host["valid"] = np.stack(valid_parts)
        for r in range(self.n_replicas):
            if self.health.state(r) != ReplicaHealth.DEAD:
                self._refresh_replica_valid(r)

    # -- fan-out query -------------------------------------------------------

    def _query_fn(self, rb: int, replica: int = 0, approx: bool = False):
        """The jitted fan-out program of one R block, cached per R-block
        size AND per replica sub-mesh AND per accuracy (see
        :func:`fanout_program`)."""
        key = (rb, replica, approx)
        if key not in self._query_fns:
            self._query_fns[key] = fanout_program(
                self.algorithm, self._replica_meshes[replica], self._axes,
                rb=rb, k=self.spec.k, dim=self.dim, s_block=self.s_block,
                tile=self.tile, approx=approx,
            )
        return self._query_fns[key]

    def _fanout_args(self, br, prep, r_valid, st, approx: bool,
                     rk=None, rr=None) -> tuple:
        """Assemble the positional args of ONE fan-out call in the exact
        order ``_query_fn``'s program expects them: R-side block inputs,
        then (approx) the replicated band keys/valids, then the replica's
        sharded stacks, then (approx) the shard LSH keys."""
        if self.algorithm == "bf":
            args = (br.indices, br.values, br.nnz)
        elif self.algorithm == "iib":
            args = (prep["r_tiles"], prep["tiles"])
        else:  # iiib
            args = (prep["r_tiles"], prep["mwt"], jnp.asarray(r_valid))
        if approx:
            args += (rk, rr)
        if self.algorithm == "bf":
            args += (st["idx"], st["val"], st["nnz"], st["ids"], st["valid"])
        elif self.algorithm == "iib":
            args += (st["rows"], st["vals"], st["counts"],
                     st["ids"], st["valid"])
        else:
            args += (st["rows"], st["vals"], st["counts"], st["mass"],
                     st["ids"], st["valid"])
        if approx:
            args += (st["lshk"],)
        return args

    def _occupied_tiles_of(self, idx: np.ndarray) -> int:
        """Dim-tiles the given rows touch (the engine's planner statistic)."""
        ok = idx < self.dim
        if not ok.any():
            return 1
        return int(np.unique(idx[ok] // self.spec.tile).size)

    def _refresh_plan_stats(self):
        """Cache the S-side planner statistics so the serving hot path
        (query → plan_for) does no O(shards × dim) host work — mirrors the
        engine's ``_refresh_plan_stats``; only mutations change these
        (every mutation path runs ``_upload_stacks``, which calls this)."""
        freq = np.zeros(self.dim, np.int64)
        for shard in self.shards:
            freq += shard.dim_freq
        (dims,) = np.nonzero(freq)
        self._occupied_tiles = (
            int(np.unique(dims // self.tile).size) if dims.size else 1
        )
        self._total_rows = sum(s.n_s for s in self.shards)
        self._f_mean = float(np.mean([s._f_mean for s in self.shards]))

    @property
    def occupied_tiles(self) -> int:
        """Dim-tiles the whole datastore touches (cached; planner statistic)."""
        return self._occupied_tiles

    def plan_for(self, R):
        n_r, f_r, _ = _shape_stats(R)
        spec = dataclasses.replace(
            self.spec, algorithm=self.algorithm, s_block=self.s_block
        )
        return plan((n_r, f_r, self.dim), (self._total_rows, self._f_mean, self.dim),
                    spec, occupied_tiles=self._occupied_tiles,
                    calibration=self.calibration)

    def _route_order(self) -> List[int]:
        """Replica preference for the next dispatch: half-open replicas
        first (the resync probe — one success re-admits them), then live
        replicas with no lost shard copies rotated round-robin (the read
        scaling), then live replicas carrying losses (fewest first — they
        serve degraded redrives only when nothing clean is left).  Dead
        replicas never appear."""
        clean = [r for r in self.health.live() if not self._lost[r]]
        lossy = sorted(
            (r for r in self.health.live() if self._lost[r]),
            key=lambda r: (len(self._lost[r]), r),
        )
        if clean:
            rot = self._rr % len(clean)
            self._rr += 1
            clean = clean[rot:] + clean[:rot]
        return self.health.half_open() + clean + lossy

    def _note_shard_failure(self, r: int, shard: int):
        """A dispatch on replica r lost ITS COPY of ``shard`` (replicated
        stores only): tombstone the copy, strike the replica's health, and
        queue the shard for anti-entropy resync.  Crossing the circuit-
        breaker threshold kills the whole replica (everything it holds is
        suspect → all shards dirty)."""
        if not 0 <= shard < self.n_shards:
            raise ValueError(f"shard {shard} out of range")
        if shard not in self._lost[r]:
            self._lost[r].add(shard)
            self._replica_dirty[r].add(shard)
            self.stats.shard_losses += 1
            obs_recorder.get_recorder().fault(
                "shard_copy_lost", replica=r, shard=shard)
        if self.health.record_failure(r):
            self.stats.replica_losses += 1
            self._replica_dirty[r] = set(range(self.n_shards))
            obs_recorder.get_recorder().fault(
                "replica_lost", replica=r, via="failure_threshold")
        else:
            self._refresh_replica_valid(r)

    def _mark_replica_dead(self, r: int):
        """Whole-replica loss (``ReplicaLostError``): bypass the failure
        threshold, stop routing to r, and mark every shard copy dirty."""
        if self.health.mark_dead(r):
            self.stats.replica_losses += 1
            obs_recorder.get_recorder().fault(
                "replica_lost", replica=r, via="ReplicaLostError")
        self._replica_dirty[r] = set(range(self.n_shards))

    def _prep_block(self, R: SparseBatch, r0: int, rb: int, approx: bool):
        """Host prep of the R block at ``r0``: the padded block, its valid
        mask, the R-side scan inputs and (approx) the band keys."""
        br, r_valid = _pad_block(R, r0, rb)
        prep = None
        if self.algorithm == "iib":
            prep = prepare_r_block_inputs(br, "iib", self.tile)
        elif self.algorithm == "iiib":
            prep = prepare_r_block_inputs(
                br, "iiib", self.tile, rank_dev=self._rank_dev)
        rk = rr = None
        if approx:
            # R band keys are host-hashed from the raw R slice (same
            # projection every shard/replica uses — identical keys to
            # the single-device engine) and replicated into the program
            stop = min(r0 + rb, R.num_vectors)
            rk_np = np.zeros((rb, self._lsh.cfg.n_bands), np.int32)
            rk_np[: stop - r0] = self._lsh.keys_host(
                np.asarray(R.indices[r0:stop]), np.asarray(R.values[r0:stop])
            )
            rr_np = r_valid.copy()
            rr_np[: stop - r0] &= np.asarray(R.nnz[r0:stop]) > 0
            rk, rr = jnp.asarray(rk_np), jnp.asarray(rr_np)
        return br, r_valid, prep, rk, rr

    def _launch_block(self, br, prep, r_valid, rb: int, approx: bool, rk, rr,
                      r0: int, allow_partial: bool):
        """Launch one R block's fan-out program on a replica, failing over
        as needed; returns (the program's asynchronous outputs, attempts,
        the replica that took the block)."""
        # failover loop: every failure tombstones a shard copy or kills
        # a replica, so attempts are bounded by the copy count.  On an
        # UNREPLICATED store `tried` stays empty and the loop marks the
        # shard lost, raises without allow_partial, and redrives degraded
        # with it.
        tried: Set[int] = set()
        last_err: Optional[Exception] = None
        attempts = 0
        while True:
            order = [r for r in self._route_order() if r not in tried]
            if not order:
                exhausted = attempts > self.n_replicas * (self.n_shards + 2)
                if not allow_partial or exhausted:
                    if isinstance(last_err, ShardLostError):
                        raise last_err
                    raise ShardLostError(
                        0,
                        "no live replica can serve a full fan-out; "
                        "recover() or resync_replicas()",
                    ) from last_err
                # degraded redrive: the best surviving copy answers with
                # its lost shards masked out
                tried.clear()
                order = self._route_order()
                if not order:
                    raise ShardLostError(0, "all replicas dead") from last_err
            r = order[0]
            attempts += 1
            probing = r in self.health.half_open()
            if probing:
                obs_recorder.get_recorder().record(
                    "half_open_probe", replica=r, r0=r0)
            self.stats.replica_dispatches[r] = (
                self.stats.replica_dispatches.get(r, 0) + 1)
            st = self._stacks[r]
            fn = self._query_fn(rb, r, approx)
            try:
                if self.fault_plan is not None:
                    self.fault_plan.on_dispatch(replica=r)
                out = fn(*self._fanout_args(br, prep, r_valid, st,
                                            approx, rk, rr))
                self.health.record_success(r)
                if tried:
                    self.stats.replica_failovers += 1
                    obs_recorder.get_recorder().fault(
                        "replica_failover", replica=r, r0=r0,
                        tried=sorted(tried))
                return out, attempts, r
            except ShardLostError as e:
                last_err = e
                if self.n_replicas == 1:
                    self._mark_lost(e.shard)
                    if not allow_partial:
                        raise
                else:
                    self._note_shard_failure(r, e.shard)
                    tried.add(r)
            except ReplicaLostError as e:
                if self.n_replicas == 1:
                    raise
                last_err = e
                self._mark_replica_dead(r)
                tried.add(r)

    def _pull_block(self, out, prep, rb: int, approx: bool, stats: JoinStats):
        """Pull one finished R block to the host: the IIIB threshold trace
        and kept-entry count, the approx candidate counts and the block's
        counters into ``stats``; returns its (scores, ids)."""
        cand_cnt = None
        if self.algorithm == "iiib":
            if approx:
                state, kept, thr, cand_cnt = out
            else:
                state, kept, thr = out
        elif approx:
            state, cand_cnt = out
        else:
            state = out
        if self.algorithm == "iiib":
            stats.list_entries += int(np.asarray(kept).sum())
            thr_np = np.asarray(thr)
            stats.min_prune_trace.append(thr_np)
            observe_thresholds(thr_np)
        if cand_cnt is not None:
            # the counts ride the SAME program (all_gather outputs) —
            # no extra dispatch, pulled with the block's result
            stats.candidate_rows += int(np.asarray(cand_cnt).sum())
            stats.scanned_rows += int(self._stacked_host["valid"].sum())
        stats.device_dispatches += 1
        stats.blocks += self._num_blocks_stacked * self.n_shards
        if self.algorithm == "bf":
            stats.dense_pairs += (
                rb * self.s_block * self._num_blocks_stacked * self.n_shards
            )
        else:
            # IIB scores the active-tile list; IIIB's dense product all T
            tiles = (int(prep["tiles"].shape[0]) if self.algorithm == "iib"
                     else num_tiles(self.dim, self.tile))
            stats.tiles_scored += (
                tiles * self._num_blocks_stacked * self.n_shards)
            if self.algorithm == "iib":
                stats.list_entries += sum(
                    blk.list_total for s in self.shards for blk in s._blocks
                )
        stats.host_syncs += 1                # the R block's result pull
        return np.asarray(state.scores), np.asarray(state.ids)

    def query(
        self,
        R: SparseBatch,
        stats: Optional[JoinStats] = None,
        allow_partial: bool = False,
        accuracy: Optional[str] = None,
    ) -> JoinResult:
        """R ⋈_KNN S over all shards.  Returns stable global S ids.

        One device dispatch (the jitted fan-out program) and one host sync
        (the result pull) per R block, independent of the shard count.
        Replicated stores route each block to ONE replica (see
        ``_route_order``); a mid-dispatch ``ShardLostError``/
        ``ReplicaLostError`` fails over to the next healthy replica within
        the same block, so the caller still gets FULL, bit-identical
        results — failover is invisible except in
        ``stats.replica_failovers``.

        ``allow_partial`` is the degraded serving mode: when no replica can
        serve a full fan-out (unreplicated shard loss, or losses on every
        live replica) the query proceeds over the best surviving copy —
        same fan-out program, the lost shards' valid masks zeroed — and the
        result carries ``missing_shards``.  Without it, a loss no replica
        covers raises :class:`ShardLostError` (callers recover() first,
        then retry — the queued-behind-recovery policy).

        ``accuracy`` overrides the spec per query (the serving scheduler's
        per-request knob): ``"approx"`` routes through the band-lookup
        fan-out variant — same dispatch count, candidate mask folded into
        each shard's valid mask on device; ``"exact"`` on an approx-built
        store uses the byte-identical exact program.
        """
        t_q = time.perf_counter()
        stats = stats if stats is not None else JoinStats()
        if R.dim != self.dim:
            raise ValueError(f"dim mismatch: store has {self.dim}, got {R.dim}")
        acc = accuracy if accuracy is not None else self.spec.accuracy
        if acc not in ("exact", "approx"):
            raise ValueError(f"unknown accuracy {acc!r}")
        approx = acc == "approx"
        if approx and self._lsh is None:
            raise ValueError(
                "store was built without the LSH band tier; build with "
                "target_recall (or accuracy='approx') to enable approx queries")
        glost = self.lost_shards
        if glost and not allow_partial:
            raise ShardLostError(
                glost[0],
                f"shard(s) {list(glost)} lost on every replica; recover() "
                "or pass allow_partial=True",
            )
        n_r = R.num_vectors
        rb = min(self.spec.r_block or self.plan_for(R).r_block, n_r)
        out_scores, out_ids = [], []
        served_missing: Set[int] = set()
        for r0 in range(0, n_r, rb):
            # one span per dispatched R block (parented to the serving
            # batch/dispatch span when one is active on this thread), split
            # into host prep, the asynchronous launch, the one wait for the
            # device, and the result pull
            with obs_trace.span("store.r_block", r0=r0,
                                algorithm=self.algorithm) as _sp:
                with obs_trace.span("store.prep"):
                    br, r_valid, prep, rk, rr = self._prep_block(R, r0, rb, approx)
                with obs_trace.span("store.launch"):
                    out, attempts, r = self._launch_block(
                        br, prep, r_valid, rb, approx, rk, rr, r0, allow_partial)
                served_missing |= self._lost[r]
                with obs_trace.span("store.wait"):
                    jax.block_until_ready(out)
                with obs_trace.span("store.pull"):
                    scores, ids = self._pull_block(out, prep, rb, approx, stats)
                    out_scores.append(scores[r_valid])
                    out_ids.append(ids[r_valid])
                if _sp is not None:
                    _sp.attrs["attempts"] = attempts
        dt = time.perf_counter() - t_q
        stats.query_wall_s += dt
        self.stats.query_wall_s += dt
        self.stats.queries += 1
        self.stats.device_dispatches += stats.device_dispatches
        self.stats.host_syncs += stats.host_syncs
        if self.n_replicas == 1:
            missing = tuple(sorted(self._lost[0]))
        else:
            missing = tuple(sorted(served_missing))
        if missing:
            self.stats.degraded_queries += 1
        return JoinResult(
            scores=jnp.asarray(np.concatenate(out_scores)),
            ids=jnp.asarray(np.concatenate(out_ids)),
            stats=stats,
            missing_shards=missing,
        )

    # -- mutation ------------------------------------------------------------

    def add(self, S_new: SparseBatch, ttl: Optional[float] = None,
            now: Optional[float] = None) -> np.ndarray:
        """Append a batch to the datastore; returns the new rows' global ids.

        Balance policy: the whole batch lands on the shard with the fewest
        live rows (chunked callers — the serving shape — converge to
        balanced shards; a single giant batch should be pre-chunked).  Only
        the target shard's TAIL blocks rebuild their tile indexes (the
        engine's extend() semantics); the retained prefix and the other
        shards' index arrays are reused (padded if the list bound grew).
        Placement writes through to every live replica and is INCREMENTAL
        while the padded stack geometry holds: only the target shard's
        slice ships (``placed_shards`` grows by the replica count, not
        replicas × shards).  ``ttl`` attaches an expiry deadline
        ``now + ttl`` consumed by :meth:`expire`.
        """
        if S_new.dim != self.dim:
            raise ValueError(f"dim mismatch: store has {self.dim}, got {S_new.dim}")
        t0 = time.perf_counter()
        glost = set(self.lost_shards)
        candidates = [i for i in range(self.n_shards) if i not in glost]
        if not candidates:
            raise ShardLostError(min(glost), "all shards lost")
        tgt = min(candidates, key=lambda i: self.shards[i].live_rows)
        deadline = None
        if ttl is not None:
            deadline = (time.time() if now is None else now) + float(ttl)
        from_block = self.shards[tgt].n_s // self.s_block
        self.shards[tgt].extend(S_new, deadline=deadline)
        n_new = S_new.num_vectors
        gids = np.arange(self._next_gid, self._next_gid + n_new, dtype=np.int32)
        self._gids[tgt] = np.concatenate([self._gids[tgt], gids])
        self._next_gid += n_new
        self._dirty.add(tgt)
        self._shard_arrays[tgt] = self._assemble_shard(tgt, from_block=from_block)
        self._upload_stacks(shards={tgt})
        self.stats.build_wall_s += time.perf_counter() - t0
        return gids

    def delete(self, ids) -> int:
        """Tombstone rows by global id across shards — a valid-mask update,
        never an index rebuild (until :meth:`compact`)."""
        ids = np.atleast_1d(np.asarray(ids, np.int64))
        newly = 0
        for i, shard in enumerate(self.shards):
            local = np.nonzero(np.isin(self._gids[i], ids))[0]
            if local.size:
                n = shard.delete(local)
                if n:
                    self._dirty.add(i)
                newly += n
        if newly:
            self.stats.deleted += newly
            if not self._maybe_compact():
                self._refresh_valid()
        return newly

    def expire(self, now: Optional[float] = None) -> int:
        """Tombstone rows whose TTL deadline has passed."""
        now = time.time() if now is None else now
        newly = 0
        for i, shard in enumerate(self.shards):
            n = shard.expire(now)
            if n:
                self._dirty.add(i)
            newly += n
        if newly:
            self.stats.expired += newly
            if not self._maybe_compact():
                self._refresh_valid()
        return newly

    def _maybe_compact(self) -> bool:
        """Compact shards over the dead-fraction threshold.  Returns True
        when a compaction ran — its stack upload already carries every
        touched shard's fresh valid mask, so the caller skips
        _refresh_valid()."""
        over = [
            i for i, s in enumerate(self.shards)
            if s.dead_rows and s.dead_rows / s.n_s >= self.auto_compact
        ]
        if over:
            self.compact(shards=over)
            return True
        return False

    def compact(self, shards: Optional[Sequence[int]] = None) -> int:
        """Physically reclaim tombstoned rows — the real rebuild that
        delete()/expire() defer.  Re-assembles only the compacted shards'
        stack slices (and, geometry permitting, re-places only those
        slices); global ids of surviving rows are unchanged (the store
        owns the id map).  A fully-dead shard compacts to the engine's
        single tombstoned placeholder row (its id kept in the map, never
        offered) and becomes the balance policy's next add() target."""
        t0 = time.perf_counter()
        removed = 0
        targets = range(self.n_shards) if shards is None else shards
        changed = []
        for i in targets:
            shard = self.shards[i]
            if shard.dead_rows == 0:
                continue
            removed += shard.compact()
            # follow the engine's surviving-row choice exactly (incl. the
            # placeholder row a fully-dead shard keeps)
            self._gids[i] = self._gids[i][shard.last_compact_keep]
            changed.append(i)
            self._dirty.add(i)
            self._shard_arrays[i] = self._assemble_shard(i)
        if changed:
            self.stats.compactions += len(changed)
            # compaction tombstone state changed OTHER shards' masks never —
            # but a shrunken b_max changes the geometry; _upload_stacks
            # falls back to the full path in that case
            self._upload_stacks(shards=set(changed))
            # the incremental path patches only the compacted shards; every
            # other shard's valid mask is already current (compaction only
            # rewrites its own rows)
        self.stats.build_wall_s += time.perf_counter() - t0
        return removed

    def refreeze(self) -> "ShardedKNNStore":
        """Recompute the IIIB superset rank from the LIVE rows of every
        shard (global frequencies) and reassemble all stacks — the store
        face of ``SparseKNNIndex.refreeze()``."""
        if self.algorithm != "iiib":
            return self
        t0 = time.perf_counter()
        freq = np.zeros(self.dim, np.int64)
        for shard in self.shards:
            ok = (shard._idx < self.dim) & shard._alive[:, None]
            np.add.at(freq, np.where(ok, shard._idx, 0).ravel(), ok.ravel())
        self._rank_np = iiib_mod.s_frequency_rank(freq)
        self._rank_dev = jnp.asarray(self._rank_np)
        self._dirty_rank = True
        for i, shard in enumerate(self.shards):
            shard.refreeze(frozen_rank=self._rank_np)
            self._dirty.add(i)
            self._shard_arrays[i] = self._assemble_shard(i)
        self._upload_stacks()
        self.stats.build_wall_s += time.perf_counter() - t0
        return self

    # -- durability (DESIGN.md §9) -------------------------------------------

    def _shard_key(self, i: int) -> str:
        return f"shard_{i:05d}"

    def _ckpt_tree(self) -> dict:
        """The persisted state: per-shard host mirrors (rows exactly as the
        engine holds them, tombstones included), tombstone/TTL masks, the
        global-id stacks, and the frozen IIIB rank.  ONE logical copy —
        replicas are a placement property, not data (device stacks, tile
        indexes and planner statistics are pure functions of this tree and
        rebuild / fan out on load)."""
        tree = {}
        for i, shard in enumerate(self.shards):
            tree[self._shard_key(i)] = {
                "idx": shard._idx.astype(np.int32),
                "val": shard._val.astype(np.float32),
                "nnz": shard._nnz.astype(np.int32),
                "alive": shard._alive,
                "deadline": shard._deadline,
                "gids": self._gids[i].astype(np.int32),
            }
        if self._rank_np is not None:
            tree["rank"] = self._rank_np
        return tree

    def _meta(self) -> dict:
        return {
            "spec": dataclasses.asdict(self.spec),
            "algorithm": self.algorithm,
            "s_block": self.s_block,
            "dim": self.dim,
            "n_shards": self.n_shards,
            "shard_rows": [int(s.n_s) for s in self.shards],
            "next_gid": int(self._next_gid),
            "auto_compact": self.auto_compact,
            # band-index config persists like the frozen IIIB rank: the
            # saved parameters win on restore, so keys round-trip
            "lsh": (dataclasses.asdict(self._lsh.cfg)
                    if self._lsh is not None else None),
        }

    def save(self, directory: str, extra: Optional[dict] = None,
             dirty_only: bool = False) -> str:
        """Commit the store to ``directory`` as a new checkpoint step
        (atomic two-phase commit via ``repro.checkpoint``).  Returns the
        committed path.  ``extra`` rides along in the manifest (the kNN-LM
        example persists its id→token value map this way).

        ``dirty_only`` (what :meth:`save_dirty` passes) hard-links every
        shard untouched since the last commit from that commit's dir
        instead of re-serializing it — an incremental save costs O(dirty
        shards) writes, not O(store).
        """
        from repro.checkpoint import ckpt as _ckpt

        t0 = time.perf_counter()
        with obs_trace.span("ckpt.save", dirty_only=dirty_only):
            ls = _ckpt.latest_step(directory)
            step = 0 if ls is None else ls + 1
            link_from = link_paths = None
            if dirty_only and self._last_save_dir is not None:
                clean = [i for i in range(self.n_shards) if i not in self._dirty]
                link_paths = set()
                for i in clean:
                    key = self._shard_key(i)
                    for leaf in ("idx", "val", "nnz", "alive", "deadline", "gids"):
                        link_paths.add(f"['{key}']['{leaf}']")
                if self._rank_np is not None and not self._dirty_rank:
                    link_paths.add("['rank']")
                link_from = self._last_save_dir
            path = _ckpt.save(
                directory, step, self._ckpt_tree(),
                extra={"store": self._meta(), **(extra or {})},
                link_from=link_from, link_paths=link_paths,
            )
            self._dirty.clear()
            self._dirty_rank = False
            self._last_save_dir = path
            self.stats.saves += 1
            self.stats.save_wall_s += time.perf_counter() - t0
        return path

    def save_dirty(self, directory: str, extra: Optional[dict] = None) -> str:
        """Incremental :meth:`save`: only shards touched by add/delete/
        expire/compact/refreeze since the last commit are re-serialized."""
        return self.save(directory, extra=extra, dirty_only=True)

    @classmethod
    def load(
        cls,
        directory: str,
        mesh=None,
        axes: Optional[Sequence[str]] = None,
        num_shards: Optional[int] = None,
        step: Optional[int] = None,
        calibration=None,
        replicas: int = 1,
        replica_fail_threshold: int = 2,
    ) -> "ShardedKNNStore":
        """Warm-restart a saved store: host mirrors, spec, frozen IIIB
        rank, id stacks and tombstone state come from the newest valid
        checkpoint (``step`` pins one); device stacks and tile indexes are
        rebuilt, elastically resharded onto whatever mesh the loader
        passes.  ``replicas=`` fans the single persisted logical copy out
        onto a replicated mesh — replication is a placement property, so a
        save from an unreplicated store restores replicated (and vice
        versa) without any on-disk difference.  Queries after load are
        bit-identical to the saved store (concatenated row order — the
        tie-winning order — is preserved across any contiguous re-split).
        The manifest ``extra`` is exposed as ``store.loaded_extra``.
        """
        from repro.checkpoint import ckpt as _ckpt

        if step is None:
            step = _ckpt.latest_step(directory)
            if step is None:
                raise FileNotFoundError(f"no valid checkpoint in {directory}")
        _sp = obs_trace.start_span("ckpt.load", step=step)
        arrays, extra = _ckpt.load_arrays(directory, step)
        meta = extra["store"]
        n_saved = int(meta["n_shards"])

        def leaf(i: int, name: str) -> np.ndarray:
            return arrays[f"['shard_{i:05d}']['{name}']"]

        # concatenate per-shard mirrors IN SHARD ORDER (this order is the
        # id-tie-winning order; any contiguous re-split preserves it),
        # padding the ragged feature axis to the widest shard
        f_max = max(leaf(i, "idx").shape[1] for i in range(n_saved))
        idxs, vals = [], []
        for i in range(n_saved):
            ii, vv = leaf(i, "idx"), leaf(i, "val")
            if ii.shape[1] < f_max:
                ii, vv = _pad_feature_axis(ii, vv, f_max, int(meta["dim"]))
            idxs.append(ii)
            vals.append(vv)
        S = SparseBatch(
            indices=jnp.asarray(np.concatenate(idxs)),
            values=jnp.asarray(np.concatenate(vals)),
            nnz=jnp.asarray(np.concatenate(
                [leaf(i, "nnz") for i in range(n_saved)])),
            dim=int(meta["dim"]),
        )
        spec = dataclasses.replace(
            JoinSpec(**meta["spec"]),
            algorithm=meta["algorithm"], s_block=int(meta["s_block"]),
        )
        store = cls(
            S, spec, mesh=mesh, axes=axes, num_shards=num_shards,
            auto_compact=float(meta["auto_compact"]), calibration=calibration,
            replicas=replicas, replica_fail_threshold=replica_fail_threshold,
            _row_ids=np.concatenate([leaf(i, "gids") for i in range(n_saved)]),
            _alive=np.concatenate([leaf(i, "alive") for i in range(n_saved)]),
            _deadline=np.concatenate(
                [leaf(i, "deadline") for i in range(n_saved)]),
            _next_gid=int(meta["next_gid"]),
            _frozen_rank=arrays.get("['rank']"),
            _shard_sizes=[int(r) for r in meta["shard_rows"]],
            _lsh_cfg=meta.get("lsh"),
        )
        # When the loaded layout matches the saved one, the in-memory state
        # EQUALS the loaded commit: nothing is dirty, and incremental saves
        # may hard-link from it.  An ELASTIC load (different shard count /
        # split) re-partitioned the rows, so the saved per-shard leaves no
        # longer correspond to this store's shards — everything stays dirty
        # and the next save is a full one.
        same_layout = (
            store.n_shards == n_saved
            and [s.n_s for s in store.shards]
            == [int(r) for r in meta["shard_rows"]]
        )
        if same_layout:
            store._dirty.clear()
            store._dirty_rank = False
            store._last_save_dir = os.path.join(directory, f"step_{step:08d}")
        store.loaded_extra = {k: v for k, v in extra.items() if k != "store"}
        obs_trace.end_span(_sp, n_shards=store.n_shards)
        return store

    # -- shard loss + recovery -----------------------------------------------

    @property
    def lost_shards(self) -> Tuple[int, ...]:
        """Shards with NO readable copy: lost on every replica (a dead
        replica counts as having lost everything it held).  These need
        :meth:`recover` (checkpoint slices); replica-local losses don't
        appear here — failover covers them until :meth:`resync_replicas`
        repairs the copy."""
        eff: Optional[Set[int]] = None
        for r in range(self.n_replicas):
            if self.health.state(r) == ReplicaHealth.DEAD:
                lost = set(range(self.n_shards))
            else:
                lost = self._lost[r]
            eff = set(lost) if eff is None else (eff & lost)
        return tuple(sorted(eff))

    @property
    def dead_replicas(self) -> Tuple[int, ...]:
        return tuple(self.health.dead())

    @property
    def needs_resync(self) -> bool:
        """True when some replica's device state diverges from the host
        mirror (dead, dirty from missed write-throughs, or carrying lost
        shard copies) — the scheduler's cue to kick
        :meth:`resync_replicas` behind traffic.  Always False
        unreplicated: a single-replica loss is data loss (recover())."""
        if self.n_replicas == 1:
            return False
        return any(
            self.health.state(r) == ReplicaHealth.DEAD
            or self._replica_dirty[r] or self._lost[r]
            for r in range(self.n_replicas)
        )

    def _mark_lost(self, i: int, replica: Optional[int] = None) -> None:
        """Mark shard i failed on ``replica`` (default: EVERY replica —
        data loss).  Its valid mask zeroes on the affected copies (degraded
        queries see no candidates from them) until :meth:`recover`
        (globally lost) or :meth:`resync_replicas` (replica-local)."""
        if not 0 <= i < self.n_shards:
            raise ValueError(f"shard {i} out of range")
        targets = range(self.n_replicas) if replica is None else (replica,)
        newly = False
        for r in targets:
            if i not in self._lost[r]:
                self._lost[r].add(i)
                self._replica_dirty[r].add(i)
                newly = True
        if newly:
            self.stats.shard_losses += 1
            obs_recorder.get_recorder().fault(
                "shard_lost", shard=i,
                replica="all" if replica is None else replica)
            for r in targets:
                if self.health.state(r) != ReplicaHealth.DEAD:
                    self._refresh_replica_valid(r)

    def mark_lost(self, i: int, replica: Optional[int] = None) -> None:
        self._mark_lost(i, replica=replica)

    def recover(self, directory: str, step: Optional[int] = None) -> Tuple[int, ...]:
        """Rebuild every GLOBALLY lost shard from its checkpoint slice and
        rejoin it to the fan-out.  Reads ONLY the lost shards' leaves
        (sha-verified); the surviving shards' state — including mutations
        since the save — is untouched.  Mutations the lost shard took
        after the checkpoint are gone (that is what 'lost' means); its
        global ids are stable because the id stack is part of the slice.
        Replica-LOCAL losses are not recovered here (resync_replicas
        repairs them from the host mirror) — but the full re-placement at
        the end refreshes every live replica.  Returns the recovered
        shard indexes.
        """
        from repro.checkpoint import ckpt as _ckpt

        glost = set(self.lost_shards)
        if not glost:
            return ()
        t0 = time.perf_counter()
        _sp = obs_trace.start_span("recover", shards=sorted(glost))
        if step is None:
            step = _ckpt.latest_step(directory)
            if step is None:
                raise FileNotFoundError(f"no valid checkpoint in {directory}")
        recovered = []
        shard_spec = dataclasses.replace(
            self.spec, algorithm=self.algorithm, s_block=self.s_block
        )
        for i in sorted(glost):
            key = self._shard_key(i)
            arrays, extra = _ckpt.load_arrays(
                directory, step, prefix=f"['{key}']"
            )
            if int(extra["store"]["n_shards"]) != self.n_shards:
                raise ValueError(
                    "checkpoint shard layout does not match the live store "
                    f"({extra['store']['n_shards']} vs {self.n_shards}); "
                    "use ShardedKNNStore.load() for elastic restarts"
                )
            g = lambda name: arrays[f"['{key}']['{name}']"]
            idx, val, nnz = g("idx"), g("val"), g("nnz")
            shard = SparseKNNIndex.build(
                _np_sparse_slice(idx, val, nnz, 0, len(nnz), self.dim),
                shard_spec, cache_device_blocks=False,
                frozen_rank=self._rank_np, calibration=self.calibration,
                lsh_cfg=self._lsh.cfg if self._lsh is not None else None,
            )
            shard._alive = np.asarray(g("alive"), bool).copy()
            shard._deadline = np.asarray(g("deadline"), np.float64).copy()
            self.shards[i] = shard
            self._gids[i] = np.asarray(g("gids"), np.int32).copy()
            recovered.append(i)
        for r in range(self.n_replicas):
            self._lost[r].difference_update(recovered)
        for i in recovered:
            # post-checkpoint mutations on the shard were lost with it, so
            # its in-memory state matches the slice we just read — but it
            # may DIFFER from the latest commit if that commit is newer, so
            # conservatively re-serialize it on the next incremental save
            self._dirty.add(i)
            self._shard_arrays[i] = self._assemble_shard(i)
        self._upload_stacks()
        self.stats.recoveries += len(recovered)
        self.stats.recovery_wall_s += time.perf_counter() - t0
        obs_trace.end_span(_sp, recovered=len(recovered))
        obs_recorder.get_recorder().record(
            "shard_recovered", shards=recovered,
            wall_s=round(time.perf_counter() - t0, 4))
        return tuple(recovered)

    # -- replica resync (DESIGN.md §10) --------------------------------------

    def resync_replicas(self) -> Tuple[int, ...]:
        """Anti-entropy pass: re-place every diverged replica's device
        state from the host mirror (the single source of truth every
        replica's stacks are a pure function of) and re-admit dead
        replicas HALF-OPEN — one successful probe dispatch returns them to
        the rotation, a failed probe drops them straight back to dead.

        Shape-stable divergence (missed write-throughs, lost shard copies)
        re-places only the dirty shards' slices; a replica that missed a
        geometry change gets a full re-placement.  No-op on an
        unreplicated store: with one copy there is nothing to resync FROM
        (that is :meth:`recover`'s job).  Returns the resynced replicas.
        """
        if self.n_replicas == 1:
            return ()
        t0 = time.perf_counter()
        _sp = obs_trace.start_span("resync_replicas")
        resynced = []
        for r in range(self.n_replicas):
            was_dead = self.health.state(r) == ReplicaHealth.DEAD
            pending = self._replica_dirty[r] | self._lost[r]
            if not was_dead and not pending:
                continue
            self._lost[r].clear()
            self._replica_dirty[r].clear()
            stale_shape = self._stacks[r] is None or any(
                tuple(self._stacks[r][k].shape) != v.shape
                for k, v in self._stacked_host.items()
            )
            if stale_shape or len(pending) >= self.n_shards:
                self._place_replica_full(r)
            else:
                self._place_replica_shards(r, sorted(pending))
                # divergence may include tombstone flips that happened while
                # the replica was out — the valid leaf re-places wholesale
                self._refresh_replica_valid(r)
            if was_dead:
                self.health.mark_resynced(r)
            resynced.append(r)
            self.stats.resyncs += 1
        if resynced:
            self.stats.resync_wall_s += time.perf_counter() - t0
            obs_recorder.get_recorder().record(
                "replicas_resynced", replicas=resynced,
                wall_s=round(time.perf_counter() - t0, 4))
        obs_trace.end_span(_sp, resynced=len(resynced))
        return tuple(resynced)

    def verify_replicas(self) -> bool:
        """Bit-parity audit: every non-dead replica's device stacks must
        equal the host mirror (index arrays, ids, and that replica's valid
        fold).  Raises ``ValueError`` naming the first divergent
        (replica, leaf); returns True when all replicas agree."""
        for r in range(self.n_replicas):
            if self.health.state(r) == ReplicaHealth.DEAD:
                continue
            for k, host in self._stacked_host.items():
                want = jnp.asarray(
                    self._replica_valid(r) if k == "valid" else host)
                got = self._stacks[r][k]
                if not np.array_equal(np.asarray(got), np.asarray(want)):
                    raise ValueError(
                        f"replica {r} leaf {k!r} diverges from the host "
                        "mirror (resync_replicas() repairs this)")
        return True
