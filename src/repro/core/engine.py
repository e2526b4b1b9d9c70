"""Build-once/query-many KNN join engine with a device-resident hot path
(DESIGN.md §3).

The paper's block nested-loop driver (Algorithm 1) is a one-shot batch
join: every (B_r, B_s) block pair builds the inverted index of B_s from
scratch.  Serving-shaped workloads (examples/knnlm_serve.py, the join
service in launch/join_job.py) stream fresh R batches against the *same*
S datastore, so the one-shot driver pays index construction
O(queries x S-blocks) times.  This module separates the two phases:

  JoinSpec        — frozen join configuration (k, algorithm, geometry, seed).
  plan()          — resolve algorithm + block geometry from the paper's
                    C2/C3 cost model when the spec leaves them open.
  SparseKNNIndex  — ``build(S, spec)`` pads S into blocks ONCE and stacks
                    them into batched device arrays; ``extend(S_new)`` grows
                    the datastore rebuilding only the tail blocks;
                    ``query(R)`` streams R blocks against the cache.
  JoinResult      — (scores, ids, stats) of one query.

**Device-resident query hot path.**  With cached device blocks, one query
costs O(R-blocks) device dispatches — not O(R-blocks x S-blocks):

  * BF / IIB: ``build`` stacks the cached S blocks (and, for IIB, their
    tile-inverted indexes) into ``(num_blocks, ...)`` batched device
    arrays, and the whole S loop of one R block runs as a single jitted
    ``lax.scan`` carrying the TopKState — one dispatch, zero per-pair host
    syncs (the only sync left is pulling the R block's final top-k).
  * IIB kernel path (``use_kernel``): the S blocks' dense dim-tiles are
    stacked at build time and one fused Pallas kernel (kernels/knn_topk)
    streams them through the tile-skipping matmul, maintaining the per-row
    top-k in VMEM across the S grid axis — block score matrices never
    round-trip HBM.
  * IIIB is as device-resident as BF/IIB: ``build`` constructs a
    threshold-INDEPENDENT superset index once per S block (every feature
    indexed, in the datastore's dim-frequency-rank order) plus per-(row,
    tile) mass partial sums, stacked like the IIB indexes.  The live
    MinPruneScore refinement is an on-device mask inside one jitted
    ``lax.scan`` whose carry holds the TopKState AND the threshold
    (core/iiib.py) — lists shrink by masking, never by rebuilding, and the
    only host sync left is the per-R-block result pull (the threshold
    trace and pruned-work counters ride home with it).

``JoinStats.device_dispatches`` / ``host_syncs`` make the dispatch shape
observable (``benchmarks/run.py --smoke`` asserts it).

``knn_join`` (core/blocknl.py) and ``ring_knn_join`` (core/ring.py) are
thin compat wrappers over this engine and return results identical to the
pre-engine implementations.  The wrappers use streaming mode
(``cache_device_blocks=False``): no stacks are built and the legacy
per-pair loop runs with O(block) device memory — also the reference the
scanned driver is tested against.
"""
from __future__ import annotations

import collections
import dataclasses
import time
from typing import Deque, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import iiib as iiib_mod
from repro.core import lsh as lsh_mod
from repro.core.bf import bf_block_scores, bf_join_block, bf_scan_join
from repro.core.iib import iib_join_block, iib_scan_join
from repro.core.iiib import iiib_masked_block, iiib_scan_join
from repro.core.index import (
    DEFAULT_TILE,
    active_tile_list,
    build_tile_index,
    dense_r_tiles,
    max_rows_bound,
)
from repro.core.topk import TopKState, init_topk, min_prune_score, topk_update
from repro.obs import trace as obs_trace
from repro.obs.registry import get_registry
from repro.sparse.format import SparseBatch, num_tiles

# planner constants: the pair-score accumulator of one (B_r, B_s) pair is
# bounded to ~64 MiB of f32, and the C3 (indexed) cost carries a per-list-
# entry overhead factor vs C2's dense MXU throughput (scatter-add + gather
# against a full-rate matmul).  The hard-coded unit costs can be replaced
# by measured ones: ``benchmarks/roofline.py --calibrate out.json`` writes
# a calibration record and ``plan(..., calibration=...)`` consumes it.
PAIR_BUDGET = 1 << 24
DEFAULT_S_BLOCK = 4096
INDEX_COST_FACTOR = 4.0

# JoinStats.min_prune_trace window: most-recent R blocks kept for ad-hoc
# inspection; the lifetime distribution is the registry histogram below
MIN_PRUNE_TRACE_CAP = 256

# similarity-score-scale buckets for the IIIB MinPruneScore histogram
# (values below the first edge — including warm-start-less early blocks —
# land in the lowest bucket; the +Inf bucket catches outliers)
_THR_BUCKETS = (0.0, 0.05, 0.1, 0.2, 0.3, 0.5, 0.75, 1.0, 1.5, 2.0,
                4.0, 8.0, 16.0)


def observe_thresholds(thr) -> None:
    """Feed one R block's MinPruneScore trace into the process-registry
    ``knn_min_prune_threshold`` histogram — the bounded, lossless view of
    threshold evolution (`Histogram.observe` drops the -inf seeds)."""
    h = get_registry().histogram(
        "knn_min_prune_threshold",
        "IIIB MinPruneScore evolution (per S block, all R blocks)",
        buckets=_THR_BUCKETS)
    for v in np.asarray(thr, np.float64).ravel():
        h.observe(v)


def load_calibration(calibration) -> Optional[dict]:
    """Resolve a planner calibration: ``None``, a dict, or a JSON file path.

    Recognised keys (all optional):
      c2_unit_s          — measured seconds per dense C2 work unit
                           (one scored dim-tile lane of one (r, s) pair)
      c3_unit_s          — measured seconds per indexed C3 work unit
      index_cost_factor  — c3_unit_s / c2_unit_s (used when only the ratio
                           was recorded); defaults to INDEX_COST_FACTOR
    """
    if calibration is None or isinstance(calibration, dict):
        return calibration
    import json

    with open(calibration) as f:
        return json.load(f)


@dataclasses.dataclass
class JoinStats:
    """Work accounting for the paper's cost-model comparisons (C2 vs C3)."""

    blocks: int = 0
    tiles_scored: int = 0          # Σ tiles scored over S blocks: IIB's active ones, IIIB's all T
    list_entries: int = 0          # Σ list entries actually scored (IIIB: unmasked only)
    dense_pairs: int = 0           # BF full-score pairs
    index_builds: int = 0          # S-block index constructions (build-once observable)
    device_dispatches: int = 0     # driver-level device launches (scan/kernel/join steps)
    host_syncs: int = 0            # device→host materializations on the query path
    build_wall_s: float = 0.0      # time spent inside build()/extend()
    query_wall_s: float = 0.0      # time spent inside query()
    # approximate tier (accuracy="approx"): band-filter observability.
    # ``recall`` is measured against an exact reference the engine does not
    # have at query time — callers (benches, the recall-contract tests) fill
    # it via ``lsh.measured_recall``; it stays None on exact queries.
    recall: Optional[float] = None
    candidate_rows: int = 0        # Σ live S rows surviving the band filter
    scanned_rows: int = 0          # Σ live S rows the exact scan would visit
    # IIIB observability: per-R-block MinPruneScore traces ((s_blocks + 1,)
    # each: [seed, after block 0, ...]) — pulled with the result, no extra
    # sync.  Bounded: the deque keeps the MOST RECENT R blocks' traces (a
    # long-running index would otherwise grow one array per block forever);
    # the lifetime threshold distribution lives in the process registry's
    # ``knn_min_prune_threshold`` histogram (see ``observe_thresholds``).
    min_prune_trace: Deque[np.ndarray] = dataclasses.field(
        default_factory=lambda: collections.deque(maxlen=MIN_PRUNE_TRACE_CAP))

    @property
    def candidate_fraction(self) -> Optional[float]:
        """Fraction of live S rows the band filter let through (approx
        queries only; None when no approximate block ran)."""
        if self.scanned_rows == 0:
            return None
        return self.candidate_rows / self.scanned_rows


@dataclasses.dataclass(frozen=True)
class JoinSpec:
    """Frozen join configuration.  ``None`` fields are resolved by the planner."""

    k: int
    algorithm: Optional[str] = None     # bf | iib | iiib | None (planner picks)
    r_block: Optional[int] = None
    s_block: Optional[int] = None
    tile: int = DEFAULT_TILE
    use_kernel: bool = False            # IIB: route scoring through the Pallas kernel
    warm_start: float = 0.0             # IIIB: S-sample fraction seeding MinPruneScore
    seed: int = 0                       # warm-start sampler seed (vary across a stream)
    # approximate tier: accuracy="approx" builds a SimHash band index
    # (core/lsh.py) whose candidate mask prunes S before the exact re-rank.
    # Setting ``target_recall`` alone implies accuracy="approx"; the default
    # accuracy="exact" is bit-identical to pre-LSH behaviour everywhere.
    accuracy: str = "exact"             # exact | approx
    target_recall: Optional[float] = None

    def __post_init__(self):
        if self.algorithm not in (None, "bf", "iib", "iiib"):
            raise ValueError(f"unknown algorithm {self.algorithm!r}")
        if self.k < 1:
            raise ValueError("k must be >= 1")
        if self.target_recall is not None and self.accuracy == "exact":
            object.__setattr__(self, "accuracy", "approx")
        if self.accuracy not in ("exact", "approx"):
            raise ValueError(f"unknown accuracy {self.accuracy!r}")
        if self.accuracy == "approx" and self.target_recall is None:
            object.__setattr__(self, "target_recall", 0.95)
        if self.target_recall is not None and not 0.0 < self.target_recall < 1.0:
            raise ValueError(
                f"target_recall must be in (0, 1), got {self.target_recall} "
                "(use accuracy='exact' for exact results)")


@dataclasses.dataclass(frozen=True)
class JoinPlan:
    """Fully-resolved join parameters plus the cost estimates behind them."""

    algorithm: str
    r_block: int
    s_block: int
    tile: int
    k: int
    cost_bf: float      # C2 estimate: every dim-tile of every pair is scored
    cost_iib: float     # C3 estimate: work proportional to inverted-list mass
    cost_iiib: float    # C3 + threshold masking; NO per-pair rebuild charge


def _shape_stats(shape) -> Tuple[int, float, int]:
    """(n_rows, mean_nnz, dim) from a SparseBatch or an (n, nnz, dim) tuple."""
    if isinstance(shape, SparseBatch):
        n = shape.num_vectors
        nnz = float(np.asarray(shape.nnz).mean()) if n else 0.0
        return n, nnz, shape.dim
    n, nnz, dim = shape
    return int(n), float(nnz), int(dim)


def plan(
    r_shape, s_shape, spec: JoinSpec,
    occupied_tiles: Optional[int] = None,
    calibration=None,
) -> JoinPlan:
    """Resolve algorithm and block geometry from the C2/C3 cost model.

    ``r_shape``/``s_shape`` are SparseBatch instances or (n, mean_nnz, dim)
    tuples.  ``occupied_tiles`` optionally narrows the tile universe to the
    tiles S actually touches (from cached dim-frequency statistics —
    concentrated data occupies far fewer tiles than the uniform model).
    ``calibration`` (dict or JSON path from ``benchmarks/roofline.py
    --calibrate``) replaces the hard-coded unit costs with measured ones,
    turning the cost estimates into wall-second predictions.

    C2 (BF): every dim-tile of every (r, s) pair is multiplied, cost
    ``n_r * n_s * D_padded``.  C3 (IIB/IIIB): per active tile the matmul is
    against the tile's row list, cost ``n_r * tile * Σ list lengths`` =
    ``n_r * n_s * tile * E[tiles per S row]``, times the per-entry overhead
    of indexed scoring.  IIIB scores through the same superset lists, built
    ONCE at ``build()`` — since the threshold refinement became an on-device
    mask there is no per-(B_r, B_s) rebuild charge in its query cost
    anymore, and masking can only shrink the scored mass, so
    ``cost_iiib <= cost_iib`` and the indexed side always resolves to IIIB.
    """
    n_r, f_r, d_r = _shape_stats(r_shape)
    n_s, f_s, d_s = _shape_stats(s_shape)
    d = max(d_r, d_s)
    t = max(1, num_tiles(d, spec.tile))
    t_eff = max(1, min(occupied_tiles, t)) if occupied_tiles else t
    # E[#tiles one S row touches] under uniform placement over occupied tiles
    tiles_per_s_row = t_eff * (1.0 - (1.0 - 1.0 / t_eff) ** max(f_s, 0.0))
    cal = load_calibration(calibration) or {}
    c2_unit = float(cal.get("c2_unit_s", 1.0))
    c3_unit = float(
        cal.get("c3_unit_s", c2_unit * cal.get("index_cost_factor", INDEX_COST_FACTOR))
    )
    cost_bf = c2_unit * float(n_r) * n_s * t * spec.tile
    cost_iib = c3_unit * float(n_r) * n_s * tiles_per_s_row * spec.tile
    cost_iiib = cost_iib

    if spec.algorithm is not None:
        algorithm = spec.algorithm
    elif spec.use_kernel:
        algorithm = "iib"
    else:
        algorithm = "bf" if cost_bf <= cost_iiib else "iiib"

    s_block = spec.s_block if spec.s_block else min(n_s, DEFAULT_S_BLOCK)
    s_block = max(1, min(s_block, max(n_s, 1)))
    r_block = spec.r_block if spec.r_block else min(n_r, max(128, PAIR_BUDGET // s_block))
    r_block = max(1, min(r_block, max(n_r, 1)))
    return JoinPlan(
        algorithm=algorithm, r_block=r_block, s_block=s_block,
        tile=spec.tile, k=spec.k, cost_bf=cost_bf, cost_iib=cost_iib,
        cost_iiib=cost_iiib,
    )


@dataclasses.dataclass
class JoinResult:
    """One query's output: (n_r, k) global-S neighbours plus work stats.

    ``missing_shards`` is non-empty only for degraded sharded-store queries
    (``allow_partial=True`` with shards lost): the result is exact over the
    surviving shards and excludes the listed ones entirely."""

    scores: jax.Array
    ids: jax.Array
    stats: JoinStats
    missing_shards: Tuple[int, ...] = ()

    @property
    def state(self) -> TopKState:
        return TopKState(scores=self.scores, ids=self.ids)


# ---------------------------------------------------------------------------
# block plumbing (host-side)
# ---------------------------------------------------------------------------

def _pad_rows_np(
    idx: np.ndarray, val: np.ndarray, nnz: np.ndarray, dim: int, size: int,
    copy_unpadded: bool = False,
):
    """Pad pre-sliced host row arrays to ``size`` rows (sentinel index = dim,
    zero values/nnz); returns the padded arrays plus the valid mask.

    The single home of the block-padding invariant — both R blocks (query
    time) and cached S blocks (build time) go through here.  Pass
    ``copy_unpadded=True`` when the result is retained (a cached mirror must
    not pin its source array across extend()); transient blocks skip the copy.
    """
    stop = idx.shape[0]
    pad = size - stop
    if pad:
        idx = np.concatenate([idx, np.full((pad, idx.shape[1]), dim, idx.dtype)])
        val = np.concatenate([val, np.zeros((pad, val.shape[1]), val.dtype)])
        nnz = np.concatenate([nnz, np.zeros(pad, nnz.dtype)])
    elif copy_unpadded:
        idx, val, nnz = idx.copy(), val.copy(), nnz.copy()
    valid = np.arange(size) < stop
    return idx, val, nnz, valid


def _pad_block(batch: SparseBatch, start: int, size: int) -> Tuple[SparseBatch, np.ndarray]:
    """Host-side block slice, padded to ``size`` rows; returns (block, valid mask)."""
    stop = min(start + size, batch.num_vectors)
    idx, val, nnz, valid = _pad_rows_np(
        np.asarray(batch.indices[start:stop]),
        np.asarray(batch.values[start:stop]),
        np.asarray(batch.nnz[start:stop]),
        batch.dim, size,
    )
    block = SparseBatch(
        indices=jnp.asarray(idx), values=jnp.asarray(val), nnz=jnp.asarray(nnz), dim=batch.dim
    )
    return block, valid


def _host_tile_any(block: SparseBatch, tile: int, t_total: int) -> np.ndarray:
    """(T,) bool — does ANY row of the block touch dim-tile t?"""
    idx = np.asarray(block.indices)
    valid = idx < block.dim
    tid = np.where(valid, idx // tile, t_total)
    out = np.zeros(t_total + 1, dtype=bool)
    out[np.minimum(tid.ravel(), t_total)] = True
    return out[:t_total]


def _host_row_occupancy(idx: np.ndarray, dim: int, tile: int) -> np.ndarray:
    """(N, T) bool — per-row dim-tile occupancy, computed host-side (numpy)."""
    t_total = num_tiles(dim, tile)
    tid = np.where(idx < dim, idx // tile, t_total)
    occ = np.zeros((idx.shape[0], t_total + 1), dtype=bool)
    occ[np.arange(idx.shape[0])[:, None], tid] = True
    return occ[:, :t_total]


def _pad_feature_axis(idx: np.ndarray, val: np.ndarray, f: int, dim: int):
    """Widen (N, F') feature arrays to F columns with sentinel padding."""
    pad = f - idx.shape[1]
    if pad <= 0:
        return idx, val
    idx = np.concatenate([idx, np.full((idx.shape[0], pad), dim, idx.dtype)], axis=1)
    val = np.concatenate([val, np.zeros((val.shape[0], pad), val.dtype)], axis=1)
    return idx, val


@jax.jit
def _bf_step(state, r_block, s_block, s_offset, s_valid):
    return bf_join_block(state, r_block, s_block, s_offset, s_valid)


# one jitted builder serves IIB (identity dims) and IIIB (rank-permuted
# superset) — both are threshold-free; IIIB's refinement is a query-time mask
_build_index_iib = jax.jit(build_tile_index, static_argnames=("max_rows", "tile"))


def _device_batch(host: SparseBatch) -> SparseBatch:
    """Upload a host-mirror SparseBatch to the device."""
    return SparseBatch(
        indices=jnp.asarray(host.indices), values=jnp.asarray(host.values),
        nnz=jnp.asarray(host.nnz), dim=host.dim,
    )


def _interpret_kernels() -> bool:
    """Pallas kernels compile to Mosaic on TPU and run under interpret mode
    on the CPU backend (tests); any other backend has no kernel path.
    Queried lazily so importing this module never initializes jax device
    state."""
    backend = jax.default_backend()
    if backend not in ("tpu", "cpu"):
        raise RuntimeError(f"use_kernel needs a TPU (or CPU interpret mode), "
                           f"not backend {backend!r}")
    return backend == "cpu"


def prepare_r_block_inputs(
    br: SparseBatch,
    algorithm: str,
    tile: int,
    rank_dev: Optional[jax.Array] = None,
    with_r_tiles: bool = True,
) -> dict:
    """R-side device inputs of one padded R block's scan step.

    The single home of the per-R-block preparation the scanned drivers
    consume — dense (rank-permuted) R tiles, IIB's host-derived active-tile
    list, and IIIB's per-tile maxWeight bound.  Shared by the engine's
    query loop and by :class:`repro.store.ShardedKNNStore`, whose fan-out
    replicates exactly these inputs to every shard (they depend only on R
    and on build-frozen datastore statistics, never on the S shard).
    """
    if algorithm == "bf":
        return {}
    if algorithm == "iib":
        t_total = num_tiles(br.dim, tile)
        # the streaming kernel path needs only the active-tile list (the
        # fused kernel densifies its own R tiles) — with_r_tiles=False
        # skips the O(T·|Br|·tile) densify + upload
        occ_any = _host_tile_any(br, tile, t_total)
        out = {"tiles": jnp.asarray(active_tile_list(occ_any))}
        if with_r_tiles:
            out["r_tiles"] = dense_r_tiles(br, None, tile)
        return out
    # IIIB's dense product scores every tile: no active-tile list
    return {
        "r_tiles": dense_r_tiles(br, rank_dev, tile),
        "mwt": iiib_mod.maxw_tiles(br, rank_dev, tile),
    }


# ---------------------------------------------------------------------------
# cached S-side stacks (built once, scanned every query)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class _BFStack:
    """All cached S blocks as one batched device array set (BF scan xs)."""

    idx: jax.Array      # (B, s_block, F) int32
    val: jax.Array      # (B, s_block, F) f32
    nnz: jax.Array      # (B, s_block) int32
    ids: jax.Array      # (B, s_block) int32 — per-row global ids
    valid: jax.Array    # (B, s_block) bool — padding AND tombstoned rows out


@dataclasses.dataclass
class _IIBStack:
    """All cached per-block tile indexes, stacked (IIB scan xs)."""

    rows: jax.Array     # (B, T+1, M) int32
    vals: jax.Array     # (B, T+1, M, tile) f32
    counts: jax.Array   # (B, T+1) int32
    ids: jax.Array      # (B, s_block) int32 — per-row global ids
    valid: jax.Array    # (B, s_block) bool — padding AND tombstoned rows out
    max_rows: int       # common static M (max over blocks, bucketed)


@dataclasses.dataclass
class _KernelStack:
    """Dense dim-tiles of ALL cached S blocks for the fused knn_topk kernel."""

    s_tiles: jax.Array    # (T+1, NS_pad, tile) f32 — sentinel tile last
    s_occ: np.ndarray     # (NS_pad, T) bool — host, feeds active_lists
    col_valid: jax.Array  # (1, NS_pad) int32
    col_ids: jax.Array    # (1, NS_pad) int32 — global S ids per stacked column
    block_s: int          # kernel S-axis block (NS_pad % block_s == 0)
    col_keys: Optional[jax.Array] = None  # (1, NS_pad, n_bands) int32 — approx tier


@dataclasses.dataclass
class _SBlock:
    """One cached S block: host mirror plus host-side index metadata."""

    host: SparseBatch             # numpy mirror (streaming re-uploads from here)
    valid: np.ndarray             # (s_block,) bool
    start: int                    # global row offset
    list_total: int = 0           # Σ list lengths of the block's tile index
    bound: int = 0                # host max_rows bound (IIB/IIIB stacking)
    tilemass: Optional[np.ndarray] = None  # (s_block, T) rank-permuted mass (IIIB)
    lshkeys: Optional[np.ndarray] = None   # (s_block, n_bands) int32 band keys (approx)


class SparseKNNIndex:
    """Build-once/query-many index over the inner join set S.

    ``build`` pays S-side preprocessing once: block padding, host mirrors,
    dim statistics, and the batched device stacks the scanned query driver
    consumes — for BF the padded-CSR blocks; for IIB the per-block
    tile-inverted indexes; for the kernel path the dense dim-tiles; for
    IIIB the threshold-independent superset indexes (rank-permuted, every
    feature indexed) plus the per-(row, tile) mass partial sums its
    query-time threshold mask compares against.  Every ``query`` then
    streams an R batch against the cached structures in O(R-blocks) device
    dispatches, and a query stream costs O(S-blocks) index builds total
    instead of O(queries x S-blocks).

    ``cache_device_blocks=False`` keeps only the host mirrors resident and
    materializes each S block (and, for IIB, its tile index) on the fly per
    (B_r, B_s) pair — the legacy streaming memory profile, O(block) device
    memory instead of O(n_s), driven by the legacy per-pair loop.  The
    one-shot ``knn_join`` wrapper uses this mode.
    """

    def __init__(
        self,
        S: SparseBatch,
        spec: JoinSpec,
        cache_device_blocks: bool = True,
        frozen_rank: Optional[np.ndarray] = None,
        calibration=None,
        lsh_cfg: Optional[lsh_mod.LSHConfig] = None,
    ):
        t0 = time.perf_counter()
        self.spec = spec
        self._cache_device = cache_device_blocks
        self.dim = S.dim
        self.tile = spec.tile
        self.stats = JoinStats()
        self.calibration = load_calibration(calibration)
        self._idx = np.asarray(S.indices)
        self._val = np.asarray(S.values)
        self._nnz = np.asarray(S.nnz)
        self.n_s = S.num_vectors
        if self.n_s < 1:
            raise ValueError("S must have at least one row")

        # tombstones: delete()/TTL expiry mark rows dead without touching
        # the cached stacks — only the valid masks change.  compact() is
        # the explicit (real) rebuild that reclaims the dead rows.
        self._alive = np.ones(self.n_s, bool)
        self._deadline = np.full(self.n_s, np.inf)

        # S-side dim statistics, maintained incrementally by extend():
        # dim_freq drives the planner's occupied-tile estimate; max_weight
        # (the S-side mirror of IIIB's R-side maxWeight_d bound) is lazy.
        self.dim_freq = np.zeros(self.dim, np.int64)
        self._accumulate_dim_stats(self._idx)
        self._refresh_plan_stats()

        f_mean = self._f_mean
        p = plan((self.n_s, f_mean, self.dim), (self.n_s, f_mean, self.dim), spec,
                 occupied_tiles=self.occupied_tiles, calibration=self.calibration)
        self.algorithm = spec.algorithm or p.algorithm
        self.s_block = max(1, min(spec.s_block or p.s_block, self.n_s))

        # IIIB superset ordering: the datastore's dim-frequency rank, FROZEN
        # at build time — extend() keeps it so retained stack blocks stay
        # valid (the ordering is a pruning heuristic, not a correctness
        # input; refreeze() recomputes it after heavy drift).  The sharded
        # store passes ``frozen_rank`` so every shard prunes in the GLOBAL
        # datastore's frequency order, matching a single-device build over
        # the concatenated S.
        if self.algorithm == "iiib":
            self._rank_np = (
                np.asarray(frozen_rank, np.int32) if frozen_rank is not None
                else iiib_mod.s_frequency_rank(self.dim_freq)
            )
            self._rank_dev = jnp.asarray(self._rank_np)
        else:
            self._rank_np = None
            self._rank_dev = None

        # approximate tier: the SimHash band hasher is build-frozen state
        # (like the IIIB rank) — the sharded store passes ``lsh_cfg`` so
        # every shard/replica hashes with the SAME projections
        self._lsh: Optional[lsh_mod.LSHBands] = None
        if spec.accuracy == "approx":
            cfg = lsh_cfg or lsh_mod.plan_lsh(spec.target_recall, seed=spec.seed)
            self._lsh = lsh_mod.LSHBands(cfg, self.dim)

        self._blocks: List[_SBlock] = []
        self._bf_stack: Optional[_BFStack] = None
        self._iib_stack: Optional[_IIBStack] = None
        self._kernel_stack: Optional[_KernelStack] = None
        self._mass_stack: Optional[jax.Array] = None   # (B, s_block, T) — IIIB
        self._lsh_stack: Optional[jax.Array] = None    # (B, s_block, n_bands)
        self._build_blocks(from_block=0)
        self.stats.build_wall_s += time.perf_counter() - t0

    # -- construction -------------------------------------------------------

    @classmethod
    def build(
        cls,
        S: SparseBatch,
        spec: JoinSpec,
        cache_device_blocks: bool = True,
        frozen_rank: Optional[np.ndarray] = None,
        calibration=None,
        lsh_cfg: Optional[lsh_mod.LSHConfig] = None,
    ) -> "SparseKNNIndex":
        return cls(
            S, spec, cache_device_blocks=cache_device_blocks,
            frozen_rank=frozen_rank, calibration=calibration, lsh_cfg=lsh_cfg,
        )

    def extend(self, S_new: SparseBatch, deadline=None) -> "SparseKNNIndex":
        """Append rows to S in place, rebuilding only the affected tail blocks.

        Equivalent to building from the row-concatenation of the old and new
        S (block geometry is fixed at build time, so only the block holding
        the old tail — if partial — plus the new blocks change).  Stacked
        device arrays are re-assembled by concatenation: the retained prefix
        of the IIB index stack is padded, never rebuilt.

        ``deadline`` optionally attaches a TTL to the new rows: a scalar or
        per-row array of absolute expiry times consumed by :meth:`expire`.
        """
        if S_new.dim != self.dim:
            raise ValueError(f"dim mismatch: index has {self.dim}, got {S_new.dim}")
        t0 = time.perf_counter()
        idx2 = np.asarray(S_new.indices)
        val2 = np.asarray(S_new.values)
        nnz2 = np.asarray(S_new.nnz)
        f = max(self._idx.shape[1], idx2.shape[1])
        self._idx, self._val = _pad_feature_axis(self._idx, self._val, f, self.dim)
        idx2, val2 = _pad_feature_axis(idx2, val2, f, self.dim)
        old_n = self.n_s
        self._idx = np.concatenate([self._idx, idx2])
        self._val = np.concatenate([self._val, val2])
        self._nnz = np.concatenate([self._nnz, nnz2])
        self.n_s = old_n + S_new.num_vectors
        self._alive = np.concatenate([self._alive, np.ones(S_new.num_vectors, bool)])
        dl = np.full(S_new.num_vectors, np.inf) if deadline is None else (
            np.broadcast_to(np.asarray(deadline, np.float64), (S_new.num_vectors,))
        )
        self._deadline = np.concatenate([self._deadline, dl])
        self._accumulate_dim_stats(idx2)
        self._refresh_plan_stats()
        self._build_blocks(from_block=old_n // self.s_block)
        self.stats.build_wall_s += time.perf_counter() - t0
        return self

    # -- mutation: tombstones (delete / TTL) and the real rebuilds -----------

    def delete(self, ids) -> int:
        """Tombstone rows by global id.  No stack rebuild — only the valid
        masks change (one host→device upload); results immediately exclude
        the rows.  Returns the number of newly-dead rows."""
        ids = np.unique(np.atleast_1d(np.asarray(ids, np.int64)))
        if ids.size and (ids.min() < 0 or ids.max() >= self.n_s):
            raise IndexError(f"ids out of range [0, {self.n_s})")
        newly = int(self._alive[ids].sum())
        self._alive[ids] = False
        self._refresh_valid()
        return newly

    def expire(self, now: float) -> int:
        """Tombstone rows whose TTL deadline has passed (``deadline <= now``).
        Same no-rebuild semantics as :meth:`delete`."""
        dead = self._alive & (self._deadline <= now)
        newly = int(dead.sum())
        if newly:
            self._alive[dead] = False
            self._refresh_valid()
        return newly

    @property
    def dead_rows(self) -> int:
        return self.n_s - int(self._alive.sum())

    @property
    def live_rows(self) -> int:
        return int(self._alive.sum())

    def compact(self) -> int:
        """Physically drop tombstoned rows and rebuild blocks + stacks — the
        real rebuild that delete()/expire() defer.  Global ids shift to the
        surviving rows' new positions (callers needing stable ids — the
        sharded store — keep their own id maps).  A fully-dead datastore
        compacts to a single still-tombstoned placeholder row (SparseBatch
        shapes need >= 1 row), so its memory is reclaimed and every query
        keeps masking it out.  Returns rows removed; the exact surviving
        row mask is exposed as ``last_compact_keep`` so id-mapping callers
        (the sharded store) follow this method's choice instead of
        predicting it."""
        removed = self.dead_rows
        if removed == 0:
            self.last_compact_keep = np.ones(self.n_s, bool)
            return 0
        t0 = time.perf_counter()
        keep = self._alive.copy()
        stub = not keep.any()
        if stub:
            keep[0] = True
            removed -= 1
        self.last_compact_keep = keep
        self._idx = self._idx[keep]
        self._val = self._val[keep]
        self._nnz = self._nnz[keep]
        self._deadline = self._deadline[keep]
        self.n_s = int(keep.sum())
        self._alive = np.full(self.n_s, not stub)
        self.dim_freq = np.zeros(self.dim, np.int64)
        self._accumulate_dim_stats(self._idx)
        self._refresh_plan_stats()
        self._bf_stack = None
        self._iib_stack = None
        self._kernel_stack = None
        self._mass_stack = None
        self._lsh_stack = None
        self._build_blocks(from_block=0)
        self.stats.build_wall_s += time.perf_counter() - t0
        return removed

    def refreeze(self, frozen_rank: Optional[np.ndarray] = None) -> "SparseKNNIndex":
        """Recompute the IIIB superset dim-frequency rank and reassemble the
        stacks (ROADMAP open item).  The frozen rank stays *exact* across
        ``extend()`` drift but prunes less as the datastore's frequency
        profile shifts; refreezing restores the prune rate at the cost of
        one full stack rebuild.  Results are unchanged (the rank is a
        pruning heuristic, not a correctness input).  No-op for BF/IIB,
        whose indexes carry no frequency ordering.  The sharded store
        passes ``frozen_rank`` (the global live-row rank) so shards stay
        in one common order."""
        if self.algorithm != "iiib":
            return self
        t0 = time.perf_counter()
        if frozen_rank is not None:
            self._rank_np = np.asarray(frozen_rank, np.int32)
        else:
            live_freq = np.zeros(self.dim, np.int64)
            valid = (self._idx < self.dim) & self._alive[:, None]
            np.add.at(live_freq, np.where(valid, self._idx, 0).ravel(), valid.ravel())
            self._rank_np = iiib_mod.s_frequency_rank(live_freq)
        self._rank_dev = jnp.asarray(self._rank_np)
        for blk in self._blocks:
            blk.bound = max_rows_bound(blk.host, self.tile, rank=self._rank_np)
            blk.tilemass = iiib_mod.tile_mass_host(
                np.asarray(blk.host.indices), np.asarray(blk.host.values),
                self.dim, self._rank_np, self.tile,
            )
        self._iib_stack = None
        self._mass_stack = None
        self._build_stacks(from_block=0)
        self.stats.build_wall_s += time.perf_counter() - t0
        return self

    def _accumulate_dim_stats(self, idx: np.ndarray):
        valid = idx < self.dim
        np.add.at(self.dim_freq, np.where(valid, idx, 0).ravel(), valid.ravel())

    def _refresh_plan_stats(self):
        # cached so the serving hot path (query -> plan_for) does no O(n_s)
        # host work; only build()/extend() change these
        self._f_mean = float(self._nnz.mean())
        (dims,) = np.nonzero(self.dim_freq)
        self._occupied_tiles = int(np.unique(dims // self.tile).size) if dims.size else 1
        self._max_weight = None

    def _build_blocks(self, from_block: int):
        del self._blocks[from_block:]
        for start in range(from_block * self.s_block, self.n_s, self.s_block):
            self._blocks.append(self._make_block(start))
        self._build_stacks(from_block)

    def _make_block(self, start: int) -> _SBlock:
        stop = min(start + self.s_block, self.n_s)
        idx, val, nnz, valid = _pad_rows_np(
            self._idx[start:stop], self._val[start:stop], self._nnz[start:stop],
            self.dim, self.s_block, copy_unpadded=True,
        )
        host = SparseBatch(indices=idx, values=val, nnz=nnz, dim=self.dim)
        blk = _SBlock(host=host, valid=valid, start=start)
        if self._lsh is not None:
            # band keys are per-row build-time state like the tilemass:
            # padded rows hash to key 0 and are excluded by the valid mask
            blk.lshkeys = self._lsh.keys_host(idx, val)
        if self.algorithm == "iib" and not self.spec.use_kernel:
            # the max_rows shape bound (host, cheap); streaming reuses it
            # per pair, cached mode to size the common stack
            blk.bound = max_rows_bound(host, self.tile)
        elif self.algorithm == "iiib":
            # superset bound + the per-(row, tile) mass partial sums the
            # threshold mask compares against (both threshold-independent)
            blk.bound = max_rows_bound(host, self.tile, rank=self._rank_np)
            blk.tilemass = iiib_mod.tile_mass_host(
                idx, val, self.dim, self._rank_np, self.tile
            )
        return blk

    # -- batched device stacks ----------------------------------------------

    def _build_stacks(self, from_block: int):
        if not self._cache_device:
            return
        if self.algorithm == "bf":
            self._bf_stack = self._stack_bf(from_block)
        elif self.algorithm == "iib":
            if self.spec.use_kernel:
                self._kernel_stack = self._stack_kernel(from_block)
            else:
                self._iib_stack = self._stack_iib(from_block)
        else:  # iiib: superset tile indexes + tilemass, stacked like IIB
            self._iib_stack = self._stack_iib(from_block, rank=self._rank_dev)
            self._mass_stack = self._stack_mass(from_block)
        if self._lsh is not None and not (
            self.spec.use_kernel and self.algorithm == "iib"
        ):
            self._lsh_stack = self._stack_lshkeys(from_block)

    def _stack_lshkeys(self, from_block: int) -> jax.Array:
        """(B, s_block, n_bands) stacked band keys; prefix retained across
        extend (mirrors ``_stack_mass`` — a key stack is per-row data, so
        tail-only reassembly carries over unchanged)."""
        parts = []
        if from_block > 0 and self._lsh_stack is not None:
            parts.append(self._lsh_stack[:from_block])
        for blk in self._blocks[from_block:]:
            parts.append(jnp.asarray(blk.lshkeys)[None])
        return jnp.concatenate(parts, axis=0)

    def _stack_ids_valid(self) -> Tuple[jax.Array, jax.Array]:
        """(B, s_block) global-id stack + valid mask (padding AND alive)."""
        b, sb = len(self._blocks), self.s_block
        ids = np.arange(b * sb, dtype=np.int32).reshape(b, sb)
        valid = np.arange(b * sb) < self.n_s
        valid[: self.n_s] &= self._alive
        return jnp.asarray(ids), jnp.asarray(valid.reshape(b, sb))

    def _refresh_valid(self):
        """Push the current alive mask into every cached stack's valid mask —
        the whole device-side cost of delete()/expire(); index structures,
        id stacks and mass stacks are untouched."""
        if not self._cache_device:
            return
        _, valid = self._stack_ids_valid()
        if self._bf_stack is not None:
            self._bf_stack.valid = valid
        if self._iib_stack is not None:
            self._iib_stack.valid = valid
        if self._kernel_stack is not None:
            ks = self._kernel_stack
            ns_pad = ks.col_ids.shape[1]
            alive = np.zeros(ns_pad, bool)
            alive[: self.n_s] = self._alive
            ks.col_valid = jnp.asarray(alive[None, :].astype(np.int32))

    def _stack_bf(self, from_block: int) -> _BFStack:
        """Stack the padded-CSR blocks: (B, s_block, F) device arrays.

        Incremental: on ``extend`` the retained prefix of the old stack is
        kept on device (feature axis padded if the new rows are wider) and
        only the tail blocks are re-uploaded from the host mirror.
        """
        b, sb, f = len(self._blocks), self.s_block, self._idx.shape[1]
        old = self._bf_stack if from_block > 0 else None
        parts_i, parts_v, parts_n = [], [], []
        if old is not None:
            oi, ov = old.idx[:from_block], old.val[:from_block]
            pad = f - oi.shape[2]
            if pad > 0:
                oi = jnp.concatenate(
                    [oi, jnp.full(oi.shape[:2] + (pad,), self.dim, oi.dtype)], axis=2
                )
                ov = jnp.concatenate(
                    [ov, jnp.zeros(ov.shape[:2] + (pad,), ov.dtype)], axis=2
                )
            parts_i.append(oi)
            parts_v.append(ov)
            parts_n.append(old.nnz[:from_block])
        lo, hi = from_block * sb, b * sb
        idx = np.full((hi - lo, f), self.dim, self._idx.dtype)
        val = np.zeros((hi - lo, f), self._val.dtype)
        nnz = np.zeros((hi - lo,), self._nnz.dtype)
        idx[: self.n_s - lo] = self._idx[lo:]
        val[: self.n_s - lo] = self._val[lo:]
        nnz[: self.n_s - lo] = self._nnz[lo:]
        parts_i.append(jnp.asarray(idx.reshape(-1, sb, f)))
        parts_v.append(jnp.asarray(val.reshape(-1, sb, f)))
        parts_n.append(jnp.asarray(nnz.reshape(-1, sb)))
        ids, valid = self._stack_ids_valid()
        return _BFStack(
            idx=jnp.concatenate(parts_i, axis=0),
            val=jnp.concatenate(parts_v, axis=0),
            nnz=jnp.concatenate(parts_n, axis=0),
            ids=ids, valid=valid,
        )

    def _stack_iib(self, from_block: int, rank: Optional[jax.Array] = None) -> _IIBStack:
        """Stack per-block tile indexes with one common ``max_rows``.

        ``rank=None`` builds IIB's identity-dim indexes; IIIB passes the
        frozen S-frequency rank to get its threshold-independent superset
        indexes (same structure, permuted dim space).

        Incremental: on ``extend`` the retained prefix of the old stack is
        only PADDED to the new bound (sentinel rows, zero values — a pad is
        not a rebuild and is not counted in ``index_builds``); fresh indexes
        are built for the tail blocks alone.
        """
        sb, tile = self.s_block, self.tile
        old = self._iib_stack if from_block > 0 else None
        tail = self._blocks[from_block:]
        m = max([blk.bound for blk in tail] + ([old.max_rows] if old else [1]))
        parts_r, parts_v, parts_c = [], [], []
        if old is not None:
            pr = old.rows[:from_block]
            pv = old.vals[:from_block]
            pc = old.counts[:from_block]
            if m > old.max_rows:
                pad = m - old.max_rows
                pr = jnp.concatenate(
                    [pr, jnp.full(pr.shape[:2] + (pad,), sb, jnp.int32)], axis=2
                )
                pv = jnp.concatenate(
                    [pv, jnp.zeros(pv.shape[:2] + (pad, tile), jnp.float32)], axis=2
                )
            parts_r.append(pr)
            parts_v.append(pv)
            parts_c.append(pc)
        for blk in tail:
            ti = _build_index_iib(_device_batch(blk.host), max_rows=m, tile=tile, rank=rank)
            self.stats.index_builds += 1
            blk.list_total = int(np.asarray(ti.counts).sum())
            parts_r.append(ti.rows[None])
            parts_v.append(ti.vals[None])
            parts_c.append(ti.counts[None])
        ids, valid = self._stack_ids_valid()
        return _IIBStack(
            rows=jnp.concatenate(parts_r, axis=0),
            vals=jnp.concatenate(parts_v, axis=0),
            counts=jnp.concatenate(parts_c, axis=0),
            ids=ids, valid=valid, max_rows=m,
        )

    def _stack_mass(self, from_block: int) -> jax.Array:
        """(B, s_block, T) stacked tilemass; prefix retained across extend."""
        parts = []
        if from_block > 0 and self._mass_stack is not None:
            parts.append(self._mass_stack[:from_block])
        for blk in self._blocks[from_block:]:
            parts.append(jnp.asarray(blk.tilemass)[None])
        return jnp.concatenate(parts, axis=0)

    def _stack_kernel(self, from_block: int) -> _KernelStack:
        """Stack dense dim-tiles of all S blocks for the fused kernel.

        Incremental: dense tiles are per-column independent, so ``extend``
        keeps the retained blocks' columns of the old device stack and only
        densifies the tail rows (plus fresh alignment padding).
        """
        ns = len(self._blocks) * self.s_block
        bs_k = 256 if ns >= 256 else -(-ns // 8) * 8
        ns_pad = -(-ns // bs_k) * bs_k
        keep = from_block * self.s_block
        old = self._kernel_stack if from_block > 0 else None
        f = self._idx.shape[1]
        idx = np.full((ns_pad - keep, f), self.dim, np.int32)
        val = np.zeros((ns_pad - keep, f), np.float32)
        nnz = np.zeros(ns_pad - keep, np.int32)
        idx[: self.n_s - keep] = self._idx[keep:]
        val[: self.n_s - keep] = self._val[keep:]
        nnz[: self.n_s - keep] = self._nnz[keep:]
        from repro.kernels.knn_score.ops import dense_tiles_with_sentinel

        tail = SparseBatch(
            indices=jnp.asarray(idx), values=jnp.asarray(val),
            nnz=jnp.asarray(nnz), dim=self.dim,
        )
        tail_tiles = dense_tiles_with_sentinel(tail, self.tile)  # (T+1, tail, tile)
        tail_occ = _host_row_occupancy(idx, self.dim, self.tile)
        if old is not None:
            s_tiles = jnp.concatenate([old.s_tiles[:, :keep, :], tail_tiles], axis=1)
            s_occ = np.concatenate([old.s_occ[:keep], tail_occ])
        else:
            s_tiles, s_occ = tail_tiles, tail_occ
        col_valid = np.zeros(ns_pad, bool)
        col_valid[: self.n_s] = self._alive
        col_ids = np.where(
            np.arange(ns_pad) < self.n_s, np.arange(ns_pad, dtype=np.int32), -1
        )
        col_valid = col_valid.astype(np.int32)
        col_keys = None
        if self._lsh is not None:
            # flat column layout of the kernel stack: band keys follow it
            # (alignment-pad columns key 0, already masked by col_valid)
            keys = np.zeros((ns_pad, self._lsh.cfg.n_bands), np.int32)
            keys[:ns] = np.concatenate([b.lshkeys for b in self._blocks])
            col_keys = jnp.asarray(keys[None])
        return _KernelStack(
            s_tiles=s_tiles,
            s_occ=s_occ,
            col_valid=jnp.asarray(col_valid[None, :]),
            col_ids=jnp.asarray(col_ids[None, :]),
            block_s=bs_k,
            col_keys=col_keys,
        )

    # -- introspection ------------------------------------------------------

    @property
    def num_vectors(self) -> int:
        return self.n_s

    @property
    def num_blocks(self) -> int:
        return len(self._blocks)

    @property
    def occupied_tiles(self) -> int:
        """Number of dim-tiles S actually touches (planner statistic)."""
        return self._occupied_tiles

    @property
    def max_weight(self) -> np.ndarray:
        """(D,) maxWeight_d(S) — the S-side mirror of IIIB's R-side bound.

        Computed lazily (invalidated by extend()); nothing on the query hot
        path reads it.
        """
        if self._max_weight is None:
            valid = self._idx < self.dim
            mw = np.zeros(self.dim, np.float32)
            np.maximum.at(
                mw, np.where(valid, self._idx, 0).ravel(),
                np.where(valid, self._val, 0.0).ravel(),
            )
            self._max_weight = mw
        return self._max_weight

    def plan_for(self, R) -> JoinPlan:
        """Resolved plan for querying with R (a SparseBatch or shape tuple)."""
        n_r, f_r, _ = _shape_stats(R)
        spec = dataclasses.replace(
            self.spec, algorithm=self.algorithm, s_block=self.s_block
        )
        return plan((n_r, f_r, self.dim), (self.n_s, self._f_mean, self.dim), spec,
                    occupied_tiles=self.occupied_tiles, calibration=self.calibration)

    # -- query --------------------------------------------------------------

    def _r_band_keys(
        self, R: SparseBatch, r0: int, rb: int, r_valid: np.ndarray,
    ) -> Tuple[np.ndarray, np.ndarray]:
        """One R block's band keys (rb, n_bands) plus the real-row mask —
        padded AND empty rows (nnz = 0, e.g. the serve scheduler's batch
        padding) are excluded from the candidate union."""
        stop = min(r0 + rb, R.num_vectors)
        keys = np.zeros((rb, self._lsh.cfg.n_bands), np.int32)
        keys[: stop - r0] = self._lsh.keys_host(
            np.asarray(R.indices[r0:stop]), np.asarray(R.values[r0:stop])
        )
        real = r_valid.copy()
        real[: stop - r0] &= np.asarray(R.nnz[r0:stop]) > 0
        return keys, real

    def query(
        self,
        R: SparseBatch,
        stats: Optional[JoinStats] = None,
        accuracy: Optional[str] = None,
    ) -> JoinResult:
        """R ⋈_KNN S against the cached structures.  Returns global S ids.

        The R-block loop is the paper's Algorithm 1 outer loop.  With cached
        device stacks the whole S side of one R block is ONE device dispatch
        — a ``lax.scan`` for BF/IIB, a threshold-in-carry ``lax.scan`` for
        IIIB, the fused knn_topk kernel for the kernel path — and the only
        host sync is the per-R-block result pull.  Streaming mode falls back
        to the legacy per-pair loop (transient device blocks, per-pair
        threshold syncs for IIIB).

        ``accuracy`` overrides the spec per query: ``"approx"`` (index must
        be built with ``target_recall``) prepends ONE jitted band-lookup
        pass per R block whose candidate mask folds into the scans' valid
        masks — the exact drivers then re-rank only the candidates.
        ``"exact"`` on an approx-built index skips the mask entirely and is
        bit-identical to an exact-built index.
        """
        t_q = time.perf_counter()
        stats = stats if stats is not None else JoinStats()
        if R.dim != self.dim:
            raise ValueError(f"dim mismatch: index has {self.dim}, got {R.dim}")
        spec = self.spec
        acc = accuracy if accuracy is not None else spec.accuracy
        if acc not in ("exact", "approx"):
            raise ValueError(f"unknown accuracy {acc!r}")
        approx = acc == "approx"
        if approx and self._lsh is None:
            raise ValueError(
                "index was built without the LSH band tier; build with "
                "target_recall (or accuracy='approx') to enable approx queries")
        algorithm = self.algorithm
        k = spec.k
        n_r, n_s = R.num_vectors, self.n_s
        rb = min(spec.r_block or self.plan_for(R).r_block, n_r)
        sb = self.s_block
        tile = self.tile
        cached = self._cache_device

        sampled_ids = None
        sampled_mask = None
        sample_block = None
        if spec.warm_start > 0 and algorithm == "iiib":
            m = max(int(n_s * spec.warm_start), k)
            rng = np.random.default_rng(spec.seed)
            # sample live rows only — a tombstoned row must never be offered
            (pool,) = np.nonzero(self._alive)
            sampled_ids = np.sort(rng.choice(pool, size=min(m, pool.size), replace=False))
            sampled_mask = np.zeros(n_s, bool)
            sampled_mask[sampled_ids] = True
            sample_block = SparseBatch(
                indices=jnp.asarray(self._idx[sampled_ids]),
                values=jnp.asarray(self._val[sampled_ids]),
                nnz=jnp.asarray(self._nnz[sampled_ids]),
                dim=self.dim,
            )

        out_scores = []
        out_ids = []
        for r0 in range(0, n_r, rb):
            # leaf span per R block (start/end, not `with` — nothing nests
            # below it on this thread); parents to whatever serving span is
            # active, a no-op None when tracing is off
            _sp = obs_trace.start_span("engine.r_block", r0=r0,
                                       algorithm=algorithm)
            br, r_valid = _pad_block(R, r0, rb)
            state = init_topk(rb, k)                       # InitPruneScore
            aux = None
            if sampled_ids is not None:
                # warm-start pass: exact BF scores of the sample seed the
                # top-k — and with it the MinPruneScore, entirely on device
                sc = bf_block_scores(br, sample_block)
                state = topk_update(state, sc, jnp.asarray(sampled_ids, jnp.int32))
                stats.dense_pairs += rb * len(sampled_ids)
                stats.device_dispatches += 1

            n_valid = min(rb, n_r - r0)          # real rows of this R block

            # approximate tier: ONE jitted band-lookup pass prunes S to a
            # candidate mask the exact drivers re-rank (the mask ANDs into
            # the same valid masks tombstones use — scan programs unchanged)
            cand = None        # device (B, s_block) — cached scan paths
            cand_np = None     # host (B, s_block) — streaming paths
            col_cand = None    # device (1, NS_pad) — fused kernel path
            cand_count = None  # device scalar, pulled with the result
            if approx:
                r_keys, r_real = self._r_band_keys(R, r0, rb, r_valid)
                if cached and spec.use_kernel and algorithm == "iib":
                    ks = self._kernel_stack
                    col_cand, cand_count = lsh_mod.candidate_mask(
                        jnp.asarray(r_keys), jnp.asarray(r_real),
                        ks.col_keys[0], ks.col_valid[0] != 0,
                    )
                    col_cand = col_cand[None]
                    stats.device_dispatches += 1
                    stats.scanned_rows += self.live_rows
                elif cached:
                    live = self._sampled_valid(sampled_mask)
                    cand, cand_count = lsh_mod.candidate_mask(
                        jnp.asarray(r_keys), jnp.asarray(r_real),
                        self._lsh_stack, jnp.asarray(live),
                    )
                    stats.device_dispatches += 1
                    stats.scanned_rows += int(live.sum())
                else:
                    # streaming mode keeps S host-resident: host mask twin
                    live = self._sampled_valid(sampled_mask)
                    cand_np = lsh_mod.candidate_mask_host(
                        r_keys, r_real,
                        np.stack([blk.lshkeys for blk in self._blocks]),
                    )
                    stats.scanned_rows += int(live.sum())
                    stats.candidate_rows += int((cand_np & live).sum())

            if algorithm == "bf":
                if cached:
                    state = self._query_bf_scanned(state, br, stats, rb, cand)
                else:
                    state = self._query_pairs(
                        state, br, None, None, stats, rb, cand_np
                    )
            elif algorithm == "iib":
                if spec.use_kernel and cached:
                    # the fused kernel derives its own (r-block, s-block)
                    # active lists from row occupancy
                    state = self._query_fused_kernel(
                        state, br, stats, rb, n_valid, col_cand
                    )
                else:
                    # R-side prep (active tiles are host-concrete — true
                    # tile skipping); shared with the sharded store
                    prep = prepare_r_block_inputs(
                        br, "iib", tile, with_r_tiles=not spec.use_kernel
                    )
                    if cached:
                        state = self._query_iib_scanned(
                            state, prep["r_tiles"], prep["tiles"], stats, cand
                        )
                    else:
                        state = self._query_pairs(
                            state, br, prep.get("r_tiles"), prep["tiles"],
                            stats, rb, cand_np,
                        )
            else:  # iiib — masked superset refinement, threshold in carry
                prep = prepare_r_block_inputs(
                    br, "iiib", tile, rank_dev=self._rank_dev
                )
                r_tiles, mwt = prep["r_tiles"], prep["mwt"]
                rv = jnp.asarray(r_valid)
                if cached:
                    state, aux = self._query_iiib_scanned(
                        state, r_tiles, mwt, stats, sampled_mask, rv, cand
                    )
                else:
                    state = self._query_pairs_iiib(
                        state, r_tiles, mwt, stats, sampled_mask, rv,
                        cand_np,
                    )

            out_scores.append(np.asarray(state.scores)[r_valid])
            out_ids.append(np.asarray(state.ids)[r_valid])
            if aux is not None:
                # rides home with the result pull — same sync point
                stats.list_entries += int(np.asarray(aux["kept"]).sum())
                thr = np.asarray(aux["thr"])
                stats.min_prune_trace.append(thr)
                observe_thresholds(thr)
            if cand_count is not None:
                stats.candidate_rows += int(np.asarray(cand_count))
                stats.host_syncs += 1          # the candidate-count pull
            stats.host_syncs += 1                          # the R block's result pull
            obs_trace.end_span(_sp)

        dt = time.perf_counter() - t_q
        stats.query_wall_s += dt
        self.stats.query_wall_s += dt
        return JoinResult(
            scores=jnp.asarray(np.concatenate(out_scores)),
            ids=jnp.asarray(np.concatenate(out_ids)),
            stats=stats,
        )

    # -- scanned drivers (cached mode: one dispatch per R block) -------------

    def _query_bf_scanned(self, state, br, stats, rb, cand=None):
        st = self._bf_stack
        b = len(self._blocks)
        valid = st.valid if cand is None else jnp.logical_and(st.valid, cand)
        state = bf_scan_join(
            state, br, st.idx, st.val, st.nnz, st.ids, valid, dim=self.dim
        )
        stats.device_dispatches += 1
        stats.blocks += b
        stats.dense_pairs += rb * self.s_block * b
        return state

    def _query_iib_scanned(self, state, r_tiles, tiles, stats, cand=None):
        st = self._iib_stack
        b = len(self._blocks)
        valid = st.valid if cand is None else jnp.logical_and(st.valid, cand)
        state = iib_scan_join(
            state, r_tiles, tiles, st.rows, st.vals, st.counts, st.ids, valid,
            tile=self.tile, num_s=self.s_block,
        )
        stats.device_dispatches += 1
        stats.blocks += b
        stats.tiles_scored += int(tiles.shape[0]) * b
        stats.list_entries += sum(blk.list_total for blk in self._blocks)
        return state

    def _sampled_valid(self, sampled_mask: Optional[np.ndarray]) -> np.ndarray:
        """(B, s_block) bool — padding, tombstoned AND warm-start-sampled rows
        masked out (sampled rows were already offered by the warm-start
        pass).  The one home of this mask: the scan stacks it, the
        streaming loop slices it."""
        b, sb = len(self._blocks), self.s_block
        valid = np.arange(b * sb) < self.n_s
        valid[: self.n_s] &= self._alive
        if sampled_mask is not None:
            valid[: self.n_s] &= ~sampled_mask
        return valid.reshape(b, sb)

    def _block_valid(self, blk: _SBlock) -> np.ndarray:
        """(s_block,) bool — one block's padding mask with tombstones folded
        in (the streaming loops' per-pair counterpart of the stack valid)."""
        v = blk.valid.copy()
        hi = min(blk.start + self.s_block, self.n_s)
        v[: hi - blk.start] &= self._alive[blk.start:hi]
        return v

    def _query_iiib_scanned(
        self, state, r_tiles, mwt, stats, sampled_mask, rv, cand=None
    ):
        """IIIB's whole S side as ONE dispatch: the superset-index scan with
        (TopKState, MinPruneScore) in the carry.  The warm-started threshold
        seeds the carry as a device scalar — no host sync before the scan —
        and the per-block threshold trace + kept-entry counts come back as
        scan outputs, pulled together with the R block's result."""
        st = self._iib_stack
        b = len(self._blocks)
        thr0 = min_prune_score(state, valid=rv)   # device scalar — warm start included
        s_valid = jnp.asarray(self._sampled_valid(sampled_mask))
        if cand is not None:
            s_valid = jnp.logical_and(s_valid, cand)
        state, _, thr_trace, kept = iiib_scan_join(
            state, thr0, r_tiles, mwt,
            st.rows, st.vals, st.counts, self._mass_stack, st.ids,
            s_valid, rv,
            tile=self.tile, num_s=self.s_block,
        )
        stats.device_dispatches += 1
        stats.blocks += b
        stats.tiles_scored += int(r_tiles.shape[0]) * b
        # trace = [seed, after block 0, ..., after block B-1]  (B+1 values)
        return state, {"thr": jnp.concatenate([thr0[None], thr_trace]), "kept": kept}

    def _fused_kernel_args(self, state, br, rb, n_valid, col_cand=None):
        """Positional and keyword arguments of ONE fused knn_topk call for
        a padded R block — shared by the query loop and
        :meth:`lowered_kernel`, so the two cannot drift apart.  The carried
        state's MinPruneScore seeds the kernel threshold; ``n_valid`` (real
        rows of a possibly-ragged final R block) keeps padding rows out of
        the kernel's threshold reduce."""
        from repro.kernels.knn_score.ops import _pad_rows, active_lists, dense_tiles_with_sentinel
        from repro.kernels.knn_topk.ops import pad_state

        ks = self._kernel_stack
        br_k = 256 if rb >= 256 else -(-rb // 8) * 8
        rv = jnp.arange(rb) < n_valid
        thr = min_prune_score(state, valid=rv).reshape(1, 1)
        r_tiles = _pad_rows(dense_tiles_with_sentinel(br, self.tile), br_k)
        r_occ = _host_row_occupancy(np.asarray(br.indices), self.dim, self.tile)
        active = jnp.asarray(active_lists(r_occ, ks.s_occ, br_k, ks.block_s))
        init_s, init_i = pad_state(state, r_tiles.shape[1])
        col_valid = ks.col_valid
        if col_cand is not None:
            col_valid = col_valid * col_cand.astype(jnp.int32)
        args = (r_tiles, ks.s_tiles, active, col_valid, ks.col_ids, init_s, init_i)
        kwargs = dict(
            thr=thr, nr_valid=jnp.full((1,), n_valid, jnp.int32),
            block_r=br_k, block_s=ks.block_s, interpret=_interpret_kernels(),
        )
        return args, kwargs

    def _query_fused_kernel(self, state, br, stats, rb, n_valid, col_cand=None):
        """One fused score→top-k kernel call covers every S block: scores
        stream tile-by-tile through VMEM, never materializing in HBM.  The
        kernel threshold rises across the S grid axis — earlier S blocks
        prune later ones without ever leaving the device."""
        from repro.kernels.knn_topk.kernel import knn_topk_pallas

        args, kwargs = self._fused_kernel_args(state, br, rb, n_valid, col_cand)
        out_s, out_i, _ = knn_topk_pallas(*args, **kwargs)
        stats.device_dispatches += 1
        stats.blocks += len(self._blocks)
        t_total = num_tiles(self.dim, self.tile)
        stats.tiles_scored += int((np.asarray(args[2]) < t_total).sum())
        return TopKState(scores=out_s[:rb], ids=out_i[:rb])

    def lowered_kernel(self, R: SparseBatch):
        """Lower (without running) the fused knn_topk call of ``R``'s first
        block, exactly as :meth:`query` makes it — ``as_text()`` shows
        whether the kernel compiles to Mosaic (``tpu_custom_call``) or runs
        interpreted."""
        from repro.kernels.knn_topk.kernel import knn_topk_pallas

        if not (self.spec.use_kernel and self._cache_device
                and self.algorithm == "iib"):
            raise ValueError("index has no fused-kernel query path "
                             "(needs use_kernel, algorithm='iib', cached blocks)")
        n_r = R.num_vectors
        rb = min(self.spec.r_block or self.plan_for(R).r_block, n_r)
        br, _ = _pad_block(R, 0, rb)
        args, kwargs = self._fused_kernel_args(
            init_topk(rb, self.spec.k), br, rb, min(rb, n_r))
        return knn_topk_pallas.lower(*args, **kwargs)

    # -- per-pair loops (streaming mode) -------------------------------------

    def _query_pairs(self, state, br, r_tiles, tiles, stats, rb, cand_np=None):
        """The legacy Algorithm-1 inner loop for BF/IIB: one step per
        (B_r, B_s) pair with transient device blocks (O(block) memory)."""
        spec = self.spec
        algorithm = self.algorithm
        sb = self.s_block
        tile = self.tile

        for bi, blk in enumerate(self._blocks):
            s0 = blk.start
            bs = _device_batch(blk.host)      # transient, per pair
            bv = self._block_valid(blk)
            if cand_np is not None:
                bv = bv & cand_np[bi]
            s_valid = jnp.asarray(bv)
            s_off = jnp.int32(s0)
            stats.blocks += 1

            if algorithm == "bf":
                state = _bf_step(state, br, bs, s_off, s_valid)
                stats.dense_pairs += rb * sb
                stats.device_dispatches += 1

            elif spec.use_kernel:
                # fused score→top-k kernel, one pair at a time (the
                # streaming counterpart of _query_fused_kernel)
                from repro.kernels.knn_topk.ops import knn_topk as _fused

                state = _fused(
                    br, bs, state=state, s_offset=s0, s_valid=bv,
                    tile=tile, block_r=min(256, rb), block_s=min(256, sb),
                    interpret=_interpret_kernels(),
                )
                stats.tiles_scored += int(tiles.shape[0])
                stats.device_dispatches += 1
            else:
                index = _build_index_iib(bs, max_rows=blk.bound, tile=tile)
                stats.index_builds += 1
                self.stats.index_builds += 1
                entries = int(np.asarray(index.counts).sum())
                stats.host_syncs += 1
                state = iib_join_block(
                    state, r_tiles, index, tiles, s_off, s_valid
                )
                stats.tiles_scored += int(tiles.shape[0])
                stats.list_entries += entries
                stats.device_dispatches += 2
        return state

    def _query_pairs_iiib(
        self, state, r_tiles, mwt, stats, sampled_mask, rv, cand_np=None
    ):
        """Streaming IIIB: the same masked-superset step as the scan, driven
        per pair — the superset index materializes transiently per (B_r,
        B_s) pair (legacy O(block) device-memory profile) and the threshold
        round-trips through the host, exactly the behaviour the scanned
        path is parity-tested against (bit-identical results; the scan just
        removes the rebuilds and the syncs)."""
        tile = self.tile
        s_valid = self._sampled_valid(sampled_mask)
        if cand_np is not None:
            s_valid = s_valid & cand_np

        for bi, blk in enumerate(self._blocks):
            bs = _device_batch(blk.host)
            index = _build_index_iib(
                bs, max_rows=blk.bound, tile=tile, rank=self._rank_dev
            )
            stats.index_builds += 1
            self.stats.index_builds += 1
            # the legacy per-pair threshold round-trip the scan eliminates
            thr = jnp.float32(float(np.asarray(min_prune_score(state, valid=rv))))
            stats.host_syncs += 1
            state, _, kept = iiib_masked_block(
                state, thr, r_tiles, index, jnp.asarray(blk.tilemass), mwt,
                jnp.int32(blk.start), jnp.asarray(s_valid[bi]), rv,
            )
            stats.device_dispatches += 2
            stats.blocks += 1
            stats.tiles_scored += int(r_tiles.shape[0])
            stats.list_entries += int(np.asarray(kept))
            stats.host_syncs += 1
        return state


# ---------------------------------------------------------------------------
# distributed face (mesh ring join)
# ---------------------------------------------------------------------------

def distributed_join(
    R: SparseBatch,
    S: SparseBatch,
    spec: JoinSpec,
    mesh,
    *,
    ring_axes: Sequence[str] = ("data",),
    dim_axis: Optional[str] = None,
    n_r_valid: Optional[int] = None,
    n_s_valid: Optional[int] = None,
) -> TopKState:
    """Mesh-distributed query: the engine face of the multi-device join.

    Rebased onto :class:`repro.store.ShardedKNNStore`: S is partitioned
    over ``ring_axes`` into per-shard device-resident index stacks (built
    once) and every R block is one fan-out dispatch with an on-device
    top-k reduction — O(R-blocks) dispatches instead of the legacy ring's
    rotate-and-rebuild.  The legacy ``lax.ppermute`` ring driver
    (core/ring.py) remains for ``dim_axis`` (dimension-sharded tensor
    parallelism), which the store does not cover yet, and for traced
    inputs: the store's build phase is host-driven (concrete block
    padding and index assembly), so under ``jax.jit`` tracing — the
    dry-run compiling the whole join as one program — the fully
    traceable ring runs instead.
    """
    import math

    n_r, n_s = R.num_vectors, S.num_vectors
    n_r_valid = n_r if n_r_valid is None else n_r_valid
    n_s_valid = n_s if n_s_valid is None else n_s_valid
    traced = isinstance(R.indices, jax.core.Tracer) or isinstance(
        S.indices, jax.core.Tracer
    )
    n_ring = math.prod(mesh.shape[a] for a in ring_axes)
    if dim_axis is not None or traced or n_s_valid < n_ring:
        # the store needs concrete data (host-driven build) and >= 1 row
        # per shard; the ppermute ring covers tracing (the dry-run),
        # dimension sharding, and degenerate tiny-S cases
        from repro.core.ring import _ring_join_impl

        return _ring_join_impl(
            R, S, spec.k, mesh,
            algorithm=spec.algorithm or "iiib",
            ring_axes=ring_axes, dim_axis=dim_axis, tile=spec.tile,
            n_r_valid=n_r_valid, n_s_valid=n_s_valid,
        )
    from repro.store import ShardedKNNStore
    # the ring API let callers pad R/S to the ring size; the store needs
    # neither the padding nor the divisibility, so strip it
    S_use = SparseBatch(
        indices=S.indices[:n_s_valid], values=S.values[:n_s_valid],
        nnz=S.nnz[:n_s_valid], dim=S.dim,
    )
    R_use = SparseBatch(
        indices=R.indices[:n_r_valid], values=R.values[:n_r_valid],
        nnz=R.nnz[:n_r_valid], dim=R.dim,
    )
    store = ShardedKNNStore(
        S_use, dataclasses.replace(spec, algorithm=spec.algorithm or "iiib"),
        mesh=mesh, axes=tuple(ring_axes),
    )
    res = store.query(R_use)
    if n_r_valid == n_r:
        return res.state
    pad = n_r - n_r_valid
    k = res.scores.shape[1]
    return TopKState(
        scores=jnp.concatenate(
            [res.scores, jnp.full((pad, k), -jnp.inf, jnp.float32)]
        ),
        ids=jnp.concatenate([res.ids, jnp.full((pad, k), -1, jnp.int32)]),
    )
