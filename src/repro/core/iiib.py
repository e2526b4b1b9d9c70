"""Improved inverted-index-based (IIIB) KNN join — paper Algorithm 4, TPU-adapted.

Two exact variants:

* **masked superset** (`iiib_masked_block` + `iiib_scan_join`; DESIGN.md §3)
  — the engine's form.  The tile-inverted index is built ONCE per S block
  with *every* feature indexed (a threshold-independent superset, in the
  datastore's dim-frequency-rank order), together with per-(row, tile)
  mass partial sums.  The paper's threshold refinement (lines 8-14 of
  Create_Inverted_List_IIIB) then becomes an on-device mask: with
  ``maxw_tile`` = per-tile maxWeight(B_r), the running upper bound of row
  s's frequency-ordered prefix is ``cumsum(maxw_tile * tilemass(s))``, and
  an entry (s, t) is "indexed" iff that inclusive prefix bound exceeds the
  live MinPruneScore — lists shrink by masking, never by rebuilding, so
  the whole S side of an R block runs as one jitted ``lax.scan`` whose
  carry holds the TopKState AND the threshold.  Candidate completion
  (paper lines 20-24) needs no separate rescue pass: the superset lists
  already hold the "unindexed" mass, so the same list values yield
  both the indexed score A (masked accumulate — what the candidate test
  reads) and the exact dot product (full accumulate — what enters the
  top-k).

* **uniform-crossing jit variant** (`iiib_join_block_uniform`) — used
  inside the distributed ring join where each step presents a *new* S
  shard (no build-once index to mask): the crossing tile is flattened to
  the block-min c_min; tiles < c_min are scored densely for all rows,
  tiles >= c_min via the pruned lists.  Exact by construction.

Soundness of the mask (tile-granular Theorem 1): for any r in the block,
``dot(r, s restricted to masked tiles) <= Σ_masked maxw_tile[t] ·
tilemass[s, t] = pref_ub(s) <= threshold <= pruneScore(r)`` — the masked
prefix alone can never improve any row's top-k, and a true candidate must
therefore share a *kept* feature (A > 0).  The true threshold only rises,
so masked sets only grow and no entry is ever wrongly skipped.
"""
from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.bf import block_ids
from repro.core.index import TileIndex, dense_r_tiles, masked_tile_scores, tile_scores
from repro.core.topk import (
    NEG_INF,
    TopKState,
    min_prune_score,
    prune_scores,
    topk_update,
)
from repro.sparse.format import (
    SparseBatch,
    dim_frequency,
    frequency_permutation,
    max_weight_per_dim,
    num_tiles,
)


def prepare_r_block(r_block: SparseBatch, tile: int):
    """Per-R-block precomputation for the ring join's IIIB variant.

    rank[d] = position of dim d in descending-frequency order (paper line 6);
    maxw[d] = maxWeight_d(B_r) in ORIGINAL dim space (paper line 7).
    """
    freq = dim_frequency(r_block)
    rank, _ = frequency_permutation(freq)
    maxw = max_weight_per_dim(r_block)
    r_tiles = dense_r_tiles(r_block, rank, tile)
    return rank, maxw, r_tiles


# ---------------------------------------------------------------------------
# build-time structures (threshold-independent; engine caches/stacks them)
# ---------------------------------------------------------------------------

def s_frequency_rank(dim_freq: np.ndarray) -> np.ndarray:
    """(D,) host rank: dim -> position in descending S-side frequency order.

    The engine's build-once analogue of the paper's per-B_r reordering
    (line 6): the datastore's own frequencies are known at ``build()`` and
    the ordering is a pruning heuristic, not a correctness input, so it is
    frozen into the superset index (stale after ``extend()`` by design —
    rebuilding would invalidate every retained stack block).
    """
    order = np.argsort(-np.asarray(dim_freq), kind="stable")
    rank = np.empty_like(order)
    rank[order] = np.arange(order.shape[0])
    return rank.astype(np.int32)


def tile_mass_host(
    idx: np.ndarray, val: np.ndarray, dim: int, rank: np.ndarray, tile: int
) -> np.ndarray:
    """(N, T) f32 — per-row value mass per rank-permuted dim-tile (host).

    The precomputed partial-sum input of the threshold mask: at query time
    ``cumsum(maxw_tile * tilemass, axis=1)`` is the frequency-ordered
    prefix upper bound of every row, and every pruning decision is a
    ``prefix_bound > threshold`` comparison against it.
    """
    t_total = num_tiles(dim, tile)
    valid = idx < dim
    p = np.where(valid, rank[np.minimum(idx, dim - 1)], t_total * tile)
    tid = np.minimum(p // tile, t_total)
    out = np.zeros((idx.shape[0], t_total + 1), np.float32)
    np.add.at(out, (np.arange(idx.shape[0])[:, None], tid), np.where(valid, val, 0.0))
    return out[:, :t_total]


def maxw_tiles(r_block: SparseBatch, rank: jax.Array, tile: int) -> jax.Array:
    """(T,) f32 — max maxWeight_d(B_r) per rank-permuted dim-tile (device).

    Tiles the R block never touches get 0, so the prefix bound only grows
    on tiles that can actually contribute to a dot product.
    """
    t_total = num_tiles(r_block.dim, tile)
    mw = max_weight_per_dim(r_block)
    out = jnp.zeros((t_total * tile,), jnp.float32).at[rank.astype(jnp.int32)].max(mw)
    return out.reshape(t_total, tile).max(axis=1)


# ---------------------------------------------------------------------------
# the masked block step (shared by the cached scan and the streaming loop)
# ---------------------------------------------------------------------------

def _masked_block(
    state: TopKState,
    thr: jax.Array,            # scalar f32 — live MinPruneScore
    r_tiles: jax.Array,        # (T, |Br|, tile) rank-permuted dense R tiles
    index: TileIndex,          # threshold-FREE superset index of the S block
    tilemass: jax.Array,       # (|Bs|, T) per-row per-tile value mass
    maxw_tile: jax.Array,      # (T,) per-tile maxWeight(B_r)
    s_offset: jax.Array,       # scalar first-row id or (|Bs|,) per-row global ids
    s_valid: jax.Array,        # (|Bs|,) bool — padding, tombstoned AND sampled rows
    r_valid: jax.Array,        # (|Br|,) bool — masks padded R rows out of the min
) -> Tuple[TopKState, jax.Array, jax.Array]:
    """One (B_r, B_s) IIIB step against the superset index; returns
    (state, new threshold, kept-entry count).  Pure jnp — inlined into the
    scan body by ``iiib_scan_join`` and jitted standalone for streaming.

    ``r_valid`` keeps a ragged final R block's padding rows (whose prune
    score is -inf forever — they never pass ``a_kept > 0``) from pinning
    the threshold at -inf; sound because the threshold only has to
    lower-bound the pruneScore of rows that can actually offer."""
    with jax.named_scope("knn.bound"):
        contrib = maxw_tile[None, :] * tilemass        # (|Bs|, T)
        cum = jnp.cumsum(contrib, axis=1)              # inclusive prefix bound
        keep = cum > thr                               # entry (s, t) stays indexed
        pref_ub = jnp.sum(jnp.where(keep, 0.0, contrib), axis=1)
    a_kept, a_full = masked_tile_scores(r_tiles, index, keep)
    prune = prune_scores(state)
    # Theorem 1 (shared kept feature) + the A + prefUB > pruneScore bound;
    # offered value is the EXACT dot (a_full) — completion without rescue
    with jax.named_scope("knn.bound"):
        offer = (
            (a_kept > 0.0)
            & (a_kept + pref_ub[None, :] > prune[:, None])
            & s_valid[None, :]
        )
        scores = jnp.where(offer, a_full, NEG_INF)
    ids = block_ids(s_offset, index.num_s)
    state = topk_update(state, scores, ids)
    kept_entries = jnp.sum(((tilemass > 0.0) & keep).astype(jnp.int32))
    return state, min_prune_score(state, valid=r_valid), kept_entries


iiib_masked_block = jax.jit(_masked_block)


@partial(jax.jit, static_argnames=("tile", "num_s"))
def iiib_scan_join(
    state: TopKState,
    thr: jax.Array,            # scalar f32 — seed threshold (warm start stays on device)
    r_tiles: jax.Array,        # (T, |Br|, tile)
    maxw_tile: jax.Array,      # (T,)
    s_rows: jax.Array,         # (B, T+1, M) int32 — stacked superset tile lists
    s_vals: jax.Array,         # (B, T+1, M, tile) f32
    s_counts: jax.Array,       # (B, T+1) int32
    s_mass: jax.Array,         # (B, num_s, T) f32 — stacked tilemass
    s_ids: jax.Array,          # (B, num_s) int32 — per-row global ids
    s_valid: jax.Array,        # (B, num_s) bool
    r_valid: jax.Array,        # (|Br|,) bool
    tile: int,
    num_s: int,
):
    """IIIB inner loop over ALL stacked S blocks as one scan — the carry is
    (TopKState, MinPruneScore), so the threshold refinement never leaves
    the device and lists shrink by masking, not rebuilding.

    Returns (state, final thr, (B,) per-block thr trace, (B,) kept-entry
    counts) — the traces ride home with the R block's result pull (same
    sync) and feed JoinStats.
    """
    pref_ub = jnp.zeros((num_s,), jnp.float32)
    crossing = jnp.zeros((num_s,), jnp.int32)

    def body(carry, xs):
        st, th = carry
        rows, vals, counts, mass, ids, vm = xs
        index = TileIndex(
            rows=rows, vals=vals, counts=counts, pref_ub=pref_ub,
            crossing=crossing, tile=tile, num_s=num_s,
        )
        st, th, kept = _masked_block(
            st, th, r_tiles, index, mass, maxw_tile, ids, vm, r_valid,
        )
        return (st, th), (th, kept)

    (state, thr), (thr_trace, kept_trace) = jax.lax.scan(
        body, (state, thr), (s_rows, s_vals, s_counts, s_mass, s_ids, s_valid)
    )
    return state, thr, thr_trace, kept_trace


# ---------------------------------------------------------------------------
# fully-jit variant (uniform crossing) — used by the distributed ring join
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("tile",))
def iiib_join_block_uniform(
    state: TopKState,
    r_block: SparseBatch,
    r_tiles: jax.Array,       # (T, |Br|, tile) permuted dense R tiles
    rank: jax.Array,
    index: TileIndex,
    s_block: SparseBatch,     # needed for the dense prefix pass
    s_offset: jax.Array,
    s_valid: jax.Array,
    tile: int,
) -> TopKState:
    """Exact jit-able IIIB step with block-uniform crossing tile.

    prefix tiles [0, c_min):  dense matmul for ALL rows (no lists needed);
    suffix tiles [c_min, T): via the pruned tile lists.
    The caller builds `index` with per-row crossings; flattening to c_min is
    done here by *also* scoring tiles in [c_min, min crossing of each row)
    densely — covered because indexed lists start at each row's own
    crossing, so dense prefix up to c_min + lists ≥ own crossing double-counts
    nothing only if lists start ≥ c_min, which per-row crossing guarantees
    (crossing(s) ≥ c_min).  Rows' features in [c_min, crossing(s)) are NOT
    in the lists and NOT in the dense prefix — so instead the caller must
    build this index with `uniform=True` semantics: crossing(s) := c_min for
    all s.  See ``build_uniform_index`` in ring.py.
    """
    t_total = r_tiles.shape[0]
    n_s = s_block.num_vectors

    # dense prefix: tiles < c_min (c_min encoded in index.crossing, uniform)
    c_min = index.crossing[0]
    s_tiles = dense_r_tiles(s_block, rank, tile)           # (T, |Bs|, tile)

    def prefix_body(acc, t):
        p = jax.lax.dot_general(
            r_tiles[t], s_tiles[t], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST,
        )
        return acc + jnp.where(t < c_min, p, 0.0), None

    acc0 = jnp.zeros((r_tiles.shape[1], n_s), jnp.float32)
    prefix, _ = jax.lax.scan(prefix_body, acc0, jnp.arange(t_total))

    # indexed suffix via lists (all tiles; lists are empty below crossing)
    suffix = tile_scores(r_tiles, index, jnp.arange(t_total, dtype=jnp.int32))

    scores = prefix + suffix
    ids = s_offset + jnp.arange(n_s, dtype=jnp.int32)
    scores = jnp.where(s_valid[None, :], scores, -jnp.inf)
    return topk_update(state, scores, ids)
