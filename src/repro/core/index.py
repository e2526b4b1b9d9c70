"""Tile-granular inverted index — the TPU-native form of the paper's I_d lists.

On TPU, a per-dimension inverted list (pointer-chasing) has no efficient
analogue.  We lift the index to *dim-tile* granularity: the dimension axis
is cut into ``tile``-wide groups (lane-width multiples); for each tile the
index stores the list of S rows with any (indexed) mass in that tile,
together with a densified ``(row, tile)`` value patch.  Scoring a tile is
then one MXU matmul ``(|Br|, tile) @ (tile, M)`` plus a column scatter-add
into the accumulator — work proportional to the *list length* ``M``, not
|Bs|, exactly the paper's C3 structure.  Every list of a block is padded to
one ``M``, so the cost is ∝ ``M`` per tile.  ``M`` is close to |Bs| on the
shipped configurations, so the IIIB scan instead puts the patches back in
S-row order and scores the block with one dense product
(``masked_tile_scores``).

The same builder implements IIIB's threshold refinement (§4.4): features
are walked in descending frequency(B_r) order accumulating the trivial
upper bound ``t += maxWeight_d(B_r)·s[d]``; a row's features are indexed
only from the tile containing the first crossing feature onward.  The
unindexed prefix then provably satisfies ``dot(r, prefix) ≤ MinPruneScore``
for every r (tile-granular Theorem 1 — our unindexed set is a subset of
the paper's unindexed prefix, so its upper bound can only be smaller).

Everything here is jit-able given a static ``max_rows`` bound; the host
driver (blocknl) computes a concrete bound per block with numpy first.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.sparse.format import SparseBatch, num_tiles

DEFAULT_TILE = 128


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class TileIndex:
    """Inverted index at dim-tile granularity over one S block (permuted dims).

    Arrays carry one extra sentinel tile (id = n_tiles) with empty lists so a
    padded active-tile list can point at it harmlessly.
    """

    rows: jax.Array      # (T+1, M) int32 — S-row ids per tile; sentinel num_s
    vals: jax.Array      # (T+1, M, tile) f32 — densified indexed values
    counts: jax.Array    # (T+1,) int32
    pref_ub: jax.Array   # (N,) f32 — UB of each row's unindexed prefix (0 for IIB)
    crossing: jax.Array  # (N,) int32 — first indexed tile per row (0 for IIB)
    tile: int            # static
    num_s: int           # static

    def tree_flatten(self):
        return (self.rows, self.vals, self.counts, self.pref_ub, self.crossing), (
            self.tile,
            self.num_s,
        )

    @classmethod
    def tree_unflatten(cls, static, leaves):
        rows, vals, counts, pref_ub, crossing = leaves
        tile, num_s = static
        return cls(rows, vals, counts, pref_ub, crossing, tile, num_s)

    @property
    def n_tiles(self) -> int:
        return self.rows.shape[0] - 1

    @property
    def max_rows(self) -> int:
        return self.rows.shape[1]


# ---------------------------------------------------------------------------
# builder
# ---------------------------------------------------------------------------

def _permuted_features(s_block: SparseBatch, rank: Optional[jax.Array]):
    """Per-row feature dims mapped through ``rank``; returns (p_idx, valid)."""
    valid = s_block.indices < s_block.dim
    if rank is not None:
        lut = jnp.concatenate([rank.astype(jnp.int32), jnp.array([s_block.dim], jnp.int32)])
        p_idx = lut[jnp.minimum(s_block.indices, s_block.dim)]
    else:
        p_idx = s_block.indices
    return jnp.where(valid, p_idx, s_block.dim), valid


def _sorted_features(s_block: SparseBatch, rank: Optional[jax.Array]):
    """Per-row features sorted by (permuted) dimension; returns (p_idx, vals, valid)."""
    p_idx, _ = _permuted_features(s_block, rank)
    order = jnp.argsort(p_idx, axis=1, stable=True)
    sp = jnp.take_along_axis(p_idx, order, axis=1)
    sv = jnp.take_along_axis(s_block.values, order, axis=1)
    sval = sp < s_block.dim
    return sp, sv, sval, order


def build_tile_index(
    s_block: SparseBatch,
    max_rows: int,
    tile: int = DEFAULT_TILE,
    rank: Optional[jax.Array] = None,
    maxw: Optional[jax.Array] = None,
    min_prune_score: Optional[jax.Array] = None,
    uniform: bool = False,
) -> TileIndex:
    """Build the tile index.  IIB: leave ``maxw``/``min_prune_score`` None.

    IIIB: pass ``rank`` (dim -> frequency position, most frequent = 0),
    ``maxw`` = maxWeight_d(B_r) in ORIGINAL dim space, and the running
    MinPruneScore.  Rows' feature prefixes whose cumulative UB never exceeds
    the threshold stay unindexed (paper Alg. 4 lines 8-14).
    """
    n, f = s_block.indices.shape
    d = s_block.dim
    t_total = num_tiles(d, tile)

    if min_prune_score is None:
        # IIB / superset path: no crossing walk, so the per-row feature sort
        # (only needed to order the cumulative-bound walk) is skipped
        sp, sval = _permuted_features(s_block, rank)
        sv = s_block.values
        crossing = jnp.zeros((n,), jnp.int32)
        pref_ub = jnp.zeros((n,), jnp.float32)
    else:
        sp, sv, sval, order = _sorted_features(s_block, rank)
        maxw_pad = jnp.concatenate([maxw.astype(jnp.float32), jnp.zeros((1,), jnp.float32)])
        m = maxw_pad[jnp.minimum(s_block.indices, d)]
        ms = jnp.take_along_axis(jnp.where(s_block.indices < d, m, 0.0), order, axis=1)
        contrib = jnp.where(sval, ms * sv, 0.0)
        cum = jnp.cumsum(contrib, axis=1)
        crossed = (cum > min_prune_score) & sval
        any_crossed = crossed.any(axis=1)
        first_pos = jnp.argmax(crossed, axis=1)
        crossing_dim = jnp.take_along_axis(sp, first_pos[:, None], axis=1)[:, 0]
        crossing = jnp.where(any_crossed, crossing_dim // tile, t_total).astype(jnp.int32)
        prev = jnp.where(first_pos > 0, jnp.take_along_axis(cum, jnp.maximum(first_pos - 1, 0)[:, None], axis=1)[:, 0], 0.0)
        # rows that never cross keep their FULL mass unindexed
        full_ub = cum[:, -1]
        pref_ub = jnp.where(any_crossed, prev, full_ub).astype(jnp.float32)
        if uniform:
            # flatten to the block-min crossing (jit-able IIIB variant):
            # strictly MORE gets indexed, so exactness is preserved; the
            # dense-prefix pass covers everything below c_min uniformly.
            c_min = jnp.min(crossing)
            crossing = jnp.full_like(crossing, c_min)
            tile_of = jnp.where(sval, sp // tile, t_total)
            pref_contrib = jnp.where(tile_of < c_min, contrib, 0.0)
            pref_ub = jnp.sum(pref_contrib, axis=1).astype(jnp.float32)

    f_tid = jnp.where(sval, sp // tile, t_total).astype(jnp.int32)
    indexed = sval & (f_tid >= crossing[:, None])

    # occupancy (N, T): row n has indexed mass in tile t
    occ = jnp.zeros((n, t_total + 1), jnp.int32)
    occ = occ.at[jnp.arange(n)[:, None], jnp.where(indexed, f_tid, t_total)].add(1)
    occ = occ[:, :t_total] > 0

    counts = occ.sum(axis=0).astype(jnp.int32)  # (T,)
    m_rows = min(max_rows, n)

    # pack occupied rows to the front, per tile: slot[s, t] = number of
    # occupied rows before s (identical packing to a stable sort on ~occ,
    # without the O(N log N · T) argsort) — one cumsum + two scatters
    slot = jnp.cumsum(occ.astype(jnp.int32), axis=0) - 1     # (N, T)
    ok_row = occ & (slot < m_rows)
    row_ids = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32)[:, None], (n, t_total))
    t_ids = jnp.broadcast_to(jnp.arange(t_total, dtype=jnp.int32)[None, :], (n, t_total))
    rows = jnp.full((t_total + 1, m_rows), n, jnp.int32)
    rows = rows.at[
        jnp.where(ok_row, t_ids, t_total), jnp.clip(slot, 0, m_rows - 1)
    ].set(jnp.where(ok_row, row_ids, n))

    # densify indexed values with ONE segment-scatter over every (row,
    # feature) pair: target (tile, list slot, lane) — replaces the former
    # lax.map over tiles (a gather + scatter per tile)
    slot_pad = jnp.concatenate([slot, jnp.zeros((n, 1), slot.dtype)], axis=1)
    slot_f = jnp.take_along_axis(slot_pad, jnp.minimum(f_tid, t_total), axis=1)  # (N, F)
    ok_f = indexed & (slot_f < m_rows)
    rel = jnp.where(ok_f, sp - f_tid * tile, tile)
    vals = jnp.zeros((t_total + 1, m_rows, tile + 1), jnp.float32)
    vals = vals.at[
        jnp.where(ok_f, f_tid, t_total), jnp.clip(slot_f, 0, m_rows - 1), rel
    ].add(jnp.where(ok_f, sv, 0.0))
    vals = vals[:, :, :tile]

    counts = jnp.concatenate([counts, jnp.zeros((1,), jnp.int32)])

    return TileIndex(
        rows=rows, vals=vals, counts=counts, pref_ub=pref_ub, crossing=crossing,
        tile=tile, num_s=n,
    )


def max_rows_bound(
    s_block: SparseBatch,
    tile: int = DEFAULT_TILE,
    rank: Optional[np.ndarray] = None,
    maxw: Optional[np.ndarray] = None,
    min_prune_score: float = -np.inf,
    bucket: int = 128,
) -> int:
    """Host-side concrete bound on the longest tile list (numpy mirror of the
    builder's occupancy computation), bucketed to limit recompilation."""
    idx = np.asarray(s_block.indices)
    val = np.asarray(s_block.values)
    d = s_block.dim
    valid = idx < d
    p_idx = np.where(valid, (rank[np.minimum(idx, d - 1)] if rank is not None else idx), d)
    t_total = num_tiles(d, tile)
    if min_prune_score == -np.inf or maxw is None:
        # threshold-free (IIB / superset) bound: no crossing walk, no sort
        sp, sval = p_idx, valid
        crossing = np.zeros(idx.shape[0], np.int64)
    else:
        order = np.argsort(p_idx, axis=1, kind="stable")
        sp = np.take_along_axis(p_idx, order, axis=1)
        sval = sp < d
        m = np.where(valid, maxw[np.minimum(idx, d - 1)], 0.0)
        ms = np.take_along_axis(m * val, order, axis=1)
        cum = np.cumsum(np.where(sval, ms, 0.0), axis=1)
        crossed = (cum > min_prune_score) & sval
        any_c = crossed.any(axis=1)
        first = np.where(any_c, np.argmax(crossed, axis=1), 0)
        cdim = np.take_along_axis(sp, first[:, None], axis=1)[:, 0]
        crossing = np.where(any_c, cdim // tile, t_total)
    f_tid = np.where(sval, sp // tile, t_total)
    indexed = sval & (f_tid >= crossing[:, None])
    occ = np.zeros((idx.shape[0], t_total + 1), np.int64)
    np.add.at(occ, (np.arange(idx.shape[0])[:, None], np.where(indexed, f_tid, t_total)), 1)
    longest = int((occ[:, :t_total] > 0).sum(axis=0).max(initial=0))
    longest = max(longest, 1)
    return min(int(-(-longest // bucket) * bucket), idx.shape[0])


# ---------------------------------------------------------------------------
# scoring with the index
# ---------------------------------------------------------------------------

def tile_scores(
    r_dense_tiles: jax.Array,    # (T, |Br|, tile) — permuted-dim dense tiles of B_r
    index: TileIndex,
    active_tiles: jax.Array,     # (A,) int32 tile ids; pad with n_tiles (sentinel)
) -> jax.Array:
    """(|Br|, |Bs|) accumulated scores over the given tiles.

    Work per tile ∝ list length M (not |Bs|): one (|Br|, tile)@(tile, M)
    matmul + a column scatter-add — the C3 cost shape on MXU hardware.
    """
    n_r = r_dense_tiles.shape[1]
    r_pad = jnp.concatenate(
        [r_dense_tiles, jnp.zeros((1,) + r_dense_tiles.shape[1:], r_dense_tiles.dtype)], axis=0
    )

    def body(acc, t):
        rt = r_pad[t]                       # (|Br|, tile)
        v = index.vals[t]                   # (M, tile)
        with jax.named_scope("knn.matmul"):
            p = jax.lax.dot_general(
                rt, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
                precision=jax.lax.Precision.HIGHEST,
            )                               # (|Br|, M)
        with jax.named_scope("knn.scatter"):
            acc = acc.at[:, index.rows[t]].add(p)
        return acc, None

    acc = jnp.zeros((n_r, index.num_s + 1), jnp.float32)
    acc, _ = jax.lax.scan(body, acc, active_tiles)
    return acc[:, : index.num_s]


# The dense scan's temporaries, per S block: the densified S, its kept copy,
# and the two stacked for the product, each (T, |Bs|, tile) float32 (the
# stack twice that): at most 4 · T · |Bs| · tile · 4 B.  At synth50k's
# geometry (T = 79, |Bs| = 4,096) that is 0.66 GB; a described v5e compile
# of the store's fan-out there holds 0.49 GB of temporaries in all.  The
# bound allows a quarter of one v5e's 16 GB: with |Bs| = 4,096, up to
# T = 512 tiles (dim 65,536); a larger dim needs a smaller s_block.
DENSE_SCAN_MAX_BYTES = 4 * 2**30


def dense_scan_bytes(t_total: int, num_s: int, tile: int) -> int:
    """Upper bound of ``masked_tile_scores``'s temporaries for one S block
    of ``num_s`` rows over ``t_total`` tiles of ``tile`` dims."""
    return 4 * t_total * num_s * tile * 4


def masked_tile_scores(
    r_dense_tiles: jax.Array,    # (T, |Br|, tile) — permuted-dim dense tiles of B_r
    index: TileIndex,
    keep: jax.Array,             # (|Bs|, T) bool — entry (s, t) survives the threshold
) -> Tuple[jax.Array, jax.Array]:
    """IIIB threshold refinement as an on-device mask over a superset index.

    ``index`` is a threshold-FREE index (every feature indexed); ``keep``
    encodes the live MinPruneScore refinement (``prefix_bound > threshold``
    per (row, tile) — see core/iiib.py).  Returns two (|Br|, |Bs|) score
    accumulators from the SAME list values:

      kept: Σ over unmasked entries — the paper's indexed-feature score A,
            what the candidate test (Theorem 1 + bound check) reads;
      full: Σ over ALL entries — since the superset index holds every
            feature, this is the exact dot product, which is what survives
            into the top-k (the paper's candidate completion, without a
            separate rescue pass: the "unindexed" mass is already sitting
            in the masked-out slots of the same lists).

    The list slots' patches go back in S-row order, once per S block, and
    both accumulators come from one product against the kept and the full
    operand.  Every list is padded to one ``M``, about |Bs| on the shipped
    configurations, so per-tile list products would do nearly the dense
    work and add a column scatter of each.  Inactive tiles hold zero R
    mass, so scoring all T tiles gives the sums of the active ones.  Raises
    where the temporaries would pass ``DENSE_SCAN_MAX_BYTES``.
    """
    t_total, n_r, tile = r_dense_tiles.shape
    n_s = index.num_s
    need = dense_scan_bytes(t_total, n_s, tile)
    if need > DENSE_SCAN_MAX_BYTES:
        raise ValueError(
            f"IIIB scan temporaries of {need / 2**30:.1f} GiB per S block "
            f"({t_total} tiles x {n_s} rows) pass the "
            f"{DENSE_SCAN_MAX_BYTES / 2**30:.0f} GiB bound; use a smaller "
            "s_block"
        )
    with jax.named_scope("knn.scatter"):
        rows = index.rows[:t_total]                              # (T, M)
        slot = jnp.arange(t_total, dtype=jnp.int32)[:, None] * n_s + rows
        # sentinel slots go to distinct out-of-range rows, which the scatter
        # drops, so every index is unique
        spare = t_total * n_s + jnp.arange(rows.size, dtype=jnp.int32)
        slot = jnp.where(rows < n_s, slot, spare.reshape(rows.shape))
        s_dense = jnp.zeros((t_total * n_s, tile), jnp.float32).at[
            slot.reshape(-1)
        ].set(
            index.vals[:t_total].reshape(-1, tile),
            mode="drop", unique_indices=True,
        ).reshape(t_total, n_s, tile)
    with jax.named_scope("knn.bound"):
        s_kept = jnp.where(keep.T[:, :, None], s_dense, 0.0)
        both = jnp.concatenate([s_kept, s_dense], axis=1)      # (T, 2|Bs|, tile)

    def flat(x):  # (T, n, tile) -> (n, T·tile)
        return jnp.transpose(x, (1, 0, 2)).reshape(x.shape[1], -1)

    # One contracting axis makes each score sum its row pair in one order
    # wherever the pair sits in the block; a one-row block would take a
    # matrix-vector product, which sums in another order, so it gets a
    # zero second row
    r_rows = flat(r_dense_tiles)
    if n_r == 1:
        r_rows = jnp.pad(r_rows, ((0, 1), (0, 0)))
    with jax.named_scope("knn.matmul"):
        acc = jax.lax.dot_general(
            r_rows, flat(both), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST,
        )[:n_r]                                                  # (|Br|, 2|Bs|)
    return acc[:, :n_s], acc[:, n_s:]


def dense_r_tiles(r_block: SparseBatch, rank: Optional[jax.Array], tile: int = DEFAULT_TILE) -> jax.Array:
    """(T, |Br|, tile) dense tiles of the R block in permuted dim space."""
    n, _ = r_block.indices.shape
    d = r_block.dim
    t_total = num_tiles(d, tile)
    valid = r_block.indices < d
    if rank is not None:
        lut = jnp.concatenate([rank.astype(jnp.int32), jnp.array([d], jnp.int32)])
        p_idx = lut[jnp.minimum(r_block.indices, d)]
    else:
        p_idx = jnp.where(valid, r_block.indices, d)
    p_idx = jnp.where(valid, p_idx, t_total * tile)
    out = jnp.zeros((n, t_total * tile + 1), jnp.float32)
    out = out.at[jnp.arange(n)[:, None], jnp.minimum(p_idx, t_total * tile)].add(
        jnp.where(valid, r_block.values, 0.0)
    )
    return out[:, : t_total * tile].reshape(n, t_total, tile).transpose(1, 0, 2)


def active_tile_list(occ_any: np.ndarray, bucket: int = 8) -> np.ndarray:
    """Host-side: concrete list of tiles with any R-block mass, padded with the
    sentinel tile id to a bucket multiple (bounds recompiles)."""
    (tiles,) = np.nonzero(occ_any)
    n_tiles = occ_any.shape[0]
    pad = -(-max(len(tiles), 1) // bucket) * bucket
    out = np.full(pad, n_tiles, dtype=np.int32)
    out[: len(tiles)] = tiles
    return out
