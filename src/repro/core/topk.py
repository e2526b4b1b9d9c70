"""Streaming top-k state for the KNN join.

The paper keeps, per outer vector r, a KNN candidate set and a
``pruneScore(r)`` = similarity of r's current k-th nearest neighbour.  We
vectorize this over a whole R block: the state is a pair of (N, k) arrays
(scores descending, global S ids), merged with each new block of scores via
``jax.lax.top_k`` on the concatenation.  ``prune_scores`` is column k-1 —
−inf until k candidates have been seen, exactly like the paper's
initialization (InitPruneScore, Algorithm 1 line 3).

``MinPruneScore`` (IIIB §4.4) is the min over the block.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

NEG_INF = jnp.float32(-jnp.inf)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class TopKState:
    scores: jax.Array  # (N, k) f32, descending; -inf for empty slots
    ids: jax.Array     # (N, k) int32, global S indices; -1 for empty slots

    def tree_flatten(self):
        return (self.scores, self.ids), None

    @classmethod
    def tree_unflatten(cls, _, leaves):
        return cls(*leaves)

    @property
    def k(self) -> int:
        return self.scores.shape[1]


def init_topk(num_vectors: int, k: int) -> TopKState:
    return TopKState(
        scores=jnp.full((num_vectors, k), NEG_INF, dtype=jnp.float32),
        ids=jnp.full((num_vectors, k), -1, dtype=jnp.int32),
    )


def topk_update(state: TopKState, new_scores: jax.Array, new_ids: jax.Array) -> TopKState:
    """Merge an (N, M) block of candidate scores into the running top-k.

    ``new_ids`` is (M,) (shared columns — the usual case: a block of S) or
    (N, M).  Invalid candidates must carry score −inf.
    """
    n, m = new_scores.shape
    with jax.named_scope("knn.topk"):
        if new_ids.ndim == 1:
            new_ids = jnp.broadcast_to(new_ids[None, :], (n, m))
        all_scores = jnp.concatenate([state.scores, new_scores.astype(jnp.float32)], axis=1)
        all_ids = jnp.concatenate([state.ids, new_ids.astype(jnp.int32)], axis=1)
        top_scores, top_pos = jax.lax.top_k(all_scores, state.k)
        top_ids = jnp.take_along_axis(all_ids, top_pos, axis=1)
    return TopKState(scores=top_scores, ids=top_ids)


def pad_topk_state(state: TopKState, n_pad: int) -> TopKState:
    """Pad to ``n_pad`` rows with empty (-inf, -1) slots (kernel block plumbing)."""
    n, k = state.scores.shape
    scores = jnp.full((n_pad, k), NEG_INF, jnp.float32).at[:n].set(
        state.scores.astype(jnp.float32)
    )
    ids = jnp.full((n_pad, k), -1, jnp.int32).at[:n].set(state.ids.astype(jnp.int32))
    return TopKState(scores=scores, ids=ids)


def merge_topk_states(a: TopKState, b: TopKState) -> TopKState:
    """Merge two per-row top-k states; ties favour ``a`` (the lower shard).

    The merge body is the shared insertion epilogue of kernels/topk_merge
    (also the per-S-block epilogue of the fused knn_topk kernel), so the
    sharded store's reduction tree and the kernels resolve ties identically
    to ``topk_update`` — equal scores keep the earliest-offered entry,
    which is what makes a fan-out/reduce over row-range shards bit-identical
    to the sequential S-block scan.
    """
    from repro.kernels.topk_merge.kernel import insert_candidates

    with jax.named_scope("knn.topk"):
        scores, ids = insert_candidates(a.scores, a.ids, b.scores, b.ids)
    return TopKState(scores=scores, ids=ids)


def tree_reduce_topk(state: TopKState, axis_name, num_shards: int) -> TopKState:
    """All-reduce per-shard TopKStates over a mesh axis into the global top-k.

    Communication is one ``all_gather`` of the (N, k) states; the merge is a
    log-depth binary tree of :func:`merge_topk_states` in shard order (shard
    i's rows precede shard i+1's in the conceptual concatenated S, so the
    lower shard always sits on the tie-winning side).  Every shard computes
    the identical reduction, so the result is replicated — callable only
    inside ``shard_map``/``pmap`` tracing over ``axis_name``.
    """
    with jax.named_scope("knn.topk"):
        all_scores = jax.lax.all_gather(state.scores, axis_name)  # (shards, N, k)
        all_ids = jax.lax.all_gather(state.ids, axis_name)
        states = [
            TopKState(scores=all_scores[i], ids=all_ids[i]) for i in range(num_shards)
        ]
        while len(states) > 1:
            nxt = [
                merge_topk_states(states[i], states[i + 1])
                if i + 1 < len(states) else states[i]
                for i in range(0, len(states), 2)
            ]
            states = nxt
    return states[0]


def prune_scores(state: TopKState) -> jax.Array:
    """(N,) — pruneScore(r): the k-th best score so far (−inf if < k seen)."""
    return state.scores[:, -1]


def min_prune_score(state: TopKState, valid: jax.Array | None = None) -> jax.Array:
    """Scalar MinPruneScore = min_{r in block} pruneScore(r) (IIIB threshold).

    ``valid`` masks padding rows out of the min: a padded row's prune score
    stays -inf forever (it never accrues candidates), which would pin the
    threshold at -inf and silently disable pruning for any partial block.
    Excluding rows that never offer candidates is sound — the threshold
    only needs to lower-bound the pruneScore of rows that DO offer.
    """
    ps = prune_scores(state)
    if valid is not None:
        ps = jnp.where(valid, ps, jnp.inf)
    return jnp.min(ps)
