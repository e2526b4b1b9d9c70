"""Streaming top-k state properties (hypothesis)."""
import numpy as np
import jax.numpy as jnp
from hypothesis import given, settings, strategies as st

from repro.core.topk import (
    init_topk,
    merge_topk_states,
    min_prune_score,
    prune_scores,
    topk_update,
)


@settings(max_examples=30, deadline=None)
@given(st.integers(1, 8), st.integers(1, 6), st.integers(0, 500))
def test_streaming_equals_global(n, k, seed):
    rng = np.random.default_rng(seed)
    m = 40
    scores = rng.standard_normal((n, m)).astype(np.float32)
    ids = np.arange(m, dtype=np.int32)
    # streaming in 4 chunks
    state = init_topk(n, k)
    for lo in range(0, m, 10):
        state = topk_update(state, jnp.asarray(scores[:, lo:lo + 10]),
                            jnp.asarray(ids[lo:lo + 10]))
    want = np.sort(scores, axis=1)[:, ::-1][:, :k]
    np.testing.assert_allclose(np.asarray(state.scores), want, atol=1e-6)


@settings(max_examples=20, deadline=None)
@given(st.integers(0, 500))
def test_prune_scores_monotone(seed):
    rng = np.random.default_rng(seed)
    state = init_topk(6, 3)
    last = np.asarray(prune_scores(state))
    for _ in range(5):
        block = rng.standard_normal((6, 7)).astype(np.float32)
        state = topk_update(state, jnp.asarray(block),
                            jnp.asarray(np.arange(7, dtype=np.int32)))
        cur = np.asarray(prune_scores(state))
        assert (cur >= last - 1e-7).all()
        last = cur
    assert float(min_prune_score(state)) == float(np.asarray(state.scores)[:, -1].min())


def test_neg_inf_initialization():
    state = init_topk(4, 3)
    assert np.isneginf(np.asarray(state.scores)).all()
    assert (np.asarray(state.ids) == -1).all()


@settings(max_examples=25, deadline=None)
@given(st.integers(1, 6), st.integers(1, 9), st.integers(0, 10_000))
def test_merge_topk_states_matches_topk_update(n, k, seed):
    """The shared insertion body (``insert_candidates``, whose column
    extraction is a one-hot masked max) merges two states exactly as a
    concat + lax.top_k does: same scores, same ids, ties to the first
    state, empty (-inf) slots included."""
    rng = np.random.default_rng(seed)

    def state(offset):
        m = int(rng.integers(0, 2 * k + 1))
        sc = rng.integers(0, 4, (n, m)).astype(np.float32)   # many ties
        sc[rng.random((n, m)) < 0.25] = -np.inf
        ids = offset + np.arange(m, dtype=np.int32)
        return topk_update(init_topk(n, k), jnp.asarray(sc), jnp.asarray(ids))

    a, b = state(0), state(1000)
    got = merge_topk_states(a, b)
    want = topk_update(a, b.scores, b.ids)
    np.testing.assert_array_equal(np.asarray(got.scores), np.asarray(want.scores))
    np.testing.assert_array_equal(np.asarray(got.ids), np.asarray(want.ids))
