"""Paper-faithful host reference implementations (Algorithms 1-4)."""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.reference import HostCSR, oracle_knn, reference_join, topk_agreement
from repro.sparse.datagen import synthetic_sparse
from repro.sparse.format import densify


def _to_host(sb):
    return HostCSR.from_padded(sb.indices, sb.values, sb.nnz, sb.dim)


def _check_against_oracle(scores, ids, osc, k):
    """Compare only positive-score slots: IIB/IIIB never return zero-overlap
    vectors (paper semantics) while the dense oracle returns arbitrary ones."""
    pos = osc > 0
    np.testing.assert_allclose(
        np.where(pos, scores, 0.0), np.where(pos, osc, 0.0), atol=1e-6
    )


@pytest.mark.parametrize("algorithm", ["bf", "iib", "iiib"])
@pytest.mark.parametrize("blocks", [(None, None), (16, 32), (7, 13)])
def test_reference_matches_oracle(small_rs, algorithm, blocks):
    R, S = small_rs
    Rh, Sh = _to_host(R), _to_host(S)
    k = 5
    sc, ids = reference_join(Rh, Sh, k, algorithm=algorithm,
                             r_block=blocks[0], s_block=blocks[1])
    osc, _ = oracle_knn(np.asarray(densify(R)), np.asarray(densify(S)), k)
    _check_against_oracle(sc, ids, osc, k)


@pytest.mark.parametrize("k", [1, 3, 10])
def test_reference_k_sweep(small_rs, k):
    R, S = small_rs
    Rh, Sh = _to_host(R), _to_host(S)
    osc, _ = oracle_knn(np.asarray(densify(R)), np.asarray(densify(S)), k)
    for algorithm in ("bf", "iib", "iiib"):
        sc, _ = reference_join(Rh, Sh, k, algorithm=algorithm, s_block=17)
        _check_against_oracle(sc, None, osc, k)


def test_three_algorithms_agree(small_rs):
    """The paper's central exactness claim: IIB and IIIB return the same
    join as BF (Theorem 1), regardless of block sizes."""
    R, S = small_rs
    Rh, Sh = _to_host(R), _to_host(S)
    sc_bf, _ = reference_join(Rh, Sh, 5, algorithm="bf", s_block=19)
    sc_iib, _ = reference_join(Rh, Sh, 5, algorithm="iib", s_block=23)
    sc_iiib, _ = reference_join(Rh, Sh, 5, algorithm="iiib", s_block=11)
    pos = sc_bf > 0
    np.testing.assert_allclose(np.where(pos, sc_iib, 0), np.where(pos, sc_bf, 0), atol=1e-9)
    np.testing.assert_allclose(np.where(pos, sc_iiib, 0), np.where(pos, sc_bf, 0), atol=1e-9)


@settings(max_examples=15, deadline=None)
@given(st.integers(0, 10_000))
def test_property_iiib_exact(seed):
    """Hypothesis: on random sparse data, IIIB == BF on all positive scores."""
    R = synthetic_sparse(12, dim=128, nnz_mean=10, nnz_std=3, seed=seed)
    S = synthetic_sparse(20, dim=128, nnz_mean=10, nnz_std=3, seed=seed + 1)
    Rh, Sh = _to_host(R), _to_host(S)
    sc_bf, _ = reference_join(Rh, Sh, 3, algorithm="bf", s_block=7)
    sc_iiib, _ = reference_join(Rh, Sh, 3, algorithm="iiib", s_block=7)
    pos = sc_bf > 0
    np.testing.assert_allclose(
        np.where(pos, sc_iiib, 0), np.where(pos, sc_bf, 0), atol=1e-9
    )


def test_threshold_tightens_across_blocks(small_rs):
    """MinPruneScore should rise as S blocks stream (monotone pruning)."""
    R, S = small_rs
    Rh, Sh = _to_host(R), _to_host(S)
    from repro.core.reference import _KnnState, _iiib_block

    state = _KnnState(Rh.num_vectors, 5)
    mps = [state.min_prune_score()]
    sb = 20
    for s0 in range(0, Sh.num_vectors, sb):
        s1 = min(s0 + sb, Sh.num_vectors)
        _iiib_block(state, Rh, Sh.slice_rows(s0, s1), s0)
        mps.append(state.min_prune_score())
    assert mps[-1] > -np.inf
    assert all(b >= a for a, b in zip(mps, mps[1:])), mps


def test_topk_agreement_tolerates_only_ties():
    """Scores within tol pass; ids are held to the reference only at slots
    more than tol from both neighbours (the k-th slot's lower neighbour
    comes from a one-deeper reference, or the slot counts as tied)."""
    ref_s = np.array([[5.0, 4.0, 4.0 - 1e-6, 2.0, 1.0, 0.5],
                      [3.0, 2.0, 1.0, -np.inf, -np.inf, -np.inf]])
    ref_i = np.array([[0, 1, 2, 3, 4, 5], [7, 8, 9, -1, -1, -1]])
    got_s, got_i = ref_s[:, :5].copy(), ref_i[:, :5].copy()
    got_i[0, [1, 2]] = [2, 1]                 # a swap inside a tie
    got_s[0, 0] += 1e-5                       # rounding within tol
    a = topk_agreement(ref_s, ref_i, got_s, got_i, tol=1e-4)
    assert a == {"max_score_err": pytest.approx(1e-5), "ids_checked": 6,
                 "id_mismatches": 0}
    assert topk_agreement(ref_s[:, :5], ref_i[:, :5], got_s, got_i,
                          tol=1e-4)["ids_checked"] == 5
    got_i[0, 3] = 9                           # an untied slot differs
    assert topk_agreement(ref_s, ref_i, got_s, got_i, tol=1e-4)["id_mismatches"] == 1
    got_s[1, 3] = 0.1                         # an empty slot filled
    assert topk_agreement(ref_s, ref_i, got_s, got_i, tol=1e-4)["max_score_err"] == np.inf
