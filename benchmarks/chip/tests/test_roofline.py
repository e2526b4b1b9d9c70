import numpy as np
import pytest

import roofline


def test_ops_and_bytes_on_a_hand_counted_case():
    # S: rows {0, 2}, {2}, {1, 2, 3} over dim 4 -> |S_d| = 1, 1, 3, 1
    s_idx = np.array([[0, 2, 4], [2, 4, 4], [1, 2, 3]], np.int32)
    s_nnz = np.array([2, 1, 3], np.int32)
    counts = roofline.dim_counts(s_idx, s_nnz, 4)
    np.testing.assert_array_equal(counts, [1, 1, 3, 1])
    # R: {2, 3} meets 3 + 1 stored non-zeros, {0} meets 1
    r_idx = np.array([[2, 3], [0, 4]], np.int32)
    r_nnz = np.array([2, 1], np.int32)
    ops, nbytes = roofline.scan_work(r_idx, r_nnz, counts, s_nnz_total=6, k=5)
    assert ops == 2 * (3 + 1 + 1)
    assert nbytes == pytest.approx(8 * 6 * 2 / 2048 + 8 * 3 + 8 * 5 * 2)


def test_least_time_takes_the_binding_roof():
    peak = {"flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert roofline.least_time_s(1000, 5, peak) == 10.0
    assert roofline.least_time_s(10, 50, peak) == 5.0


def test_peaks_of_a_v5e_and_no_default():
    p = roofline.peaks("TPU v5 lite")
    assert p["flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        roofline.peaks("cpu")
