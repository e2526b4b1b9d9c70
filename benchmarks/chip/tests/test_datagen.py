import numpy as np
import pytest

import datagen

SYN = {"dim": 10_000, "data": {"generator": "synthetic", "structure_seed": 5,
                               "nnz_mean": 120, "nnz_std": 30, "weight_low": 0.001, "weight_high": 1.0}}
SPEC = {"dim": 20_000, "data": {"generator": "spectra", "structure_seed": 5, "peaks_mean": 80,
                                "position_spread": 0.15}}


@pytest.mark.parametrize("cfg", [SYN, SPEC], ids=["synthetic", "spectra"])
def test_same_seed_same_rows_other_seed_other_rows(cfg):
    a = datagen.generate(cfg, 300, 2**31 + 17, part=0)
    b = datagen.generate(cfg, 300, 2**31 + 17, part=0)
    c = datagen.generate(cfg, 300, 2**31 + 18, part=0)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)
    assert not np.array_equal(a[1], c[1])


@pytest.mark.parametrize("cfg", [SYN, SPEC], ids=["synthetic", "spectra"])
def test_the_pattern_is_the_configurations_and_the_weights_the_seeds(cfg):
    """Every seed asks the same shapes and the same tile work."""
    a = datagen.generate(cfg, 300, 1, part=0)
    b = datagen.generate(cfg, 300, 2**31 + 99, part=0)
    np.testing.assert_array_equal(a[0], b[0])
    np.testing.assert_array_equal(a[2], b[2])
    assert not np.array_equal(a[1], b[1])
    pool = datagen.generate(cfg, 300, 1, part=1)
    other = datagen.generate(dict(cfg, data=dict(cfg["data"], structure_seed=6)),
                             300, 1, part=0)
    for x in (pool, other):
        assert not np.array_equal(a[0][:, :20], x[0][:, :20])


@pytest.mark.parametrize("cfg", [SYN, SPEC], ids=["synthetic", "spectra"])
def test_rows_are_padded_csr(cfg):
    idx, val, nnz = datagen.generate(cfg, 500, 3, part=0)
    dim = cfg["dim"]
    f = idx.shape[1]
    live = np.arange(f)[None, :] < nnz[:, None]
    assert idx.dtype == np.int32 and val.dtype == np.float32 and nnz.dtype == np.int32
    assert nnz.max() == f and nnz.min() >= 1
    assert (idx[~live] == dim).all() and (val[~live] == 0).all()
    assert ((idx[live] >= 0) & (idx[live] < dim)).all()
    d = np.where(live[:, 1:], np.diff(idx, axis=1), 1)
    assert (d > 0).all(), "dims ascend strictly within a row"
    assert (val[live] > 0).all()


def test_synthetic_follows_its_parameters():
    idx, val, nnz = datagen.generate(SYN, 4000, 5, part=0)
    assert abs(nnz.mean() - 120) < 3 and abs(nnz.std() - 30) < 3
    live = val[val > 0]
    assert live.min() >= 0.001 and live.max() <= 1.0
    counts = np.bincount(idx[idx < 10_000], minlength=10_000)
    assert counts.min() > 0, "uniform dims: every dim is used at this size"


def test_spectra_rows_peak_at_one():
    idx, val, nnz = datagen.generate(SPEC, 2000, 5, part=0)
    np.testing.assert_allclose(val.max(axis=1), 1.0)
    assert 60 < nnz.mean() < 85, "peaks merge only where they land on one dim"
