import hashlib
import json

import numpy as np
import pytest

import datagen
import layout

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


# sha256 of idx, val and nnz of each batch at the configurations' sizes,
# seed 3000000001, as the generators gave them before they became files
PINNED = {
    ("synth50k", 0): "41e84140f27c7a62b13d2576d6ac1208ad60756c80a4fdf62743eccc3d77527b",
    ("synth50k", 1): "41368945ff052f27892c3c8eb441450ac4857f985aeecbace829a6531e8cd0d7",
    ("spectra52k", 0): "2e299e7159bd0c3bd4400e0008493c5a401b95a177b811fde30cf9cfdfc34f60",
    ("spectra52k", 1): "3c1e61cda3cfaed1f73c93aad1204b4c9876d37a5029f89d92d2d90466ea857f",
}


@pytest.mark.parametrize("name,part", sorted(PINNED))
def test_the_generators_give_the_same_arrays_bit_for_bit(name, part):
    cfg = json.loads((layout.HERE / "configs" / f"{name}.json").read_text())
    rows = datagen.generate(cfg, cfg["n_s"] if part == 0 else cfg["n_r"], 3000000001, part)
    digest = hashlib.sha256(b"".join(a.tobytes() for a in rows)).hexdigest()
    assert digest == PINNED[name, part]
