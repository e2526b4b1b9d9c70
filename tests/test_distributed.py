"""Multi-device test (subprocess with fake host devices): the legacy ring
join against the oracle, for every algorithm and with a split dim axis."""
import pytest

from tests.util_subproc import run_with_devices

pytestmark = [pytest.mark.slow, pytest.mark.subproc]


def test_ring_join_all_algorithms():
    out = run_with_devices("""
import numpy as np, jax
from repro.sparse.datagen import synthetic_sparse
from repro.sparse.format import densify
from repro.core.ring import ring_knn_join, pad_to_ring
from repro.core.reference import oracle_knn
from repro.store.mesh import make_mesh
mesh = make_mesh((4, 2), ('data', 'model'))
R = synthetic_sparse(60, dim=512, nnz_mean=20, seed=0)
S = synthetic_sparse(90, dim=512, nnz_mean=20, seed=1)
Rp, nr = pad_to_ring(R, 4); Sp, ns = pad_to_ring(S, 4)
osc, _ = oracle_knn(np.asarray(densify(R)), np.asarray(densify(S)), 5)
for alg in ['bf', 'iib', 'iiib']:
    st = ring_knn_join(Rp, Sp, 5, mesh, algorithm=alg, ring_axes=('data',),
                       n_r_valid=nr, n_s_valid=ns)
    sc = np.asarray(st.scores)[:nr]
    pos = osc > 0
    assert np.allclose(np.where(pos, sc, 0), np.where(pos, osc, 0), atol=1e-4), alg
st = ring_knn_join(Rp, Sp, 5, mesh, algorithm='iib', ring_axes=('data',),
                   dim_axis='model', n_r_valid=nr, n_s_valid=ns)
sc = np.asarray(st.scores)[:nr]
pos = osc > 0
assert np.allclose(np.where(pos, sc, 0), np.where(pos, osc, 0), atol=1e-4)
print('RING_OK')
""")
    assert "RING_OK" in out
