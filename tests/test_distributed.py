"""Multi-device tests (subprocess with fake host devices): ring join,
sharded training parity, mini dry-run, elastic restore."""
import json

import pytest

from tests.util_subproc import run_module, run_with_devices

pytestmark = [pytest.mark.slow, pytest.mark.subproc]


def test_ring_join_all_algorithms():
    out = run_with_devices("""
import numpy as np, jax
from repro.sparse.datagen import synthetic_sparse
from repro.sparse.format import densify
from repro.core.ring import ring_knn_join, pad_to_ring
from repro.core.reference import oracle_knn
from repro.launch.mesh import make_mesh
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


def test_sharded_training_matches_single_device():
    """Same seed, same data: loss trajectory on a (2,2) mesh == (1,1) mesh."""
    out = run_with_devices("""
import numpy as np, jax, jax.numpy as jnp
from repro.configs.base import get_config
from repro.launch.mesh import make_host_mesh
from repro.launch.sharding import param_shardings, opt_shardings
from repro.launch.steps import make_train_step, init_train_state, StepOptions
from repro.data.pipeline import make_lm_batch

cfg = get_config('qwen3-0.6b').reduced()
losses = {}
for dp, tp in [(1, 1), (2, 2)]:
    mesh = make_host_mesh(dp, tp)
    params, opt = init_train_state(cfg)
    p_sh = param_shardings(params, mesh)
    o_sh = opt_shardings(opt, p_sh, mesh)
    step = make_train_step(cfg, mesh, StepOptions(ce_chunk=8))
    with mesh:
        jitted = jax.jit(step, in_shardings=(p_sh, o_sh, None),
                         out_shardings=(p_sh, o_sh, None))
        cur = []
        for i in range(4):
            b = make_lm_batch(0, i, 4, 16, cfg.vocab_size)
            batch = {k: jnp.asarray(v) for k, v in b.items()}
            params, opt, m = jitted(params, opt, batch)
            cur.append(float(m['loss']))
    losses[(dp, tp)] = cur
a, b = losses[(1, 1)], losses[(2, 2)]
assert np.allclose(a, b, rtol=2e-3, atol=2e-3), (a, b)
assert a[-1] < a[0], a
print('PARITY_OK')
""")
    assert "PARITY_OK" in out


def test_mini_dryrun_production_shards():
    """The real dryrun path on a small 4x4 'production' mesh with a reduced
    config: lower + compile + analyses must succeed."""
    out = run_with_devices("""
import jax, numpy as np
from repro.configs.base import get_config
from repro.launch import shapes as SH
from repro.launch.sharding import (batch_shardings, param_shardings,
                                   opt_shardings, cache_shardings)
from repro.launch.steps import (StepOptions, abstract_train_state,
                                make_train_step, make_decode_step)
from repro.launch.mesh import make_mesh
mesh = make_mesh((4, 4), ('data', 'model'))
cfg = get_config('qwen3-0.6b').reduced()
params_abs, opt_abs = abstract_train_state(cfg)
p_sh = param_shardings(params_abs, mesh)
o_sh = opt_shardings(opt_abs, p_sh, mesh)
import jax.numpy as jnp
batch_abs = {'tokens': jax.ShapeDtypeStruct((16, 64), jnp.int32),
             'labels': jax.ShapeDtypeStruct((16, 64), jnp.int32)}
b_sh = batch_shardings(batch_abs, mesh)
step = make_train_step(cfg, mesh, StepOptions(ce_chunk=16))
with mesh:
    lowered = jax.jit(step, in_shardings=(p_sh, o_sh, b_sh),
                      out_shardings=(p_sh, o_sh, None)).lower(
        params_abs, opt_abs, batch_abs)
    compiled = lowered.compile()
mem = compiled.memory_analysis()
assert mem is not None
from repro.launch.hlo_analysis import analyze
a = analyze(compiled.as_text(), 16)
assert a.flops > 0
assert a.total_collective_bytes() > 0
print('DRYRUN_OK', int(a.flops))
""", n_devices=16)
    assert "DRYRUN_OK" in out


def test_train_failure_injection_and_resume(tmp_path):
    """End-to-end: injected failure mid-run -> supervisor restores from the
    checkpoint and finishes; a fresh process resumes from disk."""
    ckpt = str(tmp_path / "ck")
    out1 = run_module([
        "repro.launch.train", "--arch", "qwen3-0.6b", "--smoke",
        "--steps", "12", "--global-batch", "4", "--seq-len", "32",
        "--ckpt-dir", ckpt, "--ckpt-every", "4", "--resume", "auto",
        "--fail-at-step", "6", "--log-every", "4",
    ], n_devices=2)
    assert "RESTORE after" in out1
    rec = json.loads(out1.strip().splitlines()[-1])
    assert rec["failures"] == 1
    assert np.isfinite(rec["final_loss"]) if (np := __import__("numpy")) else True

    # resume in a NEW process from the final checkpoint (elastic restart)
    out2 = run_module([
        "repro.launch.train", "--arch", "qwen3-0.6b", "--smoke",
        "--steps", "14", "--global-batch", "4", "--seq-len", "32",
        "--ckpt-dir", ckpt, "--resume", "auto", "--log-every", "2",
    ], n_devices=2)
    assert "resumed from step 12" in out2


def test_elastic_restore_across_mesh_sizes(tmp_path):
    """Save sharded on 8 devices, restore on 4 — mesh-free checkpoints."""
    ckpt = str(tmp_path / "ck")
    run_module([
        "repro.launch.train", "--arch", "qwen1.5-0.5b", "--smoke",
        "--steps", "4", "--global-batch", "4", "--seq-len", "16",
        "--data-par", "4", "--model-par", "2",
        "--ckpt-dir", ckpt, "--ckpt-every", "4",
    ], n_devices=8)
    out = run_module([
        "repro.launch.train", "--arch", "qwen1.5-0.5b", "--smoke",
        "--steps", "6", "--global-batch", "4", "--seq-len", "16",
        "--data-par", "2", "--model-par", "2",
        "--ckpt-dir", ckpt, "--resume", "auto",
    ], n_devices=4)
    assert "resumed from step 4" in out
