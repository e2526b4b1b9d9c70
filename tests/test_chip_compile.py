"""Compile the main path's device programs for a described TPU v5e chip.

Nothing runs: the TPU compiler, which is installed beside JAX, compiles for
a chip that is described and not attached, and refuses what the chip would
refuse (scalar stores to VMEM, unaligned or dynamic lane slices, programs
that do not fit).  Widths are those of ``chip_smoke.py`` — the paper's
synthetic deployment (``configs/paper_knn.SYNTHETIC``): dim 10,000 in
128-wide tiles (T = 79, plus the sentinel tile), R and S blocks of 2,048.

The topology is described inside a module-scoped fixture, never at import,
so every test worker collects the same tests and only the worker given this
file loads the TPU library.
"""
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.configs.paper_knn import SYNTHETIC
from repro.kernels.knn_topk.kernel import knn_topk_pallas
from repro.sparse.format import num_tiles
from repro.store.sharded import fanout_program

T = num_tiles(SYNTHETIC.dim, SYNTHETIC.tile)          # 79 dim-tiles
NR = SYNTHETIC.r_block                                # 2,048 query rows
NS = 5 * SYNTHETIC.s_block                            # 10,000 rows, 5 padded blocks
# longest inverted list of a 2,048-row S block: a row's ~120 features hit a
# given tile with p = 1 - (1 - 128/10,000)^120 ≈ 0.785, so lists hold ≈ 1,608
# rows; the engine buckets the bound up to a multiple of 128
M = 1664


@pytest.fixture(scope="module")
def topo():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("k", [5, 12])
def test_knn_topk_compiles_for_v5e(one_chip, k):
    """The engine's fused score→top-k kernel at chip_smoke's widths (one
    call covers all S blocks: grid 8 × 40 × A with 256-row blocks)."""
    block, a_len = 256, 80

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    args = (
        arg((T + 1, NR, SYNTHETIC.tile), jnp.float32),
        arg((T + 1, NS, SYNTHETIC.tile), jnp.float32),
        arg((NR // block, NS // block, a_len), jnp.int32),
        arg((1, NS), jnp.int32),
        arg((1, NS), jnp.int32),
        arg((NR, k), jnp.float32),
        arg((NR, k), jnp.int32),
        arg((1, 1), jnp.float32),
        arg((1,), jnp.int32),
    )
    compiled = knn_topk_pallas.lower(*args, block_r=block, block_s=block).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("n_shards", [1, 2])
def test_store_iiib_fanout_compiles_for_v5e(topo, monkeypatch, n_shards):
    """The store's IIIB fan-out step: scanned superset-index join over the
    shard's S blocks with the threshold in the carry, then the top-k tree
    reduction, for one 2,048-row R block — on one chip, and on one replica
    (2 chips) of a 2x2 replicated store, which compiles without the
    slice-wide launch barrier."""
    from repro.store.mesh import submesh_compiler_options

    mesh = Mesh(np.array(topo.devices[:n_shards]), ("shard",))
    # the program sees the whole described slice, as on a four-chip host
    monkeypatch.setattr(jax, "devices", lambda *a: list(topo.devices))
    assert (submesh_compiler_options(mesh) is None) == (n_shards == 1)
    fn = fanout_program("iiib", mesh, ("shard",), rb=NR, k=SYNTHETIC.k,
                        dim=SYNTHETIC.dim, s_block=SYNTHETIC.s_block,
                        tile=SYNTHETIC.tile)
    rep, shard = NamedSharding(mesh, P()), NamedSharding(mesh, P("shard"))
    # 10,000 rows split over the shards, S blocks of at most 2,048 rows
    b = -(-NS // SYNTHETIC.s_block // n_shards)
    sb = SYNTHETIC.s_block

    def arg(shape, dtype, sharding):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    args = (
        arg((T, NR, SYNTHETIC.tile), jnp.float32, rep),       # dense R tiles
        arg((T,), jnp.float32, rep),                          # maxWeight per tile
        arg((NR,), jnp.bool_, rep),                           # real R rows
        arg((n_shards, b, T + 1, M), jnp.int32, shard),       # list rows
        arg((n_shards, b, T + 1, M, SYNTHETIC.tile), jnp.float32, shard),
        arg((n_shards, b, T + 1), jnp.int32, shard),          # list lengths
        arg((n_shards, b, sb, T), jnp.float32, shard),        # tile mass
        arg((n_shards, b, sb), jnp.int32, shard),             # global ids
        arg((n_shards, b, sb), jnp.bool_, shard),             # live rows
    )
    compiled = fn.lower(*args).compile()
    mem = compiled.memory_analysis()
    # the index stacks dominate; everything must fit one 16 GB chip
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9


def _scatter_update_sizes(hlo: str):
    """Element counts of the update operands of every scatter in a
    compiled HLO module's text (operands are printed by name only, so
    their shapes are looked up from definitions and parameters)."""
    shapes = dict(re.findall(r"%?([\w.-]+) = \(?(\w+\[[\d,]*\])", hlo))
    shapes.update(re.findall(r"([\w.-]+): (\w+\[[\d,]*\])", hlo))
    sizes = []
    for args in re.findall(r" scatter\(([^)]*)\)", hlo):
        for name in re.findall(r"%([\w.-]+)", args)[2:]:
            dims = re.search(r"\[([\d,]*)\]", shapes[name]).group(1)
            sizes.append(int(np.prod([int(d) for d in dims.split(",") if d])))
    return sizes


def test_store_iiib_fanout_scores_synth50k_without_column_scatter(topo, monkeypatch):
    """The store's IIIB fan-out at the benchmark's synth50k geometry: R and
    S blocks of 4,096 rows, 13 S blocks of 50,000 rows, lists padded to
    3,456 (its longest list holds 3,330 rows, bucketed by 128).  The scan
    scores each S block with a dense product: no scatter moves a (|Br|, M)
    product, and the program fits one 16 GB chip."""
    mesh = Mesh(np.array(topo.devices[:1]), ("shard",))
    monkeypatch.setattr(jax, "devices", lambda *a: list(topo.devices))
    rb = sb = 4096
    b, m, tile = 13, 3456, SYNTHETIC.tile
    fn = fanout_program("iiib", mesh, ("shard",), rb=rb, k=SYNTHETIC.k,
                        dim=SYNTHETIC.dim, s_block=sb, tile=tile)
    rep, shard = NamedSharding(mesh, P()), NamedSharding(mesh, P("shard"))

    def arg(shape, dtype, sharding):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    args = (
        arg((T, rb, tile), jnp.float32, rep),
        arg((T,), jnp.float32, rep),
        arg((rb,), jnp.bool_, rep),
        arg((1, b, T + 1, m), jnp.int32, shard),
        arg((1, b, T + 1, m, tile), jnp.float32, shard),
        arg((1, b, T + 1), jnp.int32, shard),
        arg((1, b, sb, T), jnp.float32, shard),
        arg((1, b, sb), jnp.int32, shard),
        arg((1, b, sb), jnp.bool_, shard),
    )
    compiled = fn.lower(*args).compile()
    sizes = _scatter_update_sizes(compiled.as_text())
    assert sizes, "the S densify scatter is missing"
    assert rb * m not in sizes
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9
