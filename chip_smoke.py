#!/usr/bin/env python3
"""Bring-up smoke: the sharded kNN-join store, end to end, on a TPU.

Drives the served path once through the entry points a user calls, at the
paper's synthetic deployment (``configs/paper_knn.SYNTHETIC``: S = 10,000
rows, dim 10,000, ~120 non-zeros per row, k = 5, tile 128, R and S blocks
of 2,048), with data generated from ``--seed``:

  (a) batch join   — ``ShardedKNNStore`` (IIIB, one shard) ``query(R)`` on
                     R = 2,048 rows, twice: the first call compiles, the
                     second is warm;
  (b) serving      — a ``KNNScheduler`` answers 64 concurrent ``submit``s
                     of 1-4 rows with mixed k <= 5; every future completes
                     and no batch fails, degrades, retries or times out;
  (c) mutation     — ``add`` and ``delete`` through ``scheduler.mutate``
                     (incremental shard placement), then ``query(R)`` again;
  (d) fused kernel — the engine's ``use_kernel`` IIB path, compiled to
                     Mosaic (its lowered text holds ``tpu_custom_call``).

Every phase is checked against ``core.reference.reference_join`` (host,
float64) on a seeded sample of 256 R rows — for (c), against the live
store contents.  Any failure raises and exits non-zero.

  python chip_smoke.py                        # one chip, phases (a)-(d)
  python chip_smoke.py --chips 4              # four chips: (a) + (b) on a
                                              # 4-shard and a 2x2 replicated store
  python chip_smoke.py --config yeast-worm    # the paper's spectral shape, (a) only

Without a TPU it exits non-zero before doing any work.  The last line of
standard output is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

import numpy as np  # noqa: E402

# Scores are f32 sums of up to ~120 products of weights in (0, 1], taken
# tile by tile on the chip and in float64 by the reference.  With every
# scoring matmul at Precision.HIGHEST each product is f32-accurate, so the
# gap is f32 rounding of the sum: at most n·2^-24·Σ|products| ≈ 120 · 6e-8
# · 40 ≈ 2.9e-4 for the largest scores here (a stored copy of the query
# row, Σ w² ≈ 40).  A bf16-rounded matmul would miss by ~1e-2.  Ids must
# match wherever the reference's neighbouring scores are further apart.
TOL = 5e-4
N_SAMPLE = 256          # R rows checked against the reference
N_REQUESTS = 64         # concurrent served requests in (b)
N_ADD, N_DELETE = 64, 32


def log(**rec) -> None:
    print(json.dumps(rec), flush=True)


def require_tpu(chips: int):
    """The first JAX call: fail, without a result, unless the TPU is here."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU (JAX found {devs[0].platform!r})")
    if len(devs) < chips:
        raise SystemExit(f"chip_smoke: --chips {chips} but {len(devs)} devices")
    return devs


def make_data(cfg, n_r: int, seed: int):
    from repro.sparse.datagen import spectra_like, synthetic_sparse

    if cfg.name.startswith("yeast"):
        S = spectra_like(cfg.n_s, dim=cfg.dim, peaks_mean=cfg.nnz_mean, seed=seed)
        R = spectra_like(n_r, dim=cfg.dim, peaks_mean=cfg.nnz_mean, seed=seed + 1)
    else:
        S = synthetic_sparse(cfg.n_s, dim=cfg.dim, nnz_mean=cfg.nnz_mean, seed=seed)
        R = synthetic_sparse(n_r, dim=cfg.dim, nnz_mean=cfg.nnz_mean, seed=seed + 1)
    return S, R


def rows_of(batch, sel):
    from repro.sparse.format import SparseBatch

    return SparseBatch(indices=np.asarray(batch.indices)[sel],
                       values=np.asarray(batch.values)[sel],
                       nnz=np.asarray(batch.nnz)[sel], dim=batch.dim)


def host_csr(batch):
    from repro.core.reference import HostCSR

    return HostCSR.from_padded(np.asarray(batch.indices), np.asarray(batch.values),
                               np.asarray(batch.nnz), batch.dim)


def reference(R_sample, S, k: int, gids=None):
    """float64 top-(k+1) of the sample over S; one slot deeper than served
    so the k-th slot's tie test sees its lower neighbour.  ``gids`` maps S
    row positions to global store ids."""
    from repro.core.reference import reference_join

    scores, pos = reference_join(host_csr(R_sample), host_csr(S), k + 1,
                                 algorithm="iib")
    if gids is not None:
        pos = np.where(pos >= 0, np.asarray(gids)[np.maximum(pos, 0)], -1)
    return scores, pos


def check(phase: str, ref, got_scores, got_ids) -> dict:
    from repro.core.reference import topk_agreement

    a = topk_agreement(ref[0], ref[1], np.asarray(got_scores), np.asarray(got_ids), TOL)
    if a["max_score_err"] > TOL or a["id_mismatches"] or not a["ids_checked"]:
        raise AssertionError(f"{phase}: disagrees with the reference: {a}")
    return a


def memory(devs) -> dict:
    """Device memory as the backend reports it (one value per device)."""
    stats = [d.memory_stats() or {} for d in devs]
    return {key: [st.get(key) for st in stats]
            for key in ("peak_bytes_in_use", "bytes_in_use", "bytes_limit")}


def build_store(S, cfg, **mesh_kw):
    from repro.core import JoinSpec
    from repro.store import ShardedKNNStore

    spec = JoinSpec(k=cfg.k, algorithm=cfg.algorithm, r_block=cfg.r_block,
                    s_block=cfg.s_block, tile=cfg.tile)
    t0 = time.perf_counter()
    store = ShardedKNNStore.build(S, spec, **mesh_kw)
    jax_block(store)
    return store, time.perf_counter() - t0


def jax_block(store) -> None:
    import jax

    for st in store._stacks:
        jax.block_until_ready(st)


def check_placement(store) -> dict:
    """Each shard's stacks on their own device of their replica's mesh."""
    per_replica = []
    for r, mesh in enumerate(store._replica_meshes):
        want = list(mesh.devices.ravel())
        for name, arr in store._stacks[r].items():
            got = sorted((s.index[0].start or 0, s.device.id)
                         for s in arr.addressable_shards)
            if [d for _, d in got] != [d.id for d in want] or any(
                    s.data.shape[0] != 1 for s in arr.addressable_shards):
                raise AssertionError(
                    f"replica {r} stack {name!r}: shards on devices {got}, "
                    f"want one shard per device of {[d.id for d in want]}")
        per_replica.append([d.id for d in want])
    return {"devices_per_replica": per_replica}


def phase_batch(tag: str, store, R, sample, ref, devs):
    import jax

    # queries rotate over replicas, and each replica's program compiles on
    # its first call: one first call per replica, then the warm one
    first_s = []
    for _ in range(store.n_replicas):
        t0 = time.perf_counter()
        res = store.query(R)
        jax.block_until_ready(res.scores)
        first_s.append(time.perf_counter() - t0)
    t0 = time.perf_counter()
    res = store.query(R)
    jax.block_until_ready(res.scores)
    warm_s = time.perf_counter() - t0
    agree = check(f"{tag} batch join", ref, np.asarray(res.scores)[sample],
                  np.asarray(res.ids)[sample])
    log(phase=f"{tag}:a_batch_join", rows=R.num_vectors, first_call_s=first_s,
        warm_s=warm_s, **agree, **memory(devs))
    return res


async def _serve(store, R_sample, ref, mutations=None):
    from repro.serve import KNNScheduler

    sizes = [1 + i % 4 for i in range(N_REQUESTS)]
    ks = [1 + (i * 3) % store.spec.k for i in range(N_REQUESTS)]
    offs = np.concatenate([[0], np.cumsum(sizes)])
    assert offs[-1] <= R_sample.num_vectors
    out = None
    async with KNNScheduler(store) as sched:
        t0 = time.perf_counter()
        results = await asyncio.gather(*[
            sched.submit(rows_of(R_sample, np.arange(offs[i], offs[i + 1])), k=ks[i])
            for i in range(N_REQUESTS)])
        serve_s = time.perf_counter() - t0
        if mutations is not None:
            out = await mutations(sched)
        m = sched.metrics
    worst = {"max_score_err": 0.0, "ids_checked": 0, "id_mismatches": 0}
    for i, (ids, scores) in enumerate(results):
        lo, hi, k = offs[i], offs[i + 1], ks[i]
        if np.asarray(ids).shape != (hi - lo, k):
            raise AssertionError(f"request {i}: result shape {np.shape(ids)}")
        a = check(f"served request {i}", (ref[0][lo:hi, :k + 1], ref[1][lo:hi, :k + 1]),
                  scores, ids)
        worst["max_score_err"] = max(worst["max_score_err"], a["max_score_err"])
        worst["ids_checked"] += a["ids_checked"]
    counters = {c: int(getattr(m, c)) for c in
                ("completed", "failed", "degraded", "retries", "timeouts")}
    if counters["completed"] != N_REQUESTS or any(
            counters[c] for c in ("failed", "degraded", "retries", "timeouts")):
        raise AssertionError(f"serving counters: {counters}")
    return {"requests": N_REQUESTS, "rows": int(offs[-1]), "wall_s": serve_s,
            **counters, **worst}, out


def phase_serve(tag: str, store, R_sample, ref, devs, mutations=None):
    rec, out = asyncio.run(_serve(store, R_sample, ref, mutations))
    log(phase=f"{tag}:b_serving", **rec, **memory(devs))
    return out


def phase_mutate(store, S, R, sample, base_res, devs):
    """(c) in two parts: ``mutations`` runs add and delete through the
    scheduler of (b); ``after`` then re-queries R and checks it against the
    reference over the live rows."""
    from repro.sparse.format import SparseBatch

    R_sample = rows_of(R, sample)
    new_rows = rows_of(R_sample, np.arange(N_ADD))     # stored copies of queries
    # the current nearest neighbours of other sampled rows go away
    top1 = np.asarray(base_res.ids)[sample[N_ADD:N_ADD + N_DELETE], 0]
    dead = np.unique(top1[top1 >= 0])
    geometry = store._host_geometry

    async def mutations(sched):
        gids = await sched.mutate(store.add, new_rows)
        n_dead = await sched.mutate(store.delete, dead)
        return np.asarray(gids), n_dead

    def after(out):
        import jax

        gids_new, n_dead = out
        if store._host_geometry != geometry:
            raise AssertionError("add() changed the stack geometry: the "
                                 "incremental placement path did not run")
        t0 = time.perf_counter()
        res = store.query(R)
        jax.block_until_ready(res.scores)
        query_s = time.perf_counter() - t0
        alive = np.ones(S.num_vectors, bool)
        alive[dead] = False
        f = max(np.asarray(S.indices).shape[1], np.asarray(new_rows.indices).shape[1])

        def widen(b):
            idx, val = np.asarray(b.indices), np.asarray(b.values)
            pad = f - idx.shape[1]
            return (np.pad(idx, ((0, 0), (0, pad)), constant_values=b.dim),
                    np.pad(val, ((0, 0), (0, pad))))

        (si, sv), (ni, nv) = widen(S), widen(new_rows)
        live = SparseBatch(
            indices=np.concatenate([si[alive], ni]), values=np.concatenate([sv[alive], nv]),
            nnz=np.concatenate([np.asarray(S.nnz)[alive], np.asarray(new_rows.nnz)]),
            dim=S.dim)
        live_gids = np.concatenate([np.nonzero(alive)[0], gids_new])
        ref = reference(R_sample, live, store.spec.k, gids=live_gids)
        got_ids = np.asarray(res.ids)[sample]
        if np.isin(got_ids, dead).any():
            raise AssertionError("a deleted row was returned")
        agree = check("after mutation", ref, np.asarray(res.scores)[sample], got_ids)
        # the reference holds the added rows under their new global ids, so
        # agreement already shows them served; count the visible cases too
        added_top1 = int((got_ids[:N_ADD, 0] == gids_new).sum())
        if not added_top1:
            raise AssertionError("no added row is its query's nearest neighbour")
        log(phase="c_mutation", added=int(gids_new.size), deleted=int(n_dead),
            added_top1=added_top1,
            incremental_placement=True, query_s=query_s,
            placed_shards=int(store.stats.placed_shards), **agree, **memory(devs))

    return mutations, after


def phase_kernel(S, R, sample, ref, cfg, devs) -> None:
    import jax

    from repro.core import JoinSpec, SparseKNNIndex

    spec = JoinSpec(k=cfg.k, algorithm="iib", use_kernel=True,
                    r_block=cfg.r_block, s_block=cfg.s_block, tile=cfg.tile)
    t0 = time.perf_counter()
    index = SparseKNNIndex.build(S, spec)
    build_s = time.perf_counter() - t0
    text = index.lowered_kernel(R).as_text()
    if "tpu_custom_call" not in text:
        raise AssertionError("knn_topk did not lower to a Mosaic kernel")
    t0 = time.perf_counter()
    res = index.query(R)
    jax.block_until_ready(res.scores)
    first_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    res = index.query(R)
    jax.block_until_ready(res.scores)
    warm_s = time.perf_counter() - t0
    agree = check("fused kernel", ref, np.asarray(res.scores)[sample],
                  np.asarray(res.ids)[sample])
    log(phase="d_fused_kernel", tpu_custom_call=True, build_s=build_s,
        first_call_s=first_s, warm_s=warm_s, **agree, **memory(devs))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: only the sharded and replicated stores across four chips")
    ap.add_argument("--config", default="synthetic-10k",
                    choices=("synthetic-10k", "yeast-worm"))
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devs = require_tpu(args.chips)
    from repro.configs.paper_knn import SYNTHETIC, YEAST_WORM
    from repro.runtime.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    dev = devs[0]
    cfg = {c.name: c for c in (SYNTHETIC, YEAST_WORM)}[args.config]
    log(phase="device", platform=dev.platform, device_kind=dev.device_kind,
        count=len(devs), config=cfg.name, compile_cache=cache_dir)

    t0 = time.perf_counter()
    S, R = make_data(cfg, cfg.r_block, args.seed)
    sample = np.sort(np.random.default_rng(args.seed).choice(
        R.num_vectors, N_SAMPLE, replace=False))
    R_sample = rows_of(R, sample)
    gen_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    ref = reference(R_sample, S, cfg.k)
    log(phase="data", s_rows=S.num_vectors, r_rows=R.num_vectors, dim=S.dim,
        nnz_mean=float(np.asarray(S.nnz).mean()), gen_s=gen_s,
        reference_s=time.perf_counter() - t0, tol=TOL)

    if args.chips == 4:
        from repro.store.mesh import make_store_mesh

        layouts = {"shards4": make_store_mesh(4),
                   "shards2x2": make_store_mesh(2, replicas=2)}
        for tag, mesh in layouts.items():
            store, build_s = build_store(S, cfg, mesh=mesh)
            log(phase=f"{tag}:build", shards=store.n_shards, replicas=store.n_replicas,
                build_s=build_s, placed_bytes=int(store.stats.placed_bytes),
                **check_placement(store), **memory(devs))
            phase_batch(tag, store, R, sample, ref, devs)
            phase_serve(tag, store, R_sample, ref, devs)
            del store
    else:
        store, build_s = build_store(S, cfg, num_shards=1)
        log(phase="build", algorithm=store.algorithm, shards=store.n_shards,
            build_s=build_s, placed_bytes=int(store.stats.placed_bytes),
            **memory(devs))
        base = phase_batch("1chip", store, R, sample, ref, devs)
        if cfg is SYNTHETIC:
            mutations, after = phase_mutate(store, S, R, sample, base, devs)
            after(phase_serve("1chip", store, R_sample, ref, devs, mutations))
            del store, mutations, after         # free the store's device memory
            phase_kernel(S, R, sample, ref, cfg, devs)

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
