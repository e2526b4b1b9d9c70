"""kNN-LM retrieval serving through the continuous-batching scheduler.

Decode-time hidden states join (as R) against a MUTABLE datastore of
hidden-state keys (as S, sparse-ified by top-magnitude truncation — the
standard trick for billion-entry datastores); the retrieved values'
next tokens re-weight the LM distribution:

    p(y) = (1 - lam) * p_LM(y) + lam * softmax_knn(y)

The language model is only the query producer, so a seeded numpy stand-in
plays it: a fixed random token-embedding table and a fixed random
recurrence give the "hidden states" and the next-token logits.

This is the showcase for the serving stack (DESIGN.md §7 + §8):

* the datastore lives in a :class:`ShardedKNNStore` — indexes built once
  per shard (1 shard on a one-device host; the same script fans out
  under ``XLA_FLAGS=--xla_force_host_platform_device_count=N``);
* queries go through :class:`repro.serve.KNNScheduler`: the decode
  step's retrieval submits alongside a stream of concurrent "other user"
  requests, and the scheduler coalesces them into full r_block batches —
  ONE store dispatch serves the decode token and the background traffic;
* the store stays MUTABLE while serving: every generated token's
  (hidden-state key → next token) pair is ``add()``-ed back with a TTL,
  expired entries are tombstoned per step, and ``delete()`` evicts ids —
  all through ``scheduler.mutate()``, serialized with batch dispatches,
  with zero index rebuilds at query time;
* with ``--ckpt DIR`` the store checkpoints incrementally while serving
  (``save_dirty`` through ``mutate()`` — only mutated shards rewrite, the
  id→token value map rides in the manifest), and ``--resume`` is the
  kill-9 story: warm-restart the datastore from the newest valid commit
  (``ShardedKNNStore.load``) and keep answering with the SAME global ids
  — no index rebuild, no id reshuffle (DESIGN.md §9).

  PYTHONPATH=src python examples/knnlm_serve.py
  PYTHONPATH=src python examples/knnlm_serve.py --ckpt /tmp/knnlm.ckpt
  # kill -9 it mid-run, then:
  PYTHONPATH=src python examples/knnlm_serve.py --ckpt /tmp/knnlm.ckpt --resume
"""
import argparse
import asyncio
import time

import numpy as np

from repro.core import JoinSpec
from repro.obs import FlightRecorder, ProfileCapture
from repro.runtime.compile_cache import enable_compile_cache
from repro.serve import KNNScheduler, ServeConfig
from repro.sparse.format import SparseBatch
from repro.store import ShardedKNNStore


def sparsify(h: np.ndarray, keep: int = 32) -> SparseBatch:
    """Keep the top-|keep| magnitude dims of each row (sparse keys)."""
    n, d = h.shape
    idx = np.argsort(-np.abs(h), axis=1)[:, :keep]
    idx.sort(axis=1)
    vals = np.take_along_axis(h, idx, axis=1)
    rows = np.repeat(np.arange(n), keep)
    return SparseBatch.from_coo(
        rows, idx.ravel(), vals.ravel().astype(np.float32), n, d
    )


class ToyLM:
    """Seeded stand-in decoder: ``h' = tanh(h W + E[tok])`` over a fixed
    random embedding table ``E`` and recurrence ``W``; next-token logits
    are ``h E^T`` (tied embeddings)."""

    def __init__(self, rng, vocab_size: int = 256, d_model: int = 64):
        self.vocab_size = vocab_size
        scale = 1.0 / np.sqrt(d_model)
        self.emb = (scale * rng.standard_normal((vocab_size, d_model))).astype(np.float32)
        self.w = (scale * rng.standard_normal((d_model, d_model))).astype(np.float32)

    def step(self, h: np.ndarray, tokens: np.ndarray) -> np.ndarray:
        return np.tanh(h @ self.w + self.emb[tokens])

    def hidden_states(self, tokens: np.ndarray) -> np.ndarray:
        """Final hidden state of each row of ``tokens`` (n, t) -> (n, d)."""
        h = np.zeros((tokens.shape[0], self.w.shape[0]), np.float32)
        for t in range(tokens.shape[1]):
            h = self.step(h, tokens[:, t])
        return h

    def next_token_probs(self, h: np.ndarray) -> np.ndarray:
        logits = h @ self.emb.T
        p = np.exp(logits - logits.max(axis=-1, keepdims=True))
        return p / p.sum(axis=-1, keepdims=True)


async def main_async(ckpt: str = None, resume: bool = False,
                     flight_dump: str = None, profile_dir: str = None):
    rng = np.random.default_rng(0)
    lm = ToyLM(rng)

    # ---- build a toy datastore: (hidden-state key, next token value) ----
    n_store = 256
    store_tokens = rng.integers(0, lm.vocab_size, (n_store, 9)).astype(np.int32)
    keys = lm.hidden_states(store_tokens[:, :-1])               # (N, d)
    values = store_tokens[:, -1]                                # next tokens
    datastore = sparsify(keys)

    lam, k = 0.3, 8
    if resume:
        # kill-9 → warm restart: host mirrors + id stacks + tombstone state
        # come off disk, device stacks rebuild, global ids are STABLE — the
        # persisted id→token value map lines up with the restored id space
        t_load = time.perf_counter()
        store = ShardedKNNStore.load(ckpt)
        values = [int(v) for v in store.loaded_extra["knnlm_values"]]
        assert len(values) == store._next_gid, "value map / id space mismatch"
        print(f"resumed:   {store.num_vectors} live rows over "
              f"{store.n_shards} shard(s) in "
              f"{time.perf_counter() - t_load:.2f}s (ids stable)")
    else:
        # build the sharded datastore ONCE (every local device holds one
        # shard of S); all traffic below flows through the scheduler
        store = ShardedKNNStore.build(
            datastore, JoinSpec(k=k, algorithm="iib", r_block=8))
        values = [int(v) for v in values]   # grows with the datastore
        if ckpt:
            store.save(ckpt, extra={"knnlm_values": values})
    ttl_steps = 6                   # generated entries live this many steps

    # simulated concurrent users: perturbed datastore keys as 1-row queries
    def other_user_query() -> SparseBatch:
        base = keys[rng.integers(0, n_store)]
        return sparsify((base + 0.1 * rng.standard_normal(base.shape))[None, :])

    # ---- serve one request with kNN interpolation -----------------------
    prompt = rng.integers(0, lm.vocab_size, 8).astype(np.int32)
    max_new = 8
    h = lm.hidden_states(prompt[None])                         # prefill
    generated = [int(lm.next_token_probs(h)[0].argmax())]
    step = 0

    # observability: a private flight recorder holds the serve→store span
    # timeline (dumped as JSONL with --flight-dump); --profile arms a
    # jax.profiler capture around the first 3 coalesced batches
    recorder = FlightRecorder(auto_dump_path=flight_dump)
    profile = ProfileCapture(profile_dir) if profile_dir else None
    sched = KNNScheduler(store, ServeConfig(r_block=8, window_s=0.005),
                         recorder=recorder, profile=profile)
    async with sched:
        while len(generated) < max_new:
            # decode step: consume the last token; the new hidden state is
            # both the LM's next-token state and the retrieval query
            h = lm.step(h, np.asarray(generated[-1:]))
            query = sparsify(h)

            # the decode-step retrieval rides one coalesced batch with the
            # background users' requests — one store dispatch for all of them
            (ids, scores), *_ = await asyncio.gather(
                sched.submit(query, k=k),
                *[sched.submit(other_user_query(), k=4) for _ in range(5)],
            )
            ids, scores = ids[0], scores[0]
            valid = scores > -np.inf

            p_lm = lm.next_token_probs(h)[0]
            p_knn = np.zeros_like(p_lm)
            if valid.any():
                w = np.exp(scores[valid] - scores[valid].max())
                w /= w.sum()
                for wi, sid in zip(w, ids[valid]):
                    p_knn[values[sid]] += wi
                p = (1 - lam) * p_lm + lam * p_knn
            else:
                p = p_lm
            nxt = int(p.argmax())
            generated.append(nxt)

            # ---- mutate the datastore while serving --------------------
            # feed the fresh (key -> generated token) pair back with a TTL
            # and tombstone whatever expired this step — serialized with
            # the query batches, no index rebuild either way
            new_gids = await sched.mutate(
                store.add, query, ttl=ttl_steps, now=float(step))
            values.append(nxt)
            assert len(values) == int(new_gids[-1]) + 1
            await sched.mutate(store.expire, float(step))
            if ckpt:
                # incremental commit, serialized with dispatches: only the
                # shards this step's add/expire touched are rewritten
                await sched.mutate(
                    store.save_dirty, ckpt, {"knnlm_values": values})
            step += 1

        # explicit eviction: drop the two lowest-id seed entries
        await sched.mutate(store.delete, [0, 1])
        if ckpt:
            await sched.mutate(
                store.save_dirty, ckpt, {"knnlm_values": values})
        builds_before = store.stats.index_builds
        await sched.submit(query, k=k)
        assert store.stats.index_builds == builds_before, "query rebuilt an index!"

    m = sched.metrics
    assert m.query_index_builds == 0, "serving performed a query-time build!"
    assert m.completed == m.submitted
    assert m.batches < m.completed, "no coalescing happened"

    print("prompt:   ", prompt.tolist())
    print("generated:", generated)
    print("datastore hits blended with lam =", lam)
    print(f"datastore: {store.stats.index_builds} block-index builds for "
          f"{m.completed} scheduled queries over {store.n_shards} shard(s); "
          f"{store.stats.expired} entries TTL-expired, "
          f"{store.stats.deleted} deleted, live rows {store.num_vectors}")
    lat = m.summary()["latency"]
    occ = m.summary()["batches"]["mean_occupancy"]
    print(f"serving:   {m.completed} requests in {m.batches} coalesced "
          f"batches (occupancy {occ}), p50 {lat['p50_ms']}ms "
          f"p99 {lat['p99_ms']}ms")
    ph = m.phase_summary()
    print("phases:    " + "  ".join(
        f"{name} p50 {ph[name]['p50_ms']}ms"
        for name in ("queue_wait", "pad", "dispatch", "post")))
    rs = recorder.summary()
    print(f"recorder:  {rs['events']} events ({rs['faults']} faults) — "
          f"{rs['by_kind']}")
    if flight_dump:
        print(f"flight recorder dumped to {recorder.dump(flight_dump)}")
    if profile is not None:
        print(f"profiler:  {profile.summary()}")


def main(argv=None):
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--ckpt", default=None,
                    help="checkpoint dir: save on build + incrementally "
                         "while serving")
    ap.add_argument("--resume", action="store_true",
                    help="warm-restart the datastore from --ckpt instead "
                         "of building it")
    ap.add_argument("--flight-dump", default=None,
                    help="dump the serving flight recorder (spans + fault "
                         "events) to this JSONL path at exit")
    ap.add_argument("--profile", default=None,
                    help="capture a jax.profiler trace of the first 3 "
                         "batches into this logdir")
    args = ap.parse_args(argv)
    if args.resume and not args.ckpt:
        ap.error("--resume requires --ckpt")
    asyncio.run(main_async(ckpt=args.ckpt, resume=args.resume,
                           flight_dump=args.flight_dump,
                           profile_dir=args.profile))


if __name__ == "__main__":
    main()
