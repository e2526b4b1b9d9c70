"""Manual-collective DP trainer with int8 + error-feedback gradient sync.

Realizes the §Perf-projected lever that GSPMD cannot express (the grad
all-reduce fires inside the autodiff'd layer scan where its layout is out
of reach): a shard_map data-parallel train step whose ONLY cross-device
traffic is the once-per-step gradient all-reduce, compressed to int8 with
an error-feedback buffer (optim/compress.py).  On the production mesh this
is the cross-POD sync (the slow DCI links); intra-pod FSDP stays exact.

Per-step payload: 4x fewer bytes than f32 grad sync (1 byte/param + one
scalar scale per leaf).  EF keeps the long-run bias bounded; the parity
test (tests/test_compressed_train.py) shows the loss trajectory tracks
the exact-sync trainer.
"""
from __future__ import annotations

import jax
from jax.sharding import PartitionSpec as P

from repro.launch.steps import StepOptions, loss_fn
from repro.optim.adamw import adamw_update
from repro.optim.compress import psum_int8
from repro.optim.schedule import warmup_cosine


def make_compressed_train_step(cfg, mesh, axis: str = "data",
                               opts: StepOptions = StepOptions(),
                               total_steps: int = 10_000,
                               compress: bool = True):
    """(params, opt_state, err, batch) -> (params, opt_state, err, metrics).

    params/opt replicated; batch sharded over ``axis``; err is the EF
    buffer pytree (zeros_like(params) initially).
    """

    def local_step(params, opt_state, err, batch):
        (loss, metrics), grads = jax.value_and_grad(
            lambda p: loss_fn(p, cfg, batch, opts), has_aux=True
        )(params)
        if compress:
            grads, err = psum_int8(grads, axis, err)
        else:
            n = jax.lax.psum(1, axis)
            grads = jax.tree.map(lambda g: jax.lax.psum(g, axis) / n, grads)
        loss = jax.lax.pmean(loss, axis)
        lr_scale = warmup_cosine(opt_state["step"], total=total_steps)
        params, opt_state, om = adamw_update(params, grads, opt_state,
                                             opts.adamw, lr_scale)
        return params, opt_state, err, {"loss": loss, **om}

    rep = P()
    batch_spec = {"tokens": P(axis), "labels": P(axis)}
    return jax.jit(jax.shard_map(
        local_step,
        mesh=mesh,
        in_specs=(rep, rep, rep, batch_spec),
        out_specs=(rep, rep, rep, rep),
        check_vma=False,
    ))
