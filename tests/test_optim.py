"""Optimizer, schedule, and gradient-compression tests."""
import numpy as np
import jax
import jax.numpy as jnp

from repro.optim.adamw import AdamWConfig, adamw_init, adamw_update, global_norm
from repro.optim.compress import dequantize_int8, quantize_int8
from repro.optim.schedule import warmup_cosine


def test_adamw_minimizes_quadratic():
    target = jnp.asarray(np.random.default_rng(0).standard_normal(16), jnp.float32)
    params = {"w": jnp.zeros((16,), jnp.float32)}
    state = adamw_init(params)
    cfg = AdamWConfig(lr=0.1, weight_decay=0.0)

    def loss(p):
        return jnp.sum((p["w"] - target) ** 2)

    l0 = float(loss(params))
    for _ in range(200):
        g = jax.grad(loss)(params)
        params, state, _ = adamw_update(params, g, state, cfg)
    assert float(loss(params)) < 1e-2 * l0


def test_grad_clipping():
    params = {"w": jnp.ones((4, 8), jnp.float32) * 5}
    state = adamw_init(params)
    huge = {"w": jnp.full((4, 8), 1e6, jnp.float32)}
    cfg = AdamWConfig(lr=1e-3, grad_clip=1.0, weight_decay=0.0)
    new_params, state, metrics = adamw_update(params, huge, state, cfg)
    assert float(metrics["grad_norm"]) > 1e6
    delta = float(jnp.abs(new_params["w"] - params["w"]).max())
    assert delta < 1e-2  # clipped step is bounded by ~lr


def test_weight_decay_only_matrices():
    params = {"w": jnp.ones((4, 4)), "b": jnp.ones((4,))}
    state = adamw_init(params)
    zeros = jax.tree.map(jnp.zeros_like, params)
    cfg = AdamWConfig(lr=1.0, weight_decay=0.1)
    new_params, _, _ = adamw_update(params, zeros, state, cfg)
    assert float(new_params["w"][0, 0]) < 1.0       # decayed
    assert float(new_params["b"][0]) == 1.0          # spared


def test_schedule_shape():
    s = [float(warmup_cosine(i, warmup=10, total=100)) for i in range(100)]
    assert 0.0 < s[0] <= 0.2                # warm but never zero
    assert abs(s[9] - 1.0) < 1e-6           # peak at end of warmup
    assert s[99] < s[50] < s[9]             # decays
    assert s[99] >= 0.1 - 1e-6              # floor


def test_int8_quantization_roundtrip():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((64, 64)).astype(np.float32))
    q, scale = quantize_int8(x)
    err = jnp.abs(dequantize_int8(q, scale) - x).max()
    assert float(err) <= float(scale) / 2 + 1e-7


def test_psum_int8_with_error_feedback():
    """Compressed all-reduce ≈ exact mean; error feedback bounds drift."""
    from repro.optim.compress import psum_int8

    devs = jax.devices()
    if len(devs) < 1:
        return
    rng = np.random.default_rng(1)
    g = rng.standard_normal((4, 32)).astype(np.float32)

    # single-device psum: mean == identity; check EF telescopes over steps
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((1,), ("pod",))

    def step(grads, err):
        return psum_int8(grads, "pod", err)

    f = jax.jit(jax.shard_map(step, mesh=mesh,
                              in_specs=(jax.sharding.PartitionSpec(),) * 2,
                              out_specs=(jax.sharding.PartitionSpec(),) * 2,
                              check_vma=False))
    err = jnp.zeros_like(jnp.asarray(g))
    total = jnp.zeros_like(err)
    for i in range(8):
        red, err = f(jnp.asarray(g), err)
        total = total + red
    # accumulated compressed sum ≈ 8 * g within quantization error bounds
    np.testing.assert_allclose(np.asarray(total), 8 * g, atol=0.1)


def test_global_norm():
    t = {"a": jnp.ones((3,)), "b": jnp.ones((4,))}
    np.testing.assert_allclose(float(global_norm(t)), np.sqrt(7.0), rtol=1e-6)
