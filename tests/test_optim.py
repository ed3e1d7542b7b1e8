"""Optimizer tests: AdamW reference check, schedules, quantized state,
FxP8 gradient compression."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.optim import adamw


def _loss(p):
    return jnp.sum((p["w"] - 3.0) ** 2) + jnp.sum(p["b"] ** 2)


def test_adamw_decreases_loss():
    cfg = adamw.OptConfig(lr=0.05, weight_decay=0.0, warmup_steps=0,
                          total_steps=100, schedule="constant")
    params = {"w": jnp.zeros((4,)), "b": jnp.ones((3,))}
    state = adamw.init_opt_state(params)
    losses = []
    for step in range(80):
        g = jax.grad(_loss)(params)
        params, state, m = adamw.adamw_update(cfg, params, g, state, step)
        losses.append(float(_loss(params)))
    assert losses[-1] < losses[0] * 0.1


def test_quantized_state_tracks_fp32():
    """FxP8/16 Adam moments follow the fp32 trajectory closely."""
    cfg = adamw.OptConfig(lr=0.05, weight_decay=0.0, warmup_steps=0,
                          schedule="constant")
    p1 = {"w": jnp.zeros((8,)), "b": jnp.ones((8,))}
    p2 = jax.tree.map(jnp.copy, p1)
    s1 = adamw.init_opt_state(p1)
    s2 = adamw.init_opt_state(p2, quantized=True)
    assert s2["m_c"]["w"].dtype == jnp.int8
    assert s2["v_c"]["w"].dtype == jnp.int16
    for step in range(20):
        g1 = jax.grad(_loss)(p1)
        g2 = jax.grad(_loss)(p2)
        p1, s1, _ = adamw.adamw_update(cfg, p1, g1, s1, step)
        p2, s2, _ = adamw.adamw_update(cfg, p2, g2, s2, step)
    np.testing.assert_allclose(np.asarray(p1["w"]), np.asarray(p2["w"]),
                               atol=0.05)


def test_schedules():
    cfg = adamw.OptConfig(lr=1.0, warmup_steps=10, total_steps=100)
    # warmup ramps
    assert float(adamw.schedule(cfg, 0)) == 0.0
    assert float(adamw.schedule(cfg, 5)) == pytest.approx(0.5 * float(
        adamw.schedule(cfg, 10)), rel=0.2)
    # cosine decays to lr*0.1
    assert float(adamw.schedule(cfg, 100)) == pytest.approx(0.1, abs=0.02)
    wsd = adamw.OptConfig(lr=1.0, warmup_steps=10, total_steps=100,
                          schedule="wsd", decay_frac=0.2)
    # stable phase: constant
    assert float(adamw.schedule(wsd, 40)) == pytest.approx(
        float(adamw.schedule(wsd, 70)), rel=1e-5)
    # decay tail drops toward 0.1*lr
    assert float(adamw.schedule(wsd, 100)) < 0.2


def test_grad_clipping():
    cfg = adamw.OptConfig(lr=0.0, grad_clip=1.0, schedule="constant",
                          warmup_steps=0)
    params = {"w": jnp.zeros((4,))}
    state = adamw.init_opt_state(params)
    g = {"w": jnp.full((4,), 100.0)}
    _, _, metrics = adamw.adamw_update(cfg, params, g, state, 0)
    assert float(metrics["grad_norm"]) == pytest.approx(200.0, rel=1e-3)


def test_fxp8_grad_compression_single_device():
    """shard_map psum plumbing (axis size 1 -> compression is identity up
    to int8 quantization error)."""
    from jax.sharding import PartitionSpec as P
    mesh = jax.make_mesh((1,), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    g = {"w": jnp.linspace(-1, 1, 64)}

    def f(grads):
        return adamw.compress_grads_fxp8(grads, ("data",))

    out = jax.shard_map(f, mesh=mesh, in_specs=({"w": P()},),
                        out_specs={"w": P()})(g)
    np.testing.assert_allclose(np.asarray(out["w"]), np.asarray(g["w"]),
                               atol=2.0 / 127)
