"""Regression tests for the §Perf hillclimb features: int8 decode
attention, FxP8-compressed activation gathers, ZeRO-1 mode, bf16
partial-sum matmuls."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import PrecisionPolicy
from repro.core.precision import qmatmul
from repro.distributed.sharding import MeshRules
from repro.models import model as M

KEY = jax.random.PRNGKey(0)


def test_int8_decode_attention_matches_dequant_path():
    cfg = get_config("mistral_nemo_12b").reduced()
    p = M.init_params(cfg, KEY, dtype=jnp.float32)
    seq = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, cfg.vocab)
    pol_q = PrecisionPolicy(name="kvq", kv_cache="fxp8")
    pol_i = dataclasses.replace(pol_q, int_attention=True)
    outs = {}
    for name, pol in (("dequant", pol_q), ("int8", pol_i)):
        cache = M.init_cache(cfg, 2, 12, policy=pol, dtype=jnp.float32)
        lgs = []
        for t in range(8):
            lg, cache = M.decode_step(cfg, p, cache, seq[:, t:t + 1],
                                      policy=pol)
            lgs.append(lg)
        outs[name] = jnp.concatenate(lgs, 1)
    rel = float(jnp.max(jnp.abs(outs["dequant"] - outs["int8"]))
                / (jnp.max(jnp.abs(outs["dequant"])) + 1e-9))
    assert rel < 0.05, rel


def test_compressed_gather_numerics_and_grads():
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    rules = MeshRules(mesh)
    x = jax.random.normal(KEY, (2, 16, 32), jnp.float32)
    with mesh:
        y = rules.gather_seq_compressed(x, "fxp8")
        # identity up to int8 quantization on a 1-device mesh
        step = float(jnp.max(jnp.abs(x))) / 127
        assert float(jnp.max(jnp.abs(y - x))) <= step + 1e-6

        g = jax.grad(lambda v: jnp.sum(
            rules.gather_seq_compressed(v, "fxp8") ** 2))(x)
        assert np.isfinite(float(jnp.sum(g)))
        # STE: gradient ~ 2x (quantized) value
        np.testing.assert_allclose(np.asarray(g), 2 * np.asarray(y),
                                   atol=1e-4)


def test_zero1_shards_opt_but_replicates_params():
    from repro.launch import steps as S
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    cfg = get_config("minicpm_2b").reduced()
    _, st_sh, *_ = S.build_train_step(cfg, mesh, None, fsdp="zero1")
    # params: no 'data' in any spec; opt moments: 'data' appears
    p_axes = {str(s.spec) for s in jax.tree.leaves(
        st_sh["params"], is_leaf=lambda s: hasattr(s, "spec"))}
    o_axes = {str(s.spec) for s in jax.tree.leaves(
        st_sh["opt"], is_leaf=lambda s: hasattr(s, "spec"))}
    assert not any("data" in a for a in p_axes), p_axes
    assert any("data" in a for a in o_axes), o_axes


def test_matmul_out_bf16_dtype():
    pol = PrecisionPolicy(name="t", matmul_out="bf16")
    x = jnp.ones((4, 8), jnp.bfloat16)
    w = jnp.ones((8, 4), jnp.bfloat16)
    out = qmatmul(x, w, pol)
    assert out.dtype == jnp.bfloat16
    # numerics unchanged at these scales
    np.testing.assert_allclose(np.asarray(out, dtype=np.float32), 8.0)


def test_seq_outputs_policy_flag_runs():
    cfg = get_config("qwen2_5_14b").reduced()
    p = M.init_params(cfg, KEY, dtype=jnp.float32)
    pol = PrecisionPolicy(name="t", seq_outputs=True)
    batch = {"tokens": jax.random.randint(KEY, (2, 8), 0, cfg.vocab),
             "labels": jax.random.randint(KEY, (2, 8), 0, cfg.vocab)}
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    with mesh:
        loss, _ = M.loss_fn(cfg, p, batch, policy=pol,
                            shard=MeshRules(mesh))
    assert np.isfinite(float(loss))


def test_remat_policy_dots_runs():
    cfg = get_config("mistral_nemo_12b").reduced()
    p = M.init_params(cfg, KEY, dtype=jnp.float32)
    batch = {"tokens": jax.random.randint(KEY, (2, 8), 0, cfg.vocab),
             "labels": jax.random.randint(KEY, (2, 8), 0, cfg.vocab)}
    l1, _ = M.loss_fn(cfg, p, batch, remat_policy="full")
    l2, _ = M.loss_fn(cfg, p, batch, remat_policy="dots")
    np.testing.assert_allclose(float(l1), float(l2), rtol=1e-5)
    g = jax.grad(lambda pp: M.loss_fn(cfg, pp, batch,
                                      remat_policy="dots")[0])(p)
    assert np.isfinite(float(jax.tree.leaves(g)[0].sum()))


def test_roofline_peaks_keyed_by_device_kind():
    """The peaks table answers for the chip it lists and refuses any
    other device kind instead of assuming v5e numbers."""
    from repro.launch.roofline import chip_peaks
    v5e = chip_peaks("TPU v5 lite")
    assert (v5e["peak_flops"], v5e["int8_ops"], v5e["hbm_bw"]) == (
        197e12, 393e12, 819e9)
    with pytest.raises(ValueError, match="no published peaks"):
        chip_peaks("cpu")
