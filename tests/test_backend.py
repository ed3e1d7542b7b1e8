"""Backend-dispatch layer tests: registry + context override, SIMD
pack/unpack round-trips at every format, packed-FxP4 GEMM bit-exactness vs
the integer oracle, QuantizedTensor model surgery, and reference-vs-pallas
(interpret) parity — per-op, per-block, and greedy-decode."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.core.backend as backend_ctx
from repro.core.backend import resolve
from repro.core.fxp import FORMATS
from repro.core.precision import PrecisionPolicy, qmatmul
from repro.core.qtensor import (QuantizedTensor, dequantize_params,
                                packed_bytes, quantize_params,
                                quantize_tensor)
from repro.core import simd
from repro.kernels import dispatch
from repro.kernels.fxp_gemm.ref import fxp_gemm_codes_ref

REF = PrecisionPolicy.flexpe(8)
PAL = PrecisionPolicy.flexpe(8, backend="pallas-interpret")


# ---------------------------------------------------------------------------
# registry / backend resolution
# ---------------------------------------------------------------------------

def test_registry_has_all_ops():
    for op in ("matmul", "act", "softmax"):
        for be in ("reference", "pallas", "pallas-interpret"):
            fn, interp = dispatch.lookup(op, be)
            assert callable(fn)
            assert interp == (be == "pallas-interpret")
    with pytest.raises(NotImplementedError):
        dispatch.lookup("matmul", "cuda")


def test_backend_resolution_and_override():
    assert resolve(None) == "reference"
    assert resolve("reference") == "reference"
    # auto picks by platform; pallas off-TPU raises instead of quietly
    # running interpret mode
    if jax.default_backend() == "tpu":
        assert resolve("pallas") == "pallas"
        assert resolve("auto") == "pallas"
    else:
        with pytest.raises(RuntimeError, match="pallas-interpret"):
            resolve("pallas")
        assert resolve("auto") == "pallas-interpret"
    with backend_ctx.backend("pallas-interpret"):
        assert resolve("reference") == "pallas-interpret"
    assert resolve("reference") == "reference"
    with pytest.raises(ValueError):
        resolve("not-a-backend")


def test_policy_backend_field():
    pol = PrecisionPolicy.flexpe(8, backend="auto")
    assert pol.backend == "auto"
    assert pol.with_backend("reference").backend == "reference"
    # frozen dataclass: with_backend returns a new object
    assert pol.backend == "auto"


# ---------------------------------------------------------------------------
# SIMD pack/unpack round-trip at all four formats
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("fmt_name", ["fxp4", "fxp8", "fxp16", "fxp32"])
def test_pack_unpack_roundtrip_all_formats(fmt_name, rng):
    fmt = FORMATS[fmt_name]
    lanes = 32 // fmt.bits
    n = lanes * 5
    codes = rng.integers(fmt.qmin, fmt.qmax + 1, size=(4, n)).astype(np.int32)
    words = simd.pack(jnp.asarray(codes), fmt)
    assert words.shape == (4, n // lanes)
    out = simd.unpack(words, fmt, n)
    np.testing.assert_array_equal(np.asarray(out), codes)


# ---------------------------------------------------------------------------
# QuantizedTensor
# ---------------------------------------------------------------------------

def test_quantized_tensor_fxp4_nibble_packing(rng):
    w = jnp.asarray(rng.normal(size=(16, 24)).astype(np.float32))
    qt = quantize_tensor(w, "fxp4")
    assert qt.packed and qt.data.dtype == jnp.int32
    assert qt.data.shape == (16, 3)          # 24 nibbles -> 3 int32 words
    assert qt.shape == (16, 24)
    # codes round-trip through the packed words
    from repro.core.fxp import quantize
    codes, _ = quantize(w, FORMATS["fxp4"], axis=-2)
    np.testing.assert_array_equal(np.asarray(qt.codes()),
                                  np.asarray(codes.astype(jnp.int32)))


@pytest.mark.parametrize("fmt_name,dtype,factor", [
    ("fxp4", jnp.int32, 8), ("fxp8", jnp.int8, 4), ("fxp16", jnp.int16, 2)])
def test_quantized_tensor_storage_reduction(fmt_name, dtype, factor, rng):
    """The SIMD storage claim: 8x/4x/2x fewer weight bytes than fp32."""
    w = jnp.asarray(rng.normal(size=(64, 128)).astype(np.float32))
    qt = quantize_tensor(w, fmt_name)
    assert qt.data.dtype == dtype
    code_bytes = qt.data.size * qt.data.dtype.itemsize
    assert code_bytes * factor == 4 * 64 * 128


def test_quantized_tensor_is_pytree_and_scan_sliceable(rng):
    w = jnp.asarray(rng.normal(size=(3, 16, 32)).astype(np.float32))
    qt = quantize_tensor(w, "fxp8")
    leaves, treedef = jax.tree.flatten(qt)
    assert len(leaves) == 2
    back = jax.tree.unflatten(treedef, leaves)
    assert back.fmt_name == "fxp8" and back.n == 32

    def body(c, layer_qt):
        assert layer_qt.data.shape == (16, 32)
        return c, layer_qt.dequantize().sum()

    _, sums = jax.lax.scan(body, 0, qt)
    assert sums.shape == (3,)


def test_quantize_params_surgery(rng):
    params = {
        "embed": jnp.asarray(rng.normal(size=(32, 8)).astype(np.float32)),
        "blocks": {
            "attn": {"wq": jnp.asarray(
                rng.normal(size=(2, 8, 16)).astype(np.float32)),
                "bq": jnp.zeros((2, 16), jnp.float32)},
            "mlp": {"w1": jnp.asarray(
                rng.normal(size=(2, 8, 24)).astype(np.float32))},
            "moe": {"w1": jnp.asarray(      # 4-D expert bank [L,E,K,N]
                rng.normal(size=(2, 4, 8, 24)).astype(np.float32))},
            "norm": {"w": jnp.ones((2, 8), jnp.float32)},
        },
        "lm_head": jnp.asarray(rng.normal(size=(8, 32)).astype(np.float32)),
    }
    qp = quantize_params(params, "fxp8")
    assert isinstance(qp["blocks"]["attn"]["wq"], QuantizedTensor)
    assert isinstance(qp["blocks"]["mlp"]["w1"], QuantizedTensor)
    assert isinstance(qp["lm_head"], QuantizedTensor)
    # 4-D MoE expert banks quantize per (layer, expert, channel) — they are
    # consumed per-expert by kernels.dispatch.expert_matmul
    assert isinstance(qp["blocks"]["moe"]["w1"], QuantizedTensor)
    assert qp["blocks"]["moe"]["w1"].scale.shape == (2, 4, 1, 24)
    # embeddings, biases, norms untouched
    assert isinstance(qp["embed"], jax.Array)
    assert isinstance(qp["blocks"]["attn"]["bq"], jax.Array)
    assert isinstance(qp["blocks"]["norm"]["w"], jax.Array)
    qb, fb = packed_bytes(qp)
    assert 0 < qb < fb
    # dequantize_params inverts the structure (values on the FxP grid)
    dq = dequantize_params(qp, jnp.float32)
    assert isinstance(dq["lm_head"], jax.Array)
    assert dq["blocks"]["attn"]["wq"].shape == (2, 8, 16)


# ---------------------------------------------------------------------------
# packed-FxP4 GEMM vs the integer oracle (bit-exact)
# ---------------------------------------------------------------------------

def test_packed_fxp4_gemm_bit_exact_vs_oracle(rng):
    """The packed nibble path (QuantizedTensor storage -> bitcast -> kernel
    unpack -> int32 MAC) must reproduce the integer oracle exactly."""
    fmt = FORMATS["fxp4"]
    k, n = 64, 48
    w = jnp.asarray(rng.normal(size=(k, n)).astype(np.float32))
    qt = quantize_tensor(w, "fxp4")
    x = jnp.asarray(rng.normal(size=(8, k)).astype(np.float32))

    pol = PrecisionPolicy.edge4(backend="pallas-interpret")
    got = qmatmul(x, qt, pol)

    from repro.core.fxp import quantize
    xc, sx = quantize(x, fmt)
    acc = fxp_gemm_codes_ref(xc.astype(jnp.int32), qt.codes())
    ref = acc.astype(jnp.float32) * jnp.broadcast_to(
        (sx * qt.scale).astype(jnp.float32), (1, n))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def test_reference_and_pallas_bit_identical_on_qt(rng):
    """<=8-bit QuantizedTensor matmuls share the exact-integer contract:
    both backends must agree bit-for-bit (greedy-serving determinism)."""
    w = jnp.asarray(rng.normal(size=(96, 72)).astype(np.float32))
    x = jnp.asarray(rng.normal(size=(5, 96)).astype(np.float32))
    for fmt_name, pol_r, pol_p in [
            ("fxp8", REF, PAL),
            ("fxp4", PrecisionPolicy.edge4(),
             PrecisionPolicy.edge4(backend="pallas-interpret"))]:
        qt = quantize_tensor(w, fmt_name)
        a = qmatmul(x, qt, pol_r)
        b = qmatmul(x, qt, pol_p)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        # fused AF epilogue keeps the bit-identity
        a = qmatmul(x, qt, pol_r, af="silu")
        b = qmatmul(x, qt, pol_p, af="silu")
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# float-weight parity + act/softmax routing
# ---------------------------------------------------------------------------

def test_float_weight_reference_vs_pallas_close(rng):
    x = jnp.asarray(rng.normal(size=(4, 64)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(64, 80)).astype(np.float32))
    a = qmatmul(x, w, REF)
    b = qmatmul(x, w, PAL)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-4, atol=1e-4)


def test_act_softmax_backend_parity(rng):
    """Both sides jitted (as in real model use): the CORDIC LV stage is a
    decision cascade, so parity is defined under a compiled program — the
    eager-vs-jit fake-quant ulp noise is not part of the contract."""
    x = jnp.asarray(rng.normal(size=(8, 256)).astype(np.float32) * 3)
    for af in ("relu", "sigmoid", "tanh", "silu", "gelu"):
        a = jax.jit(lambda t, p=REF, f=af: p.act(t, f))(x)
        b = jax.jit(lambda t, p=PAL, f=af: p.act(t, f))(x)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5, err_msg=af)
    sa = jax.jit(lambda t: REF.softmax(t))(x)
    sb = jax.jit(lambda t: PAL.softmax(t))(x)
    np.testing.assert_allclose(np.asarray(sa), np.asarray(sb),
                               rtol=1e-4, atol=1e-4)


def test_with_backend_context_overrides_policy(rng):
    x = jnp.asarray(rng.normal(size=(4, 64)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(64, 32)).astype(np.float32))
    with backend_ctx.backend("pallas-interpret"):
        a = qmatmul(x, w, REF)
    b = qmatmul(x, w, PAL)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# one transformer block + greedy decode parity under flexpe-fxp8
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tiny_cfg():
    from repro.configs.base import get_config
    return get_config("qwen2_5_14b").reduced()


def test_transformer_block_parity(tiny_cfg, rng):
    """Reference vs pallas-interpret numerics for one transformer block."""
    from repro.models import model as M
    from repro.models.model import _tf_block
    cfg = tiny_cfg
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    qp = quantize_params(params, "fxp8")
    bp = jax.tree.map(
        lambda v: (QuantizedTensor(v.data[0], v.scale[0], v.fmt_name, v.n,
                                   v.packed)
                   if isinstance(v, QuantizedTensor) else v[0]),
        qp["blocks"], is_leaf=lambda v: isinstance(v, QuantizedTensor))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, cfg.d_model),
                          jnp.bfloat16)
    positions = jnp.broadcast_to(jnp.arange(16, dtype=jnp.int32), (2, 16))
    a, _ = _tf_block(bp, x, cfg, positions, REF)
    b, _ = _tf_block(bp, x, cfg, positions, PAL)
    d = np.abs(np.asarray(a, np.float32) - np.asarray(b, np.float32))
    s = np.abs(np.asarray(a, np.float32)).max() + 1e-6
    assert d.max() / s < 2e-2, (d.max(), s)


def test_greedy_decode_token_parity(tiny_cfg):
    """Acceptance: greedy tokens from the pallas backend match the reference
    backend for >= 95% of generated positions (same quantized weights),
    through the continuous-batching engine on a mixed-length batch."""
    from repro.launch.serve import prepare_serving_params
    from repro.models import model as M
    from repro.serving import Request, ServingEngine
    cfg = tiny_cfg
    pol = PrecisionPolicy.flexpe(8)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    qp = prepare_serving_params(params, pol)

    def serve(backend):
        eng = ServingEngine(cfg, qp, policy=pol.with_backend(backend),
                            max_slots=2, max_len=16, prefill_chunk=4)
        reqs = [Request(prompt=jax.random.randint(
                    jax.random.fold_in(jax.random.PRNGKey(1), i),
                    (plen,), 0, cfg.vocab), max_new_tokens=6, id=i)
                for i, plen in enumerate((4, 7))]
        return [f.tokens for f in eng.run(reqs)]

    toks_ref = serve("reference")
    toks_pal = serve("pallas-interpret")
    flat_r = [t for r in toks_ref for t in r]
    flat_p = [t for r in toks_pal for t in r]
    match = np.mean([a == b for a, b in zip(flat_r, flat_p)])
    assert match >= 0.95, (match, toks_ref, toks_pal)
