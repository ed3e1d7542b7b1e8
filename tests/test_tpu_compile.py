"""Compile rehearsals: every Pallas kernel of the serving path, compiled
for a described TPU v5e chip at MiniCPM-2B widths (d_model 2304, d_ff
5760, 36 heads of 64, paged KV blocks of 16 tokens).

Interpret mode runs a kernel body as plain jnp and accepts layouts the
chip's compiler refuses (int32 MXU operands, unaligned lane slices, more
VMEM than a kernel may use); these tests catch those without a chip.
Nothing runs: they pass shapes, not arrays, so they say nothing about
results or times. The topology is described inside a module fixture, and
every test skips where it cannot be described (no TPU compiler).
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.fxp import FORMATS
from repro.core.precision import PrecisionPolicy
from repro.core.qtensor import QuantizedTensor
from repro.kernels import dispatch
from repro.kernels.cordic_af.ops import cordic_af
from repro.kernels.cordic_softmax.ops import cordic_softmax
from repro.kernels.paged_attention.ops import paged_attention

D, FF = 2304, 5760                 # MiniCPM-2B d_model, d_ff
HEADS, HD = 36, 64                 # MiniCPM-2B heads (= kv heads), head dim
SLOTS, BS, MB = 4, 16, 18          # decode batch, KV block, blocks per row


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler / plugin here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def compile_for(one_chip):
    """compile_for(fn, *(shape, dtype), kernel=name) -> compiled program
    for one v5e chip, every Pallas call of which is the HLO instruction
    named after its kernel (the name a profiler trace shows). The
    persistent compilation cache is off meanwhile: an entry written for
    a described chip cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()

    def compile_for(fn, *shapes, kernel):
        args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
                for s, d in shapes]
        compiled = jax.jit(fn).lower(*args).compile()
        calls = re.findall(r"%([\w-]+)\.\d+ = [^\n]*"
                           r'custom_call_target="tpu_custom_call"',
                           compiled.as_text())
        assert calls and set(calls) == {kernel}, calls
        return compiled

    yield compile_for
    jax.config.update("jax_enable_compilation_cache", was)


GEMMS = [(SLOTS, D, D, None), (SLOTS, D, FF, "silu"), (SLOTS, FF, D, None),
         (128, D, FF, "silu")]


@pytest.mark.parametrize("m,k,n,af", GEMMS)
def test_fxp8_gemm_compiles(compile_for, m, k, n, af):
    """int8 codes into the MXU with int32 accumulation + fused epilogue."""
    pol = PrecisionPolicy.flexpe(8, backend="pallas")

    def fn(x, codes, scale):
        w = QuantizedTensor(codes, scale, "fxp8", n, False)
        return dispatch._matmul_pallas(x, w, pol, af=af)

    compile_for(fn, ((m, k), jnp.bfloat16), ((k, n), jnp.int8),
                ((1, n), jnp.float32), kernel="fxp_gemm")


@pytest.mark.parametrize("m,k,n,af", GEMMS)
def test_packed_fxp4_gemm_compiles(compile_for, m, k, n, af):
    """Nibble-packed FxP4 weights: 128-lane byte blocks, split halves."""
    pol = PrecisionPolicy.edge4(backend="pallas")
    words = -(-n // FORMATS["fxp4"].lanes_per_word)

    def fn(x, data, scale):
        w = QuantizedTensor(data, scale, "fxp4", n, True)
        return dispatch._matmul_pallas(x, w, pol, af=af)

    compile_for(fn, ((m, k), jnp.bfloat16), ((k, words), jnp.int32),
                ((1, n), jnp.float32), kernel="fxp_gemm")


#: (query heads, KV heads, head dim) of the served configurations
ATTN_HEADS = {"minicpm_2b": (36, 36, 64), "mistral_nemo_12b": (32, 8, 128)}


@pytest.mark.parametrize("heads", ATTN_HEADS)
@pytest.mark.parametrize("mb", [MB, 136])
@pytest.mark.parametrize("kv", ["bf16", "fxp8", "fxp8-int-attention"])
def test_paged_attention_compiles(compile_for, kv, mb, heads):
    """Fused paged decode attention over a float pool, an int8 pool
    dequantized at staging, and fully-integer attention on int8 codes, at
    both served head layouts and at the served table width (136 slots:
    max_len 2048 plus one 128-token chunk, in blocks of 16), where the
    staged row is largest."""
    h, kvh, hd = ATTN_HEADS[heads]
    nb = SLOTS * mb
    fmt = None if kv == "bf16" else FORMATS["fxp8"]
    int_attn = kv == "fxp8-int-attention"
    pol = PrecisionPolicy.flexpe(8, af_impl="exact" if int_attn else "cordic",
                                 backend="pallas")
    pool_dt = jnp.bfloat16 if fmt is None else jnp.int8
    scale = (nb, BS, kvh, 1) if fmt is not None else (1, 1, kvh, 1)

    def fn(q, kp, vp, ks, vs, tables, lengths, positions):
        return paged_attention(q, kp, vp, ks, vs, tables, lengths=lengths,
                               kv_valid=lengths + 1, positions=positions,
                               fmt=fmt, int_attention=int_attn, policy=pol)

    compile_for(fn, ((SLOTS, 1, h, hd), jnp.bfloat16),
                ((nb, BS, kvh, hd), pool_dt), ((nb, BS, kvh, hd), pool_dt),
                (scale, jnp.float32), (scale, jnp.float32),
                ((SLOTS, mb), jnp.int32), ((SLOTS,), jnp.int32),
                ((SLOTS, 1), jnp.int32), kernel="paged_attention")


@pytest.mark.parametrize("shape", [(SLOTS, FF), (128, FF)])
def test_cordic_af_compiles(compile_for, shape):
    compile_for(lambda x: cordic_af(x, "silu", precision="fxp8",
                                    interpret=False),
                (shape, jnp.float32), kernel="cordic_af")


@pytest.mark.parametrize("shape", [(SLOTS, HEADS, 1, MB * BS),
                                   (1, HEADS, 128, MB * BS)])
def test_cordic_softmax_compiles(compile_for, shape):
    compile_for(lambda x: cordic_softmax(x, precision="fxp8",
                                         interpret=False),
                (shape, jnp.float32), kernel="cordic_softmax")
