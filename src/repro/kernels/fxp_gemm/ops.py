"""Jit'd public wrappers for the fxp_gemm Pallas kernels.

`fxp_gemm(x, w, precision=...)` is the serving-path quantized matmul:
dynamic-scale quantize -> integer Pallas GEMM with the dequant (+ optional
fused Flex-PE AF) epilogue in-kernel — the PE's MAC→AF pipeline is one
kernel launch. FxP4 additionally offers `packed=True`, storing w as packed
nibbles (half the weight bytes moved — the SIMD storage win).

Model serving goes through `kernels.dispatch` (which adds QuantizedTensor
quantize-once weights); this wrapper is the standalone/kernel-test entry.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ...core.cordic import PARETO_STAGES
from ...core.fxp import FORMATS, fake_quant, quantize
from .fxp_gemm import fxp_gemm_fused_pallas


def round_up(n: int, mult: int) -> int:
    """Smallest multiple of `mult` >= n (MXU block alignment)."""
    return -(-n // mult) * mult


def pad_to(x, mult, axis, value=0):
    """Zero-pad (or `value`-pad) `axis` of x up to a multiple of `mult`."""
    p = (-x.shape[axis]) % mult
    if p == 0:
        return x
    pads = [(0, 0)] * x.ndim
    pads[axis] = (0, p)
    return jnp.pad(x, pads, constant_values=value)


@functools.partial(jax.jit, static_argnames=("precision", "af", "packed",
                                             "interpret"))
def fxp_gemm(x: jax.Array, w: jax.Array, precision: str = "fxp8",
             af: str | None = None, packed: bool = False,
             interpret: bool | None = None) -> jax.Array:
    """Quantized x @ w with FxP<precision> codes and int32 accumulation.

    >8-bit codes stay on the exact int32 accumulator while the
    overflow-free bound K * qmax^2 < 2^31 holds (FxP12: K <= 512; FxP16:
    K <= 2) — the wider-accumulator MAC contract; past the bound they fall
    back to f32 accumulation, matching the reference backend.

    x: f[M,K], w: f[K,N]. Returns f32[M,N] (optionally through the fused
    Flex-PE AF epilogue).
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    fmt = FORMATS[precision]
    assert fmt.bits == 4 or not packed, "packed path is FxP4-only"
    m, k = x.shape
    _, n = w.shape
    # padded K only appends zero codes: the live worst case is k products
    wide_exact = fmt.bits > 8 and k * fmt.qmax ** 2 < 2 ** 31

    xc, sx = quantize(x, fmt)
    wc, sw = quantize(w, fmt)
    # pad to MXU-aligned blocks (zeros contribute nothing to the dot)
    bm = min(128, round_up(max(m, 1), 8))
    bn = 256 if packed else 128      # a packed tile is bn // 2 byte lanes
    xcp = pad_to(pad_to(xc, bm, 0), 128, 1)
    wcp = pad_to(pad_to(wc, 128, 0), bn, 1)

    if packed:
        lo = wcp.astype(jnp.int8)[:, 0::2] & 0xF
        hi = wcp.astype(jnp.int8)[:, 1::2] & 0xF
        wcp = (lo | (hi << 4)).astype(jnp.int8)

    scale = jnp.broadcast_to((sx * sw).reshape(1, 1).astype(jnp.float32),
                             (1, wcp.shape[1] * 2 if packed else wcp.shape[1]))
    hr, lv, _ = PARETO_STAGES[fmt.bits]
    out = fxp_gemm_fused_pallas(xcp, wcp, scale, packed=packed, af=af,
                                hr_stages=hr, lv_stages=lv,
                                blocks=(bm, bn, 128),
                                wide_exact=wide_exact, interpret=interpret)
    out = out[:m, :n]
    if af is not None:
        # write-back quantization of the AF result — same contract as the
        # model path (kernels.dispatch): AF runs on the raw accumulator,
        # its output is snapped to the precision grid
        out = fake_quant(out, fmt)
    return out
