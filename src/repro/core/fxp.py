"""Fixed-point (FxP) formats and quantization for Flex-PE.

The paper's datapath operates on dynamic fixed-point values in [-1, 1]
(§II-D: inputs normalised to [-1, 1], MaxNorm 5.5). We model FxP<N> as a
signed two's-complement integer grid with a per-tensor (or per-channel)
dynamic scale, plus round-to-nearest-even ("data parallelised rounds-to-even
mode", §III-B).

Two views of an FxP tensor:
  * fake-quant float  — float values snapped to the FxP grid (fast jnp path,
    used inside models; exactly representable, so it is bit-equivalent to the
    integer view under the same scale).
  * integer codes     — int32 codes + scale (used by packed SIMD storage and
    the bit-accurate CORDIC emulator).
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "FxPFormat", "FXP4", "FXP8", "FXP12", "FXP16", "FXP24", "FXP32",
    "FORMATS", "quantize", "dequantize", "fake_quant", "fake_quant_ste",
    "code_dtype",
    "dynamic_scale", "amax_scale", "round_half_even",
]


@dataclasses.dataclass(frozen=True)
class FxPFormat:
    """Signed fixed-point format: `bits` total, `frac` fractional bits.

    The Q-format interpretation (value = code * 2**-frac) is used by the
    bit-accurate CORDIC emulator; the quantizer below uses dynamic scaling
    (value = code * scale) which subsumes it.
    """
    name: str
    bits: int
    frac: int  # default Q-format fractional bits (bits-2 ≈ range [-2, 2))

    @property
    def qmax(self) -> int:
        return (1 << (self.bits - 1)) - 1

    @property
    def qmin(self) -> int:
        return -(1 << (self.bits - 1))

    @property
    def lanes_per_word(self) -> int:
        """SIMD lanes in one 32-bit datapath word (paper: 16/8/4/1... capped
        by storage: we pack into int32, so 8×4b / 4×8b / 2×16b / 1×32b per
        word; the paper's 16× counts two 32b words of its dual-issue path —
        throughput modelling uses `throughput_x`)."""
        return 32 // self.bits

    @property
    def throughput_x(self) -> int:
        """Paper Table I / §V relative throughput: 16/8/4/1 for 4/8/16/32."""
        return {4: 16, 8: 8, 12: 2, 16: 4, 24: 1, 32: 1}[self.bits]

    @property
    def eps(self) -> float:
        return 2.0 ** (-self.frac)


FXP4 = FxPFormat("fxp4", 4, 2)
FXP8 = FxPFormat("fxp8", 8, 6)
FXP12 = FxPFormat("fxp12", 12, 10)
FXP16 = FxPFormat("fxp16", 16, 14)
FXP24 = FxPFormat("fxp24", 24, 22)
FXP32 = FxPFormat("fxp32", 32, 30)

FORMATS = {f.name: f for f in (FXP4, FXP8, FXP12, FXP16, FXP24, FXP32)}


def round_half_even(x: jax.Array) -> jax.Array:
    """Round-to-nearest-even on float inputs (paper §III-B)."""
    return jnp.round(x)  # jnp.round implements banker's rounding (half-even)


def dynamic_scale(x: jax.Array, fmt: FxPFormat, axis=None) -> jax.Array:
    """Per-tensor (axis=None) or per-axis dynamic scale so max|x| maps to qmax."""
    amax = jnp.max(jnp.abs(x)) if axis is None else jnp.max(
        jnp.abs(x), axis=axis, keepdims=True)
    amax = jnp.maximum(amax.astype(jnp.float32), 1e-12)
    return amax_scale(amax, fmt)


def amax_scale(amax: jax.Array, fmt: FxPFormat) -> jax.Array:
    """The scale that maps `amax` to qmax: amax times the f32 reciprocal
    of qmax. Inside jit XLA turns a division by the constant qmax into
    exactly this product, so eager and jitted calls (a Pallas kernel
    body included) give the same scale."""
    return amax * (np.float32(1) / np.float32(fmt.qmax))


def code_dtype(fmt: FxPFormat):
    """Narrowest int dtype holding the codes (memory: int8 for FxP<=8)."""
    return jnp.int8 if fmt.bits <= 8 else (
        jnp.int16 if fmt.bits <= 16 else jnp.int32)


def quantize(x: jax.Array, fmt: FxPFormat, scale=None, axis=None):
    """-> (int codes (narrowest dtype), scale). Clipped to [qmin, qmax]."""
    if scale is None:
        scale = dynamic_scale(x, fmt, axis=axis)
    codes = round_half_even(x.astype(jnp.float32) / scale)
    codes = jnp.clip(codes, fmt.qmin, fmt.qmax).astype(code_dtype(fmt))
    return codes, scale


def dequantize(codes: jax.Array, scale: jax.Array, dtype=jnp.float32):
    return (codes.astype(jnp.float32) * scale).astype(dtype)


def fake_quant(x: jax.Array, fmt: FxPFormat, scale=None,
               axis=None) -> jax.Array:
    """Snap x to the FxP grid (no gradient definition)."""
    codes, s = quantize(x, fmt, scale=scale, axis=axis)
    return dequantize(codes, s, dtype=x.dtype)


@partial(jax.custom_vjp, nondiff_argnums=(1,))
def fake_quant_ste(x: jax.Array, fmt_name: str) -> jax.Array:
    """Fake-quant with straight-through estimator (QAT path)."""
    return fake_quant(x, FORMATS[fmt_name])


def _fq_fwd(x, fmt_name):
    fmt = FORMATS[fmt_name]
    scale = dynamic_scale(x, fmt)
    # bool clip mask (1 byte/elem residual) zeroes grads outside range
    mask = jnp.abs(x) <= (scale * fmt.qmax)
    return fake_quant(x, fmt, scale=scale), mask


def _fq_bwd(fmt_name, mask, g):
    return (g * mask.astype(g.dtype),)


fake_quant_ste.defvjp(_fq_fwd, _fq_bwd)
