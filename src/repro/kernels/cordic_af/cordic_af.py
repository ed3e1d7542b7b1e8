"""Pallas TPU kernel: SIMD CORDIC activation functions (paper Fig. 4).

Elementwise sigmoid / tanh / exp via the Flex-PE datapath — unrolled
("pipelined mode") HR-CORDIC shift-add stages + LV-CORDIC division — over
VMEM-resident blocks. The 2^k range-extension factor is applied with an
exponent-field bit trick (integer add on the f32 exponent), the Pallas
analogue of the hardware barrel shift: the kernel body is multiplier-free
except for the exact 2^-i scalings, exactly like the PE.

Block shapes default to (256, 512) f32 = 512 KiB in / 512 KiB out of VMEM,
lane-dim a multiple of 128 (TPU VREG lane width), sublane a multiple of 8.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ...core.cordic import _hr_schedule, exp2_int as _exp2_int, hyperbolic_gain

_LN2 = math.log(2.0)

DEFAULT_BLOCK = (256, 512)


def _hr_exp(z, hr_stages, repeat_iters):
    """e^z on a block: HR CORDIC with ln2 range reduction. Inputs are
    saturated to the f32 exp range first (hardware saturation; also keeps
    the k*ln2 reduction exact for softmax -inf padding)."""
    z = jnp.clip(z, -87.0, 88.0)
    k = jnp.floor(z * (1.0 / _LN2) + 0.5)
    r = z - k * _LN2
    gain = hyperbolic_gain(hr_stages, repeat_iters)
    x = jnp.full_like(r, 1.0 / gain)
    y = jnp.zeros_like(r)
    for i in _hr_schedule(hr_stages, repeat_iters):
        e = math.atanh(2.0 ** (-i))
        d = jnp.where(r >= 0, 1.0, -1.0)
        x, y = x + d * y * (2.0 ** (-i)), y + d * x * (2.0 ** (-i))
        r = r - d * e
    return (x + y) * _exp2_int(k)


def _lv_div(num, den, lv_stages):
    """num/den on a block (|num| <= |den|): LV CORDIC shift-add.
    Same d-selection rule as core.cordic.lv_divide_float (d = -sign(x*y),
    ties to +1) so kernel and reference AFs are bit-identical."""
    x, y = den, num
    q = jnp.zeros_like(num)
    for i in range(1, lv_stages + 1):
        d = -jnp.sign(x * y)
        d = jnp.where(d == 0, 1.0, d)
        y = y + d * x * (2.0 ** (-i))
        q = q - d * (2.0 ** (-i))
    return q


def _af_block(x, af: str, hr: int, lv: int, repeat_iters: bool):
    if af == "relu":
        return jnp.maximum(x, 0.0)
    if af == "exp":
        return _hr_exp(x, hr, repeat_iters)
    if af == "sigmoid":
        e = _hr_exp(-jnp.abs(x), hr, repeat_iters)
        num = jnp.where(x >= 0, jnp.ones_like(e), e)
        return _lv_div(num, 1.0 + e, lv)
    if af == "tanh":
        t = _hr_exp(-2.0 * jnp.abs(x), hr, repeat_iters)
        return jnp.sign(x) * _lv_div(1.0 - t, 1.0 + t, lv)
    if af == "silu":
        e = _hr_exp(-jnp.abs(x), hr, repeat_iters)
        num = jnp.where(x >= 0, jnp.ones_like(e), e)
        return x * _lv_div(num, 1.0 + e, lv)
    if af == "gelu":  # sigmoid approximation — same CORDIC hardware (§IV-B)
        z = 1.702 * x
        e = _hr_exp(-jnp.abs(z), hr, repeat_iters)
        num = jnp.where(z >= 0, jnp.ones_like(e), e)
        return x * _lv_div(num, 1.0 + e, lv)
    raise ValueError(f"unsupported af {af!r}")


def _kernel(x_ref, o_ref, *, af, hr, lv, repeat_iters):
    o_ref[...] = _af_block(x_ref[...], af, hr, lv, repeat_iters)


def cordic_af_pallas(x: jax.Array, af: str, hr_stages: int = 4,
                     lv_stages: int = 5, repeat_iters: bool = True,
                     block=DEFAULT_BLOCK, interpret: bool = False):
    """2D blocked CORDIC AF. x: f32[M, N] with M % block[0] == N % block[1] == 0."""
    m, n = x.shape
    bm, bn = min(block[0], m), min(block[1], n)
    assert m % bm == 0 and n % bn == 0, (x.shape, (bm, bn))
    kern = functools.partial(_kernel, af=af, hr=hr_stages, lv=lv_stages,
                             repeat_iters=repeat_iters)
    return pl.pallas_call(
        kern,
        grid=(m // bm, n // bn),
        in_specs=[pl.BlockSpec((bm, bn), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        interpret=interpret,
        name="cordic_af",
    )(x)
