"""Pallas TPU kernel: multi-precision fixed-point GEMM (the Flex-PE MAC array).

The systolic-array side of the paper: quantized GEMM over integer codes with
int32 accumulation (the hardware's FxP32 accumulator), MXU-aligned
128x128x128 default blocks, and an optional packed-int4 operand path where
two FxP4 codes share one int8 byte — the SIMD storage win: int4 weights move
half the HBM->VMEM bytes and unpack with shift/mask inside the kernel,
mirroring the PE's lane-split barrel shifter. A packed weight block is
(bk, bn // 2) bytes, so on the chip bn is a multiple of 256 (a 128-lane
byte block); int8 code blocks enter the MXU as int8.

Grid is (M/bm, N/bn, K/bk) with K innermost; accumulation is
output-stationary across K steps. Two kernel families:

  * code kernel (`fxp_gemm_pallas`) — int32 output of raw code dots,
    bit-identical to the ref oracle.
  * fused kernel (`fxp_gemm_fused_pallas`) — int32 VMEM scratch accumulator
    with a dequant (+ optional CORDIC AF) epilogue at the last K step, so
    the PE's MAC→AF pipeline is ONE kernel launch: f32 output =
    AF(acc * scale[1, N]), scale carrying the per-output-channel weight
    scale folded with the dynamic activation scale.

Code dtypes and the exact-int contract past 8 bits: int8 codes (FxP4/8)
accumulate exactly in int32 for any K — worst case K * 127^2 stays far
inside int32. Wider codes are exact in int32 only while the overflow-free
bound K * qmax^2 < 2^31 holds: FxP12 (qmax 2047) is exact up to K = 512,
FxP16 (qmax 32767) only to K = 2. `ops.fxp_gemm` checks the bound per
call and passes `wide_exact` to the fused kernel; beyond the bound,
>8-bit codes accumulate in f32 — the software stand-in for the hardware's
widened accumulator (documented compromise: f32 has a 24-bit mantissa,
matching the reference backend's own accumulation). The raw code kernel
(`fxp_gemm_pallas`) always accumulates int32 and leaves the bound to the
caller — it preserves int16/int32 code dtypes instead of truncating them.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..cordic_af.cordic_af import _af_block

DEFAULT_BLOCKS = (128, 128, 128)

#: AFs the fused epilogue supports (the Flex-PE Sel_AF set, minus softmax
#: which needs a row reduction — that lives in kernels/cordic_softmax).
FUSED_AFS = ("relu", "sigmoid", "tanh", "silu", "gelu", "exp")


def _code_dot(x, w):
    """Exact int32 dot of two integer code blocks. int8 codes go into the
    MXU as int8 (its integer operand type; Mosaic refuses int32 operands);
    wider codes widen to int32 first (the >8-bit contract below)."""
    if x.dtype == jnp.int8 and w.dtype == jnp.int8:
        return jnp.dot(x, w, preferred_element_type=jnp.int32)
    return jnp.dot(x.astype(jnp.int32), w.astype(jnp.int32),
                   preferred_element_type=jnp.int32)


def _acc_dot(x, w, acc_dtype):
    if acc_dtype == jnp.int32:
        return _code_dot(x, w)
    return jnp.dot(x.astype(acc_dtype), w.astype(acc_dtype),
                   preferred_element_type=acc_dtype)


def _nibbles(wp):
    """packed int8 bytes [bk, h] -> sign-extended int8 codes (lo, hi), each
    [bk, h]: the even and odd element of every byte (lane order of
    core.simd.pack). Shift pairs on the widened byte sign-extend each
    nibble; no lane interleave is needed because the kernel keeps the two
    halves apart (see `_split_pairs`)."""
    w = wp.astype(jnp.int32)
    lo = jnp.left_shift(w, 28) >> 28
    hi = jnp.left_shift(w, 24) >> 28
    return lo.astype(jnp.int8), hi.astype(jnp.int8)


def _split_pairs(a, bn):
    """[r, n] logical columns -> the packed kernel's column order: within
    each bn-wide tile, the even columns, then the odd ones."""
    r, n = a.shape
    return a.reshape(r, n // bn, bn // 2, 2).swapaxes(-1, -2).reshape(r, n)


def _merge_pairs(a, bn):
    """Inverse of `_split_pairs`."""
    r, n = a.shape
    return a.reshape(r, n // bn, 2, bn // 2).swapaxes(-1, -2).reshape(r, n)


def _gemm_kernel(x_ref, w_ref, o_ref):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[...] += _code_dot(x_ref[...], w_ref[...])


def _gemm_kernel_fused(x_ref, w_ref, s_ref, o_ref, acc_ref, *, nk, packed,
                       af, hr, lv):
    """Output-stationary code GEMM with dequant(+AF) epilogue at k == nk-1.
    Packed weights accumulate as two lane-aligned halves: the low nibbles
    (even columns) into the first half of the tile, the high nibbles (odd
    columns) into the second."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]
    acc_t = acc_ref.dtype
    if packed:
        lo, hi = _nibbles(w_ref[...])
        h = lo.shape[1]
        acc_ref[:, :h] += _acc_dot(x, lo, acc_t)
        acc_ref[:, h:] += _acc_dot(x, hi, acc_t)
    else:
        acc_ref[...] += _acc_dot(x, w_ref[...], acc_t)

    @pl.when(k == nk - 1)
    def _():
        out = acc_ref[...].astype(jnp.float32) * s_ref[...]
        if af is not None:
            out = _af_block(out, af, hr, lv, True)
        o_ref[...] = out


def fxp_gemm_pallas(x_codes: jax.Array, w_codes: jax.Array,
                    blocks=DEFAULT_BLOCKS, interpret: bool = False):
    """int[M,K] @ int[K,N] -> int32[M,N], exact int32 accumulation.

    Codes keep their storage dtype (int8 for FxP<=8, int16 for FxP12/16)
    on the way into the kernel — the dot widens to int32 in VMEM. Exact
    for any K with int8 codes; for wider codes the caller owns the
    overflow-free bound K * qmax^2 < 2^31 (see module docstring)."""
    m, k = x_codes.shape
    k2, n = w_codes.shape
    assert k == k2
    assert (jnp.issubdtype(x_codes.dtype, jnp.integer)
            and jnp.issubdtype(w_codes.dtype, jnp.integer)), (
        x_codes.dtype, w_codes.dtype)
    bm, bn, bk = (min(b, d) for b, d in zip(blocks, (m, n, k)))
    assert m % bm == 0 and n % bn == 0 and k % bk == 0
    return pl.pallas_call(
        _gemm_kernel,
        grid=(m // bm, n // bn, k // bk),
        in_specs=[pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
                  pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j))],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.int32),
        interpret=interpret,
        name="fxp_gemm",
    )(x_codes, w_codes)


def fxp_gemm_fused_pallas(x_codes: jax.Array, w_codes: jax.Array,
                          scale: jax.Array, *, packed: bool = False,
                          af: str | None = None, hr_stages: int = 4,
                          lv_stages: int = 5, blocks=DEFAULT_BLOCKS,
                          wide_exact: bool = False,
                          interpret: bool = False):
    """Code GEMM with fused dequant(+AF) epilogue — one kernel launch.

    x_codes: int[M,K]; w_codes: int[K,N] codes, or packed-nibble int8
    [K, N//2] when packed=True. scale: f32[1,N] (per-output-channel dequant
    scale, activation scale folded in). Returns f32[M,N] = AF(acc * scale).

    `wide_exact` extends the exact-int contract to >8-bit codes: the
    caller asserts K * qmax^2 < 2^31 (no int32 partial-sum overflow —
    `ops.fxp_gemm` computes this from the format) and the kernel keeps
    the int32 accumulator instead of falling back to f32.
    """
    assert af is None or af in FUSED_AFS, af
    m, k = x_codes.shape
    k2, nw = w_codes.shape
    assert k == k2
    n = nw * 2 if packed else nw
    assert scale.shape == (1, n), (scale.shape, n)
    bm, bn, bk = (min(b, d) for b, d in zip(blocks, (m, n, k)))
    assert m % bm == 0 and n % bn == 0 and k % bk == 0
    assert not packed or bn % 2 == 0
    # exact int32 accumulation when BOTH operands are <=8-bit codes
    # (packed nibbles count: the bytes hold 4-bit lanes) — any K fits.
    # Wider codes accumulate int32 only under the caller-asserted
    # `wide_exact` bound; otherwise they take the f32 accumulator.
    def _narrow(dt, is_packed=False):
        return jnp.issubdtype(dt, jnp.integer) and (dt.itemsize == 1
                                                    or is_packed)
    exact = (_narrow(x_codes.dtype) and _narrow(w_codes.dtype, packed)
             ) or wide_exact
    acc_dtype = jnp.int32 if exact else jnp.float32
    nk = k // bk
    kern = functools.partial(_gemm_kernel_fused, nk=nk, packed=packed,
                             af=af, hr=hr_stages, lv=lv_stages)
    w_spec = (pl.BlockSpec((bk, bn // 2), lambda i, j, kk: (kk, j)) if packed
              else pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)))
    scale = scale.astype(jnp.float32)
    if packed:
        scale = _split_pairs(scale, bn)
    out = pl.pallas_call(
        kern,
        grid=(m // bm, n // bn, nk),
        in_specs=[pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
                  w_spec,
                  pl.BlockSpec((1, bn), lambda i, j, kk: (0, j))],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), acc_dtype)],
        interpret=interpret,
        name="fxp_gemm",
    )(x_codes, w_codes, scale)
    return _merge_pairs(out, bn) if packed else out
