"""Pallas TPU kernel: fused CORDIC softmax (paper Fig. 4 softmax path).

Row-tiled softmax where exp runs on the HR-CORDIC shift-add datapath and the
normalisation runs through LV-CORDIC division (|e_i| <= sum e_j, so every
element is inside the LV convergence domain by construction — the same
property the hardware exploits by streaming exponentials through a FIFO
before the SIMD divider).

One grid step owns `bm` full rows in VMEM (max-subtraction, exp, row-sum and
division fuse into a single pass — no HBM round-trip for the exponentials,
which is the kernel-level realisation of the paper's "outputs are calculated
as soon as both operands are loaded").
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..cordic_af.cordic_af import _hr_exp, _lv_div


def _kernel(x_ref, o_ref, *, hr, lv, repeat_iters):
    x = x_ref[...]
    m = jnp.max(x, axis=-1, keepdims=True)
    e = _hr_exp(x - m, hr, repeat_iters)
    s = jnp.sum(e, axis=-1, keepdims=True)
    o_ref[...] = _lv_div(e, jnp.broadcast_to(s, e.shape), lv)


def cordic_softmax_pallas(x: jax.Array, hr_stages: int = 4,
                          lv_stages: int = 5, repeat_iters: bool = True,
                          block_rows: int = 8, interpret: bool = False):
    """Softmax over the last axis. x: f32[M, N], M % block_rows == 0."""
    m, n = x.shape
    bm = min(block_rows, m)
    assert m % bm == 0, (m, bm)
    kern = functools.partial(_kernel, hr=hr_stages, lv=lv_stages,
                             repeat_iters=repeat_iters)
    return pl.pallas_call(
        kern,
        grid=(m // bm,),
        in_specs=[pl.BlockSpec((bm, n), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((bm, n), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        interpret=interpret,
        name="cordic_softmax",
    )(x)
