"""Pure-jnp oracle for fused paged decode attention.

Reproduces, op for op, what `models.layers.attention` computes on the
paged decode path when it gathers the per-row contiguous KV view and runs
masked attention over it (`gather_block_kv` + `chunked_attention` /
`int8_decode_attention` with Sq = 1) — except that the gather never
materialises in HBM as a separate XLA value the attention reads back.
Bit-exactness against the layers path is enforced by
tests/test_paged_attention.py; keep the two in lockstep.

Unallocated block-table entries use the sentinel index NB (one past the
pool) and gather exact zeros (`jnp.take` mode="fill") — every position
they could resolve is masked anyway, so for any row with at least one
valid key the output is bit-identical to the historical clip-mode gather;
fully-idle rows now produce a deterministic zero-V average instead of a
block-0-garbage average (their output is discarded by the engine either
way).
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from ...core import cordic
from ...core.activation import default_stages, softmax_lv_stages
from ...core.fxp import dequantize, quantize
from ...core.precision import ATTN_PRECISION


def gather_pool_view(pool, block_tables):
    """[NB, bs, ...] pool + [B, MB] tables -> [B, MB*bs, ...] view; table
    entries >= NB (the unallocated sentinel) read exact zeros."""
    g = jnp.take(pool, block_tables, axis=0, mode="fill", fill_value=0)
    b, mb, bs = g.shape[0], g.shape[1], g.shape[2]
    return g.reshape((b, mb * bs) + g.shape[3:])


def _exp_fn(policy):
    """Mirror of models.layers._exp_fn (the online-softmax exp)."""
    if policy is not None and policy.attn_softmax == "cordic":
        hr, _ = default_stages(policy.af)
        return lambda z: cordic.extended_exp_float(z, hr)
    return jnp.exp


def _final_div(num, den, kv_len, policy):
    """Mirror of models.layers._final_div (the online-softmax normalise)."""
    if policy is not None and policy.attn_softmax == "cordic":
        lv = softmax_lv_stages(kv_len, policy.af)
        scale = jnp.maximum(jnp.max(jnp.abs(num), axis=-1, keepdims=True),
                            den) + 1e-9
        return cordic.lv_divide_float(num / scale, den / scale, lv)
    return num / den


def _float_decode(q, k, v, lengths, kv_valid, policy):
    """Sq=1 slice of models.layers.chunked_attention (q_offset=lengths,
    kv_valid_len=kv_valid): one query block, full-row softmax."""
    b, sq, h, hd = q.shape
    _, skv, kvh, _ = k.shape
    g = h // kvh
    scale = 1.0 / math.sqrt(hd)
    expf = _exp_fn(policy)
    qoff = jnp.broadcast_to(jnp.asarray(lengths, jnp.int32), (b,))
    kvv = jnp.broadcast_to(jnp.asarray(kv_valid, jnp.int32), (b,))
    kv_pos = jnp.arange(skv)

    qh = q.reshape(b, sq, kvh, g, hd)
    s = jnp.einsum("bqkgd,bskd->bqkgs", qh.astype(jnp.float32),
                   k.astype(jnp.float32), precision=ATTN_PRECISION) * scale
    s = s.reshape(b, sq, h, skv)
    qpos = qoff[:, None] + jnp.arange(sq)[None, :]
    mask = kv_pos[None, None, :] <= qpos[:, :, None]
    s = jnp.where(mask[:, :, None, :], s, -1e30)
    vmask = kv_pos[None, :] < kvv[:, None]
    s = jnp.where(vmask[:, None, None, :], s, -1e30)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = expf(s - m)
    denom = jnp.sum(p, axis=-1)
    ph = p.reshape(b, sq, kvh, g, skv)
    o = jnp.einsum("bqkgs,bskd->bqkgd", ph, v.astype(jnp.float32),
                   precision=ATTN_PRECISION)
    o = o.reshape(b, sq, h, hd)
    return _final_div(o, denom[..., None], skv, policy).astype(q.dtype)


def _int_decode(q, k_codes, v_codes, k_scale, v_scale, fmt, policy,
                positions, kv_valid):
    """Mirror of models.layers.int8_decode_attention on gathered views."""
    b, sq_, h, hd = q.shape
    _, skv, kvh, _ = k_codes.shape
    g = h // kvh
    qc, sq = quantize(q.astype(jnp.float32) / math.sqrt(hd), fmt, axis=3)
    qh = qc.reshape(b, sq_, kvh, g, hd)
    s_int = jnp.einsum("bqkgd,bskd->bqkgs", qh.astype(jnp.int32),
                       k_codes.astype(jnp.int32))
    ks = k_scale.transpose(0, 3, 2, 1).reshape(b, 1, kvh, 1, skv)
    s = s_int.astype(jnp.float32) * sq.reshape(b, sq_, kvh, g, 1) * ks
    kv_pos = jnp.arange(skv)
    kvv = jnp.broadcast_to(jnp.asarray(kv_valid, jnp.int32), (b,))
    mask = ((kv_pos[None, None, :] <= positions[:, :, None])
            & (kv_pos[None, None, :] < kvv[:, None, None]))
    s = jnp.where(mask[:, :, None, None, :], s, -1e30)
    p = policy.softmax(s, axis=-1) if policy else jax.nn.softmax(s, axis=-1)
    vs = v_scale.transpose(0, 3, 2, 1).reshape(b, 1, kvh, 1, skv)
    pv = p.astype(jnp.float32) * vs
    pvc, spv = quantize(pv, fmt, axis=4)
    o_int = jnp.einsum("bqkgs,bskd->bqkgd", pvc.astype(jnp.int32),
                       v_codes.astype(jnp.int32))
    out = o_int.astype(jnp.float32) * spv.reshape(b, sq_, kvh, g, 1)
    return out.reshape(b, sq_, h, hd).astype(q.dtype)


def paged_attention_ref(q, k_pool, v_pool, k_scale, v_scale, block_tables,
                        *, lengths, kv_valid, positions,
                        fmt=None, int_attention: bool = False,
                        policy: Optional[object] = None):
    """Decode attention straight off the block pool (oracle).

    q: [B, 1, H, hd]; k_pool/v_pool: [NB, bs, KV, hd] (float, or int codes
    when `fmt` is set); k_scale/v_scale: [NB, bs, KV, 1] per-position
    scales (quantized pools only); block_tables: [B, MB] int32 with
    sentinel NB marking unallocated slots; lengths/kv_valid: [B] int32;
    positions: [B, 1] int32 absolute query positions. Returns
    [B, 1, H, hd] in q.dtype.
    """
    kv = gather_pool_view(k_pool, block_tables)
    vv = gather_pool_view(v_pool, block_tables)
    if fmt is None:
        return _float_decode(q, kv, vv, lengths, kv_valid, policy)
    ks = gather_pool_view(k_scale, block_tables)
    vs = gather_pool_view(v_scale, block_tables)
    if int_attention:
        return _int_decode(q, kv, vv, ks, vs, fmt, policy, positions,
                           kv_valid)
    k_full = dequantize(kv, ks, jnp.bfloat16)
    v_full = dequantize(vv, vs, jnp.bfloat16)
    return _float_decode(q, k_full, v_full, lengths, kv_valid, policy)
