"""Pallas TPU kernel: fused paged decode attention (block-table walk).

The serving decode hot loop used to gather every slot's contiguous KV
view out of the paged block pool in HBM (`gather_block_kv`) before masked
attention even started — the exact round-trip the paper's DMA-reduction
argument (62X/371X fewer ifmap/weight reads) says to eliminate. This
kernel takes the pool `[NB, bs, KV, hd]` (float values, or int8 codes +
per-position scales) and the per-slot block tables directly: grid
`(B, MB)` walks each row's table one physical block at a time, the block
index fed straight from a scalar-prefetched table (vLLM-style), with
dequantization fused into the load. No contiguous view ever touches HBM;
each live block moves HBM->VMEM exactly once.

The walk's cost follows each row's live length, not the table's width.
Slot j of row b is live while j*bs < kv_valid[b] (the row's valid length,
scalar-prefetched in `meta[b, 1]`); every position of a later, dead slot
is masked. A dead slot fetches nothing (its block index repeats the last
live slot's, slot 0 for a row with no valid position, so the pipeline
issues no copy) and computes nothing: it stages the masked scores and
zero values (zero scales on the integer path) a sentinel slot stages.

The walk maintains a flash-style running max in VMEM and stages masked
scores/values into VMEM scratch; the softmax normalisation and the AV
contraction run once in the epilogue over the full staged row. Keeping
the reductions full-row (rather than rescaling partial accumulators
block-by-block) is what makes the kernel BIT-EXACT against the gathered
reference path — fp addition is not associative, so a true streaming
accumulator would round differently. Scores stage as [MB, KV, g, bs]:
every block writes one leading-axis slot (a lane-dim write at offset
j*bs is refused by Mosaic unless bs is a multiple of 128), and the
epilogue concatenates the slots back into the [KV, g, S] row. The staged
row lives in VMEM, so the scratch (about 20 KiB per position at
MiniCPM-2B's 36 x 64 KV heads) bounds the context length; see ROADMAP.

Masking is in-kernel: position p = j*bs + offset is valid iff p <= the
row's query position and p < its valid length; unallocated table entries
(sentinel NB) zero their staged block, mirroring the zero-fill gather of
the reference path by construction. The epilogue still reduces the whole
MB*bs row, so the softmax window does not depend on the row's length.
Skipping an allocated block past the valid length is exact only for a
row with at least one valid position: a masked score still weighs its
value by exp(-1e30 - max), which is 0 under the exact exp but about
1.7e-38 under the CORDIC exp, and that contribution lies below one ulp
of the row's sum. A row with no valid position returns the zero-value
average whatever its table holds (the engine keeps such rows' tables at
the sentinel, and discards their output).

Three bodies share the walk:
  * `_float_kernel`   — bf16/f32 pools (no KV quantization).
  * `_dequant_kernel` — int8 code pools + per-position scales, dequantized
    to bf16 at staging (mirrors `dequantize(view, scales, bf16)`).
  * `_int_kernel`     — fully-integer attention on int8 codes (the
    Flex-PE SIMD MAC): int32 score/AV dots, scales folded into q and the
    softmax weights, bit-exact vs `int8_decode_attention`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...core.fxp import quantize
from ...core.precision import ATTN_PRECISION

#: meta rows: per-slot (lengths, kv_valid_len, query position) int32
META_COLS = 3

#: scoped-VMEM request for the walk: the staged row outgrows Mosaic's
#: 16 MiB default past a few hundred positions; v5e has 128 MiB of VMEM
VMEM_LIMIT_BYTES = 100 * 2**20


def _row(s_scr, mb):
    """[MB, KV, g, bs] staged score slots -> the [KV, g, MB*bs] row."""
    return jnp.concatenate([s_scr[i] for i in range(mb)], axis=-1)


def _block_positions(j, bs):
    """Absolute cache positions covered by table slot j (2-D iota: TPU
    requires >=2-D), squeezed to [bs]."""
    return (j * bs
            + jax.lax.broadcasted_iota(jnp.int32, (1, bs), 1)[0])


def _live_blocks(kv_valid, bs):
    """Table slots a row's valid length reaches, ceil(kv_valid / bs): the
    walk's live prefix. Every position in a later slot is masked."""
    return (kv_valid + bs - 1) // bs


def _float_body(tbl_ref, meta_ref, q_ref, o_ref, s_scr, v_scr, m_scr, *,
                mb, bs, nb, kvh, g, hd, exp_fn, div_fn, load_kv):
    """Shared walk/epilogue for the float and dequant variants; `load_kv`
    returns this block's (k, v) as f32 [bs, KV, hd]."""
    b, j = pl.program_id(0), pl.program_id(1)
    qpos = meta_ref[b, 2]
    kvv = meta_ref[b, 1]
    live = j < _live_blocks(kvv, bs)

    @pl.when(j == 0)
    def _():
        # -1e30 is every masked score, so the row max comes out the same
        # whether or not the dead slots take part in it
        m_scr[...] = jnp.full_like(m_scr, -1e30)

    @pl.when(live)
    def _():
        kf, vf = load_kv()
        alloc = tbl_ref[b, j] < nb      # sentinel rows stage exact zeros
        kf = jnp.where(alloc, kf, jnp.zeros_like(kf))
        vf = jnp.where(alloc, vf, jnp.zeros_like(vf))

        scale = 1.0 / (hd ** 0.5)
        qf = q_ref[0].astype(jnp.float32)                   # [KV, g, hd]
        s_blk = jnp.einsum("kgd,skd->kgs", qf, kf,
                           precision=ATTN_PRECISION) * scale  # [KV, g, bs]

        pos = _block_positions(j, bs)
        s_blk = jnp.where((pos <= qpos)[None, None, :], s_blk, -1e30)
        s_blk = jnp.where((pos < kvv)[None, None, :], s_blk, -1e30)

        m_scr[...] = jnp.maximum(m_scr[...], jnp.max(s_blk, axis=-1))
        s_scr[j] = s_blk
        v_scr[pl.ds(j * bs, bs)] = vf

    @pl.when(jnp.logical_not(live))
    def _():
        # a dead slot stages what a sentinel slot stages: masked scores
        # and zero values, so the epilogue reads nothing stale
        s_scr[j] = jnp.full(s_scr.shape[1:], -1e30, jnp.float32)
        v_scr[pl.ds(j * bs, bs)] = jnp.zeros((bs,) + v_scr.shape[1:],
                                             jnp.float32)

    @pl.when(j == mb - 1)
    def _():
        s_all = _row(s_scr, mb)                             # [KV, g, S]
        p = exp_fn(s_all - m_scr[...][..., None])
        denom = jnp.sum(p, axis=-1)                         # [KV, g]
        o = jnp.einsum("kgs,skd->kgd", p, v_scr[...],
                       precision=ATTN_PRECISION)            # [KV, g, hd]
        out = div_fn(o, denom[..., None])
        o_ref[0] = out.reshape(kvh * g, hd).astype(o_ref.dtype)


def _float_kernel(tbl_ref, meta_ref, q_ref, k_ref, v_ref, o_ref,
                  s_scr, v_scr, m_scr, **kw):
    def load_kv():
        return (k_ref[0].astype(jnp.float32), v_ref[0].astype(jnp.float32))
    _float_body(tbl_ref, meta_ref, q_ref, o_ref, s_scr, v_scr, m_scr,
                load_kv=load_kv, **kw)


def _dequant_kernel(tbl_ref, meta_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref,
                    o_ref, s_scr, v_scr, m_scr, **kw):
    def load_kv():
        # mirror dequantize(codes, scale, bf16): f32 product snapped to
        # bf16 — the value grid the gathered reference path attends over
        k = (k_ref[0].astype(jnp.float32)
             * ks_ref[0]).astype(jnp.bfloat16).astype(jnp.float32)
        v = (v_ref[0].astype(jnp.float32)
             * vs_ref[0]).astype(jnp.bfloat16).astype(jnp.float32)
        return k, v
    _float_body(tbl_ref, meta_ref, q_ref, o_ref, s_scr, v_scr, m_scr,
                load_kv=load_kv, **kw)


def _int_kernel(tbl_ref, meta_ref, qc_ref, sq_ref, k_ref, v_ref, ks_ref,
                vs_ref, o_ref, s_scr, ks_scr, v_scr, vs_scr, *,
                mb, bs, nb, kvh, g, hd, fmt, softmax_fn):
    """Fully-integer walk: int32 score dot per block (integer sums are
    associative, so blockwise accumulation is exact by construction),
    scales staged alongside the codes for the epilogue fold."""
    b, j = pl.program_id(0), pl.program_id(1)
    live = j < _live_blocks(meta_ref[b, 1], bs)

    @pl.when(live)
    def _():
        alloc = tbl_ref[b, j] < nb
        kc = jnp.where(alloc, k_ref[0], jnp.zeros((), k_ref.dtype))
        vc = jnp.where(alloc, v_ref[0], jnp.zeros((), v_ref.dtype))
        ks = jnp.where(alloc, ks_ref[0][..., 0], 0.0)       # [bs, KV]
        vs = jnp.where(alloc, vs_ref[0][..., 0], 0.0)

        # int8 codes go into the MXU as int8, accumulating exactly in int32
        s_scr[j] = jnp.einsum("kgd,skd->kgs", qc_ref[0], kc,
                              preferred_element_type=jnp.int32)
        ks_scr[pl.ds(j * bs, bs)] = ks
        vs_scr[pl.ds(j * bs, bs)] = vs
        v_scr[pl.ds(j * bs, bs)] = vc

    @pl.when(jnp.logical_not(live))
    def _():
        # a dead slot stages what a sentinel slot stages: zero codes and
        # zero scales (its positions are masked in the epilogue)
        s_scr[j] = jnp.zeros(s_scr.shape[1:], jnp.int32)
        ks_scr[pl.ds(j * bs, bs)] = jnp.zeros((bs, kvh), jnp.float32)
        vs_scr[pl.ds(j * bs, bs)] = jnp.zeros((bs, kvh), jnp.float32)
        v_scr[pl.ds(j * bs, bs)] = jnp.zeros((bs, kvh, hd), v_scr.dtype)

    @pl.when(j == mb - 1)
    def _():
        s = _row(s_scr, mb).astype(jnp.float32) * sq_ref[0]  # [KV, g, S]
        s = s * ks_scr[...].T[:, None, :]
        pos = _block_positions(0, mb * bs)
        mask = (pos <= meta_ref[b, 2]) & (pos < meta_ref[b, 1])
        s = jnp.where(mask[None, None, :], s, -1e30)
        p = softmax_fn(s)
        pv = p.astype(jnp.float32) * vs_scr[...].T[:, None, :]
        pvc, spv = quantize(pv, fmt, axis=-1)
        o = jnp.einsum("kgs,skd->kgd", pvc, v_scr[...],
                       preferred_element_type=jnp.int32)
        out = o.astype(jnp.float32) * spv
        o_ref[0] = out.reshape(kvh * g, hd).astype(o_ref.dtype)


def _grid_spec(b, mb, bs, nb, pool_specs, extra_in_specs, scratch, h, hd):
    def pool_index(bb, j, tbl, meta):
        # the block-table walk: physical block id straight from the
        # scalar-prefetched table. A dead slot (past the row's valid
        # length) repeats the last live slot's block, and a row with no
        # valid position reads slot 0, so the pipeline fetches nothing
        # new for it; sentinel entries clamp in range (their staged data
        # is zeroed in-kernel)
        last = jnp.maximum(_live_blocks(meta[bb, 1], bs) - 1, 0)
        return (jnp.minimum(tbl[bb, jnp.minimum(j, last)], nb - 1), 0, 0, 0)

    in_specs = list(extra_in_specs)
    in_specs += [pl.BlockSpec(ps, pool_index) for ps in pool_specs]
    return pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, mb),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, h, hd), lambda bb, j, tbl, meta:
                               (bb, 0, 0)),
        scratch_shapes=scratch)


def paged_attention_float_pallas(q, k_pool, v_pool, block_tables, meta, *,
                                 k_scale=None, v_scale=None, exp_fn,
                                 div_fn, out_dtype, interpret=False):
    """q: [B, KV, g, hd]; pools: [NB, bs, KV, hd] (+ [NB, bs, KV, 1]
    scale pools for the dequant variant); block_tables: [B, MB] int32
    (sentinel NB = unallocated); meta: [B, 3] int32 (lengths, kv_valid,
    position). Returns [B, KV*g, hd]."""
    b, kvh, g, hd = q.shape
    nb, bs = k_pool.shape[0], k_pool.shape[1]
    mb = block_tables.shape[1]
    s = mb * bs
    h = kvh * g
    kw = dict(mb=mb, bs=bs, nb=nb, kvh=kvh, g=g, hd=hd,
              exp_fn=exp_fn, div_fn=div_fn)
    q_spec = pl.BlockSpec((1, kvh, g, hd),
                          lambda bb, j, tbl, meta: (bb, 0, 0, 0))
    scratch = [pltpu.VMEM((mb, kvh, g, bs), jnp.float32),
               pltpu.VMEM((s, kvh, hd), jnp.float32),
               pltpu.VMEM((kvh, g), jnp.float32)]
    quant = k_scale is not None
    pool_specs = [(1, bs, kvh, hd), (1, bs, kvh, hd)]
    args = [block_tables, meta, q, k_pool, v_pool]
    if quant:
        pool_specs += [(1, bs, kvh, 1), (1, bs, kvh, 1)]
        args += [k_scale.astype(jnp.float32), v_scale.astype(jnp.float32)]
        kern = functools.partial(_dequant_kernel, **kw)
    else:
        kern = functools.partial(_float_kernel, **kw)
    grid_spec = _grid_spec(b, mb, bs, nb, pool_specs, [q_spec], scratch,
                           h, hd)
    out = pl.pallas_call(
        kern, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, hd), out_dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret, name="paged_attention")(*args)
    return out


def paged_attention_int_pallas(q_codes, q_scale, k_pool, v_pool, k_scale,
                               v_scale, block_tables, meta, *, fmt,
                               softmax_fn, out_dtype, interpret=False):
    """Integer-KV variant: q_codes [B, KV, g, hd] int8 + q_scale
    [B, KV, g, 1] f32 (quantized by the wrapper exactly as the reference
    quantizes q), int8 code pools + per-position scale pools. Returns
    [B, KV*g, hd]."""
    b, kvh, g, hd = q_codes.shape
    nb, bs = k_pool.shape[0], k_pool.shape[1]
    mb = block_tables.shape[1]
    s = mb * bs
    h = kvh * g
    kern = functools.partial(_int_kernel, mb=mb, bs=bs, nb=nb, kvh=kvh,
                             g=g, hd=hd, fmt=fmt, softmax_fn=softmax_fn)
    lead = [pl.BlockSpec((1, kvh, g, hd),
                         lambda bb, j, tbl, meta: (bb, 0, 0, 0)),
            pl.BlockSpec((1, kvh, g, 1),
                         lambda bb, j, tbl, meta: (bb, 0, 0, 0))]
    pool_specs = [(1, bs, kvh, hd), (1, bs, kvh, hd),
                  (1, bs, kvh, 1), (1, bs, kvh, 1)]
    scratch = [pltpu.VMEM((mb, kvh, g, bs), jnp.int32),
               pltpu.VMEM((s, kvh), jnp.float32),
               pltpu.VMEM((s, kvh, hd), k_pool.dtype),
               pltpu.VMEM((s, kvh), jnp.float32)]
    grid_spec = _grid_spec(b, mb, bs, nb, pool_specs, lead, scratch, h, hd)
    out = pl.pallas_call(
        kern, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, hd), out_dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret, name="paged_attention")(
            block_tables, meta, q_codes, q_scale.astype(jnp.float32),
            k_pool, v_pool, k_scale.astype(jnp.float32),
            v_scale.astype(jnp.float32))
    return out
