"""Shared model layers — all policy-aware (Flex-PE precision + CORDIC AFs).

Functional style: params are nested dicts of arrays; a parallel tree of
logical-axis tuples (same structure) drives sharding (distributed/sharding).

Attention is chunked (query-block scan with online softmax) so that the
[B,H,S,S] score matrix is never materialised — required for train_4k /
prefill_32k at production batch sizes. The online softmax has a pluggable
exp/normalise pair: exact, or the Flex-PE CORDIC datapath (HR exp +
final LV division), which is how the paper's softmax integrates with a
memory-efficient attention schedule on TPU.

KV caches support FxP8 quantized storage (policy.kv_cache) — int8 codes +
per-(batch,head) scales, halving cache HBM and its decode roofline term.

Matmul weights may be plain float arrays or `core.qtensor.QuantizedTensor`
leaves (quantize-once packed serving storage, produced by
`core.qtensor.quantize_params`); `qmatmul` accepts both on every backend,
so the same layer code serves training and packed-weight inference.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

from ..core import cordic
from ..core.activation import default_stages, softmax_lv_stages
from ..core.backend import resolve as _resolve_backend
from ..core.fxp import FORMATS, dequantize, quantize
from ..core.precision import ATTN_PRECISION, PrecisionPolicy, qmatmul


def _dispatch():
    # lazy: layers must stay importable without pulling kernel modules in
    from ..kernels import dispatch
    return dispatch

# ---------------------------------------------------------------------------
# initialisation helpers
# ---------------------------------------------------------------------------

def dense_init(key, d_in, d_out, dtype=jnp.bfloat16, scale=None):
    scale = scale if scale is not None else 1.0 / math.sqrt(d_in)
    return (jax.random.normal(key, (d_in, d_out), jnp.float32)
            * scale).astype(dtype)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def rmsnorm(x, w, eps=1e-5):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps)).astype(x.dtype) * w


def layernorm(x, w, b, eps=1e-5):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    out = (xf - mu) * jax.lax.rsqrt(var + eps)
    return out.astype(x.dtype) * w + b


def apply_norm(x, p, kind):
    if kind == "layernorm":
        return layernorm(x, p["w"], p["b"])
    return rmsnorm(x, p["w"])


def norm_init(d, kind, dtype=jnp.bfloat16):
    if kind == "layernorm":
        return {"w": jnp.ones((d,), dtype), "b": jnp.zeros((d,), dtype)}
    return {"w": jnp.ones((d,), dtype)}


def norm_axes(kind):
    if kind == "layernorm":
        return {"w": ("embed",), "b": ("embed",)}
    return {"w": ("embed",)}


# ---------------------------------------------------------------------------
# rotary position embedding
# ---------------------------------------------------------------------------

def rope_freqs(head_dim, theta=10000.0):
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                            / head_dim))


def apply_rope(x, positions, theta=10000.0):
    """x: [B, S, H, hd]; positions: [B, S] int32."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)                       # [hd/2]
    ang = positions[..., None].astype(jnp.float32) * freqs  # [B,S,hd/2]
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# online-softmax chunked attention (train/prefill path)
# ---------------------------------------------------------------------------

def _exp_fn(policy: Optional[PrecisionPolicy]):
    """exp for the online softmax: exact, or Flex-PE HR-CORDIC."""
    if policy is not None and policy.attn_softmax == "cordic":
        hr, _ = default_stages(policy.af)
        return lambda z: cordic.extended_exp_float(z, hr)
    return jnp.exp


def _final_div(num, den, kv_len, policy: Optional[PrecisionPolicy]):
    if policy is not None and policy.attn_softmax == "cordic":
        lv = softmax_lv_stages(kv_len, policy.af)
        # LV convergence needs |num| <= |den|; num rows are sums of
        # exp-weighted V, rescale by row max |V| bound via den>=max exp sum.
        scale = jnp.maximum(jnp.max(jnp.abs(num), axis=-1, keepdims=True),
                            den) + 1e-9
        # lv_divide(num/s, den/s) == num/den with both args scaled into [-1,1]
        return cordic.lv_divide_float(num / scale, den / scale, lv)
    return num / den


def chunked_attention(q, k, v, *, causal: bool, q_offset=0,
                      policy: Optional[PrecisionPolicy] = None,
                      chunk: int = 512, kv_valid_len=None):
    """q: [B,Sq,H,hd]; k,v: [B,Skv,KV,hd] -> [B,Sq,H,hd].

    Query-block scan with online softmax; scores never exceed
    [B, chunk, H, Skv] live. GQA via head-group reshape. `q_offset` is the
    absolute position of q[0] — a scalar, or a per-request [B] vector
    (ragged decode/prefill continuation: each batch row continues from its
    own cache length). When `kv_valid_len` is set (scalar or [B]), keys at
    positions >= kv_valid_len are masked (decode with a pre-allocated
    cache whose tail holds stale entries).
    """
    b, sq, h, hd = q.shape
    _, skv, kvh, _ = k.shape
    g = h // kvh
    scale = 1.0 / math.sqrt(hd)
    expf = _exp_fn(policy)
    qoff = jnp.broadcast_to(jnp.asarray(q_offset, jnp.int32), (b,))
    kvv = (None if kv_valid_len is None else
           jnp.broadcast_to(jnp.asarray(kv_valid_len, jnp.int32), (b,)))

    nq = max(1, sq // chunk)
    while sq % nq:
        nq -= 1
    qc = sq // nq
    qb = q.reshape(b, nq, qc, h, hd).transpose(1, 0, 2, 3, 4)  # [nq,B,qc,H,hd]
    kg = k  # [B,Skv,KV,hd]
    kv_pos = jnp.arange(skv)

    def one_block(carry, qblk_idx):
        qblk, idx = qblk_idx
        # scores: [B, qc, H, Skv]
        qh = qblk.reshape(b, qc, kvh, g, hd)
        s = jnp.einsum("bqkgd,bskd->bqkgs", qh.astype(jnp.float32),
                       kg.astype(jnp.float32),
                       precision=ATTN_PRECISION) * scale
        s = s.reshape(b, qc, h, skv)
        if causal:
            qpos = qoff[:, None] + idx * qc + jnp.arange(qc)[None, :]
            mask = kv_pos[None, None, :] <= qpos[:, :, None]   # [B,qc,Skv]
            s = jnp.where(mask[:, :, None, :], s, -1e30)
        if kvv is not None:
            vmask = kv_pos[None, :] < kvv[:, None]             # [B,Skv]
            s = jnp.where(vmask[:, None, None, :], s, -1e30)
        m = jnp.max(s, axis=-1, keepdims=True)
        p = expf(s - m)                                  # [B,qc,H,Skv]
        denom = jnp.sum(p, axis=-1)                      # [B,qc,H]
        ph = p.reshape(b, qc, kvh, g, skv)
        o = jnp.einsum("bqkgs,bskd->bqkgd", ph, v.astype(jnp.float32),
                       precision=ATTN_PRECISION)
        o = o.reshape(b, qc, h, hd)
        o = _final_div(o, denom[..., None], skv, policy)
        return carry, o.astype(q.dtype)

    _, outs = jax.lax.scan(one_block, 0, (qb, jnp.arange(nq)))
    return outs.transpose(1, 0, 2, 3, 4).reshape(b, sq, h, hd)


def int8_decode_attention(q, k_codes, v_codes, k_scale, v_scale, fmt,
                          policy, positions, kv_valid_len):
    """Decode attention computed on integer KV codes (Flex-PE FxP MAC):

      scores = (q_codes @ k_codes^T) * (sq * k_scale)   int8 x int8 -> int32
      out    = (p_codes @ v_codes)   * (sp * v_scale)   int8 x int8 -> int32

    q: [B,Sq,H,hd] float; k/v codes: [B,S,KV,hd] int8 with per-(pos,head)
    scales [B,S,KV,1]. `positions` [B,Sq] are the queries' absolute cache
    positions and `kv_valid_len` [B] the per-request valid cache length —
    keys above either bound (future tokens inside a prefill chunk, stale
    tail entries) are masked per row. No bf16 cache copy is materialised:
    HBM traffic for the cache is its int8 codes (the SIMD storage win
    during decode).
    """
    b, sq_, h, hd = q.shape
    _, skv, kvh, _ = k_codes.shape
    g = h // kvh
    qc, sq = quantize(q.astype(jnp.float32) / math.sqrt(hd), fmt, axis=3)
    qh = qc.reshape(b, sq_, kvh, g, hd)
    # int32 scores, dequantized with folded (q, per-position-k) scales
    s_int = jnp.einsum("bqkgd,bskd->bqkgs", qh.astype(jnp.int32),
                       k_codes.astype(jnp.int32))
    ks = k_scale.transpose(0, 3, 2, 1).reshape(b, 1, kvh, 1, skv)
    s = s_int.astype(jnp.float32) * sq.reshape(b, sq_, kvh, g, 1) * ks
    kv_pos = jnp.arange(skv)
    kvv = jnp.broadcast_to(jnp.asarray(kv_valid_len, jnp.int32), (b,))
    mask = ((kv_pos[None, None, :] <= positions[:, :, None])
            & (kv_pos[None, None, :] < kvv[:, None, None]))    # [B,Sq,Skv]
    s = jnp.where(mask[:, :, None, None, :], s, -1e30)
    p = policy.softmax(s, axis=-1) if policy else jax.nn.softmax(s, axis=-1)
    # fold per-position v scales into the softmax weights, requantize the
    # weighted probs to int8 (the paper's FxP attention weights), int-dot
    # against v codes: out = sum_s (p_s * vscale_s) * v_codes_s
    vs = v_scale.transpose(0, 3, 2, 1).reshape(b, 1, kvh, 1, skv)
    pv = p.astype(jnp.float32) * vs
    pvc, spv = quantize(pv, fmt, axis=4)
    o_int = jnp.einsum("bqkgs,bskd->bqkgd", pvc.astype(jnp.int32),
                       v_codes.astype(jnp.int32))
    out = o_int.astype(jnp.float32) * spv.reshape(b, sq_, kvh, g, 1)
    return out.reshape(b, sq_, h, hd).astype(q.dtype)


# ---------------------------------------------------------------------------
# GQA attention layer
# ---------------------------------------------------------------------------

def attn_init(key, cfg, dtype=jnp.bfloat16):
    d, h, kvh, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], d, h * hd, dtype),
        "wk": dense_init(ks[1], d, kvh * hd, dtype),
        "wv": dense_init(ks[2], d, kvh * hd, dtype),
        "wo": dense_init(ks[3], h * hd, d, dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((h * hd,), dtype)
        p["bk"] = jnp.zeros((kvh * hd,), dtype)
        p["bv"] = jnp.zeros((kvh * hd,), dtype)
    return p


def attn_axes(cfg):
    ax = {"wq": ("embed", "qkv"), "wk": ("embed", "kv_qkv"),
          "wv": ("embed", "kv_qkv"), "wo": ("qkv", "embed")}
    if cfg.qkv_bias:
        ax.update({"bq": ("qkv",), "bk": ("kv_qkv",), "bv": ("kv_qkv",)})
    return ax


def paged_cache_update(pool, new, block_tables, start, count):
    """Paged per-request cache write: the logical update
    buf[b, start[b]:start[b]+count[b]] <- new[b, :count[b]], with each
    logical position translated through the row's block table into the
    global block pool — only the blocks holding the current window see
    HBM writes (the paged analogue of `ragged_cache_update`).

    pool: [NB, bs, ...]; new: [B, S, ...]; block_tables: [B, MB] int32;
    start/count: [B] int32. Logical position p of row b lands at
    pool[block_tables[b, p // bs], p % bs]. Tokens past count[b] scatter
    to block index NB (out of range) and are dropped, so idle rows
    (count=0) are exact no-ops. Valid positions must already have a block
    in the row's table — the serving engine allocates blocks for
    [start, start+count) before dispatching the step.
    """
    b, s = new.shape[0], new.shape[1]
    nb, bs = pool.shape[0], pool.shape[1]
    pos = start[:, None] + jnp.arange(s, dtype=jnp.int32)[None, :]    # [B,S]
    valid = jnp.arange(s)[None, :] < count[:, None]                   # [B,S]
    tslot = jnp.clip(pos // bs, 0, block_tables.shape[1] - 1)
    blk = jnp.take_along_axis(block_tables, tslot, axis=1)            # [B,S]
    blk = jnp.where(valid, blk, nb)             # out-of-range -> dropped
    off = pos % bs
    flat = new.reshape((b * s,) + new.shape[2:])
    return pool.at[blk.reshape(-1), off.reshape(-1)].set(
        flat.astype(pool.dtype), mode="drop")


def gather_block_kv(pool, block_tables):
    """Materialise each row's contiguous cache view from the block pool.

    pool: [NB, bs, ...]; block_tables: [B, MB] -> [B, MB*bs, ...] where
    logical position p of row b sits at view index p (table slot p // bs,
    offset p % bs). Unallocated table entries carry the sentinel index NB
    (one past the pool — see `model.init_cache`) and gather exact zeros
    (`mode="fill"`): the "masked anyway" invariant is enforced by
    construction instead of leaking block 0's live data into positions the
    attention kernels must mask. Every such position is >= the row's valid
    length, so for any row with at least one valid key the output is
    bit-identical to the historical clip-mode gather."""
    g = jnp.take(pool, block_tables, axis=0, mode="fill", fill_value=0)
    b, mb, bs = g.shape[0], g.shape[1], g.shape[2]
    return g.reshape((b, mb * bs) + g.shape[3:])


def ragged_cache_update(buf, new, start, count):
    """Per-request cache write: buf[b, start[b]:start[b]+count[b]] <-
    new[b, :count[b]], every other position of buf untouched.

    buf: [B, Smax, ...]; new: [B, S, ...]; start/count: [B] int32. The write
    is a vmapped read-modify-write window: positions >= count[b] inside the
    window are rewritten with their current content, so rows with
    count[b]=0 (idle slots) are exact no-ops — even when XLA clamps an
    out-of-range start, the clamped window is read and written back
    unchanged. Rows with count[b] > 0 need start[b] + S <= Smax (the
    serving engine over-allocates the cache by one chunk to guarantee it).
    """
    s = new.shape[1]

    def row(buf_b, new_b, st, ct):
        cur = jax.lax.dynamic_slice_in_dim(buf_b, st, s, axis=0)
        keep = (jnp.arange(s) < ct).reshape((s,) + (1,) * (new_b.ndim - 1))
        upd = jnp.where(keep, new_b.astype(buf_b.dtype), cur)
        return jax.lax.dynamic_update_slice_in_dim(buf_b, upd, st, axis=0)

    return jax.vmap(row)(buf, new, start, count)


def attention(p, x, cfg, *, positions, policy=None, cache=None,
              lengths=None, n_valid=None, block_tables=None, pool_tp=1):
    """Returns (out, new_cache_entry|None).

    Training/prefill: cache=None -> full chunked attention over x.
    Decode / chunked prefill: cache=(k,v,k_scale,v_scale) pre-allocated
    [B,Smax,KV,hd]; x is the new token block [B,S,D]; `lengths` [B] is each
    request's valid cache length (= write offset for its new tokens) and
    `n_valid` [B] how many of this block's S tokens are real for that row
    (ragged batches: rows prefill/decode/idle independently). The block is
    causal relative to per-row absolute positions, so S > 1 serves chunked
    prefill and S = 1 plain decode through the same code.

    Paged decode: `block_tables` [B, MB] switches the cache leaves to a
    global block pool [NB, bs, KV, hd] shared by all rows (unallocated
    table slots hold the sentinel NB). New tokens scatter into the current
    block only (`paged_cache_update`); single-token decode (S = 1) then
    runs the fused `kernels/paged_attention` op, which walks the table
    over the pool directly, while chunked prefill (S > 1) attends over the
    gathered per-row view (`gather_block_kv`) — stale / unallocated tails
    are masked exactly like the contiguous cache's, so all layouts and
    paths are bit-identical in what they compute. Several rows may
    point at the SAME physical block (prefix sharing): that is safe
    because a row only ever writes at [lengths, lengths+n_valid), and the
    engine copy-on-writes any shared block before a row's write window
    reaches it.

    `pool_tp` > 1 says the pool's block axis is partitioned over that many
    mesh shards: the fused Pallas kernel (whose in-kernel block addressing
    assumes the whole pool is local) is skipped in favour of the
    gather+masked path — `jnp.take` over a sharded block axis is an index
    op GSPMD partitions exactly, so the fallback stays bit-identical to
    the fused kernel's single-shard output.
    """
    b, s, d = x.shape
    h, kvh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = qmatmul(x, p["wq"], policy)
    k = qmatmul(x, p["wk"], policy)
    v = qmatmul(x, p["wv"], policy)
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(b, s, h, hd)
    k = k.reshape(b, s, kvh, hd)
    v = v.reshape(b, s, kvh, hd)
    if cfg.rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)

    if cache is None:
        out = chunked_attention(q, k, v, causal=True, policy=policy)
        new_cache = None
    else:
        kc, vc, k_scale, v_scale = cache
        if n_valid is None:
            n_valid = jnp.full((b,), s, jnp.int32)
        kv_valid = lengths + n_valid                       # [B]
        kq_fmt = (FORMATS[policy.kv_cache]
                  if (policy and policy.kv_cache) else None)
        paged = block_tables is not None
        if paged:
            def write(buf, new):
                return paged_cache_update(buf, new, block_tables, lengths,
                                          n_valid)
            view = functools.partial(gather_block_kv,
                                     block_tables=block_tables)
        else:
            def write(buf, new):
                return ragged_cache_update(buf, new, lengths, n_valid)

            def view(buf):
                return buf
        # write each row's new k/v at its own cache length
        if kq_fmt is not None:
            # per-(position, head) scales: old codes keep their own scale
            k_codes, ks_new = quantize(k, kq_fmt, axis=3)
            v_codes, vs_new = quantize(v, kq_fmt, axis=3)
            kc = write(kc, k_codes)
            vc = write(vc, v_codes)
            k_scale = write(k_scale, ks_new)
            v_scale = write(v_scale, vs_new)
        else:
            kc = write(kc, k)
            vc = write(vc, v)
        new_cache = (kc, vc, k_scale, v_scale)
        int_attn = bool(kq_fmt is not None
                        and getattr(policy, "int_attention", False))
        if paged and s == 1 and pool_tp == 1:
            # fused paged decode: the kernel walks the block table over the
            # pool in HBM directly (dequant + masking + online softmax in
            # one launch) — no gathered contiguous view is materialised.
            # Bit-exact vs the gather path below on every backend; chunked
            # prefill (s > 1) keeps the gather path, the HBM win targets
            # the per-token decode hot loop.
            be = _resolve_backend(policy.backend if policy else None)
            out = _dispatch().paged_attention(
                q, kc, vc, k_scale, v_scale, block_tables, policy, be,
                lengths=lengths, kv_valid=kv_valid, positions=positions,
                fmt=kq_fmt, int_attention=int_attn)
        elif int_attn:
            # fully-integer FxP attention (§Perf): score/AV dots run on
            # int8 codes directly — no bf16 dequantized cache copy is
            # ever materialised; scales fold into q and the softmax
            # weights (the Flex-PE SIMD MAC applied to attention).
            out = int8_decode_attention(
                q, view(kc), view(vc), view(k_scale), view(v_scale),
                kq_fmt, policy, positions=positions,
                kv_valid_len=kv_valid)
        else:
            if kq_fmt is not None:
                k_full = dequantize(view(kc), view(k_scale), jnp.bfloat16)
                v_full = dequantize(view(vc), view(v_scale), jnp.bfloat16)
            else:
                k_full, v_full = view(kc), view(vc)
            out = chunked_attention(q, k_full, v_full, causal=True,
                                    q_offset=lengths, policy=policy,
                                    kv_valid_len=kv_valid)

    out = out.reshape(b, s, h * hd)
    return qmatmul(out, p["wo"], policy), new_cache


def init_kv_cache(cfg, batch, max_len, policy=None, n_layers=None,
                  dtype=jnp.bfloat16, block_size=None, num_blocks=None):
    """Pre-allocated per-layer KV cache, stacked on a leading layer axis.

    Contiguous (default): one [batch, max_len] window per slot. Paged
    (`block_size` set): a global block pool [num_blocks, block_size] with
    no batch axis — rows address it through a per-slot block table (see
    `model.init_cache`), so HBM scales with tokens actually cached, not
    batch * worst-case length. `num_blocks` defaults to byte parity with
    the contiguous layout."""
    n_layers = n_layers if n_layers is not None else cfg.n_layers
    kvh, hd = cfg.n_kv_heads, cfg.head_dim
    quant = policy is not None and policy.kv_cache is not None
    dt = jnp.int8 if quant else dtype
    if block_size is not None:
        nb = (num_blocks if num_blocks is not None
              else batch * -(-max_len // block_size))
        kc = jnp.zeros((n_layers, nb, block_size, kvh, hd), dt)
        vc = jnp.zeros((n_layers, nb, block_size, kvh, hd), dt)
        sshape = ((n_layers, nb, block_size, kvh, 1) if quant
                  else (n_layers, 1, 1, kvh, 1))
        ks = jnp.full(sshape, 1e-6, jnp.float32)
        vs = jnp.full(sshape, 1e-6, jnp.float32)
        return {"k": kc, "v": vc, "k_scale": ks, "v_scale": vs}
    kc = jnp.zeros((n_layers, batch, max_len, kvh, hd), dt)
    vc = jnp.zeros((n_layers, batch, max_len, kvh, hd), dt)
    slen = max_len if quant else 1
    ks = jnp.full((n_layers, batch, slen, kvh, 1), 1e-6, jnp.float32)
    vs = jnp.full((n_layers, batch, slen, kvh, 1), 1e-6, jnp.float32)
    return {"k": kc, "v": vc, "k_scale": ks, "v_scale": vs}


# ---------------------------------------------------------------------------
# MLP (dense FFN, GLU family)
# ---------------------------------------------------------------------------

def mlp_init(key, d, ff, act, dtype=jnp.bfloat16):
    ks = jax.random.split(key, 3)
    p = {"w1": dense_init(ks[0], d, ff, dtype),
         "w2": dense_init(ks[1], ff, d, dtype)}
    if act in ("silu", "swiglu"):  # gated
        p["w3"] = dense_init(ks[2], d, ff, dtype)
    return p


def mlp_axes(act):
    ax = {"w1": ("embed", "ff"), "w2": ("ff", "embed")}
    if act in ("silu", "swiglu"):
        ax["w3"] = ("embed", "ff")
    return ax


def mlp(p, x, act, policy=None):
    """FFN with the Flex-PE MAC→AF pipeline: under a policy, the activation
    is passed to qmatmul as a fused epilogue (one kernel launch on the
    pallas backend; `policy.act` post-op on reference)."""
    if "w3" in p:  # SwiGLU
        if policy:
            gate = qmatmul(x, p["w1"], policy, af="silu")
        else:
            gate = jax.nn.silu(qmatmul(x, p["w1"], policy))
        h = gate * qmatmul(x, p["w3"], policy)
    else:
        if policy:
            h = qmatmul(x, p["w1"], policy,
                        af=act if act in ("gelu", "relu", "tanh",
                                          "sigmoid") else "gelu")
        else:
            h = {"gelu": jax.nn.gelu, "relu": jax.nn.relu,
                 "tanh": jnp.tanh, "sigmoid": jax.nn.sigmoid}[act](
                     qmatmul(x, p["w1"], policy))
    return qmatmul(h, p["w2"], policy)
