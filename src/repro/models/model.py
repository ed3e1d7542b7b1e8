"""Top-level model assembly: every assigned architecture behind one API.

  init_params(cfg, key)           -> param tree (layers stacked for scan)
  param_axes(cfg)                 -> logical-axis tree (same structure)
  forward(cfg, params, batch, ..) -> logits          (train / prefill)
  decode_step(cfg, params, ...)   -> logits, cache'  (serving)
  loss_fn(cfg, params, batch, ..) -> scalar loss + metrics

Layer stacks run under jax.lax.scan with remat (per-layer activation
checkpointing): compile time and HLO size are depth-independent, and the
backward pass recomputes block activations instead of storing them —
mandatory at train_4k production sizes.

Serving accepts quantize-once params: `core.qtensor.quantize_params`
replaces matmul-weight leaves with QuantizedTensor (codes + per-channel
scale, same leading layer axis), which slice through the block scans like
any other leaf and hit the packed-int Pallas kernels when
`policy.backend` is 'pallas'/'pallas-interpret'/'auto'. Embeddings (gather
path, possibly tied to the LM head) stay float.
"""
from __future__ import annotations

import functools
import inspect
from typing import Optional

import jax
import jax.numpy as jnp

from ..core.precision import PrecisionPolicy, qmatmul
from . import moe as moe_lib
from . import ssm as ssm_lib
from .layers import (apply_norm, attention, attn_axes, attn_init, dense_init,
                     init_kv_cache, mlp, mlp_axes, mlp_init, norm_axes,
                     norm_init)

# When True, layer scans fully unroll. Used by the dry-run's cost
# calibration: XLA cost_analysis counts while-loop bodies ONCE (not x trip
# count), so roofline FLOPs/bytes/collectives are extracted from small
# unrolled lowers and extrapolated linearly in depth.
SCAN_UNROLL = False


def _scan(body, init, xs):
    return jax.lax.scan(body, init, xs, unroll=True if SCAN_UNROLL else 1)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _block_init(key, cfg, dtype):
    ks = jax.random.split(key, 4)
    p = {}
    if cfg.family == "ssm" or (cfg.family == "hybrid"):
        p["ssm_norm"] = norm_init(cfg.d_model, cfg.norm, dtype)
        p["ssm"] = ssm_lib.ssm_init(ks[0], cfg, dtype)
        if cfg.family == "ssm":
            return p
        return p  # hybrid blocks are ssm; shared attn lives at top level
    p["attn_norm"] = norm_init(cfg.d_model, cfg.norm, dtype)
    p["attn"] = attn_init(ks[0], cfg, dtype)
    p["mlp_norm"] = norm_init(cfg.d_model, cfg.norm, dtype)
    if cfg.family == "moe":
        p["moe"] = moe_lib.moe_init(ks[1], cfg, dtype)
    else:
        p["mlp"] = mlp_init(ks[1], cfg.d_model, cfg.d_ff, cfg.act, dtype)
    return p


def _block_axes(cfg):
    def stack(ax):
        return jax.tree.map(lambda t: ("layers",) + t, ax,
                            is_leaf=lambda x: isinstance(x, tuple))
    p = {}
    if cfg.family in ("ssm", "hybrid"):
        p["ssm_norm"] = norm_axes(cfg.norm)
        p["ssm"] = ssm_lib.ssm_axes(cfg)
        return stack(p)
    p["attn_norm"] = norm_axes(cfg.norm)
    p["attn"] = attn_axes(cfg)
    p["mlp_norm"] = norm_axes(cfg.norm)
    if cfg.family == "moe":
        p["moe"] = moe_lib.moe_axes(cfg)
    else:
        p["mlp"] = mlp_axes(cfg.act)
    return stack(p)


def init_params(cfg, key, dtype=jnp.bfloat16):
    ks = jax.random.split(key, 8)
    params = {}
    if cfg.input_mode == "tokens":
        params["embed"] = (jax.random.normal(ks[0],
                                             (cfg.padded_vocab, cfg.d_model),
                                             jnp.float32) * 0.02).astype(dtype)
    blocks = jax.vmap(lambda k: _block_init(k, cfg, dtype))(
        jax.random.split(ks[1], cfg.n_layers))
    params["blocks"] = blocks
    params["final_norm"] = norm_init(cfg.d_model, cfg.norm, dtype)
    out_dim = cfg.padded_vocab * max(cfg.n_codebooks, 1)
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(ks[2], cfg.d_model, out_dim, dtype,
                                       scale=0.02)
    if cfg.family == "hybrid":
        # one shared attention+MLP block (Zamba2), applied every attn_every
        # ssm blocks with [x, x0] concat -> proj input
        params["shared_attn"] = {
            "in_proj": dense_init(ks[3], 2 * cfg.d_model, cfg.d_model, dtype),
            "attn_norm": norm_init(cfg.d_model, cfg.norm, dtype),
            "attn": attn_init(ks[4], cfg, dtype),
            "mlp_norm": norm_init(cfg.d_model, cfg.norm, dtype),
            "mlp": mlp_init(ks[5], cfg.d_model, cfg.d_ff, cfg.act, dtype),
        }
    return params


def param_axes(cfg):
    axes = {}
    if cfg.input_mode == "tokens":
        axes["embed"] = ("vocab", "embed")
    axes["blocks"] = _block_axes(cfg)
    axes["final_norm"] = norm_axes(cfg.norm)
    if not cfg.tie_embeddings:
        axes["lm_head"] = ("embed", "vocab")
    if cfg.family == "hybrid":
        axes["shared_attn"] = {
            "in_proj": ("embed", "embed2"),
            "attn_norm": norm_axes(cfg.norm),
            "attn": attn_axes(cfg),
            "mlp_norm": norm_axes(cfg.norm),
            "mlp": mlp_axes(cfg.act),
        }
    return axes


# ---------------------------------------------------------------------------
# blocks
# ---------------------------------------------------------------------------

def _tf_block(bp, x, cfg, positions, policy, shard=None):
    xin = apply_norm(x, bp["attn_norm"], cfg.norm)
    if (shard is not None and policy is not None
            and policy.act_comm == "fxp8"):
        # attention needs the full sequence: gather the seq-sharded
        # residual through the FxP8-compressed collective (§Perf)
        xin = shard.gather_seq_compressed(xin, policy.act_comm and "fxp8")
    h, _ = attention(bp["attn"], xin,
                     cfg, positions=positions, policy=policy)
    if shard is not None and policy is not None and policy.seq_outputs:
        h = shard.seq(h)   # partial sums reduce-scatter (not all-reduce)
    x = x + h
    if shard is not None:
        x = shard.seq(x)
    xin = apply_norm(x, bp["mlp_norm"], cfg.norm)
    if cfg.family == "moe":
        y, aux = moe_lib.moe_ffn(bp["moe"], xin, cfg, policy, shard=shard)
    else:
        y, aux = mlp(bp["mlp"], xin, cfg.act, policy), {"aux_loss": 0.0}
    if shard is not None and policy is not None and policy.seq_outputs:
        y = shard.seq(y)
    x = x + y
    if shard is not None:
        x = shard.seq(x)
    return x, aux


def _shared_attn_block(sp, x, x0, cfg, positions, policy):
    xin = qmatmul(jnp.concatenate([x, x0], axis=-1), sp["in_proj"], policy)
    h, _ = attention(sp["attn"], apply_norm(xin, sp["attn_norm"], cfg.norm),
                     cfg, positions=positions, policy=policy)
    x = x + h
    y = mlp(sp["mlp"], apply_norm(x, sp["mlp_norm"], cfg.norm), cfg.act,
            policy)
    return x + y


# ---------------------------------------------------------------------------
# forward (train / prefill)
# ---------------------------------------------------------------------------

def _ckpt(fn, remat, remat_policy):
    if not remat:
        return fn
    if remat_policy == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    return jax.checkpoint(fn)


def _on_shard_mesh(fn):
    """Trace `fn` inside its `shard` argument's mesh, set as JAX's
    abstract-mesh context. The pallas dispatch reads that context
    (`kernels.dispatch._replicated`): Mosaic kernels cannot be
    partitioned automatically, so under a multi-device mesh each runs
    inside `jax.shard_map`. Every model entry point that takes `shard`
    carries this decorator."""
    sig = inspect.signature(fn)

    @functools.wraps(fn)
    def wrapped(*args, **kwargs):
        shard = sig.bind(*args, **kwargs).arguments.get("shard")
        if shard is None:
            return fn(*args, **kwargs)
        with jax.sharding.use_abstract_mesh(shard.mesh.abstract_mesh):
            return fn(*args, **kwargs)
    return wrapped


@_on_shard_mesh
def forward(cfg, params, batch, policy: Optional[PrecisionPolicy] = None,
            shard=None, remat: bool = True, last_only: bool = False,
            remat_policy: str = "full"):
    """batch: {'tokens': [B,S]} or {'embeds': [B,S,D]} -> logits [B,S,V*].
    last_only=True slices the final position BEFORE the lm_head (serving
    prefill: avoids materialising [B,S,V])."""
    if cfg.input_mode == "tokens":
        x = params["embed"][batch["tokens"]]
    else:
        x = batch["embeds"]
    b, s = x.shape[0], x.shape[1]
    if shard is not None:
        x = shard.seq(x)
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))

    aux_total = 0.0
    if cfg.family in ("dense", "moe", "vlm", "audio"):
        def body(carry, bp):
            x, aux = carry
            x2, a = _tf_block(bp, x, cfg, positions, policy, shard)
            return (x2, aux + a["aux_loss"]), None
        body_fn = _ckpt(body, remat, remat_policy)
        (x, aux_total), _ = _scan(body_fn, (x, 0.0), params["blocks"])
    elif cfg.family == "ssm":
        def body(x, bp):
            h, _ = ssm_lib.mamba2_layer(
                bp["ssm"], apply_norm(x, bp["ssm_norm"], cfg.norm), cfg,
                policy)
            x = x + h
            if shard is not None:
                x = shard.seq(x)
            return x, None
        body_fn = _ckpt(body, remat, remat_policy)
        x, _ = _scan(body_fn, x, params["blocks"])
    elif cfg.family == "hybrid":
        x0 = x
        per = cfg.attn_every
        n_groups = cfg.n_layers // per
        rest = cfg.n_layers - n_groups * per
        grouped = jax.tree.map(
            lambda a: a[: n_groups * per].reshape(
                (n_groups, per) + a.shape[1:]),
            params["blocks"])
        tail = jax.tree.map(lambda a: a[n_groups * per:], params["blocks"])

        def ssm_body(x, bp):
            h, _ = ssm_lib.mamba2_layer(
                bp["ssm"], apply_norm(x, bp["ssm_norm"], cfg.norm), cfg,
                policy)
            return x + h, None

        ssm_body_fn = _ckpt(ssm_body, remat, remat_policy)

        def group_body(x, gp):
            x, _ = _scan(ssm_body_fn, x, gp)
            x = _shared_attn_block(params["shared_attn"], x, x0, cfg,
                                   positions, policy)
            if shard is not None:
                x = shard.seq(x)
            return x, None

        x, _ = _scan(group_body, x, grouped)
        if rest:
            x, _ = _scan(ssm_body_fn, x, tail)
    else:
        raise ValueError(cfg.family)

    x = apply_norm(x, params["final_norm"], cfg.norm)
    if last_only:
        x = x[:, -1:, :]
    head = (params["embed"].T if cfg.tie_embeddings else params["lm_head"])
    logits = qmatmul(x, head, policy)
    if shard is not None:
        logits = shard.constraint(logits, None, "model")
    return logits, {"aux_loss": aux_total}


# ---------------------------------------------------------------------------
# loss
# ---------------------------------------------------------------------------

def loss_fn(cfg, params, batch, policy=None, shard=None, remat=True,
            remat_policy="full"):
    logits, aux = forward(cfg, params, batch, policy, shard, remat,
                          remat_policy=remat_policy)
    labels = batch["labels"]
    lf = logits.astype(jnp.float32)
    if cfg.n_codebooks:
        b, s, _ = lf.shape
        lf = lf.reshape(b, s, cfg.n_codebooks, cfg.padded_vocab)
    lse = jax.nn.logsumexp(lf, axis=-1)
    gold = jnp.take_along_axis(lf, labels[..., None], axis=-1)[..., 0]
    nll = jnp.mean(lse - gold)
    z_loss = 1e-4 * jnp.mean(lse ** 2)
    moe_w = 0.01 if cfg.family == "moe" else 0.0
    loss = nll + z_loss + moe_w * aux["aux_loss"] / max(cfg.n_layers, 1)
    return loss, {"nll": nll, "aux_loss": aux["aux_loss"]}


# ---------------------------------------------------------------------------
# decode
# ---------------------------------------------------------------------------

def init_cache(cfg, batch, max_len, policy=None, dtype=jnp.bfloat16,
               kv_block_size=None, kv_blocks=None):
    """Serving cache for one decode stream set.

    `cache["lengths"]` is a per-request [batch] int32 vector — every row
    prefills, decodes, and finishes independently (ragged continuous
    batching); there is no batch-wide position scalar.

    `kv_block_size` switches attention families to the paged layout: KV
    leaves become a global block pool [L, kv_blocks, block_size, KV, hd]
    addressed through `cache["block_tables"]` [batch, MB] (MB = blocks
    needed to cover max_len). Unallocated table entries hold the sentinel
    NB (one past the pool): gathers fill them with exact zeros and the
    fused paged-attention kernel zeroes their staged blocks, so the
    "every such position is masked" invariant holds by construction
    rather than by reading some live block's data. SSM state is a dense
    per-slot recurrent carry either way (there is no sequence axis to
    page)."""
    cache = {}
    if kv_blocks is not None and kv_block_size is None:
        raise ValueError("kv_blocks requires kv_block_size (a pool size "
                         "only makes sense for the paged layout)")
    paged = kv_block_size is not None
    if cfg.family in ("dense", "moe", "vlm", "audio"):
        cache["kv"] = init_kv_cache(cfg, batch, max_len, policy, dtype=dtype,
                                    block_size=kv_block_size,
                                    num_blocks=kv_blocks)
    elif cfg.family == "ssm":
        paged = False
        st, cv = ssm_lib.init_ssm_state(cfg, batch)
        cache["ssm"] = jax.tree.map(
            lambda a: jnp.broadcast_to(a, (cfg.n_layers,) + a.shape), (st, cv))
    elif cfg.family == "hybrid":
        st, cv = ssm_lib.init_ssm_state(cfg, batch)
        cache["ssm"] = jax.tree.map(
            lambda a: jnp.broadcast_to(a, (cfg.n_layers,) + a.shape), (st, cv))
        # one KV cache per shared-attention application
        n_apps = cfg.n_layers // cfg.attn_every
        cache["kv"] = init_kv_cache(cfg, batch, max_len, policy,
                                    n_layers=n_apps, dtype=dtype,
                                    block_size=kv_block_size,
                                    num_blocks=kv_blocks)
    if paged:
        mb = -(-max_len // kv_block_size)
        nb = int(cache["kv"]["k"].shape[1])
        cache["block_tables"] = jnp.full((batch, mb), nb, jnp.int32)
    cache["lengths"] = jnp.zeros((batch,), jnp.int32)
    return cache


def _cache_batch_axis(key: str) -> int:
    # every family cache leaf is layer-stacked [L, B, ...] except the
    # per-request length and block-table vectors [B(, MB)]
    return 0 if key in ("lengths", "block_tables") else 1


def slice_cache_rows(cache, start, size: int = 1):
    """Per-request cache window: rows [start, start+size) of every leaf's
    batch axis (serving engine: run a step on one slot's row only). Paged
    KV pools have no batch axis and are shared across rows: they pass
    through whole, addressed by the sliced block-table rows."""
    paged = "block_tables" in cache
    return {k: v if (paged and k == "kv") else jax.tree.map(
        lambda a, ax=_cache_batch_axis(k): jax.lax.dynamic_slice_in_dim(
            a, start, size, axis=ax), v)
        for k, v in cache.items()}


def update_cache_rows(cache, sub, start):
    """Write a `slice_cache_rows` window back at row `start`. A paged KV
    pool is taken from `sub` wholesale — its scatter writes only touched
    the blocks owned by the sliced rows."""
    paged = "block_tables" in cache
    return {k: sub[k] if (paged and k == "kv") else jax.tree.map(
        lambda a, u, ax=_cache_batch_axis(k):
        jax.lax.dynamic_update_slice_in_dim(a, u.astype(a.dtype), start,
                                            axis=ax), v, sub[k])
        for k, v in cache.items()}


@functools.partial(jax.jit, donate_argnums=(0,))
def copy_pool_blocks(cache, src, dst):
    """Fork physical KV pool blocks: pool[:, dst[i]] <- pool[:, src[i]] for
    every paged KV leaf (codes AND per-position scales), all layers in one
    dispatch. This is the serving engine's copy-on-write primitive: a slot
    that must append into a block whose refcount > 1 first copies it to a
    private block, so the writer diverges while every other reader of the
    shared block sees bit-identical KV.

    `src`/`dst` are equal-length int vectors of block ids. Leaves without
    a pool axis (bf16-cache scale stubs [L, 1, 1, KV, 1], lengths, block
    tables, SSM state) pass through untouched. The cache argument is
    donated — on device the copy happens in place in the pool."""
    kv = cache["kv"]
    nb = kv["k"].shape[1]
    src = jnp.asarray(src, jnp.int32)
    dst = jnp.asarray(dst, jnp.int32)
    new_kv = {
        name: (leaf.at[:, dst].set(leaf[:, src])
               if leaf.ndim == 5 and leaf.shape[1] == nb else leaf)
        for name, leaf in kv.items()
    }
    out = dict(cache)
    out["kv"] = new_kv
    return out


@_on_shard_mesh
def decode_step(cfg, params, cache, tokens_or_embeds,
                policy: Optional[PrecisionPolicy] = None, shard=None,
                n_valid=None, last_only: bool = False):
    """Serving step: tokens [B,S] (or embeds [B,S,D]) -> logits, cache'.

    S = 1 is plain decode; S > 1 is a chunked-prefill block (causal within
    the block, bulk KV/state write) — both through the same code. Each
    batch row continues from its own `cache["lengths"][b]`; `n_valid` [B]
    says how many of the S tokens are real per row (defaults to all S), so
    one call can mix rows that prefill a chunk, decode one token, or idle
    (n_valid=0 rows leave their cache row bit-untouched). A row's length
    need not start at 0: prefix-cached admission sets it to the matched
    block boundary over a pre-populated block table, and the first
    prefill chunk attends to the shared KV exactly as if this request had
    written it (positions, masks, and scales are all driven by
    `lengths`). `last_only=True` gathers each row's last *valid* position
    before the lm_head (serving: avoids materialising [B,S,V])."""
    if cfg.input_mode == "tokens":
        x = params["embed"][tokens_or_embeds]
    else:
        x = tokens_or_embeds
    b, s = x.shape[0], x.shape[1]
    lengths = cache["lengths"]
    if n_valid is None:
        n_valid = jnp.full((b,), s, jnp.int32)
    n_valid = n_valid.astype(jnp.int32)
    positions = lengths[:, None] + jnp.arange(s, dtype=jnp.int32)[None, :]
    new_cache = dict(cache)
    block_tables = cache.get("block_tables")
    # block-pool shard count: >1 only when a model-parallel mesh actually
    # partitions the pool's block axis (NB divides the axis — the same
    # divisibility rule cache_shardings applies), in which case attention
    # drops the fused kernel for the shard-exact gather path
    pool_tp = 1
    if block_tables is not None and shard is not None:
        tp = (int(shard.mesh.shape["model"])
              if "model" in shard.mesh.axis_names else 1)
        nb = int(cache["kv"]["k"].shape[1])
        pool_tp = tp if tp > 1 and nb % tp == 0 else 1

    if cfg.family in ("dense", "moe", "vlm", "audio"):
        kv = cache["kv"]

        def body(x, xs):
            bp, kc, vc, ks, vs = xs
            h, new_kv = attention(
                bp["attn"], apply_norm(x, bp["attn_norm"], cfg.norm), cfg,
                positions=positions, policy=policy,
                cache=(kc, vc, ks, vs), lengths=lengths, n_valid=n_valid,
                block_tables=block_tables, pool_tp=pool_tp)
            x = x + h
            xin = apply_norm(x, bp["mlp_norm"], cfg.norm)
            if cfg.family == "moe":
                y, _ = moe_lib.moe_ffn(bp["moe"], xin, cfg, policy,
                                       dropless=True)
            else:
                y = mlp(bp["mlp"], xin, cfg.act, policy)
            return x + y, new_kv

        x, (kcs, vcs, kss, vss) = _scan(
            body, x, (params["blocks"], kv["k"], kv["v"],
                      kv["k_scale"], kv["v_scale"]))
        new_cache["kv"] = {"k": kcs, "v": vcs, "k_scale": kss, "v_scale": vss}
    elif cfg.family == "ssm":
        def body(x, xs):
            bp, st, cv = xs
            h, (st2, cv2) = ssm_lib.mamba2_layer(
                bp["ssm"], apply_norm(x, bp["ssm_norm"], cfg.norm), cfg,
                policy, state=st, conv_state=cv, n_valid=n_valid)
            return x + h, (st2, cv2)
        x, new_ssm = _scan(body, x, (params["blocks"],) + cache["ssm"])
        new_cache["ssm"] = new_ssm
    elif cfg.family == "hybrid":
        x0 = x
        per = cfg.attn_every
        kv = cache["kv"]

        def body(carry, xs):
            x, li = carry
            bp, st, cv = xs
            h, (st2, cv2) = ssm_lib.mamba2_layer(
                bp["ssm"], apply_norm(x, bp["ssm_norm"], cfg.norm), cfg,
                policy, state=st, conv_state=cv, n_valid=n_valid)
            return (x + h, li + 1), (st2, cv2)

        # interleave: scan ssm blocks in groups, shared attn between groups
        n_groups = cfg.n_layers // per
        rest = cfg.n_layers - n_groups * per
        ssm_tree = cache["ssm"]
        outs_st, outs_cv = [], []
        new_kvs = []
        li = 0
        for gidx in range(n_groups):
            gp = jax.tree.map(lambda a: a[li:li + per], params["blocks"])
            gst = jax.tree.map(lambda a: a[li:li + per], ssm_tree)
            (x, _), (st2, cv2) = _scan(body, (x, 0), (gp,) + gst)
            outs_st.append(st2)
            outs_cv.append(cv2)
            sp = params["shared_attn"]
            xin = qmatmul(jnp.concatenate([x, x0], axis=-1), sp["in_proj"],
                          policy)
            kvq = (kv["k"][gidx], kv["v"][gidx],
                   kv["k_scale"][gidx], kv["v_scale"][gidx])
            h, new_kv = attention(
                sp["attn"], apply_norm(xin, sp["attn_norm"], cfg.norm), cfg,
                positions=positions, policy=policy, cache=kvq,
                lengths=lengths, n_valid=n_valid,
                block_tables=block_tables, pool_tp=pool_tp)
            x = x + h
            x = x + mlp(sp["mlp"], apply_norm(x, sp["mlp_norm"], cfg.norm),
                        cfg.act, policy)
            new_kvs.append(new_kv)
            li += per
        if rest:
            gp = jax.tree.map(lambda a: a[li:], params["blocks"])
            gst = jax.tree.map(lambda a: a[li:], ssm_tree)
            (x, _), (st2, cv2) = _scan(body, (x, 0), (gp,) + gst)
            outs_st.append(st2)
            outs_cv.append(cv2)
        new_cache["ssm"] = (jnp.concatenate(outs_st),
                            jnp.concatenate(outs_cv))
        new_cache["kv"] = {
            "k": jnp.stack([t[0] for t in new_kvs]),
            "v": jnp.stack([t[1] for t in new_kvs]),
            "k_scale": jnp.stack([t[2] for t in new_kvs]),
            "v_scale": jnp.stack([t[3] for t in new_kvs]),
        }
    else:
        raise ValueError(cfg.family)

    x = apply_norm(x, params["final_norm"], cfg.norm)
    if last_only:
        idx = jnp.clip(n_valid - 1, 0, s - 1)
        x = jnp.take_along_axis(x, idx[:, None, None], axis=1)   # [B,1,D]
    head = (params["embed"].T if cfg.tie_embeddings else params["lm_head"])
    logits = qmatmul(x, head, policy)
    new_cache["lengths"] = lengths + n_valid
    return logits, new_cache
