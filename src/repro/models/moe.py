"""Mixture-of-Experts layer — top-k capacity routing (GShard-style),
fine-grained shared+routed experts (DeepSeekMoE), EP-shardable.

Dispatch is group-local: tokens are viewed as [G, Sg, D] groups (G aligns
with the data-parallel sharding so dispatch one-hots stay device-local and
expert assignment crosses the mesh only through the expert-sharded einsums,
which GSPMD lowers to all-to-all / all-gather on the `model` axis).

The router softmax is a Flex-PE call site: with a CORDIC policy the gate
probabilities run through the paper's HR-exp + LV-divide datapath
(n_experts-way softmax — the classification-sized regime the paper's
5-stage LV Pareto point was designed for).
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from ..core.precision import PrecisionPolicy, qeinsum, qmatmul
from .layers import dense_init

CAPACITY_FACTOR = 1.25


def moe_init(key, cfg, dtype=jnp.bfloat16):
    d, e, ff = cfg.d_model, cfg.n_experts, cfg.expert_ff or cfg.d_ff
    ks = jax.random.split(key, 5)
    p = {
        "router": dense_init(ks[0], d, e, jnp.float32),
        "w1": (jax.random.normal(ks[1], (e, d, ff), jnp.float32)
               / math.sqrt(d)).astype(dtype),
        "w3": (jax.random.normal(ks[2], (e, d, ff), jnp.float32)
               / math.sqrt(d)).astype(dtype),
        "w2": (jax.random.normal(ks[3], (e, ff, d), jnp.float32)
               / math.sqrt(ff)).astype(dtype),
    }
    if cfg.n_shared_experts:
        sff = cfg.n_shared_experts * (cfg.expert_ff or cfg.d_ff)
        kk = jax.random.split(ks[4], 3)
        p["shared"] = {"w1": dense_init(kk[0], d, sff, dtype),
                       "w3": dense_init(kk[1], d, sff, dtype),
                       "w2": dense_init(kk[2], sff, d, dtype)}
    return p


def moe_axes(cfg):
    ax = {"router": ("embed", "expert_dim"),
          "w1": ("expert", "embed", "ff"),
          "w3": ("expert", "embed", "ff"),
          "w2": ("expert", "ff", "embed")}
    if cfg.n_shared_experts:
        ax["shared"] = {"w1": ("embed", "ff"), "w3": ("embed", "ff"),
                        "w2": ("ff", "embed")}
    return ax


def _act(h, act, policy):
    if policy is not None and policy.af is not None:
        return policy.act(h, "silu" if act == "silu" else "gelu")
    return jax.nn.silu(h) if act == "silu" else jax.nn.gelu(h)


def moe_ffn(p, x, cfg, policy: Optional[PrecisionPolicy] = None,
            n_groups: int = 0, dropless: bool = False, shard=None):
    """x: [B, S, D] -> ([B, S, D], aux_metrics)."""
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    tokens = b * s
    # ~512-token groups: dispatch buffers stay O(tokens * cap_per_512)
    # while the G axis keeps the data-parallel sharding of the batch.
    g = n_groups or max(1, tokens // 512)
    while tokens % g:
        g -= 1
    sg = tokens // g
    xt = x.reshape(g, sg, d)
    if shard is not None:
        xt = shard.constraint(xt, None, None)  # G carries dp

    logits = qmatmul(xt.astype(jnp.float32), p["router"], None)  # [G,Sg,E]
    if policy is not None and policy.attn_softmax == "cordic":
        probs = policy.softmax(logits, axis=-1)
    else:
        probs = jax.nn.softmax(logits, axis=-1)

    gate_vals, gate_idx = jax.lax.top_k(probs, k)                # [G,Sg,k]
    gate_vals = gate_vals / (jnp.sum(gate_vals, -1, keepdims=True) + 1e-9)

    if dropless:
        cap = sg * k          # worst case: every token routes to one expert
    else:
        cap = int(max(k * sg / e * CAPACITY_FACTOR, 4))
    # position of each (token, choice) within its expert queue
    onehot = jax.nn.one_hot(gate_idx, e, dtype=jnp.int32)        # [G,Sg,k,E]
    flat = onehot.reshape(g, sg * k, e)
    pos = jnp.cumsum(flat, axis=1) - 1                           # [G,Sg*k,E]
    pos = (pos * flat).sum(-1).reshape(g, sg, k)                 # queue slot
    keep = pos < cap
    gate_vals = gate_vals * keep

    # index-based dispatch (zero-FLOP scatter; one-hot einsum dispatch costs
    # G*Sg*E*cap*D flops — 10-100x the expert compute at these sizes)
    slot = jnp.where(keep, gate_idx * cap + pos, e * cap)        # [G,Sg,k]
    slot_flat = slot.reshape(g, sg * k)

    def _dispatch(slots_g, x_g):
        buf = jnp.zeros((e * cap, d), x.dtype)
        src = jnp.repeat(x_g, k, axis=0)                         # [Sg*k, D]
        return buf.at[slots_g].add(src, mode="drop")

    # GSPMD cannot partition a vmapped scatter/gather batch dim on the
    # 3-axis mesh (it replicates the [G, E*cap, D] operand — 50-100 GB at
    # prefill scale); run dispatch/combine device-LOCAL over dp via
    # shard_map when G divides the dp axes.
    smap = None
    if shard is not None:
        from jax.sharding import PartitionSpec as _P
        dpx = shard.dp_axes
        dp_size = 1
        for a in dpx:
            dp_size *= shard.mesh.shape[a]
        if g % dp_size == 0:
            def smap(fn, *args):
                def spec(r):
                    return _P(dpx, *([None] * (r - 1)))
                return jax.shard_map(
                    jax.vmap(fn), mesh=shard.mesh,
                    in_specs=tuple(spec(a.ndim) for a in args),
                    out_specs=spec(3), check_vma=False)(*args)

    if smap is not None:
        xe = smap(_dispatch, slot_flat, xt)                      # [G,E*cap,D]
    else:
        xe = jax.vmap(_dispatch)(slot_flat, xt)
    xe = xe.reshape(g, e, cap, d)
    # G carries the dp sharding; E carries EP when divisible, else the
    # expert ff dim carries TP — keep the 4D expert tensors sharded or the
    # partitioner replicates G (20 GB/device blowups at grok scale).
    ep = shard is not None and e % shard.mesh.shape["model"] == 0
    if shard is not None:
        xe = shard.constraint(xe, "model" if ep else None, None, None)
    h = qeinsum("gecd,edf->gecf", xe, p["w1"], policy)
    if shard is not None:
        h = shard.constraint(h, "model" if ep else None, None,
                             None if ep else "model")
    h = _act(h, cfg.act, policy)
    if "w3" in p and cfg.act == "silu":
        h = h * qeinsum("gecd,edf->gecf", xe, p["w3"], policy)
    ye = qeinsum("gecf,efd->gecd", h, p["w2"], policy)           # [G,E,cap,D]
    if shard is not None:
        ye = shard.constraint(ye, "model" if ep else None, None, None)

    def _combine(slots_g, gates_g, ye_g):
        ye_flat = ye_g.reshape(e * cap, d)
        picked = ye_flat.at[slots_g].get(mode="fill", fill_value=0)
        return (picked.reshape(sg, k, d)
                * gates_g.reshape(sg, k, 1).astype(ye_flat.dtype)).sum(1)

    if smap is not None:
        ye_in = shard.constraint(ye.reshape(g, e * cap, d), None, None)
        y = smap(_combine, slot_flat, gate_vals,
                 ye_in.reshape(g, e, cap, d))                    # [G,Sg,D]
    else:
        y = jax.vmap(_combine)(slot_flat, gate_vals, ye)         # [G,Sg,D]

    if "shared" in p:
        sh = p["shared"]
        hs = qmatmul(xt, sh["w1"], policy)
        hs = _act(hs, cfg.act, policy)
        if cfg.act == "silu":
            hs = hs * qmatmul(xt, sh["w3"], policy)
        y = y + qmatmul(hs, sh["w2"], policy)

    # load-balance aux loss (Switch): E * sum_e f_e * p_e
    frac = onehot.sum(2).mean(1).astype(jnp.float32)             # [G,E]
    pmean = probs.mean(1)
    aux = e * jnp.mean(jnp.sum(frac * pmean, -1))
    return y.reshape(b, s, d), {"aux_loss": aux,
                                "dropped": 1.0 - jnp.mean(keep)}
