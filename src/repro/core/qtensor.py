"""Quantize-once weight storage — the SIMD-packed serving format.

``QuantizedTensor`` is a pytree leaf-pair (integer codes + per-channel
scale) replacing a float matmul weight. Serving with it moves the *codes*
HBM→VMEM instead of re-fake-quantizing a bf16 tensor every step:

    FxP4  packed nibbles (via `core.simd.pack` int32 words)   8× fewer bytes
    FxP8  int8 codes                                          4× fewer bytes
    FxP16 int16 codes                                         2× fewer bytes
    (reductions vs. an fp32 master copy; 4×/2×/1× vs. bf16)

Codes are produced by `core.fxp.quantize` with a per-output-channel dynamic
scale (axis=-2 of a [K, N] weight), so dequant is `codes * scale[1, N]` —
the scale rides along the GEMM epilogue. Stacked layer weights [L, K, N]
(the `jax.lax.scan` layout of model blocks) quantize per (layer, channel).

`quantize_params` is the model-surgery pass: it walks a param tree and
replaces known matmul-weight leaves (wq/wk/wv/wo, w1/w2/w3, lm_head,
in_proj) with QuantizedTensor, leaving embeddings, norms, and biases float.
The result is scan-compatible: both leaves carry the same leading layer
axis, so block scans slice them together.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional

import jax
import jax.numpy as jnp

from .fxp import FORMATS, FxPFormat, amax_scale, code_dtype, quantize
from .simd import pack, unpack
from .tiers import TIERS, tier_index

__all__ = ["QuantizedTensor", "TieredWeights", "quantize_tensor",
           "quantize_params", "dequantize_params", "map_weight_leaves",
           "packed_bytes", "QUANT_PARAM_KEYS"]

#: Param-tree dict keys that hold matmul weights (consumed by `qmatmul`).
#: Embeddings (gather), norm weights, and biases stay float.
QUANT_PARAM_KEYS = frozenset(
    {"wq", "wk", "wv", "wo", "w1", "w2", "w3", "lm_head", "in_proj",
     "out_proj"})


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class QuantizedTensor:
    """Integer weight codes + per-channel scale (one FxP-quantized matrix).

    data:  codes in the narrowest int dtype ([.., K, N]), or — for packed
           FxP4 — `core.simd.pack` int32 words ([.., K, ceil(N/8)], the
           lane-packed SIMD storage; N padded to a lane multiple).
    scale: f32 per-output-channel scale, broadcastable [.., 1, N]
           (or [.., 1, 1] for per-tensor quantization).
    fmt_name: FxP format of the codes ('fxp4'...'fxp32'). Static.
    n:     logical output-feature count (un-padded last dim). Static.
    packed: whether `data` holds lane-packed int32 words. Static.
    """
    data: jax.Array
    scale: jax.Array
    fmt_name: str
    n: int
    packed: bool

    # -- pytree protocol (leaves slice through scans / tree.map) -----------
    def tree_flatten(self):
        return (self.data, self.scale), (self.fmt_name, self.n, self.packed)

    @classmethod
    def tree_unflatten(cls, aux, children):
        data, scale = children
        return cls(data, scale, *aux)

    # -- views -------------------------------------------------------------
    @property
    def fmt(self) -> FxPFormat:
        return FORMATS[self.fmt_name]

    @property
    def shape(self) -> tuple:
        """Logical (unpacked, unpadded) shape."""
        return tuple(self.data.shape[:-1]) + (self.n,)

    @property
    def ndim(self) -> int:
        return self.data.ndim

    @property
    def nbytes(self) -> int:
        """Bytes of weight storage actually moved HBM→VMEM per use."""
        return int(self.data.size * self.data.dtype.itemsize
                   + self.scale.size * self.scale.dtype.itemsize)

    @property
    def lane_granularity(self) -> int:
        """Smallest channel-count unit a last-dim (output-feature) shard
        may hold. Packed FxP4 stores `lanes_per_word` channels per int32
        word, so a tensor-parallel split of the packed dim is only valid
        when `n % (lane_granularity * shards) == 0` — whole words per
        shard, no pad nibbles straddling a shard boundary. Unpacked codes
        split at channel granularity (1)."""
        return self.fmt.lanes_per_word if self.packed else 1

    def codes(self) -> jax.Array:
        """Sign-extended integer codes [.., K, N] (unpacks FxP4 words)."""
        if not self.packed:
            return self.data
        return unpack(self.data, self.fmt, self.n)

    def dequantize(self, dtype=jnp.float32) -> jax.Array:
        """Materialise the float weight (reference backend / debugging)."""
        return (self.codes().astype(jnp.float32) * self.scale).astype(dtype)


def quantize_tensor(w: jax.Array, fmt_name: str, packed: Optional[bool] = None,
                    per_channel: bool = True,
                    scale: Optional[jax.Array] = None) -> QuantizedTensor:
    """Quantize a float weight [.., K, N] once, for serving-time reuse.

    `scale` overrides the dynamic per-channel scale — `TieredWeights`
    passes one derived from a shared amax so every tier's codes come off
    the identical grid `quantize_params` would have picked."""
    fmt = FORMATS[fmt_name]
    if packed is None:
        packed = fmt.bits == 4
    if packed and fmt.bits != 4:
        raise ValueError("lane-packed storage is FxP4-only "
                         f"(got {fmt_name})")
    axis = -2 if per_channel else (-2, -1)
    codes, scale = quantize(w, fmt, scale=scale, axis=axis)
    n = w.shape[-1]
    if packed:
        lanes = fmt.lanes_per_word  # 8 nibbles / int32 word
        pad = (-n) % lanes
        c32 = codes.astype(jnp.int32)
        if pad:
            c32 = jnp.pad(c32, [(0, 0)] * (c32.ndim - 1) + [(0, pad)])
        data = pack(c32, fmt)
    else:
        data = codes.astype(code_dtype(fmt))
    return QuantizedTensor(data, scale.astype(jnp.float32), fmt_name, n,
                           packed)


def _is_weight_leaf(v: Any) -> bool:
    # 2-D ([K, N]), scan-stacked 3-D ([L, K, N]), or stacked MoE expert
    # banks 4-D ([L, E, K, N] — per-(layer, expert, channel) scales,
    # consumed per-expert by `kernels.dispatch.expert_matmul`).
    return (isinstance(v, jax.Array) and v.ndim in (2, 3, 4)
            and jnp.issubdtype(v.dtype, jnp.floating))


def map_weight_leaves(params: Any, fn,
                      keys: frozenset = QUANT_PARAM_KEYS) -> Any:
    """Rebuild `params` with `fn` applied to every matmul-weight leaf.

    Walks nested dicts by key name; only float leaves with ndim >= 2 under a
    key in `keys` are converted (biases under e.g. 'bq' and 1-D norm scales
    pass through untouched). Works on scan-stacked [L, K, N] weights.
    """
    def walk(node):
        if isinstance(node, dict):
            return {k: fn(v) if k in keys and _is_weight_leaf(v) else walk(v)
                    for k, v in node.items()}
        if isinstance(node, (list, tuple)):
            return type(node)(walk(v) for v in node)
        return node

    return walk(params)


def quantize_params(params: Any, fmt_name: str, packed: Optional[bool] = None,
                    per_channel: bool = True,
                    keys: frozenset = QUANT_PARAM_KEYS) -> Any:
    """Model surgery: replace matmul-weight leaves with QuantizedTensor
    (see `map_weight_leaves` for which leaves convert)."""
    return map_weight_leaves(
        params, lambda w: quantize_tensor(w, fmt_name, packed=packed,
                                          per_channel=per_channel),
        keys=keys)


class TieredWeights:
    """Quantize-once weight banks for EVERY serving tier of one model.

    One float source-of-truth tree plus, per quantized ladder tier, a
    `quantize_params`-shaped view whose matmul weights are
    `QuantizedTensor` codes at that tier's bit width. The per-leaf
    dynamic-range reduction (`amax` over input channels — the expensive
    scan of the float weight) runs ONCE and is shared: each tier's scale
    is amax over its qmax (`amax_scale`), exactly what `quantize_params`
    computes per tier, so `for_tier(t)` is bitwise identical to
    independent surgery — a replica serving from a TieredWeights view
    decodes the same tokens as one quantized standalone. The 'bf16' tier
    serves the float source directly (no copy).

    Memory model: resident bytes = the float source + one code bank per
    quantized tier (FxP4 nibble-packed, FxP8 int8, FxP16 int16) + a
    shared-magnitude f32 scale per bank — `bytes_by_tier()` itemises it.
    This is the paper's SIMD storage story fleet-wide: a heterogeneous
    fleet serves N precision tiers from one weight load, not N model
    copies."""

    def __init__(self, params: Any, tiers, per_channel: bool = True,
                 keys: frozenset = QUANT_PARAM_KEYS):
        names = []
        for t in tiers:
            tier_index(t)                      # unknown tier -> ValueError
            if t not in names:
                names.append(t)
        if not names:
            raise ValueError("TieredWeights needs at least one tier")
        self.tier_names = tuple(names)
        self.source = params
        axis = -2 if per_channel else (-2, -1)
        amax_memo: dict = {}                   # id(leaf) -> shared amax

        def shared_amax(w):
            if id(w) not in amax_memo:
                amax = jnp.max(jnp.abs(w), axis=axis, keepdims=True)
                amax_memo[id(w)] = jnp.maximum(amax.astype(jnp.float32),
                                               1e-12)
            return amax_memo[id(w)]

        self._views = {}
        for t in names:
            bits = TIERS[t].bits
            if bits is None:
                self._views[t] = params
                continue
            fmt = FORMATS[t]
            self._views[t] = map_weight_leaves(
                params, lambda w, _fmt=fmt, _t=t: quantize_tensor(
                    w, _t, per_channel=per_channel,
                    scale=amax_scale(shared_amax(w), _fmt)),
                keys=keys)

    def __contains__(self, tier: str) -> bool:
        return tier in self._views

    def for_tier(self, tier: str) -> Any:
        """The param tree a replica pinned to `tier` serves from."""
        if tier not in self._views:
            raise ValueError(f"tier {tier!r} not in this TieredWeights "
                             f"(has {list(self.tier_names)})")
        return self._views[tier]

    def bytes_by_tier(self) -> dict:
        """Resident weight bytes per tier view ('bf16' counts the float
        source, which every quantized tier shares for free)."""
        out = {}
        for t in self.tier_names:
            if TIERS[t].bits is None:
                out[t] = sum(leaf.size * leaf.dtype.itemsize
                             for leaf in jax.tree.leaves(self.source))
            else:
                out[t] = sum(
                    leaf.nbytes for leaf in jax.tree.leaves(
                        self._views[t],
                        is_leaf=lambda v: isinstance(v, QuantizedTensor))
                    if isinstance(leaf, QuantizedTensor))
        return out


def dequantize_params(params: Any, dtype=jnp.bfloat16) -> Any:
    """Inverse surgery: materialise every QuantizedTensor back to float."""
    return jax.tree.map(
        lambda v: v.dequantize(dtype) if isinstance(v, QuantizedTensor) else v,
        params, is_leaf=lambda v: isinstance(v, QuantizedTensor))


def packed_bytes(params: Any) -> tuple[int, int]:
    """(quantized_bytes, fp32_equivalent_bytes) over QuantizedTensor leaves."""
    qb = fb = 0
    for leaf in jax.tree.leaves(
            params, is_leaf=lambda v: isinstance(v, QuantizedTensor)):
        if isinstance(leaf, QuantizedTensor):
            qb += leaf.nbytes
            # python ints: full-size stacked weights overflow int32
            fb += 4 * math.prod(leaf.shape)
    return qb, fb
