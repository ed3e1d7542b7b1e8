"""Logical-axis sharding rules (MaxText-style) for the production mesh.

Params carry logical axis names (trees built by models.*_axes); the rule
table maps logical -> mesh axes. Two presets:

  * tp-only:   weights sharded over `model` only (replicated over data) —
    fine for <= ~15B-param models at bf16.
  * fsdp:      additionally shards the non-tensor-parallel weight dim over
    `data` (ZeRO-3); required for grok-1-314b / deepseek-coder-33b training
    fits. All-gathers are inserted by GSPMD at use sites.

Activation specs: batch over (pod, data), model-parallel feature dims over
`model`. `kv_seq` shards decode KV caches along sequence over `model`
(split-KV decode) since GQA kv_heads (8) < model axis (16).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from ..core.qtensor import QuantizedTensor

# logical axis -> mesh axes, per preset
RULES_TP = {
    "vocab": "model", "qkv": "model", "kv_qkv": None, "heads": "model",
    "ff": "model", "expert": "model", "ssm_inner": "model",
    "ssm_heads": "model", "embed": None, "expert_dim": None,
    "layers": None, "conv": None, "stage": None,
}
# FSDP: embed (the non-TP dim of every big matrix) shards over data
RULES_FSDP = dict(RULES_TP, embed="data")
# Serving TP: the bit-exactness-preserving subset of RULES_TP. Sharding a
# float weight's contraction dim (or an activation dim a later float
# reduction crosses) changes float summation order, so tp>1 would no
# longer be token-identical to tp==1. Integer accumulation IS associative,
# which is why QuantizedTensor leaves shard freely under these rules
# (cross-shard K reductions all-reduce exact int32 partials) while float
# leaves replicate except the embedding table (a vocab-dim gather — also
# exact, and the tied lm_head it transposes into only shards the output
# dim). SSM inner/head dims stay replicated: the mamba2 recurrence mixes
# float contractions across them.
RULES_SERVE_TP = dict(RULES_TP, ssm_inner=None, ssm_heads=None)


@dataclasses.dataclass(frozen=True)
class MeshRules:
    mesh: Mesh
    fsdp: bool = False
    # serving preset: only exact-under-sharding params split (quantized
    # weights, the embedding gather) so multi-device decode stays
    # token-identical to single-device — see RULES_SERVE_TP
    serve: bool = False

    @property
    def rules(self):
        if self.serve:
            return RULES_SERVE_TP
        return RULES_FSDP if self.fsdp else RULES_TP

    @property
    def dp_axes(self):
        return (("pod", "data") if "pod" in self.mesh.axis_names
                else ("data",))

    def spec_for(self, logical_axes) -> P:
        if logical_axes is None:
            return P()
        return P(*(self.rules.get(a) for a in logical_axes))

    def sharding_for(self, logical_axes) -> NamedSharding:
        return NamedSharding(self.mesh, self.spec_for(logical_axes))

    def param_shardings(self, axes_tree, spec_tree=None):
        """Map a logical-axes tree -> NamedSharding tree (same structure).

        With `spec_tree` (arrays or ShapeDtypeStructs, same structure),
        any dim whose size does not divide the assigned mesh axis is
        replicated instead — the divisibility safety net. A
        `QuantizedTensor` spec leaf resolves to a QuantizedTensor of
        NamedShardings for (codes, scale) — structurally a valid sharding
        tree for both `jax.device_put` and jit `in_shardings` — with the
        packed-lane boundary guard (see `_qtensor_sharding`)."""
        def is_leaf(x):
            return isinstance(x, tuple) or x is None
        if spec_tree is None:
            return jax.tree.map(self.sharding_for, axes_tree, is_leaf=is_leaf)

        def resolve(axes, spec):
            if isinstance(spec, QuantizedTensor):
                return self._qtensor_sharding(axes, spec)
            if axes is None:
                return NamedSharding(self.mesh, P())
            if self.serve and axes != ("vocab", "embed"):
                # serving preset: float weights replicate — only the
                # embedding table (vocab-dim gather, exact under
                # sharding) and QuantizedTensor leaves split. See
                # RULES_SERVE_TP for why.
                return NamedSharding(self.mesh, P())
            names, used = [], set()
            for dim, a in zip(spec.shape, axes):
                m = self.rules.get(a)
                if m is not None and (dim % self.mesh.shape[m] != 0
                                      or m in used):
                    # divisibility/duplicate safety net: e.g. MoE experts
                    # take `model` (EP) -> expert ff dim falls back to
                    # replicated; grok's 8 experts < 16 -> EP off, ff TP on.
                    m = None
                if m is not None:
                    used.add(m)
                names.append(m)
            return NamedSharding(self.mesh, P(*names))

        return jax.tree.map(resolve, axes_tree, spec_tree, is_leaf=is_leaf)

    def _qtensor_sharding(self, axes, qt: QuantizedTensor):
        """Sharding pair for one quantized weight: codes sharded by the
        logical-axis rules, the per-channel scale sharded iff the codes'
        channel (last) dim is. The last dim additionally honours the
        packed-lane boundary: FxP4 stores `lane_granularity` channels per
        int32 word, so a model-parallel split must hand every shard whole
        words AND an equal slice of the un-padded logical channel count
        (`n % (size * lanes) == 0`); anything else replicates."""
        rep = NamedSharding(self.mesh, P())
        if axes is None:
            return QuantizedTensor(rep, rep, qt.fmt_name, qt.n, qt.packed)
        lanes = qt.lane_granularity
        names, used = [], set()
        nd = qt.data.ndim
        for i, (dim, a) in enumerate(zip(qt.data.shape, axes)):
            m = self.rules.get(a)
            if m is not None:
                size = self.mesh.shape[m]
                ok = dim % size == 0 and m not in used
                if i == nd - 1:
                    ok = ok and qt.n % (size * lanes) == 0
                if not ok:
                    m = None
            if m is not None:
                used.add(m)
            names.append(m)
        data_sh = NamedSharding(self.mesh, P(*names))
        snames = [None] * qt.scale.ndim
        if (names and names[-1] is not None
                and qt.scale.shape[-1] % self.mesh.shape[names[-1]] == 0):
            snames[-1] = names[-1]
        scale_sh = NamedSharding(self.mesh, P(*snames))
        return QuantizedTensor(data_sh, scale_sh, qt.fmt_name, qt.n,
                               qt.packed)

    # -- activation specs ---------------------------------------------------
    def act(self, *rest) -> NamedSharding:
        """[batch, ...rest] activations: batch over dp."""
        return NamedSharding(self.mesh, P(self.dp_axes, *rest))

    def act_btd(self) -> NamedSharding:
        return self.act(None, None)

    def constraint(self, x, *rest):
        """Shape-aware activation constraint: [batch, *rest]; any axis whose
        dim doesn't divide its mesh axis is replicated (e.g. decode S=1,
        long_500k B=1). The residual stream uses ('model', None) rest —
        sequence-parallel residuals (Megatron-SP): saved scan residuals are
        1/TP the size, which is what lets train_4k fit HBM."""
        def ok(dim, axes):
            if axes is None:
                return None
            tup = axes if isinstance(axes, tuple) else (axes,)
            size = 1
            for a in tup:
                size *= self.mesh.shape[a]
            return axes if dim % size == 0 else None

        specs = [ok(x.shape[0], self.dp_axes)]
        for dim, a in zip(x.shape[1:], rest):
            specs.append(ok(dim, a))
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(self.mesh, P(*specs)))

    def seq(self, x):
        """Residual-stream constraint: [B(dp), S(model), D]."""
        return self.constraint(x, "model", None)

    def gather_seq_compressed(self, x, fmt_name: str = "fxp8"):
        """Explicit FxP8-compressed all-gather of a seq-sharded activation
        (§Perf beyond-paper lever): quantize per-token to int8 codes, gather
        codes + scales over `model` (half the bf16 gather bytes), dequantize
        locally. Backward is an uncompressed psum-scatter (STE through the
        quantizer). Falls back to a plain constraint when S doesn't divide
        the model axis (decode)."""
        from ..core.fxp import FORMATS, dequantize, quantize

        if x.ndim != 3 or x.shape[1] % self.mesh.shape["model"] != 0:
            return self.constraint(x, None, None)
        fmt = FORMATS[fmt_name]
        mesh, dpx = self.mesh, self.dp_axes
        dp_ok = x.shape[0] % self._axes_size(dpx) == 0
        dps = dpx if dp_ok else None

        @jax.custom_vjp
        def cg(xx):
            return _fwd(xx)

        def _fwd(xx):
            codes, scale = quantize(xx, fmt, axis=-1)  # [B,S,D]i8,[B,S,1]f32

            def g(c, sc):
                c = jax.lax.all_gather(c, "model", axis=1, tiled=True)
                sc = jax.lax.all_gather(sc, "model", axis=1, tiled=True)
                return c, sc

            c2, s2 = jax.shard_map(
                g, mesh=mesh,
                in_specs=(P(dps, "model", None), P(dps, "model", None)),
                out_specs=(P(dps, None, None), P(dps, None, None)),
                check_vma=False)(codes, scale)
            return dequantize(c2, s2, xx.dtype)

        def cg_fwd(xx):
            return _fwd(xx), None

        def cg_bwd(_, gy):
            def r(gl):
                return jax.lax.psum_scatter(gl, "model",
                                            scatter_dimension=1, tiled=True)

            gx = jax.shard_map(r, mesh=mesh,
                               in_specs=(P(dps, None, None),),
                               out_specs=P(dps, "model", None),
                               check_vma=False)(gy.astype(jnp.float32))
            return (gx.astype(gy.dtype),)

        cg.defvjp(cg_fwd, cg_bwd)
        return cg(x)

    def _axes_size(self, axes):
        size = 1
        for a in (axes if isinstance(axes, tuple) else (axes,)):
            size *= self.mesh.shape[a]
        return size


def logical_to_shardings(mesh: Mesh, axes_tree, fsdp: bool = False):
    return MeshRules(mesh, fsdp).param_shardings(axes_tree)
