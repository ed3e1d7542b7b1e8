"""PrecisionPolicy — the framework-level contract for Flex-PE precision modes.

The hardware's precision_sel / Sel_AF / ctrl_op registers become a per-layer
policy object threaded through every model. A policy is static per compiled
step (XLA needs static dtypes); "run-time switching" is realized as
selection among compiled specializations — the idiomatic TPU equivalent of
writing mode registers between workloads.

`qmatmul` is the single matmul entry point used by all models. Which
implementation serves it is the policy's `backend` field (overridable with
`with core.backend.backend(...)`):

  * 'reference'        — fake-quant float path (STE gradients): training,
                         QAT, and the numerics oracle.
  * 'pallas'           — the real packed-int `kernels/fxp_gemm` datapath
                         (+ CORDIC AF/softmax kernels) behind the same
                         numerics contract; serving fast path, forward-only.
  * 'pallas-interpret' — same kernels in Pallas interpret mode (CPU).
  * 'auto'             — pallas on TPU, pallas-interpret elsewhere.

Weights may be plain float arrays or `core.qtensor.QuantizedTensor`
(quantize-once packed storage); both backends accept both.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

# import names, not the module: the package re-exports the `backend`
# context manager under the same name as the submodule
from .backend import is_pallas as _is_pallas
from .backend import resolve as _resolve_backend
from .activation import flex_af
from .fxp import fake_quant_ste
from .qtensor import QuantizedTensor
# the serving-tier ladder lives in the jax-free `tiers` module (the
# pure-host Scheduler validates tier names without importing jax);
# re-exported here because precision.py owns the tier -> policy mapping
from .tiers import TIER_LADDER, TIERS, PrecisionTier, tier_index

__all__ = ["PrecisionPolicy", "qmatmul", "qeinsum", "PrecisionTier",
           "TIERS", "TIER_LADDER", "tier_index", "tier_policy",
           "policy_tier", "ATTN_PRECISION"]

#: Precision of the float attention dots (scores, and the softmax weights
#: against V) in the XLA paths and the fused paged kernel alike. At the
#: default precision XLA and Mosaic both round f32 operands to bf16 on a
#: TPU, so the softmax weights lose 2^-9; pinned to HIGHEST, the kernel's
#: dot and XLA's give the same bits on a v5e, and the weights keep their
#: f32 value (on the CPU both are plain f32 anyway).
ATTN_PRECISION = jax.lax.Precision.HIGHEST


def _dispatch():
    # lazy: core must stay importable without pulling kernel modules in
    from ..kernels import dispatch
    return dispatch


@dataclasses.dataclass(frozen=True)
class PrecisionPolicy:
    """Per-layer-kind precision configuration (hardware mode registers).

    matmul/af/kv_cache: FxP format names or None (= native bf16/fp32).
    af_impl: 'cordic' (paper datapath) or 'exact'.
    attn_softmax: 'cordic' routes attention softmax through the Flex-PE
      softmax path; 'exact' uses jax.nn.softmax.
    grad_compression: 'none' | 'fxp8' — quantized DP gradient all-reduce.
    backend: kernel backend serving qmatmul / act / softmax — 'reference',
      'pallas', 'pallas-interpret', or 'auto' (see module docstring).
    """
    name: str = "bf16"
    matmul: Optional[str] = None
    af: Optional[str] = None
    af_impl: str = "exact"
    attn_softmax: str = "exact"
    kv_cache: Optional[str] = None
    grad_compression: str = "none"
    # decode attention computed on integer KV codes (no bf16 cache copy);
    # requires kv_cache set — the §Perf memory-bound hillclimb lever
    int_attention: bool = False
    # 'fxp8': compress the sequence-parallel activation all-gather at
    # attention block inputs (half the dominant train collective bytes)
    act_comm: str = "none"
    # matmul partial-sum dtype crossing TP all-reduces: 'f32' (default) or
    # 'bf16' (halves AR bytes; MXU accumulates fp32 internally either way)
    matmul_out: str = "f32"
    # constrain TP matmul OUTPUTS to the seq-sharded layout before the
    # residual add, turning all-reduces into reduce-scatters (half bytes)
    seq_outputs: bool = False
    # kernel backend for qmatmul / act / softmax (see module docstring)
    backend: str = "reference"

    # -- factories ---------------------------------------------------------
    @staticmethod
    def bf16() -> "PrecisionPolicy":
        """Native-precision baseline (no Flex-PE datapath)."""
        return PrecisionPolicy(name="bf16")

    @staticmethod
    def flexpe(bits: int = 8, af_impl: str = "cordic",
               grad_compression: str = "none",
               backend: str = "reference") -> "PrecisionPolicy":
        """Paper-faithful FxP<bits> mode: quantized matmuls + CORDIC AFs."""
        fmt = f"fxp{bits}"
        return PrecisionPolicy(
            name=f"flexpe-{fmt}", matmul=fmt, af=fmt, af_impl=af_impl,
            attn_softmax=af_impl if af_impl == "cordic" else "exact",
            kv_cache=fmt if bits >= 8 else "fxp8",
            grad_compression=grad_compression, backend=backend)

    @staticmethod
    def edge4(backend: str = "reference") -> "PrecisionPolicy":
        """FxP4 edge-inference mode (paper §III-B: first 4-bit config-AF)."""
        return PrecisionPolicy(name="flexpe-fxp4", matmul="fxp4", af="fxp4",
                               af_impl="cordic", attn_softmax="cordic",
                               kv_cache="fxp8", backend=backend)

    def with_backend(self, backend: str) -> "PrecisionPolicy":
        return dataclasses.replace(self, backend=backend)

    def resolved_backend(self) -> str:
        """Concrete backend name after `with backend(...)` override + auto."""
        return _resolve_backend(self.backend)

    # -- ops ---------------------------------------------------------------
    def act(self, x: jax.Array, af: str, axis: int = -1) -> jax.Array:
        be = self.resolved_backend()
        if (_is_pallas(be) and self.af_impl == "cordic"
                and af != "softmax"):
            return _dispatch().act(x, af, self, backend=be)
        return flex_af(x, af, precision=self.af, impl=self.af_impl, axis=axis)

    def softmax(self, x: jax.Array, axis: int = -1) -> jax.Array:
        if self.attn_softmax != "cordic":
            return flex_af(x, "softmax", precision=None, impl="exact",
                           axis=axis)
        be = self.resolved_backend()
        if _is_pallas(be) and axis in (-1, x.ndim - 1):
            return _dispatch().softmax(x, self, backend=be, axis=axis)
        from .activation import default_stages, softmax_lv_stages
        hr, _ = default_stages(self.af)
        lv = softmax_lv_stages(x.shape[axis], self.af)
        return flex_af(x, "softmax", precision=self.af, impl="cordic",
                       stages=(hr, lv), axis=axis)


def tier_policy(tier: str, backend: str = "reference",
                af_impl: str = "cordic") -> PrecisionPolicy:
    """The `PrecisionPolicy` a replica pinned to ladder tier `tier` runs.

    FxP tiers map to the paper-faithful `flexpe(bits)` mode (quantized
    matmuls + CORDIC AFs at the tier's Pareto stage pick — `flexpe`
    reads the same `PARETO_STAGES` table the ladder mirrors); 'bf16' is
    the native-precision policy. Unknown names raise the ladder's
    ValueError."""
    t = TIER_LADDER[tier_index(tier)]
    if t.bits is None:
        return PrecisionPolicy.bf16().with_backend(backend)
    return PrecisionPolicy.flexpe(t.bits, af_impl=af_impl, backend=backend)


def policy_tier(policy: Optional["PrecisionPolicy"]) -> Optional[str]:
    """Ladder tier a policy serves at: its matmul format name when that
    is a rung ('fxp4'/'fxp8'/'fxp16'), 'bf16' for native-precision
    policies (matmul None), None for off-ladder formats (e.g. fxp12) —
    such an engine serves untiered and rejects tier-pinned requests."""
    if policy is None or policy.matmul is None:
        return "bf16"
    return policy.matmul if policy.matmul in TIERS else None


def _maybe_q(x: jax.Array, fmt_name: Optional[str]) -> jax.Array:
    if fmt_name is None:
        return x
    return fake_quant_ste(x, fmt_name)


def qmatmul(x: jax.Array, w, policy: Optional[PrecisionPolicy],
            preferred=jnp.float32, af: Optional[str] = None) -> jax.Array:
    """Policy-aware matmul, dispatched per `policy.backend`.

    reference: fake-quant operands to the FxP grid (STE grads), accumulate
    in fp32 (the hardware's FxP32 accumulator). With policy.matmul_out=
    'bf16' the dot OUTPUT (the tensor that crosses TP all-reduces) is bf16 —
    the MXU's internal accumulation stays fp32.

    pallas(-interpret): real integer GEMM on quantized codes with the
    dequant (+ fused `af` epilogue) inside the kernel; `w` may be a
    `QuantizedTensor` so only packed codes move HBM→VMEM.

    `af` (optional) applies the named Flex-PE activation to the output —
    fused into the kernel epilogue on pallas, `policy.act` post-op on
    reference.
    """
    be = _resolve_backend(policy.backend if policy is not None else None)
    if _is_pallas(be) or isinstance(w, QuantizedTensor) or af is not None:
        # dispatch owns QuantizedTensor plumbing and the shared
        # accumulator-AF contract (identical on every backend)
        return _dispatch().matmul(x, w, policy, backend=be, af=af)
    if policy is not None and policy.matmul is not None:
        x = _maybe_q(x, policy.matmul)
        w = _maybe_q(w, policy.matmul)
    if policy is not None and policy.matmul_out == "bf16":
        preferred = jnp.bfloat16
    return jax.lax.dot_general(
        x, w, (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=preferred).astype(x.dtype)


#: MoE expert-bank einsums ("gecd,edf->gecf" family): batched per-expert
#: GEMMs with the contraction on x's last / w's middle axis — the shape
#: `kernels.dispatch.expert_matmul` serves on every backend.
_EXPERT_BANK_SPECS = frozenset({"gecd,edf->gecf", "gecf,efd->gecd"})


def qeinsum(spec: str, x: jax.Array, w, policy: Optional[PrecisionPolicy]):
    """Einsum sibling of qmatmul.

    MoE expert-bank specs with a pallas backend or a QuantizedTensor bank
    dispatch through `kernels.dispatch.expert_matmul` (per-expert packed-int
    GEMMs, same exact-int contract as qmatmul). Anything else is the
    fake-quant reference einsum."""
    be = _resolve_backend(policy.backend if policy is not None else None)
    if spec in _EXPERT_BANK_SPECS and (_is_pallas(be)
                                       or isinstance(w, QuantizedTensor)):
        return _dispatch().expert_matmul(x, w, policy, backend=be)
    if isinstance(w, QuantizedTensor):
        w = w.dequantize(x.dtype)
    if policy is not None and policy.matmul is not None:
        x = _maybe_q(x, policy.matmul)
        w = _maybe_q(w, policy.matmul)
    pref = (jnp.bfloat16 if policy is not None
            and policy.matmul_out == "bf16" else jnp.float32)
    return jnp.einsum(spec, x, w,
                      preferred_element_type=pref).astype(x.dtype)
