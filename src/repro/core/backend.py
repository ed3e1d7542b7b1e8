"""Kernel backend selection — which implementation serves each policy op.

The Flex-PE datapath has two software realizations with one numerics
contract:

  * ``reference``        — fake-quant float path (XLA dots + float CORDIC
                           emulation). Gradient-capable via STE; this is the
                           training path and the numerics oracle.
  * ``pallas``           — the real integer kernels: ``kernels/fxp_gemm``
                           (packed-int SIMD storage, int32 accumulation,
                           fused AF epilogue) + ``kernels/cordic_af`` /
                           ``kernels/cordic_softmax``. Serving fast path;
                           forward-only.
  * ``pallas-interpret`` — same kernels, Pallas interpret mode (kernel body
                           executed as traced jnp on CPU — validation and
                           CI without a TPU).
  * ``auto``             — resolves to ``pallas`` on TPU, else
                           ``pallas-interpret``.

``pallas`` means the compiled kernels and nothing else: asked for where
JAX sees no TPU, it raises instead of quietly running interpret mode, so
a chip run that lost its chip fails rather than finishing on the CPU.

Selection has two inputs, in priority order:

  1. a dynamic ``with backend("pallas"):`` override (trace-time scoped), and
  2. the static ``PrecisionPolicy.backend`` field.

``resolve(...)`` collapses both to a concrete backend name; op routing lives
in ``kernels/dispatch.py`` (kept out of ``core`` so ``core`` never imports
kernel modules at import time).
"""
from __future__ import annotations

import contextlib
from typing import Iterator, Optional

import jax

__all__ = ["BACKENDS", "backend", "current_override", "resolve",
           "is_pallas", "interpret_mode"]

#: Recognised backend names (``auto`` resolves to one of the concrete ones).
BACKENDS = ("reference", "pallas", "pallas-interpret", "auto")

# dynamic override stack for `with backend(...)`. Trace-time state: entering
# the context during jit tracing routes every policy op traced inside it.
_OVERRIDE: list[str] = []


@contextlib.contextmanager
def backend(name: str) -> Iterator[None]:
    """Scoped backend override: ``with backend("pallas-interpret"): ...``
    routes every policy op (qmatmul / act / softmax) traced inside the block
    through the named backend, regardless of ``policy.backend``."""
    if name not in BACKENDS:
        raise ValueError(
            f"unknown backend {name!r}; expected one of {BACKENDS}")
    _OVERRIDE.append(name)
    try:
        yield
    finally:
        _OVERRIDE.pop()


def current_override() -> Optional[str]:
    return _OVERRIDE[-1] if _OVERRIDE else None


def resolve(policy_backend: Optional[str]) -> str:
    """Collapse (dynamic override, policy field) to a concrete backend name.

    'auto' picks the compiled kernels on TPU and interpret mode elsewhere.
    'pallas' off-TPU is an error: Mosaic compiles for the TPU only, and
    interpret mode is asked for by name ('pallas-interpret')."""
    name = current_override() or policy_backend or "reference"
    if name not in BACKENDS:
        raise ValueError(
            f"unknown backend {name!r}; expected one of {BACKENDS}")
    on_tpu = jax.default_backend() == "tpu"
    if name == "auto":
        return "pallas" if on_tpu else "pallas-interpret"
    if name == "pallas" and not on_tpu:
        raise RuntimeError(
            "backend 'pallas' runs the compiled Mosaic kernels, but JAX "
            f"found no TPU (default backend: {jax.default_backend()!r}); "
            "use 'pallas-interpret' to run the same kernels in interpret "
            "mode")
    return name


def is_pallas(name: str) -> bool:
    return name in ("pallas", "pallas-interpret")


def interpret_mode(name: str) -> bool:
    """Pallas interpret flag for a resolved backend name."""
    return name == "pallas-interpret"
