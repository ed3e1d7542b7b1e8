"""How the trace names the program's step programs and kernels today.

The program names none of them itself yet (no `jax.named_scope`, no
`pallas_call(name=...)`), and a Pallas call shows in the trace only as a
`tpu_custom_call` operation with empty kernel metadata. So steps are
matched by their jitted function's name, and kernels by their operands,
as the trace gives them in the operation's HLO text:

- fxp_gemm: int8 activation codes, weight codes and a float32 scale row
  in, a float32 [M, N] out;
- paged decode attention: the int32 block table and per-row metadata
  first, a bfloat16 [B, H, hd] out.
"""
import re

DECODE_PROGRAM = r"decode_sample"
PREFILL_PROGRAM = r"prefill"
GEMM_KERNEL = r"^fxp_gemm$"
PAGED_ATTENTION_KERNEL = r"^paged_attention$"

_KERNELS = (
    ("fxp_gemm", re.compile(
        r"= f32\[\d+,\d+\]\S* custom-call\(s8\[\d+,\d+\]\S* \S+, "
        r"s8\[\d+,\d+\]\S* \S+, f32\[1,\d+\]")),
    ("paged_attention", re.compile(
        r"= bf16\[\d+,\d+,\d+\]\S* custom-call\(s32\[\d+,\d+\]\S* \S+, "
        r"s32\[\d+,3\]")),
)


def kernel_of(op_text: str):
    """The kernel an operation runs, by name; None for other operations
    ("pallas" for a Pallas call of another kind)."""
    if 'custom_call_target="tpu_custom_call"' not in op_text:
        return None
    for name, pattern in _KERNELS:
        if pattern.search(op_text):
            return name
    return "pallas"
