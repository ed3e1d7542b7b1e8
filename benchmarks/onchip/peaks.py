"""Published peaks of one chip, keyed by `jax.Device.device_kind`.

Source: Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16,
393 TOP/s int8, 16 GB of HBM at 819 GB/s. A kind missing here is an
error, never a default.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9},
}


def chip_peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r} (known: {sorted(PEAKS)})") from None


def bound_s(ops: float, nbytes: float, peaks: dict) -> float:
    """Least time the chip could take: the larger of int8 operations over
    the int8 peak and bytes over HBM bandwidth (FxP8 GEMM operands are
    int8 codes, so the int8 peak is the compute roof)."""
    return max(ops / peaks["int8_ops"], nbytes / peaks["hbm_bytes_per_s"])
