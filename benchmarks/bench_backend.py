"""Backend benchmark: reference vs pallas GEMM wall-clock and weight
bytes-moved per precision.

    PYTHONPATH=src python benchmarks/bench_backend.py [--m 512 --k 1024
        --n 1024 --iters 20] [--precisions fxp4,fxp8,fxp16]

For each FxP precision this times the policy-dispatched `qmatmul` on both
backends over the same quantize-once `QuantizedTensor` weight and reports:

  * wall-clock per matmul (median of `--iters`, after a warmup compile),
  * weight bytes actually moved HBM->VMEM per use (the packed code bytes)
    vs the fp32 master copy — the paper's SIMD storage claim:
    FxP4 8x, FxP8 4x, FxP16 2x.

Off the chip the pallas column runs `pallas-interpret`, so it measures the
kernels' *semantics* (and the bytes column the real storage win); run on a
TPU host for the compiled Mosaic numbers.
"""
from __future__ import annotations

import argparse
import statistics
import time

import jax
import jax.numpy as jnp

from repro.core.precision import PrecisionPolicy, qmatmul
from repro.core.qtensor import quantize_tensor


def _time(fn, iters: int) -> float:
    fn()  # warmup / compile
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        times.append(time.perf_counter() - t0)
    return statistics.median(times)


def _policy(fmt_name: str, backend: str) -> PrecisionPolicy:
    bits = int(fmt_name.replace("fxp", ""))
    if bits == 4:
        return PrecisionPolicy.edge4(backend=backend)
    return PrecisionPolicy.flexpe(bits, backend=backend)


def bench(m: int, k: int, n: int, iters: int, precisions) -> list[dict]:
    key = jax.random.PRNGKey(0)
    kx, kw = jax.random.split(key)
    x = jax.random.normal(kx, (m, k), jnp.float32)
    w = jax.random.normal(kw, (k, n), jnp.float32)
    fp32_bytes = 4 * k * n

    rows = []
    for fmt_name in precisions:
        qt = quantize_tensor(w, fmt_name)
        code_bytes = qt.data.size * qt.data.dtype.itemsize
        row = {"precision": fmt_name,
               "weight_bytes": code_bytes,
               "fp32_bytes": fp32_bytes,
               "reduction_x": fp32_bytes / code_bytes}
        # the kernels compile on a TPU and run in interpret mode elsewhere
        kernels = ("pallas" if jax.default_backend() == "tpu"
                   else "pallas-interpret")
        for label, backend in (("reference", "reference"),
                               ("pallas", kernels)):
            pol = _policy(fmt_name, backend)
            f = jax.jit(lambda xx, pp=pol: qmatmul(xx, qt, pp))
            row[f"{label}_s"] = _time(lambda: f(x), iters)
        rows.append(row)
    return rows


def run(rows):
    """benchmarks.run harness hook: small shapes, CSV rows appended."""
    for r in bench(128, 256, 256, 5, ("fxp4", "fxp8", "fxp16")):
        rows.append((f"backend_gemm_ref_{r['precision']}",
                     r["reference_s"] * 1e6,
                     f"wbytes={r['weight_bytes']}"))
        rows.append((f"backend_gemm_pallas_{r['precision']}",
                     r["pallas_s"] * 1e6,
                     f"reduction={r['reduction_x']:.1f}x"))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--m", type=int, default=512)
    ap.add_argument("--k", type=int, default=1024)
    ap.add_argument("--n", type=int, default=1024)
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--precisions", default="fxp4,fxp8,fxp16")
    args = ap.parse_args(argv)
    precisions = args.precisions.split(",")

    rows = bench(args.m, args.k, args.n, args.iters, precisions)
    be = jax.default_backend()
    print(f"# backend bench: [{args.m}x{args.k}] @ [{args.k}x{args.n}], "
          f"jax backend={be} (pallas runs "
          f"{'compiled' if be == 'tpu' else 'interpret'})")
    hdr = (f"{'precision':<10} {'reference':>12} {'pallas':>12} "
           f"{'w-bytes':>10} {'vs fp32':>8}")
    print(hdr)
    print("-" * len(hdr))
    for r in rows:
        print(f"{r['precision']:<10} {r['reference_s'] * 1e3:>10.2f}ms "
              f"{r['pallas_s'] * 1e3:>10.2f}ms "
              f"{r['weight_bytes']:>10} {r['reduction_x']:>7.1f}x")
    return rows


if __name__ == "__main__":
    main()
