"""Roofline-bound time of the fxp_gemm calls of the prefill chunks in the
traced window (each GEMM over the chunk's prompt tokens, the head over
its last one, from `costs.py`) over those calls' kernel time, in
percent."""
from onchip import costs, names, peaks


def read(ctx):
    n, t = ctx.trace.kernel_seconds(names.GEMM_KERNEL, names.PREFILL_PROGRAM)
    if not n or not ctx.ticks.prefill:
        return None
    spec = ctx.cell.spec
    bound = 0.0
    for _, take in ctx.ticks.prefill:
        for m, k, nn in costs.step_gemms(spec, take, 1):
            bound += peaks.bound_s(*costs.gemm(m, k, nn), ctx.peaks)
    return 100.0 * bound / t
