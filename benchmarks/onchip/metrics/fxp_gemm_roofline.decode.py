"""Roofline-bound time of the fxp_gemm calls of the decode steps in the
traced window (each GEMM over the step's live rows, from `costs.py`)
over those calls' kernel time, in percent."""
from onchip import costs, names, peaks


def read(ctx):
    n, t = ctx.trace.kernel_seconds(names.GEMM_KERNEL, names.DECODE_PROGRAM)
    if not n or not ctx.ticks.decode_steps:
        return None
    spec = ctx.cell.spec
    bound = 0.0
    for step in ctx.ticks.decode_steps:
        for m, k, nn in costs.step_gemms(spec, len(step), len(step)):
            bound += peaks.bound_s(*costs.gemm(m, k, nn), ctx.peaks)
    return 100.0 * bound / t
