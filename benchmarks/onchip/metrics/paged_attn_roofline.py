"""Roofline-bound time of the paged decode attention calls in the traced
window (one per layer and decode step, over the live rows' contexts,
from `costs.py`) over those calls' kernel time, in percent."""
from onchip import costs, names, peaks


def read(ctx):
    n, t = ctx.trace.kernel_seconds(names.PAGED_ATTENTION_KERNEL,
                                    names.DECODE_PROGRAM)
    if not n or not ctx.ticks.decode_steps:
        return None
    spec = ctx.cell.spec
    layers = spec["num_hidden_layers"]
    bound = sum(layers * peaks.bound_s(*costs.attention_decode(spec, step),
                                       ctx.peaks)
                for step in ctx.ticks.decode_steps)
    return 100.0 * bound / t
