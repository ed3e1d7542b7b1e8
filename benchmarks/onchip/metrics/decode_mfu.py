"""Model operations of the tokens decoded in the traced window over the
device time of the decode programs times the int8 peak, in percent."""
from onchip import costs, names


def read(ctx):
    t = sum(ctx.trace.program_seconds(names.DECODE_PROGRAM))
    if not t or not ctx.ticks.decode_steps:
        return None
    spec = ctx.cell.spec
    ops = sum(costs.token_ops(spec, c - 1) for step in ctx.ticks.decode_steps
              for c in step)
    return 100.0 * ops / (t * ctx.peaks["int8_ops"])
