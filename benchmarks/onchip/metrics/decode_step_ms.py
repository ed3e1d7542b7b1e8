"""Mean device time of the fused decode+sample program, in ms."""
from onchip import names


def read(ctx):
    runs = ctx.trace.program_seconds(names.DECODE_PROGRAM)
    if not runs:
        return None
    return 1e3 * sum(runs) / len(runs)
