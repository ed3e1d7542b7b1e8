"""Tokens decoded in the traced window per fused decode+sample program
that ran on the device in it."""
from onchip import names


def read(ctx):
    steps = len(ctx.trace.program_seconds(names.DECODE_PROGRAM))
    if not steps or not ctx.ticks.decode_tokens:
        return None
    return ctx.ticks.decode_tokens / steps
