"""Model operations of the prompt tokens prefilled in the traced window
(the head once per chunk, on its last token) over the device time of the
prefill programs times the int8 peak, in percent."""
from onchip import costs, names


def read(ctx):
    t = sum(ctx.trace.program_seconds(names.PREFILL_PROGRAM))
    if not t or not ctx.ticks.prefill:
        return None
    spec = ctx.cell.spec
    head = 2.0 * spec["hidden_size"] * spec["vocab_size"]
    ops = sum(costs.token_ops(spec, p, head=False)
              for start, take in ctx.ticks.prefill
              for p in range(start, start + take))
    ops += head * len(ctx.ticks.prefill)
    return 100.0 * ops / (t * ctx.peaks["int8_ops"])
