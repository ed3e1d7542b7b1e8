"""Share of the prompt tokens of the requests finished after arriving in
the window that the prefix cache served (`prefix_hit_tokens` on each
request's terminal event), in percent."""


def read(ctx):
    done = [tr for tr in ctx.due_in_window if tr.finished]
    prompt = sum(len(tr.req.prompt) for tr in done)
    if not prompt:
        return None
    return 100.0 * sum(tr.prefix_hit for tr in done) / prompt
