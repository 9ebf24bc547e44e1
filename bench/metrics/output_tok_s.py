"""output_tok_s: output tokens committed inside the window, all LLMs,
over the window's seconds.  Host clock."""


def read(ctx):
    n = sum(1 for r in ctx.requests for t in r.token_times
            if 0.0 <= t < ctx.seconds)
    return n / ctx.seconds
