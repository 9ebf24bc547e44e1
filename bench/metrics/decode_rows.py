"""decode_rows (scheduler): mean over (tick, LLM) pairs that decoded in
the window of the rows decoded, from ``MuxScheduler.tick_decode_by``.
Moves output_tok_s."""


def read(ctx):
    rows = [n for t in ctx.ticks if 0.0 <= t.t0 < ctx.seconds
            for n in t.decode_by.values() if n]
    return sum(rows) / len(rows) if rows else None
