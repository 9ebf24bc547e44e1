"""kv_used_share (pool): mean over the window's ticks of the share of
the unified KV arena's head-blocks in use (allocator ``physical_used``
over the pool's blocks), in %.  Moves output_tok_s."""


def read(ctx):
    shares = [t.kv_used / t.kv_blocks for t in ctx.ticks
              if 0.0 <= t.t0 < ctx.seconds and t.kv_blocks]
    return 100.0 * sum(shares) / len(shares) if shares else None
