"""decode_ms_per_tick (engine steps): device time of the decode step
programs (``_decode_impl``) in the traced window over the ticks in it
that decoded.  Device trace.  Moves tpot_p50_ms."""
from metrics._util import program_seconds


def read(ctx):
    prog = program_seconds(ctx, "decode")
    ticks = [t for t in ctx.ticks if ctx.in_trace(t.t0, t.t1)
             and any(t.decode_by.values())]
    if prog is None or not prog[0] or not ticks:
        return None
    return prog[1] / len(ticks) * 1e3
