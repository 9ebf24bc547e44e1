"""tpot_p50_ms: median over requests of the mean gap between the output
tokens each committed inside the window (at least two).  Host clock."""
from metrics._util import percentile, tpots


def read(ctx):
    v = percentile(tpots(ctx), 50)
    return None if v is None else v * 1e3
