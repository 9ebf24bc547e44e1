"""tpot_p90_ms: 90th percentile over requests of the mean gap between
the output tokens each committed inside the window.  Host clock."""
from metrics._util import percentile, tpots


def read(ctx):
    v = percentile(tpots(ctx), 90)
    return None if v is None else v * 1e3
