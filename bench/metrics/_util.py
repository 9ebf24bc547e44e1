"""Helpers the metric readers share."""
from __future__ import annotations

import numpy as np


def percentile(xs, q):
    """The q-th percentile (linear interpolation), or None for no data."""
    return float(np.percentile(np.asarray(xs, float), q)) if len(xs) else None


def tpots(ctx):
    """Per request, the mean gap between the output tokens it committed
    inside the window (requests with at least two such tokens), s."""
    out = []
    for r in ctx.requests:
        ts = [t for t in r.token_times if 0.0 <= t < ctx.seconds]
        if len(ts) >= 2:
            out.append((ts[-1] - ts[0]) / (len(ts) - 1))
    return out


def program_seconds(ctx, kind):
    """(runs, device seconds) of one kind of step program in the traced
    window, or None without a device trace."""
    if ctx.trace is None:
        return None
    p = ctx.trace["programs"][kind]
    return p["runs"], p["seconds"]


def traced_steps(ctx, kind):
    return [s for s in ctx.steps if s.kind == kind and ctx.in_trace(s.t0, s.t1)]
