"""tick_host_ms (scheduler): mean, over the serving-loop steps whose tick
lies in the window, of the step's span ``mux.step`` less every decode
and prefill step span in it: routing arrivals, admission, quotas,
harvest and observation, the scheduler's own host time.  The program's
own spans (``_spans.py``).  Moves tpot_p50_ms."""
from collections import defaultdict

from metrics._spans import aligned


def read(ctx):
    a = aligned(ctx)
    if a is None:
        return None
    steps = {}
    for t in a.ticks[a.in_window]:
        p = a.v.parent[t]
        if p < 0 or a.names[p] != "mux.step":
            return None
        steps[int(p)] = a.dur[p]
    if not steps:
        return None
    work = defaultdict(float)
    for i in a.step_roots("decode") + a.step_roots("prefill"):
        if a.windowed(i):
            work[int(a.v.parent[a.ticks[a.tick[i]]])] += a.dur[i]
    return sum(d - work[p] for p, d in steps.items()) / len(steps) * 1e3
