"""decode_host_ms (engine steps): mean, over the decode steps that start
in a tick of the window, of the host time of the step's span
``mux.decode.<llm>`` not spent in its ``sync`` phase (the host blocked
on the device's tokens): tables, padding, transfers, state gathers, the
launch and the commit.  The program's own spans (``_spans.py``).  Moves
tpot_p50_ms."""
from metrics._spans import aligned


def read(ctx):
    a = aligned(ctx)
    if a is None:
        return None
    host = []
    for i in a.step_roots("decode"):
        if a.windowed(i):
            sync = [c for c in a.children(i)
                    if a.names[c] == a.names[i] + ".sync"]
            host.append(a.dur[i] - sum(a.dur[c] for c in sync))
    return sum(host) / len(host) * 1e3 if host else None
