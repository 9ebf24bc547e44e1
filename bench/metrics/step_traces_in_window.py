"""step_traces_in_window (engine steps): step programs traced inside a
tick of the window (the program's ``mux.trace.<step>`` events, each a
program about to compile).  0 when warm-up covered every shape the
window used.  The program's own record (``_spans.py``).  Moves
tpot_p90_ms: a compile in the window is a token gap of seconds."""
from metrics._spans import aligned


def read(ctx):
    a = aligned(ctx)
    if a is None:
        return None
    return sum(1 for i, n in enumerate(a.names)
               if n.startswith("mux.trace.") and a.windowed(i))
