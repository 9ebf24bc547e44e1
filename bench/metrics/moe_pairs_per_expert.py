"""moe_pairs_per_expert (experts): mean, over the decode steps that
start in a tick of the window and over their MoE layers, of the tokens
routed to each expert this chip holds.  The program's own record
(``_spans.py``): each MoE decode step keeps its counts ([MoE layers,
held experts]) beside its spans as the value of a ``mux.moe.<llm>``
event.  A program without such events reads as None.  Moves
tpot_p50_ms: the tokens each held expert's weights are read for."""
import numpy as np

from metrics._spans import aligned


def read(ctx):
    a = aligned(ctx)
    if a is None:
        return None
    values = getattr(a.v, "values", None) or {}
    loads = [values[i] for i, n in enumerate(a.names)
             if n.startswith("mux.moe.") and i in values and a.windowed(i)]
    if not loads:
        return None
    return float(np.mean([np.mean(v) for v in loads]))
