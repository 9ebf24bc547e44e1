"""queue_wait_ms_p50 (scheduler): median over requests due in the window
of the wait from the due time to admission, when the scheduler
dispatched the request's prefill (``Request.prefill_done``, stamped by
``serving/mux.py``).  Moves ttft_p90_ms: a request's first token
comes no sooner than its admission."""
from metrics._util import percentile


def read(ctx):
    waits = [r.admitted - r.due for r in ctx.due_in_window()
             if r.admitted is not None]
    v = percentile(waits, 50)
    return None if v is None else v * 1e3
