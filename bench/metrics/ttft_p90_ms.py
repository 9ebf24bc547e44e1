"""ttft_p90_ms: 90th percentile of time to first token over every
request due in the window, all LLMs, from its due time to the host stamp
after the tick that committed its first token.  A request with no first
token a minute past the close counts to that moment.  Host clock;
an end-to-end metric of a cell offered load below its knee."""
from metrics._util import percentile


def read(ctx):
    due = ctx.due_in_window()
    ttft = [(r.token_times[0] if r.token_times else ctx.drain_end) - r.due
            for r in due]
    v = percentile(ttft, 90)
    return None if v is None else v * 1e3
