"""prefill_ms_per_chunk (engine steps): device time of the chunked
prefill step programs (``_prefill_chunk_impl``,
``_prefill_chunk_ssm_impl``) in the traced window over their runs.
Device trace.  Moves ttft_p90_ms: a request's first token comes from
its last prefill chunk, and a queued request waits out the chunks
before it."""
from metrics._util import program_seconds


def read(ctx):
    prog = program_seconds(ctx, "prefill")
    if prog is None or not prog[0]:
        return None
    return prog[1] / prog[0] * 1e3
