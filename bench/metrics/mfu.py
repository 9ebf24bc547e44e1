"""mfu (model step): FLOPs the model needs for every token the step
programs processed in the traced window (decode and prefill chunks, from
``bench/work.py``) over the device time of those programs times the
chip's bf16 peak, in %.  Device trace.  Moves tpot_p50_ms."""
from metrics._util import program_seconds, traced_steps
from work import step_work


def read(ctx):
    progs = [program_seconds(ctx, k) for k in ("decode", "prefill")]
    if ctx.peak is None or any(p is None for p in progs):
        return None
    secs = sum(p[1] for p in progs)
    flops = sum(step_work(ctx.llms[s.llm], kind, s.detail,
                          ctx.dtype_bytes)[0]
                for kind in ("decode", "prefill")
                for s in traced_steps(ctx, kind))
    if not secs or not flops:
        return None
    return 100.0 * flops / (secs * ctx.peak["bf16_flops_per_s"])
