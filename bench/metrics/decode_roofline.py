"""decode_roofline (kernels: the XLA decode step programs): the least
time the chip needs for the traced window's decode steps — per step the
larger of its FLOPs over the bf16 peak and its bytes over the HBM
bandwidth, from ``bench/work.py`` — over the device time of the decode
programs, in %.  Device trace.  Moves tpot_p50_ms."""
from metrics._util import program_seconds, traced_steps
from work import least_time, step_work


def read(ctx):
    prog = program_seconds(ctx, "decode")
    steps = traced_steps(ctx, "decode")
    if prog is None or ctx.peak is None or not prog[1] or not steps:
        return None
    least = 0.0
    for s in steps:
        f, b = step_work(ctx.llms[s.llm], "decode", s.detail, ctx.dtype_bytes)
        least += least_time(f, b, ctx.peak)["seconds"]
    return 100.0 * least / prog[1]
