#!/usr/bin/env python3
"""Serve a cell under variants of one parameter, in one process: the
sweep that finds an open-loop cell's knee, and the sizing of a
configuration's serving block on the chip.

    python3 bench/sweep.py --workload <cell> --seconds <s> --vary mix.rate_per_s=1.5,1.7,1.9 [--seeds 1,2]
    python3 bench/sweep.py --workload <cell> --seconds <s> --vary serving.pool_blocks=401408 --set serving.context_tokens=784 --trace

``--vary`` names a key of the cell's traffic mix (``mix.<key>``) or of
its configuration's serving block (``serving.<key>``) and the values to
try; ``--set`` fixes further keys the same way.  For each value and
seed the cell's traffic is served for ``--seconds`` and one line gives
what was offered and what came out: requests due and finished, those
still waiting for a first token at the close, the TTFT p90 of the
requests due in each half of the window (a queue that grows through
the window shows as a second half far above the first), the cell's
end-to-end metrics, and the process's device memory peak.  With
``--trace`` the line also carries the per-layer metrics, and the runs
per step program of device copies of the KV arena.  The memory peak is
the process's so far: give each serving variant a process of its own.

The knee is the highest rate at which the queue does not grow through
the window; the cell then offers a fixed rate below it, written into
its mix file.  A benchmark run never searches for one.
"""
from __future__ import annotations

import argparse
import json
import os
import re
import sys

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))
sys.path.insert(0, BENCH)


def _assign(b, key: str, value) -> None:
    where, name = key.split(".", 1)
    if where == "mix":
        b.mix = dict(b.mix, **{name: value})
    elif where == "serving":
        b.config["serving"][name] = value
    else:
        raise SystemExit(f"--vary/--set: {key!r} is not mix.* or serving.*")


def _values(text: str):
    return [json.loads(v) for v in text.split(",")]


def _ttft_p90(reqs, close):
    first = [(r.token_times[0] if r.token_times else close) - r.due
             for r in reqs]
    return float(np.percentile(first, 90)) * 1e3 if first else None


def row(b, ctx, trace: bool) -> dict:
    due = ctx.due_in_window()
    half = ctx.seconds / 2
    out = {"due": len(due),
           "finished": sum(1 for r in due if r.finished),
           "waiting_at_close": sum(1 for r in due if not any(
               t < ctx.seconds for t in r.token_times)),
           "ttft_p90_ms_first_half": _ttft_p90(
               [r for r in due if r.due < half], ctx.drain_end),
           "ttft_p90_ms_second_half": _ttft_p90(
               [r for r in due if r.due >= half], ctx.drain_end),
           "memory_peak_bytes": ctx.memory_peak_bytes,
           "metrics": {k: v["value"]
                       for k, v in b.metrics(ctx, False).items()}}
    if trace and ctx.trace is not None:
        out["per_layer"] = {k: v["value"]
                            for k, v in b.metrics(ctx, True).items()}
        arena = re.compile(
            rf"^copy \w+\[{b.config['serving']['pool_blocks']},")
        steps = sum(p["runs"] for p in ctx.trace["programs"].values())
        copies = sum(n for op, n in ctx.trace["op_runs"].items()
                     if arena.match(op))
        out["arena_copies_per_step"] = copies / steps if steps else None
        out["device_ops"] = ctx.trace["device_ops"][:5]
    return out


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--vary", required=True, help="mix.<key>=v1,v2,...")
    p.add_argument("--set", action="append", default=[],
                   help="mix.<key>=v or serving.<key>=v, fixed")
    p.add_argument("--seeds", default="1")
    p.add_argument("--trace", action="store_true")
    p.add_argument("--rehearse", action="store_true")
    args = p.parse_args(argv)
    import harness
    b = harness.Bench(args.workload, args.rehearse,
                      say=lambda m: print(m, flush=True))
    b.drain_s = 0.0          # past the knee the queue never drains
    for fixed in args.set:
        key, value = fixed.split("=", 1)
        _assign(b, key, json.loads(value))
    key, values = args.vary.split("=", 1)
    rows = []
    for value in _values(values):
        _assign(b, key, value)
        for seed in (int(s) for s in args.seeds.split(",")):
            ctx = b.window(seed, args.seconds, trace=args.trace)
            r = {key: value, "seed": seed, **row(b, ctx, args.trace)}
            rows.append(r)
            print(f"[sweep] {json.dumps(r)}", flush=True)
    print(json.dumps(rows), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
