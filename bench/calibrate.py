#!/usr/bin/env python3
"""Readings that set a cell's correctness limits, in one process.

    python3 bench/calibrate.py --workload <cell> --seconds <s> --seeds 1,2,3 [--control]

For each seed: the cell's unit is built with that seed's weights, its
traffic is served for ``--seconds`` at the cell's own load (long enough
to finish the mix's longest requests), and the served tokens are
compared with the float32 reference exactly as a benchmark run compares
them.  With ``--control`` the same sample is also read by the control,
the reference with fp8 (e4m3) weight matrices (``bench/check.py``), and
its readings go through the same ``harness.passed`` that decides a
run's ``correct``, which has to come out false.  Programs compiled for the first seed serve the rest, so set-up is paid
once.  Prints one line per seed and LLM, and a JSON summary last: per
LLM the largest reading of the system and the smallest of the control.
A benchmark run never runs this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))
sys.path.insert(0, BENCH)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--control", action="store_true")
    p.add_argument("--rehearse", action="store_true")
    args = p.parse_args(argv)
    import harness
    b = harness.Bench(args.workload, args.rehearse,
                      say=lambda m: print(m, flush=True))
    served, control, verdicts = {}, {}, []
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        ctx = b.window(seed, args.seconds, trace=False)
        checks = b.check(ctx, seed, control=args.control)
        for name, c in checks.items():
            served.setdefault(name, []).append(c["value"])
            if args.control:
                control.setdefault(name, []).append(c["control"])
            print(f"[calibrate] seed {seed} {name}: served {c['value']} "
                  f"control {c.get('control')} over {c['tokens']} tokens "
                  f"of {c['requests']} requests "
                  f"({time.perf_counter() - t0:.1f} s)", flush=True)
        verdict = {"seed": seed, "correct": harness.passed(checks)}
        if args.control:
            verdict["control_correct"] = harness.passed(
                {n: dict(c, value=c["control"]) for n, c in checks.items()})
        verdicts.append(verdict)
        print(f"[calibrate] {json.dumps(verdict)}", flush=True)
    def top(xs, pick):
        xs = [x for x in xs if x is not None]
        return pick(xs) if xs else None
    summary = {n: {"served_max": top(v, max), "served": v,
                   **({"control_min": top(control[n], min),
                       "control": control[n]} if args.control else {})}
               for n, v in served.items()}
    summary["verdicts"] = verdicts
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
