#!/usr/bin/env python3
"""Run one benchmark cell and print its result.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

from the checkout's root, on a machine whose JAX sees the chips the cell
asks for.  Set-up (weights from the seed, the cell's programs warmed,
for a backlog the slots filled) is timed as ``setup_s``; then the cell's
traffic is served open loop on the wall clock for ``--seconds``.  With
``--trace 0`` the result carries the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, read from a profile of a few
seconds inside the window.  After the window the served tokens are
compared with the float32 reference, which decides ``correct``.

The last line of standard output is the result as one JSON object; the
last lines of standard error are the numbers compared, each with its
limit.  Without an accelerator, with too few chips, or on a chip
``bench/peaks.json`` does not know, it exits non-zero and prints no
result.  ``--rehearse`` runs the cell at the configuration's reduced
sizes on any backend (the CPU included), closes its window once every
LLM has finished as many requests as it has slots (``--seconds`` at the
latest), and reports no device metric: it is for rehearsals and tests,
never for measurement.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))
sys.path.insert(0, BENCH)


def parse(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--rehearse", action="store_true",
                   help="reduced sizes, any backend, no device metrics")
    return p.parse_args(argv)


def main(argv=None, fault=None) -> int:
    args = parse(argv)
    import harness

    def say(msg):
        print(msg, flush=True)
    try:
        result = harness.run(args.workload, args.seed, args.seconds,
                             bool(args.trace), T_START,
                             rehearse=args.rehearse, fault=fault, say=say)
    except harness.Refused as e:
        print(f"bench: refused: {e}", file=sys.stderr, flush=True)
        return 2
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']}, "
              f"{c['tokens']} tokens of {c['requests']} requests in "
              f"{c['slots']} slots)",
              file=sys.stderr, flush=True)
    print(f"correct: {result['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
