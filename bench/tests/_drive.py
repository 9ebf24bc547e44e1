"""What the tests do to a cell, found by its name alone: a rehearsal
window, the fp8 control's readings, and a run with its timed path
broken.  ``test_contract.py`` drives the same functions in a copy of
the benchmark to which a cell was added as data alone.

A rehearsal's window closes once every LLM has finished as many
requests as it has slots (``harness.Bench.window``); ``REHEARSAL_S`` is
only the latest it may close, the same for every cell."""
import itertools
import json
import os

import numpy as np

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


def cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return [w["name"] for w in json.load(f)["workloads"]]


REHEARSAL_S = 240.0


def control(cell: str, seed: int) -> dict:
    """The cell's checks on one rehearsal window, each with the fp8
    control's reading beside the system's."""
    import harness
    b = harness.Bench(cell, rehearse=True, say=lambda m: None)
    ctx = b.window(seed, REHEARSAL_S, trace=False)
    return b.check(ctx, seed, control=True)


def control_passed(checks: dict) -> bool:
    import harness
    return harness.passed({n: dict(c, value=c["control"])
                           for n, c in checks.items()})


def token_altered(unit):
    """A decoded token altered where the engine commits it: one in each
    decode step, on the next row each step, so that every slot's
    requests carry some and a sample of any of them can show it."""
    for eng in unit.engines.values():
        def commit(job, nxt, orig=eng.apply_decode_result,
                   vocab=eng.cfg.vocab_size, steps=itertools.count()):
            nxt = np.array(nxt)
            row = next(steps) % len(nxt)
            nxt[row] = (nxt[row] + 1) % vocab
            return orig(job, nxt)
        eng.apply_decode_result = commit


def state_unchanged(unit):
    """A decode step that hands back its KV cache and SSM state
    unchanged."""
    import jax.numpy as jnp
    for eng in unit.engines.values():
        def step(params, midx, last, lens, pk, pv, table, ssm, tail,
                 orig=eng._decode_fn):
            keep = [None if a is None else jnp.copy(a)
                    for a in (pk, pv, ssm, tail)]
            out = orig(params, midx, last, lens, pk, pv, table, ssm, tail)
            return keep[0], keep[1], out[2], keep[2], keep[3]
        eng._decode_fn = step


def half_batch(unit):
    """Half of each decode batch left out: those rows repeat their last
    token instead of the step's."""
    for eng in unit.engines.values():
        def commit(job, nxt, orig=eng.apply_decode_result):
            nxt = np.array(nxt)
            nxt[len(nxt) // 2:] = job.last_tok[len(nxt) // 2:]
            return orig(job, nxt)
        eng.apply_decode_result = commit


FAULTS = {f.__name__: f for f in (token_altered, state_unchanged,
                                  half_batch)}


def faulted(cell: str, fault: str, seed: int) -> dict:
    """A rehearsal run of the cell with ``fault`` planted in its unit."""
    import harness
    return harness.run(cell, seed, REHEARSAL_S, False, None,
                       rehearse=True, fault=FAULTS[fault],
                       say=lambda m: None)
