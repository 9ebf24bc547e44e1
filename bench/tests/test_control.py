"""The comparison that decides ``correct`` fails its control and each
fault the timed path of a serving cell can have, at rehearsal size."""
import json
import os

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
CELLS = [w["name"] for w in json.load(
    open(os.path.join(ROOT, "BENCHMARK.json")))["workloads"]]
SECONDS = {"phi3v-mamba2.chat-short": 4.0, "phi3v-solo.decode-backlog": 3.0}


@pytest.mark.parametrize("cell", CELLS)
def test_fp8_control_fails_a_limit_the_system_passes(cell):
    import harness
    b = harness.Bench(cell, rehearse=True, say=lambda m: None)
    ctx = b.window(11, SECONDS[cell], trace=False)
    checks = b.check(ctx, 11, control=True)
    assert harness.passed(checks)
    assert not harness.passed({n: dict(c, value=c["control"])
                               for n, c in checks.items()})


def token_altered(unit):
    """A decoded token altered where the engine commits it."""
    for eng in unit.engines.values():
        def commit(job, nxt, orig=eng.apply_decode_result,
                   vocab=eng.cfg.vocab_size):
            nxt = np.array(nxt)
            nxt[0] = (nxt[0] + 1) % vocab
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


@pytest.mark.parametrize("fault", [token_altered, state_unchanged,
                                   half_batch])
@pytest.mark.parametrize("cell", CELLS)
def test_a_broken_timed_path_is_not_correct(cell, fault):
    import harness
    r = harness.run(cell, 5, SECONDS[cell], False, None, rehearse=True,
                    fault=fault, say=lambda m: None)
    assert r["correct"] is False
