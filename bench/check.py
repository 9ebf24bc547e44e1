"""The comparison that decides ``correct``.

After the window, a sample of the requests the system finished — drawn
from the seed, the longest of each LLM always in it — is run through the
plain float32 reference (``bench/arch/<arch>.py``, matmuls at
``highest`` precision) over each prompt followed by the tokens the
system served.  Every served token was picked greedily by the system,
so in an exact system it is the reference's best token at its position;
the number compared is, per LLM, the widest gap by which a served
token's reference logit lies below the reference's best logit there.
The first token of each request comes from the chunked prefill step and
every later one from a decode step through the paged KV cache or the
SSM state, so the comparison covers each LLM's timed step programs at
the sizes the window ran them.

The reference rebuilds each LLM's weights from the seed with the
benchmark's own generator; it takes nothing the system made.

The control (``control=True``, run by ``bench/calibrate.py`` and the
tests, never by a benchmark run) is the same reference with every
weight matrix rounded to float8 e4m3 with a per-output-channel scale —
the step below the configuration's bfloat16.  At each position it reads
the gap of the token the fp8 forward puts first.
"""
from __future__ import annotations

from typing import Dict, List, Optional

import numpy as np

from unit import arch_module, make_weights

_FNS: Dict[tuple, object] = {}


def fp8(w):
    """Round a float32 [..., in, out] matrix to float8 e4m3, scaled per
    output channel so its largest entry maps to e4m3's largest."""
    import jax.numpy as jnp
    s = jnp.max(jnp.abs(w), axis=-2, keepdims=True) / 448.0
    s = jnp.where(s == 0, 1.0, s)
    return (w / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _bucket(n: int) -> int:
    b = 128
    while b < n:
        b *= 2
    return b


def _logits(llm: dict, quantize):
    import jax
    key = (llm["name"], repr(llm["config"]), quantize is not None)
    if key not in _FNS:
        arch, c = arch_module(llm["arch"]), llm["config"]
        _FNS[key] = jax.jit(lambda w, t: arch.forward(w, c, t, quantize))
    return _FNS[key]


def sample(records, llm_name: str, seed: int, budget_tokens: int,
           max_requests: int) -> List:
    """Finished requests of one LLM: the longest output first, then in
    an order drawn from the seed, until the token budget is spent."""
    done = [r for r in records if r.model == llm_name and r.finished
            and len(r.output) > 0]
    if not done:
        return []
    longest = max(done, key=lambda r: (len(r.output), -r.rid))
    rest = [r for r in done if r is not longest]
    order = np.random.default_rng([seed, 0xC4EC]).permutation(len(rest))
    picked, tokens = [longest], len(longest.output)
    for i in order:
        if tokens >= budget_tokens or len(picked) >= max_requests:
            break
        picked.append(rest[i])
        tokens += len(rest[i].output)
    return picked


def gaps(llm: dict, index: int, seed: int, dtype: str, reqs,
         control: bool = False) -> Dict[str, Optional[float]]:
    """Widest gap of the served tokens (and of the control's picks)
    against the float32 reference, over ``reqs``."""
    import jax
    import jax.numpy as jnp
    if not reqs:
        return {"served": None, "control": None, "tokens": 0}
    w = make_weights(llm, seed, index, dtype)
    ref_fn = _logits(llm, None)
    q_fn = _logits(llm, fp8) if control else None
    widest, widest_q, tokens = 0.0, 0.0, 0
    with jax.default_matmul_precision("highest"):
        for r in reqs:
            seq = list(r.prompt) + list(r.output[:-1])
            toks = np.zeros((1, _bucket(len(seq))), np.int32)
            toks[0, :len(seq)] = seq
            pos = np.arange(len(r.prompt) - 1, len(seq))
            ref = np.asarray(ref_fn(w, jnp.asarray(toks))[0], np.float32)[pos]
            best = ref.max(-1)
            served = np.asarray(r.output)
            g = best - ref[np.arange(len(pos)), served]
            widest = max(widest, float(np.nanmax(np.where(
                np.isfinite(g), g, np.inf))))
            tokens += len(pos)
            if q_fn is not None:
                q = np.asarray(q_fn(w, jnp.asarray(toks))[0], np.float32)[pos]
                pick = q.argmax(-1)
                widest_q = max(widest_q, float(
                    (best - ref[np.arange(len(pos)), pick]).max()))
    del w
    return {"served": widest, "control": widest_q if control else None,
            "tokens": tokens}
