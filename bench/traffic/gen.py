"""The one traffic generator: turns a mix's parameter file
(``bench/traffic/<mix>.json``) into arrival-ordered requests.

Every seed gets the same work in another order.  The sizes and the gaps
between arrivals are fixed quantiles of the mix's distributions (n
evenly spaced probabilities, so the set follows the distribution
exactly), and the number of requests each LLM gets is fixed by its
popularity share; the seed only permutes them and draws the token ids.
So two seeds offer the same tokens at the same mean rate.  With
``stratum`` the seed permutes only within consecutive strata of that
many requests, each dealt the same values for every seed, so every seed
also offers the same work in each few seconds of the window, and runs
of different seeds spread no wider than runs of one seed.

Parameters of a mix file:

* ``arrival``: ``"poisson"`` (open loop: exponential gaps at
  ``rate_per_s``, arrivals over the run's seconds) or ``"backlog"``
  (``backlog`` requests all due at t = 0, offline batch work).
* ``popularity_alpha``: power-law popularity over the configuration's
  LLMs in their listed order, rate_i ∝ (i+1)^−alpha (MuxServe §4.2).
* ``prompt`` / ``output``: ``{"mean", "sigma", "min", "max"}`` of a
  lognormal length (ShareGPT-shaped, MuxServe §2.1), clipped to
  [min, max].
* ``stratum`` (optional): requests per stratum, consecutive in arrival
  order.  Each attribute (gap, LLM, prompt and output length) is dealt
  to the strata so that each stratum spans its whole distribution, by
  a layout that is the same for every seed; the seed permutes each
  attribute within each stratum.  Without it the seed permutes the
  whole run.
* Each request is then fitted to the configuration's ``context_tokens``
  (the longest sequence a slot holds): the prompt keeps at least room
  for min(output, context/2) tokens, and the output is cut to what is
  left.  The cut is listed in the configuration's ``reduced``.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist
from typing import Dict, List, Sequence

import numpy as np


@dataclass
class Req:
    rid: int
    model: str
    due: float             # seconds after the window opens
    prompt: List[int]
    max_new: int


def power_law_shares(n: int, alpha: float) -> List[float]:
    """Popularity shares ∝ (i+1)^−alpha (copied from the system's
    ``core/workload.power_law_rates``), normalised to sum to 1."""
    raw = [(i + 1.0) ** (-alpha) for i in range(n)]
    return [r / sum(raw) for r in raw]


def lognormal_quantiles(n: int, mean: float, sigma: float, lo: int,
                        hi: int) -> np.ndarray:
    """n lengths at evenly spaced quantiles of a lognormal with the
    given mean (the ShareGPT shape of ``core/workload.sharegpt_lengths``)."""
    mu = math.log(mean) - sigma ** 2 / 2
    z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    return np.clip(np.exp(mu + sigma * z).astype(int), lo, hi)


def exponential_gaps(n: int, rate: float) -> np.ndarray:
    """n gaps at evenly spaced quantiles of Exp(rate)."""
    p = (np.arange(n) + 0.5) / n
    return -np.log1p(-p) / rate


def split_counts(n: int, shares: Sequence[float]) -> List[int]:
    """Largest-remainder split of n requests by shares."""
    exact = [n * s for s in shares]
    counts = [int(e) for e in exact]
    order = sorted(range(len(shares)), key=lambda i: exact[i] - counts[i],
                   reverse=True)
    for i in order[:n - sum(counts)]:
        counts[i] += 1
    return counts


def request_count(mix: dict, seconds: float) -> int:
    if mix["arrival"] == "backlog":
        return int(mix["backlog"])
    return max(1, int(round(mix["rate_per_s"] * seconds)))


def rates(mix: dict, models: Sequence[str]) -> Dict[str, float]:
    """Per-LLM offered rates (req/s); a backlog offers equal shares."""
    shares = power_law_shares(len(models), mix.get("popularity_alpha", 0.0))
    total = mix.get("rate_per_s", 1.0)
    return {m: total * s for m, s in zip(models, shares)}


BASE_KEY = 0x5EED     # the fixed layout that strata are cut from


def shuffled(values: np.ndarray, rng, stratum: int, tag: int) -> np.ndarray:
    """``values`` in the seed's order: a full permutation; or, with
    ``stratum``, strata of that many values, each of which takes one
    value from every run of consecutive sorted values (which stratum
    takes which is fixed by ``tag``, the same for every seed), so each
    stratum spans the whole distribution, and the seed permutes within
    each stratum."""
    if not stratum:
        return rng.permutation(values)
    v = np.sort(values)
    m = -(-len(v) // stratum)                       # number of strata
    fixed = np.random.default_rng([BASE_KEY, tag])
    strata: List[list] = [[] for _ in range(m)]
    for g in range(0, len(v), m):
        for s, x in zip(fixed.permutation(m), v[g:g + m]):
            strata[s].append(x)
    return np.concatenate([rng.permutation(np.asarray(s, v.dtype))
                           for s in strata if s])


def generate(mix: dict, models: Sequence[str], vocab: Dict[str, int],
             context_tokens: int, seed: int, seconds: float) -> List[Req]:
    """Arrival-ordered requests of one run."""
    n = request_count(mix, seconds)
    rng = np.random.default_rng(seed)
    k = int(mix.get("stratum", 0))
    shares = power_law_shares(len(models), mix.get("popularity_alpha", 0.0))
    who = shuffled(np.repeat(np.arange(len(models)), split_counts(n, shares)),
                   rng, k, 0)
    po, oo = mix["prompt"], mix["output"]
    plen = shuffled(lognormal_quantiles(n, po["mean"], po["sigma"],
                                        po["min"], po["max"]), rng, k, 1)
    olen = shuffled(lognormal_quantiles(n, oo["mean"], oo["sigma"],
                                        oo["min"], oo["max"]), rng, k, 2)
    if mix["arrival"] == "backlog":
        due = np.zeros(n)
    else:
        due = np.cumsum(shuffled(exponential_gaps(n, mix["rate_per_s"]),
                                 rng, k, 3))
    out: List[Req] = []
    for i in range(n):
        o = int(olen[i])
        p = min(int(plen[i]), context_tokens - min(o, context_tokens // 2))
        o = min(o, context_tokens - p)
        model = models[int(who[i])]
        toks = rng.integers(1, vocab[model], p).tolist()
        out.append(Req(i, model, float(due[i]), toks, o))
    return out
