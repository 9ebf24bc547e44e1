"""Builds the system under test for one configuration file, with its
weights made from the run's seed.

The unit is assembled from the serving system's own parts, as its
``build_unit_from_specs`` does (one ``UnifiedKVPool``,
one ``Engine`` per LLM with a quota split by popularity, one
``MuxScheduler``), except that the weights come from the benchmark:
each LLM's tree is made on the device in one jitted call from the
run's seed (``bench/arch/<arch>.py``), in the type it is served in, so
that the reference can rebuild the same weights without taking anything
the system made.
"""
from __future__ import annotations

import copy
import importlib
from typing import Dict, List

import numpy as np


def arch_module(name: str):
    return importlib.import_module(f"arch.{name}")


def effective_config(config: dict, rehearse: bool) -> dict:
    """The configuration as run: published sizes, or the rehearsal's
    reduced sizes (CPU rehearsal mode only)."""
    cfg = copy.deepcopy(config)
    if rehearse:
        r = cfg["rehearsal"]
        for llm in cfg["llms"]:
            llm["config"].update(r["llms"][llm["name"]])
        cfg["serving"].update(r.get("serving", {}))
        cfg["check"]["gap_limit"] = r["gap_limit"]
    return cfg


def model_key(seed: int, index: int):
    """The PRNG key of LLM ``index``'s weights: any whole-number seed
    (beyond 32 bits too) is mixed down to a 31-bit key."""
    import jax
    mixed = np.random.SeedSequence([seed, index]).generate_state(1)[0]
    return jax.random.PRNGKey(int(mixed) & 0x7FFFFFFF)


_INIT = {}


def make_weights(llm: dict, seed: int, index: int, dtype: str):
    """One jitted call per LLM, cached per (arch, sizes, dtype)."""
    import jax
    import jax.numpy as jnp
    arch = arch_module(llm["arch"])
    key = (llm["arch"], repr(sorted(llm["config"].items())), dtype)
    if key not in _INIT:
        c = llm["config"]
        _INIT[key] = jax.jit(lambda k: arch.init_weights(k, c, jnp.dtype(dtype)))
    return _INIT[key](model_key(seed, index))


def program_model_config(llm: dict):
    """The serving system's ``ModelConfig`` of one LLM: its arch
    module's keywords, with the name and source of its entry."""
    from repro.config import ModelConfig
    kw = arch_module(llm["arch"]).program_config(llm["config"])
    return ModelConfig(name=llm["name"], source=llm["source"], **kw)


def build(config: dict, rates: Dict[str, float], seed: int):
    """The unit: pool, engines and scheduler, weights from ``seed``."""
    import jax.numpy as jnp
    from repro.serving.engine import Engine
    from repro.serving.kvcache import UnifiedKVPool
    from repro.serving.mux import MuxScheduler
    sv = config["serving"]
    cfgs = [program_model_config(llm) for llm in config["llms"]]
    head_dims = {c.hd for c in cfgs if not c.attn_free} or {64}
    if len(head_dims) != 1:
        raise ValueError(f"{config['name']}: the unit's pool takes one "
                         f"head_dim, its LLMs have {sorted(head_dims)}")
    pool = UnifiedKVPool(sv["pool_blocks"], head_dims.pop(),
                         dtype=jnp.dtype(config["dtype"]).type)
    # quota split by popularity, floored as build_unit_from_specs does
    total = sum(rates.values()) or 1.0
    floor = max(sv["pool_blocks"] // (8 * len(cfgs)), 1)
    engines = {}
    for i, (llm, cfg) in enumerate(zip(config["llms"], cfgs)):
        quota = max(int(sv["pool_blocks"] * rates[llm["name"]] / total),
                    floor)
        view = pool.register_model(cfg, quota)
        engines[llm["name"]] = Engine(
            cfg, make_weights(llm, seed, i, config["dtype"]), view,
            max_slots=sv["max_slots"],
            max_blocks_per_seq=sv["max_blocks_per_seq"],
            chunk_tokens=sv["chunk_tokens"])
    return MuxScheduler(engines, pool, policy=sv["policy"])


def warm(unit, max_slots: int) -> List[int]:
    """Compile every program the window can use, and no other: for
    each LLM and each row count 1..max_slots, one chunked prefill of
    that many one-block prompts and one decode step over the same rows
    (which covers the power-of-two row buckets and the per-row-count
    host slices).  Runs on the engines directly, so the scheduler's
    quotas and counters stay untouched.  Returns the row counts."""
    from repro.serving.engine import Request
    rows = list(range(1, max_slots + 1))
    rng = np.random.default_rng(0)
    for name, eng in unit.engines.items():
        for b in rows:
            probe = [Request(-1 - i, name, rng.integers(
                1, eng.cfg.vocab_size, 16).tolist(), 2) for i in range(b)]
            eng.prefill(probe)
            while eng.has_prefill_work():
                eng.prefill([])
            while eng.has_decode_work():
                eng.decode()
            eng.finished.clear()
    return rows
