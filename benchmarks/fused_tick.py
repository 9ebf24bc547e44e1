"""Fused multi-LLM tick vs serial per-engine ticks — the real runtime
(DESIGN.md §2), not the discrete-event simulator.

Colocates N same-architecture reduced LLMs on one unified KV pool and
drains an identical MIXED prefill+decode workload twice: once with the
serial tick (per-engine chunked-prefill and decode dispatches) and
once with ``fused=True`` (one jitted stacked-weights prefill sweep +
one decode sweep per iteration, zero-copy weights).  Greedy decoding
makes the generated tokens identical in both modes (asserted), so the
throughput ratios isolate the dispatch/launch amortization of the
fusion.  Alongside tokens/s the harness records:

  * weight HBM bytes (de-duplicated — the zero-copy win) and pool
    arena bytes (grown by the reclaimed weight copy in fused mode);
  * jit trace counts during the measured drain — shape-stable
    bucketing means ZERO compilations after warm-up (asserted over a
    drain of ≥ 50 ticks).

``check_fused_baseline.py`` gates CI on the aggregate fused/serial
speedup of this harness against a committed baseline JSON.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.config import replace
from repro.serving.engine import (TRACE_COUNTS, Engine, Request,
                                  init_stacked_params, unique_tree_bytes)
from repro.serving.kvcache import UnifiedKVPool
from repro.serving.mux import MuxScheduler

from benchmarks.common import save

# deterministic prompt-length cycle: spans 2-4 chunks so the prefill
# phase is a real fraction of the work, and keeps the shape buckets of
# the warm-up and measured drains identical
PROMPT_LENS = (24, 40, 56)
CHUNK_TOKENS = 16
# block-table width sized to the workload envelope (16 blocks = 256
# tokens vs a max sequence of 56+24): the attention gather scales with
# table width, and a 64-wide table for 5-block sequences buries the
# dispatch-amortization signal under 92% wasted gather traffic
MAX_BLOCKS = 16


def _build(n_models: int, fused: bool, arch: str = "qwen2-7b",
           max_slots: int = 4, pool_blocks: int = 200_000):
    base = configs.get_reduced(arch)
    pool = UnifiedKVPool(pool_blocks, base.hd, dtype=jnp.float32)
    engines = {}
    for i in range(n_models):
        cfg = replace(base, name=f"llm{i}")
        params = init_stacked_params(jax.random.PRNGKey(i), cfg,
                                     jnp.float32)
        view = pool.register_model(cfg, pool_blocks // n_models)
        engines[cfg.name] = Engine(cfg, params, view, max_slots=max_slots,
                                   chunk_tokens=CHUNK_TOKENS,
                                   max_blocks_per_seq=MAX_BLOCKS)
    return MuxScheduler(engines, pool, policy="adbs", fused=fused)


def _submit(mux: MuxScheduler, n_per_model: int, max_new: int,
            seed: int, rid_base: int = 0) -> int:
    """Submit one wave; request ids start at ``rid_base`` so ids stay
    unique across waves (the parity check keys on them)."""
    rng = np.random.default_rng(seed)
    rid = rid_base
    for name, eng in mux.engines.items():
        for j in range(n_per_model):
            plen = PROMPT_LENS[j % len(PROMPT_LENS)]
            prompt = list(rng.integers(1, eng.cfg.vocab_size, plen))
            mux.submit(Request(rid, name, prompt, max_new))
            rid += 1
    return rid - rid_base


def _drain(mux: MuxScheduler) -> float:
    t0 = time.perf_counter()
    mux.run(max_ticks=5_000)
    return time.perf_counter() - t0


def run(quick: bool = False) -> dict:
    # quick still needs enough steps for the fused/serial gap to rise
    # above tick-level noise (very short drains are warmup-bound) and a
    # ≥50-tick measured drain for the compile-constancy assertion
    n_models = 3
    max_new = 20 if quick else 24
    n_per_model = 6 if quick else 8

    out = {"n_models": n_models, "max_new": max_new,
           "n_per_model": n_per_model, "chunk_tokens": CHUNK_TOKENS,
           "prompt_lens": list(PROMPT_LENS), "modes": {}}
    outputs = {}
    for fused in (False, True):
        mux = _build(n_models, fused)
        # warmup drain: compiles the jit programs for every shape
        # bucket the measured drain revisits (both modes get the same
        # treatment) — bucketed batching makes this set bounded
        _submit(mux, n_per_model, max_new, seed=1)
        _drain(mux)
        base_prefill = mux.stats.prefill_tokens
        base_decode = mux.stats.decode_tokens
        base_ticks = mux.stats.ticks
        base_finished = len(mux.stats.finished)
        traces_warm = sum(TRACE_COUNTS.values())
        # two measured waves: enough ticks (>50 in either mode) for the
        # compile-constancy assertion to mean something
        n = 0
        wall = 0.0
        for wave in range(2):
            n += _submit(mux, n_per_model, max_new, seed=2 + wave,
                         rid_base=n)
            wall += _drain(mux)
        traces_measured = sum(TRACE_COUNTS.values()) - traces_warm
        prefill_tok = mux.stats.prefill_tokens - base_prefill
        decode_tok = mux.stats.decode_tokens - base_decode
        ticks = mux.stats.ticks - base_ticks
        finished = mux.stats.finished[base_finished:]
        assert len(finished) == n, (len(finished), n)
        assert ticks >= 50, f"need a ≥50-tick measured drain, got {ticks}"
        assert traces_measured == 0,\
            f"shape-stable serving must not re-trace ({traces_measured})"
        outputs[fused] = {r.req_id: r.output for r in finished}
        mode = "fused" if fused else "serial"
        out["modes"][mode] = {
            "prefill_tokens": prefill_tok,
            "decode_tokens": decode_tok,
            "wall_s": wall,
            "ticks": ticks,
            "prefill_tok_per_s": prefill_tok / max(wall, 1e-9),
            "decode_tok_per_s": decode_tok / max(wall, 1e-9),
            "aggregate_tok_per_s": (prefill_tok + decode_tok)
                                   / max(wall, 1e-9),
            "jit_traces_measured": traces_measured,
            "weight_hbm_bytes": unique_tree_bytes(
                [e.params for e in mux.engines.values()]),
            "pool_hbm_bytes": mux.pool.hbm_bytes(),
            "pool_head_blocks": mux.pool.n_head_blocks,
            "reclaimed_weight_bytes": mux.reclaimed_weight_bytes,
        }
        m = out["modes"][mode]
        print(f"[fused_tick] {mode:6s}: {prefill_tok} prefill + "
              f"{decode_tok} decode tokens in {wall:.2f}s over {ticks} "
              f"ticks → {m['aggregate_tok_per_s']:.1f} tok/s aggregate "
              f"({m['prefill_tok_per_s']:.1f} prefill, "
              f"{m['decode_tok_per_s']:.1f} decode; "
              f"{traces_measured} jit traces, "
              f"{m['weight_hbm_bytes'] / 1e6:.1f} MB weights, "
              f"{m['pool_hbm_bytes'] / 1e6:.0f} MB pool, "
              f"{len(mux.fused_groups)} fused groups)")

    assert len(outputs[True]) == len(outputs[False]) == 2 * n_models\
        * n_per_model, "req ids must be unique across measured waves"
    assert outputs[True] == outputs[False],\
        "fused and serial ticks must produce identical tokens"
    out["parity"] = True
    s, f = out["modes"]["serial"], out["modes"]["fused"]
    # ONE speedup number: parity makes both modes process identical
    # token counts, so every per-phase ratio reduces to the same
    # wall-clock ratio — reporting phase-wise "speedups" would imply a
    # per-phase timing that doesn't exist
    out["speedup_aggregate"] = (f["aggregate_tok_per_s"]
                                / max(s["aggregate_tok_per_s"], 1e-9))
    # the zero-copy win, in bytes: fused weights must not exceed serial
    # weights (ONE stacked tree vs N private trees), and the reclaimed
    # copy shows up as extra pool arena
    out["weight_dedup_ok"] = f["weight_hbm_bytes"] <= s["weight_hbm_bytes"]
    assert out["weight_dedup_ok"], (f["weight_hbm_bytes"],
                                    s["weight_hbm_bytes"])
    print(f"[fused_tick] fused/serial: {out['speedup_aggregate']:.2f}× "
          f"aggregate tok/s; fused pool grew by "
          f"{f['pool_head_blocks'] - s['pool_head_blocks']} "
          f"head-blocks from reclaimed weights")
    save("fused_tick", out)
    return out


if __name__ == "__main__":
    run()
