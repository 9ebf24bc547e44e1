#!/usr/bin/env python3
"""On-chip smoke test: two published-width LLMs colocated on one TPU chip.

Drives the serving main path once, through the entry points
``launch/serve.py`` uses — ``build_unit_from_specs`` → ``MuxScheduler``
→ ``Engine`` → ``UnifiedKVPool`` — with phi-3-vision-4.2b (text path,
paged KV at head_dim 96) and mamba2-2.7b (SSM state) at their published
widths, bfloat16, random weights from a seed.  Then it checks what came
out:

  a. device  — a TPU must be present; there is no CPU fallback;
  b. serving — a seeded Poisson trace served under the wall clock:
               every request finishes, none is shed, and every pool
               block is free at the end;
  c. logits  — for one prompt per model, the engine's own jitted
               chunked prefill (the function serving calls) against a
               float32 reference forward (``models/transformer.forward``)
               on the same seeded weights;
  d. kernels — every Pallas kernel of the main path, compiled for the
               chip (not interpreted), against its jnp oracle at real
               widths.

Usage (from the checkout root, one TPU chip, one process):

    python3 chip_smoke.py

A failed check exits non-zero.  On success the last line of stdout is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}``.
Compiled programs go to the persistent cache of
``repro.launch.compile_cache`` (``JAX_COMPILATION_CACHE_DIR`` or
``.jax_cache/``), so a second run compiles less.
"""
from __future__ import annotations

import collections
import dataclasses
import gc
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

# --- the unit (phase b) ----------------------------------------------------
# Trace seed: 12 requests, 6 per LLM; two pairs of mamba2 requests
# arrive 32 ms apart, so both of its slots serve at once.
SEED = 7
ARCHS = ("phi-3-vision-4.2b", "mamba2-2.7b")   # most popular first
MAX_RATE = 2.0             # req/s of the most popular LLM
ALPHA = 1.0                # power-law popularity exponent
HORIZON = 4.0              # seconds of arrivals
PROMPT_MIN, PROMPT_MAX = 128, 512
MAX_NEW = 16
CHUNK_TOKENS = 256
# Memory plan for 16 GiB of HBM: 13.05 GB of bf16 weights (7.65 + 5.41),
# SSM state 2 slots × 168 MB, and an arena for phi-3's KV only (mamba2's
# state lives off-arena): two slots × 33 token-blocks (528 tokens) ×
# 1024 head-blocks per token-block = 67,584 head-blocks × 6 KiB = 415 MB.
# The step programs add up to ~1.8 GB of temporaries (their compiled
# memory_analysis for v5e), which is why there are two slots and not more.
MAX_SLOTS = 2
POOL_BLOCKS = MAX_SLOTS * -(-(PROMPT_MAX + MAX_NEW) // 16) * 32 * 32

# --- correctness (phase c) -------------------------------------------------
# 1.5 chunks: the second, partial chunk reads the first from the pool.
# SSM probes take whole SSD chunks (the reference scan needs them).
PROBE_LEN = 384
# The engine computes in bfloat16 (weights, activations, residual stream
# and KV; SSM state in float32).  How far bf16 alone moves the logits is
# measured, not assumed: the same reference forward run in bf16 gives the
# error a correct bf16 implementation has on these weights (on a CPU, at
# the published depths and reduced widths: 1.6% of the logit scale for 32
# dense layers, 7% for 64 SSM layers).  The engine may be off the float32
# reference by at most twice that, and never by less than a bf16 ulp.
# A lower precision than bf16 (fp8: 16x coarser) or an indexing bug
# fails it.
LOGIT_TOL_FACTOR = 2.0
LOGIT_TOL_FLOOR = 2.0 ** -8

# --- kernels (phase d) -----------------------------------------------------
# Kernel and oracle read the same bf16 inputs and accumulate in float32.
# The kernel writes bf16, so rounding alone moves an output by up to 2^-8
# (3.9e-3) of the largest value in its batch row, and it may run its
# matmuls as single bf16 MXU passes.  The error is taken per batch row (one
# sequence) against that row's own largest oracle value, so the small
# outputs of a long row are not measured against the large ones of a
# short row.  Readings per row on a TPU v5e: 1.9e-3 (flash prefill) to
# 4.9e-3 (SSD scan), near the rounding bound.  The limit is twice the
# largest reading and 2.5 times the bound.
KERNEL_TOL = 1e-2    # max over rows of max|kernel − oracle| / max|oracle|


class CompileTimes:
    """First-call cost per jitted program, from JAX's compile events
    (tracing + lowering + backend compile, or cache retrieval)."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        self.secs = collections.defaultdict(float)
        self.count = collections.Counter()

    def __call__(self, event, duration, **kw):
        if event not in self.EVENTS:
            return
        name = str(kw.get("fun_name", "?"))
        if name.startswith("jit(") and name.endswith(")"):
            name = name[4:-1]
        elif name.startswith("jit_"):
            name = name[4:]
        self.secs[name] += duration
        if event == self.EVENTS[-1]:
            self.count[name] += 1

    def steps(self):
        """(step impl, programs, seconds) for the engine's step kinds."""
        return sorted((n, self.count[n], s) for n, s in self.secs.items()
                      if n.endswith("_impl"))

    def total(self) -> float:
        return sum(self.secs.values())


def fail(msg: str) -> None:
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr, flush=True)
    raise SystemExit(1)


def say(msg: str) -> None:
    print(msg, flush=True)


def device_info():
    """Phase a: the chip JAX found, or exit."""
    import jax
    devs = jax.devices()
    platform = devs[0].platform
    if platform != "tpu":
        fail(f"no TPU found: JAX's devices are {len(devs)} × {platform} "
             f"({devs[0].device_kind}); this smoke test has no CPU fallback")
    return platform, devs[0].device_kind, len(devs)


def hbm() -> dict:
    import jax
    st = jax.devices()[0].memory_stats() or {}
    return {k: st.get(k) for k in ("bytes_in_use", "peak_bytes_in_use",
                                   "bytes_limit")}


def make_trace(names, seed=SEED):
    """Seeded power-law Poisson trace, prompts clipped to 128–512."""
    from repro.core.workload import poisson_trace, power_law_rates
    rates = power_law_rates(list(names), ALPHA, MAX_RATE)
    wl = poisson_trace(rates, HORIZON, seed=seed, mean_prompt=256,
                       mean_output=MAX_NEW, max_len=PROMPT_MAX)
    wl.requests = [dataclasses.replace(
        r, prompt_len=int(np.clip(r.prompt_len, PROMPT_MIN, PROMPT_MAX)))
        for r in wl.requests]
    return wl


def serve_phase(archs, *, reduced, pool_blocks, max_slots, chunk_tokens,
                seed=SEED):
    """Phase b: build the unit, serve the trace, check dispositions and
    that the pool drained.  Returns (unit, report)."""
    from repro.serving.driver import build_unit_from_specs, serve_workload
    wl = make_trace(archs, seed)
    unit = build_unit_from_specs(
        [(a, a, wl.rates[a]) for a in archs], pool_blocks=pool_blocks,
        max_slots=max_slots, chunk_tokens=chunk_tokens, seed=seed,
        policy="adbs", reduced=reduced)
    # built stacked, the weights peak at their own size: no second copy
    say(f"[b] HBM after building the unit {hbm()}")
    report = serve_workload([unit], wl, seed=seed, max_new_cap=MAX_NEW)
    agg = report.aggregate
    say(f"[b] served {agg.finished}/{agg.submitted} requests, "
        f"shed {agg.shed}, cancelled {agg.cancelled}, "
        f"{report.ticks} ticks, {report.wall_s:.3f} s wall")
    for name, r in report.per_llm.items():
        say(f"[b]   {name}: finished {r.finished}/{r.submitted}  "
            f"TTFT p50 {r.ttft.p50 * 1e3:.2f} ms  "
            f"TPOT p50 {r.tpot.p50 * 1e3:.2f} ms")
    pool = unit.pool
    free, total = pool.allocator.free_blocks, pool.n_head_blocks
    used = {n: v.used for n, v in pool.views.items()}
    say(f"[b] pool: {free}/{total} head-blocks free at drain, "
        f"view charges {used}")
    if agg.submitted == 0 or agg.finished != agg.submitted:
        fail(f"finished {agg.finished} of {agg.submitted} submitted")
    if agg.shed:
        fail(f"{agg.shed} requests shed: {report.aggregate.shed_reasons}")
    if free != total or any(used.values()):
        fail(f"pool not drained: {free}/{total} free, charges {used}")
    return unit, report


def engine_prefill_logits(eng, prompt):
    """Phase c, engine side: run ``prompt`` through the engine's jitted
    chunked-prefill step (``Engine._chunk_fn``, what serving calls when
    ``chunk_tokens`` is set) on a fresh single-request table.  Returns
    {position: float32 logits} at the end of every chunk."""
    import jax.numpy as jnp
    C, S = eng.chunk_tokens, len(prompt)
    out = {}
    if eng.cfg.ssm:
        # the probe's state: slot 0 of the drained engine, named by the
        # table's one column (a pure-SSM model has no KV blocks)
        table = jnp.zeros((1, 1), jnp.int32)
    else:
        view, pool = eng.view, eng.pool
        sid = -1                      # probe sequence; the view is empty
        if not view.append_tokens(sid, S):
            fail(f"{eng.cfg.name}: no pool room for a {S}-token probe")
        table = jnp.asarray(view.block_table([sid], eng.max_blocks))
    for off in range(0, S, C):
        n = min(C, S - off)
        toks = np.zeros((1, C), np.int32)
        toks[0, :n] = prompt[off:off + n]
        toks, offs = jnp.asarray(toks), jnp.asarray([off], jnp.int32)
        clens = jnp.asarray([n], jnp.int32)
        if eng.cfg.ssm:
            logits, eng.ssm_state, eng.conv_tail = eng._chunk_fn(
                eng.params, eng.model_index, toks, offs, clens, table,
                eng.ssm_state, eng.conv_tail)
        else:
            pool.k, pool.v, logits = eng._chunk_fn(
                eng.params, eng.model_index, toks, offs, clens, pool.k,
                pool.v, table)
        out[off + n - 1] = np.asarray(logits[0], np.float32)
    if not eng.cfg.ssm:
        view.free_seq(sid)
    return out


def reference_logits(cfg, seed, dtype, prompt):
    """Phase c, reference side: ``models/transformer.forward`` at
    ``highest`` matmul precision on the engine's seeded weights, in
    float32 and — to measure what bf16 alone costs — in bf16.  For the
    float32 pass the embedding and head are upcast whole, and each
    layer's bf16 weights are upcast inside the layer scan, one layer at
    a time, so a float32 copy of the whole tree (15 GB for phi-3) never
    exists.  Returns (float32 logits, bf16 logits), both [S, vocab]."""
    import jax
    import jax.numpy as jnp
    from repro.models.transformer import forward
    from repro.serving.engine import init_stacked_params
    params = init_stacked_params(jax.random.PRNGKey(seed), cfg, dtype)

    def ref(p, toks, compute):
        p = jax.tree_util.tree_map(lambda a: a[0], p)
        # float32: cast the embedding and head only (the layers promote
        # inside the scan); bf16: everything, a no-op for bf16 weights
        cast = p if compute == jnp.bfloat16 else {"tok": p["tok"]}
        p.update(jax.tree_util.tree_map(lambda a: a.astype(compute), cast))
        logits, _ = forward(p, cfg, toks, remat=False)
        return logits[0].astype(jnp.float32)

    toks = jnp.asarray([prompt], jnp.int32)
    with jax.default_matmul_precision("highest"):
        return tuple(np.asarray(jax.jit(ref, static_argnums=2)(
            params, toks, c)) for c in (jnp.float32, jnp.bfloat16))


def probe_len(cfg) -> int:
    return 2 * cfg.ssm.chunk_size if cfg.ssm else PROBE_LEN


def logits_phase(unit, archs, seed=SEED):
    """Phase c, engine side: one seeded probe prompt per model, run
    while the unit lives.  ``compare_probes`` checks them after the
    unit is released."""
    rng = np.random.default_rng(seed)
    probes = {}
    for i, name in enumerate(archs):
        eng = unit.engines[name]
        prompt = [int(t) for t in rng.integers(1, eng.cfg.vocab_size,
                                               probe_len(eng.cfg))]
        probes[name] = (eng.cfg, seed + i, eng.params["tok"]["embed"].dtype,
                        prompt, engine_prefill_logits(eng, prompt))
    return probes


def compare_probes(probes):
    """Phase c, comparison: engine logits against the float32 reference
    at every chunk end, under the tolerance set beside
    ``LOGIT_TOL_FACTOR``.  Returns the worst error / tolerance ratio."""
    worst = 0.0
    for name, (cfg, seed, dtype, prompt, got) in probes.items():
        ref32, ref16 = reference_logits(cfg, seed, dtype, prompt)
        pos = sorted(got)
        scale = max(float(np.abs(ref32[p]).max()) for p in pos)
        err = max(float(np.abs(got[p] - ref32[p]).max()) for p in pos)
        err16 = max(float(np.abs(ref16[p] - ref32[p]).max()) for p in pos)
        rel, rel16 = err / scale, err16 / scale
        tol = max(LOGIT_TOL_FACTOR * rel16, LOGIT_TOL_FLOOR)
        worst = max(worst, rel / tol)
        say(f"[c] {name}: logits at positions {pos}: max abs err "
            f"{err:.5g} (rel {rel:.5g} of max |ref| {scale:.5g}); a bf16 "
            f"forward is off by rel {rel16:.5g}; tol {tol:.5g}")
        if not np.isfinite(rel) or rel > tol:
            fail(f"{name}: prefill logits off the float32 reference by "
                 f"rel {rel:.5g} > {tol:.5g}")
    return worst


def _check_kernel(name, kernel_fn, oracle_fn, args, interpret, tol):
    import jax
    import jax.numpy as jnp
    fn = jax.jit(lambda *a: kernel_fn(*a, interpret=interpret))
    compiled = "tpu_custom_call" in fn.lower(*args).as_text()
    if not interpret and not compiled:
        fail(f"{name}: no Mosaic custom call in the lowered program")
    outs = jax.tree_util.tree_leaves(fn(*args))
    # the oracle runs in float32 on the same (bf16-valued) inputs
    f32 = [a.astype(jnp.float32) if jnp.issubdtype(a.dtype, jnp.floating)
           else a for a in args]
    with jax.default_matmul_precision("highest"):
        refs = jax.tree_util.tree_leaves(jax.jit(oracle_fn)(*f32))
    errs = []
    for o, r in zip(outs, refs):
        o, r = (np.asarray(a, np.float32).reshape(a.shape[0], -1)
                for a in (o, r))
        errs.append(np.abs(o - r).max(1) / np.abs(r).max(1))
    worst = float(np.max(np.concatenate(errs)))      # a NaN stays NaN
    say(f"[d] {name}: {'Mosaic-compiled' if compiled else 'interpreted'}, "
        f"max err {worst:.5g} of its row's largest oracle value "
        f"(tol {tol})")
    if not np.isfinite(worst) or worst > tol:
        fail(f"{name}: off its oracle by {worst:.5g} > {tol}")


def kernel_phase(*, interpret=False, small=False, tol=KERNEL_TOL):
    """Phase d: each Pallas kernel against its oracle at real widths
    (``small`` shrinks the shapes for an interpreted CPU rehearsal)."""
    import jax
    import jax.numpy as jnp
    from repro.kernels.flash_prefill import (flash_prefill,
                                             fused_paged_flash_prefill)
    from repro.kernels.paged_attention import paged_decode_attention
    from repro.kernels.ssd_scan import ssd_scan
    from repro.models.layers import causal_attention
    from repro.models.mamba2 import ssd_chunked
    from repro.paging import paged_decode_attention as paged_oracle
    from repro.paging import resolve_physical_blocks
    from repro.serving.cache_ops import fused_paged_chunk_attention
    from repro.serving.kvcache import arena_block_shape

    bf = jnp.bfloat16
    keys = iter(jax.random.split(jax.random.PRNGKey(SEED), 64))
    rng = np.random.default_rng(SEED)

    def normal(shape, dtype=bf):
        return jax.random.normal(next(keys), shape, jnp.float32).astype(dtype)

    BT, layer = 16, 3                    # BLOCK_TOKENS, a middle layer
    NB, N, B, S, C = (64, 8192, 4, 512, 128) if not small else \
        (4, 256, 2, 64, 16)

    def as_arena(blocks):
        # the kernels take [N, BT, hd] blocks; the engine's oracles are
        # checked on the same bytes in the serving arena's form
        return blocks.reshape(N, *arena_block_shape(BT, blocks.shape[-1]))

    def table(rows, n_kv, lens):
        # scattered group bases, −1 past each row's length
        hi = N - (layer + 1) * n_kv
        t = rng.integers(0, hi, (rows, NB)).astype(np.int32)
        t[np.arange(NB)[None, :] * BT >= np.asarray(lens)[:, None]] = -1
        return jnp.asarray(t)

    # paged decode: phi-3 (MHA, group 1, hd 96) and a GQA hd-128 layout
    # (qwen2-7b: 28 query heads over 4 kv heads)
    for H, KV, hd in ((32, 32, 96), (28, 4, 128)):
        if small:
            H, KV = H // 4, max(KV // 4, 1)
        lens = np.linspace(1, NB * BT, B).astype(np.int32)
        _check_kernel(
            f"paged_decode_attention hd{hd} H{H}/KV{KV} BT{BT}",
            lambda q, k, v, t, n, interpret, KV=KV: paged_decode_attention(
                q, k, v, t, n, layer, n_kv=KV, interpret=interpret),
            lambda q, k, v, t, n, KV=KV: paged_oracle(
                q, as_arena(k), as_arena(v), t, n, layer, KV),
            (normal((B, H, hd)), normal((N, BT, hd)), normal((N, BT, hd)),
             table(B, KV, lens), jnp.asarray(lens)), interpret, tol)
    # paged chunked prefill at phi-3's widths: a fresh prompt and a
    # chunk that resumes mid-prompt, reading earlier blocks
    H, KV, hd = (32, 32, 96) if not small else (8, 8, 96)
    offs = np.array([0, 2 * BT + 5], np.int32)
    phys = resolve_physical_blocks(table(2, KV, offs + C), layer, KV)
    _check_kernel(
        f"fused_paged_flash_prefill hd{hd} H{H} C{C}",
        lambda q, k, v, p, o, interpret: fused_paged_flash_prefill(
            q, k, v, p, o, interpret=interpret),
        lambda q, k, v, p, o: fused_paged_chunk_attention(
            q, as_arena(k), as_arena(v), p, o),
        (normal((2, C, H, hd)), normal((N, BT, hd)), normal((N, BT, hd)),
         phys, jnp.asarray(offs)), interpret, tol)
    # dense flash prefill at phi-3's widths with the default blocks
    _check_kernel(
        f"flash_prefill hd{hd} H{H} S{S}",
        lambda q, k, v, interpret: flash_prefill(q, k, v,
                                                 interpret=interpret),
        causal_attention,
        tuple(normal((1, S, H, hd)) for _ in range(3)), interpret, tol)
    # SSD scan at mamba2-2.7b's widths: 80 heads × P 64, d_state 128,
    # one B/C group, chunk 256
    Hs, P, Ns, chunk = (80, 64, 128, 256) if not small else (4, 64, 128, 32)
    _check_kernel(
        f"ssd_scan H{Hs} P{P} N{Ns} chunk{chunk}",
        lambda *a, interpret: ssd_scan(*a, chunk=chunk, interpret=interpret),
        lambda *a: ssd_chunked(*a, chunk),
        (normal((1, S, Hs, P)),
         jax.nn.softplus(normal((1, S, Hs), jnp.float32) - 2.0),
         jnp.log(jnp.linspace(1.0, 16.0, Hs, dtype=jnp.float32)),
         normal((1, S, 1, Ns)), normal((1, S, 1, Ns)),
         jnp.ones((Hs,), jnp.float32)), interpret, tol)


def main() -> int:
    platform, kind, count = device_info()
    say(f"[a] device: {platform} {kind} × {count}")

    from jax import monitoring
    from repro.launch.compile_cache import enable_compile_cache
    from repro.serving.engine import TRACE_COUNTS, unique_tree_bytes
    cache_dir = enable_compile_cache()
    say(f"[a] compile cache: {cache_dir}")
    times = CompileTimes()
    monitoring.register_event_duration_secs_listener(times)
    t0 = time.perf_counter()

    unit, report = serve_phase(ARCHS, reduced=False, pool_blocks=POOL_BLOCKS,
                               max_slots=MAX_SLOTS, chunk_tokens=CHUNK_TOKENS)
    weights = unique_tree_bytes([e.params for e in unit.engines.values()])
    say(f"[b] weights {weights} B, arena {unit.pool.hbm_bytes()} B "
        f"({unit.pool.n_head_blocks} head-blocks of head_dim "
        f"{unit.pool.head_dim}, {unit.pool.dtype.__name__})")
    for name, programs, secs in times.steps():
        say(f"[b] compile {name}: {programs} programs, {secs:.3f} s")
    say(f"[b] TRACE_COUNTS {dict(TRACE_COUNTS)}")
    say(f"[b] HBM after serving {hbm()}")

    probes = logits_phase(unit, ARCHS)
    del unit, report
    gc.collect()
    say(f"[c] unit released: HBM {hbm()}")
    compare_probes(probes)

    kernel_phase()
    stats = hbm()
    say(f"[d] HBM at exit {stats}")
    say(f"[summary] compile seconds {times.total():.3f} "
        f"(engine steps {sum(s for _, _, s in times.steps()):.3f}), "
        f"peak_bytes_in_use {stats['peak_bytes_in_use']}, "
        f"wall {time.perf_counter() - t0:.3f} s")
    print(json.dumps({"ok": True, "device": {"platform": platform,
                                             "kind": kind, "count": count}}),
          flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
