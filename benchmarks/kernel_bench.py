"""Kernel micro-benchmarks (CSV): wall time of the XLA reference path
and the Pallas kernels in interpret mode (correctness-path timing on
CPU — TPU timings require hardware; the dry-run covers the lowering).

Prints ``name,us_per_call,derived`` rows.  ``--quick`` trims shapes and
iteration counts for the per-PR CI smoke job; the JSON written by
``benchmarks.common.save`` is uploaded as a build artifact so fused
decode-path regressions are visible per PR.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.flash_prefill import flash_prefill
from repro.kernels.paged_attention import paged_decode_attention
from repro.kernels.ssd_scan import ssd_scan
from repro.models.mamba2 import ssd_chunked
from repro.serving import cache_ops
from repro.serving.kvcache import arena_block_shape


def as_arena(blocks):
    """[N, BT, hd] kernel blocks in the engine's arena form."""
    n, bt, hd = blocks.shape
    return blocks.reshape(n, *arena_block_shape(bt, hd))


def _time(fn, *args, n=5) -> float:
    fn(*args)                      # compile
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n * 1e6


def run(quick: bool = False) -> dict:
    rows = []
    key = jax.random.PRNGKey(0)
    n_iters = 2 if quick else 5

    # flash prefill (XLA oracle path at a serving-ish shape)
    b, s, h, hd = (1, 256, 4, 64) if quick else (1, 1024, 8, 64)
    q = jax.random.normal(key, (b, s, h, hd), jnp.float32)
    k = jax.random.normal(key, (b, s, h, hd), jnp.float32)
    v = jax.random.normal(key, (b, s, h, hd), jnp.float32)
    t_ref = _time(jax.jit(ref.flash_prefill_ref), q, k, v, n=n_iters)
    rows.append(("flash_prefill_xla_ref", t_ref,
                 f"b{b}s{s}h{h}d{hd}"))
    blk = 128 if quick else 256        # full-mode baseline unchanged
    t_pl = _time(lambda *a: flash_prefill(*a, block_q=blk, block_k=blk,
                                          interpret=True), q, k, v, n=1)
    rows.append(("flash_prefill_pallas_interp", t_pl, "interpret=True"))

    # paged decode attention
    bt, nb, kv = 16, (4 if quick else 8), 2
    group = 1 * kv
    pool_k = jax.random.normal(key, (nb * group * 4, bt, hd), jnp.float32)
    pool_v = jax.random.normal(key, (nb * group * 4, bt, hd), jnp.float32)
    qd = jax.random.normal(key, (4, h, hd), jnp.float32)
    table = jnp.arange(4 * nb, dtype=jnp.int32).reshape(4, nb) * group
    lens = jnp.full((4,), nb * bt, jnp.int32)
    # the XLA path runs on the same blocks in the engine's arena form
    t_ref = _time(jax.jit(lambda *a: cache_ops.paged_decode_attention(
        *a, 0, kv)), qd, as_arena(pool_k), as_arena(pool_v), table, lens,
        n=n_iters)
    rows.append(("paged_decode_xla_ref", t_ref, f"b4 blocks{nb} bt{bt}"))
    t_pl = _time(lambda *a: paged_decode_attention(
        *a, 0, n_kv=kv, interpret=True), qd, pool_k, pool_v, table, lens,
        n=1)
    rows.append(("paged_decode_pallas_interp", t_pl, "interpret=True"))

    # fused multi-LLM decode attention (DESIGN.md §2): M colocated
    # models' rows in ONE sweep vs M sequential per-model sweeps.
    M = 2 if quick else 4
    # per-model tables are DISJOINT: model m owns [m*4*nb*group, ...)
    # (each model's table spans 4 sequences × nb blocks × group ids)
    tables = [table + m * 4 * nb * group for m in range(M)]
    qs = [jax.random.normal(jax.random.PRNGKey(m), (4, h, hd), jnp.float32)
          for m in range(M)]
    pool_fk = jax.random.normal(key, (M * 4 * nb * group + 8, bt, hd),
                                jnp.float32)
    pool_fv = jax.random.normal(key, (M * 4 * nb * group + 8, bt, hd),
                                jnp.float32)

    # serial = M separate jitted dispatches (what the serial tick pays);
    # fused = ONE jitted sweep over the concatenated rows
    serial_one = jax.jit(lambda q, t, pk, pv: cache_ops.
                         paged_decode_attention(q, pk, pv, t, lens, 0, kv))

    def serial_sweep(pool_k, pool_v):
        out = None
        for m in range(M):
            out = serial_one(qs[m], tables[m], pool_k, pool_v)
        return out

    def fused_sweep(pool_k, pool_v):
        phys = jnp.concatenate([cache_ops.resolve_physical_blocks(
            tables[m], 0, kv) for m in range(M)])
        return cache_ops.fused_paged_decode_attention(
            jnp.concatenate(qs), pool_k, pool_v, phys,
            jnp.concatenate([lens] * M))

    pool_fk, pool_fv = as_arena(pool_fk), as_arena(pool_fv)
    t_serial = _time(serial_sweep, pool_fk, pool_fv, n=n_iters)
    rows.append(("fused_decode_serial_dispatch", t_serial,
                 f"{M} models x b4 blocks{nb}"))
    t_fused = _time(jax.jit(fused_sweep), pool_fk, pool_fv, n=n_iters)
    rows.append(("fused_decode_one_sweep", t_fused,
                 f"1 sweep x {M * 4} rows"))

    # SSD scan
    b2, s2, h2, p2, n2 = (1, 128, 2, 64, 32) if quick\
        else (1, 512, 4, 64, 64)
    x = jax.random.normal(key, (b2, s2, h2, p2), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(key, (b2, s2, h2))) * 0.1
    a_log = jnp.log(jnp.linspace(1.0, 4.0, h2))
    B = jax.random.normal(key, (b2, s2, 1, n2), jnp.float32)
    C = jax.random.normal(key, (b2, s2, 1, n2), jnp.float32)
    d_skip = jnp.ones((h2,))
    chunk = 64 if quick else 128       # full-mode baseline unchanged
    t_ref = _time(jax.jit(lambda *a: ssd_chunked(*a, chunk)), x, dt, a_log,
                  B, C, d_skip, n=n_iters)
    rows.append(("ssd_scan_xla_ref", t_ref, f"s{s2}h{h2}p{p2}n{n2}"))
    t_pl = _time(lambda *a: ssd_scan(*a, chunk=chunk, interpret=True), x,
                 dt, a_log, B, C, d_skip, n=1)
    rows.append(("ssd_scan_pallas_interp", t_pl, "interpret=True"))

    print("name,us_per_call,derived")
    for name, us, extra in rows:
        print(f"{name},{us:.1f},{extra}")
    from benchmarks.common import save
    path = save("kernel_bench", {"quick": quick, "rows": [
        {"name": n, "us": u, "derived": d} for n, u, d in rows]})
    print(f"[kernel_bench] results → {path}")
    return {"rows": rows}


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="small shapes / few iters (CI smoke job)")
    run(quick=ap.parse_args().quick)   # exceptions → non-zero exit
