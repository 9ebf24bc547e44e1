"""Pallas TPU kernel: decode attention over the unified head-wise pool.

This is the hot-spot of MuxServe's unified resource manager: every
colocated LLM's decode job reads scattered head-blocks from the shared
arena.  The GPU original inherits vLLM's paged-attention CUDA kernel;
the TPU rethink uses *scalar-prefetched block tables*
(``PrefetchScalarGridSpec``) so the physical block id for grid step
(b, h, j) — ``table[b, j] + layer*KV + kv_head`` — is known early
enough for the pipeline to stream the right ``[BLOCK_TOKENS, head_dim]``
tile HBM→VMEM while the VPU/MXU works on the previous one.

Grid: (batch, kv_heads, max_blocks) with the block axis sequential; the
q-head group of each kv head ([group, hd] — the GQA sublane batch)
stays resident in VMEM and online-softmax accumulators live in scratch.

A ``[16, 128]`` head-block is exactly the bf16 minimum tile.  Streaming
one head-block per step is DMA-latency-bound for long contexts; the
§Perf hillclimb evaluates BLOCK_TOKENS=64 pools (4 tiles per fetch) —
the pool granularity is a config knob, not a kernel assumption.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.paging import resolve_physical_blocks

NEG_INF = -1e30


def _paged_kernel(phys_ref, lens_ref,                # scalar prefetch
                  q_ref, k_ref, v_ref, o_ref,
                  m_ref, l_ref, acc_ref, *,
                  bt: int, n_blocks: int, scale: float, group: int):
    b = pl.program_id(0)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    seq_len = lens_ref[b]
    run = j * bt < seq_len

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)              # [group, hd]
        k = k_ref[0].astype(jnp.float32)                 # [bt, hd]
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        t_pos = j * bt + jax.lax.broadcasted_iota(jnp.int32, (group, bt), 1)
        s = jnp.where(t_pos < seq_len, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(j == n_blocks - 1)
    def _finalize():
        o_ref[0, 0] = (acc_ref[...] /
                       jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def fused_paged_decode_attention(q, pool_k, pool_v, phys, seq_lens, *,
                                 interpret: bool = False):
    """Multi-sequence decode attention over pre-resolved physical blocks.

    The fused multi-LLM tick (DESIGN.md §2) concatenates the decode
    rows of every colocated same-architecture engine into one batch;
    each row's ``phys`` entries already carry the (model, layer) →
    physical-id resolution from the unified pool, so one kernel sweep
    serves all colocated LLMs at once instead of one launch per model.

    q: [B, H, hd] (one post-RoPE query token per row; rows may belong
        to different models)
    pool_k/v: [N, BT, hd] head-block arena
    phys: [B, n_kv, max_blocks] int32 physical head-block ids (invalid
        entries must point at a valid block — e.g. 0 — and be masked
        via seq_lens)
    seq_lens: [B] (length including the current token)
    """
    B, H, hd = q.shape
    N, BT, _ = pool_k.shape
    n_kv, max_blocks = phys.shape[1], phys.shape[2]
    group = H // n_kv
    scale = 1.0 / math.sqrt(hd)

    qt = q.reshape(B, n_kv, group, hd)
    kernel = functools.partial(_paged_kernel, bt=BT, n_blocks=max_blocks,
                               scale=scale, group=group)

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, n_kv, max_blocks),
            in_specs=[
                pl.BlockSpec((1, 1, group, hd),
                             lambda b, h, j, *refs: (b, h, 0, 0)),
                pl.BlockSpec((1, BT, hd),
                             lambda b, h, j, phys_ref, lens_ref:
                                 (phys_ref[b, h, j], 0, 0)),
                pl.BlockSpec((1, BT, hd),
                             lambda b, h, j, phys_ref, lens_ref:
                                 (phys_ref[b, h, j], 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, group, hd),
                                   lambda b, h, j, *refs: (b, h, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((group, 1), jnp.float32),
                pltpu.VMEM((group, 1), jnp.float32),
                pltpu.VMEM((group, hd), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, n_kv, group, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(phys, seq_lens, qt, pool_k, pool_v)
    return out.reshape(B, H, hd)


@functools.partial(jax.jit, static_argnames=("n_kv", "interpret"))
def paged_decode_attention(q, pool_k, pool_v, table, seq_lens, layer, *,
                           n_kv: int, interpret: bool = False):
    """Decode attention against the paged pool (single-model view).

    q: [B, H, hd] (one post-RoPE query token per sequence)
    pool_k/v: [N, BT, hd] head-block arena
    table: [B, max_blocks] int32 group bases (−1 padded)
    seq_lens: [B] (length including the current token)
    layer: int32 scalar — attention-layer cache index
    """
    # padded table entries resolve to block 0 but are masked by
    # seq_lens in-kernel (shared resolution with the XLA oracle)
    phys = resolve_physical_blocks(table, layer, n_kv)
    return fused_paged_decode_attention(q, pool_k, pool_v, phys, seq_lens,
                                        interpret=interpret)
