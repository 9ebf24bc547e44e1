"""Pallas TPU kernel: blocked causal flash attention (prefill phase).

The prefill job is compute-bound (paper §2.1) — this kernel keeps the
MXU busy with [block_q × hd] · [hd × block_k] matmuls while the online
softmax keeps the working set in VMEM.

Grid: (batch, q_heads, num_q_blocks, num_k_blocks), with the k-block
axis innermost/sequential; (m, l, acc) accumulators live in VMEM scratch
and persist across the k-block iterations.  GQA is handled in the
index maps: q head h reads kv head h // group.

Block sizes default to (256 q × 512 k) at head_dim 128 →
q(64KB) + k(128KB) + v(128KB) + acc(128KB f32) ≈ 0.5MB VMEM per step,
well inside the ~16MB/core budget while giving 256×512 MXU tiles.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  block_q: int, block_k: int, n_kb: int, scale: float,
                  window: int | None):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_pos = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    k_pos = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    mask = k_pos <= q_pos
    if window is not None:
        mask &= k_pos > q_pos - window

    # skip fully-masked blocks (start of the window / above the diagonal)
    run = (ki * block_k <= qi * block_q + block_q - 1)
    if window is not None:
        run &= (ki + 1) * block_k - 1 > qi * block_q - window

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)              # [bq, hd]
        k = k_ref[0, 0].astype(jnp.float32)              # [bk, hd]
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ki == n_kb - 1)
    def _finalize():
        o_ref[0, 0] = (acc_ref[...] /
                       jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_q", "block_k", "window",
                                             "interpret"))
def flash_prefill(q, k, v, *, block_q: int = 256, block_k: int = 512,
                  window: int | None = None, interpret: bool = False):
    """Causal flash attention.  q: [B,S,H,hd]; k/v: [B,S,KV,hd]."""
    b, s, h, hd = q.shape
    kv = k.shape[2]
    group = h // kv
    block_q = min(block_q, s)
    block_k = min(block_k, s)
    assert s % block_q == 0 and s % block_k == 0
    n_qb, n_kb = s // block_q, s // block_k
    scale = 1.0 / math.sqrt(hd)

    qt = q.transpose(0, 2, 1, 3)       # [B,H,S,hd]
    kt = k.transpose(0, 2, 1, 3)       # [B,KV,S,hd]
    vt = v.transpose(0, 2, 1, 3)

    kernel = functools.partial(_flash_kernel, block_q=block_q,
                               block_k=block_k, n_kb=n_kb, scale=scale,
                               window=window)
    out = pl.pallas_call(
        kernel,
        grid=(b, h, n_qb, n_kb),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, hd),
                         lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, block_k, hd),
                         lambda bi, hi, qi, ki, g=group: (bi, hi // g, ki, 0)),
            pl.BlockSpec((1, 1, block_k, hd),
                         lambda bi, hi, qi, ki, g=group: (bi, hi // g, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, hd),
                               lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, s, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),   # m
            pltpu.VMEM((block_q, 1), jnp.float32),   # l
            pltpu.VMEM((block_q, hd), jnp.float32),  # acc
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(qt, kt, vt)
    return out.transpose(0, 2, 1, 3)   # [B,S,H,hd]


# ---------------------------------------------------------------------------
# fused paged flash prefill — the prefill-phase mirror of
# kernels/paged_attention.fused_paged_decode_attention (DESIGN.md §2)
# ---------------------------------------------------------------------------
def _paged_prefill_kernel(phys_ref, offs_ref,            # scalar prefetch
                          q_ref, k_ref, v_ref, o_ref,
                          m_ref, l_ref, acc_ref, *,
                          bt: int, n_blocks: int, scale: float,
                          rows: int, group: int, chunk: int):
    b = pl.program_id(0)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    off = offs_ref[b]
    # blocks entirely above the last query position are fully masked
    run = j * bt <= off + chunk - 1

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)              # [rows, hd]
        k = k_ref[0].astype(jnp.float32)                 # [bt, hd]
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        # row r = c*group + g queries absolute position off + c; the
        # causal mask admits every pool position ≤ that (earlier
        # chunks + this chunk's already-written KV), matching the XLA
        # oracle (cache_ops.fused_paged_chunk_attention)
        t_pos = j * bt + jax.lax.broadcasted_iota(
            jnp.int32, (rows, bt), 1)
        q_pos = off + jax.lax.broadcasted_iota(
            jnp.int32, (rows, bt), 0) // group
        s = jnp.where(t_pos <= q_pos, s, NEG_INF)
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
def fused_paged_flash_prefill(q, pool_k, pool_v, phys, q_offset, *,
                              interpret: bool = False):
    """Multi-sequence chunked-prefill attention over pre-resolved
    physical head-blocks.

    The fused multi-LLM prefill sweep (DESIGN.md §2) flattens every
    in-flight prompt chunk of every colocated same-architecture engine
    into one batch; ``phys`` rows already carry the (model, layer) →
    physical-id resolution, so one kernel sweep serves all colocated
    LLMs' prefill chunks at once — mirroring
    ``fused_paged_decode_attention`` with C query tokens per row and a
    causal chunk mask.  Scalar-prefetched block ids stream the right
    ``[BLOCK_TOKENS, head_dim]`` tile HBM→VMEM ahead of compute; the
    chunk's query block ([C·group, hd]) stays resident in VMEM.

    q: [B, C, H, hd] (post-RoPE, absolute positions q_offset+i; rows
        may belong to different models)
    pool_k/v: [N, BT, hd] head-block arena
    phys: [B, n_kv, max_blocks] int32 physical head-block ids (invalid
        entries must point at a valid block — e.g. 0 — and be masked
        via the causal positions)
    q_offset: [B] int32 absolute position of each row's first query
    Returns [B, C, H, hd].
    """
    B, C, H, hd = q.shape
    N, BT, _ = pool_k.shape
    n_kv, max_blocks = phys.shape[1], phys.shape[2]
    group = H // n_kv
    rows = C * group
    scale = 1.0 / math.sqrt(hd)

    qt = (q.reshape(B, C, n_kv, group, hd)
           .transpose(0, 2, 1, 3, 4)
           .reshape(B, n_kv, rows, hd))
    kernel = functools.partial(_paged_prefill_kernel, bt=BT,
                               n_blocks=max_blocks, scale=scale,
                               rows=rows, group=group, chunk=C)

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, n_kv, max_blocks),
            in_specs=[
                pl.BlockSpec((1, 1, rows, hd),
                             lambda b, h, j, *refs: (b, h, 0, 0)),
                pl.BlockSpec((1, BT, hd),
                             lambda b, h, j, phys_ref, offs_ref:
                                 (phys_ref[b, h, j], 0, 0)),
                pl.BlockSpec((1, BT, hd),
                             lambda b, h, j, phys_ref, offs_ref:
                                 (phys_ref[b, h, j], 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, rows, hd),
                                   lambda b, h, j, *refs: (b, h, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((rows, 1), jnp.float32),
                pltpu.VMEM((rows, 1), jnp.float32),
                pltpu.VMEM((rows, hd), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, n_kv, rows, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(phys, q_offset, qt, pool_k, pool_v)
    return (out.reshape(B, n_kv, C, group, hd)
               .transpose(0, 2, 1, 3, 4)
               .reshape(B, C, H, hd))
