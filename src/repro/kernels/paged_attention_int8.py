"""Pallas TPU kernel: int8 paged decode attention (W8/KV8 serving path).

Same grid/pipeline structure as ``paged_attention.py`` (scalar-
prefetched block tables, online softmax in VMEM scratch), but the KV
head-blocks are stored int8 with one f32 scale per (block, token):
dequantization happens in-register after the HBM→VMEM copy, so the
DMA traffic is half the bf16 kernel's — exactly the §Perf P2 memory
win, now at kernel granularity.
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


def _paged_kernel_i8(phys_ref, lens_ref,                 # scalar prefetch
                     q_ref, k_ref, v_ref, sk_ref, sv_ref, o_ref,
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
        # dequantize in-register: int8 values × per-token f32 scales
        k = k_ref[0].astype(jnp.float32) * sk_ref[0][:, :1]
        v = v_ref[0].astype(jnp.float32) * sv_ref[0][:, :1]
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


@functools.partial(jax.jit, static_argnames=("n_kv", "interpret"))
def paged_decode_attention_int8(q, pool_k, pool_v, pool_sk, pool_sv,
                                table, seq_lens, layer, *,
                                n_kv: int, interpret: bool = False):
    """Decode attention over an int8 paged pool.

    q: [B, H, hd] (post-RoPE); pool_k/v: [N, BT, hd] int8;
    pool_sk/sv: [N, BT] f32 per-token scales; table: [B, max_blocks]
    int32 group bases (−1 padded); seq_lens: [B]."""
    B, H, hd = q.shape
    N, BT, _ = pool_k.shape
    max_blocks = table.shape[1]
    group = H // n_kv
    scale = 1.0 / math.sqrt(hd)

    phys = resolve_physical_blocks(table, layer, n_kv)

    qt = q.reshape(B, n_kv, group, hd)
    # scales carried as [N, BT, 1] so the lane dim exists for VMEM tiles
    sk = pool_sk[..., None]
    sv = pool_sv[..., None]
    kernel = functools.partial(_paged_kernel_i8, bt=BT,
                               n_blocks=max_blocks, scale=scale,
                               group=group)

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
                pl.BlockSpec((1, BT, 1),
                             lambda b, h, j, phys_ref, lens_ref:
                                 (phys_ref[b, h, j], 0, 0)),
                pl.BlockSpec((1, BT, 1),
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
    )(phys, seq_lens, qt, pool_k, pool_v, sk, sv)
    return out.reshape(B, H, hd)
