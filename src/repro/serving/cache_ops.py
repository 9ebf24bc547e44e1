"""Device-side ops on the unified head-wise KV pool (pure jnp).

These are the XLA reference semantics for ``kernels/paged_attention``
and are used directly by the CPU engine and the dry-run lowering.

Physical head-block id for (token-block base b, layer l, kv head h) of a
model with KV kv-heads: ``b + l*KV + h`` (groups are contiguous —
see serving/kvcache.py).

The arena is ``[N, *block]``: each head-block is one lane-dense row
``[BT*hd]``, or ``[BT, hd]`` where hd fills whole lane tiles (serving/
kvcache.py ``arena_block_shape`` says why).  Every op here reads and
writes whole blocks, so it works on either form, and reads ``BT`` as a
block's size over hd.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.paging import (attend_blocks, fused_paged_decode_attention,
                          paged_decode_attention, resolve_physical_blocks,
                          take_blocks)

__all__ = ["write_tokens", "resolve_physical_blocks", "copy_block_groups",
           "fused_paged_decode_attention", "paged_decode_attention",
           "fused_paged_chunk_attention", "paged_chunk_attention",
           "windowed_decode_attention", "write_window"]


def copy_block_groups(pool_k, pool_v, src_bases, dst_bases, n_kv, n_layers,
                      src_k=None, src_v=None):
    """Device-side page copy between block groups — one gather/scatter
    over every (layer, kv-head) page of each group.

    Logical group bases are resolved to physical head-block ids through
    ``paging.resolve_physical_blocks`` — the SAME resolution every
    kernel uses, so the copy can never disagree with the pool layout.
    Source and destination index lists are elementwise aligned, making
    this an exact page copy.  Powers copy-on-write divergence of a
    shared prefix block (same-pool: ``src_k/src_v`` default to the
    destination arrays) and cross-pool KV migration (pass the source
    pool's arrays).

    pool_k/pool_v: destination arena [N, *block]
    src_bases/dst_bases: group base per token-block (host lists)
    Returns updated (pool_k, pool_v).
    """
    if src_k is None:
        src_k, src_v = pool_k, pool_v
    st = jnp.asarray(src_bases, jnp.int32)[None, :]
    dt = jnp.asarray(dst_bases, jnp.int32)[None, :]
    sp = jnp.concatenate([resolve_physical_blocks(st, li, n_kv)
                          for li in range(n_layers)], axis=1).reshape(-1)
    dp = jnp.concatenate([resolve_physical_blocks(dt, li, n_kv)
                          for li in range(n_layers)], axis=1).reshape(-1)
    return pool_k.at[dp].set(src_k[sp]), pool_v.at[dp].set(src_v[sp])


def write_tokens(pool_k, pool_v, k_new, v_new, table, start_pos, layer, n_kv):
    """Scatter new KV into the pool.

    pool_k/v: [N, *block]
    k_new/v_new: [B, S, KV, hd] — S new tokens starting at start_pos[b]
    table: [B, max_blocks] int32 group bases (−1 padded)
    start_pos: [B] int32 — position of the first new token
    Returns updated (pool_k, pool_v).

    Writes whole blocks: every head-block the S tokens touch is
    gathered, the new tokens are merged into it, and the blocks are
    scattered back.  In the row form a token is a window at column
    ``off*hd`` that is not lane-aligned, and the TPU compiler expands a
    scatter of such windows into a loop over tokens; a scatter of
    whole blocks stays one op.
    """
    B, S, KV, hd = k_new.shape
    N, block = pool_k.shape[0], pool_k.shape[1:]
    BT = math.prod(block) // hd
    nb = (S + BT - 2) // BT + 1             # blocks S tokens can touch
    blk = start_pos[:, None] // BT + jnp.arange(nb)              # [B,nb]
    base = jnp.take_along_axis(table, blk, axis=1)               # [B,nb]
    # invalid blocks → OOB index, dropped by the scatter
    phys = jnp.where(base[..., None] >= 0,
                     base[..., None] + (layer * n_kv + jnp.arange(KV)),
                     N)                                          # [B,nb,KV]
    # new token landing in slot t of block j (−1 / ≥ S: none)
    i = blk[..., None] * BT + jnp.arange(BT) - start_pos[:, None, None]
    fresh = ((i >= 0) & (i < S))[:, :, None, :, None]           # [B,nb,1,BT,1]
    src = jnp.clip(i, 0, S - 1).reshape(B, nb * BT, 1, 1)
    kv = jnp.take_along_axis(jnp.stack([k_new, v_new], axis=1),
                             src[:, None], axis=2)      # [B,2,nb*BT,KV,hd]
    kv = kv.reshape(B, 2, nb, BT, KV, hd).transpose(1, 0, 2, 4, 3, 5)
    ids = phys.reshape(-1)

    def put(pool, new):
        old = take_blocks(pool, jnp.minimum(ids, N - 1))
        blocks = jnp.where(fresh, new.astype(pool.dtype),
                           old.reshape(B, nb, KV, BT, hd))
        return pool.at[ids].set(blocks.reshape(-1, *block), mode="drop")

    return put(pool_k, kv[0]), put(pool_v, kv[1])


def fused_paged_chunk_attention(q, pool_k, pool_v, phys, q_offset):
    """Multi-sequence chunk attention over pre-resolved physical blocks.

    Prefill-phase mirror of ``fused_paged_decode_attention``: the fused
    multi-LLM prefill sweep (DESIGN.md §2) flattens every in-flight
    prompt chunk of every colocated same-architecture engine into one
    batch; each row's ``phys`` entries already encode (model, layer) →
    physical id, so the chunk attention itself is model-agnostic.

    q: [B, C, H, hd] (post-RoPE, absolute positions q_offset+i; rows
        may belong to different models)
    pool_k/v: [N, *block]
    phys: [B, n_kv, max_blocks] int32 physical head-block ids
    q_offset: [B] int32 absolute position of each row's first query
    Returns [B, C, H, hd].
    """
    B, C, H, hd = q.shape
    BT = math.prod(pool_k.shape[1:]) // hd
    n_kv, max_blocks = phys.shape[1], phys.shape[2]
    group = H // n_kv
    scale = 1.0 / math.sqrt(hd)

    qh = q.reshape(B, C, n_kv, group, hd)
    t_pos = jnp.arange(max_blocks * BT)[None, None, None, None, :]
    q_pos = (q_offset[:, None] + jnp.arange(C))[:, None, None, :, None]
    mask = t_pos <= q_pos

    def attend(k, v):                               # [B, n_kv, nb, *block]
        k = k.reshape(B, n_kv, max_blocks * BT, hd)
        v = v.reshape(B, n_kv, max_blocks * BT, hd)
        scores = jnp.einsum("bckgd,bktd->bkgct", qh, k).astype(
            jnp.float32) * scale
        scores = jnp.where(mask, scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
        out = jnp.einsum("bkgct,bktd->bckgd", probs, v)
        return out.reshape(B, C, H, hd)

    return attend_blocks(pool_k, pool_v, phys, attend)


def paged_chunk_attention(q, pool_k, pool_v, table, q_offset, layer, n_kv):
    """Chunked-prefill attention: a chunk of C query tokens per sequence
    attends causally against the pool (earlier chunks + this chunk's
    already-written KV).  Single-model view: resolves the group-base
    table, then delegates to the fused multi-sequence path so the
    serial and fused prefill sweeps share one set of semantics.

    q: [B, C, H, hd] (post-RoPE, absolute positions q_offset+i)
    pool_k/v: [N, *block]; table: [B, max_blocks]; q_offset: [B]
    Returns [B, C, H, hd].
    """
    phys = resolve_physical_blocks(table, layer, n_kv)       # [B,KV,nb]
    return fused_paged_chunk_attention(q, pool_k, pool_v, phys, q_offset)


def windowed_decode_attention(q, win_k, win_v, seq_lens, window):
    """Decode attention over a ring-buffer sliding-window cache.

    q: [B,H,hd]; win_k/v: [B, KV, W, hd] ring buffers; seq_lens: [B]
    (length including current token).  Slot for position p is p % W.
    """
    B, H, hd = q.shape
    KV, W = win_k.shape[1], win_k.shape[2]
    group = H // KV
    scale = 1.0 / math.sqrt(hd)
    qh = q.reshape(B, KV, group, hd)
    scores = jnp.einsum("bkgd,bkwd->bkgw", qh, win_k).astype(jnp.float32) * scale
    # valid slots: positions in [seq_len - min(seq_len, W), seq_len)
    slot = jnp.arange(W)[None, :]
    cur = seq_lens[:, None]                                    # [B,1]
    # position stored in slot s: the largest p < cur with p % W == s
    p_in_slot = cur - 1 - ((cur - 1 - slot) % W)
    valid = (p_in_slot >= 0) & (p_in_slot >= cur - W)
    scores = jnp.where(valid[:, None, None, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bkgw,bkwd->bkgd", probs, win_v).reshape(B, H, hd)


def write_window(win_k, win_v, k_new, v_new, pos):
    """Write one token's KV into the ring buffer at slot pos % W.

    win_k/v: [B,KV,W,hd]; k_new/v_new: [B,KV,hd]; pos: [B]."""
    W = win_k.shape[2]
    slot = pos % W
    b_idx = jnp.arange(win_k.shape[0])
    win_k = win_k.at[b_idx, :, slot].set(k_new)
    win_v = win_v.at[b_idx, :, slot].set(v_new)
    return win_k, win_v
