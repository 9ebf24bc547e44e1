"""Shared paged-KV index arithmetic and decode oracles (layer-neutral).

Physical head-block id for (token-block base b, layer l, kv head h) of
a model with KV kv-heads: ``b + l*KV + h`` (groups are contiguous —
see serving/kvcache.py).  Both the XLA oracle (serving/cache_ops) and
the Pallas kernels (kernels/paged_attention) resolve tables through
this one function so the two layers can never disagree on the pool
layout.

The paged *decode attention* oracles live here too: they are pure
functions of (query, arena, resolved blocks) with no serving-state
dependency, and both the serving engine (via serving/cache_ops) and
the kernel test oracles (kernels/ref.py) consume them.  Hosting them
in this shared leaf keeps the layer DAG acyclic — kernels must not
import serving (ARCHITECTURE.md; enforced by ``tools/muxlint``
``layering``).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

# bf16 rows in one TPU memory tile: 8 sublanes, 2 rows packed in each
TILE_ROWS = 16


def resolve_physical_blocks(table, layer, n_kv):
    """Resolve a group-base block table to physical head-block ids.

    table: [..., max_blocks] int32 group bases (−1 padded) — any number
    of leading batch dims (the fused multi-LLM sweeps pass their row
    batches flattened or as [M, rows]).
    Returns [..., n_kv, max_blocks] int32 physical ids (invalid → head
    h's id in group 0, so the heads of every entry stay consecutive;
    the caller masks those positions via seq_lens / query positions).
    Rows of a *fused* multi-LLM batch can come from different models as
    long as their (layer, n_kv) resolution has already been applied
    here — this is the per-row handoff point between the pool and the
    fused kernels (decode AND prefill).
    """
    layer = jnp.asarray(layer, jnp.int32)
    heads = jnp.arange(n_kv, dtype=jnp.int32)[:, None]       # [n_kv, 1]
    phys = jnp.maximum(table, 0)[..., None, :] + layer * n_kv + heads
    return jnp.where(table[..., None, :] >= 0, phys, heads).astype(jnp.int32)


def take_blocks(pool, ids):
    """``pool[ids]`` for in-range ids, as one gather (no index
    normalisation: the step programs trace it for every layer)."""
    dims = tuple(range(ids.ndim, ids.ndim + pool.ndim - 1))
    return lax.gather(
        pool, ids[..., None],
        lax.GatherDimensionNumbers(offset_dims=dims,
                                   collapsed_slice_dims=(0,),
                                   start_index_map=(0,)),
        slice_sizes=(1, *pool.shape[1:]),
        mode=lax.GatherScatterMode.PROMISE_IN_BOUNDS)


def attend_blocks(pool_k, pool_v, phys, attend):
    """``attend(k, v)`` over the head-blocks ``phys`` [B, n_kv, nb]
    names, k/v gathered as [B, n_kv, nb, *block].

    In the row form a gathered row is one short piece per lane tile of
    the arena (a tile holds 16 blocks' rows), so a gather of single rows
    moves the arena at a fraction of its bandwidth.  Where n_kv and the
    arena's size are multiples of 16, a block's heads are gathered as
    whole tile rows instead, each one contiguous piece of the arena.
    That reads the right rows because such a model's group bases are
    multiples of 16 (``BlockAllocator.alloc`` starts every run of whole
    tile rows on one; ``serving/sanitize.py`` checks it) and
    ``resolve_physical_blocks`` keeps each entry's heads consecutive.
    """
    B, n_kv, nb = phys.shape
    N = pool_k.shape[0]
    if pool_k.ndim != 2 or n_kv % TILE_ROWS or N % TILE_ROWS:
        return attend(take_blocks(pool_k, phys), take_blocks(pool_v, phys))
    t = (phys[:, 0, :] // TILE_ROWS)[..., None] + jnp.arange(
        n_kv // TILE_ROWS)                          # [B, nb, n_kv/16]

    def tile_rows(pool):
        tiles = pool.reshape(N // TILE_ROWS, TILE_ROWS, pool.shape[1])
        return jnp.swapaxes(take_blocks(tiles, t).reshape(B, nb, n_kv, -1),
                            1, 2)

    return attend(tile_rows(pool_k), tile_rows(pool_v))


def fused_paged_decode_attention(q, pool_k, pool_v, phys, seq_lens):
    """Multi-sequence decode attention over pre-resolved physical blocks.

    The fused multi-LLM tick (DESIGN.md §2) flattens the decode rows of
    all colocated same-architecture engines into one batch; each row's
    ``phys`` entries already encode (model, layer) → physical id, so
    the attention sweep itself is model-agnostic.

    q: [B, H, hd] — one query token per row (post-RoPE)
    pool_k/v: [N, BT*hd] or [N, BT, hd] (serving/kvcache.py
        ``arena_block_shape``)
    phys: [B, n_kv, max_blocks] int32 physical head-block ids
    seq_lens: [B] (length INCLUDING the current token)
    Returns [B, H, hd].

    Row-form blocks are contracted as they lie: a gathered row viewed
    as ``[BT, hd]`` is a relayout of every gathered block on the TPU,
    so the query is spread over the row instead (``seg`` marks which
    token each column belongs to), and the value rows are folded back
    to hd after the weighted sum.
    """
    B, H, hd = q.shape
    BT = math.prod(pool_k.shape[1:]) // hd
    n_kv, max_blocks = phys.shape[1], phys.shape[2]
    group = H // n_kv
    scale = 1.0 / math.sqrt(hd)

    qh = q.reshape(B, n_kv, group, hd)
    t_pos = jnp.arange(max_blocks * BT)[None, None, None, :]
    mask = t_pos < seq_lens[:, None, None, None]

    def softmax(scores):
        scores = jnp.where(mask, scores.astype(jnp.float32) * scale, -1e30)
        return jax.nn.softmax(scores, axis=-1).astype(q.dtype)

    if pool_k.ndim == 3:
        k = take_blocks(pool_k, phys).reshape(B, n_kv, max_blocks * BT, hd)
        v = take_blocks(pool_v, phys).reshape(B, n_kv, max_blocks * BT, hd)
        probs = softmax(jnp.einsum("bkgd,bktd->bkgt", qh, k))
        return jnp.einsum("bkgt,bktd->bkgd", probs, v).reshape(B, H, hd)

    row = BT * hd
    seg = (jnp.arange(row)[None, :] // hd
           == jnp.arange(BT)[:, None]).astype(q.dtype)       # [BT, row]
    qx = jnp.tile(qh, (1, 1, 1, BT))[:, :, :, None, :] * seg

    def attend(k, v):                                  # [B, n_kv, nb, row]
        scores = jnp.einsum("bkgtx,bkjx->bkgjt", qx, k)
        probs = softmax(scores.reshape(B, n_kv, group, max_blocks * BT))
        spread = jnp.einsum(
            "bkgjt,bkjx->bkgtx", probs.reshape(B, n_kv, group, max_blocks, BT),
            v, preferred_element_type=jnp.float32)
        out = (spread * seg).sum(axis=3).reshape(B, n_kv, group, BT, hd)
        return out.sum(axis=3).astype(q.dtype).reshape(B, H, hd)

    return attend_blocks(pool_k, pool_v, phys, attend)


def paged_decode_attention(q, pool_k, pool_v, table, seq_lens, layer, n_kv):
    """Single-token decode attention against the paged pool (oracle).

    q: [B, H, hd] — one query token per sequence (post-RoPE)
    pool_k/v: [N, BT*hd] or [N, BT, hd]
    table: [B, max_blocks]; seq_lens: [B] (length INCLUDING current token,
    whose KV must already be written).
    Returns [B, H, hd].
    """
    phys = resolve_physical_blocks(table, layer, n_kv)
    return fused_paged_decode_attention(q, pool_k, pool_v, phys, seq_lens)
