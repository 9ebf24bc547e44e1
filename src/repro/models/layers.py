"""Core transformer building blocks — pure JAX, explicit param pytrees.

Parameters are plain nested dicts of ``jnp.ndarray``.  Per-layer weights
are *stacked* on a leading layer axis so the model forward is a single
``jax.lax.scan`` over layers (keeps the HLO small — essential for the
512-device dry-run compiles).
"""
from __future__ import annotations

import math
from typing import Dict, Optional

import jax
import jax.numpy as jnp

from repro.config import ModelConfig

Params = Dict[str, jnp.ndarray]


# ---------------------------------------------------------------------------
# activation-sharding hygiene
# ---------------------------------------------------------------------------
def _mesh_axes():
    try:
        mesh = jax.sharding.get_abstract_mesh()
    except Exception:                                    # noqa: BLE001
        return ()
    if mesh is None or not mesh.axis_names:
        return ()
    return tuple(mesh.axis_names)


# global sharding policy knobs (set per lowering by launch/specs.py):
#   attn_tp   — shard attention heads / MLP hidden on the model axis
#               (Megatron TP).  Off for MoE-EP layouts where the model
#               axis belongs to the experts and attention runs
#               data-parallel (§Perf, qwen3-moe train).
#   seq_shard — sequence-shard the residual stream between layers
#               (Megatron-SP).  Off when attention is data-parallel
#               (no TP collectives to amortize; the AG/RS ping-pong
#               would be pure overhead).
_POLICY = {"attn_tp": True, "seq_shard": True}


def set_sharding_policy(**kw) -> dict:
    old = dict(_POLICY)
    for k, v in kw.items():
        assert k in _POLICY, k
        _POLICY[k] = v
    return old


def model_axis_size() -> int:
    """Size of the 'model' mesh axis in the current tracing context
    (1 outside a mesh — CPU tests)."""
    try:
        mesh = jax.sharding.get_abstract_mesh()
        if mesh is None or "model" not in mesh.axis_names:
            return 1
        return int(mesh.shape["model"])
    except Exception:                                    # noqa: BLE001
        return 1


def constrain(x: jnp.ndarray, *axes) -> jnp.ndarray:
    """``with_sharding_constraint`` filtered to axes present in the
    current abstract mesh (no-op on CPU/1-device runs).  Each entry is
    an axis name, a tuple of names, or None."""
    present = set(_mesh_axes())
    if not present:
        return x

    def keep(a):
        if a is None:
            return None
        if isinstance(a, tuple):
            t = tuple(x_ for x_ in a if x_ in present)
            return t if t else None
        return a if a in present else None

    spec = [keep(a) for a in axes]
    if all(s is None for s in spec):
        return x
    return jax.lax.with_sharding_constraint(
        x, jax.sharding.PartitionSpec(*spec))


def shard_activation(x: jnp.ndarray, *, last: str | None = None,
                     seq: str | None = None) -> jnp.ndarray:
    """Constrain an activation to batch-on-(pod,data) [+ seq/last dims].

    Without these constraints GSPMD occasionally re-shards the residual
    stream onto the model axis with the batch replicated — measured at
    24 GiB/device of stacked residuals on phi-3 train_4k (EXPERIMENTS.md
    §Perf).  ``seq="model"`` additionally shards dim 1 (sequence
    parallelism for the residual stream between layers — Megatron-SP
    style; GSPMD inserts the all-gather before attention and the
    reduce-scatter after).  No-op outside a mesh context (CPU tests see
    1 device).
    """
    axes = _mesh_axes()
    if not axes:
        return x
    batch = tuple(a for a in ("pod", "data") if a in axes)
    if not batch:
        return x
    spec = [batch] + [None] * (x.ndim - 1)
    if seq is not None and seq in axes and x.ndim >= 3 \
            and x.shape[1] % 16 == 0 and _POLICY["seq_shard"]:
        spec[1] = seq
    if last is not None and last in axes:
        spec[-1] = last
    return jax.lax.with_sharding_constraint(
        x, jax.sharding.PartitionSpec(*spec))


# ---------------------------------------------------------------------------
# norms / rope
# ---------------------------------------------------------------------------
def rms_norm(x: jnp.ndarray, w: jnp.ndarray, eps: float = 1e-5) -> jnp.ndarray:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)).astype(dtype)


def rope_freqs(head_dim: int, theta: float) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """x: [..., seq, heads, head_dim]; positions: [..., seq]."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)                       # [hd/2]
    angles = positions[..., None].astype(jnp.float32) * freqs  # [..., seq, hd/2]
    cos = jnp.cos(angles)[..., None, :]                 # [..., seq, 1, hd/2]
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# attention (XLA reference path; the Pallas kernels mirror these semantics)
# ---------------------------------------------------------------------------
def repeat_kv(x: jnp.ndarray, n_rep: int) -> jnp.ndarray:
    """[B, S, KV, hd] -> [B, S, KV*n_rep, hd] (GQA broadcast)."""
    if n_rep == 1:
        return x
    b, s, kv, hd = x.shape
    x = jnp.broadcast_to(x[:, :, :, None, :], (b, s, kv, n_rep, hd))
    return x.reshape(b, s, kv * n_rep, hd)


def causal_attention(q, k, v, *, window: Optional[int] = None,
                     q_offset: int = 0) -> jnp.ndarray:
    """Plain causal attention.  q: [B,Sq,H,hd], k/v: [B,Sk,KV,hd].

    ``q_offset`` positions q tokens at ``q_offset + arange(Sq)`` in the
    kv timeline (used for chunked prefill).  ``window``: sliding window.
    """
    b, sq, h, hd = q.shape
    kvh = k.shape[2]
    k = repeat_kv(k, h // kvh)
    v = repeat_kv(v, h // kvh)
    scale = 1.0 / math.sqrt(hd)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    q_pos = q_offset + jnp.arange(sq)[:, None]
    k_pos = jnp.arange(k.shape[1])[None, :]
    mask = k_pos <= q_pos
    if window is not None:
        mask &= k_pos > q_pos - window
    scores = jnp.where(mask[None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def blocked_causal_attention(q, k, v, *, block_q: int = 512,
                             block_k: int = 1024,
                             window: Optional[int] = None) -> jnp.ndarray:
    """Memory-bounded causal attention: online-softmax over kv blocks.

    Pure-jnp flash attention — the oracle for ``kernels/flash_prefill``
    and the XLA fallback used in dry-run lowering (keeps the 32k×32k
    score matrix out of the memory analysis).
    """
    b, s, h, hd = q.shape
    kvh = k.shape[2]
    if s % block_q != 0 or s % block_k != 0:
        return causal_attention(q, k, v, window=window)
    n_rep = h // kvh
    nq, nk = s // block_q, s // block_k
    scale = 1.0 / math.sqrt(hd)

    qb = q.reshape(b, nq, block_q, h, hd)
    kb = k.reshape(b, nk, block_k, kvh, hd)
    vb = v.reshape(b, nk, block_k, kvh, hd)

    def per_qblock(qi, q_blk):
        # online softmax accumulation over kv blocks
        m0 = jnp.full((b, h, block_q), -1e30, jnp.float32)
        l0 = jnp.zeros((b, h, block_q), jnp.float32)
        acc0 = jnp.zeros((b, h, block_q, hd), jnp.float32)

        @jax.checkpoint
        def body(carry, ki):
            # checkpointed: without it the scan's backward saves every
            # [b,h,block_q,block_k] f32 probability block — the full
            # S×S matrix in aggregate (32 GiB/device/layer at 4k×256,
            # measured in the dry-run).  Recompute-per-block is the
            # flash-attention backward strategy.
            m, l, acc = carry
            k_blk = repeat_kv(kb[:, ki], n_rep)          # [b,block_k,h,hd]
            v_blk = repeat_kv(vb[:, ki], n_rep)
            s_ij = jnp.einsum("bqhd,bkhd->bhqk", q_blk, k_blk).astype(jnp.float32) * scale
            q_pos = qi * block_q + jnp.arange(block_q)[:, None]
            k_pos = ki * block_k + jnp.arange(block_k)[None, :]
            mask = k_pos <= q_pos
            if window is not None:
                mask &= k_pos > q_pos - window
            s_ij = jnp.where(mask[None, None], s_ij, -1e30)
            m_new = jnp.maximum(m, s_ij.max(-1))
            p = jnp.exp(s_ij - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bhqk,bkhd->bhqd", p, v_blk.astype(jnp.float32))
            return (m_new, l_new, acc_new), None

        # only kv blocks that intersect the causal/window mask matter;
        # keep the scan over all blocks (masked) for a static shape.
        (m, l, acc), _ = jax.lax.scan(body, (m0, l0, acc0), jnp.arange(nk))
        out = acc / jnp.maximum(l[..., None], 1e-30)
        return out.astype(q.dtype).transpose(0, 2, 1, 3)   # [b,block_q,h,hd]

    outs = jax.lax.map(lambda qi: per_qblock(qi, qb[:, qi]), jnp.arange(nq))
    # outs: [nq, b, block_q, h, hd] -> [b, s, h, hd]
    return outs.transpose(1, 0, 2, 3, 4).reshape(b, s, h, hd)


# ---------------------------------------------------------------------------
# attention block params
# ---------------------------------------------------------------------------
def init_attn(key, cfg: ModelConfig, n_layers: int, dtype=jnp.bfloat16) -> Params:
    d, h, kv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    ks = jax.random.split(key, 4)
    sc = 1.0 / math.sqrt(d)
    sco = 1.0 / math.sqrt(h * hd)
    L = n_layers
    p = {
        "wq": jax.random.normal(ks[0], (L, d, h * hd), dtype) * sc,
        "wk": jax.random.normal(ks[1], (L, d, kv * hd), dtype) * sc,
        "wv": jax.random.normal(ks[2], (L, d, kv * hd), dtype) * sc,
        "wo": jax.random.normal(ks[3], (L, h * hd, d), dtype) * sco,
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((L, h * hd), dtype)
        p["bk"] = jnp.zeros((L, kv * hd), dtype)
        p["bv"] = jnp.zeros((L, kv * hd), dtype)
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((L, hd), dtype)
        p["k_norm"] = jnp.ones((L, hd), dtype)
    return p


def attn_qkv(x, p, li, cfg: ModelConfig, positions):
    """Project to q/k/v (+bias, qk_norm, rope unless the model has no
    positional encoding, q scaled to ``attention_multiplier``).
    x: [B,S,d]."""
    b, s, _ = x.shape
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    q = x @ p["wq"][li]
    k = x @ p["wk"][li]
    v = x @ p["wv"][li]
    if cfg.qkv_bias:
        q, k, v = q + p["bq"][li], k + p["bk"][li], v + p["bv"][li]
    hx = "model" if _POLICY["attn_tp"] else None
    q = constrain(q.reshape(b, s, h, hd), ("pod", "data"), None, hx, None)
    k = constrain(k.reshape(b, s, kv, hd), ("pod", "data"), None, hx, None)
    v = constrain(v.reshape(b, s, kv, hd), ("pod", "data"), None, hx, None)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"][li], cfg.rms_eps)
        k = rms_norm(k, p["k_norm"][li], cfg.rms_eps)
    if cfg.rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    if cfg.attention_multiplier:
        # every attention path scales scores by 1/sqrt(hd): folding the
        # rest of the model's own scale into q gives its scores
        q = q * (cfg.attention_multiplier * math.sqrt(hd))
    return q, k, v


# ---------------------------------------------------------------------------
# dense SwiGLU MLP
# ---------------------------------------------------------------------------
def init_mlp(key, d: int, f: int, n_layers: int, dtype=jnp.bfloat16) -> Params:
    ks = jax.random.split(key, 3)
    return {
        "w_gate": jax.random.normal(ks[0], (n_layers, d, f), dtype) / math.sqrt(d),
        "w_up": jax.random.normal(ks[1], (n_layers, d, f), dtype) / math.sqrt(d),
        "w_down": jax.random.normal(ks[2], (n_layers, f, d), dtype) / math.sqrt(f),
    }


def mlp(x, p, li):
    # Megatron TP: the hidden dim rides the model axis with the batch
    # on (pod,data) — without this constraint GSPMD keeps the residual
    # stream's sequence sharding and fully replicates w_down instead
    # (1.55 GiB f32 × live-set on command-r train, EXPERIMENTS §Perf)
    h = jax.nn.silu(x @ p["w_gate"][li]) * (x @ p["w_up"][li])
    last = "model" if _POLICY["attn_tp"] else None
    spec = [("pod", "data")] + [None] * (h.ndim - 2) + [last]
    h = constrain(h, *spec)
    return h @ p["w_down"][li]


# ---------------------------------------------------------------------------
# embeddings
# ---------------------------------------------------------------------------
def init_embed(key, cfg: ModelConfig, v_padded: int, dtype=jnp.bfloat16) -> Params:
    d = cfg.d_model
    ks = jax.random.split(key, 2)
    p = {
        "embed": jax.random.normal(ks[0], (v_padded, d), dtype) * 0.02,
        "out_norm": jnp.ones((d,), dtype),
    }
    if not cfg.tie_embeddings:
        p["lm_head"] = jax.random.normal(ks[1], (d, v_padded), dtype) / math.sqrt(d)
    return p


def lm_logits(x, p, cfg: ModelConfig):
    x = rms_norm(x, p["out_norm"], cfg.rms_eps)
    if cfg.tie_embeddings:
        return x @ p["embed"].T
    return x @ p["lm_head"]
