"""Mixture-of-Experts FFN: the serving path's held-expert layer and the
training path's capacity-based dispatch.

``moe_ffn_held`` (serving) computes the pairs routed to the experts one
chip holds, with no capacity: a token's result never depends on the
rest of the batch.  ``moe_ffn`` (training, dry-run) uses top-k routing
with a static per-expert capacity (tokens beyond capacity are dropped —
standard Switch/GShard semantics).  Its dispatch is
implemented with integer scatter/gather (not one-hot matmuls) so the
compiled FLOPs reflect *active* compute, which matters for the roofline
(MODEL_FLOPS uses 6·N_active·D for MoE).

Expert weights are stacked ``[L, E, d, f]`` → expert-parallel sharding
puts E on the ``model`` mesh axis; with tokens sharded on ``data`` the
dispatch/combine lower to all-to-all style collectives under GSPMD.
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config import ModelConfig, MoEConfig


def init_moe(key, cfg: ModelConfig, n_layers: int, dtype=jnp.bfloat16) -> Dict:
    """The router over all ``n_experts``; the weights of the experts
    this chip holds (``MoEConfig.n_held``) and of the shared expert."""
    mc = cfg.moe
    d, fe, E, L = cfg.d_model, mc.d_expert, mc.n_experts, n_layers
    Eh, ds = mc.n_held, mc.d_shared
    ks = jax.random.split(key, 7)
    p = {
        "router": jax.random.normal(ks[0], (L, d, E), jnp.float32) * 0.02,
        "w_gate": jax.random.normal(ks[1], (L, Eh, d, fe), dtype) / math.sqrt(d),
        "w_up": jax.random.normal(ks[2], (L, Eh, d, fe), dtype) / math.sqrt(d),
        "w_down": jax.random.normal(ks[3], (L, Eh, fe, d), dtype) / math.sqrt(fe),
    }
    if ds:
        p["ws_gate"] = jax.random.normal(ks[4], (L, d, ds), dtype) / math.sqrt(d)
        p["ws_up"] = jax.random.normal(ks[5], (L, d, ds), dtype) / math.sqrt(d)
        p["ws_down"] = jax.random.normal(ks[6], (L, ds, d), dtype) / math.sqrt(ds)
    return p


def expert_capacity(n_tokens: int, mc: MoEConfig) -> int:
    cap = int(math.ceil(n_tokens * mc.top_k / mc.n_experts * mc.capacity_factor))
    return max(8, ((cap + 7) // 8) * 8)   # pad to 8 for TPU-friendly tiling


def route(x: jnp.ndarray, router_w: jnp.ndarray, mc: MoEConfig
          ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """x: [T, d] → (gates [T,k], expert_idx [T,k], aux_loss)."""
    logits = x.astype(jnp.float32) @ router_w            # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(probs, mc.top_k)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    # Switch-style load-balance aux loss
    me = probs.mean(0)                                   # mean prob per expert
    ce = jnp.zeros((mc.n_experts,), jnp.float32).at[idx[:, 0]].add(1.0)
    ce = ce / x.shape[0]
    aux = mc.n_experts * jnp.sum(me * ce) * mc.aux_loss_coef
    return gates, idx, aux


def shared_expert(x: jnp.ndarray, p: Dict, li) -> jnp.ndarray:
    """The always-on shared expert (SwiGLU).  x: [..., d]."""
    return (jax.nn.silu(x @ p["ws_gate"][li]) * (x @ p["ws_up"][li])) \
        @ p["ws_down"][li]


def moe_ffn_held(x: jnp.ndarray, p: Dict, li, cfg: ModelConfig,
                 keep: Optional[jnp.ndarray] = None
                 ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """The expert layer of one chip of an expert-parallel deployment.

    Routes each token over all ``n_experts`` (top-k, gates a softmax
    over the chosen logits) and computes only the (token, expert) pairs
    routed to the experts this chip holds: the pairs are sorted by held
    expert and each runs through one grouped matmul
    (``jax.lax.ragged_dot``), which reads each held expert's weights
    once.  The pair buffer holds ``T·min(k, held)`` rows, every pair a
    batch can route here, so no pair is dropped and a token's result
    does not depend on the rest of the batch.  The shared expert runs
    for every token.  What the absent experts would add is left out:
    the partial result is this chip's.  With every expert held this is
    the whole layer.

    x: [..., d]; ``keep`` ([...], bool) masks tokens that route nowhere
    (padding).  Returns (out [..., d], aux loss, tokens routed to each
    held expert [held] int32).
    """
    mc = cfg.moe
    shape, d = x.shape, x.shape[-1]
    xt = x.reshape(-1, d)
    T, k, Eh = xt.shape[0], mc.top_k, mc.n_held
    gates, idx, aux = route(xt, p["router"][li], mc)      # [T,k]
    local = idx - mc.held_start
    held = (local >= 0) & (local < Eh)
    if keep is not None:
        held &= keep.reshape(T, 1)
    # sort key: the held expert, or Eh for a pair computed elsewhere
    key = jnp.where(held, local, Eh).reshape(-1)          # [T*k]
    order = jnp.argsort(key, stable=True)[:T * min(k, Eh)]
    sizes = jnp.zeros((Eh + 1,), jnp.int32).at[key].add(1)[:Eh]
    tok = order // k
    xs = xt[tok]
    # the grouped matmuls take every layer's experts as groups, those of
    # other layers empty: a layer's slice of the stacked weights would
    # be copied out before the kernel could read it
    L = p["w_gate"].shape[0]
    groups = jax.lax.dynamic_update_slice(
        jnp.zeros((L * Eh,), jnp.int32), sizes, (li * Eh,))

    def grouped(a, w):
        return jax.lax.ragged_dot(a, w.reshape(L * Eh, *w.shape[2:]), groups)

    h = grouped(xs, p["w_gate"])
    u = grouped(xs, p["w_up"])
    y = grouped(jax.nn.silu(h) * u, p["w_down"])
    g = gates.reshape(-1)[order]
    y = jnp.where((key[order] < Eh)[:, None],
                  y.astype(jnp.float32) * g[:, None], 0.0)
    out = jnp.zeros((T, d), jnp.float32).at[tok].add(y)
    if mc.d_shared:
        out = out + shared_expert(xt, p, li).astype(jnp.float32)
    return out.astype(x.dtype).reshape(shape), aux, sizes


def moe_ffn(x: jnp.ndarray, p: Dict, li, cfg: ModelConfig
            ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: [B, S, d] → (out [B, S, d], aux_loss scalar).

    Sharding: token-major tensors ride the (pod, data) axes, the
    expert-major dispatch buffer rides the model axis — the T-sharded →
    E-sharded transition is the expert-parallel all-to-all under GSPMD.
    Without the explicit constraints GSPMD replicates the [T·k, d]
    combine intermediates (measured 128 GiB/device on qwen3-moe
    train_4k).  Every expert held (``MoEConfig.n_held``), no shared
    expert: the serving path's ``moe_ffn_held`` covers those.
    """
    from repro.models.layers import constrain
    mc = cfg.moe
    assert mc.n_held == mc.n_experts and not mc.d_shared
    b, s, d = x.shape
    T = b * s
    xt = constrain(x.reshape(T, d), ("pod", "data"), None)
    gates, idx, aux = route(xt, p["router"][li], mc)     # [T,k]

    E, k = mc.n_experts, mc.top_k
    cap = expert_capacity(T, mc)

    # position of each (token, slot) within its expert, in flat order
    flat_e = idx.reshape(-1)                              # [T*k]
    onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)   # [T*k, E]
    pos_in_e = jnp.take_along_axis(jnp.cumsum(onehot, axis=0) - onehot,
                                   flat_e[:, None], axis=1)[:, 0]
    keep = pos_in_e < cap                                 # capacity mask

    # dispatch: [E, cap, d].  slot i belongs to token i//k, so the
    # token→slot expansion is a local broadcast+reshape (keeps the
    # (pod,data) sharding — a gather ``xt[tok_of_slot]`` would force an
    # all-gather of the whole token tensor).  The scatter into the
    # E-major buffer is the expert-parallel all-to-all.
    e_safe = jnp.where(keep, flat_e, 0)
    p_safe = jnp.where(keep, pos_in_e, cap - 1)
    xt_rep = jnp.broadcast_to(xt[:, None], (T, k, d)).reshape(T * k, d)
    upd = jnp.where(keep[:, None], xt_rep, 0).astype(xt.dtype)
    upd = constrain(upd, ("pod", "data"), None)
    disp = jnp.zeros((E, cap, d), xt.dtype)
    disp = disp.at[e_safe, p_safe].add(upd)
    disp = constrain(disp, "model", None, None)

    # expert FFN: [E, cap, d] x [E, d, fe]  (E on the model axis)
    h = jnp.einsum("ecd,edf->ecf", disp, p["w_gate"][li])
    u = jnp.einsum("ecd,edf->ecf", disp, p["w_up"][li])
    y = jnp.einsum("ecf,efd->ecd", jax.nn.silu(h) * u, p["w_down"][li])
    y = constrain(y, "model", None, None)

    # combine: slot outputs gathered back token-major, then a local
    # [T, k] reduction (no scatter — slots of one token are adjacent)
    slot_out = y[e_safe, p_safe]                          # [T*k, d]
    slot_out = constrain(slot_out, ("pod", "data"), None)
    slot_out = jnp.where(keep[:, None], slot_out, 0)
    w = (gates.reshape(-1) * keep).astype(slot_out.dtype) # [T*k]
    out = (slot_out * w[:, None]).reshape(T, k, d).sum(axis=1)
    out = constrain(out, ("pod", "data"), None)
    return out.reshape(b, s, d), aux
