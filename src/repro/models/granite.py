"""Granite-4.0-H layers: a mixer chosen by the layer pattern
(``ModelConfig.layer_types``: Mamba-2 or attention), then the MoE FFN
with its shared expert, each residual branch scaled.

One layer is ``post(x, mixer(pre(x)))``: ``pre`` is the mixer's norm,
``post`` adds the scaled mixer output and the scaled FFN branch.  The
mixer is whatever the caller runs at that layer: the full-sequence
forward below, or the serving engine's chunk and decode steps over the
paged KV and the SSM state.  Embeddings are scaled by
``embedding_multiplier`` (``embed``), logits divided by
``logits_scaling`` (``logits``).

Weights: ``tok`` (tied embedding, final norm); ``layers`` (per layer:
both norms, the router, the held experts, the shared expert);
``mamba`` stacked over the Mamba-2 layers in order, ``attn`` over the
attention layers — a layer's mixer weights are at its ordinal among
the layers of its kind (``mixer_ordinals``).
"""
from __future__ import annotations

import math
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp

from repro.config import ModelConfig, pad_vocab
from repro.models import mamba2 as M2
from repro.models import moe as MoE
from repro.models.layers import (attn_qkv, causal_attention, init_attn,
                                 init_embed, lm_logits, rms_norm)

Params = Dict


def init_params(key, cfg: ModelConfig, dtype=jnp.bfloat16) -> Params:
    """Seeded weights.  The tied embedding is normal with scale
    1/(``embedding_multiplier``·sqrt(d)): a scaled embedding of unit
    norm, which leaves a random-weight model's residual stream to its
    layers rather than to its input token."""
    ks = jax.random.split(key, 5)
    L, d = cfg.n_layers, cfg.d_model
    tok = init_embed(ks[0], cfg, pad_vocab(cfg.vocab_size), dtype)
    tok["embed"] = (jax.random.normal(ks[4], tok["embed"].shape, jnp.float32)
                    / (cfg.embedding_multiplier * math.sqrt(d))).astype(dtype)
    return {
        "tok": tok,
        "layers": {**MoE.init_moe(ks[1], cfg, L, dtype),
                   "ln1": jnp.ones((L, d), dtype),
                   "ln2": jnp.ones((L, d), dtype)},
        "mamba": M2.init_mamba2(ks[2], cfg, cfg.n_ssm_layers, dtype),
        "attn": init_attn(ks[3], cfg, cfg.n_attn_layers, dtype),
    }


def mixer_ordinals(cfg: ModelConfig) -> List[int]:
    """Per layer, its ordinal among the layers of its own kind."""
    seen = {"mamba": 0, "attention": 0}
    out = []
    for kind in cfg.layer_types:
        out.append(seen[kind])
        seen[kind] += 1
    return out


def embed(p: Params, cfg: ModelConfig, tokens):
    return p["tok"]["embed"][tokens] * cfg.embedding_multiplier


def logits(p: Params, cfg: ModelConfig, x):
    out = lm_logits(x, p["tok"], cfg)[..., :cfg.vocab_size]
    return out / cfg.logits_scaling


def pre(x, p: Params, li: int, cfg: ModelConfig):
    """The mixer's input: layer ``li``'s first norm."""
    return rms_norm(x, p["layers"]["ln1"][li], cfg.rms_eps)


def post(x, mixed, p: Params, li: int, cfg: ModelConfig,
         keep: Optional[jnp.ndarray] = None):
    """The rest of layer ``li`` after its mixer gave ``mixed``: both
    scaled residual branches, the second the MoE FFN and the shared
    expert.  ``keep`` masks padding tokens out of the routing.
    Returns (x, tokens routed to each held expert)."""
    r = cfg.residual_multiplier
    x = x + mixed * r
    h = rms_norm(x, p["layers"]["ln2"][li], cfg.rms_eps)
    out, _, load = MoE.moe_ffn_held(h, p["layers"], li, cfg, keep)
    return x + out * r, load


def forward(params: Params, cfg: ModelConfig, tokens):
    """Full causal forward over ``tokens`` [B, S]: logits [B, S, vocab]."""
    x = embed(params, cfg, tokens)
    b, s = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    for li, (kind, oi) in enumerate(zip(cfg.layer_types,
                                        mixer_ordinals(cfg))):
        h = pre(x, params, li, cfg)
        if kind == "mamba":
            mixed, _ = M2.mamba2_mixer(h, params["mamba"], oi, cfg)
        else:
            pa = params["attn"]
            q, k, v = attn_qkv(h, pa, oi, cfg, positions)
            mixed = causal_attention(q, k, v).reshape(b, s, -1) \
                @ pa["wo"][oi]
        x, _ = post(x, mixed, params, li, cfg)
    return logits(params, cfg, x)
