"""Model assembly: init + train/prefill forward for every family.

The forward is a single ``jax.lax.scan`` over stacked layer params so
the traced HLO has one layer body regardless of depth (compile-time
control for the 512-device dry-run).  Families:

  dense / vlm / audio : [attn → mlp] × L
  moe                 : [attn → moe_ffn] × L
  ssm                 : [mamba2] × L
  hybrid (zamba2)     : [mamba2 (+ shared attn every k)] × L
  hybrid (pattern)    : [mamba2 | attn → held moe_ffn] per layer_types
                        (models/granite.py)

VLM/audio frontends are stubs: precomputed patch/frame embeddings are
consumed as prefix tokens (DESIGN.md §4).
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config import ModelConfig, pad_vocab
from repro.models import granite as G
from repro.models import layers as Lyr
from repro.models import mamba2 as M2
from repro.models import moe as MoE
from repro.models.layers import (attn_qkv, blocked_causal_attention,
                                 init_attn, init_embed,
                                 init_mlp, lm_logits, mlp, rms_norm,
                                 shard_activation)

Params = Dict


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------
def init_params(key, cfg: ModelConfig, dtype=jnp.bfloat16) -> Params:
    if cfg.layer_types is not None:
        return G.init_params(key, cfg, dtype)
    keys = jax.random.split(key, 8)
    v_pad = pad_vocab(cfg.vocab_size)
    p: Params = {"tok": init_embed(keys[0], cfg, v_pad, dtype)}
    L = cfg.n_layers

    if cfg.family in ("dense", "vlm", "audio"):
        p["layers"] = {
            **init_attn(keys[1], cfg, L, dtype),
            **init_mlp(keys[2], cfg.d_model, cfg.d_ff, L, dtype),
            "ln1": jnp.ones((L, cfg.d_model), dtype),
            "ln2": jnp.ones((L, cfg.d_model), dtype),
        }
    elif cfg.family == "moe":
        p["layers"] = {
            **init_attn(keys[1], cfg, L, dtype),
            **MoE.init_moe(keys[2], cfg, L, dtype),
            "ln1": jnp.ones((L, cfg.d_model), dtype),
            "ln2": jnp.ones((L, cfg.d_model), dtype),
        }
    elif cfg.family == "ssm":
        p["layers"] = {
            **M2.init_mamba2(keys[1], cfg, L, dtype),
            "ln1": jnp.ones((L, cfg.d_model), dtype),
        }
    elif cfg.family == "hybrid":
        p["layers"] = {
            **M2.init_mamba2(keys[1], cfg, L, dtype),
            "ln1": jnp.ones((L, cfg.d_model), dtype),
        }
        # one shared attention+MLP block (Zamba2-style tied weights)
        p["shared_attn"] = {
            **init_attn(keys[3], cfg, 1, dtype),
            **init_mlp(keys[4], cfg.d_model, cfg.d_ff, 1, dtype),
            "ln1": jnp.ones((1, cfg.d_model), dtype),
            "ln2": jnp.ones((1, cfg.d_model), dtype),
        }
    else:
        raise ValueError(f"unknown family {cfg.family}")
    return p


# ---------------------------------------------------------------------------
# forward (train / prefill semantics: full sequence, causal)
# ---------------------------------------------------------------------------
def _attn_block(x, lp, li, cfg: ModelConfig, positions, window):
    h = rms_norm(x, lp["ln1"][li], cfg.rms_eps)
    q, k, v = attn_qkv(h, lp, li, cfg, positions)
    o = blocked_causal_attention(q, k, v, window=window)
    b, s, _, _ = o.shape
    x = x + o.reshape(b, s, -1) @ lp["wo"][li]
    return x


def _mlp_block(x, lp, li, cfg: ModelConfig):
    h = rms_norm(x, lp["ln2"][li], cfg.rms_eps)
    return x + mlp(h, lp, li)


def _moe_block(x, lp, li, cfg: ModelConfig, dropless: bool = False):
    h = rms_norm(x, lp["ln2"][li], cfg.rms_eps)
    fn = MoE.moe_ffn_held if dropless else MoE.moe_ffn
    out, aux = fn(h, lp, li, cfg)[:2]
    return x + out, aux


def embed_inputs(params: Params, cfg: ModelConfig, tokens: jnp.ndarray,
                 prefix_emb: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    x = params["tok"]["embed"][tokens]                   # [B,S,d]
    if prefix_emb is not None:
        x = jnp.concatenate([prefix_emb.astype(x.dtype), x], axis=1)
    return x


def forward(params: Params, cfg: ModelConfig, tokens: jnp.ndarray,
            prefix_emb: Optional[jnp.ndarray] = None,
            window: Optional[int] = None,
            remat: bool = True,
            moe_dropless: bool = False,
            slice_vocab: bool = True) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Full causal forward.  Returns (logits [B,S_total,vocab], aux_loss).

    ``window`` optionally restricts attention (sliding-window variant).
    ``moe_dropless``: the serving path's held-expert layer (no
    capacity drop) in place of the capacity dispatch.  A layer-pattern
    model runs its own layers (``models/granite.py``), unrolled, and
    always returns the sliced vocabulary.
    """
    if cfg.layer_types is not None:
        assert prefix_emb is None and window is None
        return G.forward(params, cfg, tokens), jnp.float32(0)
    x = embed_inputs(params, cfg, tokens, prefix_emb)
    x = shard_activation(x)
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    lp = params["layers"]

    if cfg.family in ("dense", "vlm", "audio", "moe"):
        is_moe = cfg.family == "moe"

        def layer(carry, li):
            x, aux = carry
            x = _attn_block(x, lp, li, cfg, positions, window)
            if is_moe:
                x, a = _moe_block(x, lp, li, cfg, moe_dropless)
                aux = aux + a
            else:
                x = _mlp_block(x, lp, li, cfg)
            return (shard_activation(x, seq="model"), aux), None

        body = jax.checkpoint(layer, prevent_cse=False) if remat else layer
        (x, aux), _ = jax.lax.scan(body, (x, jnp.float32(0)),
                                   jnp.arange(cfg.n_layers))

    elif cfg.family == "ssm":
        sp = Lyr.model_axis_size()     # sequence-parallel SSD (§Perf)

        def layer(x, li):
            h = rms_norm(x, lp["ln1"][li], cfg.rms_eps)
            out, _ = M2.mamba2_mixer(h, lp, li, cfg, seq_parallel=sp)
            return shard_activation(x + out, seq="model"), None

        body = jax.checkpoint(layer, prevent_cse=False) if remat else layer
        x, _ = jax.lax.scan(body, x, jnp.arange(cfg.n_layers))
        aux = jnp.float32(0)

    elif cfg.family == "hybrid":
        # grouped scan: [attn_every × mamba2 → shared attn] × n_groups,
        # then the ungrouped tail layers.  No lax.cond in the body —
        # the static structure lowers cleaner and keeps the HLO FLOP
        # count well-defined (launch/hlo_analysis.py).
        sa = params["shared_attn"]
        n_groups = cfg.n_layers // cfg.attn_every
        tail_layers = cfg.n_layers - n_groups * cfg.attn_every
        sp = Lyr.model_axis_size()     # sequence-parallel SSD (§Perf)

        def ssm_layer(x, li):
            h = rms_norm(x, lp["ln1"][li], cfg.rms_eps)
            out, _ = M2.mamba2_mixer(h, lp, li, cfg, seq_parallel=sp)
            return shard_activation(x + out, seq="model")

        def group(x, gi):
            for j in range(cfg.attn_every):
                x = ssm_layer(x, gi * cfg.attn_every + j)
            x = _attn_block(x, sa, 0, cfg, positions, window)
            x = _mlp_block(x, sa, 0, cfg)
            return shard_activation(x, seq="model"), None

        body = jax.checkpoint(group, prevent_cse=False) if remat else group
        x, _ = jax.lax.scan(body, x, jnp.arange(n_groups))
        for j in range(tail_layers):
            x = ssm_layer(x, n_groups * cfg.attn_every + j)
        aux = jnp.float32(0)
    else:
        raise ValueError(cfg.family)

    logits = lm_logits(x, params["tok"], cfg)
    if slice_vocab:
        logits = logits[..., :cfg.vocab_size]
    return logits, aux


def cross_entropy(logits, labels):
    """CE that stays sharding-friendly when the vocab dim is sharded.

    Avoids materializing a full f32 log-softmax and avoids the gather of
    ``take_along_axis`` along a (potentially model-sharded) vocab axis:
    reductions (max / logsumexp) partition cleanly under GSPMD, and the
    label logit is picked with a fused iota-compare mask.
    """
    logits = Lyr.shard_activation(logits, last="model")
    mask = labels >= 0
    safe = jnp.maximum(labels, 0)
    lmax = jax.lax.stop_gradient(logits.max(axis=-1, keepdims=True))
    shifted = (logits - lmax).astype(jnp.float32)
    lse = jnp.log(jnp.sum(jnp.exp(shifted), axis=-1))
    vocab_iota = jax.lax.broadcasted_iota(jnp.int32, logits.shape,
                                          logits.ndim - 1)
    picked = jnp.sum(jnp.where(vocab_iota == safe[..., None], shifted, 0.0),
                     axis=-1)
    nll = lse - picked
    n = jnp.maximum(mask.sum(), 1)
    return (nll * mask).sum() / n


def loss_fn(params: Params, cfg: ModelConfig, tokens, labels,
            prefix_emb=None, remat: bool = True):
    """Causal LM cross-entropy (labels −100 are masked)."""
    # keep the padded vocab dim intact: the CE reductions shard cleanly
    # and labels never index the padding (slicing would break the
    # model-axis sharding of the logits)
    logits, aux = forward(params, cfg, tokens, prefix_emb, remat=remat,
                          slice_vocab=False)
    if prefix_emb is not None:
        logits = logits[:, prefix_emb.shape[1]:]
    return cross_entropy(logits, labels) + aux
