"""Distributed serve-step definitions (the jobs MuxServe schedules).

One definition per (family × phase), lowered both by the 512-device
dry-run (full configs, ShapeDtypeStructs) and by CPU-scale examples
(reduced configs, real arrays).  The layer loop is a ``jax.lax.scan``
over stacked params with the per-layer KV/state cache as scanned xs/ys,
so the HLO stays one-layer-sized regardless of depth.

Phases (paper §2.1):
  * ``prefill``: full causal forward over the prompt, emit KV/state
    caches + last-token logits (compute-bound job).
  * ``decode``: ONE new token against a cache of ``seq_len`` context
    (memory-bound job) — this is what decode_32k / long_500k lower.

Cache layouts:
  dense/moe/vlm/audio : cache_k/v [L, B, S, KV, hd]
  windowed (long_500k): wkey/wval [L, B, KV, W, hd] ring buffers
  ssm                 : state [L, B, H, P, N] f32, conv_tail [L, B, K-1, C]
  hybrid (zamba2)     : ssm caches for all L + attn cache for the
                        n_attn shared-block applications
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.models import mamba2 as M2
from repro.models import moe as MoE
from repro.models.layers import (attn_qkv, blocked_causal_attention,
                                 causal_attention, lm_logits, mlp, rms_norm)
from repro.serving.cache_ops import windowed_decode_attention, write_window


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------
def _attention_prefill(x, lp, li, cfg, positions, window):
    h = rms_norm(x, lp["ln1"][li], cfg.rms_eps)
    q, k, v = attn_qkv(h, lp, li, cfg, positions)
    if x.shape[1] >= 1024:
        o = blocked_causal_attention(q, k, v, window=window)
    else:
        o = causal_attention(q, k, v, window=window)
    b, s, _, _ = o.shape
    return x + o.reshape(b, s, -1) @ lp["wo"][li], k, v


def _decode_attend_dense(q, ck, cv, lens, chunk: int = 2048):
    """q: [B,H,hd]; ck/cv: [B,S,KV,hd]; lens: [B] incl current token.

    Chunked online softmax over the context so the f32 score/prob
    temporaries stay O(chunk) rather than O(S) — at 32k context × 128
    batch the naive version's two [B,KV,G,S] f32 tensors dominate the
    per-device temp memory (measured in the dry-run; see EXPERIMENTS.md
    §Perf)."""
    B, H, hd = q.shape
    S, KV = ck.shape[1], ck.shape[2]
    G = H // KV
    scale = 1.0 / math.sqrt(hd)
    qh = q.reshape(B, KV, G, hd).astype(jnp.float32)
    if S % chunk != 0:
        chunk = S
    nc = S // chunk
    ckc = ck.reshape(B, nc, chunk, KV, hd)
    cvc = cv.reshape(B, nc, chunk, KV, hd)

    def body(carry, ci):
        m, l, acc = carry
        k = ckc[:, ci].astype(jnp.float32)               # [B,chunk,KV,hd]
        v = cvc[:, ci].astype(jnp.float32)
        s = jnp.einsum("bkgd,bskd->bkgs", qh, k) * scale
        t = ci * chunk + jnp.arange(chunk)[None, None, None, :]
        s = jnp.where(t < lens[:, None, None, None], s, -1e30)
        m_new = jnp.maximum(m, s.max(-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(-1)
        acc_new = acc * corr[..., None] + jnp.einsum("bkgs,bskd->bkgd", p, v)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, KV, G), -1e30, jnp.float32)
    l0 = jnp.zeros((B, KV, G), jnp.float32)
    a0 = jnp.zeros((B, KV, G, hd), jnp.float32)
    if nc == 1:
        (m, l, acc), _ = body((m0, l0, a0), 0)
    else:
        (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0), jnp.arange(nc))
    o = acc / jnp.maximum(l[..., None], 1e-30)
    return o.reshape(B, H, hd).astype(q.dtype)


def _write_dense(ck, cv, k_new, v_new, pos):
    """Insert one token's KV at pos[b].  ck: [B,S,KV,hd]; k_new [B,KV,hd]."""
    b_idx = jnp.arange(ck.shape[0])
    ck = ck.at[b_idx, pos].set(k_new.astype(ck.dtype))
    cv = cv.at[b_idx, pos].set(v_new.astype(cv.dtype))
    return ck, cv


def _attn_decode_token(x, lp, li, cfg, pos):
    """QKV for one token.  x: [B,d] → q/k/v [B,·,hd]."""
    h = rms_norm(x, lp["ln1"][li], cfg.rms_eps)
    q, k, v = attn_qkv(h[:, None, :], lp, li, cfg, pos[:, None])
    return q[:, 0], k[:, 0], v[:, 0]


def _ffn_decode(x, lp, li, cfg, dropless):
    h = rms_norm(x, lp["ln2"][li], cfg.rms_eps)
    if cfg.family == "moe":
        fn = MoE.moe_ffn_held if dropless else MoE.moe_ffn
        out = fn(h[:, None, :], lp, li, cfg)[0]
        return x + out[:, 0]
    return x + mlp(h, lp, li)


def _decode_attend_dense_q(q, ckq, cvq, sk, sv, lens, chunk: int = 2048):
    """Chunked online-softmax decode attention over an int8 KV cache.

    ckq/cvq: [B,S,KV,hd] int8; sk/sv: [B,S,KV] f32 per-token scales."""
    B, H, hd = q.shape
    S, KV = ckq.shape[1], ckq.shape[2]
    G = H // KV
    scale = 1.0 / math.sqrt(hd)
    qh = q.reshape(B, KV, G, hd).astype(jnp.float32)
    if S % chunk != 0:
        chunk = S
    nc = S // chunk
    ckc = ckq.reshape(B, nc, chunk, KV, hd)
    cvc = cvq.reshape(B, nc, chunk, KV, hd)
    skc = sk.reshape(B, nc, chunk, KV)
    svc = sv.reshape(B, nc, chunk, KV)

    def body(carry, ci):
        m, l, acc = carry
        k = ckc[:, ci].astype(jnp.float32) * skc[:, ci][..., None]
        v = cvc[:, ci].astype(jnp.float32) * svc[:, ci][..., None]
        s = jnp.einsum("bkgd,bskd->bkgs", qh, k) * scale
        t = ci * chunk + jnp.arange(chunk)[None, None, None, :]
        s = jnp.where(t < lens[:, None, None, None], s, -1e30)
        m_new = jnp.maximum(m, s.max(-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(-1)
        acc_new = acc * corr[..., None] + jnp.einsum("bkgs,bskd->bkgd", p, v)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, KV, G), -1e30, jnp.float32)
    l0 = jnp.zeros((B, KV, G), jnp.float32)
    a0 = jnp.zeros((B, KV, G, hd), jnp.float32)
    if nc == 1:
        (m, l, acc), _ = body((m0, l0, a0), 0)
    else:
        (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0), jnp.arange(nc))
    o = acc / jnp.maximum(l[..., None], 1e-30)
    return o.reshape(B, H, hd).astype(q.dtype)


def make_decode_step_w8kv8(cfg: ModelConfig, moe_dropless: bool = False):
    """int8-weight + int8-KV decode step (dense/moe/vlm/audio families).

    §Perf beyond-paper variant: storage halves twice over, so the
    weights serve with model-axis-only sharding (no FSDP all-gathers)
    and the KV read per step halves.  Params come from
    ``serving.quantize.quantize_params``; caches carry int8 values plus
    per-(token, head) f32 scales.
    """
    from repro.serving.quantize import (QLayerView, qmatmul, quantize_kv)
    assert cfg.family in ("dense", "moe", "vlm", "audio")

    def decode(qparams, cache_k, cache_v, scale_k, scale_v, last_tok,
               lens):
        tok = qparams["tok"]
        x = (tok["embed_q"][last_tok].astype(jnp.bfloat16)
             * jnp.squeeze(tok["embed_s"]).astype(jnp.bfloat16))
        pos = (lens - 1).astype(jnp.int32)
        b_idx = jnp.arange(x.shape[0])

        def layer(carry, li):
            x, cks, cvs, sks, svs = carry
            lp = QLayerView(qparams["layers"], li)
            q, k, v = _attn_decode_token(x, lp, 0, cfg, pos)
            kq, ks_ = quantize_kv(k)
            vq, vs_ = quantize_kv(v)
            ck = jax.lax.dynamic_index_in_dim(cks, li, keepdims=False)
            cv = jax.lax.dynamic_index_in_dim(cvs, li, keepdims=False)
            sk = jax.lax.dynamic_index_in_dim(sks, li, keepdims=False)
            sv = jax.lax.dynamic_index_in_dim(svs, li, keepdims=False)
            ck = ck.at[b_idx, pos].set(kq)
            cv = cv.at[b_idx, pos].set(vq)
            sk = sk.at[b_idx, pos].set(ks_)
            sv = sv.at[b_idx, pos].set(vs_)
            o = _decode_attend_dense_q(q, ck, cv, sk, sv, lens)
            x = x + o.reshape(x.shape[0], -1) @ lp["wo"][0]
            x = _ffn_decode(x, lp, 0, cfg, moe_dropless)
            cks = jax.lax.dynamic_update_index_in_dim(cks, ck, li, 0)
            cvs = jax.lax.dynamic_update_index_in_dim(cvs, cv, li, 0)
            sks = jax.lax.dynamic_update_index_in_dim(sks, sk, li, 0)
            svs = jax.lax.dynamic_update_index_in_dim(svs, sv, li, 0)
            return (x, cks, cvs, sks, svs), None

        (x, ck2, cv2, sk2, sv2), _ = jax.lax.scan(
            layer, (x, cache_k, cache_v, scale_k, scale_v),
            jnp.arange(cfg.n_layers))
        h = rms_norm(x, tok["out_norm"], cfg.rms_eps)
        if cfg.tie_embeddings:
            # embed scales are per-d column: fold into h, exact
            hs = (h.astype(jnp.float32)
                  * jnp.squeeze(tok["embed_s"])).astype(jnp.bfloat16)
            logits = hs @ tok["embed_q"].astype(jnp.bfloat16).T
        else:
            logits = qmatmul(h, tok["lm_head_q"], tok["lm_head_s"])
        return {"logits": logits[..., :cfg.vocab_size],
                "cache_k": ck2, "cache_v": cv2,
                "scale_k": sk2, "scale_v": sv2}

    return decode


# ---------------------------------------------------------------------------
# prefill steps
# ---------------------------------------------------------------------------
def make_prefill_step(cfg: ModelConfig, window: Optional[int] = None,
                      moe_dropless: bool = False):
    """Returns prefill(params, tokens, lens[, prefix_emb]) → outputs dict.

    ``moe_dropless``: the serving path's held-expert layer, no capacity
    drop (batch-composition-independent outputs — the CPU
    engine/consistency-test path); default
    is capacity-based dispatch (the distributed path).
    """
    fam = cfg.family

    if fam in ("dense", "moe", "vlm", "audio"):
        def prefill(params, tokens, lens, prefix_emb=None):
            x = params["tok"]["embed"][tokens]
            if prefix_emb is not None:
                x = jnp.concatenate([prefix_emb.astype(x.dtype), x], axis=1)
            B, S, _ = x.shape
            positions = jnp.broadcast_to(jnp.arange(S), (B, S))
            lp = params["layers"]

            def layer(x, li):
                x, k, v = _attention_prefill(x, lp, li, cfg, positions,
                                             window)
                h = rms_norm(x, lp["ln2"][li], cfg.rms_eps)
                if fam == "moe":
                    fn = MoE.moe_ffn_held if moe_dropless else MoE.moe_ffn
                    out = fn(h, lp, li, cfg)[0]
                    x = x + out
                else:
                    x = x + mlp(h, lp, li)
                return x, (k, v)

            x, (ks, vs) = jax.lax.scan(layer, x, jnp.arange(cfg.n_layers))
            n_pre = 0 if prefix_emb is None else prefix_emb.shape[1]
            idx = jnp.maximum(lens + n_pre - 1, 0)
            x_last = jnp.take_along_axis(x, idx[:, None, None], axis=1)[:, 0]
            logits = lm_logits(x_last, params["tok"], cfg)
            return {"logits": logits[..., :cfg.vocab_size],
                    "cache_k": ks, "cache_v": vs}
        return prefill

    if fam == "ssm":
        def prefill(params, tokens, lens, prefix_emb=None):
            x = params["tok"]["embed"][tokens]
            B, S, _ = x.shape
            positions = jnp.broadcast_to(jnp.arange(S), (B, S))
            mask = positions < lens[:, None]
            lp = params["layers"]
            from repro.models.layers import constrain, model_axis_size
            sp = model_axis_size()   # sequence-parallel SSD (§Perf)
            # residual stream stays sequence-sharded on the model axis
            # so the slab reshape inside the mixer is a local slice
            x = constrain(x, ("pod", "data"), "model", None)

            def layer(x, li):
                h = rms_norm(x, lp["ln1"][li], cfg.rms_eps)
                out, st, tail = M2.mamba2_mixer(h, lp, li, cfg,
                                                return_cache=True,
                                                length_mask=mask,
                                                seq_parallel=sp)
                return constrain(x + out, ("pod", "data"), "model",
                                 None), (st, tail)

            x, (sts, tails) = jax.lax.scan(layer, x, jnp.arange(cfg.n_layers))
            idx = jnp.maximum(lens - 1, 0)
            x_last = jnp.take_along_axis(x, idx[:, None, None], axis=1)[:, 0]
            logits = lm_logits(x_last, params["tok"], cfg)
            return {"logits": logits[..., :cfg.vocab_size],
                    "ssm_state": sts, "conv_tail": tails}
        return prefill

    if fam == "hybrid":
        n_groups = cfg.n_layers // cfg.attn_every
        tail_layers = cfg.n_layers - n_groups * cfg.attn_every

        def prefill(params, tokens, lens, prefix_emb=None):
            x = params["tok"]["embed"][tokens]
            B, S, _ = x.shape
            positions = jnp.broadcast_to(jnp.arange(S), (B, S))
            mask = positions < lens[:, None]
            lp = params["layers"]
            sa = params["shared_attn"]

            from repro.models.layers import model_axis_size
            sp = model_axis_size()     # sequence-parallel SSD (§Perf)

            def ssm_layer(x, li):
                h = rms_norm(x, lp["ln1"][li], cfg.rms_eps)
                out, st, tail = M2.mamba2_mixer(h, lp, li, cfg,
                                                return_cache=True,
                                                length_mask=mask,
                                                seq_parallel=sp)
                return x + out, st, tail

            def group(x, gi):
                sts, tails = [], []
                for j in range(cfg.attn_every):
                    li = gi * cfg.attn_every + j
                    x, st, tail = ssm_layer(x, li)
                    sts.append(st)
                    tails.append(tail)
                x, k, v = _attention_prefill(x, sa, 0, cfg, positions,
                                             window)
                h = rms_norm(x, sa["ln2"][0], cfg.rms_eps)
                x = x + mlp(h, sa, 0)
                return x, (jnp.stack(sts), jnp.stack(tails), k, v)

            x, (sts, tails, ks, vs) = jax.lax.scan(group, x,
                                                   jnp.arange(n_groups))
            sts = sts.reshape((-1,) + sts.shape[2:])
            tails = tails.reshape((-1,) + tails.shape[2:])
            for j in range(tail_layers):
                li = n_groups * cfg.attn_every + j
                x, st, tail = ssm_layer(x, li)
                sts = jnp.concatenate([sts, st[None]], 0)
                tails = jnp.concatenate([tails, tail[None]], 0)
            idx = jnp.maximum(lens - 1, 0)
            x_last = jnp.take_along_axis(x, idx[:, None, None], axis=1)[:, 0]
            logits = lm_logits(x_last, params["tok"], cfg)
            return {"logits": logits[..., :cfg.vocab_size],
                    "ssm_state": sts, "conv_tail": tails,
                    "cache_k": ks, "cache_v": vs}
        return prefill

    raise ValueError(fam)


# ---------------------------------------------------------------------------
# decode steps — ONE new token with a seq_len context cache
# ---------------------------------------------------------------------------
def make_decode_step(cfg: ModelConfig, windowed: bool = False,
                     moe_dropless: bool = False):
    """Returns decode(params, caches..., last_tok, lens) → outputs dict.

    ``lens`` is the context length INCLUDING the new token (position
    lens−1).  ``windowed=True`` uses ring-buffer sliding-window caches
    of width ``cfg.sliding_window`` (the sub-quadratic long_500k path).
    """
    fam = cfg.family

    if fam in ("dense", "moe", "vlm", "audio") and not windowed:
        def decode(params, cache_k, cache_v, last_tok, lens):
            x = params["tok"]["embed"][last_tok]          # [B, d]
            pos = (lens - 1).astype(jnp.int32)
            lp = params["layers"]

            # the cache rides the scan CARRY (not xs/ys): XLA aliases
            # while-loop carries in place, so the multi-GiB cache is a
            # single buffer (ys-stacking double-buffers it — measured
            # +2× temp on command-r decode_32k, EXPERIMENTS.md §Perf)
            def layer(carry, li):
                x, cks, cvs = carry
                ck = jax.lax.dynamic_index_in_dim(cks, li, keepdims=False)
                cv = jax.lax.dynamic_index_in_dim(cvs, li, keepdims=False)
                q, k, v = _attn_decode_token(x, lp, li, cfg, pos)
                ck, cv = _write_dense(ck, cv, k, v, pos)
                o = _decode_attend_dense(q, ck, cv, lens)
                x = x + o.reshape(x.shape[0], -1) @ lp["wo"][li]
                x = _ffn_decode(x, lp, li, cfg, moe_dropless)
                cks = jax.lax.dynamic_update_index_in_dim(cks, ck, li, 0)
                cvs = jax.lax.dynamic_update_index_in_dim(cvs, cv, li, 0)
                return (x, cks, cvs), None

            (x, ck2, cv2), _ = jax.lax.scan(
                layer, (x, cache_k, cache_v), jnp.arange(cfg.n_layers))
            logits = lm_logits(x, params["tok"], cfg)
            return {"logits": logits[..., :cfg.vocab_size],
                    "cache_k": ck2, "cache_v": cv2}
        return decode

    if fam in ("dense", "moe", "vlm", "audio") and windowed:
        W = cfg.sliding_window
        assert W, f"{cfg.name} has no sliding_window — long_500k skipped"

        def decode(params, wkey, wval, last_tok, lens):
            x = params["tok"]["embed"][last_tok]
            pos = (lens - 1).astype(jnp.int32)
            lp = params["layers"]

            def layer(x, xs):
                li, wk, wv = xs
                q, k, v = _attn_decode_token(x, lp, li, cfg, pos)
                wk, wv = write_window(wk, wv, k, v, pos)
                o = windowed_decode_attention(q, wk, wv, lens, W)
                x = x + o.reshape(x.shape[0], -1) @ lp["wo"][li]
                x = _ffn_decode(x, lp, li, cfg, moe_dropless)
                return x, (wk, wv)

            x, (wk2, wv2) = jax.lax.scan(
                layer, x, (jnp.arange(cfg.n_layers), wkey, wval))
            logits = lm_logits(x, params["tok"], cfg)
            return {"logits": logits[..., :cfg.vocab_size],
                    "wkey": wk2, "wval": wv2}
        return decode

    if fam == "ssm":
        def decode(params, ssm_state, conv_tail, last_tok, lens):
            x = params["tok"]["embed"][last_tok]
            lp = params["layers"]

            def layer(x, xs):
                li, st, tail = xs
                h = rms_norm(x, lp["ln1"][li], cfg.rms_eps)
                out, tail2, st2 = M2.mamba2_decode_step(h, lp, li, cfg,
                                                        tail, st)
                return x + out, (st2, tail2)

            x, (st2, tail2) = jax.lax.scan(
                layer, x, (jnp.arange(cfg.n_layers), ssm_state, conv_tail))
            logits = lm_logits(x, params["tok"], cfg)
            return {"logits": logits[..., :cfg.vocab_size],
                    "ssm_state": st2, "conv_tail": tail2}
        return decode

    if fam == "hybrid":
        n_groups = cfg.n_layers // cfg.attn_every
        tail_layers = cfg.n_layers - n_groups * cfg.attn_every
        W = cfg.sliding_window if windowed else None

        def decode(params, ssm_state, conv_tail, cache_k, cache_v,
                   last_tok, lens):
            x = params["tok"]["embed"][last_tok]
            pos = (lens - 1).astype(jnp.int32)
            lp = params["layers"]
            sa = params["shared_attn"]

            def ssm_step(x, li, st, tail):
                h = rms_norm(x, lp["ln1"][li], cfg.rms_eps)
                out, tail2, st2 = M2.mamba2_decode_step(h, lp, li, cfg,
                                                        tail, st)
                return x + out, st2, tail2

            def group(x, xs):
                gi, sts, tails, ck, cv = xs
                new_sts, new_tails = [], []
                for j in range(cfg.attn_every):
                    li = gi * cfg.attn_every + j
                    x, st2, tail2 = ssm_step(x, li, sts[j], tails[j])
                    new_sts.append(st2)
                    new_tails.append(tail2)
                q, k, v = _attn_decode_token(x, sa, 0, cfg, pos)
                if windowed:
                    ck, cv = write_window(ck, cv, k, v, pos)
                    o = windowed_decode_attention(q, ck, cv, lens, W)
                else:
                    ck, cv = _write_dense(ck, cv, k, v, pos)
                    o = _decode_attend_dense(q, ck, cv, lens)
                x = x + o.reshape(x.shape[0], -1) @ sa["wo"][0]
                h = rms_norm(x, sa["ln2"][0], cfg.rms_eps)
                x = x + mlp(h, sa, 0)
                return x, (jnp.stack(new_sts), jnp.stack(new_tails), ck, cv)

            g_sts = ssm_state[:n_groups * cfg.attn_every].reshape(
                (n_groups, cfg.attn_every) + ssm_state.shape[1:])
            g_tails = conv_tail[:n_groups * cfg.attn_every].reshape(
                (n_groups, cfg.attn_every) + conv_tail.shape[1:])
            x, (sts2, tails2, ck2, cv2) = jax.lax.scan(
                group, x, (jnp.arange(n_groups), g_sts, g_tails,
                           cache_k, cache_v))
            sts2 = sts2.reshape((-1,) + sts2.shape[2:])
            tails2 = tails2.reshape((-1,) + tails2.shape[2:])
            for j in range(tail_layers):
                li = n_groups * cfg.attn_every + j
                x, st2, tail2 = ssm_step(x, li, ssm_state[li],
                                         conv_tail[li])
                sts2 = jnp.concatenate([sts2, st2[None]], 0)
                tails2 = jnp.concatenate([tails2, tail2[None]], 0)
            logits = lm_logits(x, params["tok"], cfg)
            return {"logits": logits[..., :cfg.vocab_size],
                    "ssm_state": sts2, "conv_tail": tails2,
                    "cache_k": ck2, "cache_v": cv2}
        return decode

    raise ValueError(fam)
