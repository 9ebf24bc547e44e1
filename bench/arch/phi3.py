"""Phi-3 text decoder (phi-3-vision-4.2b's language model): the
benchmark's own copy of the plain reference, its seeded weights and the
work each serving step needs.

Nothing here imports the system under test.  The weight tree has the
layout the serving engine takes (``tok``/``layers`` leaves stacked on a
leading model axis of 1, per-layer leaves stacked on a layer axis), so
the harness can hand it to the engine and the reference can rebuild it
from the same seed.

Layer equations, as in the published model (RMSNorm → MHA with rotary
embeddings → residual; RMSNorm → SwiGLU MLP → residual; final RMSNorm
and an untied LM head).  Departure noted in the configuration file: the
rotary embedding is plain (theta = rope_theta), without the "su"
rescaling factors of the 128k-context checkpoint.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
MATRICES = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")


def dims(c: dict) -> dict:
    d, h = c["hidden_size"], c["num_attention_heads"]
    hd = d // h
    return dict(L=c["num_hidden_layers"], d=d, H=h,
                KV=c["num_key_value_heads"], hd=hd,
                f=c["intermediate_size"], V=c["vocab_size"],
                Vp=-(-c["vocab_size"] // 256) * 256,
                eps=c["rms_norm_eps"], theta=c["rope_theta"])


def program_config(c: dict) -> dict:
    """Keyword arguments of the serving system's model configuration."""
    m = dims(c)
    return dict(family="vlm", n_layers=m["L"], d_model=m["d"],
                n_heads=m["H"], n_kv_heads=m["KV"], d_ff=m["f"],
                vocab_size=m["V"], rope_theta=m["theta"],
                rms_eps=m["eps"], frontend_dim=m["d"],
                n_prefix_tokens=0)


def init_weights(key, c: dict, dtype):
    """Seeded weights, normal with 1/sqrt(fan-in) scale, norms at 1."""
    m = dims(c)
    L, d, H, KV, hd, f, Vp = (m[k] for k in ("L", "d", "H", "KV", "hd",
                                             "f", "Vp"))
    ks = iter(jax.random.split(key, 9))

    def normal(shape, scale):
        return (jax.random.normal(next(ks), shape, F32) * scale).astype(dtype)

    tok = {"embed": normal((Vp, d), 0.02),
           "out_norm": jnp.ones((d,), dtype),
           "lm_head": normal((d, Vp), 1 / math.sqrt(d))}
    layers = {"wq": normal((L, d, H * hd), 1 / math.sqrt(d)),
              "wk": normal((L, d, KV * hd), 1 / math.sqrt(d)),
              "wv": normal((L, d, KV * hd), 1 / math.sqrt(d)),
              "wo": normal((L, H * hd, d), 1 / math.sqrt(H * hd)),
              "w_gate": normal((L, d, f), 1 / math.sqrt(d)),
              "w_up": normal((L, d, f), 1 / math.sqrt(d)),
              "w_down": normal((L, f, d), 1 / math.sqrt(f)),
              "ln1": jnp.ones((L, d), dtype),
              "ln2": jnp.ones((L, d), dtype)}
    return jax.tree_util.tree_map(lambda a: a[None],
                                  {"tok": tok, "layers": layers})


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rotate(x, pos, theta):
    """Rotary embedding, rotate-half form.  x: [B, S, H, hd]."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd)
    ang = pos[:, None].astype(F32) * inv[None, :]
    ang = jnp.concatenate([ang, ang], -1)[None, :, None, :]
    half = jnp.concatenate([-x[..., hd // 2:], x[..., :hd // 2]], -1)
    return x * jnp.cos(ang) + half * jnp.sin(ang)


def forward(w, c: dict, tokens, quantize=None):
    """Logits [B, S, vocab] in float32 of a causal forward over
    ``tokens`` [B, S].  Every leaf is upcast to float32 inside the layer
    scan, one layer at a time.  ``quantize`` (a function on a float32
    matrix) replaces each weight matrix before use: the lower-precision
    control."""
    m = dims(c)
    q_ = quantize or (lambda a: a)
    p = jax.tree_util.tree_map(lambda a: a[0], w)
    B, S = tokens.shape
    x = p["tok"]["embed"][tokens].astype(F32)
    pos = jnp.arange(S)
    causal = jnp.tril(jnp.ones((S, S), bool))

    def layer(x, lw):
        lw = {k: v.astype(F32) for k, v in lw.items()}
        lw.update({k: q_(lw[k]) for k in MATRICES})
        h = _rms(x, lw["ln1"], m["eps"])
        q = (h @ lw["wq"]).reshape(B, S, m["H"], m["hd"])
        k = (h @ lw["wk"]).reshape(B, S, m["KV"], m["hd"])
        v = (h @ lw["wv"]).reshape(B, S, m["KV"], m["hd"])
        q, k = _rotate(q, pos, m["theta"]), _rotate(k, pos, m["theta"])
        rep = m["H"] // m["KV"]
        k, v = jnp.repeat(k, rep, 2), jnp.repeat(v, rep, 2)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(m["hd"])
        s = jnp.where(causal[None, None], s, -jnp.inf)
        o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)
        x = x + o.reshape(B, S, -1) @ lw["wo"]
        h = _rms(x, lw["ln2"], m["eps"])
        return x + (jax.nn.silu(h @ lw["w_gate"])
                    * (h @ lw["w_up"])) @ lw["w_down"], None

    x, _ = jax.lax.scan(layer, x, p["layers"])
    x = _rms(x, p["tok"]["out_norm"].astype(F32), m["eps"])
    head = q_(p["tok"]["lm_head"][:, :m["V"]].astype(F32))
    return x @ head


# ---------------------------------------------------------------------------
# work a step needs (FLOPs and HBM bytes), from shapes alone
# ---------------------------------------------------------------------------
def _layer_matmul_params(m) -> int:
    return (m["d"] * (m["H"] + 2 * m["KV"]) * m["hd"]
            + m["H"] * m["hd"] * m["d"] + 3 * m["d"] * m["f"])


def weight_bytes(c: dict, dtype_bytes: int = 2) -> int:
    """Weights one step has to read: every layer, the norms and the LM
    head (the embedding is gathered, a row per token, counted apart)."""
    m = dims(c)
    per_layer = _layer_matmul_params(m) + 2 * m["d"]
    return dtype_bytes * (m["L"] * per_layer + m["d"] * m["V"] + m["d"])


def decode_work(c: dict, lens, dtype_bytes: int = 2):
    """(FLOPs, bytes) of one decode step over rows whose contexts hold
    ``lens`` tokens each (the new token included)."""
    m = dims(c)
    kv_tok = m["L"] * 2 * m["KV"] * m["hd"] * dtype_bytes
    flops = bytes_ = 0
    for n in lens:
        flops += 2 * (m["L"] * _layer_matmul_params(m) + m["d"] * m["V"])
        flops += 4 * m["L"] * m["H"] * m["hd"] * n      # scores + mix
        bytes_ += (n - 1) * kv_tok + kv_tok              # read, write
        bytes_ += m["d"] * dtype_bytes                   # embedding row
    return flops, bytes_ + weight_bytes(c, dtype_bytes)


def prefill_work(c: dict, rows, dtype_bytes: int = 2):
    """(FLOPs, bytes) of one prefill chunk step; ``rows`` holds
    (offset, tokens) per sequence: ``tokens`` prompt tokens at
    positions offset .. offset+tokens-1, logits at the last one."""
    m = dims(c)
    kv_tok = m["L"] * 2 * m["KV"] * m["hd"] * dtype_bytes
    flops = bytes_ = 0
    for off, n in rows:
        flops += 2 * m["L"] * _layer_matmul_params(m) * n
        flops += 2 * m["d"] * m["V"]                     # last position
        # each query attends to off + its own causal prefix
        flops += 4 * m["L"] * m["H"] * m["hd"] * (n * off + n * (n + 1) // 2)
        bytes_ += off * kv_tok + n * kv_tok + n * m["d"] * dtype_bytes
    return flops, bytes_ + weight_bytes(c, dtype_bytes)
