"""Mamba-2 (mamba2-2.7b): the benchmark's own copy of the plain
reference, its seeded weights and the work each serving step needs.

Only ``program_config`` imports the system under test (its
configuration classes); the reference imports nothing of it.  The
weight tree has the layout the serving engine takes (leaves stacked on
a leading model axis of 1, per-layer leaves on a layer axis).

Layer equations, as published (arXiv:2405.21060, ``Mamba2`` block with
``norm_before_gate=False``): in_proj → [z, xBC, dt]; depthwise causal
conv1d + SiLU over xBC; dt = softplus(dt + dt_bias), A = −exp(A_log);
per head the state recurrence s_t = exp(dt_t·A)·s_{t−1} + dt_t·x_t⊗B_t,
y_t = C_t·s_t + D·x_t; y = RMSNorm(y ⊙ SiLU(z)); out_proj; residual.
The reference runs the recurrence token by token, which is the
definition the chunked SSD algorithm computes.  The LM head is tied to
the embedding.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
MATRICES = ("in_proj", "out_proj")


def dims(c: dict) -> dict:
    """Sizes by letter.  ``V`` is the published vocabulary padded to
    ``pad_vocab_size_multiple`` (the checkpoint's embedding rows and
    logits); ``Vp`` pads it further for the weight tree's layout."""
    s = c["ssm_cfg"]
    d = c["d_model"]
    di = s["expand"] * d
    pad = c.get("pad_vocab_size_multiple", 1)
    V = -(-c["vocab_size"] // pad) * pad
    return dict(L=c["n_layer"], d=d, di=di, N=s["d_state"],
                P=s["headdim"], H=di // s["headdim"], G=s["ngroups"],
                K=s["d_conv"], Q=s["chunk_size"], V=V,
                Vp=-(-V // 256) * 256, eps=c["norm_epsilon"])


def program_config(c: dict) -> dict:
    """Keyword arguments of the serving system's model configuration,
    its ``SSMConfig`` included."""
    from repro.config import SSMConfig
    m, s = dims(c), c["ssm_cfg"]
    return dict(family="ssm", n_layers=m["L"], d_model=m["d"], n_heads=0,
                n_kv_heads=0, d_ff=0, vocab_size=m["V"],
                rms_eps=m["eps"], tie_embeddings=True,
                ssm=SSMConfig(d_state=s["d_state"], head_dim=s["headdim"],
                              expand=s["expand"], conv_kernel=s["d_conv"],
                              chunk_size=s["chunk_size"],
                              n_groups=s["ngroups"]))


def init_weights(key, c: dict, dtype):
    """Seeded weights: projections normal with 1/sqrt(fan-in) scale,
    A_log = log(U[1, 16]) and dt in [1e-3, 0.1] spread over the heads
    (the published initialisation ranges), D = 1, norms at 1."""
    m = dims(c)
    L, d, di, N, H, G, K, Vp = (m[k] for k in ("L", "d", "di", "N", "H",
                                               "G", "K", "Vp"))
    conv = di + 2 * G * N
    ks = iter(jax.random.split(key, 5))

    def normal(shape, scale):
        return (jax.random.normal(next(ks), shape, F32) * scale).astype(dtype)

    dt = jnp.linspace(1e-3, 0.1, H, dtype=F32)
    layers = {"in_proj": normal((L, d, 2 * di + 2 * G * N + H),
                                1 / math.sqrt(d)),
              "conv_w": normal((L, K, conv), 0.2),
              "conv_b": jnp.zeros((L, conv), dtype),
              "a_log": jnp.broadcast_to(
                  jnp.log(jnp.linspace(1.0, 16.0, H, dtype=F32)), (L, H)),
              "dt_bias": jnp.broadcast_to(dt + jnp.log(-jnp.expm1(-dt)),
                                          (L, H)),
              "d_skip": jnp.ones((L, H), F32),
              "gnorm": jnp.ones((L, di), dtype),
              "out_proj": normal((L, di, d), 1 / math.sqrt(di)),
              "ln1": jnp.ones((L, d), dtype)}
    tok = {"embed": normal((Vp, d), 0.02), "out_norm": jnp.ones((d,), dtype)}
    return jax.tree_util.tree_map(lambda a: a[None],
                                  {"tok": tok, "layers": layers})


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def forward(w, c: dict, tokens, quantize=None):
    """Logits [B, S, vocab] in float32 of a causal forward over
    ``tokens`` [B, S], the state recurrence run token by token.
    ``quantize`` replaces each weight matrix (the projections and the
    tied head) before use: the lower-precision control."""
    m = dims(c)
    q_ = quantize or (lambda a: a)
    p = jax.tree_util.tree_map(lambda a: a[0], w)
    B, S = tokens.shape
    di, N, H, P, G, K = (m[k] for k in ("di", "N", "H", "P", "G", "K"))
    x = p["tok"]["embed"][tokens].astype(F32)

    def layer(x, lw):
        lw = {k: v.astype(F32) for k, v in lw.items()}
        lw.update({k: q_(lw[k]) for k in MATRICES})
        h = _rms(x, lw["ln1"], m["eps"])
        zxbcdt = h @ lw["in_proj"]
        z = zxbcdt[..., :di]
        xbc = zxbcdt[..., di:2 * di + 2 * G * N]
        dt = zxbcdt[..., 2 * di + 2 * G * N:]
        pad = jnp.concatenate([jnp.zeros((B, K - 1, xbc.shape[-1]), F32),
                               xbc], 1)
        xbc = jax.nn.silu(sum(pad[:, i:i + S] * lw["conv_w"][i]
                              for i in range(K)) + lw["conv_b"])
        xs = xbc[..., :di].reshape(B, S, H, P)
        bm = xbc[..., di:di + G * N].reshape(B, S, G, N)
        cm = xbc[..., di + G * N:].reshape(B, S, G, N)
        bm, cm = jnp.repeat(bm, H // G, 2), jnp.repeat(cm, H // G, 2)
        dt = jax.nn.softplus(dt + lw["dt_bias"])                  # [B,S,H]
        a = -jnp.exp(lw["a_log"])

        def step(s, t):
            xt, bt, ct, dtt = t
            s = (s * jnp.exp(dtt * a)[..., None, None]
                 + (dtt[..., None] * xt)[..., None] * bt[:, :, None, :])
            return s, jnp.einsum("bhpn,bhn->bhp", s, ct)

        seq = tuple(jnp.moveaxis(t, 1, 0) for t in (xs, bm, cm, dt))
        _, y = jax.lax.scan(step, jnp.zeros((B, H, P, N), F32), seq)
        y = jnp.moveaxis(y, 0, 1) + lw["d_skip"][:, None] * xs
        y = _rms(y.reshape(B, S, di) * jax.nn.silu(z), lw["gnorm"],
                 m["eps"])
        return x + y @ lw["out_proj"], None

    x, _ = jax.lax.scan(layer, x, p["layers"])
    x = _rms(x, p["tok"]["out_norm"].astype(F32), m["eps"])
    return x @ q_(p["tok"]["embed"][:m["V"]].astype(F32).T)


# ---------------------------------------------------------------------------
# work a step needs (FLOPs and HBM bytes), from shapes alone
# ---------------------------------------------------------------------------
def _layer_matmul_params(m) -> int:
    return m["d"] * (2 * m["di"] + 2 * m["G"] * m["N"] + m["H"]) \
        + m["di"] * m["d"]


def _token_layer_flops(m) -> int:
    """Per token and layer: projections, conv, state update (decay,
    outer product, add) and readout, skip and gate."""
    conv = m["di"] + 2 * m["G"] * m["N"]
    state = m["H"] * m["P"] * m["N"]
    return (2 * _layer_matmul_params(m) + 2 * m["K"] * conv
            + 5 * state + 2 * m["di"])


def weight_bytes(c: dict, dtype_bytes: int = 2) -> int:
    """Weights one step has to read: every layer (projections, conv,
    norms; A, dt bias and D in float32) and the tied head."""
    m = dims(c)
    conv = m["di"] + 2 * m["G"] * m["N"]
    per_layer = (_layer_matmul_params(m) + (m["K"] + 1) * conv
                 + m["di"] + m["d"])
    return (dtype_bytes * (m["L"] * per_layer + m["V"] * m["d"] + m["d"])
            + 4 * 3 * m["L"] * m["H"])


def decode_work(c: dict, lens, dtype_bytes: int = 2):
    """(FLOPs, bytes) of one decode step over ``len(lens)`` rows (the
    context length does not change a recurrent step's work)."""
    m = dims(c)
    conv = m["di"] + 2 * m["G"] * m["N"]
    rows = len(lens)
    flops = rows * (m["L"] * _token_layer_flops(m) + 2 * m["d"] * m["V"])
    state = m["L"] * (4 * m["H"] * m["P"] * m["N"]
                      + dtype_bytes * (m["K"] - 1) * conv)
    bytes_ = rows * (2 * state + m["d"] * dtype_bytes)  # read + write
    return flops, bytes_ + weight_bytes(c, dtype_bytes)


def prefill_work(c: dict, rows, dtype_bytes: int = 2):
    """(FLOPs, bytes) of one prefill chunk step; ``rows`` holds
    (offset, tokens) per sequence, logits at each row's last token."""
    m = dims(c)
    conv = m["di"] + 2 * m["G"] * m["N"]
    state = m["L"] * (4 * m["H"] * m["P"] * m["N"]
                      + dtype_bytes * (m["K"] - 1) * conv)
    flops = bytes_ = 0
    for off, n in rows:
        flops += n * m["L"] * _token_layer_flops(m) + 2 * m["d"] * m["V"]
        bytes_ += (2 if off else 1) * state + n * m["d"] * dtype_bytes
    return flops, bytes_ + weight_bytes(c, dtype_bytes)
