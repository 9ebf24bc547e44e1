"""Granite-4.0-H (granite-4.0-h-small): the benchmark's own copy of the
plain reference, its seeded weights and the work each serving step
needs, for one chip's share of an expert-parallel deployment.

Only ``program_config`` imports the system under test (its
configuration classes); the reference imports nothing of it.  The
weight tree has the layout the serving engine takes (leaves on a
leading model axis of 1): ``tok`` (tied embedding, final norm),
``layers`` (per layer: both norms, the router, the held experts, the
shared expert), ``mamba`` (per Mamba-2 layer, in order) and ``attn``
(per attention layer, in order).

Layer equations, as published (``GraniteMoeHybridForCausalLM``):
embeddings × ``embedding_multiplier``; per layer, RMSNorm → the mixer
``layer_types`` names → residual scaled by ``residual_multiplier``;
RMSNorm → MoE FFN (router over all experts, top-k, gates a softmax
over the chosen logits, SwiGLU experts) plus the shared SwiGLU expert →
residual scaled the same; final RMSNorm, the tied head, logits ÷
``logits_scaling``.  The Mamba-2 mixer is the one of
``bench/arch/mamba2.py`` (conv with bias, gated RMSNorm), its
recurrence run token by token; attention is causal GQA with no
positional encoding and score scale ``attention_multiplier``.

Departures, each also in the configuration file: the chip holds
``num_local_experts`` of the ``num_global_experts`` experts, from
``first_local_expert``; the router scores all of them, and the layer's
MoE output is the held experts' share alone (what the other chips of
the deployment would add is left out, in the program and here alike).
The router's weights are float32.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
MATRICES = {"layers": ("router", "w_gate", "w_up", "w_down", "ws_gate",
                       "ws_up", "ws_down"),
            "mamba": ("in_proj", "out_proj"),
            "attn": ("wq", "wk", "wv", "wo")}


def dims(c: dict) -> dict:
    """Sizes by letter: ``Lm``/``La`` Mamba-2 and attention layers, ``E``
    experts routed over, ``Eh`` held from ``E0``, ``Hm`` Mamba heads."""
    d, H = c["hidden_size"], c["num_attention_heads"]
    types = tuple(c["layer_types"])
    assert len(types) == c["num_hidden_layers"], "one type per layer"
    Hm, P = c["mamba_n_heads"], c["mamba_d_head"]
    assert Hm * P == c["mamba_expand"] * d
    return dict(L=len(types), types=types, Lm=types.count("mamba"),
                La=types.count("attention"), d=d, H=H,
                KV=c["num_key_value_heads"], hd=d // H,
                E=c["num_global_experts"], Eh=c["num_local_experts"],
                E0=c["first_local_expert"], k=c["num_experts_per_tok"],
                fe=c["intermediate_size"], ds=c["shared_intermediate_size"],
                Hm=Hm, P=P, di=Hm * P, N=c["mamba_d_state"],
                G=c["mamba_n_groups"], K=c["mamba_d_conv"],
                Q=c["mamba_chunk_size"], V=c["vocab_size"],
                Vp=-(-c["vocab_size"] // 256) * 256, eps=c["rms_norm_eps"],
                emb=c["embedding_multiplier"], res=c["residual_multiplier"],
                lsc=c["logits_scaling"], att=c["attention_multiplier"])


def program_config(c: dict) -> dict:
    """Keyword arguments of the serving system's model configuration,
    its ``MoEConfig`` and ``SSMConfig`` included."""
    from repro.config import MoEConfig, SSMConfig
    assert c["position_embedding_type"] == "nope"
    assert c["tie_word_embeddings"] and c["hidden_act"] == "silu"
    assert c["mamba_conv_bias"] and not c["mamba_proj_bias"]
    assert not c["attention_bias"]
    m = dims(c)
    return dict(family="hybrid", n_layers=m["L"], d_model=m["d"],
                n_heads=m["H"], n_kv_heads=m["KV"], head_dim=m["hd"],
                d_ff=0, vocab_size=m["V"], rms_eps=m["eps"],
                tie_embeddings=True, layer_types=m["types"], rope=False,
                embedding_multiplier=float(m["emb"]),
                residual_multiplier=float(m["res"]),
                logits_scaling=float(m["lsc"]),
                attention_multiplier=float(m["att"]),
                moe=MoEConfig(n_experts=m["E"], top_k=m["k"],
                              d_expert=m["fe"], d_shared=m["ds"],
                              held_start=m["E0"], held_count=m["Eh"]),
                ssm=SSMConfig(d_state=m["N"], head_dim=m["P"],
                              expand=c["mamba_expand"], conv_kernel=m["K"],
                              chunk_size=m["Q"], n_groups=m["G"]))


def init_weights(key, c: dict, dtype):
    """Seeded weights: matrices normal with 1/sqrt(fan-in) scale (the
    router too, in float32); the tied embedding with 1/(embedding
    multiplier · sqrt(d)), so that the scaled embedding has unit norm
    and the random layers, not the input token, decide the logits; conv
    bias normal 0.1; Mamba-2 A and dt over the published initialisation
    ranges, D = 1; norms at 1."""
    m = dims(c)
    L, Lm, La, d, di, N, Hm, G, K = (m[x] for x in (
        "L", "Lm", "La", "d", "di", "N", "Hm", "G", "K"))
    E, Eh, fe, ds, H, KV, hd = (m[x] for x in ("E", "Eh", "fe", "ds", "H",
                                                "KV", "hd"))
    conv = di + 2 * G * N
    ks = iter(jax.random.split(key, 18))

    def normal(shape, scale, dt=None):
        return (jax.random.normal(next(ks), shape, F32) * scale).astype(
            dt or dtype)

    tok = {"embed": normal((m["Vp"], d), 1 / (m["emb"] * math.sqrt(d))),
           "out_norm": jnp.ones((d,), dtype)}
    layers = {"ln1": jnp.ones((L, d), dtype), "ln2": jnp.ones((L, d), dtype),
              "router": normal((L, d, E), 1 / math.sqrt(d), F32),
              "w_gate": normal((L, Eh, d, fe), 1 / math.sqrt(d)),
              "w_up": normal((L, Eh, d, fe), 1 / math.sqrt(d)),
              "w_down": normal((L, Eh, fe, d), 1 / math.sqrt(fe)),
              "ws_gate": normal((L, d, ds), 1 / math.sqrt(d)),
              "ws_up": normal((L, d, ds), 1 / math.sqrt(d)),
              "ws_down": normal((L, ds, d), 1 / math.sqrt(ds))}
    dt = jnp.linspace(1e-3, 0.1, Hm, dtype=F32)
    mamba = {"in_proj": normal((Lm, d, 2 * di + 2 * G * N + Hm),
                               1 / math.sqrt(d)),
             "conv_w": normal((Lm, K, conv), 0.2),
             "conv_b": normal((Lm, conv), 0.1),
             "a_log": jnp.broadcast_to(
                 jnp.log(jnp.linspace(1.0, 16.0, Hm, dtype=F32)), (Lm, Hm)),
             "dt_bias": jnp.broadcast_to(dt + jnp.log(-jnp.expm1(-dt)),
                                         (Lm, Hm)),
             "d_skip": jnp.ones((Lm, Hm), F32),
             "gnorm": jnp.ones((Lm, di), dtype),
             "out_proj": normal((Lm, di, d), 1 / math.sqrt(di))}
    attn = {"wq": normal((La, d, H * hd), 1 / math.sqrt(d)),
            "wk": normal((La, d, KV * hd), 1 / math.sqrt(d)),
            "wv": normal((La, d, KV * hd), 1 / math.sqrt(d)),
            "wo": normal((La, H * hd, d), 1 / math.sqrt(H * hd))}
    return jax.tree_util.tree_map(
        lambda a: a[None],
        {"tok": tok, "layers": layers, "mamba": mamba, "attn": attn})


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _mamba(h, lw, m):
    """The Mamba-2 mixer over h [B, S, d], its recurrence token by token."""
    B, S, _ = h.shape
    di, N, Hm, P, G, K = (m[x] for x in ("di", "N", "Hm", "P", "G", "K"))
    zxbcdt = h @ lw["in_proj"]
    z = zxbcdt[..., :di]
    xbc = zxbcdt[..., di:2 * di + 2 * G * N]
    dt = zxbcdt[..., 2 * di + 2 * G * N:]
    pad = jnp.concatenate([jnp.zeros((B, K - 1, xbc.shape[-1]), F32), xbc], 1)
    xbc = jax.nn.silu(sum(pad[:, i:i + S] * lw["conv_w"][i]
                          for i in range(K)) + lw["conv_b"])
    xs = xbc[..., :di].reshape(B, S, Hm, P)
    bm = xbc[..., di:di + G * N].reshape(B, S, G, N)
    cm = xbc[..., di + G * N:].reshape(B, S, G, N)
    bm, cm = jnp.repeat(bm, Hm // G, 2), jnp.repeat(cm, Hm // G, 2)
    dt = jax.nn.softplus(dt + lw["dt_bias"])                      # [B,S,Hm]
    a = -jnp.exp(lw["a_log"])

    def step(s, t):
        xt, bt, ct, dtt = t
        s = (s * jnp.exp(dtt * a)[..., None, None]
             + (dtt[..., None] * xt)[..., None] * bt[:, :, None, :])
        return s, jnp.einsum("bhpn,bhn->bhp", s, ct)

    seq = tuple(jnp.moveaxis(t, 1, 0) for t in (xs, bm, cm, dt))
    _, y = jax.lax.scan(step, jnp.zeros((B, Hm, P, N), F32), seq)
    y = jnp.moveaxis(y, 0, 1) + lw["d_skip"][:, None] * xs
    y = _rms(y.reshape(B, S, di) * jax.nn.silu(z), lw["gnorm"], m["eps"])
    return y @ lw["out_proj"]


def _attention(h, lw, m):
    """Causal GQA over h [B, S, d]: no positional encoding, scores
    scaled by ``attention_multiplier``."""
    B, S, _ = h.shape
    q = (h @ lw["wq"]).reshape(B, S, m["H"], m["hd"])
    k = (h @ lw["wk"]).reshape(B, S, m["KV"], m["hd"])
    v = (h @ lw["wv"]).reshape(B, S, m["KV"], m["hd"])
    rep = m["H"] // m["KV"]
    k, v = jnp.repeat(k, rep, 2), jnp.repeat(v, rep, 2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * m["att"]
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool))[None, None], s, -jnp.inf)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)
    return o.reshape(B, S, -1) @ lw["wo"]


def _moe(h, lw, m):
    """The held experts' share of the MoE FFN, plus the shared expert:
    each held expert weighs its output by the token's gate for it (0
    where the token's top k leave it out)."""
    logits = h @ lw["router"]                                     # [B,S,E]
    top, idx = jax.lax.top_k(logits, m["k"])
    gates = jax.nn.softmax(top, -1)
    held = m["E0"] + jnp.arange(m["Eh"])
    w = jnp.einsum("bsk,bske->bse", gates,
                   (idx[..., None] == held).astype(F32))         # [B,S,Eh]
    g = jnp.einsum("bsd,edf->bsef", h, lw["w_gate"])
    u = jnp.einsum("bsd,edf->bsef", h, lw["w_up"])
    y = jnp.einsum("bsef,efd->bsed", jax.nn.silu(g) * u, lw["w_down"])
    shared = (jax.nn.silu(h @ lw["ws_gate"]) * (h @ lw["ws_up"])) \
        @ lw["ws_down"]
    return jnp.einsum("bse,bsed->bsd", w, y) + shared


def forward(w, c: dict, tokens, quantize=None):
    """Logits [B, S, vocab] in float32 of a causal forward over
    ``tokens`` [B, S].  One scan over the layers: each step runs the
    layer's mixer (a switch on its type; its weights at its ordinal
    among the layers of that type) and its FFN.  ``quantize`` replaces
    each weight matrix (projections, experts, router, the tied head)
    before use: the lower-precision control."""
    m = dims(c)
    q_ = quantize or (lambda a: a)
    p = jax.tree_util.tree_map(lambda a: a[0], w)
    x = p["tok"]["embed"][tokens].astype(F32) * m["emb"]
    is_attn = jnp.array([t == "attention" for t in m["types"]])
    ordinal = jnp.cumsum(jnp.stack([~is_attn, is_attn]), 1) - 1  # [2, L]

    def take(group, i):
        lw = {k: v[i].astype(F32) for k, v in p[group].items()}
        return {k: q_(v) if k in MATRICES[group] else v
                for k, v in lw.items()}

    def layer(x, li):
        lw = take("layers", li)
        h = _rms(x, lw["ln1"], m["eps"])
        mixed = jax.lax.cond(
            is_attn[li],
            lambda h: _attention(h, take("attn", ordinal[1, li]), m),
            lambda h: _mamba(h, take("mamba", ordinal[0, li]), m), h)
        x = x + mixed * m["res"]
        h = _rms(x, lw["ln2"], m["eps"])
        return x + _moe(h, lw, m) * m["res"], None

    x, _ = jax.lax.scan(layer, x, jnp.arange(m["L"]))
    x = _rms(x, p["tok"]["out_norm"].astype(F32), m["eps"])
    return x @ q_(p["tok"]["embed"][:m["V"]].astype(F32).T) / m["lsc"]


# ---------------------------------------------------------------------------
# work a step needs (FLOPs and HBM bytes), from shapes alone
# ---------------------------------------------------------------------------
def _mamba_matmul_params(m) -> int:
    return m["d"] * (2 * m["di"] + 2 * m["G"] * m["N"] + m["Hm"]) \
        + m["di"] * m["d"]


def _attn_matmul_params(m) -> int:
    return m["d"] * (m["H"] + 2 * m["KV"]) * m["hd"] + m["H"] * m["hd"] * m["d"]


def _dense_token_flops(m) -> int:
    """Per token, every layer's work but the routed experts' and the
    attention scores: mixer projections, Mamba-2 conv, state update and
    readout and gate, router, shared expert; and the head."""
    conv = m["di"] + 2 * m["G"] * m["N"]
    state = m["Hm"] * m["P"] * m["N"]
    mamba = (2 * _mamba_matmul_params(m) + 2 * m["K"] * conv + 5 * state
             + 2 * m["di"])
    ffn = 2 * (m["d"] * m["E"] + 3 * m["d"] * m["ds"])
    return (m["Lm"] * mamba + m["La"] * 2 * _attn_matmul_params(m)
            + m["L"] * ffn + 2 * m["d"] * m["V"])


def _expert_bytes(m) -> int:
    return 3 * m["d"] * m["fe"]


def _moe_work(m, tokens: int, dtype_bytes: int):
    """(FLOPs, bytes) of the held experts over ``tokens`` tokens under
    uniform routing: T·k·Eh/E pairs, each held expert read once if any
    of the T tokens picks it, E_held·(1 − (1 − k/E)^T) of them."""
    k, E, Eh = m["k"], m["E"], m["Eh"]
    pairs = tokens * k * Eh / E
    hit = Eh * (1.0 - (1.0 - k / E) ** tokens)
    return (m["L"] * pairs * 2 * _expert_bytes(m),
            m["L"] * hit * _expert_bytes(m) * dtype_bytes)


def weight_bytes(c: dict, dtype_bytes: int = 2) -> int:
    """Weights one step reads apart from the routed experts (which
    ``decode_work``/``prefill_work`` count by the experts a step's
    tokens reach): mixers, norms, router (float32), shared expert, the
    tied head; Mamba-2's A, dt bias and D in float32."""
    m = dims(c)
    conv = m["di"] + 2 * m["G"] * m["N"]
    mamba = _mamba_matmul_params(m) + (m["K"] + 1) * conv + m["di"]
    per_layer = 2 * m["d"] + 3 * m["d"] * m["ds"]
    return (dtype_bytes * (m["Lm"] * mamba + m["La"] * _attn_matmul_params(m)
                           + m["L"] * per_layer + m["V"] * m["d"] + m["d"])
            + 4 * (m["L"] * m["d"] * m["E"] + 3 * m["Lm"] * m["Hm"]))


def _state_bytes(m, dtype_bytes: int) -> int:
    conv = m["di"] + 2 * m["G"] * m["N"]
    return m["Lm"] * (4 * m["Hm"] * m["P"] * m["N"]
                      + dtype_bytes * (m["K"] - 1) * conv)


def decode_work(c: dict, lens, dtype_bytes: int = 2):
    """(FLOPs, bytes) of one decode step over rows whose contexts hold
    ``lens`` tokens each (the new token included)."""
    m = dims(c)
    kv_tok = m["La"] * 2 * m["KV"] * m["hd"] * dtype_bytes
    moe_f, moe_b = _moe_work(m, len(lens), dtype_bytes)
    flops, bytes_ = moe_f, moe_b
    for n in lens:
        flops += _dense_token_flops(m) + 4 * m["La"] * m["H"] * m["hd"] * n
        bytes_ += n * kv_tok                         # read n - 1, write 1
        bytes_ += 2 * _state_bytes(m, dtype_bytes)   # read and write
        bytes_ += m["d"] * dtype_bytes               # embedding row
    return flops, bytes_ + weight_bytes(c, dtype_bytes)


def prefill_work(c: dict, rows, dtype_bytes: int = 2):
    """(FLOPs, bytes) of one prefill chunk step; ``rows`` holds
    (offset, tokens) per sequence, logits at each row's last token."""
    m = dims(c)
    kv_tok = m["La"] * 2 * m["KV"] * m["hd"] * dtype_bytes
    tokens = sum(n for _, n in rows)
    moe_f, moe_b = _moe_work(m, tokens, dtype_bytes)
    head = 2 * m["d"] * m["V"]
    flops, bytes_ = moe_f, moe_b
    for off, n in rows:
        flops += n * (_dense_token_flops(m) - head) + head
        flops += 4 * m["La"] * m["H"] * m["hd"] * (n * off + n * (n + 1) // 2)
        bytes_ += (2 if off else 1) * _state_bytes(m, dtype_bytes)
        bytes_ += (off + n) * kv_tok + n * m["d"] * dtype_bytes
    return flops, bytes_ + weight_bytes(c, dtype_bytes)
