"""Mamba2 — SSD (state-space duality) mixer, pure JAX.

Implements the chunked SSD algorithm [arXiv:2405.21060 §6]: within a
chunk the sequence mixing is a dense (masked) matmul — MXU-friendly —
and states are carried across chunks with a first-order recurrence.
``kernels/ssd_scan`` is the Pallas version of the chunk kernel; this
module is the oracle and the XLA fallback.

Layer structure (Mamba2 block):
  in_proj: d → [z(di), x(di), B(G·N), C(G·N), dt(H)]
  causal conv1d (kernel K) over [x, B, C]
  SSD: y = SSD(x·dt, A·dt, B, C) + D⊙x
  gated RMSNorm(y · silu(z)); out_proj: di → d

Decode keeps a per-sequence cache: conv tail [conv_dim, K-1] and SSM
state [H, P, N] — constant size, stored in the unified pool as state
pages (DESIGN.md §4).
"""
from __future__ import annotations

import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.models.layers import rms_norm


def init_mamba2(key, cfg: ModelConfig, n_layers: int, dtype=jnp.bfloat16) -> Dict:
    sc = cfg.ssm
    d, di = cfg.d_model, cfg.d_inner
    H, P, N, G, K = cfg.n_ssm_heads, sc.head_dim, sc.d_state, sc.n_groups, sc.conv_kernel
    L = n_layers
    conv_dim = di + 2 * G * N
    ks = jax.random.split(key, 5)
    d_in_proj = 2 * di + 2 * G * N + H
    return {
        "in_proj": jax.random.normal(ks[0], (L, d, d_in_proj), dtype) / math.sqrt(d),
        "conv_w": jax.random.normal(ks[1], (L, K, conv_dim), dtype) * 0.2,
        "conv_b": jnp.zeros((L, conv_dim), dtype),
        "a_log": jnp.log(jnp.broadcast_to(
            jnp.linspace(1.0, 16.0, H, dtype=jnp.float32), (L, H))),
        "dt_bias": jnp.broadcast_to(
            jnp.log(jnp.expm1(jnp.linspace(1e-3, 0.1, H, dtype=jnp.float32))),
            (L, H)),
        "d_skip": jnp.ones((L, H), jnp.float32),
        "gnorm": jnp.ones((L, di), dtype),
        "out_proj": jax.random.normal(ks[2], (L, di, d), dtype) / math.sqrt(di),
    }


def _split_proj(zxbcdt, cfg: ModelConfig):
    sc = cfg.ssm
    di, G, N, H = cfg.d_inner, sc.n_groups, sc.d_state, cfg.n_ssm_heads
    z, xs, B, C, dt = jnp.split(
        zxbcdt, [di, 2 * di, 2 * di + G * N, 2 * di + 2 * G * N], axis=-1)
    return z, xs, B, C, dt


def causal_conv(x: jnp.ndarray, w: jnp.ndarray, b: jnp.ndarray,
                tail: jnp.ndarray | None = None) -> jnp.ndarray:
    """Depthwise causal conv1d.  x: [B,S,C], w: [K,C], tail: [B,K-1,C]."""
    K = w.shape[0]
    if tail is None:
        tail = jnp.zeros((x.shape[0], K - 1, x.shape[-1]), x.dtype)
    xp = jnp.concatenate([tail, x], axis=1)              # [B, S+K-1, C]
    out = sum(xp[:, i:i + x.shape[1]] * w[i] for i in range(K))
    return jax.nn.silu(out + b)


def causal_conv_slabbed(x: jnp.ndarray, w: jnp.ndarray, b: jnp.ndarray,
                        slabs: int) -> jnp.ndarray:
    """Causal conv over a sequence whose slabs ride the model axis.

    The K−1 halo comes from the previous slab's tail via a shift along
    the (sharded) slab dim — a [B, slabs, K−1, C] boundary exchange
    instead of GSPMD's whole-tensor resharding of the shifted slices
    (22.6 GiB → KB-scale permutes on mamba2 prefill_32k, §Perf).
    Zero halo for the first slab ≡ zero conv tail (prefill semantics).
    """
    from repro.models.layers import constrain
    B_, S, C = x.shape
    K = w.shape[0]
    Ls = S // slabs
    xs = x.reshape(B_, slabs, Ls, C)
    xs = constrain(xs, ("pod", "data"), "model", None, None)
    tails = xs[:, :, Ls - (K - 1):, :]                  # [B,slabs,K-1,C]
    halo = jnp.pad(tails[:, :-1], ((0, 0), (1, 0), (0, 0), (0, 0)))
    xp = jnp.concatenate([halo, xs], axis=2)            # [B,slabs,K-1+Ls,C]
    out = sum(xp[:, :, i:i + Ls] * w[i] for i in range(K))
    out = jax.nn.silu(out + b)
    return out.reshape(B_, S, C)


def ssd_chunked(x, dt, a_log, B, C, d_skip, chunk: int,
                init_state=None, shard_heads: bool = True
                ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Chunked SSD scan (oracle semantics for kernels/ssd_scan).

    x:  [b, S, H, P]   inputs per head
    dt: [b, S, H]      softplus-activated step sizes
    B:  [b, S, G, N]   input projections (G groups broadcast over H)
    C:  [b, S, G, N]   output projections
    Returns (y [b,S,H,P], final_state [b,H,P,N]).
    """
    b, S, H, P = x.shape
    G, N = B.shape[2], B.shape[3]
    nc = S // chunk
    assert S % chunk == 0, "sequence must be divisible by chunk"
    rep = H // G

    a = -jnp.exp(a_log.astype(jnp.float32))              # [H] (negative)
    dA = dt.astype(jnp.float32) * a                      # [b,S,H] log-decay
    xdt = x.astype(jnp.float32) * dt.astype(jnp.float32)[..., None]

    # reshape into chunks
    xc = xdt.reshape(b, nc, chunk, H, P)
    dAc = dA.reshape(b, nc, chunk, H)
    Bc = B.astype(jnp.float32).reshape(b, nc, chunk, G, N)
    Cc = C.astype(jnp.float32).reshape(b, nc, chunk, G, N)

    # cumulative decay within chunk: l[i] = sum_{j<=i} dA[j]
    l = jnp.cumsum(dAc, axis=2)                          # [b,nc,Q,H]
    total = l[:, :, -1]                                  # [b,nc,H]

    # --- intra-chunk (dense, MXU-friendly) -----------------------------
    # scores[i,j] = (C_i · B_j) * exp(l_i - l_j) for i >= j
    from repro.models.layers import constrain
    Bh = jnp.repeat(Bc, rep, axis=3)                     # [b,nc,Q,H,N]
    Ch = jnp.repeat(Cc, rep, axis=3)
    if shard_heads:
        Bh = constrain(Bh, ("pod", "data"), None, None, "model", None)
        Ch = constrain(Ch, ("pod", "data"), None, None, "model", None)
    cb = jnp.einsum("bnihN,bnjhN->bnhij", Ch, Bh)        # [b,nc,H,Q,Q]
    seg = l[:, :, :, None, :] - l[:, :, None, :, :]      # l_i - l_j [b,nc,Q,Q,H]
    seg = seg.transpose(0, 1, 4, 2, 3)                   # [b,nc,H,Q,Q]
    causal = jnp.tril(jnp.ones((chunk, chunk), bool))
    # mask BEFORE the exp: above the diagonal seg is a positive sum of
    # decays, exp overflows to inf, and although where() masks the
    # forward, the backward is d(exp)=exp=inf × 0-cotangent = NaN
    decay = jnp.exp(jnp.where(causal, seg, -1e30))
    y_intra = jnp.einsum("bnhij,bnjhp->bnihp", cb * decay, xc)

    # --- chunk states ---------------------------------------------------
    # S_n = sum_j exp(total - l_j) * B_j ⊗ x_j   [b,nc,H,P,N]
    w = jnp.exp(total[:, :, None] - l)                   # [b,nc,Q,H]
    states = jnp.einsum("bnjhN,bnjhp,bnjh->bnhpN", Bh, xc, w)

    # --- inter-chunk recurrence ------------------------------------------
    if init_state is None:
        init_state = jnp.zeros((b, H, P, N), jnp.float32)

    decay_chunk = jnp.exp(total)                         # [b,nc,H]

    def step(carry, inp):
        s_prev = carry
        st, dc = inp                                     # [b,H,P,N], [b,H]
        s_new = s_prev * dc[:, :, None, None] + st
        return s_new, s_prev

    (final_state, prev_states) = jax.lax.scan(
        step, init_state,
        (states.transpose(1, 0, 2, 3, 4), decay_chunk.transpose(1, 0, 2)))
    prev_states = prev_states.transpose(1, 0, 2, 3, 4)   # [b,nc,H,P,N]

    # y_inter[i] = (C_i · prev_state) * exp(l_i)
    y_inter = jnp.einsum("bnihN,bnhpN,bnih->bnihp", Ch, prev_states, jnp.exp(l))
    y = (y_intra + y_inter).reshape(b, S, H, P)
    y = y + d_skip[None, None, :, None] * x.astype(jnp.float32)
    return y.astype(x.dtype), final_state


def ssd_seq_parallel(x, dt, a_log, B, C, d_skip, chunk: int,
                     slabs: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Sequence-parallel chunked SSD (§Perf, mamba2 prefill).

    The sequence is cut into ``slabs`` that ride the batch dim (merged
    ``b·slabs`` sharded over (data, model)); each slab runs the local
    chunked SSD from a zero state, and the cross-slab composition uses
    the fact that the SSM is affine in its state:

        s_out = D_slab ⊙ s_in + s_local,  D_slab = exp(Σ_slab dA)

    so a [b, slabs, H, P, N] prefix scan (MB-scale traffic) replaces
    the per-layer tensor-parallel all-reduces of head sharding —
    measured 124 GiB → sub-GiB collectives on mamba2 prefill_32k.
    Exact: matches ssd_chunked bit-for-bit up to f32 reassociation
    (asserted in tests/test_kernels.py).
    """
    from repro.models.layers import constrain
    b, S, H, P = x.shape
    G, N = B.shape[2], B.shape[3]
    assert S % slabs == 0
    Ls = S // slabs
    rep = H // G

    def slab(t):
        # [b, S, ...] → [slabs·b, Ls, ...] (SLAB-major merge so the
        # merged dim shards ('model','pod','data')-major and every row
        # stays on the device that already holds it — a batch-major
        # merge forces ~50 MB collective-permutes per layer, measured)
        return t.reshape((b, slabs, Ls) + t.shape[2:]) \
                .swapaxes(0, 1) \
                .reshape((slabs * b, Ls) + t.shape[2:])

    xs, dts, Bs, Cs = slab(x), slab(dt), slab(B), slab(C)
    xs = constrain(xs, ("model", "pod", "data"), None, None, None)
    dts = constrain(dts, ("model", "pod", "data"), None, None)
    y_loc, fs_loc = ssd_chunked(xs, dts, a_log, Bs, Cs, d_skip,
                                min(chunk, Ls), shard_heads=False)

    # slab decay D = exp(Σ dA) and prefix states across slabs
    a = -jnp.exp(a_log.astype(jnp.float32))              # [H]
    dA_tot = (dts.astype(jnp.float32) * a).sum(axis=1)   # [slabs·b, H]
    D = dA_tot.reshape(slabs, b, H)
    fs = fs_loc.reshape(slabs, b, H, P, N)

    def step(s_prev, inp):
        st, dc = inp                                     # [b,H,P,N],[b,H]
        s_new = s_prev * jnp.exp(dc)[:, :, None, None] + st
        return s_new, s_prev

    final, prefix = jax.lax.scan(
        step, jnp.zeros((b, H, P, N), jnp.float32), (fs, D))
    # prefix: [slabs, b, H, P, N]

    # correction: y[t] += exp(l_local(t)) · C_t · prefix_state
    l_loc = jnp.cumsum(
        (dts.astype(jnp.float32) * a).reshape(slabs, b, Ls, H), axis=2)
    Ch = jnp.repeat(Cs.reshape(slabs, b, Ls, G, N), rep, axis=3)
    corr = jnp.einsum("sbihN,sbhpN,sbih->sbihp",
                      Ch.astype(jnp.float32), prefix, jnp.exp(l_loc))
    y = y_loc.reshape(slabs, b, Ls, H, P).astype(jnp.float32) + corr
    y = y.swapaxes(0, 1).reshape(b, S, H, P)
    return y.astype(x.dtype), final


def mamba2_mixer(x, p, li, cfg: ModelConfig,
                 conv_tail=None, ssm_state=None, return_cache=False,
                 length_mask=None, seq_parallel: int = 0):
    """Full Mamba2 block (train/prefill path).  x: [B,S,d].

    ``length_mask`` [B,S] (True = real token): padded positions get
    dt=0 so they neither update nor decay the SSM state — the final
    state equals the state at the last real token.
    """
    sc = cfg.ssm
    b, s, _ = x.shape
    H, P, G, N, K = cfg.n_ssm_heads, sc.head_dim, sc.n_groups, sc.d_state, sc.conv_kernel
    di = cfg.d_inner

    zxbcdt = x @ p["in_proj"][li]
    z, xs, B, C, dt = _split_proj(zxbcdt, cfg)
    xbc_pre = jnp.concatenate([xs, B, C], axis=-1)       # pre-conv inputs
    if seq_parallel > 1 and s % seq_parallel == 0 and conv_tail is None:
        xbc = causal_conv_slabbed(xbc_pre, p["conv_w"][li],
                                  p["conv_b"][li], seq_parallel)
    else:
        xbc = causal_conv(xbc_pre, p["conv_w"][li], p["conv_b"][li],
                          conv_tail)
    xs, B, C = jnp.split(xbc, [di, di + G * N], axis=-1)

    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"][li])
    if length_mask is not None:
        dt = dt * length_mask[:, :, None].astype(dt.dtype)
    xh = xs.reshape(b, s, H, P)
    Bg = B.reshape(b, s, G, N)
    Cg = C.reshape(b, s, G, N)
    chunk = min(sc.chunk_size, s)
    from repro.models.layers import constrain
    if seq_parallel > 1 and s % seq_parallel == 0 and ssm_state is None:
        # sequence-parallel SSD (prefill path — §Perf)
        y, final_state = ssd_seq_parallel(xh, dt, p["a_log"][li], Bg, Cg,
                                          p["d_skip"][li], chunk,
                                          slabs=seq_parallel)
    else:
        # SSM head parallelism: heads ride the model axis (the SSD scan
        # is independent per head); B/C are per-group (G=1), replicated.
        # Without this the SSD quadratic intra-chunk term is computed
        # replicated on every model rank (measured 41 GiB/dev temp and a
        # 16× compute waste on mamba2 prefill_32k — EXPERIMENTS.md §Perf)
        xh = constrain(xh, ("pod", "data"), None, "model", None)
        dt = constrain(dt, ("pod", "data"), None, "model")
        # a sequence longer than a chunk but not a whole number of them
        # is padded with steps of dt = 0, which leave the state as is;
        # a whole number of chunks lowers with no pad at all
        if s % chunk:
            pad = ((0, 0), (0, -s % chunk)) + ((0, 0),) * 2
            xh, Bg, Cg = (jnp.pad(a, pad) for a in (xh, Bg, Cg))
            dt = jnp.pad(dt, pad[:3])
        y, final_state = ssd_chunked(xh, dt, p["a_log"][li], Bg, Cg,
                                     p["d_skip"][li], chunk,
                                     init_state=ssm_state)
        y = y[:, :s]
    y = y.reshape(b, s, di)
    y = rms_norm(y * jax.nn.silu(z), p["gnorm"][li], cfg.rms_eps)
    out = y @ p["out_proj"][li]
    if return_cache:
        # conv tail = last K-1 *pre-activation* conv inputs of each
        # sequence (positions len-K+1 .. len-1; padded batches gather at
        # their own length, zeros when the sequence is shorter than K-1)
        prev = conv_tail if conv_tail is not None else \
            jnp.zeros((b, K - 1, di + 2 * G * N), x.dtype)
        full = jnp.concatenate([prev, xbc_pre], axis=1)   # [b, K-1+S, conv]
        if length_mask is not None:
            lens = length_mask.sum(axis=1).astype(jnp.int32)     # [b]
        else:
            lens = jnp.full((b,), s, jnp.int32)
        idx = lens[:, None] + jnp.arange(K - 1)[None, :]  # last K-1 slots
        new_tail = jnp.take_along_axis(full, idx[:, :, None], axis=1)
        return out, final_state, new_tail
    return out, final_state


def mamba2_decode_step(x, p, li, cfg: ModelConfig, conv_tail, ssm_state):
    """Single-token decode.  x: [B,d]; conv_tail: [B,K-1,conv_dim];
    ssm_state: [B,H,P,N] (float32).  Returns (out, new_tail, new_state)."""
    sc = cfg.ssm
    b = x.shape[0]
    H, P, G, N, K = cfg.n_ssm_heads, sc.head_dim, sc.n_groups, sc.d_state, sc.conv_kernel
    di = cfg.d_inner

    zxbcdt = x @ p["in_proj"][li]
    z, xs, B, C, dt = _split_proj(zxbcdt, cfg)
    xbc_new = jnp.concatenate([xs, B, C], axis=-1)       # [B, conv_dim]

    window = jnp.concatenate([conv_tail, xbc_new[:, None]], axis=1)  # [B,K,conv]
    conv_out = jnp.einsum("bkc,kc->bc", window, p["conv_w"][li]) + p["conv_b"][li]
    conv_out = jax.nn.silu(conv_out)
    new_tail = window[:, 1:]

    xs, B, C = jnp.split(conv_out, [di, di + G * N], axis=-1)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"][li])   # [B,H]
    a = -jnp.exp(p["a_log"][li].astype(jnp.float32))     # [H]
    dA = jnp.exp(dt * a)                                 # [B,H]

    xh = xs.reshape(b, H, P).astype(jnp.float32)
    Bg = jnp.repeat(B.reshape(b, G, N), H // G, axis=1).astype(jnp.float32)
    Cg = jnp.repeat(C.reshape(b, G, N), H // G, axis=1).astype(jnp.float32)

    # s ← s·exp(dtA) + dt·(B ⊗ x)
    new_state = ssm_state * dA[:, :, None, None] + \
        jnp.einsum("bhp,bhN,bh->bhpN", xh, Bg, dt)
    y = jnp.einsum("bhpN,bhN->bhp", new_state, Cg) + \
        p["d_skip"][li][None, :, None] * xh
    y = y.reshape(b, di).astype(x.dtype)
    y = rms_norm(y * jax.nn.silu(z), p["gnorm"][li], cfg.rms_eps)
    return y @ p["out_proj"][li], new_tail, new_state
