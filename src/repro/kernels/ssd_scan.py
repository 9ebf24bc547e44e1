"""Pallas TPU kernel: Mamba2 SSD chunked scan.

TPU adaptation of the SSD algorithm [arXiv:2405.21060]: the intra-chunk
term is a masked [Q×Q] matmul (MXU), the inter-chunk state recurrence is
a first-order scan carried in VMEM scratch across the sequential chunk
axis of the grid — the TPU analogue of the GPU kernel's SM-local
chunk-state pipeline.

Grid: (batch, heads, num_chunks), chunk axis sequential.  Per step the
kernel holds x[Q,P], dt[Q], B[Q,N], C[Q,N] plus the carried state [P,N]
in VMEM: at Q=256, P=64, N=128 that is ≈ 0.4MB — small; Q is chosen so
the [Q×Q] decay matmul saturates the MXU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, d_ref,
                y_ref, fs_ref, state_ref, *,
                chunk: int, n_chunks: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[0, 0].astype(jnp.float32)          # [Q, P]
    dt = dt_ref[0, 0].astype(jnp.float32)        # [Q, 1]
    a = a_ref[0, 0]                              # [1, 1] f32 (A_log)
    B = b_ref[0, 0].astype(jnp.float32)          # [Q, N]
    C = c_ref[0, 0].astype(jnp.float32)          # [Q, N]
    d_skip = d_ref[0, 0]                         # [1, 1] f32

    neg_a = -jnp.exp(a[0, 0])
    dA = dt * neg_a                              # [Q, 1] log-decay
    xdt = x * dt                                 # [Q, P]

    # cumulative decay l_i = Σ_{j≤i} dA_j as a lower-triangular matmul
    # (Mosaic has no cumsum), once as a column and once as a row so the
    # pairwise differences need no vector transpose
    li = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    lj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    tril = (li >= lj).astype(jnp.float32)
    exact = jax.lax.Precision.HIGHEST
    l = jax.lax.dot_general(tril, dA, (((1,), (0,)), ((), ())),
                            precision=exact,
                            preferred_element_type=jnp.float32)   # [Q,1]
    l_row = jax.lax.dot_general(dA, tril, (((0,), (1,)), ((), ())),
                                precision=exact,
                                preferred_element_type=jnp.float32)  # [1,Q]

    # intra-chunk: scores[i,j] = (C_i·B_j)·exp(l_i − l_j), i ≥ j
    cb = jax.lax.dot_general(C, B, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # [Q,Q]
    # mask before exp (overflow above the diagonal — see mamba2.py)
    decay = jnp.exp(jnp.where(li >= lj, l - l_row, -1e30))
    y_intra = jax.lax.dot_general(cb * decay, xdt, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)

    # inter-chunk: y_inter[i] = exp(l_i) · (C_i · S_prev)
    s_prev = state_ref[...]                      # [P, N]
    y_inter = jnp.exp(l) * jax.lax.dot_general(
        C, s_prev, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)      # [Q, P]

    y_ref[0, 0] = (y_intra + y_inter + d_skip[0, 0] * x).astype(y_ref.dtype)

    # state update: S ← exp(Σ dA)·S_prev + Σ_j exp(l_last − l_j)·x_j ⊗ B_j
    l_last = jnp.sum(dA)                         # scalar: l at the chunk end
    w = jnp.exp(l_last - l)                      # [Q, 1]
    s_new = s_prev * jnp.exp(l_last) + jax.lax.dot_general(
        xdt * w, B, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)      # [P, N]
    state_ref[...] = s_new

    @pl.when(ci == n_chunks - 1)
    def _emit_state():
        fs_ref[0, 0] = s_new


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x, dt, a_log, B, C, d_skip, *, chunk: int = 256,
             interpret: bool = False):
    """Chunked SSD.  x:[b,S,H,P], dt:[b,S,H], a_log:[H], B/C:[b,S,G,N],
    d_skip:[H] → (y [b,S,H,P], final_state [b,H,P,N]).
    """
    b, S, H, P = x.shape
    G, N = B.shape[2], B.shape[3]
    rep = H // G
    chunk = min(chunk, S)
    assert S % chunk == 0
    nc = S // chunk

    # layout: head-major so each grid step reads contiguous [Q,·] tiles
    xt = x.transpose(0, 2, 1, 3)                               # [b,H,S,P]
    dtt = dt.transpose(0, 2, 1)[..., None]                     # [b,H,S,1]
    Bt = jnp.repeat(B.transpose(0, 2, 1, 3), rep, axis=1)      # [b,H,S,N]
    Ct = jnp.repeat(C.transpose(0, 2, 1, 3), rep, axis=1)
    a2 = jnp.broadcast_to(a_log.astype(jnp.float32)[None, :, None, None],
                          (b, H, 1, 1))
    d2 = jnp.broadcast_to(d_skip.astype(jnp.float32)[None, :, None, None],
                          (b, H, 1, 1))

    kernel = functools.partial(_ssd_kernel, chunk=chunk, n_chunks=nc)
    y, fs = pl.pallas_call(
        kernel,
        grid=(b, H, nc),
        in_specs=[
            pl.BlockSpec((1, 1, chunk, P), lambda bi, hi, ci: (bi, hi, ci, 0)),
            pl.BlockSpec((1, 1, chunk, 1), lambda bi, hi, ci: (bi, hi, ci, 0)),
            pl.BlockSpec((1, 1, 1, 1), lambda bi, hi, ci: (bi, hi, 0, 0)),
            pl.BlockSpec((1, 1, chunk, N), lambda bi, hi, ci: (bi, hi, ci, 0)),
            pl.BlockSpec((1, 1, chunk, N), lambda bi, hi, ci: (bi, hi, ci, 0)),
            pl.BlockSpec((1, 1, 1, 1), lambda bi, hi, ci: (bi, hi, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, chunk, P), lambda bi, hi, ci: (bi, hi, ci, 0)),
            pl.BlockSpec((1, 1, P, N), lambda bi, hi, ci: (bi, hi, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, H, S, P), x.dtype),
            jax.ShapeDtypeStruct((b, H, P, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(xt, dtt, a2, Bt, Ct, d2)
    return y.transpose(0, 2, 1, 3), fs
