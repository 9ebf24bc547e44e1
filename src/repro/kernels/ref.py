"""Pure-jnp oracles for every Pallas kernel (the ground truth in tests).

These delegate to the framework's reference implementations so kernel
tests pin the kernels to exactly the semantics the engine/dry-run use.
"""
from __future__ import annotations

from repro.models.layers import causal_attention
from repro.models.mamba2 import ssd_chunked
from repro.paging import paged_decode_attention as _paged_ref


def flash_prefill_ref(q, k, v, *, window=None):
    """Causal attention oracle.  q:[B,S,H,hd], k/v:[B,S,KV,hd]."""
    return causal_attention(q, k, v, window=window)


def paged_decode_ref(q, pool_k, pool_v, table, seq_lens, layer, *, n_kv):
    """Paged decode oracle — the engine's XLA path.  Takes the kernels'
    ``[N, BT, hd]`` blocks as they are: the engine's ops read a block's
    size from the shape, so either arena form gives the same answer."""
    return _paged_ref(q, pool_k, pool_v, table, seq_lens, layer, n_kv)


def ssd_scan_ref(x, dt, a_log, B, C, d_skip, *, chunk=64):
    """SSD oracle — the model's chunked scan (itself validated against a
    step-by-step recurrence in tests/test_mamba2.py)."""
    return ssd_chunked(x, dt, a_log, B, C, d_skip, chunk)
