"""Jit'd dispatch wrappers over the Pallas kernels.

``backend`` names the implementation, and nothing is chosen behind the
caller's back:

* ``"auto"`` (default) — the compiled Pallas TPU kernel.  It needs a
  TPU: on any other backend it raises instead of degrading.
* ``"interpret"`` — the same kernel run by the Pallas interpreter
  (validation on CPU).
* ``"xla"`` — the pure-jnp reference path.

The backend is read at call time, never at import, so importing this
module initialises no JAX backend.
"""
from __future__ import annotations

import jax

from repro.kernels import flash_prefill as _fp
from repro.kernels import paged_attention as _pa
from repro.kernels import ssd_scan as _ssd
from repro.kernels import ref as _ref

BACKENDS = ("auto", "interpret", "xla")


def _interpret(backend: str) -> bool:
    """Resolve a kernel backend to Pallas' ``interpret`` flag."""
    if backend not in BACKENDS:
        raise ValueError(f"backend must be one of {BACKENDS}, got "
                         f"{backend!r}")
    if backend == "interpret":
        return True
    platform = jax.default_backend()
    if platform != "tpu":
        raise RuntimeError(
            f"backend='auto' runs the compiled Pallas TPU kernel, but JAX's "
            f"default backend is {platform!r}; pass backend='interpret' "
            f"or backend='xla' to run elsewhere")
    return False


def flash_attention(q, k, v, *, window=None, backend: str = "auto",
                    block_q: int = 256, block_k: int = 512):
    if backend == "xla":
        from repro.models.layers import blocked_causal_attention
        return blocked_causal_attention(q, k, v, window=window)
    return _fp.flash_prefill(q, k, v, window=window, block_q=block_q,
                             block_k=block_k, interpret=_interpret(backend))


def paged_attention(q, pool_k, pool_v, table, seq_lens, layer, *, n_kv,
                    backend: str = "auto"):
    if backend == "xla":
        return _ref.paged_decode_ref(q, pool_k, pool_v, table, seq_lens,
                                     layer, n_kv=n_kv)
    return _pa.paged_decode_attention(q, pool_k, pool_v, table, seq_lens,
                                      layer, n_kv=n_kv,
                                      interpret=_interpret(backend))


def ssd(x, dt, a_log, B, C, d_skip, *, chunk=256, backend: str = "auto"):
    if backend == "xla":
        return _ref.ssd_scan_ref(x, dt, a_log, B, C, d_skip, chunk=chunk)
    return _ssd.ssd_scan(x, dt, a_log, B, C, d_skip, chunk=chunk,
                         interpret=_interpret(backend))
