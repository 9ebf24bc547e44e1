"""Compile rehearsal: every Pallas kernel of the main path, compiled by
the TPU compiler for a described (not attached) v5e chip at real widths.

Interpret mode cannot see what only the chip's compiler refuses: tile
alignment, fast-memory budgets, primitives Mosaic does not lower.  These
compiles can, in about a second each, with no chip.  The topology is
described inside a module fixture (never at import), so every test
worker collects the same tests and only the worker given this file
loads the TPU library.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.config import BLOCK_TOKENS
from repro.kernels.flash_prefill import (flash_prefill,
                                         fused_paged_flash_prefill)
from repro.kernels.paged_attention import paged_decode_attention
from repro.kernels.paged_attention_int8 import paged_decode_attention_int8
from repro.kernels.ssd_scan import ssd_scan

ARENA, TABLE_W, ROWS = 8192, 64, 8

# (query heads, kv heads, head_dim) of the published configs the
# kernels serve: phi-3-vision-4.2b (MHA, group 1, head_dim 96) and
# qwen2-7b (GQA 7:1, head_dim 128)
LAYOUTS = [pytest.param(32, 32, 96, id="phi3-hd96"),
           pytest.param(28, 4, 128, id="qwen2-hd128")]


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                                # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def chip(topo):
    """One described v5e chip, with the persistent compile cache off
    (an entry compiled here could not be read back without a chip)."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(chip, fn, *shapes):
    """Compile ``fn`` for the described chip; assert the Pallas kernel
    reached Mosaic as a TPU custom call."""
    args = [jax.ShapeDtypeStruct(s, d, sharding=chip) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("h,kv,hd", LAYOUTS)
def test_paged_decode_compiles(chip, h, kv, hd):
    _compile(chip,
             lambda q, k, v, t, n: paged_decode_attention(
                 q, k, v, t, n, 3, n_kv=kv),
             ((ROWS, h, hd), jnp.bfloat16),
             ((ARENA, BLOCK_TOKENS, hd), jnp.bfloat16),
             ((ARENA, BLOCK_TOKENS, hd), jnp.bfloat16),
             ((ROWS, TABLE_W), jnp.int32), ((ROWS,), jnp.int32))


@pytest.mark.parametrize("h,kv,hd", LAYOUTS)
def test_paged_decode_int8_compiles(chip, h, kv, hd):
    _compile(chip,
             lambda q, k, v, sk, sv, t, n: paged_decode_attention_int8(
                 q, k, v, sk, sv, t, n, 3, n_kv=kv),
             ((ROWS, h, hd), jnp.bfloat16),
             ((ARENA, BLOCK_TOKENS, hd), jnp.int8),
             ((ARENA, BLOCK_TOKENS, hd), jnp.int8),
             ((ARENA, BLOCK_TOKENS), jnp.float32),
             ((ARENA, BLOCK_TOKENS), jnp.float32),
             ((ROWS, TABLE_W), jnp.int32), ((ROWS,), jnp.int32))


@pytest.mark.parametrize("h,kv,hd", LAYOUTS)
def test_paged_chunk_prefill_compiles(chip, h, kv, hd):
    chunk = 256                      # the serving chunk of chip_smoke.py
    _compile(chip, fused_paged_flash_prefill,
             ((2, chunk, h, hd), jnp.bfloat16),
             ((ARENA, BLOCK_TOKENS, hd), jnp.bfloat16),
             ((ARENA, BLOCK_TOKENS, hd), jnp.bfloat16),
             ((2, kv, TABLE_W), jnp.int32), ((2,), jnp.int32))


@pytest.mark.parametrize("h,kv,hd", LAYOUTS)
def test_flash_prefill_compiles(chip, h, kv, hd):
    s = 1024                         # two q blocks of 256 × two k of 512
    _compile(chip, lambda q, k, v: flash_prefill(q, k, v, block_q=256,
                                                 block_k=512),
             ((1, s, h, hd), jnp.bfloat16), ((1, s, kv, hd), jnp.bfloat16),
             ((1, s, kv, hd), jnp.bfloat16))


def test_ssd_scan_compiles_at_mamba2_widths(chip):
    # mamba2-2.7b: 80 heads × head_dim 64, d_state 128, one B/C group,
    # SSD chunk 256; two chunks exercise the carried state
    s, h, p, n = 512, 80, 64, 128
    _compile(chip, lambda *a: ssd_scan(*a, chunk=256),
             ((1, s, h, p), jnp.bfloat16), ((1, s, h), jnp.float32),
             ((h,), jnp.float32), ((1, s, 1, n), jnp.bfloat16),
             ((1, s, 1, n), jnp.bfloat16), ((h,), jnp.float32))
