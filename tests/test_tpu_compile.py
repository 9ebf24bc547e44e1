"""Compile rehearsal: every Pallas kernel of the main path, and the
serving engine's step programs, compiled by the TPU compiler for a
described (not attached) v5e chip at real widths.

Interpret mode cannot see what only the chip's compiler refuses: tile
alignment, fast-memory budgets, primitives Mosaic does not lower.  These
compiles can, in about a second each, with no chip.  The topology is
described inside a module fixture (never at import), so every test
worker collects the same tests and only the worker given this file
loads the TPU library.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.config import BLOCK_TOKENS, replace
from repro.configs.granite_4p0_h_small import CONFIG as GRANITE
from repro.configs.mamba2_2p7b import CONFIG as MAMBA2
from repro.configs.phi_3_vision_4p2b import CONFIG as PHI3
from repro.kernels.flash_prefill import (flash_prefill,
                                         fused_paged_flash_prefill)
from repro.kernels.paged_attention import paged_decode_attention
from repro.kernels.paged_attention_int8 import paged_decode_attention_int8
from repro.kernels.ssd_scan import ssd_scan
from repro.serving.engine import init_stacked_params, jitted_step
from repro.serving.kvcache import arena_block_shape

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


# the phi-3-vision-4.2b solo deployment's arena (8 slots of 784 tokens
# over 32 layers x 32 KV heads); two of its 32 layers keep the compile
# short, and the arena's copies do not depend on the layer count
STEP_ARENA, STEP_ROWS, STEP_TABLE, STEP_CHUNK = 401_408, 8, 64, 256


def _step_args(chip, kind, cfg):
    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)
    params = jax.tree_util.tree_map(
        lambda a: spec(a.shape, a.dtype),
        jax.eval_shape(lambda k: init_stacked_params(k, cfg, jnp.bfloat16),
                       jax.random.PRNGKey(0)))
    pool = spec((STEP_ARENA, *arena_block_shape(BLOCK_TOKENS, cfg.hd)),
                jnp.bfloat16)
    i32 = jnp.int32
    if kind == "decode":
        b = STEP_ROWS
        return (params, spec((), i32), spec((b,), i32), spec((b,), i32),
                pool, pool, spec((b, STEP_TABLE), i32), None, None)
    b = 2
    return (params, spec((), i32), spec((b, STEP_CHUNK), i32),
            spec((b,), i32), spec((b,), i32), pool, pool,
            spec((b, STEP_TABLE), i32))


@pytest.mark.parametrize("hd", [64, 96, 128])
@pytest.mark.parametrize("kind", ["decode", "chunk"])
def test_step_writes_the_arena_in_place(chip, kind, hd):
    """A step program updates the KV arena where it lies: the arena
    keeps a row-major layout and is never copied (a [N, BT, hd] arena
    with hd below 128 is laid out with N in the lanes, and a program
    copies all of it four times), no per-token loop writes the KV, and
    the temporaries are far below the arena's size."""
    cfg = replace(PHI3, name="", n_layers=2, head_dim=hd)
    compiled = jitted_step(kind, cfg).lower(
        *_step_args(chip, kind, cfg)).compile()
    text = compiled.as_text()
    arena = re.compile(rf"= \w+\[{STEP_ARENA},[\d,]*\]"
                       rf"\{{([\d,]*)\S* (copy|parameter)\(")
    ops = [m.groups() for m in map(arena.search, text.splitlines()) if m]
    block = arena_block_shape(BLOCK_TOKENS, hd)
    row_major = ",".join(str(d) for d in range(len(block), -1, -1))
    assert ops and all(op == "parameter" for _, op in ops), ops
    assert {lay for lay, _ in ops} == {row_major}, ops
    assert " while(" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 0.2e9


# models with SSM layers at published widths: mamba2-2.7b (4 of its 64
# layers, the colocated cells' 2 slots) and granite-4.0-h-small (one
# period of its layer pattern, 9 Mamba-2 layers and 1 attention layer;
# 9 of the 72 experts held, one chip's share of eight; the backlog
# cell's 16 slots over its arena, 16 slots x 64 token-blocks x 2
# attention layers x 8 KV heads), each step's rows filling the slots
GRANITE_ROWS, GRANITE_ARENA = 16, 16_384


def _ssm_cfg(model):
    if model == "mamba2":
        return replace(MAMBA2, name="", n_layers=4), 2
    return replace(GRANITE, name="", n_layers=10,
                   layer_types=GRANITE.layer_types[:10],
                   moe=replace(GRANITE.moe, held_count=9)), GRANITE_ROWS


def _ssm_step(chip, model, kind, slots):
    """Compile ``kind`` of ``model`` over a state of ``slots`` slots;
    returns (compiled, the state's and the conv tail's shapes)."""
    cfg, b = _ssm_cfg(model)
    sc = cfg.ssm

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)
    params = jax.tree_util.tree_map(
        lambda a: spec(a.shape, a.dtype),
        jax.eval_shape(lambda k: init_stacked_params(k, cfg, jnp.bfloat16),
                       jax.random.PRNGKey(0)))
    shapes = ((cfg.n_ssm_layers, slots, cfg.n_ssm_heads, sc.head_dim,
               sc.d_state),
              (cfg.n_ssm_layers, slots, (sc.conv_kernel - 1)
               * (cfg.d_inner + 2 * sc.n_groups * sc.d_state)))
    state = (spec(shapes[0], jnp.float32), spec(shapes[1], jnp.bfloat16))
    pool = spec((GRANITE_ARENA, *arena_block_shape(BLOCK_TOKENS, 128)),
                jnp.bfloat16)
    i32 = jnp.int32
    table = spec((b, STEP_TABLE + 1), i32)      # the slots' column last
    if kind == "decode":
        args = (spec((b,), i32), spec((b,), i32), pool, pool, table)
    else:
        rows = (spec((b, STEP_CHUNK), i32), spec((b,), i32), spec((b,), i32))
        args = rows + ((table,) if model == "mamba2" else (pool, pool, table))
        kind = "chunk_ssm" if model == "mamba2" else "chunk"
    compiled = jitted_step(kind, cfg).lower(
        params, spec((), i32), *args, *state).compile()
    return compiled, shapes


@pytest.mark.parametrize("kind", ["decode", "chunk"])
def test_granite_step_gathers_no_expert_weights_per_token(chip, kind):
    """The held-expert layer reads each expert's weights where they
    lie: no temporary comes near a per-token gather of the routed
    experts' weights ([T, k, d, f_e]), and a decode step's temporaries
    are below one layer's held experts (a layer's slice of the stacked
    expert weights is never copied out for the grouped matmul)."""
    cfg, b = _ssm_cfg("granite")
    mc = cfg.moe
    compiled, _ = _ssm_step(chip, "granite", kind, b)
    tokens = b if kind == "decode" else b * STEP_CHUNK
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < tokens * mc.top_k * cfg.d_model * mc.d_expert * 2
    if kind == "decode":
        assert temp < mc.n_held * 3 * cfg.d_model * mc.d_expert * 2


@pytest.mark.parametrize("kind", ["decode", "chunk"])
@pytest.mark.parametrize("model", ["mamba2", "granite"])
def test_step_writes_the_ssm_state_in_place(chip, model, kind):
    """A step of a model with SSM layers takes the whole SSM state and
    conv tail and writes its rows' slots where they lie: each appears
    only as a parameter, never copied, in one layout; the program
    returns it aliased to its input; and its temporaries do not grow
    with the slots the state holds (a decode step's stay far below the
    state's size)."""
    cfg, b = _ssm_cfg(model)
    compiled, shapes = _ssm_step(chip, model, kind, b)
    text = compiled.as_text()
    for shape in shapes:
        dims = ",".join(map(str, shape))
        op = re.compile(rf"= \w+\[{dims}\]\{{([\d,]*)\S* (copy|parameter)\(")
        ops = [m.groups() for m in map(op.search, text.splitlines()) if m]
        assert ops and all(o == "parameter" for _, o in ops), ops
        assert len({lay for lay, _ in ops}) == 1, ops
    aliased = {int(i) for i in re.findall(r"\((\d+), \{\}, \w+-alias\)",
                                          text.splitlines()[0])}
    params = dict(re.findall(r"parameter\((\d+)\).*op_name=\"(ssm_state|"
                             r"conv_tail)\"", text))
    assert len(params) == 2 and {int(i) for i in params} <= aliased, params
    state_bytes = 4 * int(np.prod(shapes[0]))
    temp = compiled.memory_analysis().temp_size_in_bytes
    wider, _ = _ssm_step(chip, model, kind, 2 * b)
    assert wider.memory_analysis().temp_size_in_bytes - temp \
        < state_bytes / 8
    if kind == "decode":
        assert temp < state_bytes / 4
