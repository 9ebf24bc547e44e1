"""The arena's forms: each [BT, hd] head-block is one row of [N, BT*hd],
or stays [BT, hd] where hd fills whole lane tiles.  Writes and paged
attention on the arena must give exactly what the same ops give on the
[N, BT, hd] block form of the same bytes (the form the Pallas kernels
take), with every token written by an element scatter."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.config import BLOCK_TOKENS, replace
from repro.paging import fused_paged_decode_attention, resolve_physical_blocks
from repro.serving import cache_ops
from repro.serving.kvcache import UnifiedKVPool, arena_block_shape

BT, KV, LAYERS = BLOCK_TOKENS, 2, 2


# ---------------------------------------------------------------------------
# reference: the same ops on the [N, BT, hd] block form
# ---------------------------------------------------------------------------
def _write_blocks(kb, vb, k_new, v_new, table, start, layer, n_kv):
    """Element scatter of each token's hd values at (block, offset)."""
    B, S, n, hd = k_new.shape
    pos = start[:, None] + jnp.arange(S)[None, :]
    base = jnp.take_along_axis(table, pos // BT, axis=1)
    phys = (jnp.maximum(base, 0)[:, :, None] + layer * n_kv
            + jnp.arange(n)[None, None, :])
    phys = jnp.where((base >= 0)[:, :, None], phys, kb.shape[0])
    off = jnp.broadcast_to((pos % BT)[:, :, None], phys.shape)

    def put(a, x):
        return a.at[phys.reshape(-1), off.reshape(-1)].set(
            x.reshape(-1, hd), mode="drop")

    return put(kb, k_new), put(vb, v_new)


def _attend_blocks(q, kb, vb, phys, visible):
    """Attention of q [B, C, H, hd] over the blocks' tokens, position t
    of row b visible to query c where ``visible[b, c, t]``."""
    B, C, H, hd = q.shape
    n_kv, nb = phys.shape[1], phys.shape[2]
    k = kb[phys].reshape(B, n_kv, nb * BT, hd)
    v = vb[phys].reshape(B, n_kv, nb * BT, hd)
    qh = q.reshape(B, C, n_kv, H // n_kv, hd)
    s = jnp.einsum("bckgd,bktd->bkgct", qh, k) / math.sqrt(hd)
    s = jnp.where(visible[:, None, None], s, -1e30)
    o = jnp.einsum("bkgct,bktd->bckgd", jax.nn.softmax(s, axis=-1), v)
    return o.reshape(B, C, H, hd)


def _arena(n, hd, seed):
    kk, kv = jax.random.split(jax.random.PRNGKey(seed))
    return (jax.random.normal(kk, (n, BT, hd), jnp.float32),
            jax.random.normal(kv, (n, BT, hd), jnp.float32))


def _as_arena(blocks):
    """The same bytes in the engine's arena form."""
    n, _, hd = blocks.shape
    return blocks.reshape(n, *arena_block_shape(BT, hd))


def test_arena_form_by_head_dim():
    assert arena_block_shape(16, 64) == (1024,)
    assert arena_block_shape(16, 96) == (1536,)
    assert arena_block_shape(16, 80) == (1280,)
    assert arena_block_shape(16, 128) == (16, 128)
    assert arena_block_shape(16, 256) == (16, 256)


def _tables(n_kv=KV):
    """Three rows of group bases: two live sequences and a padded row."""
    g = LAYERS * n_kv
    return jnp.asarray(np.array([[0, 3 * g, g, -1],
                                 [2 * g, 5 * g, 4 * g, 6 * g],
                                 [-1, -1, -1, -1]], np.int32))


# two heads (a gather of single rows); 16 heads, whose group bases the
# allocator starts on tile rows (a gather of whole tile rows)
HEADS = [KV, 16]


# ---------------------------------------------------------------------------
# writes
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("hd", [64, 96, 128])
@pytest.mark.parametrize("s,starts", [
    (1, (0, 17, 5)),          # a decode step, mid-block and block start
    (5, (14, 30, 0)),         # crosses a block boundary
    (16, (0, 16, 3)),         # one whole block, aligned and not
    (37, (11, 2, 0)),         # spans three to four blocks
    (20, (40, 50, 0)),        # runs past the table's last block
])
def test_write_tokens_match_element_scatter(hd, s, starts):
    N = 7 * LAYERS * KV + 3
    kb, vb = _arena(N, hd, 0)
    table = _tables()
    start = jnp.asarray(np.array(starts, np.int32))
    k_new, v_new = jax.random.normal(jax.random.PRNGKey(1),
                                     (2, 3, s, KV, hd), jnp.float32)
    for layer in range(LAYERS):
        want_k, want_v = _write_blocks(kb, vb, k_new, v_new, table, start,
                                       layer, KV)
        got_k, got_v = cache_ops.write_tokens(
            _as_arena(kb), _as_arena(vb), k_new, v_new, table, start, layer,
            KV)
        assert got_k.shape == got_v.shape == _as_arena(kb).shape
        np.testing.assert_array_equal(np.asarray(got_k),
                                      np.asarray(_as_arena(want_k)))
        np.testing.assert_array_equal(np.asarray(got_v),
                                      np.asarray(_as_arena(want_v)))


@pytest.mark.parametrize("hd", [64, 128])
def test_write_tokens_casts_to_the_arena_dtype(hd):
    N = 4 * KV + 1
    pool = jnp.zeros((N, *arena_block_shape(BT, hd)), jnp.bfloat16)
    k_new = jnp.full((1, 1, KV, hd), 1.5, jnp.float32)
    table = jnp.asarray([[0]], jnp.int32)
    k, v = cache_ops.write_tokens(pool, pool, k_new, -k_new, table,
                                  jnp.asarray([3], jnp.int32), 0, KV)
    assert k.dtype == jnp.bfloat16
    rows = np.asarray(k.reshape(N, BT, hd), np.float32)
    assert (rows[:KV, 3] == 1.5).all()
    rows[:KV, 3] = 0
    assert not rows.any(), "only slot 3 of the two head-blocks is written"


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("n_kv", HEADS)
@pytest.mark.parametrize("hd", [64, 96, 128])
def test_decode_attention_matches_block_form(hd, n_kv):
    N = 8 * LAYERS * n_kv
    kb, vb = _arena(N, hd, 2)
    table = _tables(n_kv)
    lens = jnp.asarray(np.array([40, 64, 1], np.int32))
    q = jax.random.normal(jax.random.PRNGKey(3), (3, 2 * n_kv, hd),
                          jnp.float32)
    for layer in range(LAYERS):
        phys = resolve_physical_blocks(table, layer, n_kv)
        t = jnp.arange(table.shape[1] * BT)
        want = _attend_blocks(q[:, None], kb, vb, phys,
                              (t[None, :] < lens[:, None])[:, None])[:, 0]
        got = fused_paged_decode_attention(q, _as_arena(kb), _as_arena(vb),
                                           phys, lens)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("n_kv", HEADS)
@pytest.mark.parametrize("hd", [64, 96, 128])
def test_chunk_attention_matches_block_form(hd, n_kv):
    N = 8 * LAYERS * n_kv
    kb, vb = _arena(N, hd, 4)
    table = _tables(n_kv)
    offs = jnp.asarray(np.array([0, 21, 0], np.int32))
    C = 8
    q = jax.random.normal(jax.random.PRNGKey(5), (3, C, 2 * n_kv, hd),
                          jnp.float32)
    for layer in range(LAYERS):
        t = jnp.arange(table.shape[1] * BT)
        qpos = offs[:, None] + jnp.arange(C)[None, :]
        want = _attend_blocks(q, kb, vb,
                              resolve_physical_blocks(table, layer, n_kv),
                              t[None, None, :] <= qpos[:, :, None])
        got = cache_ops.paged_chunk_attention(
            q, _as_arena(kb), _as_arena(vb), table, offs, layer, n_kv)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# the pool keeps its form through every resize and page copy
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("hd", [96, 128])
def test_pool_keeps_its_form_through_grow_shrink_and_copy(hd):
    pool = UnifiedKVPool(64, hd, dtype=jnp.float32)
    block = arena_block_shape(BT, hd)
    assert pool.block_shape == block
    assert pool.k.shape == pool.v.shape == (64, *block)
    assert pool.head_block_bytes == 2 * BT * hd * 4
    assert pool.hbm_bytes() == 64 * pool.head_block_bytes
    cfg = replace(configs.get_reduced("qwen2-7b"), head_dim=hd)
    view = pool.register_model(cfg, quota=64)
    assert view.append_tokens(0, BLOCK_TOKENS)     # live: grow concatenates
    assert pool.grow(32) == 32
    assert pool.k.shape == pool.v.shape == (96, *block)
    g = view.group_size
    src = view.seqs[0].bases[0]
    pool.k = pool.k.at[src:src + g].set(
        jnp.arange(g * BT * hd, dtype=jnp.float32).reshape(g, *block))
    dst = pool.allocator.alloc(g)
    k, v = cache_ops.copy_block_groups(pool.k, pool.v, [src], [dst],
                                       cfg.n_kv_heads, cfg.n_layers)
    assert k.shape == v.shape == (96, *block)
    np.testing.assert_array_equal(np.asarray(k[dst:dst + g]),
                                  np.asarray(pool.k[src:src + g]))
    pool.allocator.free(dst, g)
    assert pool.shrink(32) == 32
    assert pool.k.shape == pool.v.shape == (64, *block)
    view.free_seq(0)
    assert pool.grow(8) == 8                        # idle: reallocates
    assert pool.k.shape == pool.v.shape == (72, *block)
