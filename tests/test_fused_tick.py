"""Fused multi-LLM decode tick (DESIGN.md §2): parity with the serial
tick, pool block-table state equivalence, and heterogeneous fallback."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.config import replace
from repro.models.transformer import init_params
from repro.serving import cache_ops
from repro.serving.engine import Engine, Request
from repro.serving.kvcache import UnifiedKVPool, fused_block_tables
from repro.serving.mux import MuxScheduler

from _weights import with_model_axis


def _colocated(archs, fused, max_slots=2, quota=30_000, n_blocks=100_000):
    """Build a unit of colocated reduced engines (repeated archs get
    distinct weights + names) and a MuxScheduler over them."""
    pool = UnifiedKVPool(n_blocks, 64, dtype=jnp.float32)
    engines = {}
    for i, a in enumerate(archs):
        cfg = replace(configs.get_reduced(a), name=f"m{i}")
        params = init_params(jax.random.PRNGKey(i), cfg, jnp.float32)
        view = pool.register_model(cfg, quota)
        engines[cfg.name] = Engine(cfg, with_model_axis(params), view,
                                   max_slots=max_slots)
    return MuxScheduler(engines, pool, policy="adbs", fused=fused), pool


def _submit(mux, n_reqs, max_new=4, seed=7):
    rng = np.random.default_rng(seed)
    names = list(mux.engines)
    reqs = []
    for i in range(n_reqs):
        name = names[i % len(names)]
        vocab = mux.engines[name].cfg.vocab_size
        r = Request(i, name, list(rng.integers(1, vocab, 6 + i % 5)), max_new)
        reqs.append(r)
        mux.submit(r)
    return reqs


def _pool_state(mux):
    """Canonical host-side cache-state snapshot: per-model per-seq
    token counts and block counts, per-view usage accounting, and the
    arena's used-block total.  Physical base ids are deliberately NOT
    compared — allocation ORDER is scheduler-path-dependent (serial
    ticks allocate in rotated engine order, the fused sweep in group
    order), so bases may differ while the logical state is identical.
    Quotas are NOT compared either: the fused scheduler grants the
    head-blocks reclaimed by weight de-duplication to the group's
    views (DESIGN.md §2), so fused quotas are larger by design.
    """
    state = {}
    for name, eng in mux.engines.items():
        state[name] = ({sid: (len(sc.bases), sc.n_tokens)
                        for sid, sc in eng.view.seqs.items()},
                       eng.view.used)
    state["__used__"] = mux.pool.allocator.used
    return state


@pytest.mark.parametrize("n_models", [2, 3])
def test_fused_parity_with_serial(n_models):
    """Fused decode == serial decode: identical tokens AND identical
    canonical pool state at every tick, for colocated same-arch
    engines with distinct weights.  max_new crosses a 16-token block
    boundary mid-decode so decode-time allocation is exercised, not
    just prefill-time."""
    archs = ["qwen2-7b"] * n_models
    mux_s, pool_s = _colocated(archs, fused=False)
    mux_f, pool_f = _colocated(archs, fused=True)
    assert len(mux_f.fused_groups) == 1
    assert len(mux_f.fused_groups[0].engines) == n_models
    assert mux_f._serial_names == []

    _submit(mux_s, 2 * n_models, max_new=20)
    reqs_f = _submit(mux_f, 2 * n_models, max_new=20)

    for _ in range(400):
        if not (mux_s.pending() or mux_f.pending()):
            break
        mux_s.tick()
        mux_f.tick()
        assert _pool_state(mux_s) == _pool_state(mux_f)

    assert len(mux_s.stats.finished) == len(mux_f.stats.finished) \
        == 2 * n_models
    outs_s = {r.req_id: r.output for r in mux_s.stats.finished}
    for r in reqs_f:
        assert r.output == outs_s[r.req_id], r.req_id
    assert pool_s.allocator.used == 0 and pool_f.allocator.used == 0
    assert mux_s.stats.decode_tokens == mux_f.stats.decode_tokens


def test_fused_heterogeneous_fallback():
    """Transformer + mamba2 colocation: no fusable pair exists, the
    fused scheduler serves both on the serial path, and results match
    the serial scheduler exactly."""
    archs = ["qwen2-7b", "mamba2-2.7b"]
    mux_s, _ = _colocated(archs, fused=False)
    mux_f, pool_f = _colocated(archs, fused=True)
    assert mux_f.fused_groups == []          # SSM is fusion-ineligible
    assert set(mux_f._serial_names) == set(mux_f.engines)

    _submit(mux_s, 6)
    reqs_f = _submit(mux_f, 6)
    mux_s.run(max_ticks=200)
    mux_f.run(max_ticks=200)

    assert len(mux_f.stats.finished) == 6
    outs_s = {r.req_id: r.output for r in mux_s.stats.finished}
    for r in reqs_f:
        assert r.output == outs_s[r.req_id]
    assert pool_f.allocator.used == 0


def test_fused_mixed_group_and_fallback():
    """Two fusable same-arch engines + one SSM engine in one unit: the
    pair fuses, the SSM engine decodes serially, everything drains."""
    archs = ["qwen2-7b", "qwen2-7b", "mamba2-2.7b"]
    mux_f, pool_f = _colocated(archs, fused=True)
    assert len(mux_f.fused_groups) == 1
    assert len(mux_f.fused_groups[0].engines) == 2
    assert mux_f._serial_names == ["m2"]

    mux_s, _ = _colocated(archs, fused=False)
    _submit(mux_s, 6)
    reqs_f = _submit(mux_f, 6)
    mux_s.run(max_ticks=200)
    mux_f.run(max_ticks=200)
    assert len(mux_f.stats.finished) == 6
    outs_s = {r.req_id: r.output for r in mux_s.stats.finished}
    for r in reqs_f:
        assert r.output == outs_s[r.req_id]
    assert pool_f.allocator.used == 0


def test_fusion_signature_eligibility():
    cfg_t = configs.get_reduced("qwen2-7b")
    cfg_s = configs.get_reduced("mamba2-2.7b")
    pool = UnifiedKVPool(50_000, 64, dtype=jnp.float32)
    pt = init_params(jax.random.PRNGKey(0), cfg_t, jnp.float32)
    ps = init_params(jax.random.PRNGKey(1), cfg_s, jnp.float32)
    et = Engine(cfg_t, with_model_axis(pt), pool.register_model(cfg_t, 10_000))
    es = Engine(cfg_s, with_model_axis(ps), pool.register_model(cfg_s, 10_000))
    assert et.fusion_signature() is not None
    assert es.fusion_signature() is None     # SSM keeps its own scan
    # a different block-table width must not fuse (padding mismatch)
    cfg_t2 = replace(cfg_t, name="t2")
    et2 = Engine(cfg_t2, with_model_axis(pt),
                 pool.register_model(cfg_t2, 10_000),
                 max_blocks_per_seq=32)
    assert et2.fusion_signature() != et.fusion_signature()


def test_fused_block_tables_assembly():
    """Combined block-table padding: −1 tables / len-1 rows for padded
    entries, real rows resolved through each model's own view."""
    cfg = replace(configs.get_reduced("qwen2-7b"), name="a")
    cfg2 = replace(configs.get_reduced("qwen2-7b"), name="b")
    pool = UnifiedKVPool(50_000, 64, dtype=jnp.float32)
    va = pool.register_model(cfg, 20_000)
    vb = pool.register_model(cfg2, 20_000)
    assert va.append_tokens(0, 20)           # 2 token-blocks
    assert vb.append_tokens(0, 5)            # 1 token-block
    tables, lens = fused_block_tables([(va, [0]), (vb, [0])],
                                      rows=2, max_blocks=4)
    assert tables.shape == (2, 2, 4) and lens.shape == (2, 2)
    np.testing.assert_array_equal(tables[0, 0],
                                  va.block_table([0], 4)[0])
    np.testing.assert_array_equal(tables[1, 0],
                                  vb.block_table([0], 4)[0])
    assert (tables[0, 1] == -1).all() and (tables[1, 1] == -1).all()
    np.testing.assert_array_equal(lens[:, 0], [20, 5])
    np.testing.assert_array_equal(lens[:, 1], [1, 1])


def test_fused_kernel_matches_oracle():
    """Pallas fused_paged_decode_attention (interpret mode) == XLA
    oracle on a cross-model row batch with pre-resolved phys ids."""
    from repro.kernels.paged_attention import fused_paged_decode_attention
    key = jax.random.PRNGKey(3)
    bt, nb, kv, h, hd = 16, 4, 2, 4, 64
    pool_k = jax.random.normal(key, (256, bt, hd), jnp.float32)
    pool_v = jax.random.normal(jax.random.PRNGKey(4), (256, bt, hd),
                               jnp.float32)
    q = jax.random.normal(jax.random.PRNGKey(5), (4, h, hd), jnp.float32)
    # rows from two "models": different layer offsets in the same arena
    t0 = np.array([[0, 8, -1, -1], [16, 24, 32, -1]], np.int32)
    t1 = np.array([[40, 48, -1, -1], [56, 64, 72, 80]], np.int32)
    phys = jnp.concatenate([
        cache_ops.resolve_physical_blocks(jnp.asarray(t0), 0, kv),
        cache_ops.resolve_physical_blocks(jnp.asarray(t1), 1, kv)])
    lens = jnp.asarray(np.array([20, 40, 30, 64], np.int32))
    oracle = cache_ops.fused_paged_decode_attention(
        q, pool_k, pool_v, phys, lens)
    out = fused_paged_decode_attention(q, pool_k, pool_v, phys, lens,
                                       interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(oracle),
                               rtol=2e-5, atol=2e-5)
