"""Zero-copy stacked weights + fused chunked-prefill sweep + shape-
stable batching (DESIGN.md §2/§5).

A fused group must hold exactly ONE weight tree (members index the
stacked buffer — no private copies), the reclaimed HBM must grow the
unified pool, the fused prefill sweep must be greedy-parity with the
serial chunk path, and the bucketed hot paths must stop compiling new
programs once their shape buckets are warm.
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.config import replace
from repro.models.transformer import init_params
from repro.serving.engine import (TRACE_COUNTS, Engine, Request, tree_bytes,
                                  unique_tree_bytes)
from repro.serving.kvcache import UnifiedKVPool
from repro.serving.mux import MuxScheduler

from _weights import with_model_axis


def _colocated(archs, fused, max_slots=2, quota=30_000, n_blocks=100_000,
               chunk_tokens=None):
    """Build a unit of colocated reduced engines (repeated archs get
    distinct weights + names) and a MuxScheduler over them."""
    pool = UnifiedKVPool(n_blocks, 64, dtype=jnp.float32)
    engines = {}
    for i, a in enumerate(archs):
        cfg = replace(configs.get_reduced(a), name=f"m{i}")
        params = init_params(jax.random.PRNGKey(i), cfg, jnp.float32)
        view = pool.register_model(cfg, quota)
        engines[cfg.name] = Engine(cfg, with_model_axis(params), view,
                                   max_slots=max_slots,
                                   chunk_tokens=chunk_tokens)
    return MuxScheduler(engines, pool, policy="adbs", fused=fused), pool


def _submit(mux, n_reqs, max_new=4, seed=7, plen=None):
    rng = np.random.default_rng(seed)
    names = list(mux.engines)
    reqs = []
    for i in range(n_reqs):
        name = names[i % len(names)]
        vocab = mux.engines[name].cfg.vocab_size
        n = plen(i) if plen else 6 + i % 5
        r = Request(i, name, list(rng.integers(1, vocab, n)), max_new)
        reqs.append(r)
        mux.submit(r)
    return reqs


# ---------------------------------------------------------------------------
# zero-copy weight de-duplication
# ---------------------------------------------------------------------------
def test_fused_group_holds_single_weight_tree():
    """No engine in a fused group holds a private full weight tree: all
    members point at the group's stacked tree, so the group's live
    weight bytes are ~1× (the stacked tree), not 2×."""
    mux, _ = _colocated(["qwen2-7b"] * 3, fused=True)
    assert len(mux.fused_groups) == 1
    grp = mux.fused_groups[0]
    for eng in grp.engines:
        assert eng.params is grp.params, \
            "fused-group engine must index the shared stacked tree"
    live = unique_tree_bytes([e.params for e in grp.engines])
    assert live == tree_bytes(grp.params)
    # the serial scheduler's engines own one tree each — the fused
    # group's live bytes must equal that total (1×), not double it
    mux_s, _ = _colocated(["qwen2-7b"] * 3, fused=False)
    serial_live = sum(unique_tree_bytes([e.params])
                      for e in mux_s.engines.values())
    assert live == serial_live
    assert grp.reclaimed_bytes == serial_live
    assert mux.reclaimed_weight_bytes == grp.reclaimed_bytes


def test_reclaimed_bytes_grow_pool():
    """The weight copy reclaimed by de-duplication is granted to the
    unified pool as extra head-blocks, split across the group's views
    as quota (the paper's memory-multiplexing dividend)."""
    n_blocks, quota = 50_000, 10_000
    mux_s, pool_s = _colocated(["qwen2-7b"] * 2, fused=False,
                               n_blocks=n_blocks, quota=quota)
    mux_f, pool_f = _colocated(["qwen2-7b"] * 2, fused=True,
                               n_blocks=n_blocks, quota=quota)
    grp = mux_f.fused_groups[0]
    extra = grp.reclaimed_bytes // pool_f.head_block_bytes
    assert extra > 0
    assert pool_f.n_head_blocks == n_blocks + extra
    assert pool_f.allocator.n_blocks == n_blocks + extra
    assert pool_f.allocator.free_blocks \
        == pool_s.allocator.free_blocks + extra
    assert pool_f.k.shape[0] == n_blocks + extra
    share = extra // len(grp.engines)
    for eng in mux_f.engines.values():
        assert eng.view.quota == quota + share
    # the grown range is allocatable
    base = pool_f.allocator.alloc(pool_f.allocator.free_blocks)
    assert base is not None
    pool_f.allocator.free(base, pool_f.allocator.used)


def test_serial_fallback_runs_off_stacked_tree():
    """A lone-active group member decodes AND prefills off the shared
    stacked tree (via its model index) with outputs identical to a
    standalone engine holding the same weights privately."""
    mux, _ = _colocated(["qwen2-7b"] * 2, fused=True)
    rng = np.random.default_rng(11)
    cfg = mux.engines["m1"].cfg
    prompt = list(rng.integers(1, cfg.vocab_size, 9))
    r = Request(0, "m1", list(prompt), 6)
    mux.submit(r)
    mux.run(max_ticks=100)
    assert r.done

    # standalone reference: same seed ⇒ same weights, private tree
    cfg1 = replace(configs.get_reduced("qwen2-7b"), name="m1")
    params = init_params(jax.random.PRNGKey(1), cfg1, jnp.float32)
    pool2 = UnifiedKVPool(50_000, 64, dtype=jnp.float32)
    solo = Engine(cfg1, with_model_axis(params),
                  pool2.register_model(cfg1, 20_000),
                  max_slots=2)
    q = Request(9, "m1", list(prompt), 6)
    solo.prefill([q])
    while not q.done:
        solo.decode()
    assert r.output == q.output


# ---------------------------------------------------------------------------
# fused chunked-prefill sweep
# ---------------------------------------------------------------------------
def test_fused_prefill_parity_with_serial():
    """Fused prefill sweep == serial chunked prefill: greedy outputs
    bit-identical for colocated same-arch engines with distinct
    weights, prompts long enough to span several chunks, and decode
    interleaved between chunks."""
    archs = ["qwen2-7b"] * 3
    mux_s, pool_s = _colocated(archs, fused=False, chunk_tokens=8)
    mux_f, pool_f = _colocated(archs, fused=True, chunk_tokens=8)
    assert len(mux_f.fused_groups) == 1
    assert mux_f.fused_groups[0].chunk_tokens == 8
    # chunked group members leave the serial prefill rotation entirely
    assert mux_f._prefill_serial_names == []

    plen = lambda i: (11, 23, 34)[i % 3]  # noqa: E731 — spans 2-5 chunks
    _submit(mux_s, 6, max_new=20, plen=plen)
    reqs_f = _submit(mux_f, 6, max_new=20, plen=plen)
    mux_s.run(max_ticks=400)
    mux_f.run(max_ticks=400)

    assert len(mux_s.stats.finished) == len(mux_f.stats.finished) == 6
    outs_s = {r.req_id: r.output for r in mux_s.stats.finished}
    for r in reqs_f:
        assert r.output == outs_s[r.req_id], r.req_id
    assert mux_s.stats.prefill_tokens == mux_f.stats.prefill_tokens
    assert pool_s.allocator.used == 0 and pool_f.allocator.used == 0


def test_fused_prefill_mixed_chunk_and_whole_prompt():
    """Engines with different chunk windows must not share a group
    (the sweep needs one common chunk shape), and whole-prompt fused
    groups keep prefilling serially while decoding fused."""
    pool = UnifiedKVPool(100_000, 64, dtype=jnp.float32)
    engines = {}
    for i, chunk in enumerate((8, 8, None)):
        cfg = replace(configs.get_reduced("qwen2-7b"), name=f"m{i}")
        params = init_params(jax.random.PRNGKey(i), cfg, jnp.float32)
        engines[cfg.name] = Engine(cfg, with_model_axis(params),
                                   pool.register_model(cfg, 30_000),
                                   max_slots=2, chunk_tokens=chunk)
    mux = MuxScheduler(engines, pool, policy="adbs", fused=True)
    # chunk window is part of the fusion signature: m0+m1 group, m2
    # (whole-prompt) stays serial for both phases
    assert len(mux.fused_groups) == 1
    assert set(mux.fused_groups[0].names) == {"m0", "m1"}
    assert mux._serial_names == ["m2"]
    assert mux._prefill_serial_names == ["m2"]
    reqs = _submit(mux, 6, max_new=6)
    mux.run(max_ticks=300)
    assert all(r.done for r in reqs)
    assert pool.allocator.used == 0


# ---------------------------------------------------------------------------
# shape-stable batching
# ---------------------------------------------------------------------------
def _drain_wave(eng, prompts, max_new):
    reqs = [Request(i, eng.cfg.name, list(p), max_new)
            for i, p in enumerate(prompts)]
    pending = list(reqs)
    for _ in range(200):
        if pending or eng.has_prefill_work():
            eng.prefill(pending[:len(eng.free_slots())])
            pending = [r for r in pending if not hasattr(r, "_seq_id")]
        eng.decode()
        if all(r.done for r in reqs):
            return reqs
    raise AssertionError("wave did not drain")


def test_bucketing_bounds_compile_count():
    """Once the (pow2-B, block-multiple-S) buckets of a workload are
    warm, serving a second workload with the same bucket profile must
    compile NOTHING new — the trace counter proves shape stability."""
    cfg = replace(configs.get_reduced("qwen2-7b"), name="tc0")
    params = init_params(jax.random.PRNGKey(0), cfg, jnp.float32)
    pool = UnifiedKVPool(100_000, 64, dtype=jnp.float32)
    eng = Engine(cfg, with_model_axis(params),
                 pool.register_model(cfg, 50_000), max_slots=4)

    def wave(engine, lens, max_new, seed):
        rr = np.random.default_rng(seed)
        return _drain_wave(
            engine, [list(rr.integers(1, cfg.vocab_size, n)) for n in lens],
            max_new)

    # warm the buckets: prefill B=3→pow2 4, S=48; decode B=3→pow2 4
    wave(eng, [9, 17, 37], max_new=5, seed=1)
    warm = sum(TRACE_COUNTS.values())
    # same bucket profile, different raw shapes (lens land in the same
    # 16-token S buckets and the same pow2 row buckets)
    wave(eng, [13, 30, 42], max_new=5, seed=2)
    assert sum(TRACE_COUNTS.values()) == warm, \
        "warm shape buckets must not re-trace"

    # a same-geometry engine shares the jit cache: serving a second
    # instance of the architecture over the warm buckets compiles
    # nothing either
    cfg2 = replace(configs.get_reduced("qwen2-7b"), name="tc1")
    params2 = init_params(jax.random.PRNGKey(1), cfg2, jnp.float32)
    eng2 = Engine(cfg2, with_model_axis(params2),
                  pool.register_model(cfg2, 30_000),
                  max_slots=4)
    wave(eng2, [11, 21, 41], max_new=5, seed=3)
    assert sum(TRACE_COUNTS.values()) == warm, \
        "same-geometry engines must share compiled programs"


def test_chunked_bucketing_bounds_compile_count():
    """The chunked-prefill path is shape-stable too: fused sweep rows
    pad to the group's fixed row count, serial chunks to pow2 rows."""
    mux, _ = _colocated(["qwen2-7b"] * 2, fused=True, chunk_tokens=8,
                        max_slots=2)
    _submit(mux, 4, max_new=8, seed=3, plen=lambda i: 10 + 9 * (i % 2))
    mux.run(max_ticks=300)
    warm = sum(TRACE_COUNTS.values())
    _submit(mux, 4, max_new=8, seed=4, plen=lambda i: 12 + 7 * (i % 2))
    mux.run(max_ticks=300)
    assert sum(TRACE_COUNTS.values()) == warm, \
        "steady-state fused serving must not re-trace"
