"""Unified KV pool + block allocator: unit + property tests."""
import numpy as np
import pytest

from _hypothesis_compat import given, settings, st

from repro import configs
from repro.config import BLOCK_TOKENS
from repro.serving.kvcache import BlockAllocator, UnifiedKVPool


# ---------------------------------------------------------------------------
# allocator properties (hypothesis)
# ---------------------------------------------------------------------------
@settings(max_examples=200, deadline=None)
@given(st.lists(st.tuples(st.booleans(), st.integers(1, 64)),
                min_size=1, max_size=80))
def test_allocator_invariants(ops):
    """Random alloc/free interleavings keep the free-space accounting
    exact and ranges disjoint."""
    alloc = BlockAllocator(1024)
    live = []  # (start, n)
    for is_alloc, n in ops:
        if is_alloc:
            s = alloc.alloc(n)
            if s is not None:
                assert 0 <= s and s + n <= 1024
                for (s2, n2) in live:
                    assert s + n <= s2 or s2 + n2 <= s, "overlap!"
                live.append((s, n))
        elif live:
            s, n = live.pop(np.random.default_rng(n).integers(0, len(live)))
            alloc.free(s, n)
        assert alloc.used == sum(n for _, n in live)
        assert alloc.free_blocks == 1024 - alloc.used
    # free everything → one coalesced range
    for s, n in live:
        alloc.free(s, n)
    assert alloc.free_blocks == 1024
    assert alloc.largest_free_range() == 1024
    assert alloc.fragmentation() == 0.0


def test_allocator_exhaustion():
    a = BlockAllocator(10)
    assert a.alloc(8) == 0
    assert a.alloc(4) is None          # doesn't fit
    assert a.alloc(2) == 8
    assert a.alloc(1) is None
    a.free(0, 8)
    assert a.alloc(8) == 0


def test_allocator_starts_whole_tile_rows_on_a_tile_row():
    """A run of whole 16-block tile rows starts on a tile row (the
    row-form arena gathers such a model's heads by tile rows); other
    runs stay first fit, and fill the gap the alignment left."""
    a = BlockAllocator(128)
    assert a.alloc(8) == 0
    assert a.alloc(32) == 16
    assert a.alloc(8) == 8
    assert a.alloc(16) == 48
    a.free(16, 32)
    assert a.alloc(3) == 16
    assert a.alloc(16) == 32
    assert a.free_blocks == 128 - 8 - 8 - 16 - 3 - 16


def test_allocator_shrink_exact_inverse_of_grow_when_idle():
    a = BlockAllocator(128)
    a.grow(64)
    assert a.n_blocks == 192 and a.free_blocks == 192
    assert a.shrink(64) == 64
    assert a.n_blocks == 128 and a.free_blocks == 128
    assert a.largest_free_range() == 128
    # idle arena shrinks all the way to zero if asked
    assert a.shrink(1_000) == 128
    assert a.n_blocks == 0 and a.free_blocks == 0


def test_allocator_shrink_refuses_in_use_tail():
    a = BlockAllocator(64)
    s = a.alloc(64)
    assert a.shrink(16) == 0, "a fully-used arena must not shrink"
    assert a.n_blocks == 64
    a.free(s, 64)
    # now only the free tail is reclaimable past a live head range
    s = a.alloc(16)                     # occupies [0, 16)
    assert a.shrink(64) == 48, "clamp to the free tail"
    assert a.n_blocks == 16 and a.free_blocks == 0
    a.free(s, 16)
    assert a.free_blocks == 16


@settings(max_examples=100, deadline=None)
@given(st.integers(1, 256), st.integers(0, 256), st.integers(0, 512))
def test_allocator_grow_shrink_roundtrip(base, grown, live):
    """grow(n) then shrink(n) restores the arena exactly whenever the
    grown tail stayed idle, regardless of interior allocations."""
    a = BlockAllocator(base)
    s = a.alloc(min(live, base)) if live and min(live, base) > 0 else None
    used = a.used
    a.grow(grown)
    assert a.free_blocks == base - used + grown
    assert a.shrink(grown) == grown
    assert a.n_blocks == base and a.used == used
    if s is not None:
        a.free(s, min(live, base))
    assert a.free_blocks == base


def test_pool_shrink_inverse_of_grow():
    pool = _pool(1024)
    k0, v0 = pool.k.shape, pool.v.shape
    assert pool.grow(512) == 512
    assert pool.k.shape[0] == 1536
    assert pool.shrink(512) == 512
    assert pool.n_head_blocks == 1024
    assert pool.k.shape == k0 and pool.v.shape == v0
    assert pool.allocator.free_blocks == 1024


def test_pool_shrink_clamped_by_live_blocks():
    pool = _pool(256)
    cfg = configs.get_reduced("qwen2-7b")
    view = pool.register_model(cfg, quota=256)
    assert view.append_tokens(0, BLOCK_TOKENS)   # head of the arena live
    pool.grow(64)
    removed = pool.shrink(1_000)
    assert removed == 256 + 64 - view.used, \
        "shrink stops at the in-use head range"
    assert pool.n_head_blocks == view.used
    assert pool.k.shape[0] == view.used
    view.free_seq(0)
    assert pool.allocator.used == 0


# ---------------------------------------------------------------------------
# pool + per-model views
# ---------------------------------------------------------------------------
def _pool(n_blocks=4096, hd=64):
    return UnifiedKVPool(n_blocks, hd)


def test_view_quota_enforced():
    pool = _pool()
    cfg = configs.get_reduced("qwen2-7b")
    group = cfg.n_layers * cfg.n_kv_heads
    view = pool.register_model(cfg, quota=group * 4)  # 4 token-blocks
    assert view.append_tokens(0, BLOCK_TOKENS * 4)     # exactly quota
    assert view.used == group * 4
    assert not view.append_tokens(0, 1), "over quota must fail"
    view.free_seq(0)
    assert view.used == 0
    assert pool.allocator.used == 0


def test_register_model_rejects_mismatched_head_dim():
    """Regression: the head-dim guard was a tautology (`... or True`)
    until PR 10, silently admitting views whose pages could never fit
    the arena rows.  A mismatched attention model must be rejected;
    attention-free models carry no KV pages and register anywhere."""
    from repro.config import replace
    pool = _pool(hd=64)
    cfg = configs.get_reduced("qwen2-7b")
    bad = replace(cfg, name="bad-hd", head_dim=48)
    with pytest.raises(AssertionError, match="head_dim"):
        pool.register_model(bad, quota=256)
    assert "bad-hd" not in pool.views
    # matching head_dim and attention-free both still register
    pool.register_model(cfg, quota=256)
    ssm = configs.get_reduced("mamba2-2.7b")
    assert ssm.attn_free
    view = pool.register_model(ssm, quota=256)
    assert view.group_size == 0


def test_two_models_share_pool():
    """Two different reduced models allocate from one arena."""
    pool = _pool()
    a = configs.get_reduced("qwen2-7b")
    b = configs.get_reduced("musicgen-medium")
    va = pool.register_model(a, quota=2048)
    vb = pool.register_model(b, quota=2048)
    assert va.append_tokens(0, 40)
    assert vb.append_tokens(0, 40)
    assert pool.allocator.used == va.used + vb.used
    va.free_seq(0)
    vb.free_seq(0)
    assert pool.allocator.used == 0


def test_quota_adaptation_moves_to_hot_model():
    pool = _pool(8192)
    a = configs.get_reduced("qwen2-7b")
    b = configs.get_reduced("deepseek-coder-33b")
    va = pool.register_model(a, quota=256)
    vb = pool.register_model(b, quota=256)
    # b is busy (>20% of quota), a idle
    for i in range(6):
        assert vb.append_tokens(i, 64)
    q_a, q_b = va.quota, vb.quota
    pool.adapt_quotas()
    assert vb.quota > q_b and va.quota < q_a, \
        "quota must flow from idle to busy LLM (Alg. 3)"


def test_ssm_state_accounted():
    """SSM state lives in the engine's slots: a pure-SSM view charges
    neither quota nor arena, however long its sequences grow."""
    pool = _pool()
    m = configs.get_reduced("mamba2-2.7b")
    v = pool.register_model(m, quota=1024)
    assert v.group_size == 0                     # no attention blocks
    assert v.append_tokens(0, 100)
    assert v.used == 0 and pool.allocator.used == 0
    v.append_tokens(0, 400)
    assert v.used == 0 and 0 in v.seqs
    assert v.can_append(1, 100_000)
    v.free_seq(0)
    assert v.used == 0 and not v.seqs


@settings(max_examples=50, deadline=None)
@given(st.lists(st.integers(1, 200), min_size=1, max_size=12))
def test_block_table_roundtrip(lens):
    pool = _pool(65536)
    cfg = configs.get_reduced("qwen3-14b")
    view = pool.register_model(cfg, quota=65536)
    ok_ids = []
    for sid, n in enumerate(lens):
        if view.append_tokens(sid, n):
            ok_ids.append(sid)
    tbl = view.block_table(ok_ids, max_blocks=16)
    sl = view.seq_lens(ok_ids)
    for i, sid in enumerate(ok_ids):
        n_blocks = -(-lens[sid] // BLOCK_TOKENS)
        got = (tbl[i] >= 0).sum()
        assert got == min(n_blocks, 16)
        assert sl[i] == lens[sid]
    for sid in ok_ids:
        view.free_seq(sid)
    assert pool.allocator.used == 0

# ---------------------------------------------------------------------------
# grow/shrink/alloc under grant-debt settlement (hypothesis)
# ---------------------------------------------------------------------------
@settings(max_examples=50, deadline=None)
@given(st.lists(st.tuples(st.integers(0, 3), st.integers(1, 96)),
                min_size=1, max_size=60))
def test_pool_grant_debt_interleaving(ops):
    """Random interleavings of seq alloc-to-exhaustion, frees, and the
    fused-group grant algebra (``MuxScheduler``: build settles debt
    before growing, dissolve shrinks and books the unreclaimed tail as
    debt) keep the arena exactly sized: no block is double-freed, none
    is minted, and ``n_head_blocks == base + granted + debt`` at every
    step.  This is the accounting a block-loss fault (``pool.shrink``
    mid-flight, serving/faults.py) and a crash recovery (dissolve +
    rebuild) both lean on."""
    base = 512
    pool = _pool(base)
    cfg = configs.get_reduced("qwen2-7b")
    view = pool.register_model(cfg, quota=10**9)
    granted = debt = 0
    live: list = []
    next_sid = 0
    for kind, n in ops:
        if kind == 0:                      # alloc (may exhaust: ok=False)
            if view.append_tokens(next_sid, n * BLOCK_TOKENS):
                live.append(next_sid)
            next_sid += 1
        elif kind == 1 and live:           # free a live seq
            view.free_seq(live.pop(n % len(live)))
        elif kind == 2 and granted == 0:   # build: settle debt, grow rest
            want = n
            settle = min(debt, want)
            debt -= settle
            grown = pool.grow(want - settle)
            assert grown == want - settle, "grow is unconditional"
            granted = grown + settle
        elif kind == 3 and granted > 0:    # dissolve: shrink, book debt
            got = pool.shrink(granted)
            assert 0 <= got <= granted
            debt += granted - got
            granted = 0
        assert debt >= 0 and granted >= 0
        assert pool.n_head_blocks == base + granted + debt, \
            "arena size must equal base + outstanding grant + debt"
        assert pool.allocator.used == view.used, "accounting exact"
        assert pool.allocator.free_blocks \
            == pool.n_head_blocks - pool.allocator.used
        assert pool.k.shape[0] == pool.n_head_blocks
    # cleanup: free everything, dissolve, settle all debt — the arena
    # returns to its seed size with zero leaked blocks
    for sid in list(live):
        view.free_seq(sid)
    if granted:
        debt += granted - pool.shrink(granted)
    assert pool.shrink(debt) == debt, "idle tail settles all debt"
    assert pool.n_head_blocks == base and pool.allocator.used == 0
    assert pool.allocator.free_blocks == base

# ---------------------------------------------------------------------------
# refcounted sharing (prefix caching, DESIGN.md §13)
# ---------------------------------------------------------------------------
def test_allocator_share_refcounts_and_double_free():
    a = BlockAllocator(16)
    s = a.alloc(4)
    a.share(s, 4)
    assert a.used == 8 and a.physical_used == 4
    assert a.refcount(s) == 2
    a.free(s, 4)                        # one holder lets go...
    assert a.used == 4 and a.physical_used == 4, \
        "a block must never be reclaimed while refcount > 0"
    assert a.alloc(16) is None, "shared blocks still occupy the arena"
    a.free(s, 4)                        # ...now the last one does
    assert a.used == 0 and a.free_blocks == 16
    with pytest.raises(ValueError):
        a.free(s, 4)                    # double free must raise
    with pytest.raises(ValueError):
        a.share(s, 1)                   # sharing free space is a bug
    assert a.alloc(16) == 0


def test_fragmentation_vs_shrinkable_tail():
    """Regression: ``largest_free_range``/``fragmentation`` describe
    interior allocatability and must NOT be read as shrink capacity —
    a single pinned tail block clamps ``shrink`` regardless of how big
    the interior free space is.  ``shrinkable_tail`` is the honest
    shrink figure."""
    a = BlockAllocator(64)
    s1 = a.alloc(48)
    s2 = a.alloc(16)                    # pins [48, 64): the tail
    a.free(s1, 48)                      # huge interior free run
    assert a.largest_free_range() == 48
    assert a.fragmentation() == 0.0
    assert a.shrinkable_tail() == 0, "pinned tail → nothing shrinkable"
    assert a.shrink(16) == 0, "shrink must refuse the pinned tail"
    assert a.n_blocks == 64
    a.free(s2, 16)
    assert a.shrinkable_tail() == 64


def test_pool_shrinkable_tail_exposed():
    pool = _pool(256)
    cfg = configs.get_reduced("qwen2-7b")
    view = pool.register_model(cfg, quota=10**6)
    assert pool.shrinkable_tail() == 256
    assert view.append_tokens(0, BLOCK_TOKENS)
    assert pool.shrinkable_tail() == 256 - view.used
    view.free_seq(0)
    assert pool.shrinkable_tail() == 256


@settings(max_examples=25, deadline=None)
@given(st.lists(st.tuples(st.integers(0, 5), st.integers(1, 64)),
                min_size=1, max_size=50))
def test_pool_sharing_interleaving(ops):
    """Random interleavings of seq allocation, frees, prefix sharing
    (``share_prefix``), copy-on-write appends into shared tails, and
    the fused-grant grow/shrink/debt algebra keep every allocator
    invariant exact: ``n_head_blocks == base + granted + debt``,
    ``used`` equals the refcount-weighted live set, ``physical_used``
    counts distinct live blocks, and the free list stays sorted,
    coalesced, disjoint from live blocks and in-bounds.  No block is
    reclaimed while a holder remains (DESIGN.md §13)."""
    base = 512
    pool = UnifiedKVPool(base, 16)
    from repro.config import replace
    cfg = replace(configs.get_reduced("qwen2-7b"), head_dim=16)
    view = pool.register_model(cfg, quota=10**9)
    gs = view.group_size
    granted = debt = 0
    live: list = []
    next_sid = 0
    for kind, n in ops:
        if kind == 0:                      # new seq (may exhaust: ok=False)
            if view.append_tokens(next_sid, (n % 8 + 1) * BLOCK_TOKENS):
                live.append(next_sid)
            next_sid += 1
        elif kind == 1 and live:           # free a live seq
            view.free_seq(live.pop(n % len(live)))
        elif kind == 2 and granted == 0:   # build: settle debt, grow rest
            settle = min(debt, n)
            debt -= settle
            pool.grow(n - settle)
            granted = n
        elif kind == 3 and granted > 0:    # dissolve: shrink, book debt
            got = pool.shrink(granted)
            debt += granted - got
            granted = 0
        elif kind == 4 and live:           # adopt a donor's prefix
            donor = view.seqs[live[n % len(live)]]
            if donor.bases:
                k = 1 + n % len(donor.bases)
                tok = (k - 1) * BLOCK_TOKENS + 1 + n % BLOCK_TOKENS
                if view.share_prefix(next_sid, donor.bases[:k], tok):
                    live.append(next_sid)
                next_sid += 1
        elif kind == 5 and live:           # append (COW on shared tails)
            view.append_tokens(live[n % len(live)], n)
        alloc = pool.allocator
        assert pool.n_head_blocks == base + granted + debt
        refs = alloc.refcounts()
        assert alloc.used == sum(refs.values()) == view.used
        assert view.used == sum(len(view.seqs[s].bases) * gs for s in live)
        assert alloc.physical_used == len(refs)
        assert alloc.free_blocks == pool.n_head_blocks - len(refs)
        free_set: set = set()
        prev_end = -1
        for s, e in alloc._free:
            assert 0 <= s < e <= alloc.n_blocks, "free range out of bounds"
            assert s > prev_end, "free list must stay sorted + coalesced"
            prev_end = e
            free_set.update(range(s, e))
        assert len(free_set) == alloc.free_blocks
        assert not free_set & refs.keys(), \
            "a live (possibly shared) block leaked into the free list"
        for sid in live:
            sc = view.seqs[sid]
            assert sc.shared <= len(sc.bases)
            assert all(b + gs <= pool.n_head_blocks for b in sc.bases)
    for sid in list(live):
        view.free_seq(sid)
    if granted:
        debt += granted - pool.shrink(granted)
    assert pool.shrink(debt) == debt, "idle tail settles all debt"
    assert pool.n_head_blocks == base and pool.allocator.used == 0
    assert pool.allocator.free_blocks == base
