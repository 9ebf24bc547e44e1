"""Engine + MuxScheduler: the CPU-scale runtime over the unified pool."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.models.transformer import forward, init_params
from repro.serving.engine import Engine, Request
from repro.serving.kvcache import UnifiedKVPool
from repro.serving.metrics import SPANS
from repro.serving.mux import MuxScheduler

from _rows import serve_rows_out_of_order
from _weights import with_model_axis


def _engine(arch, quota=100_000, n_blocks=200_000, max_slots=4, seed=0):
    cfg = configs.get_reduced(arch)
    params = init_params(jax.random.PRNGKey(seed), cfg, jnp.float32)
    pool = UnifiedKVPool(n_blocks, cfg.hd if cfg.hd else 64,
                         dtype=jnp.float32)
    view = pool.register_model(cfg, quota)
    eng = Engine(cfg, with_model_axis(params), view, max_slots=max_slots)
    return eng, pool, cfg, params


@pytest.mark.parametrize("arch", ["qwen2-7b", "zamba2-1.2b", "mamba2-2.7b",
                                  "granite-4.0-h-small"])
def test_engine_generates_greedy_match(arch):
    """Engine prefill+decode (paged pool) == full-forward greedy."""
    eng, pool, cfg, params = _engine(arch)
    prompt = list(np.random.default_rng(0).integers(1, cfg.vocab_size, 12))
    req = Request(req_id=0, model=cfg.name, prompt=prompt, max_new_tokens=5)
    assert eng.prefill([req]) > 0
    while not req.done:
        eng.decode()
    # reference greedy generation by full recompute
    seq = list(prompt)
    for _ in range(5):
        logits, _ = forward(params, cfg, jnp.asarray([seq]), remat=False,
                            moe_dropless=True)
        seq.append(int(jnp.argmax(logits[0, -1])))
    assert req.output == seq[len(prompt):], (req.output, seq[len(prompt):])


@pytest.mark.parametrize("arch", ["mamba2-2.7b", "zamba2-1.2b"])
def test_ssm_steps_index_their_slots_out_of_order(arch):
    """Six requests in six slots; those in slots 0, 2 and 5 finish with
    their prompt, so every later step runs 3 rows (slots 4, 3, 1, in
    that order) in a bucket of 4.  Each step leaves the other slots'
    state as it was; a chunk that starts its prompt starts from zero
    state, whatever the slot held; every request's tokens are the
    full-forward greedy ones.  mamba2 chunks its prompts, the hybrid
    prefills them whole."""
    cfg = configs.get_reduced(arch)
    params = init_params(jax.random.PRNGKey(0), cfg, jnp.float32)
    pool = UnifiedKVPool(200_000, cfg.hd, dtype=jnp.float32)
    eng = Engine(cfg, with_model_axis(params),
                 pool.register_model(cfg, 100_000), max_slots=6,
                 chunk_tokens=16)
    rng = np.random.default_rng(5)
    reqs = [Request(i, cfg.name,
                    list(rng.integers(1, cfg.vocab_size, int(n))), m)
            for i, (n, m) in enumerate(zip((12, 40, 9, 35, 21, 14),
                                           (1, 6, 1, 5, 6, 1)))]
    rows = serve_rows_out_of_order(eng, reqs)
    assert 3 in rows and all(r.done for r in reqs)
    for r in reqs:
        seq = list(r.prompt)
        for _ in range(r.max_new_tokens):
            logits, _ = forward(params, cfg, jnp.asarray([seq]), remat=False)
            seq.append(int(jnp.argmax(logits[0, -1])))
        assert r.output == seq[len(r.prompt):], r.req_id


def test_engine_batched_consistency():
    """Two requests served together == each served alone (isolation)."""
    eng, pool, cfg, params = _engine("qwen2-7b")
    rng = np.random.default_rng(1)
    p1 = list(rng.integers(1, cfg.vocab_size, 9))
    p2 = list(rng.integers(1, cfg.vocab_size, 14))
    r1 = Request(0, cfg.name, p1, 4)
    r2 = Request(1, cfg.name, p2, 4)
    eng.prefill([r1, r2])
    while not (r1.done and r2.done):
        eng.decode()

    eng2, _, _, _ = _engine("qwen2-7b")
    a1 = Request(0, cfg.name, p1, 4)
    eng2.prefill([a1])
    while not a1.done:
        eng2.decode()
    assert r1.output == a1.output


def test_engine_slot_reuse():
    eng, pool, cfg, _ = _engine("qwen2-7b", max_slots=2)
    rng = np.random.default_rng(2)
    reqs = [Request(i, cfg.name, list(rng.integers(1, cfg.vocab_size, 6)), 2)
            for i in range(5)]
    served = 0
    pending = list(reqs)
    for _ in range(50):
        if pending:
            eng.prefill(pending[:len(eng.free_slots())])
        eng.decode()
        pending = [r for r in pending if not r.output]
        served = sum(1 for r in reqs if r.done)
        if served == 5:
            break
    assert served == 5
    assert pool.allocator.used == 0, "all cache freed after completion"


def test_mux_scheduler_two_llms():
    """Two colocated reduced LLMs share the pool under ADBS and both
    finish; outputs match single-LLM serving."""
    cfg_a = configs.get_reduced("qwen2-7b")
    cfg_b = configs.get_reduced("musicgen-medium")
    pool = UnifiedKVPool(200_000, 64, dtype=jnp.float32)
    pa = init_params(jax.random.PRNGKey(0), cfg_a, jnp.float32)
    pb = init_params(jax.random.PRNGKey(1), cfg_b, jnp.float32)
    va = pool.register_model(cfg_a, 100_000)
    vb = pool.register_model(cfg_b, 100_000)
    engines = {cfg_a.name: Engine(cfg_a, with_model_axis(pa), va,
                                  max_slots=2),
               cfg_b.name: Engine(cfg_b, with_model_axis(pb), vb,
                                  max_slots=2)}
    mux = MuxScheduler(engines, pool, policy="adbs")
    rng = np.random.default_rng(3)
    reqs = []
    for i in range(3):
        reqs.append(Request(i, cfg_a.name,
                            list(rng.integers(1, cfg_a.vocab_size, 8)), 3))
        reqs.append(Request(10 + i, cfg_b.name,
                            list(rng.integers(1, cfg_b.vocab_size, 8)), 3))
    for r in reqs:
        mux.submit(r)
    stats = mux.run(max_ticks=200)
    assert len(stats.finished) == 6
    assert stats.prefill_tokens > 0 and stats.decode_tokens > 0
    assert pool.allocator.used == 0

    # isolation: serving alone gives the same tokens
    solo_pool = UnifiedKVPool(200_000, 64, dtype=jnp.float32)
    sv = solo_pool.register_model(cfg_a, 100_000)
    solo = Engine(cfg_a, with_model_axis(pa), sv, max_slots=2)
    q = Request(0, cfg_a.name, reqs[0].prompt, 3)
    solo.prefill([q])
    while not q.done:
        solo.decode()
    muxed = next(r for r in stats.finished
                 if r.model == cfg_a.name and r.prompt == reqs[0].prompt)
    assert muxed.output == q.output


def test_batch_admission_accounts_for_pending():
    """A single prefill batch must not overcommit the quota: each
    candidate is checked against headroom minus the lifetime blocks of
    requests already selected for the batch."""
    cfg = configs.get_reduced("qwen2-7b")
    # group_size = 4 head-blocks per 16-token block; quota 12 = 3
    # groups, but each 22-token lifetime needs 2 → only one fits.
    pool = UnifiedKVPool(1000, cfg.hd, dtype=jnp.float32)
    view = pool.register_model(cfg, 12)
    params = init_params(jax.random.PRNGKey(0), cfg, jnp.float32)
    eng = Engine(cfg, with_model_axis(params), view, max_slots=2)
    rng = np.random.default_rng(0)
    r1 = Request(0, cfg.name, list(rng.integers(1, 512, 20)), 8)
    r2 = Request(1, cfg.name, list(rng.integers(1, 512, 20)), 8)
    eng.prefill([r1, r2])                    # must not crash or corrupt
    assert len(eng.active_slots()) == 1      # second request deferred


def test_decode_quota_overcommit_rolls_back():
    """Admitted sequences' future growth is not reserved, so requests
    admitted in separate batches can overcommit a small quota; decode
    must stall-and-retry the loser (rolling back the unreservable
    token) rather than corrupt its KV."""
    cfg = configs.get_reduced("qwen2-7b")
    # quota 12 = 3 groups; each request's lifetime is 2 groups, but at
    # admission time each sees enough headroom (growth unreserved).
    pool = UnifiedKVPool(1000, cfg.hd, dtype=jnp.float32)
    view = pool.register_model(cfg, 12)
    params = init_params(jax.random.PRNGKey(0), cfg, jnp.float32)
    eng = Engine(cfg, with_model_axis(params), view, max_slots=2)
    rng = np.random.default_rng(0)
    r1 = Request(0, cfg.name, list(rng.integers(1, 512, 14)), 8)
    r2 = Request(1, cfg.name, list(rng.integers(1, 512, 14)), 8)
    eng.prefill([r1])
    eng.prefill([r2])
    assert len(eng.active_slots()) == 2      # both admitted (overcommit)
    for _ in range(60):
        eng.decode()
        if r1.done and r2.done:
            break
    assert r1.done and r2.done
    assert not eng.preempted                 # r1 kept progressing
    assert pool.allocator.used == 0
    # no corruption: the stalled request's tokens match uncontended runs
    pool2 = UnifiedKVPool(1000, cfg.hd, dtype=jnp.float32)
    eng2 = Engine(cfg, with_model_axis(params),
                  pool2.register_model(cfg, 1000),
                  max_slots=2)
    for r in (r1, r2):
        q = Request(9, cfg.name, list(r.prompt), 8)
        eng2.prefill([q])
        while not q.done:
            eng2.decode()
        assert r.output == q.output


def _refuse_odd_positions_once(view):
    """A pure-SSM model keeps no KV blocks, so its pool never refuses a
    token.  Refuse instead, the first time it is asked, each one-token
    reservation that follows an odd number of tokens (the prompts here
    have even lengths: no prompt's first token is refused)."""
    refused = set()
    reserve = view.append_tokens

    def append_tokens(seq_id, n_tokens):
        at = view.seqs[seq_id].n_tokens if seq_id in view.seqs else 0
        if n_tokens == 1 and at % 2 and (seq_id, at) not in refused:
            refused.add((seq_id, at))
            return False
        return reserve(seq_id, n_tokens)
    view.append_tokens = append_tokens


def _held_rows(llm):
    """Rows whose state a decode step held, by the ``mux.ssm_held``
    events recorded since ``SPANS`` was last cleared."""
    v = SPANS.view()
    return sum(v.values[i] for i, n in enumerate(v.name)
               if v.names[n] == f"mux.ssm_held.{llm}")


@pytest.mark.parametrize("arch", ["zamba2-1.2b", "granite-4.0-h-small",
                                  "mamba2-2.7b"])
def test_decode_overcommit_hybrid_state_revert(arch):
    """Under quota overcommit a decode row that cannot reserve its next
    token is held: its step leaves its SSM state and carry as they
    were, or the retry re-advances the state and commits a different
    token than an uncontended run.  ``mux.ssm_held.<llm>`` counts the
    held rows; an uncontended run records none.  A pure-SSM model's
    pool never refuses, so its reservations are refused by hand."""
    cfg = configs.get_reduced(arch)
    params = init_params(jax.random.PRNGKey(0), cfg, jnp.float32)
    rng = np.random.default_rng(4)
    p1 = list(rng.integers(1, cfg.vocab_size, 14))
    p2 = list(rng.integers(1, cfg.vocab_size, 14))
    max_new = 24
    quota = 50_000
    if cfg.family != "ssm":
        # probe the quota analytically: admit r1, leave exactly one more
        # lifetime of headroom so r2 admits but their growth overcommits
        probe_pool = UnifiedKVPool(50_000, cfg.hd, dtype=jnp.float32)
        probe = Engine(cfg, with_model_axis(params),
                       probe_pool.register_model(cfg, 50_000), max_slots=2)
        pr = Request(0, cfg.name, list(p1), max_new)
        lifetime = probe.lifetime_blocks(pr)
        probe.prefill([pr])
        used_p = probe_pool.views[cfg.name].used
        assert lifetime > used_p, "need unreserved growth for overcommit"
        quota = used_p + lifetime

    pool = UnifiedKVPool(50_000, cfg.hd, dtype=jnp.float32)
    eng = Engine(cfg, with_model_axis(params),
                 pool.register_model(cfg, quota), max_slots=2)
    if cfg.family == "ssm":
        _refuse_odd_positions_once(eng.view)
    mux = MuxScheduler({cfg.name: eng}, pool, policy="adbs")
    r1 = Request(0, cfg.name, list(p1), max_new)
    r2 = Request(1, cfg.name, list(p2), max_new)
    mux.submit(r1)
    mux.submit(r2)
    SPANS.clear()
    stats = mux.run(max_ticks=600)
    assert len(stats.finished) == 2
    assert pool.allocator.used == 0
    assert _held_rows(cfg.name) > 0
    # outputs must match uncontended serving despite hold/preempt
    pool2 = UnifiedKVPool(50_000, cfg.hd, dtype=jnp.float32)
    eng2 = Engine(cfg, with_model_axis(params),
                  pool2.register_model(cfg, 50_000),
                  max_slots=2)
    SPANS.clear()
    for r in (r1, r2):
        q = Request(9, cfg.name, list(r.prompt), max_new)
        eng2.prefill([q])
        while not q.done:
            eng2.decode()
        assert r.output == q.output, r.req_id
    assert _held_rows(cfg.name) == 0


def test_quota_regrant_for_oversized_head_request():
    """A request whose lifetime exceeds its LLM's (shrunken) quota
    must not re-queue forever: the scheduler pulls spare quota back
    from other views before admission."""
    cfg_a = configs.get_reduced("qwen2-7b")
    cfg_b = configs.get_reduced("qwen3-14b")
    pool = UnifiedKVPool(100_000, 64, dtype=jnp.float32)
    pa = init_params(jax.random.PRNGKey(0), cfg_a, jnp.float32)
    pb = init_params(jax.random.PRNGKey(1), cfg_b, jnp.float32)
    va = pool.register_model(cfg_a, 4)           # as if adapt shrank it
    vb = pool.register_model(cfg_b, 50_000)
    engines = {cfg_a.name: Engine(cfg_a, with_model_axis(pa), va,
                                  max_slots=2),
               cfg_b.name: Engine(cfg_b, with_model_axis(pb), vb,
                                  max_slots=2)}
    mux = MuxScheduler(engines, pool, policy="adbs")
    rng = np.random.default_rng(6)
    r = Request(0, cfg_a.name, list(rng.integers(1, 512, 14)), 8)
    assert engines[cfg_a.name].lifetime_blocks(r) > va.quota
    mux.submit(r)
    stats = mux.run(max_ticks=100)
    assert len(stats.finished) == 1 and r.done
    assert va.quota >= engines[cfg_a.name].lifetime_blocks(r)
    assert pool.allocator.used == 0


def test_stall_escape_preemption_unblocks_deadlock():
    """Cross-batch growth overcommit can stall every active sequence
    at once (admission reserves nothing beyond the prompt); the stall
    escape must preempt one sequence so the rest finish, and the
    scheduler must restart the evicted request to completion."""
    cfg = configs.get_reduced("qwen2-7b")
    # quota 12 = 3 groups.  A (lifetime 3 groups) admitted first and
    # B (lifetime 2 groups, fits 12-4=8 headroom) in a later batch:
    # once A holds 2 groups and B holds 2, headroom is 0 with both
    # mid-lifetime → every decode tick rolls back.
    pool = UnifiedKVPool(1000, cfg.hd, dtype=jnp.float32)
    view = pool.register_model(cfg, 12)
    params = init_params(jax.random.PRNGKey(0), cfg, jnp.float32)
    eng = Engine(cfg, with_model_axis(params), view, max_slots=2)
    mux = MuxScheduler({cfg.name: eng}, pool, policy="adbs")
    rng = np.random.default_rng(1)
    ra = Request(0, cfg.name, list(rng.integers(1, 512, 14)), 28)
    rb = Request(1, cfg.name, list(rng.integers(1, 512, 14)), 8)
    mux.submit(ra)
    mux.submit(rb)
    stats = mux.run(max_ticks=400)
    assert len(stats.finished) == 2, [r.req_id for r in stats.finished]
    assert len(ra.output) == 28 and len(rb.output) == 8
    assert pool.allocator.used == 0
    # the preempted request's restart must be output-identical
    pool2 = UnifiedKVPool(1000, cfg.hd, dtype=jnp.float32)
    eng2 = Engine(cfg, with_model_axis(params),
                  pool2.register_model(cfg, 1000),
                  max_slots=2)
    for r in (ra, rb):
        q = Request(9, cfg.name, list(r.prompt), r.max_new_tokens)
        eng2.prefill([q])
        while not q.done:
            eng2.decode()
        assert r.output == q.output


@pytest.mark.parametrize("policy", ["adbs", "fcfs", "round_robin"])
def test_mux_policies_drain(policy):
    cfg = configs.get_reduced("qwen3-14b")
    pool = UnifiedKVPool(100_000, cfg.hd, dtype=jnp.float32)
    params = init_params(jax.random.PRNGKey(0), cfg, jnp.float32)
    view = pool.register_model(cfg, 100_000)
    eng = Engine(cfg, with_model_axis(params), view, max_slots=2)
    mux = MuxScheduler({cfg.name: eng},
                       pool, policy=policy)
    rng = np.random.default_rng(0)
    for i in range(3):
        mux.submit(Request(i, cfg.name,
                           list(rng.integers(1, cfg.vocab_size, 5)), 2))
    stats = mux.run(max_ticks=100)
    assert len(stats.finished) == 3
