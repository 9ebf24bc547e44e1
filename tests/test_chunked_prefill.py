"""Chunked prefill (beyond-paper, Sarathi-style): correctness + the
interleaving property it exists for."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.models.transformer import init_params
from repro.serving.engine import Engine, Request
from repro.serving.kvcache import UnifiedKVPool
from repro.serving.mux import MuxScheduler

from _weights import with_model_axis


def _serve(cfg, params, prompts, chunk, max_new=4):
    pool = UnifiedKVPool(100_000, cfg.hd, dtype=jnp.float32)
    view = pool.register_model(cfg, 100_000)
    eng = Engine(cfg, with_model_axis(params), view, max_slots=len(prompts),
                 chunk_tokens=chunk)
    reqs = [Request(i, cfg.name, p, max_new)
            for i, p in enumerate(prompts)]
    eng.prefill(reqs)
    for _ in range(60):
        if eng.has_prefill_work():
            eng.prefill([])
        eng.decode()
        if all(r.done for r in reqs):
            break
    assert all(r.done for r in reqs)
    return [r.output for r in reqs]


@pytest.mark.parametrize("arch", ["qwen2-7b", "granite-moe-3b-a800m",
                                  "mamba2-2.7b"])
@pytest.mark.parametrize("chunk", [4, 16])
def test_chunked_matches_unchunked(arch, chunk):
    cfg = configs.get_reduced(arch)
    params = init_params(jax.random.PRNGKey(0), cfg, jnp.float32)
    rng = np.random.default_rng(0)
    prompts = [list(rng.integers(1, cfg.vocab_size, n)) for n in (23, 9)]
    ref = _serve(cfg, params, prompts, None)
    out = _serve(cfg, params, prompts, chunk)
    assert out == ref


def test_chunked_prefill_interleaves_decode():
    """The point of chunking: while LLM A's long prompt prefills chunk
    by chunk, LLM B's decode makes progress between chunks (with
    unchunked prefill, B's first decode waits for the whole prompt)."""
    cfg_a = configs.get_reduced("qwen2-7b")
    cfg_b = configs.get_reduced("musicgen-medium")
    pa = init_params(jax.random.PRNGKey(0), cfg_a, jnp.float32)
    pb = init_params(jax.random.PRNGKey(1), cfg_b, jnp.float32)
    pool = UnifiedKVPool(200_000, 64, dtype=jnp.float32)
    va = pool.register_model(cfg_a, 100_000)
    vb = pool.register_model(cfg_b, 100_000)
    eng_a = Engine(cfg_a, with_model_axis(pa), va, max_slots=1,
                   chunk_tokens=8)
    eng_b = Engine(cfg_b, with_model_axis(pb), vb, max_slots=1)
    mux = MuxScheduler({cfg_a.name: eng_a, cfg_b.name: eng_b}, pool,
                       policy="adbs")
    rng = np.random.default_rng(2)
    long_req = Request(0, cfg_a.name,
                       list(rng.integers(1, cfg_a.vocab_size, 64)), 2)
    short_req = Request(1, cfg_b.name,
                        list(rng.integers(1, cfg_b.vocab_size, 6)), 4)
    mux.submit(long_req)
    mux.submit(short_req)
    # drive ticks manually; B must produce tokens while A still prefills
    b_tokens_during_a_prefill = 0
    for _ in range(40):
        mux.tick()
        if eng_a.has_prefill_work() and short_req.output:
            b_tokens_during_a_prefill = len(short_req.output)
        if long_req.done and short_req.done:
            break
    assert long_req.done and short_req.done
    assert b_tokens_during_a_prefill > 0, \
        "decode of the colocated LLM must progress between prefill chunks"
    assert pool.allocator.used == 0
