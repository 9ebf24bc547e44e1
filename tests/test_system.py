"""End-to-end system behaviour: the paper's pipeline from workload →
placement → multiplexed serving, at CPU scale with real engines, plus
simulator-vs-estimator coherence."""
import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.core.placement import place, place_spatial
from repro.core.simulator import simulate
from repro.core.workload import llama_config, synthesize
from repro.models.transformer import init_params
from repro.serving.engine import Engine, Request
from repro.serving.kvcache import UnifiedKVPool
from repro.serving.mux import MuxScheduler

from _weights import with_model_axis


def test_end_to_end_pipeline_simulated():
    """Workload → Alg.1 placement → ADBS simulation: MuxServe's
    aggregate throughput ≥ both baselines on a skewed workload (the
    paper's headline ordering, Fig. 5)."""
    cfgs = [llama_config("llama-7b", f"-{i}") for i in range(4)]
    rates = [16.0, 2.0, 0.8, 0.4]
    models = list(zip(cfgs, rates))
    wl = synthesize([c.name for c in cfgs], alpha=1.7, max_rate=16.0,
                    horizon=45.0, seed=11)
    wl.rates = dict(zip([c.name for c in cfgs], rates))

    mux_pl = place(models, n_devices=8, group_limit=32)
    sp_pl = place_spatial(models, n_devices=8)
    mux = simulate(mux_pl, wl, mode="spatial-temporal", policy="adbs")
    spatial = simulate(sp_pl, wl, mode="spatial", policy="adbs")
    temporal = simulate(mux_pl, wl, mode="temporal", policy="fcfs")

    assert mux.throughput >= 0.95 * spatial.throughput
    assert mux.throughput >= 0.95 * temporal.throughput
    assert mux.finished > 0


def test_end_to_end_real_engines_multiplexed():
    """Three reduced LLMs of different families colocated on one pool,
    scheduled by ADBS with interleaved arrivals — everything finishes,
    cache accounting returns to zero, per-model outputs are
    deterministic replays of solo serving."""
    archs = ["qwen2-7b", "mamba2-2.7b", "musicgen-medium"]
    cfgs = {a: configs.get_reduced(a) for a in archs}
    pool = UnifiedKVPool(300_000, 64, dtype=jnp.float32)
    engines = {}
    params = {}
    for i, a in enumerate(archs):
        cfg = cfgs[a]
        params[a] = init_params(jax.random.PRNGKey(i), cfg, jnp.float32)
        view = pool.register_model(cfg, 100_000)
        engines[cfg.name] = Engine(cfg, with_model_axis(params[a]), view,
                                   max_slots=2)
    mux = MuxScheduler(engines, pool, policy="adbs", adapt_every=4)

    rng = np.random.default_rng(0)
    reqs = []
    for i in range(9):
        a = archs[i % 3]
        cfg = cfgs[a]
        reqs.append(Request(i, cfg.name,
                            list(rng.integers(1, cfg.vocab_size, 6 + i % 5)),
                            max_new_tokens=3))
    for r in reqs:
        mux.submit(r)
    stats = mux.run(max_ticks=400)
    assert len(stats.finished) == 9
    assert pool.allocator.used == 0
    for a in archs:
        n = sum(1 for r in stats.finished if r.model == cfgs[a].name)
        assert n == 3, f"{a}: {n}/3 finished"

    # replay one request solo → identical output tokens
    target = reqs[0]
    cfg = cfgs[archs[0]]
    pool2 = UnifiedKVPool(100_000, 64, dtype=jnp.float32)
    v2 = pool2.register_model(cfg, 100_000)
    solo = Engine(cfg, with_model_axis(params[archs[0]]), v2, max_slots=1)
    q = Request(99, cfg.name, target.prompt, 3)
    solo.prefill([q])
    while not q.done:
        solo.decode()
    muxed = next(r for r in stats.finished if r.req_id == 0)
    assert muxed.output == q.output, "multiplexing must not change tokens"


def test_quota_pressure_backpressures_not_crashes():
    """Tiny pool: requests queue instead of failing; everything still
    completes eventually."""
    cfg = configs.get_reduced("qwen2-7b")
    group = cfg.n_layers * cfg.n_kv_heads  # head-blocks per token-block
    pool = UnifiedKVPool(group * 6, cfg.hd, dtype=jnp.float32)
    params = init_params(jax.random.PRNGKey(0), cfg, jnp.float32)
    view = pool.register_model(cfg, group * 6)
    eng = Engine(cfg, with_model_axis(params), view, max_slots=2)
    mux = MuxScheduler({cfg.name: eng}, pool, policy="adbs")
    rng = np.random.default_rng(1)
    for i in range(4):
        mux.submit(Request(i, cfg.name,
                           list(rng.integers(1, cfg.vocab_size, 8)), 2))
    stats = mux.run(max_ticks=500)
    assert len(stats.finished) == 4
    assert pool.allocator.used == 0
