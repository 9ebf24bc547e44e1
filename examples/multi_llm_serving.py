"""End-to-end driver: MuxServe's spatial-temporal multiplexing of three
LLM families (dense GQA, SSM, audio-decoder) on one shared pool, with
Poisson arrivals — comparing ADBS against FCFS on the same workload.

  PYTHONPATH=src python examples/multi_llm_serving.py
"""
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.serving.engine import Engine, Request, init_stacked_params
from repro.serving.kvcache import UnifiedKVPool
from repro.serving.mux import MuxScheduler

ARCHS = ["qwen2-7b", "mamba2-2.7b", "musicgen-medium"]
RATES = {"qwen2-7b": 3.0, "mamba2-2.7b": 1.0, "musicgen-medium": 0.5}


def build(policy: str):
    pool = UnifiedKVPool(300_000, 64, dtype=jnp.float32)
    engines = {}
    for i, a in enumerate(ARCHS):
        cfg = configs.get_reduced(a)
        params = init_stacked_params(jax.random.PRNGKey(i), cfg, jnp.float32)
        view = pool.register_model(cfg, 100_000)
        engines[cfg.name] = Engine(cfg, params, view, max_slots=2)
    return MuxScheduler(engines, pool, policy=policy), pool


def workload(seed=0, horizon=6.0, max_new=6):
    rng = np.random.default_rng(seed)
    reqs = []
    rid = 0
    for a in ARCHS:
        cfg = configs.get_reduced(a)
        n = rng.poisson(RATES[a] * horizon)
        for t in np.sort(rng.uniform(0, horizon, n)):
            plen = int(rng.integers(4, 20))
            reqs.append(Request(rid, cfg.name,
                                list(rng.integers(1, cfg.vocab_size, plen)),
                                max_new, arrival=float(t)))
            rid += 1
    reqs.sort(key=lambda r: r.arrival)
    return reqs


def serve(policy: str):
    mux, pool = build(policy)
    reqs = workload()
    t0 = time.perf_counter()
    idx = 0
    while idx < len(reqs) or mux.pending():
        now = time.perf_counter() - t0
        while idx < len(reqs) and reqs[idx].arrival <= now:
            mux.submit(reqs[idx])
            idx += 1
        if mux.pending():
            mux.tick()
    wall = time.perf_counter() - t0
    st = mux.stats
    lat = np.array([r.finish - (t0 + r.arrival) for r in st.finished])
    assert pool.allocator.used == 0
    return {"policy": policy, "wall": wall,
            "req_s": len(st.finished) / wall,
            "tok_s": (st.prefill_tokens + st.decode_tokens) / wall,
            "p99_lat": float(np.percentile(lat, 99)) if len(lat) else 0.0,
            "finished": len(st.finished), "total": len(reqs)}


def main():
    print(f"colocating {ARCHS} on one unified KV pool")
    for policy in ("adbs", "fcfs"):
        r = serve(policy)
        print(f"[{r['policy']:>5s}] {r['finished']}/{r['total']} reqs in "
              f"{r['wall']:.1f}s → {r['req_s']:.2f} req/s, "
              f"{r['tok_s']:.0f} tok/s, p99 latency {r['p99_lat']:.2f}s")


if __name__ == "__main__":
    main()
