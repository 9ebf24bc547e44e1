"""Quickstart: serve one LLM through the MuxServe runtime.

Builds a reduced qwen2-7b, registers it on a unified KV pool, runs a
prefill + greedy decode through the paged-cache engine, and checks the
result against a plain full-recompute forward.

  PYTHONPATH=src python examples/quickstart.py
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.models.transformer import forward
from repro.serving.engine import Engine, Request, init_stacked_params
from repro.serving.kvcache import UnifiedKVPool


def main():
    cfg = configs.get_reduced("qwen2-7b")
    print(f"model: {cfg.name} ({cfg.n_layers}L d={cfg.d_model} "
          f"h={cfg.n_heads}/{cfg.n_kv_heads})")
    # weights carry a leading model axis (M=1): the engine's layout
    params = init_stacked_params(jax.random.PRNGKey(0), cfg, jnp.float32)

    # the unified head-wise KV pool (paper §3.4) + one model view
    pool = UnifiedKVPool(n_head_blocks=100_000, head_dim=cfg.hd,
                         dtype=jnp.float32)
    view = pool.register_model(cfg, quota=100_000)
    engine = Engine(cfg, params, view, max_slots=2)

    rng = np.random.default_rng(0)
    prompt = [int(t) for t in rng.integers(1, cfg.vocab_size, 12)]
    req = Request(req_id=0, model=cfg.name, prompt=prompt,
                  max_new_tokens=8)
    engine.prefill([req])
    while not req.done:
        engine.decode()
    print("prompt:", prompt)
    print("generated:", req.output)

    # sanity: greedy generation by full recompute must match exactly
    seq = list(prompt)
    one = jax.tree_util.tree_map(lambda a: a[0], params)
    for _ in range(8):
        logits, _ = forward(one, cfg, jnp.asarray([seq]), remat=False)
        seq.append(int(jnp.argmax(logits[0, -1])))
    assert req.output == seq[len(prompt):], "engine must match recompute"
    print("matches full-recompute greedy decoding ✓")
    print(f"pool blocks used at peak, now free: "
          f"{pool.allocator.free_blocks}/{pool.n_head_blocks}")


if __name__ == "__main__":
    main()
