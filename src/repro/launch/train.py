"""Training driver (CPU-scale on reduced configs; the same step is
lowered at production scale by dryrun.py).

  PYTHONPATH=src python -m repro.launch.train --arch qwen2-7b \
      --steps 200 --batch 8 --seq 64 --ckpt /tmp/ckpt
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.launch.compile_cache import enable_compile_cache
from repro.models.transformer import init_params
from repro.train import checkpoint as ckpt_lib
from repro.train.data import DataConfig, synth_batch
from repro.train.optimizer import AdamWConfig, init_state
from repro.train.train_step import make_train_step


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-7b")
    ap.add_argument("--full", action="store_true",
                    help="use the full config (needs real accelerators)")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--ckpt-every", type=int, default=100)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = configs.get(args.arch) if args.full\
        else configs.get_reduced(args.arch)
    opt = AdamWConfig(lr=args.lr, warmup_steps=max(args.steps // 20, 2),
                      total_steps=args.steps)
    params = init_params(jax.random.PRNGKey(args.seed), cfg, jnp.float32)
    state = init_state(params)
    start = 0
    if args.ckpt and ckpt_lib.latest_step(args.ckpt) is not None:
        tree, start, _ = ckpt_lib.restore(args.ckpt,
                                          {"p": params, "o": state})
        params, state = tree["p"], tree["o"]
        print(f"[train] resumed from step {start}")

    dcfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq,
                      global_batch=args.batch, seed=args.seed,
                      frontend_dim=cfg.frontend_dim,
                      n_prefix_tokens=cfg.n_prefix_tokens)
    step_fn = jax.jit(make_train_step(cfg, opt, remat=False,
                                      microbatches=args.microbatches))

    n_params = sum(np.prod(p.shape) for p in jax.tree.leaves(params))
    print(f"[train] {cfg.name}: {n_params / 1e6:.1f}M params, "
          f"{args.steps} steps @ batch {args.batch} × seq {args.seq}")
    t0 = time.perf_counter()
    for i in range(start, args.steps):
        toks, labels, prefix = synth_batch(dcfg, i)
        a = [params, state, jnp.asarray(toks), jnp.asarray(labels)]
        if prefix is not None:
            a.append(jnp.asarray(prefix))
        params, state, m = step_fn(*a)
        if (i + 1) % args.log_every == 0 or i == start:
            tps = args.batch * args.seq * (i + 1 - start)\
                / (time.perf_counter() - t0)
            print(f"[train] step {i + 1:5d}  loss={float(m['loss']):.4f}  "
                  f"lr={float(m['lr']):.2e}  "
                  f"gnorm={float(m['grad_norm']):.2f}  tok/s={tps:.0f}")
        if args.ckpt and (i + 1) % args.ckpt_every == 0:
            path = ckpt_lib.save(args.ckpt, {"p": params, "o": state},
                                 step=i + 1)
            print(f"[train] checkpoint → {path}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
