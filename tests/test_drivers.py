"""CLI drivers + engine↔Pallas integration."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.kernels import ops
from repro.models.transformer import init_params
from repro.serving import cache_ops
from repro.serving.engine import Engine, Request
from repro.serving.kvcache import UnifiedKVPool

from _weights import with_model_axis


def _run(args, timeout=480):
    return subprocess.run([sys.executable, *args], capture_output=True,
                          text=True, timeout=timeout,
                          env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                               "JAX_PLATFORMS": "cpu",
                               # tests run without a persistent cache
                               "JAX_ENABLE_COMPILATION_CACHE": "false",
                               **{k: os.environ[k] for k in ("HOME", "TMPDIR")
                                  if k in os.environ}})


def test_train_driver_cli():
    r = _run(["-m", "repro.launch.train", "--arch", "qwen2-7b",
              "--steps", "6", "--batch", "2", "--seq", "16",
              "--log-every", "3"])
    assert r.returncode == 0, r.stderr[-2000:]
    assert "loss=" in r.stdout


def test_serve_driver_cli():
    r = _run(["-m", "repro.launch.serve", "--archs", "qwen2-7b",
              "--rate", "1.0", "--horizon", "2", "--max-new", "3"])
    assert r.returncode == 0, r.stderr[-2000:]
    assert "finished" in r.stdout
    assert "SLO[" in r.stdout          # attainment report is part of CLI


def test_serve_driver_cli_placement_bridge(tmp_path):
    """launch/serve.py --placement runs a unit built from a
    core/placement.py plan end-to-end (the acceptance path)."""
    plan = {
        "total_tpt": 2.0,
        "meshes": [{"mesh_id": 0, "n_devices": 2, "specs": [
            {"name": "qwen2-7b#0", "arch": "qwen2-7b", "rate": 1.5,
             "tp": 2, "sm_frac": 0.5, "mean_prompt": 16, "mean_output": 4},
            {"name": "qwen2-7b#1", "arch": "qwen2-7b", "rate": 0.5,
             "tp": 2, "sm_frac": 0.5, "mean_prompt": 16, "mean_output": 4},
        ]}],
    }
    path = tmp_path / "plan.json"
    path.write_text(__import__("json").dumps(plan))
    r = _run(["-m", "repro.launch.serve", "--placement", str(path),
              "--policy", "adbs", "--fused", "--chunk-tokens", "16",
              "--horizon", "2", "--deterministic", "--mean-output", "4"])
    assert r.returncode == 0, r.stderr[-2000:]
    assert "placement plan" in r.stdout
    assert "fused group (2 engines)" in r.stdout
    assert "SLO[" in r.stdout


def test_engine_pool_matches_pallas_kernel():
    """The engine's XLA paged-attention path and the Pallas kernel
    (interpret mode) agree on a pool the engine actually filled."""
    cfg = configs.get_reduced("qwen2-7b")
    params = init_params(jax.random.PRNGKey(0), cfg, jnp.float32)
    pool = UnifiedKVPool(50_000, cfg.hd, dtype=jnp.float32)
    view = pool.register_model(cfg, 50_000)
    eng = Engine(cfg, with_model_axis(params), view, max_slots=2)
    rng = np.random.default_rng(0)
    reqs = [Request(i, cfg.name,
                    list(rng.integers(1, cfg.vocab_size, 10 + 3 * i)), 2)
            for i in range(2)]
    eng.prefill(reqs)

    seq_ids = [r._seq_id for r in reqs]
    table = jnp.asarray(view.block_table(seq_ids, 8))
    lens = jnp.asarray(view.seq_lens(seq_ids))
    q = jax.random.normal(jax.random.PRNGKey(1),
                          (2, cfg.n_heads, cfg.hd), jnp.float32)
    for layer in (0, cfg.n_layers - 1):
        ref = cache_ops.paged_decode_attention(
            q, pool.k, pool.v, table, lens, layer, cfg.n_kv_heads)
        pal = ops.paged_attention(q, pool.k, pool.v, table, lens, layer,
                                  n_kv=cfg.n_kv_heads,
                                  backend="interpret")
        np.testing.assert_allclose(np.asarray(ref), np.asarray(pal),
                                   rtol=1e-4, atol=1e-4)
