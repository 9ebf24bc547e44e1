"""The published-width build path of ``build_unit_from_specs`` at CPU
size: the pool's head_dim comes from the colocated attention models,
weights are built stacked (no second copy), an SSM state larger than
its rate share still fills every slot, the compile-cache helper's paths, and
kernel dispatch that never falls back silently."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.config import replace
from repro.core.estimator import LLMSpec
from repro.core.placement import Mesh, Placement
from repro.core.workload import RequestSpec, Workload
from repro.kernels import ops
from repro.launch import compile_cache
from repro.models.transformer import init_params
from repro.serving import driver
from repro.serving.driver import (TickCostModel, build_unit_from_specs,
                                  serve_workload, unit_head_dim)
from repro.serving.engine import Engine, Request, init_stacked_params
from repro.serving.kvcache import UnifiedKVPool
from repro.serving.reconfig import MigrationExecutor


def _patch_reduced(monkeypatch, **overrides):
    """Serve reduced configs with some fields replaced, per arch."""
    real = configs.get_reduced

    def get_reduced(arch):
        return replace(real(arch), **overrides.get(arch, {}))
    monkeypatch.setattr(driver.configs, "get_reduced", get_reduced)


def _home_env():
    """The caller's own HOME and TMPDIR, for a child process."""
    return {k: os.environ[k] for k in ("HOME", "TMPDIR") if k in os.environ}


def _trace(names, n_each=1, prompt=40, out=3):
    reqs = [RequestSpec(m, 0.01 * i, prompt, out)
            for i, m in enumerate(n for n in names for _ in range(n_each))]
    return Workload(rates={m: 1.0 for m in names}, requests=reqs,
                    horizon=1.0)


def test_unit_pool_takes_head_dim_from_its_attention_model(monkeypatch):
    _patch_reduced(monkeypatch, **{"phi-3-vision-4.2b": {"head_dim": 96}})
    unit = build_unit_from_specs(
        [("v", "phi-3-vision-4.2b", 1.0), ("s", "mamba2-2.7b", 0.5)],
        pool_blocks=4096, max_slots=2, chunk_tokens=16)
    assert unit.pool.head_dim == 96
    assert unit.pool.dtype == jnp.float32         # reduced keeps float32
    rep = serve_workload([unit], _trace(["v", "v"], prompt=40), seed=0,
                         cost=TickCostModel())
    assert rep.per_llm["v"].finished == rep.per_llm["v"].submitted == 2
    assert rep.aggregate.shed == 0
    assert unit.pool.allocator.free_blocks == unit.pool.n_head_blocks


def test_mixed_head_dims_in_one_unit_raise(monkeypatch):
    _patch_reduced(monkeypatch, **{"phi-3-vision-4.2b": {"head_dim": 96}})
    with pytest.raises(ValueError, match="one head_dim"):
        build_unit_from_specs([("v", "phi-3-vision-4.2b", 1.0),
                               ("q", "qwen2-7b", 1.0)], pool_blocks=4096)
    # attention-free models do not count toward the unit's head_dim
    ssm = configs.get_reduced("mamba2-2.7b")
    assert unit_head_dim([ssm]) == 64
    assert unit_head_dim([ssm, replace(configs.get_reduced("qwen2-7b"),
                                       head_dim=128)]) == 128


@pytest.mark.parametrize("rebalance", [False, True])
def test_ssm_state_above_its_rate_share_still_admits(monkeypatch, rebalance):
    # d_state 256: one sequence's state is 1 MiB, four times the
    # unpopular LLM's whole quota (32 head-blocks of 8 KiB), at startup
    # and after a re-plan re-splits the quotas by rate
    mamba = configs.get_reduced("mamba2-2.7b")
    _patch_reduced(monkeypatch, **{"mamba2-2.7b": {
        "ssm": replace(mamba.ssm, d_state=256)}})
    specs = [("a", "qwen2-7b", 100.0), ("s", "mamba2-2.7b", 1.0)]
    unit = build_unit_from_specs(specs, pool_blocks=512, max_slots=2,
                                 chunk_tokens=16)
    if rebalance:
        # the re-plan halves the popular LLM's rate
        plan = Placement([Mesh(0, 1, [LLMSpec(unit.engines[n].cfg, r)
                                      for n, r in (("a", 50.0), ("s", 1.0))])],
                         0.0)
        assert MigrationExecutor({0: unit}).rebalance_quotas(plan) > 0
    eng = unit.engines["s"]
    state = eng.ssm_state[:, 0].nbytes
    assert state >= 4 * eng.view.quota * unit.pool.head_block_bytes
    # the state lives in the engine's slots: it is charged nothing, so
    # neither the quota nor a full arena holds it back
    arena = unit.pool.allocator
    held = arena.alloc(arena.free_blocks)
    r0, r1 = (Request(i, "s", list(range(1, 41)), 3) for i in range(2))
    assert eng.lifetime_blocks(r0) == 0
    assert eng.can_admit(r1, pending_blocks=eng.lifetime_blocks(r0))
    arena.free(held, arena.n_blocks)
    # both slots serve at once
    for i in range(2):
        unit.submit(Request(i, "s", list(range(1, 41)), 3))
    busy = 0
    for _ in range(200):
        if not unit.pending():
            break
        unit.tick()
        busy = max(busy, sum(r is not None for r in eng.slots))
    assert busy == 2
    assert len(unit.stats.finished) == 2
    assert unit.pool.views["s"].used == 0


def test_stacked_weights_are_adopted_without_a_copy():
    cfg = configs.get_reduced("qwen2-7b")
    stacked = init_stacked_params(jax.random.PRNGKey(3), cfg, jnp.float32)
    eager = init_params(jax.random.PRNGKey(3), cfg, jnp.float32)
    for s, e in zip(jax.tree_util.tree_leaves(stacked),
                    jax.tree_util.tree_leaves(eager)):
        assert s.shape == (1,) + e.shape
        # one fused program vs op-by-op: the same draws up to rounding
        np.testing.assert_allclose(np.asarray(s[0]), np.asarray(e),
                                   rtol=1e-6, atol=1e-7)
    pool = UnifiedKVPool(1024, cfg.hd, dtype=jnp.float32)
    eng = Engine(cfg, stacked, pool.register_model(cfg, 1024))
    assert all(a is b for a, b in zip(jax.tree_util.tree_leaves(eng.params),
                                      jax.tree_util.tree_leaves(stacked)))


def test_compile_cache_dir_honours_env_else_fixed_repo_path(monkeypatch):
    monkeypatch.setenv(compile_cache.ENV_VAR, "/somewhere/else")
    assert compile_cache.compile_cache_dir() == "/somewhere/else"
    monkeypatch.delenv(compile_cache.ENV_VAR)
    first = compile_cache.compile_cache_dir()
    assert first == compile_cache.compile_cache_dir()
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert first == os.path.join(repo, ".jax_cache")


def test_enable_compile_cache_sets_no_path_when_env_is_set(monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv(compile_cache.ENV_VAR, "/from/env")
        assert compile_cache.enable_compile_cache() == "/from/env"
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv(compile_cache.ENV_VAR)
        path = compile_cache.enable_compile_cache()
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_auto_kernel_backend_refuses_a_non_tpu_backend():
    q = jnp.zeros((1, 4, 64), jnp.float32)
    pool = jnp.zeros((16, 16, 64), jnp.float32)
    table = jnp.zeros((1, 2), jnp.int32)
    lens = jnp.ones((1,), jnp.int32)
    with pytest.raises(RuntimeError, match="default backend is 'cpu'"):
        ops.paged_attention(q, pool, pool, table, lens, 0, n_kv=4)
    with pytest.raises(ValueError, match="backend must be one of"):
        ops.paged_attention(q, pool, pool, table, lens, 0, n_kv=4,
                            backend="pallas")


def test_importing_kernel_ops_initialises_no_backend():
    code = ("import repro.kernels.ops\n"
            "from jax._src import xla_bridge\n"
            "print(xla_bridge.backends_are_initialized())\n")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=120,
                       env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                            "JAX_PLATFORMS": "cpu", **_home_env()})
    assert r.returncode == 0, r.stderr[-2000:]
    assert r.stdout.strip() == "False"
