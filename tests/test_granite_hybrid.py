"""Granite-4.0-H (a Mamba-2/attention layer pattern, each layer ending in
a held-expert MoE FFN with a shared expert) through the serving
engine: chunked prefill and decode over the paged pool and the SSM
state against a plain float64 reference; the expert-parallel shares
of a layer against the uncut layer; a colocated unit built from specs.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.config import replace
from repro.models import moe as MoE
from repro.models.transformer import init_params
from repro.serving.driver import TickCostModel, build_unit_from_specs, \
    serve_workload
from repro.serving.engine import Engine, Request
from repro.serving.kvcache import UnifiedKVPool
from repro.core.workload import RequestSpec, Workload

from _weights import with_model_axis

CFG = configs.get_reduced("granite-4.0-h-small")


# ---------------------------------------------------------------------------
# the plain reference: numpy float64, the recurrence token by token
# ---------------------------------------------------------------------------
def _rms(x, w, eps):
    return x / np.sqrt(np.mean(x * x, -1, keepdims=True) + eps) * w


def _silu(x):
    return x / (1.0 + np.exp(-x))


def _mamba(h, p, i, cfg):
    sc = cfg.ssm
    S, di, N, H, P = h.shape[0], cfg.d_inner, sc.d_state, cfg.n_ssm_heads, \
        sc.head_dim
    zxbcdt = h @ p["in_proj"][i]
    z, xbc, dt = np.split(zxbcdt, [di, 2 * di + 2 * N], -1)
    pad = np.concatenate([np.zeros((sc.conv_kernel - 1, xbc.shape[1])), xbc])
    xbc = _silu(sum(pad[k:k + S] * p["conv_w"][i][k]
                    for k in range(sc.conv_kernel)) + p["conv_b"][i])
    xs, bm, cm = np.split(xbc, [di, di + N], -1)
    dt = np.logaddexp(0.0, dt + p["dt_bias"][i])                  # [S,H]
    a = -np.exp(p["a_log"][i])
    s = np.zeros((H, P, N))
    y = np.zeros((S, H, P))
    for t in range(S):
        x_t = xs[t].reshape(H, P)
        s = s * np.exp(dt[t] * a)[:, None, None] \
            + (dt[t][:, None] * x_t)[:, :, None] * bm[t][None, None, :]
        y[t] = s @ cm[t] + p["d_skip"][i][:, None] * x_t
    y = _rms(y.reshape(S, di) * _silu(z), p["gnorm"][i], cfg.rms_eps)
    return y @ p["out_proj"][i]


def _attention(h, p, i, cfg):
    S, H, KV, hd = h.shape[0], cfg.n_heads, cfg.n_kv_heads, cfg.hd
    q = (h @ p["wq"][i]).reshape(S, H, hd)
    k = np.repeat((h @ p["wk"][i]).reshape(S, KV, hd), H // KV, 1)
    v = np.repeat((h @ p["wv"][i]).reshape(S, KV, hd), H // KV, 1)
    s = np.einsum("qhd,khd->hqk", q, k) * cfg.attention_multiplier
    s = np.where(np.tril(np.ones((S, S), bool)), s, -np.inf)
    w = np.exp(s - s.max(-1, keepdims=True))
    w /= w.sum(-1, keepdims=True)
    return np.einsum("hqk,khd->qhd", w, v).reshape(S, -1) @ p["wo"][i]


def _moe(h, p, li, cfg):
    """Held experts' share (gates a softmax over the top-k router
    logits, 0 where a held expert is not chosen) plus the shared expert."""
    mc = cfg.moe
    logits = h @ p["router"][li]
    top = np.argsort(-logits, -1)[:, :mc.top_k]
    chosen = np.take_along_axis(logits, top, -1)
    gates = np.exp(chosen - chosen.max(-1, keepdims=True))
    gates /= gates.sum(-1, keepdims=True)
    out = _silu(h @ p["ws_gate"][li]) * (h @ p["ws_up"][li]) @ p["ws_down"][li]
    for e in range(mc.n_held):
        g = (gates * (top == mc.held_start + e)).sum(-1)
        y = _silu(h @ p["w_gate"][li][e]) * (h @ p["w_up"][li][e]) \
            @ p["w_down"][li][e]
        out = out + g[:, None] * y
    return out


def reference_logits(params, cfg, tokens):
    """Logits [S, vocab] of a causal forward over ``tokens``, float64."""
    p = jax.tree_util.tree_map(lambda a: np.asarray(a, np.float64), params)
    x = p["tok"]["embed"][np.asarray(tokens)] * cfg.embedding_multiplier
    ordinal = {"mamba": 0, "attention": 0}
    for li, kind in enumerate(cfg.layer_types):
        i = ordinal[kind]
        ordinal[kind] += 1
        h = _rms(x, p["layers"]["ln1"][li], cfg.rms_eps)
        mixed = _mamba(h, p["mamba"], i, cfg) if kind == "mamba" \
            else _attention(h, p["attn"], i, cfg)
        x = x + cfg.residual_multiplier * mixed
        h = _rms(x, p["layers"]["ln2"][li], cfg.rms_eps)
        x = x + cfg.residual_multiplier * _moe(h, p["layers"], li, cfg)
    x = _rms(x, p["tok"]["out_norm"], cfg.rms_eps)
    return x @ p["tok"]["embed"][:cfg.vocab_size].T / cfg.logits_scaling


# ---------------------------------------------------------------------------
# the engine, its step logits recorded
# ---------------------------------------------------------------------------
def _served_logits(dtype, chunk_tokens=32):
    """Serve three prompts (20, 40 and 70 tokens: the longer two cross
    chunk boundaries) and 5 output tokens each through one engine with
    chunked prefill; record every step's logits with the sequence
    position they predict from.  Returns (params, requests,
    [(request, position, logits)])."""
    params = init_params(jax.random.PRNGKey(3), CFG, jnp.float32)
    served = jax.tree_util.tree_map(lambda a: a.astype(dtype)
                                    if a.dtype == jnp.float32 and a.ndim > 1
                                    and a.shape[-1] != CFG.moe.n_experts
                                    else a, params)
    pool = UnifiedKVPool(4096, CFG.hd, dtype=dtype)
    view = pool.register_model(CFG, 4096)
    eng = Engine(CFG, with_model_axis(served), view, max_slots=4,
                 chunk_tokens=chunk_tokens)
    seen = []
    chunk_fn, decode_fn = eng._chunk_fn, eng._decode_fn

    def chunk(*args):
        out = chunk_fn(*args)
        reqs = [eng.slots[s] for s in sorted(eng._prefilling)]
        offs, clens = np.asarray(args[3]), np.asarray(args[4])
        for i, r in enumerate(reqs):
            seen.append((r, int(offs[i] + clens[i] - 1), np.asarray(
                out[2][i], np.float64)))
        return out

    def decode(*args):
        out = decode_fn(*args)
        logits, load = out[2]
        assert load.shape == (CFG.n_layers, CFG.moe.n_held)
        for i, r in enumerate(eng.export_decode_job().reqs):
            seen.append((r, len(r.prompt) + len(r.output) - 1,
                         np.asarray(logits[i], np.float64)))
        return out

    eng._chunk_fn, eng._decode_fn = chunk, decode
    rng = np.random.default_rng(0)
    reqs = [Request(i, CFG.name, rng.integers(1, CFG.vocab_size, n).tolist(),
                    5) for i, n in enumerate((20, 40, 70))]
    eng.prefill(reqs)
    while eng.has_prefill_work():
        eng.prefill([])
    while eng.has_decode_work():
        eng.decode()
    assert all(r.done for r in reqs)
    return params, reqs, seen


def _worst_error(dtype):
    params, reqs, seen = _served_logits(dtype)
    ref = {r.req_id: reference_logits(params, CFG, r.prompt + r.output[:-1])
           for r in reqs}
    scale = np.std(np.concatenate([v for v in ref.values()]))
    err = max(np.abs(lg - ref[r.req_id][pos]).max() for r, pos, lg in seen)
    assert {pos for r, pos, _ in seen if r is reqs[2]} >= {31, 63, 69, 73}
    return err / scale


# float32 engine against the float64 reference: the gap is float32
# rounding, reassociated by the chunked SSD scan and the grouped
# matmuls (5e-6 of the logits' standard deviation here); 1e-4 of it
# leaves room above that, and a bfloat16 engine (a relative step of
# 2^-8 in every weight and activation, and router choices flipped by
# it) lands far over (0.7).
TOLERANCE = 1e-4


def test_engine_chunked_prefill_and_decode_match_the_reference():
    assert _worst_error(jnp.float32) < TOLERANCE


def test_bfloat16_engine_fails_the_tolerance():
    assert _worst_error(jnp.bfloat16) > TOLERANCE


# ---------------------------------------------------------------------------
# expert-parallel shares
# ---------------------------------------------------------------------------
def test_held_shares_sum_to_the_uncut_layer():
    """Every chip's share of the layer (its held experts' part plus the
    shared expert), summed with the shared expert counted once, is the
    uncut layer; and each token's pairs land on exactly one share."""
    full = replace(CFG, moe=replace(CFG.moe, held_start=0, held_count=0))
    mc = full.moe
    p = MoE.init_moe(jax.random.PRNGKey(1), full, 2, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(2), (3, 7, full.d_model))
    uncut, _, load = MoE.moe_ffn_held(x, p, 1, full)
    n = mc.n_experts // CFG.moe.n_held
    parts, loads = [], []
    for c in range(n):
        lo, hi = c * CFG.moe.n_held, (c + 1) * CFG.moe.n_held
        share = replace(CFG, moe=replace(CFG.moe, held_start=lo))
        ps = {k: (v[:, lo:hi] if k in ("w_gate", "w_up", "w_down") else v)
              for k, v in p.items()}
        out, _, ld = MoE.moe_ffn_held(x, ps, 1, share)
        parts.append(out)
        loads.append(ld)
    shared = MoE.shared_expert(x, p, 1)
    total = sum(parts) - (n - 1) * shared
    np.testing.assert_allclose(total, uncut, rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.concatenate(loads), load)
    assert int(load.sum()) == 3 * 7 * mc.top_k


def test_held_layer_ignores_the_rest_of_the_batch():
    """No capacity: a token's output is the same alone and in a batch,
    and masked tokens route to no expert."""
    p = MoE.init_moe(jax.random.PRNGKey(1), CFG, 1, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(4), (32, CFG.d_model))
    batch, _, _ = MoE.moe_ffn_held(x, p, 0, CFG)
    alone, _, _ = MoE.moe_ffn_held(x[5:6], p, 0, CFG)
    np.testing.assert_allclose(batch[5:6], alone, rtol=1e-5, atol=1e-6)
    keep = jnp.arange(32) < 16
    _, _, load = MoE.moe_ffn_held(x, p, 0, CFG, keep)
    _, _, half = MoE.moe_ffn_held(x[:16], p, 0, CFG)
    np.testing.assert_array_equal(load, half)


# ---------------------------------------------------------------------------
# a unit from specs
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("chunk_tokens", [0, 16])
def test_unit_serves_granite_beside_mamba2(chunk_tokens):
    unit = build_unit_from_specs(
        [("g", "granite-4.0-h-small", 1.0), ("m", "mamba2-2.7b", 0.5)],
        pool_blocks=4096, max_slots=2, chunk_tokens=chunk_tokens)
    eng = unit.engines["g"]
    assert eng.ssm_state.shape[0] == CFG.n_ssm_layers == 3
    assert eng.view.group_size == CFG.n_attn_layers * CFG.n_kv_heads
    assert eng.chunk_tokens == (chunk_tokens or None)
    reqs = [RequestSpec(m, 0.01 * i, 40, 4)
            for i, m in enumerate(["g", "m", "g", "g"])]
    rep = serve_workload([unit], Workload(rates={"g": 1.0, "m": 0.5},
                                          requests=reqs, horizon=1.0),
                         seed=0, cost=TickCostModel())
    assert rep.per_llm["g"].finished == rep.per_llm["g"].submitted == 3
    assert rep.per_llm["m"].finished == 1
    assert unit.pool.allocator.free_blocks == unit.pool.n_head_blocks


def test_published_config_counts_follow_the_pattern():
    """40 layers, 36 Mamba-2 and 4 attention (layers 5, 15, 25, 35):
    32.2 B parameters, 8.8 B of them active a token ("32B-A9B")."""
    cfg = configs.get("granite-4.0-h-small")
    assert [i for i, t in enumerate(cfg.layer_types) if t == "attention"] \
        == [5, 15, 25, 35]
    assert (cfg.n_ssm_layers, cfg.n_attn_layers) == (36, 4)
    assert cfg.kv_bytes_per_token() == 2 * 4 * 8 * 128 * 2
    assert 32.1e9 < cfg.param_count() < 32.3e9
    assert 8.7e9 < cfg.active_param_count() < 8.9e9



@pytest.mark.parametrize("s, padded", [(64, False), (80, True)])
def test_ssd_pads_only_a_partial_chunk(s, padded):
    """The Mamba-2 mixer pads a sequence that ends mid-chunk (steps of
    dt = 0) and lowers a whole number of chunks, as mamba2-2.7b's chunk
    steps run, with no pad, as before padding existed.  Padded, it gives
    the outputs of a whole-chunk run over the same prefix and the final
    state of that run with the rest masked out."""
    from repro.models import mamba2 as M2
    cfg = configs.get_reduced("mamba2-2.7b")
    c = cfg.ssm.chunk_size
    p = M2.init_mamba2(jax.random.PRNGKey(0), cfg, 1, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, -(-s // c) * c,
                                                  cfg.d_model))

    def mixer(x, mask=None):
        return M2.mamba2_mixer(x, p, 0, cfg, return_cache=True,
                               length_mask=mask)
    text = jax.jit(mixer).lower(x[:, :s]).as_text()
    assert ("stablehlo.pad" in text) == padded
    y, st, _ = mixer(x[:, :s])
    mask = jnp.broadcast_to(jnp.arange(x.shape[1]) < s, x.shape[:2])
    y_whole, st_whole, _ = mixer(x, mask)
    np.testing.assert_allclose(y, y_whole[:, :s], rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(st, st_whole, rtol=1e-5, atol=1e-5)
