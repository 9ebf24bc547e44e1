"""muxlint static passes + runtime invariant sanitizer (PR 10).

One positive (violation detected) and one negative (idiomatic code
stays clean) fixture per static pass, the suppression machinery
(inline pragma with mandatory reason, reviewed baseline, stale-entry
failure), CLI exit codes, and the sanitizer's corruption detectors —
each planted corruption must raise ``SanitizeError`` naming the law
it broke, and a clean sanitized run must be bit-identical to an
unsanitized one (modulo the wall-clock diagnostic).
"""
import json
import textwrap

import numpy as np
import pytest

from repro.serving.driver import (LogicalClock, TickCostModel,
                                  ServeSession, build_unit_from_specs,
                                  serve_requests)
from repro.serving.engine import Request
from repro.serving.sanitize import (PoolSanitizer, SanitizeError,
                                    SchedulerSanitizer)
from tools.muxlint.core import (Source, all_passes, lint_paths,
                                load_baseline, match_baseline)
from tools.muxlint.__main__ import main as muxlint_main

COST = TickCostModel()


# ---------------------------------------------------------------------------
# static passes: one positive + one negative fixture each
# ---------------------------------------------------------------------------
def _lint(text, path="src/repro/serving/x.py", select=None):
    src = Source.parse(path, textwrap.dedent(text))
    passes = all_passes()
    if select:
        passes = {k: v for k, v in passes.items() if k in select}
    out = []
    for fn in passes.values():
        out.extend(f for f in fn(src) if not src.suppressed(f))
    return out


def test_layering_flags_upward_import():
    bad = _lint("from repro.serving.mux import MuxScheduler\n",
                path="src/repro/kernels/paged.py")
    assert [f.rule for f in bad] == ["layering"]
    assert "kernels -> serving" in bad[0].message


def test_layering_allows_declared_edges():
    assert not _lint("import repro.paging\nfrom repro.config import replace\n",
                     path="src/repro/kernels/paged.py")
    assert not _lint("from repro.core.estimator import estimate\n",
                     path="src/repro/serving/mux.py")
    # files outside repro/ (tools, tests) are unconstrained
    assert not _lint("from repro.launch.serve import main\n",
                     path="tools/muxlint/x.py")


def test_clock_flags_wallclock_in_serving():
    bad = _lint("""\
        import time
        def tick(self):
            return time.perf_counter()
        """)
    assert [f.rule for f in bad] == ["clock"]
    assert "perf_counter" in bad[0].message
    bad = _lint("from time import monotonic\n",
                path="src/repro/core/simulator.py")
    assert [f.rule for f in bad] == ["clock"]


def test_clock_exemptions():
    # a WallClock class is the one structural owner of wall time
    assert not _lint("""\
        import time
        class WallClock:
            def __call__(self):
                return time.perf_counter()
        """)
    # outside serving/core the clock pass does not apply
    assert not _lint("import time\nt = time.time()\n",
                     path="src/repro/launch/bench.py")


def test_rng_flags_unseeded_draws():
    bad = _lint("""\
        import numpy as np
        import random
        a = np.random.default_rng()
        b = np.random.uniform()
        c = random.random()
        """)
    assert [f.rule for f in bad] == ["rng"] * 3
    assert "explicit seed" in bad[0].message


def test_rng_allows_seeded_generators():
    assert not _lint("""\
        import numpy as np
        import jax
        rng = np.random.default_rng(0)
        x = rng.uniform()
        key = jax.random.PRNGKey(0)
        y = jax.random.uniform(key)
        """)


def test_jit_hazard_flags_host_escapes():
    bad = _lint("""\
        def decode_impl(q, lens):
            n = int(lens)
            q.item()
            if lens > 0:
                print(q)
            return q if lens else n
        """, select={"jit-hazard"})
    rules = sorted(f.message.split("`")[1] for f in bad)
    assert len(bad) == 5
    assert any(".item" in f.message or "item" in f.message for f in bad)
    assert any("retraces" in f.message for f in bad)
    assert any("ternary" in f.message for f in bad)
    assert rules  # each message names the offending construct


def test_jit_hazard_static_kwargs_and_plain_functions_ok():
    # kw-only params are the static-config convention — not traced
    assert not _lint("""\
        def step_impl(x, *, cfg):
            if cfg.fused:
                return x + 1
            return x
        """, select={"jit-hazard"})
    # host code that is never jitted is out of scope
    assert not _lint("""\
        def summarize(report):
            n = int(report.ticks)
            if n > 0:
                print(n)
            return n
        """, select={"jit-hazard"})


def test_jit_hazard_scopes_jax_jit_targets():
    bad = _lint("""\
        import jax
        def fwd(x):
            return int(x)
        f = jax.jit(fwd)
        """, select={"jit-hazard"})
    assert len(bad) == 1 and "concretizes" in bad[0].message


def test_dead_assert_flags():
    bad = _lint("""\
        def f(x, q):
            assert x == 1 or True
            assert x == x
            assert True
            assert (x, "message")
            assert (y := x) > 0
            assert q.pop() is not None
        """, path="src/repro/serving/y.py", select={"dead-assert"})
    assert len(bad) == 6
    msgs = " | ".join(f.message for f in bad)
    for frag in ("tautological", "self-comparison", "truthy constant",
                 "non-empty tuple", "walrus", "side-effecting"):
        assert frag in msgs, frag


def test_dead_assert_negative():
    assert not _lint("""\
        def f(x, items):
            assert x > 0, "positive"
            assert x == len(items)
            if x > 10:
                assert False, "unreachable"
        """, select={"dead-assert"})


# ---------------------------------------------------------------------------
# suppression machinery
# ---------------------------------------------------------------------------
def test_pragma_needs_a_reason():
    justified = "import time\nt = time.time()  # muxlint: ok[clock] probe\n"
    bare = "import time\nt = time.time()  # muxlint: ok[clock]\n"
    src = Source.parse("src/repro/serving/x.py", justified)
    f = next(iter(all_passes()["purity"](src)))
    assert src.suppressed(f)
    src = Source.parse("src/repro/serving/x.py", bare)
    f = next(iter(all_passes()["purity"](src)))
    assert not src.suppressed(f), "a pragma without a reason is inert"


def test_baseline_match_and_stale_split():
    src = Source.parse("src/repro/serving/x.py",
                       "import time\nt = time.time()\n")
    findings = list(all_passes()["purity"](src))
    hit = {"rule": "clock", "path": "src/repro/serving/x.py",
           "line_text": "t = time.time()", "why": "reviewed"}
    stale = {"rule": "clock", "path": "src/repro/serving/gone.py",
             "line_text": "t = time.time()", "why": "reviewed"}
    kept, stale_out = match_baseline(findings, [hit, stale])
    assert kept == [] and stale_out == [stale]


def test_baseline_rejects_missing_why(tmp_path):
    p = tmp_path / "baseline.json"
    p.write_text(json.dumps({"suppressions": [
        {"rule": "clock", "path": "a.py", "line_text": "x", "why": ""}]}))
    with pytest.raises(ValueError, match="why"):
        load_baseline(str(p))


def test_cli_exit_codes(tmp_path):
    clean = tmp_path / "clean.py"
    clean.write_text("x = 1\n")
    dirty = tmp_path / "dirty.py"
    dirty.write_text("assert True\n")
    assert muxlint_main([str(clean), "--no-baseline"]) == 0
    assert muxlint_main([str(dirty), "--no-baseline"]) == 1
    stale = tmp_path / "baseline.json"
    stale.write_text(json.dumps({"suppressions": [
        {"rule": "clock", "path": "nope.py", "line_text": "z = 1",
         "why": "obsolete"}]}))
    assert muxlint_main([str(clean), "--baseline", str(stale)]) == 2


def test_cli_nonzero_per_violation_class(tmp_path):
    """One planted violation per pass, each through the real CLI."""
    plants = {
        "kernels/bad_layer.py": "from repro.serving import mux\n",
        "serving/bad_clock.py": "import time\nt = time.time()\n",
        "serving/bad_jit.py": "def step_impl(x):\n    return int(x)\n",
        "serving/bad_assert.py": "def f(x):\n    assert x or True\n",
    }
    for rel, code in plants.items():
        root = tmp_path / rel.replace("/", "_")
        target = root / "src" / "repro" / rel
        target.parent.mkdir(parents=True)
        target.write_text(code)
        assert muxlint_main([str(target), "--root", str(root),
                             "--no-baseline"]) == 1, rel


def test_repo_src_is_clean():
    """The CI gate: the shipped tree has zero unsuppressed findings
    and no stale baseline entries."""
    import pathlib
    root = pathlib.Path(__file__).resolve().parent.parent
    assert muxlint_main(["src", "--root", str(root)]) == 0


def test_lint_paths_reports_parse_errors_nonfatal(tmp_path):
    (tmp_path / "broken.py").write_text("def f(:\n")
    (tmp_path / "fine.py").write_text("assert True\n")
    kept, _sup, errors = lint_paths([str(tmp_path)])
    assert len(errors) == 1 and "broken.py" in errors[0]
    assert any(f.rule == "dead-assert" for f in kept), \
        "a syntax error in one file must not mask findings in others"


# ---------------------------------------------------------------------------
# runtime sanitizer: clean runs pass, planted corruption is caught
# ---------------------------------------------------------------------------
def _unit(**kw):
    u = build_unit_from_specs(
        [("a", "qwen2-7b", 3.0), ("b", "qwen2-7b", 1.0)],
        pool_blocks=4_000, max_slots=4, chunk_tokens=16, seed=0,
        policy="adbs", fused=True, **kw)
    clock = LogicalClock()
    u.clock = clock
    for e in u.engines.values():
        e.clock = clock
    return u


def _requests(n_a=3, n_b=2, plen=16, out=3):
    rng = np.random.default_rng(5)
    reqs = [Request(i, "a", list(rng.integers(1, 500, plen)), out,
                    arrival=0.0) for i in range(n_a)]
    reqs += [Request(100 + i, "b", list(rng.integers(1, 500, plen)), out,
                     arrival=0.0) for i in range(n_b)]
    return reqs


def test_pool_sanitizer_clean_then_corrupted():
    from repro import configs
    from repro.serving.kvcache import BLOCK_TOKENS, UnifiedKVPool
    pool = UnifiedKVPool(2_048, 64)
    cfg = configs.get_reduced("qwen2-7b")
    view = pool.register_model(cfg, quota=2_048)
    assert view.append_tokens(0, BLOCK_TOKENS * 2)
    san = PoolSanitizer(pool)
    san.check("clean")

    pool.allocator.used += 3                     # refcount-weighted law
    with pytest.raises(SanitizeError, match="refcount-weighted"):
        san.check("corrupted")
    pool.allocator.used -= 3
    san.check("restored")

    view.used += 1                               # view charge law
    with pytest.raises(SanitizeError, match="recomputed"):
        san.check("view-corrupted")
    view.used -= 1


def test_pool_sanitizer_detects_a_group_off_its_tile_row():
    from repro import configs
    from repro.config import replace
    from repro.serving.kvcache import BLOCK_TOKENS, UnifiedKVPool
    pool = UnifiedKVPool(2_048, 64)
    cfg = replace(configs.get_reduced("qwen2-7b"), n_heads=8, n_kv_heads=8,
                  head_dim=64)
    view = pool.register_model(cfg, quota=2_048)
    assert view.group_size % 16 == 0
    assert view.append_tokens(0, BLOCK_TOKENS)
    PoolSanitizer(pool).check("aligned")
    view.seqs[0].bases[0] += 1
    with pytest.raises(SanitizeError, match="off a tile row"):
        PoolSanitizer(pool).check("misaligned")


def test_pool_sanitizer_detects_free_live_overlap():
    from repro import configs
    from repro.serving.kvcache import BLOCK_TOKENS, UnifiedKVPool
    pool = UnifiedKVPool(2_048, 64)
    cfg = configs.get_reduced("qwen2-7b")
    view = pool.register_model(cfg, quota=2_048)
    assert view.append_tokens(0, BLOCK_TOKENS)
    base = view.seqs[0].bases[0]
    # plant a live block on the free list (a double-free would do this)
    pool.allocator._free.insert(0, (base, base + 1))
    with pytest.raises(SanitizeError, match="free and live|covers"):
        PoolSanitizer(pool).check("double-free")


def test_scheduler_sanitizer_grant_algebra():
    u = _unit()
    san = SchedulerSanitizer(u)
    assert u.sanitizer is san, "attach installs the fault-report hook"
    san.check("clean")
    u._grant_debt += 5                           # phantom debt
    with pytest.raises(SanitizeError, match="grant algebra"):
        san.check("debt-corrupted")
    u._grant_debt -= 5
    san.check("restored")


def test_session_sanitizer_clean_run_and_parity():
    """A sanitized deterministic run completes with every tick checked
    and produces a bit-identical report (the sanitizer is a pure
    reader) — wall_s is the one real-wall-time diagnostic field."""
    reqs = _requests()
    reports = []
    for sanitize in (False, True):
        u = _unit()
        rep = serve_requests([u], [Request(r.req_id, r.model,
                                           list(r.prompt),
                                           r.max_new_tokens,
                                           arrival=r.arrival)
                                   for r in reqs],
                             cost=COST, warm=False, sanitize=sanitize)
        d = rep.to_json()
        d.pop("wall_s")
        reports.append(d)
    assert reports[0] == reports[1], \
        "sanitizer must not perturb scheduling"


def test_session_sanitizer_detects_silently_lost_request():
    u = _unit()
    sess = ServeSession([u], _requests(), cost=COST, warm=False,
                        sanitize=True)
    assert sess.sanitizer is not None
    status, _ = sess.step()                      # submits + first tick
    assert status == "tick"
    # vanish one held request: not finished/shed/cancelled, yet in no
    # queue, slot, or preempt buffer — the silent-loss bug class
    for q in u.queues.values():
        if q:
            q.popleft()
            break
    else:
        for eng in u.engines.values():
            for i, r in enumerate(eng.slots):
                if r is not None:
                    eng.slots[i] = None
                    break
    with pytest.raises(SanitizeError, match="SILENTLY LOST"):
        sess.sanitizer.check("after-theft")
