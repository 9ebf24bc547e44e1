"""Host spans of the serving path (serving/metrics.py ``SPANS``): the
tree one serving loop records, its exact durations on the logical
clock, the trace events, the operator's counters, the record's bounds,
and the admission stamp they exposed."""
import time
from collections import Counter

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.config import replace
from repro.serving.driver import (LogicalClock, ServeSession, SLORef,
                                  TickCostModel, build_unit_from_specs)
from repro.serving.engine import (STEP_PHASES, TRACE_COUNTS, Engine,
                                  Request, init_stacked_params)
from repro.serving.kvcache import UnifiedKVPool
from repro.serving.metrics import SPANS, ServingMetrics, SpanRecord
from repro.serving.mux import MuxScheduler

COST = TickCostModel()
LAUNCH_S, COMMIT_S = 1.0, 0.25


def _reqs(models, n=2, plen=24, out=4):
    rng = np.random.default_rng(3)
    return [Request(i, m, list(rng.integers(1, 400, plen)), out)
            for i, m in enumerate(models * n)]


def _charge(session, eng):
    """Each step launch advances the logical clock by LAUNCH_S and each
    commit by COMMIT_S, so every span's duration is known exactly."""
    clock = session.clock

    def timed(fn, dt):
        def run(*a, **kw):
            clock.advance(dt)
            return fn(*a, **kw)
        return run
    eng._decode_fn = timed(eng._decode_fn, LAUNCH_S)
    eng._chunk_fn = timed(eng._chunk_fn, LAUNCH_S)
    eng.apply_decode_result = timed(eng.apply_decode_result, COMMIT_S)
    eng.apply_prefill_result = timed(eng.apply_prefill_result, COMMIT_S)


def _serve(session):
    while session.step()[0] != "done":
        pass


def _mixed_unit():
    return build_unit_from_specs(
        [("a", "qwen2-7b", 2.0), ("s", "mamba2-2.7b", 1.0)],
        pool_blocks=4_000, max_slots=2, chunk_tokens=16, seed=0)


def test_span_tree_of_a_serving_loop_under_the_logical_clock():
    u = _mixed_unit()
    session = ServeSession([u], _reqs(["a", "s"]), cost=COST)
    for eng in u.engines.values():
        _charge(session, eng)
    _serve(session)
    v = SPANS.view()
    name = [v.names[i] for i in v.name]
    dur = v.t1 - v.t0
    parent = lambda i: name[v.parent[i]] if v.parent[i] >= 0 else None  # noqa: E731
    steps = [i for i, n in enumerate(name) if n == "mux.step"]
    assert steps and all(parent(i) is None for i in steps)
    assert len(steps) == session.ticks + 1    # the last step finds it done
    ticks = [i for i, n in enumerate(name) if n == "mux.tick"]
    assert len(ticks) == u.stats.ticks == session.ticks
    for i, n in enumerate(name):
        if n in ("mux.submit", "mux.tick"):
            assert parent(i) == "mux.step"
        elif n in ("mux.harvest", "mux.quota") or n.startswith("mux.admit."):
            assert parent(i) == "mux.tick"
    roots = [i for i, n in enumerate(name)
             if n.split(".")[1] in ("decode", "prefill")
             and parent(i) == "mux.tick"]
    assert {name[i] for i in roots} == {
        "mux.decode.a", "mux.decode.s", "mux.prefill.a", "mux.prefill.s"}
    for r in roots:
        kids = [i for i in range(len(name)) if v.parent[i] == r]
        phases = [name[i][len(name[r]) + 1:] for i in kids]
        # the SSM state's scatter commits before the host syncs
        ssm = name[r].endswith(".s")
        assert phases == (["prep", "launch", "commit", "sync", "commit"]
                          if ssm else list(STEP_PHASES)), (name[r], phases)
        by = Counter()
        for i, p in zip(kids, phases):
            by[p] += dur[i]
            assert v.t0[r] <= v.t0[i] <= v.t1[i] <= v.t1[r]
        # the phases cover their step: only launch and commit take time
        assert by == pytest.approx({"launch": LAUNCH_S, "commit": COMMIT_S,
                                    "prep": 0.0, "sync": 0.0}, abs=1e-9)
        assert dur[r] == pytest.approx(sum(dur[i] for i in kids), abs=1e-9)
    # a tick takes exactly its steps' time; the session's clock charge
    # comes after it, inside the serving-loop step
    for t in ticks:
        inner = [r for r in roots if v.parent[r] == t]
        assert dur[t] == pytest.approx(len(inner) * (LAUNCH_S + COMMIT_S),
                                       abs=1e-9)
        assert dur[v.parent[t]] > dur[t]
    # trace events, if any program traced here, sit in a launch
    for i, n in enumerate(name):
        if n.startswith("mux.trace."):
            assert dur[i] == 0.0 and parent(i).endswith(".launch")


def test_a_fused_group_steps_under_one_span_with_the_same_phases():
    u = build_unit_from_specs([("a", "qwen2-7b", 1.0), ("b", "qwen2-7b", 1.0)],
                              pool_blocks=4_000, max_slots=2, chunk_tokens=16,
                              seed=0, fused=True)
    session = ServeSession([u], _reqs(["a", "b"]), cost=COST)
    _serve(session)
    v = SPANS.view()
    name = [v.names[i] for i in v.name]
    for root in ("mux.decode.a+b", "mux.prefill.a+b"):
        roots = [i for i, n in enumerate(name) if n == root]
        assert roots
        for r in roots:
            assert name[v.parent[r]] == "mux.tick"
            assert [name[i] for i in np.flatnonzero(v.parent == r)] == \
                [f"{root}.{p}" for p in STEP_PHASES]


def _odd_engine(name, max_slots=2):
    """An engine of a geometry no other test compiles (d_ff 136), so
    its step programs trace here."""
    cfg = replace(configs.get_reduced("qwen2-7b"), name=name, n_layers=1,
                  d_ff=136)
    pool = UnifiedKVPool(2_000, cfg.hd, dtype=jnp.float32)
    view = pool.register_model(cfg, 2_000)
    params = init_stacked_params(jax.random.PRNGKey(0), cfg, jnp.float32)
    return Engine(cfg, params, view, max_slots=max_slots, chunk_tokens=16)


def _trace_events():
    v = SPANS.view()
    return Counter(v.names[i][len("mux.trace."):] for i in v.name
                   if v.names[i].startswith("mux.trace."))


def test_trace_event_fires_once_per_new_program():
    eng = _odd_engine("odd")
    SPANS.install(LogicalClock())
    before = Counter(TRACE_COUNTS)

    def drain(req):
        eng.prefill([req])
        while eng.has_prefill_work():
            eng.prefill([])
        while eng.has_decode_work():
            eng.decode()

    drain(_reqs(["odd"], n=1)[0])
    first = _trace_events()
    assert first == Counter(TRACE_COUNTS) - before
    assert first["prefill_chunk"] == 1 and first["decode"] == 1
    drain(_reqs(["odd"], n=1)[0])         # the same shapes: no new program
    assert _trace_events() == first
    assert Counter(TRACE_COUNTS) - before == first


def test_span_seconds_and_step_traces_reach_the_exposition():
    eng = _odd_engine("b", max_slots=4)
    u = MuxScheduler({"b": eng}, eng.pool)
    m = ServingMetrics()
    before = Counter(TRACE_COUNTS)
    session = ServeSession([u], _reqs(["b"], n=3), cost=COST, metrics=m)
    _charge(session, eng)
    _serve(session)
    v = SPANS.view()
    name = [v.names[i] for i in v.name]
    launches = name.count("mux.decode.b.launch")
    assert launches > 0
    assert m.span_seconds.value(span="mux.decode.b.launch") == \
        pytest.approx(launches * LAUNCH_S, abs=1e-9)
    assert m.span_seconds.value(span="mux.tick") == pytest.approx(
        sum(v.t1[i] - v.t0[i] for i, n in enumerate(name) if n == "mux.tick"))
    traced = Counter(TRACE_COUNTS) - before
    assert traced and all(m.step_traces.value(step=s) == k
                          for s, k in traced.items())
    text = m.render()
    assert "# TYPE mux_span_seconds_total counter" in text
    assert 'mux_span_seconds_total{span="mux.decode.b.launch"}' in text
    assert 'mux_step_traces_total{step="decode"}' in text


def test_record_is_bounded_and_says_so():
    rec = SpanRecord(capacity=4)
    rec.install(LogicalClock())
    a, b = rec.handle("a"), rec.handle("b")
    assert rec.handle("a") is a                 # interned once
    with a:
        with b:
            rec.event("e")
    assert rec.view().parent.tolist() == [-1, 0, 1]
    with a:
        with b:                                 # the fifth entry
            pass
    assert rec.overflowed and rec.view() is None
    rec.install(LogicalClock())                 # a new session starts empty
    assert not rec.overflowed and rec.view().name.size == 0


def test_an_event_keeps_its_value_by_entry():
    """A valued event (an MoE decode step's expert counts) is kept by
    its entry, under the span it fired in, until the next session."""
    rec = SpanRecord(capacity=8)
    rec.install(LogicalClock())
    with rec.handle("step"):
        rec.event("plain")
        rec.event("counts", np.array([[2, 0], [1, 3]]))
    view = rec.view()
    assert list(view.values) == [2] and view.parent[2] == 0
    np.testing.assert_array_equal(view.values[2], [[2, 0], [1, 3]])
    rec.install(LogicalClock())
    assert rec.view().values == {}


def test_totals_by_name_outlive_the_record():
    """The per-name totals, which the operator's counters export, go
    on after the record of entries is full, and only closed spans
    count."""
    rec = SpanRecord(capacity=3)
    clock = LogicalClock()
    rec.install(clock)
    a, b = rec.handle("a"), rec.handle("b")
    with a:
        for _ in range(4):
            with b:
                clock.advance(1.0)
        rec.event("e")
        assert rec.overflowed and rec.view() is None
        assert list(rec.seconds) == [0.0, 4.0, 0.0]
        assert list(rec.count) == [0, 4, 1]
    assert rec.seconds[0] == 4.0 and rec.count[0] == 1
    with a:
        rec.install(clock)                      # a span open at the clear
        with b:
            clock.advance(2.0)
    assert list(rec.seconds) == [0.0, 2.0, 0.0]
    assert list(rec.count) == [0, 1, 0]
    assert rec.view().parent.tolist() == [-1]


def test_prefill_done_precedes_the_chunk_it_admits_under_the_wall_clock():
    """Admission is stamped before the step runs, not after it returns:
    with a chunk step that sleeps, the stamp must precede the step's
    launch span (same clock)."""
    u = build_unit_from_specs([("a", "qwen2-7b", 1.0)], pool_blocks=2_000,
                              max_slots=2, chunk_tokens=16, seed=0)
    eng = u.engines["a"]
    chunk = eng._chunk_fn

    def slow_chunk(*a):
        time.sleep(0.05)
        return chunk(*a)
    eng._chunk_fn = slow_chunk
    reqs = _reqs(["a"], n=1, plen=40, out=2)
    session = ServeSession([u], reqs, warm=False,
                           refs={"a": SLORef(0.0, 0.0)})
    _serve(session)
    v = SPANS.view()
    launch = [v.t0[i] for i in range(v.name.size)
              if v.names[v.name[i]] == "mux.prefill.a.launch"]
    assert len(launch) == 3                     # 40 tokens in chunks of 16
    assert 0.0 <= reqs[0].prefill_done <= launch[0]
    assert reqs[0].first_token > launch[-1] + 0.05
