"""Live serving front end (serving/frontend.py, router.py, metrics.py;
DESIGN.md §14): streaming determinism against the closed-loop driver,
cross-LLM routing strategies, client cancellation as a first-class
disposition, backpressure surfacing as stream errors, and the
Prometheus-style metrics layer."""
import asyncio
import json
import urllib.request

import numpy as np
import pytest

from repro.core.workload import synthesize
from repro.serving.driver import (ServeSession, TickCostModel,
                                  build_unit_from_specs,
                                  requests_from_workload, serve_requests)
from repro.serving.engine import Request
from repro.serving.faults import FaultPlan
from repro.serving.frontend import (ServingFrontend, StreamCancelled,
                                    StreamShed, serve_and_collect)
from repro.serving.metrics import MetricsServer, ServingMetrics
from repro.serving.router import (ExplicitTarget, LeastLoaded, RoundRobin,
                                  Router, WeightedByRate, family_of,
                                  make_strategy)

COST = TickCostModel()
NAMES = ["llm0", "llm1", "llm2"]


def _workload(max_rate=10.0, horizon=1.5):
    return synthesize(NAMES, alpha=2.1, max_rate=max_rate, horizon=horizon,
                      seed=0, mean_prompt=16, mean_output=6, max_len=128)


def _unit(wl, fused=True, **kw):
    return build_unit_from_specs(
        [(n, "qwen2-7b", wl.rates[n]) for n in NAMES],
        pool_blocks=8_000, max_slots=4, chunk_tokens=16, seed=0,
        policy="adbs", fused=fused, **kw)


def _build(wl, fused=True, **kw):
    u = _unit(wl, fused=fused, **kw)
    return u, requests_from_workload(wl, u.engines, seed=1)


def _ab_unit(**kw):
    return build_unit_from_specs(
        [("a", "qwen2-7b", 3.0), ("b", "qwen2-7b", 1.0)],
        pool_blocks=4_000, max_slots=4, chunk_tokens=16, seed=0,
        policy="adbs", fused=True, **kw)


def _reqs(n, model="a", plen=24, out=6, arrival=0.0):
    rng = np.random.default_rng(7)
    return [Request(i, model, list(rng.integers(1, 500, plen)), out,
                    arrival=arrival) for i in range(n)]


# ---------------------------------------------------------------------------
# streaming determinism: open-loop == closed-loop, bit for bit
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("fused", [True, False],
                         ids=["fused", "serial"])
def test_streams_bit_identical_to_closed_loop(fused):
    """The frontend drives the SAME ServeSession stepper as the
    closed-loop driver, so under the virtual clock every streamed
    token sequence equals the driver's Request.output exactly — for
    both the fused sweep and serial per-engine ticks."""
    wl = _workload()
    u1, r1 = _build(wl, fused=fused)
    rep1 = serve_requests([u1], r1, cost=COST)
    u2, r2 = _build(wl, fused=fused)
    fe = ServingFrontend([u2], r2, cost=COST)
    rep2, outs = serve_and_collect(fe)
    by_id = {r.req_id: r for r in r1}
    for r in r2:
        assert outs[r.req_id] == by_id[r.req_id].output == r.output
    assert rep1.ticks == rep2.ticks
    assert rep1.horizon == rep2.horizon
    assert rep1.aggregate.attainment == rep2.aggregate.attainment
    assert rep1.aggregate.finished == rep2.aggregate.finished


def test_frontend_rerun_reproducible():
    """Same trace + fresh units ⇒ the frontend reproduces itself
    bit-for-bit (open-loop streaming adds no hidden nondeterminism)."""
    wl = _workload(max_rate=6.0, horizon=1.0)
    runs = []
    for _ in range(2):
        u, reqs = _build(wl)
        rep, outs = serve_and_collect(ServingFrontend([u], reqs, cost=COST))
        runs.append((rep.ticks, rep.horizon,
                     {i: tuple(o) for i, o in outs.items()}))
    assert runs[0] == runs[1]


# ---------------------------------------------------------------------------
# router
# ---------------------------------------------------------------------------
def test_family_convention():
    assert family_of("llm-a@1") == "llm-a"
    assert family_of("solo") == "solo"


def _two_replica_units():
    ua = build_unit_from_specs([("m@0", "qwen2-7b", 2.0)],
                               pool_blocks=4_000, max_slots=2,
                               chunk_tokens=16, seed=0, policy="adbs")
    ub = build_unit_from_specs([("m@1", "qwen2-7b", 2.0)],
                               pool_blocks=4_000, max_slots=2,
                               chunk_tokens=16, seed=0, policy="adbs")
    return ua, ub


def test_router_strategies():
    ua, ub = _two_replica_units()
    r = Router([ua, ub], strategy=RoundRobin())
    # exact names short-circuit every strategy
    assert r.resolve("m@0") == "m@0"
    # round-robin alternates replicas deterministically
    assert [r.resolve("m") for _ in range(4)] == ["m@0", "m@1"] * 2
    with pytest.raises(KeyError):
        r.resolve("nope")
    # explicit refuses family fan-out
    r2 = Router([ua, ub], strategy=ExplicitTarget())
    with pytest.raises(KeyError):
        r2.resolve("m")
    # weighted: 3:1 planned rates → 3:1 long-run split (smooth WRR)
    r3 = Router([ua, ub], strategy=WeightedByRate({"m@0": 3.0, "m@1": 1.0}))
    picks = [r3.resolve("m") for _ in range(8)]
    assert picks.count("m@0") == 6 and picks.count("m@1") == 2
    # least-loaded follows queue depth
    r4 = Router([ua, ub], strategy=LeastLoaded())
    ua.submit(_reqs(1, model="m@0")[0])
    assert r4.resolve("m") == "m@1"
    for name in ("explicit", "round_robin", "weighted", "least_loaded"):
        assert make_strategy(name, {"m@0": 1.0}).name == name
    with pytest.raises(ValueError):
        make_strategy("bogus")


def test_router_refresh_follows_topology():
    ua, ub = _two_replica_units()
    r = Router([ua, ub], strategy=RoundRobin())
    assert sorted(r.families["m"]) == ["m@0", "m@1"]
    # a removed engine disappears from the view on refresh
    ub.remove_engine("m@1")
    r.refresh()
    assert r.families["m"] == ["m@0"]
    assert all(r.resolve("m") == "m@0" for _ in range(3))


# ---------------------------------------------------------------------------
# cancellation: the third disposition
# ---------------------------------------------------------------------------
def test_cancel_queued_and_prearrival():
    """Cancelling a queued request frees its queue slot immediately;
    cancelling before arrival means it is never submitted.  Both count
    as `cancelled`, and submitted = finished + shed + cancelled."""
    u = _ab_unit()
    reqs = _reqs(6, model="a") + _reqs(1, model="b", arrival=5.0)
    late = reqs[-1]
    session = ServeSession([u], reqs, cost=COST)
    assert session.cancel(late)          # pre-arrival: never submitted
    assert not session.cancel(late)      # idempotent
    session.step()                       # t=0 arrivals submitted
    queued = next(iter(u.queues["a"]), None)
    assert queued is not None
    assert session.cancel(queued)
    assert queued not in u.queues["a"] and queued.cancelled
    while session.step()[0] != "done":
        pass
    rep = session.report()
    agg = rep.aggregate
    assert agg.cancelled == 2
    assert agg.submitted == agg.finished + agg.shed + agg.cancelled
    assert rep.per_llm["a"].cancelled == 1
    assert rep.per_llm["b"].cancelled == 1
    assert "cancelled=2" in rep.summary()
    assert rep.to_json()["aggregate"]["cancelled"] == 2
    # cancelled ≠ shed: sheds stay zero here
    assert agg.shed == 0


def test_cancel_inflight_frees_kv_now():
    """Cancelling a RUNNING request evicts its sequence: slot, KV
    blocks and prefix refs return to the pool immediately, not at the
    request's would-have-been finish."""
    u = _ab_unit()
    (victim,), rest = _reqs(1, model="a", out=64), _reqs(3, model="b")
    session = ServeSession([u], [victim] + rest, cost=COST)
    for _ in range(200):
        session.step()
        if victim.first_token >= 0:
            break
    assert victim.first_token >= 0 and victim.finish < 0
    used_before = u.engines["a"].view.used
    assert used_before > 0
    assert session.cancel(victim)
    assert victim.cancelled and not victim.shed
    assert u.engines["a"].view.used < used_before
    while session.step()[0] != "done":
        pass
    # pool fully drains: nothing leaked by the mid-flight eviction
    assert all(v.used == 0 for v in u.pool.views.values())
    rep = session.report()
    assert rep.aggregate.cancelled == 1
    assert rep.aggregate.submitted == \
        rep.aggregate.finished + rep.aggregate.shed + rep.aggregate.cancelled


def test_cancel_terminates_stream():
    """frontend.cancel ends the request's stream with StreamCancelled
    (after ≥1 streamed token, so the cancel is genuinely mid-flight)."""
    u = _ab_unit()
    victim = _reqs(1, model="a", out=64)[0]
    fe = ServingFrontend([u], [victim], cost=COST)

    async def _main():
        stream = fe.stream(victim)
        serve_task = asyncio.ensure_future(fe.serve())

        async def consume():
            got = 0
            with pytest.raises(StreamCancelled):
                async for _tok in stream:
                    got += 1
                    if got == 2:
                        assert fe.cancel(victim)
            return got

        got = await consume()
        await serve_task
        return got

    assert asyncio.run(_main()) >= 2
    assert victim.cancelled
    assert fe.report().aggregate.cancelled == 1


# ---------------------------------------------------------------------------
# backpressure surfaces as stream errors
# ---------------------------------------------------------------------------
def test_shed_surfaces_as_stream_error():
    """Bounded-queue shedding terminates the affected streams with
    StreamShed carrying the reason — clients see backpressure, never a
    silent hang — and the metrics layer counts the stream errors."""
    u = _ab_unit(max_queue=1, shed_policy="reject")
    reqs = _reqs(6, model="a")
    metrics = ServingMetrics()
    fe = ServingFrontend([u], reqs, metrics=metrics, cost=COST)
    rep, outs = serve_and_collect(fe)
    sheds = {i: o for i, o in outs.items() if isinstance(o, StreamShed)}
    fins = {i: o for i, o in outs.items() if isinstance(o, list)}
    assert sheds and fins
    assert len(sheds) + len(fins) == len(reqs)
    assert all(o.reason == "queue_full" for o in sheds.values())
    assert rep.aggregate.shed == len(sheds)
    assert rep.aggregate.submitted == \
        rep.aggregate.finished + rep.aggregate.shed
    snap = {f["name"]: f for f in metrics.snapshot()["families"]}
    errs = sum(s["value"]
               for s in snap["mux_stream_errors_total"]["series"])
    assert errs == len(sheds)


# ---------------------------------------------------------------------------
# metrics layer
# ---------------------------------------------------------------------------
def test_metrics_registry_and_exposition():
    m = ServingMetrics()
    m.requests_submitted.inc(llm="a")
    m.requests_submitted.inc(2, llm="b")
    m.llm_qps.set(3.25, llm="a")
    for v in (0.004, 0.04, 0.4):
        m.ttft_seconds.observe(v, llm="a")
    m.reconfig_events.inc(kind="move")
    m.fault_events.inc(kind="engine_crash")
    text = m.registry.render()
    assert "# TYPE mux_requests_submitted_total counter" in text
    assert 'mux_requests_submitted_total{llm="b"} 2' in text
    assert 'mux_llm_qps{llm="a"} 3.25' in text
    assert 'mux_ttft_seconds_bucket{llm="a",le="+Inf"} 3' in text
    assert 'mux_ttft_seconds_count{llm="a"} 3' in text
    assert 'mux_reconfig_events_total{kind="move"} 1' in text
    assert 'mux_fault_events_total{kind="engine_crash"} 1' in text
    with pytest.raises(ValueError):
        m.requests_submitted.inc(-1, llm="a")


def test_metrics_http_endpoint():
    m = ServingMetrics()
    m.requests_submitted.inc(llm="a")
    m.log.emit(0.0, "submit", 1, llm="a")
    srv = MetricsServer(m, port=0).start()
    try:
        with urllib.request.urlopen(f"{srv.url}/metrics") as resp:
            body = resp.read().decode()
            assert resp.status == 200
            assert 'mux_requests_submitted_total{llm="a"} 1' in body
        with urllib.request.urlopen(f"{srv.url}/metrics.json") as resp:
            snap = json.loads(resp.read())
            assert any(f["name"] == "mux_requests_submitted_total"
                       for f in snap["families"])
        with urllib.request.urlopen(f"{srv.url}/events") as resp:
            assert "data: " in resp.read().decode()
        with urllib.request.urlopen(f"{srv.url}/nope") as resp:
            pytest.fail("404 expected")
    except urllib.error.HTTPError as e:
        assert e.code == 404
    finally:
        srv.close()
    srv.close()                          # idempotent (thread already down)


def test_serving_records_metrics_and_report_embeds_snapshot():
    """One armed run records the full taxonomy: lifecycle counters and
    latency histograms agree with the report's roll-ups, a fired fault
    lands in the fault counter, request-correlated structured logs
    exist, and the final snapshot rides in ServeReport (schema v2)."""
    u = _ab_unit()
    reqs = _reqs(4, model="a") + _reqs(2, model="b")
    metrics = ServingMetrics()
    rep = serve_requests([u], reqs, cost=COST, metrics=metrics,
                         faults=FaultPlan.parse("crash:a@0.02"))
    assert rep.to_json()["schema_version"] == 2
    assert rep.metrics is not None
    fams = {f["name"]: f for f in rep.metrics["families"]}
    fin = sum(s["value"]
              for s in fams["mux_requests_finished_total"]["series"])
    assert fin == rep.aggregate.finished
    ttft_n = sum(s["count"] for s in fams["mux_ttft_seconds"]["series"])
    assert ttft_n == rep.aggregate.finished
    tok = sum(s["value"] for s in fams["mux_tokens_total"]["series"])
    assert tok > 0
    faults = {s["labels"]["kind"]: s["value"]
              for s in fams["mux_fault_events_total"]["series"]}
    assert faults.get("engine_crash", 0) >= 1
    recov = {s["labels"]["llm"]: s["value"]
             for s in fams["mux_recoveries_total"]["series"]}
    assert recov.get("a", 0) >= 1
    # request-correlated structured log: every request has a submit
    # record, finished ones also a finish record
    for r in reqs:
        events = [rec.event for rec in metrics.log.for_request(r.req_id)]
        assert "submit" in events
        if r.finish >= 0:
            assert "finish" in events
    # full exposition renders without error and carries the live qps
    assert "mux_llm_qps" in metrics.registry.render()
