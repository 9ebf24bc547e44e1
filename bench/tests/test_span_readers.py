"""The readers of the program's span record (decode_host_ms,
tick_host_ms, step_traces_in_window) on a hand-built record and
``Ctx``: the tick alignment, and None where the record cannot be read."""
import pytest

import repro.serving.metrics as serving_metrics
from harness import Ctx, Tick
from metrics import decode_host_ms, step_traces_in_window, tick_host_ms
from repro.serving.driver import LogicalClock
from repro.serving.metrics import SpanRecord

READERS = (decode_host_ms, tick_host_ms, step_traces_in_window)
# tick starts relative to the window's opening: one before it, two in
# it, one after it (the drain)
TICK_T0 = (-0.5, 0.0, 0.4, 1.0)
SECONDS = 1.0


def record(capacity=256, trace_in=(2, 3)):
    """Four serving-loop steps, each: submit 1 ms, a tick holding one
    decode step (prep 2, launch 1, sync 10, commit 1 ms) and a harvest
    0.5 ms, then 0.5 ms of the loop after the tick.  A program traces
    in the launch of the ticks ``trace_in``."""
    rec = SpanRecord(capacity)
    clock = LogicalClock()
    rec.install(clock)
    h = rec.handle
    for k in range(len(TICK_T0)):
        with h("mux.step"):
            with h("mux.submit"):
                clock.advance(0.001)
            with h("mux.tick"):
                with h("mux.decode.x"):
                    for phase, dt in (("prep", 0.002), ("launch", 0.001),
                                      ("sync", 0.010), ("commit", 0.001)):
                        with h(f"mux.decode.x.{phase}"):
                            if phase == "launch" and k in trace_in:
                                rec.event("mux.trace.decode")
                            clock.advance(dt)
                with h("mux.harvest"):
                    clock.advance(0.0005)
            clock.advance(0.0005)
    return rec


def ctx(n_ticks=len(TICK_T0)):
    ticks = [Tick(t0, t0 + 0.0145, {"x": 1}, {}, 0, 1)
             for t0 in TICK_T0[:n_ticks]]
    return Ctx(seconds=SECONDS, setup_s=0.0, requests=[], ticks=ticks,
               steps=[], drain_end=1.5, open_loop=False, llms={},
               dtype_bytes=2)


@pytest.fixture
def use(monkeypatch):
    def put(rec):
        monkeypatch.setattr(serving_metrics, "SPANS", rec)
    return put


def test_readers_on_the_ticks_of_the_window(use):
    use(record())
    c = ctx()
    # host time of a decode step without its sync: 2 + 1 + 1 ms
    assert decode_host_ms.read(c) == pytest.approx(4.0, abs=1e-9)
    # the loop step less its decode step: submit 1 + harvest 0.5 + 0.5
    assert tick_host_ms.read(c) == pytest.approx(2.0, abs=1e-9)
    # traced in ticks 2 (in the window) and 3 (after it)
    assert step_traces_in_window.read(c) == 1


def test_no_trace_in_the_window_reads_zero(use):
    use(record(trace_in=(0, 3)))
    assert step_traces_in_window.read(ctx()) == 0


def test_tick_counts_that_differ_read_none(use):
    use(record())
    assert all(r.read(ctx(n_ticks=3)) is None for r in READERS)


def test_an_overflowed_record_reads_none(use):
    rec = record(capacity=30)                   # 4 steps need 4 x 9 + 2
    assert rec.overflowed
    use(rec)
    assert all(r.read(ctx()) is None for r in READERS)


def test_a_program_without_the_record_reads_none(monkeypatch):
    monkeypatch.delattr(serving_metrics, "SPANS")
    assert all(r.read(ctx()) is None for r in READERS)
