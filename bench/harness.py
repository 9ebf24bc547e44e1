"""One run of one cell: set-up, the measured window, the readers, the
check.

Everything that belongs to one configuration, traffic mix, cell or
metric is found by name: ``bench/configs/<config>.json``,
``bench/traffic/<traffic>.json`` (read by ``traffic/gen.py``),
``bench/cells/<workload>.json`` and ``bench/metrics/<metric>.py``.
Nothing here branches on a cell.

A cell's own file, where it has one, holds what ``BENCHMARK.json``'s
entry has no key for: ``check``, the ``sample_tokens`` and
``sample_requests`` laid over the configuration's ``check`` for a cell
that needs a larger sample than the configuration's other cells, and
``about``, why.  Nothing else is read from it.

A rehearsal (``rehearse=True``: reduced sizes, any backend) closes its
window once every LLM has finished as many requests as it has slots,
or after ``seconds`` at the latest, so that its check has requests of
each LLM to compare however loaded the host is.
"""
from __future__ import annotations

import gc
import importlib
import json
import os
import shutil
import tempfile
import time
from dataclasses import dataclass
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
DRAIN_S = 60.0          # how long a due request may wait past the close
# the traced seconds end at the window's close: stopping the profiler
# stalls the host for seconds, which must not fall inside the window
TRACE_S = 5.0


class Refused(Exception):
    """The run cannot produce a result (no chip, unknown chip, bad
    cell); the harness exits non-zero and prints none."""


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str) -> dict:
    bench = _json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise Refused(f"no workload {name!r} in BENCHMARK.json")
    cell = dict(cells[name])
    own = os.path.join(BENCH, "cells", name + ".json")
    check = _json(own).get("check") if os.path.isfile(own) else None
    if check is not None:
        cell["check"] = check
    conf = next(c for c in bench["configs"] if c["name"] == cell["config"])
    wanted = lambda m: "workloads" not in m or name in m["workloads"]  # noqa: E731
    return {"cell": cell,
            "config": _json(os.path.join(ROOT, conf["file"])),
            "mix": _json(os.path.join(BENCH, "traffic",
                                      cell["traffic"] + ".json")),
            "end_to_end": [m for m in bench["end_to_end"] if wanted(m)],
            "per_layer": [m for m in bench["per_layer"] if wanted(m)]}


def setup_jax(rehearse: bool):
    """JAX with its persistent compilation cache at a fixed directory
    inside the checkout, whatever the environment names, so that only a
    checkout's first run compiles and two checkouts share nothing.  A
    rehearsal keeps no persistent cache."""
    if rehearse:
        os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
        import jax
        jax.config.update("jax_enable_compilation_cache", False)
        return jax
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    # the TPU runtime's own logs go under the run's temporary directory
    os.environ.setdefault("TPU_LOG_DIR",
                          os.path.join(tempfile.gettempdir(), "tpu_logs"))
    import jax
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return jax


def device_info(jax, chips: int, rehearse: bool) -> Tuple[dict, Optional[dict]]:
    """The devices JAX found and the chip's peaks.  A measurement run
    refuses a host without an accelerator, with fewer chips than the
    cell asks for, or of a kind ``peaks.json`` does not know."""
    from work import peaks
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if rehearse:
        return info, None
    if info["platform"] != "tpu":
        raise Refused(f"no accelerator: JAX found {len(devs)} x "
                      f"{info['platform']} ({info['kind']}); a measurement "
                      "run needs a TPU (--rehearse runs on the CPU)")
    if len(devs) < chips:
        raise Refused(f"the cell needs {chips} chips, JAX found {len(devs)}")
    try:
        return info, peaks(info["kind"])
    except KeyError as e:
        raise Refused(str(e)) from e


class CompileCount:
    """Programs traced and compiled, from JAX's compile events."""

    def __init__(self):
        self.traces = self.compiles = 0

    def __call__(self, event, duration, **kw):
        if event == "/jax/core/compile/jaxpr_trace_duration":
            self.traces += 1
        elif event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1


class GcWatch:
    """Python's garbage collections while armed: count and longest
    pause per generation.  A full collection walks every object set-up
    made (traced programs hold hundreds of thousands), so set-up's
    objects are frozen out of it before the window (``gc.freeze``)."""

    def __init__(self):
        self.count = [0, 0, 0]
        self.longest = [0.0, 0.0, 0.0]
        self._t0 = 0.0
        gc.callbacks.append(self)

    def __call__(self, phase, info):
        if phase == "start":
            self._t0 = time.perf_counter()
            return
        g = info["generation"]
        self.count[g] += 1
        self.longest[g] = max(self.longest[g], time.perf_counter() - self._t0)

    def close(self):
        gc.callbacks.remove(self)

    def __str__(self):
        return ", ".join(f"gen {g} {n} (longest {t * 1e3:.3f} ms)"
                         for g, (n, t) in enumerate(zip(self.count,
                                                        self.longest)))


# ---------------------------------------------------------------------------
# recording
# ---------------------------------------------------------------------------
@dataclass
class Tick:
    t0: float
    t1: float
    decode_by: Dict[str, int]
    prefill_by: Dict[str, int]
    kv_used: int
    kv_blocks: int


@dataclass
class Step:
    kind: str              # "decode" | "prefill"
    llm: str
    t0: float
    t1: float
    detail: list           # decode: context lengths; prefill: (off, n)


class Recorder:
    """Wraps the unit's tick and each engine's prefill and decode, on
    the instances, with host spans (``jax.profiler.TraceAnnotation``)
    and records of what each did."""

    def __init__(self, unit, clock: Callable[[], float]):
        from jax.profiler import TraceAnnotation
        self.clock = clock
        self.ticks: List[Tick] = []
        self.steps: List[Step] = []
        self.slots: Dict[int, set] = {}     # id(request) -> slots decoded in
        tick = unit.tick

        def traced_tick():
            t0 = clock()
            with TraceAnnotation("bench.tick"):
                tick()
            self.ticks.append(Tick(t0, clock(), dict(unit.tick_decode_by),
                                   dict(unit.tick_prefill_by),
                                   unit.pool.allocator.physical_used,
                                   unit.pool.n_head_blocks))
        unit.tick = traced_tick
        for name, eng in unit.engines.items():
            self._wrap(name, eng, TraceAnnotation)

    def _wrap(self, name, eng, TraceAnnotation):
        decode, prefill, export = eng.decode, eng.prefill, \
            eng.export_prefill_job
        exported: List = []

        def traced_decode(job=None):
            job = job or eng.export_decode_job()
            if job is None:
                return 0
            lens = eng.view.seq_lens(job.seq_ids).tolist()
            for r, slot in zip(job.reqs, job.slots):
                self.slots.setdefault(id(r), set()).add(slot)
            t0 = self.clock()
            with TraceAnnotation(f"bench.decode.{name}"):
                n = decode(job)
            self.steps.append(Step("decode", name, t0, self.clock(), lens))
            return n

        def traced_export():
            job = export()
            if job is not None:
                exported.append(job)
            return job

        def traced_prefill(reqs):
            exported.clear()
            t0 = self.clock()
            with TraceAnnotation(f"bench.prefill.{name}"):
                n = prefill(reqs)
            if exported:
                job = exported[-1]
                self.steps.append(Step(
                    "prefill", name, t0, self.clock(),
                    [(int(o), int(c)) for o, c in zip(job.offs, job.clens)]))
            return n

        eng.decode = traced_decode
        eng.prefill = traced_prefill
        eng.export_prefill_job = traced_export


@dataclass
class ReqRecord:
    rid: int
    model: str
    due: float                    # window-relative
    prompt: List[int]
    admitted: Optional[float]     # prefill dispatched, window-relative
    token_times: List[float]      # window-relative host stamps
    output: List[int]
    finished: bool
    shed: bool
    slots: Tuple[int, ...] = ()   # the engine slots it decoded in


@dataclass
class Ctx:
    """What the metric readers read (``bench/metrics/<name>.py``)."""
    seconds: float
    setup_s: float
    requests: List[ReqRecord]
    ticks: List[Tick]
    steps: List[Step]
    drain_end: float
    open_loop: bool
    llms: Dict[str, dict]
    dtype_bytes: int
    peak: Optional[dict] = None
    memory_peak_bytes: int = 0
    trace: Optional[dict] = None
    trace_window: Optional[Tuple[float, float]] = None

    def due_in_window(self) -> List[ReqRecord]:
        return [r for r in self.requests if 0.0 <= r.due < self.seconds]

    def in_trace(self, t0: float, t1: float) -> bool:
        return (self.trace_window is not None
                and self.trace_window[0] <= t0 and t1 <= self.trace_window[1])


def reader(name: str):
    return importlib.import_module(f"metrics.{name}").read


# ---------------------------------------------------------------------------
# the window
# ---------------------------------------------------------------------------
def serve(session, seconds: float, open_loop: bool, compiles: CompileCount,
          trace_dir=None, drain_s: float = DRAIN_S,
          until: Optional[Callable[[], bool]] = None):
    """Drive ``session.step`` open loop on the wall clock.  ``until``
    (rehearsals only) closes the window early, at the first step after
    which it holds; a trace then covers the whole window.  Returns
    (t_open, t_close, token stamps by request id, generator lateness,
    trace window, drain end, traces and compiles inside the window),
    times on the session's clock."""
    import jax
    from jax.profiler import TraceAnnotation
    clock = session.clock
    reqs = session.requests
    stamps: Dict[int, List[float]] = {id(r): [] for r in reqs}
    active: List = []
    late: List[float] = []

    def stamp(t):
        for r in list(active):
            got, have = len(r.output), len(stamps[id(r)])
            if got > have:
                stamps[id(r)].extend([t] * (got - have))
            elif got < have:                    # preempted: restarted
                del stamps[id(r)][got:]
            if r.finish >= 0 or r.shed or r.cancelled:
                active.remove(r)

    def step():
        now, idx = clock(), session.idx
        with TraceAnnotation("bench.step"):
            status, wait = session.step()
        for r in reqs[idx:session.idx]:
            late.append(now - r.arrival)
            active.append(r)
        stamp(clock())
        return status, wait

    t_open = 0.0
    if not open_loop:
        # a backlog: the window opens once every slot is decoding
        engines = list(session.engines.values())
        while not all(len(e.active_slots()) == e.max_slots
                      and not e.has_prefill_work() for e in engines):
            step()
        t_open = clock()
    t_close = t_open + seconds
    counted = [compiles.traces, compiles.compiles, None]
    t_trace = [t_close - min(TRACE_S, seconds / 2), t_close]
    if until is not None:
        t_trace[0] = t_open
    trace_win, ann = None, None
    while True:
        now = clock()
        if until is not None and now < t_close and until():
            t_close = t_trace[1] = now
        if trace_dir and ann is None and trace_win is None \
                and now >= t_trace[0]:
            jax.profiler.start_trace(trace_dir)
            ann = TraceAnnotation("bench.window")
            ann.__enter__()
            trace_win = [clock(), None]
        if ann is not None and now >= t_trace[1]:
            # the window closes here; the profiler's stop comes after
            ann.__exit__(None, None, None)
            trace_win[1] = clock()
            jax.profiler.stop_trace()
            ann = None
        if now >= t_close and counted[2] is None:
            counted[2] = {"traces": compiles.traces - counted[0],
                          "compiles": compiles.compiles - counted[1]}
        if now >= t_close:
            waiting = [r for r in reqs if r.arrival < t_close
                       and not stamps[id(r)] and not r.shed]
            if not open_loop or not waiting or now >= t_close + drain_s:
                break
        status, wait = step()
        if status in ("idle", "done"):
            nxt = [t for t in (t_close, *t_trace) if t > clock()]
            if status == "idle":
                nxt.append(clock() + wait)
            with TraceAnnotation("bench.wait"):
                time.sleep(max(0.0, min(nxt) - clock()) if nxt else 0.001)
    return t_open, t_close, stamps, late, trace_win, clock(), counted[2]


def records(session, stamps, t_open, slots) -> List[ReqRecord]:
    out = []
    for r in session.requests:
        out.append(ReqRecord(
            rid=r.req_id, model=r.model, due=r.arrival - t_open,
            prompt=list(r.prompt),
            admitted=(r.prefill_done - t_open if r.prefill_done >= 0
                      else None),
            token_times=[t - t_open for t in stamps[id(r)]],
            output=list(r.output), finished=r.finish >= 0, shed=r.shed,
            slots=tuple(sorted(slots.get(id(r), ())))))
    return out


def release(unit) -> None:
    """Drop every device buffer the unit holds: weights, arena, state."""
    for eng in unit.engines.values():
        eng.params = None
        eng.ssm_state = eng.conv_tail = None
    unit.pool.k = unit.pool.v = None


def memory_peak(jax) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()]
    return int(max(peaks or [0]))


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------
class Bench:
    """A cell's harness in one process: its files, JAX, the device and
    the compile counter.  ``window`` serves one seed and returns what
    the readers read; ``check`` compares that seed's served tokens with
    the reference.  ``bench/run.py`` makes one window and one check;
    ``bench/calibrate.py`` makes several in one process."""

    def __init__(self, name: str, rehearse: bool = False,
                 say: Callable[[str], None] = print):
        import unit as unit_mod
        spec = load_cell(name)
        self.name, self.say, self.rehearse = name, say, rehearse
        self.cell, self.mix = spec["cell"], spec["mix"]
        self.end_to_end, self.per_layer = spec["end_to_end"], \
            spec["per_layer"]
        self.config = unit_mod.effective_config(spec["config"], rehearse)
        self.llms = {llm["name"]: llm for llm in self.config["llms"]}
        self.jax = setup_jax(rehearse)
        self.device, self.peak = device_info(self.jax, self.cell["chips"],
                                             rehearse)
        say(f"[device] {self.device['platform']} {self.device['kind']} x "
            f"{self.device['count']}"
            + (" (rehearsal: reduced sizes, no device metrics)"
               if rehearse else ""))
        from jax import monitoring
        self.compiles = CompileCount()
        monitoring.register_event_duration_secs_listener(self.compiles)
        self.drain_s = DRAIN_S

    def window(self, seed: int, seconds: float, trace: bool,
               t_start: Optional[float] = None,
               fault: Optional[Callable] = None) -> Ctx:
        """Build the unit with ``seed``'s weights, warm it, serve
        ``seed``'s traffic for ``seconds`` (a rehearsal: until every LLM
        has finished as many requests as it has slots, ``seconds`` at
        the latest), read the device's memory peak, then free the unit.  ``fault`` (tests only) patches the
        unit after it is built, to break the timed path."""
        import unit as unit_mod
        from repro.serving.driver import ServeSession, SLORef
        from repro.serving.engine import Request
        from traffic import gen
        config, llms, say = self.config, self.llms, self.say
        names = list(llms)
        u = unit_mod.build(config, gen.rates(self.mix, names), seed)
        if fault is not None:
            fault(u)
        rows = unit_mod.warm(u, config["serving"]["max_slots"])
        open_loop = self.mix["arrival"] != "backlog"
        vocab = {n: unit_mod.arch_module(llms[n]["arch"]).dims(
            llms[n]["config"])["V"] for n in names}
        reqs = [r for r in gen.generate(
            self.mix, names, vocab, config["serving"]["context_tokens"],
            seed, seconds) if r.due < seconds]
        preq = [Request(r.rid, r.model, r.prompt, r.max_new, arrival=r.due)
                for r in reqs]
        say(f"[setup] warmed rows {rows} of {names}; {len(preq)} requests, "
            f"{sum(len(r.prompt) for r in preq)} prompt tokens, "
            f"{sum(r.max_new_tokens for r in preq)} output tokens at most")
        # set-up's objects leave the collector's walks; the session's
        # clock (the window's origin) starts after this
        gc.collect()
        gc.freeze()
        frozen = gc.get_freeze_count()
        session = ServeSession([u], preq, warm=False,
                               refs={n: SLORef(0.0, 0.0) for n in names})
        rec = Recorder(u, session.clock)
        trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace \
            else None
        watch = GcWatch()
        until = None
        if self.rehearse:
            slots = {n: e.max_slots for n, e in u.engines.items()}
            until = lambda: all(  # noqa: E731
                sum(r.finish >= 0 for r in preq if r.model == n) >= k
                for n, k in slots.items())
        try:
            try:
                t_open, t_close, stamps, late, twin, t_end, counts = serve(
                    session, seconds, open_loop, self.compiles, trace_dir,
                    self.drain_s, until)
            finally:
                watch.close()
                gc.unfreeze()
            if until is not None:
                early = t_close < t_open + seconds
                seconds = t_close - t_open
                say(f"[window] rehearsal closed at {seconds:.3f} s"
                    + ("" if early else ", the latest allowed"))
            shift = lambda t: t - t_open  # noqa: E731
            ctx = Ctx(
                seconds=seconds,
                # set-up: from the process's start to the window's opening
                setup_s=(session.clock.t0 + t_open - t_start
                         if t_start is not None else None),
                requests=records(session, stamps, t_open, rec.slots),
                ticks=[Tick(shift(t.t0), shift(t.t1), t.decode_by,
                            t.prefill_by, t.kv_used, t.kv_blocks)
                       for t in rec.ticks],
                steps=[Step(s.kind, s.llm, shift(s.t0), shift(s.t1),
                            s.detail) for s in rec.steps],
                drain_end=shift(t_end), open_loop=open_loop, llms=llms,
                dtype_bytes=self.jax.numpy.dtype(config["dtype"]).itemsize,
                peak=self.peak, memory_peak_bytes=memory_peak(self.jax))
            if trace:
                from devtrace import events, reduce
                if twin and twin[1] is not None:
                    ctx.trace_window = (shift(twin[0]), shift(twin[1]))
                ctx.trace = reduce(events(trace_dir))
                if ctx.trace is not None:
                    say(f"[trace] window {ctx.trace['window_s']:.6f} s, busy "
                        f"{ctx.trace['busy_s']:.6f} s, programs "
                        f"{ctx.trace['programs']}")
        finally:
            if trace_dir:
                shutil.rmtree(trace_dir, ignore_errors=True)
        from repro.serving.engine import TRACE_COUNTS
        say(f"[window] compiles in window {counts['compiles']}, traces "
            f"{counts['traces']}; engine step programs traced so far "
            f"{dict(TRACE_COUNTS)}")
        say(f"[window] garbage collections in the window and drain: {watch}; "
            f"{frozen} objects of set-up frozen out of them")
        in_win = [t for t in ctx.ticks if 0.0 <= t.t0 < seconds]
        say(f"[window] {len(in_win)} ticks, "
            f"{sum(sum(t.decode_by.values()) for t in in_win)} decode and "
            f"{sum(sum(t.prefill_by.values()) for t in in_win)} prefill "
            f"tokens, {sum(r.finished for r in ctx.requests)} requests "
            f"finished, longest tick "
            f"{max((t.t1 - t.t0 for t in in_win), default=0.0) * 1e3:.3f} ms")
        # where a stall of the host falls: each tick over half a second,
        # and the engine step inside it that took longest
        for t in in_win:
            if t.t1 - t.t0 > 0.5:
                inner = max((s for s in ctx.steps if t.t0 <= s.t0 < t.t1),
                            key=lambda s: s.t1 - s.t0, default=None)
                say(f"[window] stall: tick at {t.t0:.3f} s took "
                    f"{(t.t1 - t.t0) * 1e3:.3f} ms; longest step in it "
                    + (f"{inner.kind} {inner.llm} "
                       f"{(inner.t1 - inner.t0) * 1e3:.3f} ms"
                       if inner else "none"))
        if late:
            say(f"[window] generator lateness p50 "
                f"{np.percentile(late, 50) * 1e3:.3f} ms max "
                f"{max(late) * 1e3:.3f} ms over {len(late)} submits")
        # the program's state goes before the reference runs
        release(u)
        del session, rec, u
        gc.collect()
        return ctx

    def attempted(self, ctx: Ctx) -> Tuple[int, int]:
        """(attempted, failed): open loop, the requests due in the
        window, failed without a first token a minute past the close;
        a backlog, the requests admitted before the close."""
        if ctx.open_loop:
            due = ctx.due_in_window()
        else:
            due = [r for r in ctx.requests
                   if r.admitted is not None and r.admitted < ctx.seconds]
        failed = sum(1 for r in due
                     if r.shed or (ctx.open_loop and not r.token_times))
        return len(due), failed

    def metrics(self, ctx: Ctx, trace: bool) -> dict:
        values = {}
        for m in (self.per_layer if trace else self.end_to_end):
            v = reader(m["name"])(ctx)
            if v is not None:
                values[m["name"]] = {"value": v, "unit": m["unit"]}
        return values

    def check(self, ctx: Ctx, seed: int, control: bool = False) -> dict:
        """Per LLM: the widest gap of the sampled served tokens below
        the reference's best, its limit, the requests, tokens and engine
        slots the sample covers, and with ``control`` the fp8 control's
        reading on the same sample."""
        import check
        ck = {**self.config["check"], **self.cell.get("check", {})}
        out = {}
        for i, llm in enumerate(self.config["llms"]):
            picked = check.sample(ctx.requests, llm["name"], seed,
                                  ck["sample_tokens"], ck["sample_requests"])
            g = check.gaps(llm, i, seed, self.config["dtype"], picked,
                           control=control)
            out[f"gap.{llm['name']}"] = {
                "value": g["served"], "limit": ck["gap_limit"][llm["name"]],
                "tokens": g["tokens"], "requests": len(picked),
                "slots": len({s for r in picked for s in r.slots}),
                **({"control": g["control"]} if control else {})}
        return out


def passed(checks: dict) -> bool:
    return all(c["value"] is not None and c["limit"] is not None
               and c["value"] <= c["limit"] for c in checks.values())


def run(name: str, seed: int, seconds: float, trace: bool, t_start: float,
        rehearse: bool = False, fault: Optional[Callable] = None,
        say: Callable[[str], None] = print) -> dict:
    """One run of cell ``name``: the result line's object."""
    b = Bench(name, rehearse, say)
    ctx = b.window(seed, seconds, trace, t_start, fault)
    values = b.metrics(ctx, trace)
    if trace and b.peak is not None:
        from work import least_time, step_work
        bound = [0.0, 0.0]
        for st in ctx.steps:
            if st.kind == "decode" and ctx.in_trace(st.t0, st.t1):
                t = least_time(*step_work(b.llms[st.llm], "decode", st.detail,
                                          ctx.dtype_bytes), b.peak)
                bound[0] += t["compute_s"]
                bound[1] += t["memory_s"]
        say(f"[trace] traced decode steps need {bound[0]:.6f} s of compute "
            f"and {bound[1]:.6f} s of HBM traffic at the chip's peaks: bound "
            f"by {'memory' if bound[1] >= bound[0] else 'compute'}")
    attempted, failed = b.attempted(ctx)
    say(f"[window] {attempted} requests attempted, {failed} failed, "
        f"{sum(r.finished for r in ctx.requests)} finished, "
        f"{len(ctx.ticks)} ticks")
    t0 = time.perf_counter()
    checks = b.check(ctx, seed)
    say(f"[check] {time.perf_counter() - t0:.3f} s")
    device = dict(b.device, memory_peak_bytes=ctx.memory_peak_bytes)
    result = {"correct": passed(checks), "attempted": attempted,
              "failed": failed, "metrics": values, "device": device}
    if trace and ctx.trace is not None:
        device["busy_s"] = ctx.trace["busy_s"]
        device["window_s"] = ctx.trace["window_s"]
        result["breakdown"] = {"device_ops": ctx.trace["device_ops"],
                               "idle_gaps": ctx.trace["idle_gaps"]}
    result["checks"] = checks
    return result

