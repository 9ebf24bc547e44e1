"""MuxScheduler — spatial-temporal multiplexing of colocated LLMs.

Implements the paper's ADBS (Alg. 3) over real ``Engine`` instances
sharing one ``UnifiedKVPool``:

  * prefill jobs are prioritized and selected round-robin across LLMs;
  * remaining capacity is filled with decode jobs round-robin;
  * per-LLM token-block quotas bound KV usage (fairness, Eq. 2's R);
  * quotas adapt periodically from low- to high-utilization LLMs.

On TPU the "fill remaining SMs" of the paper becomes fusing the jobs
of all colocated LLMs into the same scheduler tick (DESIGN.md §2).
With ``fused=True`` this runtime executes that fusion for real, in
BOTH phases: same-architecture engines form a ``FusedGroup`` whose
stacked weight tree is the *single* weight copy for the whole group
(members index it on the leading model axis — zero-copy), every tick
runs ONE jitted decode sweep, and — when the engines use chunked
prefill — ONE jitted prefill sweep advances every member's in-flight
prompt chunks.  The HBM reclaimed by de-duplicating weights is granted
to the unified pool as extra head-blocks (more admitted sequences —
the paper's memory-multiplexing argument).  Heterogeneous leftovers
(SSM engines keep their own scan, MoE its routed FFN, singleton
architectures) fall back to the serial per-engine path in the same
tick — off the same stacked buffers when they belong to a group.  With
``fused=False`` every engine steps back-to-back and the benefit of
colocation shows up only as higher aggregate tokens/s than
FCFS/temporal multiplexing (benchmarks/fig9).

``policy``: "adbs" (paper), "fcfs" (temporal multiplexing baseline),
"round_robin" (no prefill priority, fixed quotas).

``sm_frac``: per-engine compute shares from the placement optimizer
(Alg. 2's candidates).  When given, the scheduler *enforces* them —
the runtime twin of the paper's MPS SM-percentage assignment
(DESIGN.md §11): decode jobs are dispatched first under their planned
shares and prefill chunks fill the residual compute of the tick
(Fig. 4's dispatch order), every tick is metered per engine and per
phase (``tick_prefill_by`` / ``tick_decode_by``), and the
deterministic clock (``serving/driver.TickCostModel.tick_dt``)
charges each phase by ``tokens / (devices × effective_share)`` with
roofline flatness and oversubscription contention.  Without shares
the unit keeps the legacy temporal accounting (every job charged as
if it took the whole mesh in turn).
"""
from __future__ import annotations

import time
from collections import deque
from dataclasses import dataclass, field
from typing import Deque, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.serving.engine import (Engine, Request, jitted_step, step_spans,
                                  tree_bytes, unique_tree_bytes)
from repro.serving.faults import FaultInjector
from repro.serving.kvcache import UnifiedKVPool, fused_block_tables
from repro.serving.metrics import span

SHED_POLICIES = ("none", "reject", "deadline")

# host spans of the scheduler (serving/metrics.py)
_TICK, _QUOTA, _HARVEST = span("mux.tick"), span("mux.quota"), \
    span("mux.harvest")


@dataclass
class MuxStats:
    finished: List[Request] = field(default_factory=list)
    # deliberately dropped requests (DESIGN.md §12): backpressure,
    # deadline shedding, requeue-budget exhaustion, watchdog drains.
    # Each carries its ``shed_reason``; the driver rolls them up as
    # SLO misses with a visible disposition, never silent losses.
    shed: List[Request] = field(default_factory=list)
    # client-abandoned requests (DESIGN.md §14): the third disposition —
    # the server stayed healthy, the CLIENT walked away; reports keep
    # ``submitted = finished + shed + cancelled``
    cancelled: List[Request] = field(default_factory=list)
    prefill_tokens: int = 0
    decode_tokens: int = 0
    ticks: int = 0

    def throughput_reqs(self, horizon: float) -> float:
        return len(self.finished) / max(horizon, 1e-9)


class FusedGroup:
    """Colocated engines whose decode (and chunked-prefill) steps run
    as ONE jitted sweep.

    Engines land in the same group when ``Engine.fusion_signature()``
    matches (same layer/head geometry, vocab padding, param dtype,
    block-table width and chunk window).  Their weight trees are
    concatenated once on a leading model axis and the members *adopt*
    the stacked tree (``Engine.adopt_stacked``): each engine's private
    copy is freed and every step — the fused sweeps, serial prefill,
    the lone-active-engine fallback — indexes the one shared buffer.
    A fused group therefore pays ~1× weight memory (asserted by
    ``unique_tree_bytes`` in tests).  ``reclaimed_bytes`` is the
    second full weight copy fused serving paid BEFORE de-duplication
    (private trees alongside the stacked cache — the "known cost" this
    design removes); the scheduler grants exactly those bytes to the
    pool as extra head-blocks, so a fused deployment's HBM budget is
    unchanged while the former duplicate-copy waste now admits
    sequences.  Relative to *serial* serving the grant is additional
    arena, sized only by what fusion used to waste.
    """

    def __init__(self, engines: List[Engine],
                 names: Optional[List[str]] = None):
        assert len(engines) >= 2
        sigs = {e.fusion_signature() for e in engines}
        assert len(sigs) == 1 and None not in sigs, \
            "fused group requires matching fusion signatures"
        self.engines = engines
        self.names = list(names) if names else [e.cfg.name for e in engines]
        self.cfg = engines[0].cfg
        self.cfg_key = engines[0].cfg_key
        self.max_blocks = engines[0].max_blocks
        self.chunk_tokens = engines[0].chunk_tokens
        # fixed row count: padding every tick to max_slots keeps the
        # jitted sweeps at ONE compilation per group (a shrinking
        # active-row count would otherwise re-trace the whole stacked
        # forward for every distinct batch size)
        self.rows = max(e.max_slots for e in engines)
        # zero-copy adoption: concatenate the members' [1, ...] stacks
        # into the group tree, then point every member at it — the
        # per-engine trees are freed, leaving exactly ONE weight copy
        member_bytes = sum(tree_bytes(e.params) for e in engines)
        self.params = jax.tree_util.tree_map(
            lambda *xs: jnp.concatenate(xs, axis=0),
            *[e.params for e in engines])
        for m, e in enumerate(engines):
            e.adopt_stacked(self.params, m)
        self.reclaimed_bytes = member_bytes
        # pool grant bookkeeping, set by the scheduler when it converts
        # reclaimed_bytes into head-blocks: total blocks grown into the
        # pool and the per-member quota share — dissolve() needs both
        # to hand the grant back (live reconfiguration, DESIGN.md §10)
        self.granted_blocks = 0
        self.quota_share = 0
        self._decode_fn = jitted_step("fused_decode", self.cfg_key)
        self._prefill_fn = (jitted_step("fused_prefill_chunk", self.cfg_key)
                            if self.chunk_tokens else None)
        name = "+".join(self.names)
        self.decode_spans = step_spans("decode", name)
        self.prefill_spans = step_spans("prefill", name)

    def weight_bytes(self) -> int:
        """Live weight bytes of the whole group (de-duplicated)."""
        return unique_tree_bytes([e.params for e in self.engines])

    def dissolve(self) -> None:
        """Undo the zero-copy adoption: every member re-materializes a
        private ``[1, ...]`` slice of its weights so the shared stacked
        tree can be dropped.  The scheduler pairs this with revoking
        the quota shares and shrinking the pool by ``granted_blocks``
        (``MuxScheduler.dissolve_fused_groups``)."""
        for e in self.engines:
            e.materialize_private()

    def decode(self, jobs) -> Dict[str, int]:
        """Run one fused decode step.  ``jobs`` is aligned with
        ``self.engines`` (None where an engine has no decode work this
        tick — its rows are padded and masked, since the stacked param
        tree always carries every group member).  Returns committed
        #tokens per member name (the scheduler's per-phase share
        metering needs the split, not just the sum)."""
        pool = self.engines[0].pool
        rows = self.rows
        step, prep, launch, sync, commit = self.decode_spans
        with step:
            with prep:
                toks = np.zeros((len(self.engines), rows), np.int32)
                for m, job in enumerate(jobs):
                    if job is not None:
                        toks[m, :len(job)] = job.last_tok
                tables, lens = fused_block_tables(
                    [(eng.view, job.seq_ids if job is not None else [])
                     for eng, job in zip(self.engines, jobs)],
                    rows, self.max_blocks)
                for eng, job in zip(self.engines, jobs):
                    if job is not None:
                        eng.reserve_decode(job)
                args = (jnp.asarray(toks), jnp.asarray(lens), pool.k,
                        pool.v, jnp.asarray(tables))
            with launch:
                pool.k, pool.v, logits = self._decode_fn(self.params, *args)
            with sync:
                nxt = np.asarray(jnp.argmax(logits, axis=-1))  # [M, rows]
            with commit:
                per: Dict[str, int] = {}
                for m, (eng, job) in enumerate(zip(self.engines, jobs)):
                    if job is not None:
                        per[eng.cfg.name] = eng.apply_decode_result(
                            job, nxt[m, :len(job)])
        return per

    def prefill(self, jobs) -> Dict[str, int]:
        """Run one fused chunked-prefill sweep: every member's in-flight
        prompt chunks advance by one window in ONE jitted step.
        ``jobs`` is aligned with ``self.engines`` (None where a member
        has nothing prefilling — its rows are padded: −1 tables drop
        the KV writes, zero chunk lengths mark the logits dead).
        Returns #prompt tokens processed per member name."""
        pool = self.engines[0].pool
        rows, C, M = self.rows, self.chunk_tokens, len(self.engines)
        step, prep, launch, sync, commit = self.prefill_spans
        with step:
            with prep:
                toks = np.zeros((M, rows, C), np.int32)
                offs = np.zeros((M, rows), np.int32)
                clens = np.zeros((M, rows), np.int32)
                tables = np.full((M, rows, self.max_blocks), -1, np.int32)
                for m, (eng, job) in enumerate(zip(self.engines, jobs)):
                    if job is None:
                        continue
                    b = len(job)
                    toks[m, :b] = job.toks
                    offs[m, :b] = job.offs
                    clens[m, :b] = job.clens
                    tables[m, :b] = eng.view.block_table(job.seq_ids,
                                                         self.max_blocks)
                args = (jnp.asarray(toks), jnp.asarray(offs),
                        jnp.asarray(clens), pool.k, pool.v,
                        jnp.asarray(tables))
            with launch:
                pool.k, pool.v, logits = self._prefill_fn(self.params, *args)
            with sync:
                nxt = np.asarray(jnp.argmax(logits, axis=-1))  # [M, rows]
            with commit:
                per: Dict[str, int] = {}
                for m, (eng, job) in enumerate(zip(self.engines, jobs)):
                    if job is not None:
                        per[eng.cfg.name] = eng.apply_prefill_result(
                            job, nxt[m, :len(job)])
        return per


# backwards-compatible name (the group now fuses prefill too)
FusedDecodeGroup = FusedGroup


class MuxScheduler:
    """Paper Alg. 3 (ADBS) over real engines.

    Simulator counterpart: ``core/simulator.UnitSim`` runs the same
    policy branches against cost-model latencies — each branch below
    names the Alg. 3 step it implements so sim/runtime divergence is
    auditable (the sim's version lives in
    ``UnitSim._round_spatial_temporal``).

    ``clock``: zero-argument callable supplying the current time for
    request timestamps (``Request.first_token`` / ``finish`` /
    ``prefill_done``).  Defaults to wall time; a deterministic driver
    (``serving/driver.py``) passes a logical clock it advances itself,
    which makes SLO accounting reproducible across machines.
    """

    def __init__(self, engines: Dict[str, Engine], pool: UnifiedKVPool,
                 policy: str = "adbs", adapt_every: int = 16,
                 fused: bool = False, clock=None,
                 sm_frac: Optional[Dict[str, float]] = None,
                 injector: Optional[FaultInjector] = None,
                 max_queue: Optional[int] = None,
                 shed_policy: str = "none",
                 requeue_budget: int = 3, retry_budget: int = 3):
        assert shed_policy in SHED_POLICIES, shed_policy
        assert max_queue is None or max_queue > 0, max_queue
        self.engines = engines
        self.pool = pool
        self.policy = policy
        self.adapt_every = adapt_every
        # graceful degradation (DESIGN.md §12) — all default-off:
        #   injector        fault plan polled at every tick
        #   max_queue       per-LLM admission-queue bound (backpressure
        #                   sheds NEW arrivals when full; requeues from
        #                   preemption/recovery bypass it — in-flight
        #                   work is never dropped by the bound)
        #   shed_policy     "none" | "reject" (backpressure only) |
        #                   "deadline" (also shed queue heads whose
        #                   Request.deadline has passed)
        #   requeue_budget  teardowns one request may survive before it
        #                   is shed instead of requeued
        #   retry_budget    consecutive transiently-failed ticks before
        #                   a transient window escalates to crash
        #                   recovery
        self.injector = injector
        self.max_queue = max_queue
        self.shed_policy = shed_policy
        self.requeue_budget = requeue_budget
        self.retry_budget = retry_budget
        # recovery/degradation events of this unit, drained (and clock-
        # charged in deterministic mode) by serving/driver.py
        self.fault_events: List[dict] = []
        self._down: set = set()                  # transient-down engines
        self._transient_ticks: Dict[str, int] = {}
        self.queues: Dict[str, Deque[Request]] = {
            name: deque() for name in engines}
        self._names = list(engines)
        self._prefill_rr = 0
        self._decode_rr = 0
        self.stats = MuxStats()
        # per-engine compute shares (placement sm_frac, DESIGN.md §11).
        # Shares are *enforced* only when the caller supplies them —
        # hand-built units keep the legacy temporal accounting, and
        # fcfs (the temporal-multiplexing baseline) never enforces: a
        # baseline that serves one LLM at a time has no shares to hold.
        self.sm_frac: Dict[str, float] = {n: 1.0 for n in engines}
        if sm_frac:
            self.sm_frac.update({n: float(f) for n, f in sm_frac.items()
                                 if n in engines})
        self.enforce_shares = sm_frac is not None and policy != "fcfs"
        # per-tick, per-engine phase metering (reset every tick): which
        # engines prefilled/decoded how many tokens — the deterministic
        # clock's share-aware tick cost reads these
        self.tick_prefill_by: Dict[str, int] = {}
        self.tick_decode_by: Dict[str, int] = {}
        # one time domain for every timestamp: the scheduler's clock is
        # pushed onto all engines so Request timelines are coherent
        self.clock = clock if clock is not None else time.perf_counter
        for eng in engines.values():
            eng.clock = self.clock
        # token-emission hook (serving/frontend.py) — see ``set_emit``
        self.emit = None
        # fused multi-LLM tick (DESIGN.md §2): group colocated engines
        # by fusion signature; members adopt ONE stacked weight tree
        # per group (zero-copy) for the lifetime of the scheduler, and
        # the HBM the de-dup reclaims is granted to the pool as extra
        # head-blocks (split across the group's views as quota).  fcfs
        # (the temporal baseline) never reaches the fused tick — don't
        # regroup its weights for it.
        self.fused = fused and policy != "fcfs"
        self.fused_groups: List[FusedGroup] = []
        self._serial_names = list(engines)          # serial decode set
        self._prefill_serial_names = list(engines)  # serial prefill set
        self.reclaimed_weight_bytes = 0
        # mesh identity + device count inside a placement
        # (units_from_placement tags both); −1 / 1 for hand-built
        # units.  The reconfiguration subsystem keys its migration
        # schedule on mesh_id; the deterministic clock scales a tick's
        # per-token cost by n_devices (bigger mesh = faster tick).
        self.mesh_id = -1
        self.n_devices = 1
        # un-returned zero-copy grant: blocks a dissolve wanted back
        # but the pool's in-use tail kept (UnifiedKVPool.shrink
        # clamps).  The next build settles this debt before growing,
        # so repeated dissolve/rebuild cycles (live reconfiguration)
        # cannot inflate the arena past its reclaimed-weight backing.
        self._grant_debt = 0
        # optional runtime invariant checker (serving.sanitize);
        # SchedulerSanitizer installs itself here so the block-loss
        # fault path can report arena shrinks that change the base
        self.sanitizer = None
        if self.fused:
            self._build_fused_groups()

    def _build_fused_groups(self) -> None:
        """Group engines by fusion signature, stack weights zero-copy,
        and grant the de-dup dividend to the pool (the __init__ path,
        shared with live-reconfiguration rebuilds)."""
        by_sig: Dict[tuple, List[str]] = {}
        for name, eng in self.engines.items():
            sig = eng.fusion_signature()
            if sig is not None:
                by_sig.setdefault(sig, []).append(name)
        grouped, chunk_grouped = set(), set()
        for names in by_sig.values():
            if len(names) >= 2:
                grp = FusedGroup([self.engines[n] for n in names], names)
                self.fused_groups.append(grp)
                grouped.update(names)
                if grp.chunk_tokens:
                    chunk_grouped.update(names)
                # zero-copy dividend: de-duplicated weight bytes
                # become KV head-blocks for the group's LLMs — minus
                # any un-returned grant from a prior dissolve (the
                # arena still holds those blocks; re-growing the full
                # amount would double-count the reclaimed bytes)
                want = grp.reclaimed_bytes // self.pool.head_block_bytes
                settle = min(self._grant_debt, want)
                self._grant_debt -= settle
                granted = self.pool.grow(want - settle) + settle
                share = granted // len(grp.engines)
                grp.granted_blocks = granted
                grp.quota_share = share
                if share:
                    for e in grp.engines:
                        e.view.quota += share
                self.reclaimed_weight_bytes += grp.reclaimed_bytes
        self._serial_names = [n for n in self.engines if n not in grouped]
        self._prefill_serial_names = [n for n in self.engines
                                      if n not in chunk_grouped]

    def dissolve_fused_groups(self) -> int:
        """Undo every fused group: members re-own private weight
        copies, their quota shares are revoked (clamped so quota never
        drops below live usage) and the pool shrinks by the zero-copy
        grant — ``UnifiedKVPool.shrink`` refuses to cut below in-use
        blocks, so a grant whose tail is occupied is only partially
        returned (the arena re-grows on the next build).  Returns the
        head-blocks actually shrunk."""
        shrunk = 0
        for grp in self.fused_groups:
            grp.dissolve()
            if grp.quota_share:
                for e in grp.engines:
                    e.view.quota -= min(grp.quota_share,
                                        max(e.view.quota - e.view.used, 0))
            got = self.pool.shrink(grp.granted_blocks)
            self._grant_debt += grp.granted_blocks - got
            shrunk += got
            self.reclaimed_weight_bytes -= grp.reclaimed_bytes
        self.fused_groups = []
        self._serial_names = list(self.engines)
        self._prefill_serial_names = list(self.engines)
        return shrunk

    def rebuild_fused_groups(self) -> None:
        """Re-derive fused groups after a membership change (an engine
        joined or left the unit).  Dissolve-then-build keeps one code
        path for the zero-copy stacking and its pool grant."""
        self.dissolve_fused_groups()
        if self.fused:
            self._build_fused_groups()

    # ------------------------------------------------------------------
    def remove_engine(self, name: str):
        """Detach one engine for migration: dissolve its fused group
        (and rebuild the remainder), drop it from every scheduling
        structure and hand back ``(engine, queued_requests)``.  The
        engine keeps its live slots and cache view — the caller
        migrates the view and re-homes the engine via ``add_engine``.
        """
        assert name in self.engines, name
        eng = self.engines.pop(name)
        queued = list(self.queues.pop(name))
        self.sm_frac.pop(name, None)
        self._names = list(self.engines)
        self._prefill_rr = self._decode_rr = 0
        self.rebuild_fused_groups()
        return eng, queued

    def add_engine(self, name: str, eng, queued=(),
                   sm_frac: float = 1.0) -> None:
        """Adopt a migrated engine (and its carried queue) into this
        unit: it joins the tick rotation, inherits the scheduler's
        clock and compute share (``sm_frac``, re-set from the new plan
        by ``MigrationExecutor.apply_shares``), and fuses with
        matching-signature residents."""
        assert name not in self.engines, name
        assert eng.pool is self.pool, \
            "migrate the engine's view to this unit's pool first"
        self.engines[name] = eng
        self.queues[name] = deque(queued)
        self.sm_frac[name] = float(sm_frac)
        eng.clock = self.clock
        eng.emit = self.emit
        self._names = list(self.engines)
        self._prefill_rr = self._decode_rr = 0
        self.rebuild_fused_groups()

    # ------------------------------------------------------------------
    def set_emit(self, fn) -> None:
        """Install the token-emission hook on this unit and every
        engine it hosts: ``fn(event, request, token)`` with events
        "token" / "finish" / "reset" (engine-level commit points),
        "shed" and "cancelled" (scheduler dispositions).  ``add_engine``
        re-applies the hook, so engines rebuilt by crash recovery or
        adopted after a migration keep streaming (the fused sweeps need
        no wiring of their own — they commit through the member
        engines' ``apply_*_result``)."""
        self.emit = fn
        for eng in self.engines.values():
            eng.emit = fn

    def submit(self, req: Request) -> None:
        q = self.queues[req.model]
        if (self.shed_policy != "none" and self.max_queue is not None
                and len(q) >= self.max_queue):
            # bounded admission queue: backpressure sheds the NEW
            # arrival (recorded, SLO-missed) instead of growing the
            # queue without bound under overload
            self._shed(req, "queue_full")
            return
        q.append(req)

    def pending(self) -> int:
        return sum(len(q) for q in self.queues.values()) + sum(
            len(e.active_slots()) for e in self.engines.values())

    # ---- graceful degradation (DESIGN.md §12) ------------------------
    def _shed(self, req: Request, reason: str) -> None:
        """Deliberately drop one request: flagged (never silent),
        ``finish`` stays −1 so the roll-up counts an SLO miss with a
        ``shed`` disposition."""
        req.shed = True
        req.shed_reason = reason
        self.stats.shed.append(req)
        if self.emit is not None:
            self.emit("shed", req, -1)

    def _shed_expired(self) -> None:
        """Deadline-aware shedding: pop queue heads whose admission
        deadline has passed — by ``Request.deadline``'s construction
        (driver-stamped) even immediate solo-speed service would miss
        their scaled TTFT target, so carrying them only burns capacity
        other requests could still meet their SLOs with."""
        now = self.clock()
        for q in self.queues.values():
            while q and q[0].deadline < now:
                self._shed(q.popleft(), "deadline")

    def cancel(self, req: Request) -> bool:
        """Client abandonment (DESIGN.md §14): release everything the
        request holds NOW — its queue position, or its engine slot plus
        KV blocks and prefix-index refs (``evict_seqs`` → ``free_seq``
        drops shared-prefix refcounts with the rest) — and record the
        ``cancelled`` disposition.  Distinct from shedding: the server
        sheds to protect itself, the client cancels; the roll-up keeps
        ``submitted = finished + shed + cancelled``.  Returns False
        when the request already finished, was shed, or isn't held by
        this unit (nothing to free)."""
        if req.cancelled or req.shed or req.finish >= 0:
            return False
        removed = False
        q = self.queues.get(req.model)
        if q is not None and req in q:
            q.remove(req)
            removed = True
        else:
            eng = self.engines.get(req.model)
            if eng is not None:
                if req in eng.preempted:
                    # evicted this tick, awaiting requeue — drop it
                    # before _harvest puts it back on the queue
                    eng.preempted.remove(req)
                    removed = True
                else:
                    for slot in eng.active_slots():
                        if eng.slots[slot] is req:
                            eng.evict_seqs([int(eng.slot_seq[slot])])
                            removed = True
                            break
        if not removed:
            return False
        req.cancelled = True
        self.stats.cancelled.append(req)
        if self.emit is not None:
            self.emit("cancelled", req, -1)
        return True

    def _apply_faults(self) -> None:
        """Tick preamble: fire due plan events for this unit and track
        transient windows (serving/faults.py).  Crash and block-loss
        events mutate the unit immediately; a transient window marks
        its engine down for this tick (its phase work is skipped and
        retried next tick) and escalates to crash recovery once it has
        burned ``retry_budget`` consecutive ticks."""
        now = self.clock()
        for ev in self.injector.poll(self, now):
            if ev.kind == "engine_crash":
                self.recover_engine(ev.target, reason="crash")
            elif ev.kind == "block_loss":
                self._lose_blocks(ev.magnitude)
        for name in list(self.engines):
            if self.injector.consume_transient(name):
                ticks = self._transient_ticks.get(name, 0) + 1
                if ticks > self.retry_budget:
                    # retry budget exhausted: the engine is wedged, not
                    # hiccuping — rebuild it (clears the window too)
                    self._transient_ticks.pop(name, None)
                    self.injector.clear_transient(name)
                    self.recover_engine(name, reason="transient")
                else:
                    self._transient_ticks[name] = ticks
                    self._down.add(name)
            else:
                self._transient_ticks.pop(name, None)

    def recover_engine(self, name: str, reason: str = "crash") -> dict:
        """Crash recovery: tear down the dead engine and rebuild it on
        a fresh pool view, requeueing its in-flight requests.  Reuses
        the PR-4 migration machinery end to end — ``remove_engine``
        dissolves the fused groups (settling grant debt on rebuild),
        the eviction path is the migration eviction path, and
        ``add_engine`` re-fuses the rebuilt engine with its matching-
        signature residents.  The rebuilt engine starts from clean
        device state (zero SSM carries, empty slots) because the crash
        lost the old state; restart-from-scratch is exact under greedy
        decoding.  Requests past ``requeue_budget`` teardowns are shed
        instead of requeued (a request must not ping-pong through
        recoveries forever).  Returns the recovery record (also
        appended to ``fault_events`` for the driver to clock-charge).
        """
        share = self.sm_frac.get(name, 1.0)
        eng, queued = self.remove_engine(name)
        blocks_held = eng.view.used
        evicted = eng.evict_seqs(eng.live_seq_ids())
        quota = eng.view.quota
        self.pool.unregister_model(name)
        view = self.pool.register_model(eng.cfg, quota)
        # remove_engine left the dead engine on its private [1, ...]
        # tree: the rebuilt engine adopts those buffers without a copy
        fresh = Engine(eng.cfg, eng.params, view, max_slots=eng.max_slots,
                       max_blocks_per_seq=eng.max_blocks,
                       chunk_tokens=eng.chunk_tokens, clock=self.clock)
        for r in evicted:
            r.requeues += 1
        carried: List[Request] = []
        shed = 0
        # deterministic arrival-order requeue: evicted in-flight work
        # and the carried queue re-enter in (arrival, req_id) order,
        # independent of slot/eviction order
        for r in sorted(list(evicted) + list(queued),
                        key=lambda r: (r.arrival, r.req_id)):
            if r.requeues > self.requeue_budget:
                self._shed(r, "requeue_budget")
                shed += 1
            else:
                carried.append(r)
        self.add_engine(name, fresh, carried, sm_frac=share)
        rec = {"kind": "engine_crash", "reason": reason,
               "t": self.clock(), "target": name,
               "requeued": len(evicted), "shed": shed,
               "blocks": blocks_held}
        self.fault_events.append(rec)
        return rec

    def _lose_blocks(self, n: int) -> dict:
        """Block-loss fault: the arena loses its last ``n`` head-blocks
        (a bad HBM region).  Sequences with pages in the doomed tail
        are torn down at the engine level (pool accounting stays
        exact) and requeued at the head of their queues in arrival
        order; once the victims are gone the tail is entirely free and
        the pool shrinks by exactly the lost blocks.  A shared doomed
        block evicts every sharer (each sharer's block table names it,
        so ``tail_victims`` lists them all), and ``pool.shrink`` drops
        doomed prefix-index entries with it — no dangling cached base
        can survive a block loss."""
        n = min(max(n, 0), self.pool.n_head_blocks)
        requeued = shed = 0
        for name, sids in self.pool.tail_victims(n).items():
            eng = self.engines.get(name)
            if eng is None:
                continue
            evicted = eng.evict_seqs(sids)
            keep: List[Request] = []
            for r in evicted:
                r.requeues += 1
                if r.requeues > self.requeue_budget:
                    self._shed(r, "requeue_budget")
                    shed += 1
                else:
                    keep.append(r)
            for r in sorted(keep, key=lambda r: (r.arrival, r.req_id),
                            reverse=True):
                self.queues[name].appendleft(r)
            requeued += len(evicted)
        removed = self.pool.shrink(n)
        if self.sanitizer is not None:
            self.sanitizer.note_blocks_lost(removed)
        rec = {"kind": "block_loss", "t": self.clock(), "target": None,
               "requeued": requeued, "shed": shed, "blocks": removed}
        self.fault_events.append(rec)
        return rec

    def prefix_stats(self) -> Dict[str, dict]:
        """Per-LLM prefix-cache counters for this unit's pool (empty
        when ``--prefix-cache`` is off) — the ServeReport's hit-rate
        source.  Read from the pool's CURRENT views, so counters
        survive engine replacement on crash recovery (the fresh view's
        index starts cold, as it must: the old refs died with it)."""
        return self.pool.prefix_stats()

    def shed_all(self, reason: str = "watchdog") -> int:
        """Force-drain the unit: shed every queued AND in-flight
        request (the watchdog's last resort — a stall that survived
        every recovery path must still terminate with ``submitted =
        finished + shed``, not hang).  Returns the number shed."""
        n = 0
        for q in self.queues.values():
            while q:
                self._shed(q.popleft(), reason)
                n += 1
        for eng in self.engines.values():
            for r in eng.evict_seqs(eng.live_seq_ids()):
                self._shed(r, reason)
                n += 1
        return n

    # ------------------------------------------------------------------
    def _meter(self, counter: Dict[str, int], name: str, toks: int) -> None:
        """Credit one engine's phase tokens for this tick (share-aware
        clock input; reset at every ``tick``)."""
        if toks:
            counter[name] = counter.get(name, 0) + toks

    # ------------------------------------------------------------------
    def _pull_batch(self, name: str) -> List[Request]:
        """Pop an admissible batch for one LLM — Alg. 3's
        ``resource_enough`` gate (Eq. 2's per-LLM cache share R):
        whole-lifetime quota check, cumulative across the batch.
        Simulator counterpart: ``UnitSim._try_prefill_batch`` (same
        lifetime reservation, in bytes instead of head-blocks)."""
        if name in self._down:
            # transient step failure this tick: admit nothing, retry
            # the same queue next tick
            return []
        q = self.queues[name]
        eng = self.engines[name]
        with eng.admit_span:
            if q and eng.lifetime_blocks(q[0]) > eng.view.quota:
                # adapt_quotas shrank this LLM's quota below the head
                # request's whole lifetime — it would re-queue forever;
                # pull spare quota back before trying to admit
                self.pool.grant_min_quota(eng.view,
                                          eng.lifetime_blocks(q[0]))
            batch: List[Request] = []
            pending = 0   # lifetime blocks of already-selected requests
            while q and len(batch) < len(eng.free_slots()):
                if eng.can_admit(q[0], pending):
                    pending += eng.lifetime_blocks(q[0])
                    batch.append(q.popleft())
                else:
                    break
        return batch

    def _run_prefill_round_robin(self) -> bool:
        """Try one prefill job round-robin across the serially-prefilled
        LLMs — Alg. 3's prefill-selection step (prefill jobs are
        prioritized; round-robin order across LLMs is the fairness
        rule).  Fused-prefill group members are handled by
        ``_run_prefill_fused_groups`` instead.  Simulator counterpart:
        the round-robin prefill loop in
        ``UnitSim._round_spatial_temporal``."""
        names = self._prefill_serial_names
        n = len(names)
        for i in range(n):
            name = names[(self._prefill_rr + i) % n]
            if name in self._down:
                continue
            eng = self.engines[name]
            batch = self._pull_batch(name)
            if batch or eng.has_prefill_work():
                # admission time, before the step runs (as the fused and
                # fcfs paths stamp it)
                now = self.clock()
                for r in batch:
                    r.prefill_done = now
                toks = eng.prefill(batch)
                self.stats.prefill_tokens += toks
                self._meter(self.tick_prefill_by, name, toks)
                self._prefill_rr = (self._prefill_rr + i + 1) % n
                return True
        return False

    def _run_prefill_fused_groups(self) -> bool:
        """Fused multi-LLM prefill tick: admit round-robin into every
        chunked group member (host-side bookkeeping only), then advance
        ALL members' in-flight chunks in one jitted sweep per group —
        the prefill-phase mirror of the fused decode tick."""
        ran = False
        for grp in self.fused_groups:
            if grp.chunk_tokens is None:
                continue
            now = self.clock()
            for name, eng in zip(grp.names, grp.engines):
                batch = self._pull_batch(name)
                if batch:
                    eng.admit_chunked(batch)
                    for r in batch:
                        r.prefill_done = now
            jobs = [None if name in self._down else eng.export_prefill_job()
                    for name, eng in zip(grp.names, grp.engines)]
            n_active = sum(j is not None for j in jobs)
            if n_active == 0:
                continue
            if n_active == 1:
                # a lone prefilling engine gains nothing from the fused
                # sweep — run its exported job serially (off the SAME
                # stacked buffers, via its model index)
                m = next(i for i, j in enumerate(jobs) if j is not None)
                toks = grp.engines[m].run_chunk_job(jobs[m])
                self.stats.prefill_tokens += toks
                self._meter(self.tick_prefill_by, grp.names[m], toks)
            else:
                per = grp.prefill(jobs)
                self.stats.prefill_tokens += sum(per.values())
                for name, toks in per.items():
                    self._meter(self.tick_prefill_by, name, toks)
            ran = True
        return ran

    def _run_prefill(self) -> bool:
        ran = self._run_prefill_fused_groups() if self.fused else False
        return self._run_prefill_round_robin() or ran

    def _run_decode_round_robin(self) -> int:
        """Fill the tick with decode jobs from every LLM — Alg. 3's
        decode-fill step ("remaining resources go to decode jobs"),
        i.e. decode-decode colocation.  Simulator counterpart: the
        concurrent-decode block of ``UnitSim._round_spatial_temporal``
        (``t_round = Σ t_p + max_m t_d^m``, Eq. 3's round shape)."""
        total = 0
        n = len(self._names)
        for i in range(n):
            name = self._names[(self._decode_rr + i) % n]
            if name in self._down:
                continue
            eng = self.engines[name]
            if eng.has_decode_work():
                toks = eng.decode()
                self._meter(self.tick_decode_by, name, toks)
                total += toks
        self._decode_rr = (self._decode_rr + 1) % max(n, 1)
        return total

    def _run_decode_fused(self) -> int:
        """Fused multi-LLM decode tick: one jitted sweep per fused
        group, serial fallback for heterogeneous leftovers."""
        total = 0
        for grp in self.fused_groups:
            jobs = [None if name in self._down else eng.export_decode_job()
                    for name, eng in zip(grp.names, grp.engines)]
            n_active = sum(j is not None for j in jobs)
            if n_active == 0:
                continue
            if n_active == 1:
                # a lone active engine gains nothing from the fused
                # sweep — run its (already exported) job serially
                m = next(i for i, j in enumerate(jobs) if j is not None)
                toks = grp.engines[m].decode(jobs[m])
                self._meter(self.tick_decode_by, grp.names[m], toks)
                total += toks
            else:
                per = grp.decode(jobs)
                for name, toks in per.items():
                    self._meter(self.tick_decode_by, name, toks)
                total += sum(per.values())
        n = len(self._serial_names)
        for i in range(n):
            name = self._serial_names[(self._decode_rr + i) % n]
            if name in self._down:
                continue
            eng = self.engines[name]
            if eng.has_decode_work():
                toks = eng.decode()
                self._meter(self.tick_decode_by, name, toks)
                total += toks
        self._decode_rr = (self._decode_rr + 1) % max(n, 1)
        return total

    def _decode_tick(self) -> int:
        return self._run_decode_fused() if self.fused \
            else self._run_decode_round_robin()

    def _harvest(self) -> None:
        with _HARVEST:
            for name, eng in self.engines.items():
                if eng.finished:
                    self.stats.finished.extend(eng.finished)
                    eng.finished.clear()
                if eng.preempted:
                    # stall-escape evictions go back to the head of
                    # their queue and restart from scratch on the next
                    # prefill — in (arrival, req_id) order, NOT eviction
                    # order: the engine preempts youngest-first, and
                    # letting that order leak into the retry queue would
                    # serve a later arrival before an earlier one
                    # evicted the same tick (and make the requeue order
                    # depend on slot layout)
                    for r in sorted(eng.preempted,
                                    key=lambda r: (r.arrival, r.req_id),
                                    reverse=True):
                        self.queues[name].appendleft(r)
                    eng.preempted.clear()

    # ------------------------------------------------------------------
    def tick(self) -> None:
        """One scheduler iteration (paper Alg. 3 main loop).

        Branch ↔ paper mapping (sim counterpart in parentheses — both
        must stay in step, tests/test_slo_driver.py compares them on
        shared conventions):

        * ``adbs`` — Alg. 3 verbatim: prefill-priority round-robin
          selection, decode fills the remaining resources, and
          ``adapt_quota_periodically`` every ``adapt_every`` ticks
          (``UnitSim._round_spatial_temporal`` + ``_adapt_quotas``).
        * ``round_robin`` — Fig. 9 ablation arm: the same loop without
          prefill priority (prefill only every other tick) and with
          FIXED quotas — isolates what ADBS's two mechanisms add.
        * ``fcfs`` — temporal-multiplexing baseline (AlpaServe-style):
          strict global arrival order, one LLM at a time, no quotas
          (``UnitSim._round_temporal``).

        With ``enforce_shares`` the adbs branch flips its intra-tick
        phase order: decode jobs are dispatched FIRST, each under its
        planned ``sm_frac``, and prefill chunks fill the residual
        compute afterwards — the paper's Fig.-4 dispatch (decode jobs
        hold their small SM shares, prefill takes the rest) and the
        order the share-aware clock assumes when it computes the
        residual share from the tick's decode set (DESIGN.md §11).
        """
        with _TICK:
            self._tick()

    def _tick(self) -> None:
        self.stats.ticks += 1
        self.tick_prefill_by = {}
        self.tick_decode_by = {}
        # fault/degradation preamble (DESIGN.md §12): shed expired
        # queue heads, fire due fault-plan events, mark transient-down
        # engines for this tick — before any policy branch, so every
        # policy sees the same post-fault unit
        self._down = set()
        if self.shed_policy == "deadline":
            self._shed_expired()
        if self.injector is not None:
            self._apply_faults()
        if self.policy == "adbs":
            if self.enforce_shares:
                # decode under the planned shares first; prefill fills
                # the residual compute of the tick
                self.stats.decode_tokens += self._decode_tick()
                self._run_prefill()
            else:
                self._run_prefill()
                # decode jobs fill the remaining resources: one fused
                # multi-LLM sweep when fused=True, back-to-back
                # otherwise
                self.stats.decode_tokens += self._decode_tick()
            if self.stats.ticks % self.adapt_every == 0:
                # Alg. 3's adapt_quota_periodically (sim counterpart:
                # UnitSim._adapt_quotas, same low→high utilization move)
                with _QUOTA:
                    self.pool.adapt_quotas()
        elif self.policy == "round_robin":
            # no prefill priority, no quota adaptation
            if self.stats.ticks % 2 == 0:
                self._run_prefill()
            self.stats.decode_tokens += self._decode_tick()
        elif self.policy == "fcfs":
            # temporal multiplexing: serve the LLM with the oldest
            # pending request, prefill+decode to completion batch-wise.
            # In-flight prompt chunks must keep advancing regardless of
            # admission — a chunked prefill that only moved when a NEW
            # batch was admissible would stall forever once slots or
            # quota block the queue head (the unit is busy until the
            # current batch completes; new admissions wait).
            # the one-LLM-at-a-time admission gate reads the FULL busy
            # sets; transient-down engines only skip the work loops
            # (their in-flight batch still blocks new admissions)
            busy_prefill = [n for n, e in self.engines.items()
                            if e.has_prefill_work()]
            busy_decode = [n for n, e in self.engines.items()
                           if e.has_decode_work()]
            prefilling = [n for n in busy_prefill if n not in self._down]
            for name in prefilling:
                toks = self.engines[name].prefill([])
                self.stats.prefill_tokens += toks
                self._meter(self.tick_prefill_by, name, toks)
            active = [n for n in busy_decode if n not in self._down]
            oldest_name, oldest_t = None, float("inf")
            for name, q in self.queues.items():
                if q and q[0].arrival < oldest_t:
                    oldest_name, oldest_t = name, q[0].arrival
            if oldest_name is not None and not busy_decode \
                    and not busy_prefill and oldest_name not in self._down:
                eng = self.engines[oldest_name]
                q = self.queues[oldest_name]
                with eng.admit_span:
                    if q and eng.lifetime_blocks(q[0]) > eng.view.quota:
                        # same escape as _pull_batch: a head request
                        # whose lifetime exceeds the LLM's quota would
                        # re-queue forever (fcfs has no adaptation)
                        self.pool.grant_min_quota(
                            eng.view, eng.lifetime_blocks(q[0]))
                    batch = []
                    pending = 0
                    while q and len(batch) < len(eng.free_slots()) \
                            and eng.can_admit(q[0], pending):
                        pending += eng.lifetime_blocks(q[0])
                        batch.append(q.popleft())
                if batch:
                    now = self.clock()
                    for r in batch:
                        r.prefill_done = now
                    toks = eng.prefill(batch)
                    self.stats.prefill_tokens += toks
                    self._meter(self.tick_prefill_by, oldest_name, toks)
            for name in active:
                toks = self.engines[name].decode()
                self.stats.decode_tokens += toks
                self._meter(self.tick_decode_by, name, toks)
        else:
            raise ValueError(self.policy)
        self._harvest()

    def run(self, max_ticks: int = 10_000) -> MuxStats:
        """Drain all queues."""
        t = 0
        while self.pending() and t < max_ticks:
            self.tick()
            t += 1
        return self.stats
