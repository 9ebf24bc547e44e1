"""Per-LLM runtime engine: disaggregated prefill / decode jobs.

Mirrors MuxServe's runtime-engine design (§3.4): prefill and decode are
*separate jobs* operating on shared weights and the unified KV pool.
The global ADBS scheduler (serving/mux.py) decides which job runs each
tick; the analogue of MPS SM-assignment is the fused multi-LLM step
(DESIGN.md §2) — ``export_decode_job`` / ``apply_decode_result`` and
``export_prefill_job`` / ``apply_prefill_result`` are this engine's
half of that contract, ``_fused_decode_impl`` /
``_fused_prefill_chunk_impl`` the stacked-weights sweeps themselves.

Zero-copy stacked weights (DESIGN.md §2): every jitted step takes a
param tree stacked on a leading model axis ``M`` plus a model index —
a singleton engine carries an ``M=1`` stack of its own weights, and an
engine adopted into a fused group (``adopt_stacked``) points at the
group's shared tree instead of keeping a private copy.  The per-model
slice happens *inside* the jitted program (a dynamic index on the
leading axis), so one compiled program serves every group member and
no second weight copy ever lives in HBM.

Shape stability: every hot-path batch is padded to a bucketed shape —
powers-of-2 batch rows (masked via −1 block tables / zero lengths) and
block-multiple prompt lengths — so steady-state serving compiles a
bounded set of programs instead of re-tracing per tick.  The
``TRACE_COUNTS`` hook counts impl traces (each jit compilation traces
the impl exactly once) and is asserted bounded in tests and reported
by ``benchmarks/fused_tick``.

The engine manages a fixed number of decode *slots* (continuous
batching): a sequence occupies a slot from prefill completion until
finish, and its attention KV lives in the unified pool while SSM state
(constant-size) lives in per-slot dense arrays.
"""
from __future__ import annotations

import time
from collections import Counter
from dataclasses import dataclass, field
from functools import lru_cache, partial
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import BLOCK_TOKENS, ModelConfig, replace
from repro.models import mamba2 as M2
from repro.models import moe as MoE
from repro.models.layers import (attn_qkv, causal_attention, lm_logits,
                                 mlp, rms_norm)
from repro.models.transformer import init_params
from repro.serving import cache_ops
from repro.serving.kvcache import ModelCacheView
from repro.serving.metrics import event, span


@dataclass
class Request:
    """One serving request, carrying its whole latency timeline.

    Timestamps are stamped by the engine/scheduler from the owning
    scheduler's clock (``MuxScheduler(clock=...)``), so they live in a
    single time domain — wall seconds for live serving, logical
    seconds under a deterministic clock (serving/driver.py):

      * ``arrival``      — trace arrival time (set by the submitter;
        queueing delay before admission counts toward TTFT/E2E, as in
        the paper's latency accounting);
      * ``prefill_done`` — prefill job dispatched (admission time);
      * ``first_token``  — first output token committed (TTFT end);
      * ``finish``       — last token committed (E2E end).

    DESIGN.md §9 defines the derived metrics (TTFT/TPOT/E2E) and the
    SLO-attainment convention shared with ``core/simulator.py``.

    Degradation disposition (DESIGN.md §12): a request is never
    silently dropped — overload/fault handling either requeues it
    (``requeues`` counts teardowns it survived; a finished request
    with ``requeues > 0`` was *recovered*) or sheds it (``shed`` set,
    ``finish`` stays −1 so it is an SLO miss at every scale, and
    ``shed_reason`` records why).  ``deadline`` is the absolute clock
    instant past which admission can no longer meet the request's
    scaled TTFT target (stamped by the driver under
    ``shed_policy="deadline"``; +inf = never deadline-shed).
    """
    req_id: int
    model: str
    prompt: List[int]
    max_new_tokens: int
    arrival: float = 0.0
    # runtime state
    output: List[int] = field(default_factory=list)
    prefill_done: float = -1.0
    first_token: float = -1.0
    finish: float = -1.0
    # degradation disposition (serving/faults.py, DESIGN.md §12)
    deadline: float = float("inf")
    shed: bool = False
    shed_reason: str = ""
    requeues: int = 0
    # client abandonment (DESIGN.md §14): the third disposition next to
    # finished/shed — ``MuxScheduler.cancel`` frees the request's slot,
    # KV blocks and prefix refs immediately and reports preserve
    # ``submitted = finished + shed + cancelled``
    cancelled: bool = False

    @property
    def done(self) -> bool:
        return len(self.output) >= self.max_new_tokens


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def _next_pow2(x: int) -> int:
    """Smallest power of two ≥ x (bucketed batch rows — DESIGN.md §5)."""
    return 1 << max(0, (x - 1).bit_length())


def _pad_rows(rows: int, *specs):
    """Pad each ``(array, fill)`` to ``rows`` leading rows.

    One place defines the padded-row invariants of every bucketed
    batch: −1 block tables (KV writes drop, attention resolves to a
    masked block), 0 tokens/lengths (dead logits, sliced off
    host-side) and length-1 decode rows (one masked garbage softmax).
    """
    out = []
    for arr, fill in specs:
        p = np.full((rows,) + arr.shape[1:], fill, arr.dtype)
        p[:arr.shape[0]] = arr
        out.append(p)
    return out


# ---------------------------------------------------------------------------
# weight-tree accounting (zero-copy stacked weights, DESIGN.md §2)
# ---------------------------------------------------------------------------
def tree_bytes(tree) -> int:
    """Total bytes of every leaf in a param tree."""
    return sum(leaf.nbytes for leaf in jax.tree_util.tree_leaves(tree))


def init_stacked_params(key, cfg: ModelConfig, dtype):
    """Seeded weights built directly with the leading model axis M=1.

    One jitted program writes every leaf straight into its ``[1, ...]``
    buffer, so the device never holds an unstacked copy beside the
    stacked one — at published widths each copy is several GiB.  This
    is the tree ``Engine`` takes."""
    return _stacked_init(replace(cfg, name=""), jnp.dtype(dtype))(key)


@lru_cache(maxsize=None)
def _stacked_init(cfg_key: ModelConfig, dtype):
    """One compiled init program per geometry and dtype (model names
    do not shape the weights), shared by every unit that is built."""
    return jax.jit(lambda k: jax.tree_util.tree_map(
        lambda a: a[None], init_params(k, cfg_key, dtype)))


def unique_tree_bytes(trees) -> int:
    """Bytes of the *distinct* buffers across several param trees.

    Engines of a fused group share one stacked tree, so their leaves
    are the same objects — counting each buffer once is the live-memory
    accounting that proves the group pays ~1× (not 2×) weight memory.
    """
    seen: set = set()
    total = 0
    for tree in trees:
        for leaf in jax.tree_util.tree_leaves(tree):
            if id(leaf) not in seen:
                seen.add(id(leaf))
                total += leaf.nbytes
    return total


# ---------------------------------------------------------------------------
# trace counting (shape-stability instrumentation)
# ---------------------------------------------------------------------------
# Each entry counts how many times jit TRACED the named step impl —
# i.e. how many distinct programs were compiled for it.  A shape-stable
# runtime stops growing these after warm-up (asserted in
# tests/test_zero_copy.py, reported by benchmarks/fused_tick).
TRACE_COUNTS: Counter = Counter()


def _note_trace(name: str) -> None:
    TRACE_COUNTS[name] += 1
    event(f"mux.trace.{name}")


def total_traces() -> int:
    return sum(TRACE_COUNTS.values())


# the four host phases of a step, each a child span of the step's span:
#   prep    host work before the jitted call (tables, padding, transfers
#           to the device, the SSM state's gathers)
#   launch  the jitted call until it returns (it dispatches, asynchronously)
#   sync    the host blocked on the step's sampled tokens
#   commit  ``apply_*_result``, with the SSM state's scatter and rollback
STEP_PHASES = ("prep", "launch", "sync", "commit")


def step_spans(kind: str, name: str):
    """Span handles (serving/metrics.py) of one ``kind`` step
    ("decode" | "prefill") of LLM or fused group ``name``: the step's
    ``mux.<kind>.<name>`` and its phases, interned once per owner."""
    root = f"mux.{kind}.{name}"
    return (span(root),) + tuple(span(f"{root}.{p}") for p in STEP_PHASES)


def _select_model(params, midx):
    """Slice one model's tree out of a stacked ``[M, ...]`` tree.

    ``midx`` is a *traced* scalar, so the slice is a dynamic index
    inside the compiled program: every member of a fused group (and
    the M=1 singleton case) shares ONE compilation per shape bucket,
    and no per-model weight copy persists outside the step.
    """
    return jax.tree_util.tree_map(lambda a: a[midx], params)


@dataclass
class DecodeJob:
    """One engine's decode rows for the current tick, in export form.

    The fused multi-LLM tick (DESIGN.md §2) stacks the jobs of all
    colocated same-architecture engines into a single jitted step; the
    serial path consumes a job one engine at a time.  Block tables and
    sequence lengths are resolved from the pool view at execution time
    (``ModelCacheView.block_table`` / ``fused_block_tables``) so the
    job stays valid across the padding decisions of either path.
    """
    slots: List[int]
    reqs: List[Request]
    seq_ids: List[int]
    last_tok: np.ndarray          # [B] int32 — token decoded this step

    def __len__(self) -> int:
        return len(self.reqs)


@dataclass
class PrefillJob:
    """One engine's in-flight prompt chunks for the current tick.

    Mirror of ``DecodeJob`` for the chunked-prefill phase: the fused
    multi-LLM prefill sweep pads the jobs of all group members to the
    group's fixed row count and advances them in ONE jitted step; the
    serial path pads to a power-of-2 row bucket instead.  Arrays are
    exported *unpadded* — the runner owns the padding policy.
    """
    slots: List[int]
    reqs: List[Request]
    seq_ids: List[int]
    toks: np.ndarray              # [B, C] int32 chunk tokens
    offs: np.ndarray              # [B] int32 absolute chunk start
    clens: np.ndarray             # [B] int32 true chunk lengths

    def __len__(self) -> int:
        return len(self.reqs)


class Engine:
    """Inference engine for one LLM over the shared pool.

    Its step programs are the jnp/XLA implementations below; they run
    on any JAX backend, and at published widths on the TPU."""

    def __init__(self, cfg: ModelConfig, params, view: ModelCacheView,
                 max_slots: int = 8, max_blocks_per_seq: int = 64,
                 rng_seed: int = 0, chunk_tokens: Optional[int] = None,
                 clock=time.perf_counter):
        """``params`` carries the leading M=1 model axis
        (``init_stacked_params``, or another engine's private tree) and
        is adopted as is, without a copy.

        ``chunk_tokens``: enable CHUNKED PREFILL (beyond-paper —
        Sarathi-style): prompts are processed ``chunk_tokens`` at a
        time, one chunk per scheduler tick, so colocated LLMs' decode
        jobs interleave between chunks and a long prompt cannot
        monopolize the unit (bounds TTFT interference under ADBS).
        Attention families only (SSM state chunking is a natural
        extension — the mixer already carries state)."""
        self.cfg = cfg
        # host spans of this LLM's steps and admissions
        self.decode_spans = step_spans("decode", cfg.name)
        self.prefill_spans = step_spans("prefill", cfg.name)
        self.admit_span = span(f"mux.admit.{cfg.name}")
        # request timestamps (first_token/finish) are stamped from this
        # clock so a deterministic driver can own the time domain
        # (serving/driver.py); MuxScheduler re-points it on all engines
        self.clock = clock
        # jit programs are cached per *geometry*, not per model name —
        # colocated instances of the same architecture share programs
        self.cfg_key = replace(cfg, name="")
        self.view = view
        self.pool = view.pool
        self.max_slots = max_slots
        self.max_blocks = max_blocks_per_seq
        # chunked prefill: attention families chunk against the pool;
        # pure-SSM models chunk via the mixer's state carry.  Hybrid
        # (zamba2) keeps whole-prompt prefill (mixed cache chunking is
        # a straightforward extension, not done here).
        self.chunk_tokens = None if cfg.family == "hybrid" else chunk_tokens
        self.slots: List[Optional[Request]] = [None] * max_slots
        self.slot_seq: np.ndarray = np.full(max_slots, -1, np.int64)
        self.finished: List[Request] = []
        self.preempted: List[Request] = []      # evicted by stall escape
        self._prefilling: Dict[int, int] = {}   # slot → next prompt pos
        self._stall_ticks = 0
        self._rolled_rows: List[int] = []
        self._next_seq = 0
        self._rng = np.random.default_rng(rng_seed)
        # token-emission hook (serving/frontend.py): called as
        # ``emit(event, request, token)`` at every COMMITTED progress
        # point — "token" (an output token survived its reserve/validate
        # step; rolled-back tokens never emit), "finish" (request
        # finalized), "reset" (an eviction cleared the request's
        # progress; previously streamed tokens are void).  Installed by
        # ``MuxScheduler.set_emit`` (which re-applies it to engines
        # rebuilt by crash recovery); None = no streaming consumer.
        self.emit: Optional[Callable[[str, Request, int], None]] = None

        # SSM per-slot state
        if cfg.ssm:
            sc = cfg.ssm
            conv_dim = cfg.d_inner + 2 * sc.n_groups * sc.d_state
            self.ssm_state = jnp.zeros(
                (cfg.n_layers, max_slots, cfg.n_ssm_heads, sc.head_dim,
                 sc.d_state), jnp.float32)
            self.conv_tail = jnp.zeros(
                (cfg.n_layers, max_slots, sc.conv_kernel - 1, conv_dim),
                jnp.bfloat16 if params["tok"]["embed"].dtype == jnp.bfloat16
                else params["tok"]["embed"].dtype)
        else:
            self.ssm_state = None
            self.conv_tail = None

        # zero-copy weights: the engine holds an M=1 *stacked* tree and
        # always runs the (stacked, model_index) step signature — when
        # a FusedGroup adopts this engine (``adopt_stacked``) the tree
        # is swapped for the group's shared stack and the private copy
        # is freed, with no change to any step path.
        self.params = params
        self.model_index = 0
        self._prefill_fn = jitted_step("prefill", self.cfg_key)
        self._decode_fn = jitted_step("decode", self.cfg_key)
        self._chunk_fn = jitted_step(
            "chunk_ssm" if cfg.family == "ssm" else "chunk", self.cfg_key)

    # ------------------------------------------------------------------
    def adopt_stacked(self, stacked, model_index: int) -> None:
        """Point this engine at a fused group's shared stacked tree.

        The private ``[1, ...]`` tree is dropped (freeing its buffers)
        and every step — prefill, chunked prefill, decode, the
        lone-engine fallback — runs off the group's buffers via the
        leading-axis model index.  This is the zero-copy contract:
        after adoption the group holds exactly ONE weight tree.
        """
        self.params = stacked
        self.model_index = model_index

    def materialize_private(self) -> None:
        """Inverse of ``adopt_stacked``: re-own a private ``[1, ...]``
        stacked copy of this engine's weights, sliced out of whatever
        tree it currently points at.  Live reconfiguration dissolves a
        fused group through this before the group's shared buffer is
        dropped — every step keeps the same (stacked, model_index)
        signature, only the tree narrows back to M=1."""
        m = self.model_index
        self.params = jax.tree_util.tree_map(lambda a: a[m:m + 1],
                                             self.params)
        self.model_index = 0

    def rebind_view(self, view: ModelCacheView) -> None:
        """Point the engine at a migrated cache view (and its pool).
        The view must carry this engine's live sequences — block
        tables and lengths are re-resolved from it on every step, so
        in-flight decodes continue without any engine-side fixup."""
        assert view.cfg.name == self.cfg.name
        self.view = view
        self.pool = view.pool

    def evict_prefilling(self) -> List[Request]:
        """Evict every in-flight (chunk-phase) prefill: free its cache,
        reset its progress and hand the requests back for requeueing.
        Migration uses drain-or-carry per request — decodes carry
        their KV to the destination pool, but a half-written prompt is
        cheaper to restart than to move (the chunk position would have
        to migrate too); greedy decoding makes the restart exact."""
        out: List[Request] = []
        for slot in sorted(self._prefilling):
            r = self.slots[slot]
            self.view.free_seq(int(self.slot_seq[slot]))
            self.slots[slot] = None
            self.slot_seq[slot] = -1
            r.output.clear()
            r.prefill_done = -1.0
            r.first_token = -1.0
            if self.emit is not None:
                self.emit("reset", r, -1)
            out.append(r)
        self._prefilling.clear()
        return out

    def evict_seqs(self, seq_ids) -> List[Request]:
        """Evict specific live sequences (prefilling OR decoding): free
        their cache, reset request progress and hand the requests back
        for requeueing.  The fault-handling twin of
        ``evict_prefilling`` — crash recovery evicts every live seq,
        block loss only the seqs whose pages sat in the lost arena
        tail.  Restart-from-scratch is exact for every family (greedy
        decoding; a fresh prefill rebuilds KV and SSM state alike)."""
        wanted = set(int(s) for s in seq_ids)
        out: List[Request] = []
        for slot in self.active_slots():
            sid = int(self.slot_seq[slot])
            if sid not in wanted:
                continue
            r = self.slots[slot]
            self.view.free_seq(sid)
            self.slots[slot] = None
            self.slot_seq[slot] = -1
            self._prefilling.pop(slot, None)
            r.output.clear()
            r.prefill_done = -1.0
            r.first_token = -1.0
            if self.emit is not None:
                self.emit("reset", r, -1)
            out.append(r)
        return out

    def live_seq_ids(self) -> List[int]:
        """Sequence ids of every occupied slot (prefilling included)."""
        return [int(self.slot_seq[s]) for s in self.active_slots()]

    # ------------------------------------------------------------------
    def _finish_slot(self, slot: int, r: Request) -> None:
        """Finalize a request: stamp ``finish``, free its cache and
        slot, hand it to ``finished`` (one definition shared by the
        decode path and the prefill-completes-the-request edge cases —
        ``max_new_tokens ≤ 1``)."""
        r.finish = self.clock()
        self.view.free_seq(int(self.slot_seq[slot]))
        self.slots[slot] = None
        self.slot_seq[slot] = -1
        self.finished.append(r)
        if self.emit is not None:
            self.emit("finish", r, -1)

    def free_slots(self) -> List[int]:
        return [i for i, r in enumerate(self.slots) if r is None]

    def active_slots(self) -> List[int]:
        return [i for i, r in enumerate(self.slots) if r is not None]

    def lifetime_blocks(self, req: Request) -> int:
        """Head-blocks of KV this request needs over its whole
        lifetime (prompt + max_new tokens); SSM state takes a slot and
        no blocks."""
        total = len(req.prompt) + req.max_new_tokens
        return -(-total // BLOCK_TOKENS) * self.view.group_size

    def can_admit(self, req: Request, pending_blocks: int = 0) -> bool:
        """Whether the request's whole-lifetime quota fits the current
        headroom.  ``pending_blocks``: lifetime blocks of requests
        already selected for the same batch but not yet reserved —
        batch admission must accumulate it, or every candidate is
        checked against the same un-decremented headroom and the batch
        overcommits the quota."""
        if not self.free_slots():
            return False
        # available_blocks counts evictable prefix-cache inventory —
        # cached blocks are disposable and must never starve admission
        return self.lifetime_blocks(req) + pending_blocks <= min(
            self.view.quota_headroom(),
            self.pool.available_blocks())

    # ------------------------------------------------------------------
    def prefill(self, reqs: List[Request]) -> int:
        """Run one prefill job for up to len(free_slots) requests.

        Returns number of prompt tokens processed (0 if nothing ran).
        With ``chunk_tokens`` set, admits the requests and advances all
        in-flight prefills by one chunk instead (call again next tick).
        """
        if self.chunk_tokens:
            return self._prefill_chunked(reqs)
        with self.admit_span:
            reqs = reqs[:len(self.free_slots())]
            admitted = []
            pending = 0
            for r in reqs:
                if self.can_admit(r, pending):
                    admitted.append(r)
                    pending += self.lifetime_blocks(r)
        if not admitted:
            return 0
        step, prep, launch, sync, commit = self.prefill_spans
        with step:
            with prep:
                args, slot_ids, seq_ids = self._prefill_inputs(admitted)
            with launch:
                pool_k, pool_v, logits, new_ssm, new_tail = \
                    self._prefill_fn(self.params, self.model_index, *args)
            B = len(admitted)
            self.pool.k, self.pool.v = pool_k, pool_v
            if self.cfg.ssm:
                with commit:
                    sl = jnp.asarray(slot_ids)
                    self.ssm_state = self.ssm_state.at[:, sl].set(
                        new_ssm[:, :B])
                    self.conv_tail = self.conv_tail.at[:, sl].set(
                        new_tail[:, :B].astype(self.conv_tail.dtype))
            # sample first token
            with sync:
                nxt = np.asarray(jnp.argmax(logits[:B], axis=-1))
            with commit:
                return self._commit_prefill(admitted, slot_ids, seq_ids,
                                            nxt)

    def _prefill_inputs(self, admitted: List[Request]):
        """Reserve the admitted prompts and bind their slots; returns
        the whole-prompt step's device arguments after the weights,
        the slots and the sequence ids."""
        B = len(admitted)
        # shape buckets (DESIGN.md §5): rows to the next power of two,
        # prompt length to the next BLOCK_TOKENS multiple — the padded
        # rows carry −1 tables (KV writes drop) and zero lengths, so
        # steady state revisits a bounded set of compiled programs
        Bp = _next_pow2(B)
        S = _round_up(max(len(r.prompt) for r in admitted), BLOCK_TOKENS)
        toks = np.zeros((B, S), np.int32)
        lens = np.zeros((B,), np.int32)
        slot_ids = self.free_slots()[:B]
        seq_ids = []
        for i, r in enumerate(admitted):
            lens[i] = len(r.prompt)
            toks[i, :lens[i]] = r.prompt
            sid = self._next_seq
            self._next_seq += 1
            seq_ids.append(sid)
            ok = self.view.append_tokens(sid, int(lens[i]))
            assert ok, "admission check guaranteed quota"
            self.slots[slot_ids[i]] = r
            self.slot_seq[slot_ids[i]] = sid
            r._seq_id = sid

        toks, lens, table = _pad_rows(
            Bp, (toks, 0), (lens, 0),
            (self.view.block_table(seq_ids, self.max_blocks), -1))
        return ((jnp.asarray(toks), jnp.asarray(lens), self.pool.k,
                 self.pool.v, jnp.asarray(table)), slot_ids, seq_ids)

    def _commit_prefill(self, admitted: List[Request], slot_ids, seq_ids,
                        nxt: np.ndarray) -> int:
        """Commit the whole-prompt step's first tokens; returns the
        prompt tokens processed."""
        for i, r in enumerate(admitted):
            if r.max_new_tokens <= 0:
                # degenerate prefill-only request: done at prompt end,
                # no output token to commit (first_token = finish so
                # downstream TTFT math stays finite)
                r.first_token = self.clock()
                self._finish_slot(slot_ids[i], r)
                continue
            # reserve BEFORE committing the token: on quota overcommit
            # (admission point-checks headroom per request) the token
            # is dropped and decode regenerates it at the same
            # position once blocks free up — never a silent desync
            if self.view.append_tokens(seq_ids[i], 1):
                r.output.append(int(nxt[i]))
                r.first_token = self.clock()
                if self.emit is not None:
                    self.emit("token", r, int(nxt[i]))
                if r.done:
                    # max_new_tokens == 1: the prefill-committed token
                    # IS the whole output — finalize here, or a decode
                    # tick would append a second token past max_new
                    # and bill a spurious decode step to the timeline
                    self._finish_slot(slot_ids[i], r)
        return sum(len(r.prompt) for r in admitted)

    # ------------------------------------------------------------------
    def _adopt_prefix(self, sid: int, r: Request) -> int:
        """Consult the per-LLM prefix index at admission (DESIGN.md
        §13).  On a hit the cached prefix blocks are adopted read-only
        via ``share_prefix`` and prefill resumes at the first uncached
        block.  Chunked engines only: the chunk machinery natively
        starts at any offset, whereas the whole-prompt path cannot
        resume mid-prompt.  Returns adopted tokens (0 = miss; always a
        BLOCK_TOKENS multiple ≤ len(prompt) − 1, so prefill still
        computes the logits the first generated token needs)."""
        idx = self.view.prefix_index
        if idx is None:
            return 0
        hit, bases = idx.lookup(r.prompt)
        if hit and self.view.share_prefix(sid, bases, hit):
            return hit
        return 0

    def admit_chunked(self, reqs: List[Request]) -> None:
        """Host-side admission for chunked prefill: reserve the prompt,
        bind a slot and mark it in-flight — no compute.  The chunk
        advance itself runs either serially (``run_chunk_job``) or as
        part of a fused group sweep (``FusedGroup.prefill``)."""
        with self.admit_span:
            # admission: same cumulative lifetime check as the unchunked
            # path; prompts reserve immediately, so only the not-yet-
            # reserved growth of earlier admits carries into ``pending``
            pending = 0
            for r in reqs[:len(self.free_slots())]:
                if not self.free_slots():
                    break
                if not self.can_admit(r, pending):
                    continue
                slot = self.free_slots()[0]
                sid = self._next_seq
                self._next_seq += 1
                used_before = self.view.used
                hit = self._adopt_prefix(sid, r)
                ok = self.view.append_tokens(sid, len(r.prompt) - hit)
                if not ok and hit:
                    # adoption landed but the private remainder could not
                    # be carved out — drop the shared refs and admit the
                    # request unshared (the lifetime check covered it)
                    self.view.free_seq(sid)
                    hit = 0
                    ok = self.view.append_tokens(sid, len(r.prompt))
                assert ok
                pending += self.lifetime_blocks(r) - (self.view.used
                                                      - used_before)
                self.slots[slot] = r
                self.slot_seq[slot] = sid
                r._seq_id = sid
                # prefill resumes at the first uncached token — a partial
                # hit leaves prefill_done/first_token stamping untouched
                # (they stamp at prompt completion, whenever that is)
                self._prefilling[slot] = hit

    def export_prefill_job(self) -> Optional[PrefillJob]:
        """Snapshot the in-flight chunk rows the fused prefill sweep
        (or the serial chunk step) needs from this engine.  Returns
        None when nothing is prefilling."""
        if not self._prefilling:
            return None
        C = self.chunk_tokens
        slots = sorted(self._prefilling)
        B = len(slots)
        toks = np.zeros((B, C), np.int32)
        offs = np.zeros((B,), np.int32)
        clens = np.zeros((B,), np.int32)
        for i, sl in enumerate(slots):
            r = self.slots[sl]
            pos = self._prefilling[sl]
            n = min(C, len(r.prompt) - pos)
            toks[i, :n] = r.prompt[pos:pos + n]
            offs[i] = pos
            clens[i] = n
        return PrefillJob(slots=slots, reqs=[self.slots[sl] for sl in slots],
                          seq_ids=[int(self.slot_seq[sl]) for sl in slots],
                          toks=toks, offs=offs, clens=clens)

    def apply_prefill_result(self, job: PrefillJob, nxt: np.ndarray) -> int:
        """Commit one chunk advance back into engine bookkeeping
        (shared by the serial and fused prefill paths).  ``nxt`` is the
        greedy next token per job row (used when a prompt completes)."""
        done_tokens = 0
        for i, sl in enumerate(job.slots):
            r = self.slots[sl]
            self._prefilling[sl] += int(job.clens[i])
            done_tokens += int(job.clens[i])
            if self._prefilling[sl] >= len(r.prompt):
                del self._prefilling[sl]
                # prompt complete → its full blocks are final (decode
                # appends strictly past the prompt): index them now so
                # later requests can adopt — before _finish_slot, so
                # even prefill-only requests populate the cache (the
                # index's own refs keep the blocks alive)
                idx = self.view.prefix_index
                if idx is not None:
                    idx.insert(r.prompt, self.view.seqs[r._seq_id].bases)
                if r.max_new_tokens <= 0:
                    # prefill-only request: finalize at prompt end
                    r.first_token = self.clock()
                    self._finish_slot(sl, r)
                    continue
                # first generated token — same reserve-then-commit as
                # the unchunked path (decode retries on overcommit)
                if self.view.append_tokens(r._seq_id, 1):
                    r.output.append(int(nxt[i]))
                    r.first_token = self.clock()
                    if self.emit is not None:
                        self.emit("token", r, int(nxt[i]))
                    if r.done:
                        # max_new_tokens == 1 completes at prefill
                        self._finish_slot(sl, r)
        return done_tokens

    def run_chunk_job(self, job: Optional[PrefillJob] = None) -> int:
        """Advance one chunk job serially (attention families): one
        jitted step over a power-of-2 row bucket.  ``job`` defaults to
        the engine's in-flight chunks (``export_prefill_job``)."""
        step, prep, launch, sync, commit = self.prefill_spans
        with step:
            with prep:
                job = job or self.export_prefill_job()
                B = len(job)
                Bp = _next_pow2(B)
                toks, offs, clens, table = _pad_rows(
                    Bp, (job.toks, 0), (job.offs, 0), (job.clens, 0),
                    (self.view.block_table(job.seq_ids, self.max_blocks),
                     -1))
                args = (jnp.asarray(toks), jnp.asarray(offs),
                        jnp.asarray(clens), self.pool.k, self.pool.v,
                        jnp.asarray(table))
            with launch:
                pool_k, pool_v, logits = self._chunk_fn(
                    self.params, self.model_index, *args)
            self.pool.k, self.pool.v = pool_k, pool_v
            with sync:
                nxt = np.asarray(jnp.argmax(logits[:B], axis=-1))
            with commit:
                return self.apply_prefill_result(job, nxt)

    def _prefill_chunked(self, reqs: List[Request]) -> int:
        """Admit new requests, then advance every in-flight prefill by
        one ``chunk_tokens`` window (one jitted step for the batch)."""
        self.admit_chunked(reqs)
        if not self._prefilling:
            return 0
        if self.cfg.ssm:
            return self._run_chunk_ssm()
        return self.run_chunk_job()

    def _run_chunk_ssm(self) -> int:
        """Chunk advance for pure-SSM engines (state carry, no pool)."""
        step, prep, launch, sync, commit = self.prefill_spans
        with step:
            with prep:
                job = self.export_prefill_job()
                sl_idx = jnp.asarray(np.array(job.slots))
                st = self.ssm_state[:, sl_idx]
                tail = self.conv_tail[:, sl_idx]
                # fresh sequences start from zero state
                fresh = jnp.asarray((job.offs == 0).astype(np.float32))
                st = st * (1.0 - fresh)[None, :, None, None, None]
                tail = tail * (1.0 - fresh[None, :, None, None]).astype(
                    tail.dtype)
                args = (jnp.asarray(job.toks), jnp.asarray(job.clens), st,
                        tail)
            with launch:
                logits, new_st, new_tail = self._chunk_fn(
                    self.params, self.model_index, *args)
            with commit:
                self.ssm_state = self.ssm_state.at[:, sl_idx].set(new_st)
                self.conv_tail = self.conv_tail.at[:, sl_idx].set(
                    new_tail.astype(self.conv_tail.dtype))
            with sync:
                nxt = np.asarray(jnp.argmax(logits, axis=-1))
            with commit:
                return self.apply_prefill_result(job, nxt)

    # ------------------------------------------------------------------
    def export_decode_job(self) -> Optional[DecodeJob]:
        """Snapshot the tensors the fused multi-LLM tick needs from this
        engine: active decode rows (prefilling slots are excluded until
        their prompt completes) plus per-row sequence identity for
        block-table resolution against the pool.  Returns None when the
        engine has no decode work this tick."""
        act = [s for s in self.active_slots() if s not in self._prefilling]
        if not act:
            return None
        reqs = [self.slots[i] for i in act]
        last = np.array([r.output[-1] if r.output else r.prompt[-1]
                         for r in reqs], np.int32)
        return DecodeJob(slots=act, reqs=reqs,
                         seq_ids=[r._seq_id for r in reqs], last_tok=last)

    def apply_decode_result(self, job: DecodeJob, nxt: np.ndarray) -> int:
        """Commit one decode step's sampled tokens back into engine and
        pool bookkeeping (shared by the serial and fused paths).

        Rows that cannot reserve their next-token block are rolled back
        (indices recorded in ``self._rolled_rows`` for the caller to
        revert any non-idempotent per-step state, e.g. SSM carries).
        """
        done_tokens = 0
        self._rolled_rows = []
        for i, r in enumerate(job.reqs):
            r.output.append(int(nxt[i]))
            done_tokens += 1
            if r.done:
                if r.first_token < 0:
                    # prefill's first token rolled back on overcommit
                    # and decode regenerated it — TTFT ends here
                    r.first_token = self.clock()
                if self.emit is not None:
                    self.emit("token", r, int(nxt[i]))
                self._finish_slot(job.slots[i], r)
            else:
                ok = self.view.append_tokens(job.seq_ids[i], 1)
                if ok:
                    if r.first_token < 0:
                        r.first_token = self.clock()
                    # emit only AFTER the reserve validated: a token that
                    # rolls back below was never committed and must not
                    # reach a stream
                    if self.emit is not None:
                        self.emit("token", r, int(nxt[i]))
                if not ok:
                    # quota overcommit (admitted sequences' future
                    # growth is not reserved, and adapt_quotas may
                    # shrink the quota): a silent miss here would
                    # desync lens/pos and corrupt the sequence's KV on
                    # the next step.  Instead roll the token back and
                    # retry next tick — lens is unchanged, so the
                    # retry recomputes the same position (greedy ⇒ the
                    # same token) once another sequence frees blocks.
                    # The KV rewrite is idempotent; decode() reverts
                    # SSM state for rolled-back rows.
                    r.output.pop()
                    done_tokens -= 1
                    self._rolled_rows.append(i)
        # stall escape: if EVERY row rolled back and nothing finished,
        # no sequence can ever free blocks for the others — after two
        # such ticks, preempt the youngest sequence (evict its cache,
        # restart it from scratch via the scheduler queue; greedy ⇒ it
        # regenerates the same tokens) so the rest can proceed.
        rollbacks = len(self._rolled_rows)
        if rollbacks and rollbacks == len(job.reqs):
            self._stall_ticks += 1
            if self._stall_ticks >= 2:
                self._preempt_youngest()
                self._stall_ticks = 0
        else:
            self._stall_ticks = 0
        return done_tokens

    def _preempt_youngest(self) -> None:
        """Evict the most recently admitted sequence: free its cache,
        reset its progress, and hand the request back via
        ``self.preempted`` (the scheduler re-queues it; direct engine
        users resubmit through ``prefill``).  Restart-from-scratch is
        exact for every family — a fresh prefill rebuilds KV and SSM
        state alike."""
        act = [s for s in self.active_slots() if s not in self._prefilling]
        if not act:
            return
        slot = max(act, key=lambda s: self.slot_seq[s])
        r = self.slots[slot]
        self.view.free_seq(int(self.slot_seq[slot]))
        self.slots[slot] = None
        self.slot_seq[slot] = -1
        r.output.clear()
        r.prefill_done = -1.0
        r.first_token = -1.0
        if self.emit is not None:
            self.emit("reset", r, -1)
        self.preempted.append(r)

    def decode(self, job: Optional[DecodeJob] = None) -> int:
        """One decode step over all active slots.  Returns #tokens."""
        step, prep, launch, sync, commit = self.decode_spans
        with step:
            with prep:
                job = job or self.export_decode_job()
                if job is None:
                    return 0
                B = len(job)
                # lengths include the reserved current token
                lens = self.view.seq_lens(job.seq_ids)
                table = self.view.block_table(job.seq_ids, self.max_blocks)
                last_tok = job.last_tok
                if not self.cfg.ssm:
                    # power-of-2 row bucket (padded rows: len 1, table −1
                    # — one masked garbage softmax, discarded below).
                    # SSM/hybrid keep exact rows: their per-slot state
                    # scatter must not see duplicated padded slot indices.
                    Bp = _next_pow2(B)
                    if Bp != B:
                        last_tok, lens, table = _pad_rows(
                            Bp, (job.last_tok, 0), (lens, 1), (table, -1))
                sl = jnp.asarray(np.array(job.slots))
                ssm_state = self.ssm_state[:, sl] if self.cfg.ssm else None
                conv_tail = self.conv_tail[:, sl] if self.cfg.ssm else None
                args = (jnp.asarray(last_tok), jnp.asarray(lens),
                        self.pool.k, self.pool.v, jnp.asarray(table),
                        ssm_state, conv_tail)
            with launch:
                pool_k, pool_v, logits, new_ssm, new_tail = self._decode_fn(
                    self.params, self.model_index, *args)
            self.pool.k, self.pool.v = pool_k, pool_v
            if self.cfg.ssm:
                with commit:
                    prev_ssm, prev_tail = self.ssm_state, self.conv_tail
                    self.ssm_state = self.ssm_state.at[:, sl].set(new_ssm)
                    self.conv_tail = self.conv_tail.at[:, sl].set(new_tail)
            with sync:
                nxt = np.asarray(jnp.argmax(logits[:B], axis=-1))
            with commit:
                toks = self.apply_decode_result(job, nxt)
                if self.cfg.ssm and self._rolled_rows:
                    # rolled-back rows must retry from the PRE-step state:
                    # the SSM carry is not idempotent (re-advancing it on
                    # retry would silently change the eventually-committed
                    # token)
                    rs = jnp.asarray(np.array([job.slots[i]
                                               for i in self._rolled_rows]))
                    self.ssm_state = self.ssm_state.at[:, rs].set(
                        prev_ssm[:, rs])
                    self.conv_tail = self.conv_tail.at[:, rs].set(
                        prev_tail[:, rs])
            return toks

    def has_decode_work(self) -> bool:
        return any(s not in self._prefilling for s in self.active_slots())

    def has_prefill_work(self) -> bool:
        return bool(self._prefilling)

    # ------------------------------------------------------------------
    def fusion_signature(self) -> Optional[tuple]:
        """Key under which this engine's decode step can be fused with
        other colocated engines (DESIGN.md §2): engines whose signature
        matches share one stacked-weights jitted step.  ``None`` marks
        the engine fusion-ineligible (SSM/hybrid keep their own scan;
        MoE keeps its own routed FFN) — the scheduler falls back to the
        serial per-engine tick for those.

        The signature pins everything that shapes the stacked param
        tree and the fused computation: layer geometry, head layout,
        projection extras, vocab padding, param dtype, the device
        block-table width and the chunked-prefill window (the fused
        prefill sweep needs one common chunk shape).
        """
        cfg = self.cfg
        if cfg.family not in ("dense", "vlm", "audio") or cfg.ssm \
                or cfg.moe:
            return None
        return (cfg.family, cfg.n_layers, cfg.d_model, cfg.n_heads,
                cfg.n_kv_heads, cfg.hd, cfg.d_ff, cfg.vocab_size,
                cfg.qkv_bias, cfg.qk_norm, cfg.rope_theta, cfg.rms_eps,
                cfg.tie_embeddings, cfg.frontend_dim, cfg.n_prefix_tokens,
                str(self.params["tok"]["embed"].dtype), self.max_blocks,
                self.chunk_tokens)


# ---------------------------------------------------------------------------
# jitted step implementations (XLA reference path)
#
# Every impl takes a STACKED param tree ([M, ...] leading model axis)
# plus a model index; the per-model slice happens at trace time inside
# the program (``_select_model``), so fused-group members and the M=1
# singleton case run off the same buffers with zero weight copies.
# ---------------------------------------------------------------------------
def _prefill_chunk_impl(params, midx, toks, offs, clens, pool_k, pool_v,
                        table, *, cfg: ModelConfig):
    """One chunked-prefill step: process C prompt tokens per sequence at
    absolute positions offs+i, writing KV into the pool and attending
    against everything written so far.  Garbage KV at padded positions
    (i ≥ clens) lands on future decode slots, which decode overwrites
    before attending — harmless by construction."""
    _note_trace("prefill_chunk")
    p = _select_model(params, midx)
    B, C = toks.shape
    x = p["tok"]["embed"][toks]
    positions = offs[:, None] + jnp.arange(C)[None, :]
    lp = p["layers"]

    attn_li = 0
    for li in range(cfg.n_layers):
        h = rms_norm(x, lp["ln1"][li], cfg.rms_eps)
        q, k, v = attn_qkv(h, lp, li, cfg, positions)
        pool_k, pool_v = cache_ops.write_tokens(
            pool_k, pool_v, k, v, table, offs, attn_li, cfg.n_kv_heads)
        o = cache_ops.paged_chunk_attention(
            q, pool_k, pool_v, table, offs, attn_li, cfg.n_kv_heads)
        x = x + o.reshape(B, C, -1) @ lp["wo"][li]
        attn_li += 1
        h = rms_norm(x, lp["ln2"][li], cfg.rms_eps)
        if cfg.family == "moe":
            out, _ = MoE.moe_ffn_dropless(h, lp, li, cfg)
            x = x + out
        else:
            x = x + mlp(h, lp, li)

    idx = jnp.maximum(clens - 1, 0)
    x_last = jnp.take_along_axis(x, idx[:, None, None], axis=1)[:, 0]
    logits = lm_logits(x_last, p["tok"], cfg)[..., :cfg.vocab_size]
    return pool_k, pool_v, logits


def _prefill_chunk_ssm_impl(params, midx, toks, clens, ssm_state, conv_tail,
                            *, cfg: ModelConfig):
    """Chunked prefill for pure-SSM models: the mixer's conv-tail +
    state carry IS the chunk boundary.  ``clens`` masks padded chunk
    positions (dt=0 ⇒ state frozen past the true chunk length)."""
    _note_trace("prefill_chunk_ssm")
    p = _select_model(params, midx)
    B, C = toks.shape
    x = p["tok"]["embed"][toks]
    mask = jnp.arange(C)[None, :] < clens[:, None]
    lp = p["layers"]
    new_ssm = ssm_state
    new_tail = conv_tail
    for li in range(cfg.n_layers):
        h = rms_norm(x, lp["ln1"][li], cfg.rms_eps)
        out, st, tail = M2.mamba2_mixer(
            h, lp, li, cfg, conv_tail=conv_tail[li],
            ssm_state=ssm_state[li], return_cache=True, length_mask=mask)
        x = x + out
        new_ssm = new_ssm.at[li].set(st)
        new_tail = new_tail.at[li].set(tail.astype(new_tail.dtype))
    idx = jnp.maximum(clens - 1, 0)
    x_last = jnp.take_along_axis(x, idx[:, None, None], axis=1)[:, 0]
    logits = lm_logits(x_last, p["tok"], cfg)[..., :cfg.vocab_size]
    return logits, new_ssm, new_tail


def _prefill_impl(params, midx, toks, lens, pool_k, pool_v, table, *,
                  cfg: ModelConfig):
    """Prefill: full causal forward, write KV/state caches, last logits."""
    _note_trace("prefill")
    p = _select_model(params, midx)
    B, S = toks.shape
    x = p["tok"]["embed"][toks]
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    lp = p["layers"]

    new_ssm = None
    new_tail = None
    if cfg.ssm:
        sc = cfg.ssm
        conv_dim = cfg.d_inner + 2 * sc.n_groups * sc.d_state
        new_ssm = jnp.zeros((cfg.n_layers, B, cfg.n_ssm_heads, sc.head_dim,
                             sc.d_state), jnp.float32)
        new_tail = jnp.zeros((cfg.n_layers, B, sc.conv_kernel - 1, conv_dim),
                             x.dtype)

    def attn_layer(x, li, attn_li, lp_attn, pool_k, pool_v):
        h = rms_norm(x, lp_attn["ln1"][li], cfg.rms_eps)
        q, k, v = attn_qkv(h, lp_attn, li, cfg, positions)
        o = causal_attention(q, k, v)
        pool_k, pool_v = cache_ops.write_tokens(
            pool_k, pool_v, k, v, table, jnp.zeros((B,), jnp.int32),
            attn_li, cfg.n_kv_heads)
        x = x + o.reshape(B, S, -1) @ lp_attn["wo"][li]
        return x, pool_k, pool_v

    # NOTE: python loop over layers (engine path is CPU small-model;
    # lowering cost is acceptable and lets attn-layer cache indices be
    # static).
    attn_li = 0
    for li in range(cfg.n_layers):
        if cfg.family in ("dense", "vlm", "audio", "moe"):
            x, pool_k, pool_v = attn_layer(x, li, attn_li, lp, pool_k, pool_v)
            attn_li += 1
            h = rms_norm(x, lp["ln2"][li], cfg.rms_eps)
            if cfg.family == "moe":
                out, _ = MoE.moe_ffn_dropless(h, lp, li, cfg)
                x = x + out
            else:
                x = x + mlp(h, lp, li)
        else:  # ssm / hybrid
            h = rms_norm(x, lp["ln1"][li], cfg.rms_eps)
            out, fstate, tail = M2.mamba2_mixer(
                h, lp, li, cfg, return_cache=True,
                length_mask=positions < lens[:, None])
            x = x + out
            new_ssm = new_ssm.at[li].set(fstate)
            new_tail = new_tail.at[li].set(tail.astype(x.dtype))
            if cfg.family == "hybrid" and (li + 1) % cfg.attn_every == 0:
                sa = p["shared_attn"]
                x, pool_k, pool_v = attn_layer(x, 0, attn_li, sa,
                                               pool_k, pool_v)
                attn_li += 1
                h2 = rms_norm(x, sa["ln2"][0], cfg.rms_eps)
                x = x + mlp(h2, sa, 0)

    # logits at the true last prompt token
    idx = jnp.maximum(lens - 1, 0)
    x_last = jnp.take_along_axis(x, idx[:, None, None], axis=1)[:, 0]
    logits = lm_logits(x_last, p["tok"], cfg)[..., :cfg.vocab_size]
    return pool_k, pool_v, logits, new_ssm, new_tail


def _decode_impl(params, midx, last_tok, lens, pool_k, pool_v, table,
                 ssm_state, conv_tail, *, cfg: ModelConfig):
    """One decode step: write KV of current token, attend, next logits.

    ``lens`` includes the current token (its slot is already reserved);
    its position is lens-1.
    """
    _note_trace("decode")
    p = _select_model(params, midx)
    B = last_tok.shape[0]
    x = p["tok"]["embed"][last_tok]                         # [B,d]
    pos = (lens - 1).astype(jnp.int32)
    lp = p["layers"]

    new_ssm = ssm_state
    new_tail = conv_tail

    def attn_layer(x, li, attn_li, lp_attn, pool_k, pool_v):
        h = rms_norm(x, lp_attn["ln1"][li], cfg.rms_eps)
        q, k, v = attn_qkv(h[:, None, :], lp_attn, li, cfg, pos[:, None])
        q, k, v = q[:, 0], k[:, 0], v[:, 0]                 # [B,H,hd]
        pool_k, pool_v = cache_ops.write_tokens(
            pool_k, pool_v, k[:, None], v[:, None], table, pos,
            attn_li, cfg.n_kv_heads)
        o = cache_ops.paged_decode_attention(
            q, pool_k, pool_v, table, lens, attn_li, cfg.n_kv_heads)
        x = x + o.reshape(B, -1) @ lp_attn["wo"][li]
        return x, pool_k, pool_v

    attn_li = 0
    for li in range(cfg.n_layers):
        if cfg.family in ("dense", "vlm", "audio", "moe"):
            x, pool_k, pool_v = attn_layer(x, li, attn_li, lp, pool_k, pool_v)
            attn_li += 1
            h = rms_norm(x, lp["ln2"][li], cfg.rms_eps)
            if cfg.family == "moe":
                out, _ = MoE.moe_ffn_dropless(h[:, None, :], lp, li, cfg)
                x = x + out[:, 0]
            else:
                x = x + mlp(h, lp, li)
        else:
            h = rms_norm(x, lp["ln1"][li], cfg.rms_eps)
            out, tail_i, st_i = M2.mamba2_decode_step(
                h, lp, li, cfg, conv_tail[li], ssm_state[li])
            x = x + out
            new_ssm = new_ssm.at[li].set(st_i)
            new_tail = new_tail.at[li].set(tail_i)
            if cfg.family == "hybrid" and (li + 1) % cfg.attn_every == 0:
                sa = p["shared_attn"]
                x, pool_k, pool_v = attn_layer(x, 0, attn_li, sa,
                                               pool_k, pool_v)
                attn_li += 1
                h2 = rms_norm(x, sa["ln2"][0], cfg.rms_eps)
                x = x + mlp(h2, sa, 0)

    logits = lm_logits(x, p["tok"], cfg)[..., :cfg.vocab_size]
    return pool_k, pool_v, logits, new_ssm, new_tail


def _fused_decode_impl(params, toks, lens, pool_k, pool_v, tables, *,
                       cfg: ModelConfig):
    """Fused multi-LLM decode step (DESIGN.md §2).

    One jitted sweep advances every colocated same-architecture engine
    by one token: model-private matmuls run as batched contractions over
    the stacked weight axis M, while KV writes and paged attention
    flatten all M×R rows into a single pool operation — the per-row
    block tables already resolve each row to its own model's physical
    head-blocks, so the shared arena needs no per-model dispatch.

    params: engine param trees stacked on a leading [M] axis
    toks: [M, R] int32 last tokens (padded rows are masked by the
        caller; their table entries are −1 so their KV writes drop)
    lens: [M, R] lengths incl. the current token (1 on padded rows)
    tables: [M, R, W] int32 group bases (−1 padded)
    Returns (pool_k, pool_v, logits [M, R, vocab]).
    """
    _note_trace("fused_decode")
    M, R = toks.shape
    W = tables.shape[2]
    lp = params["layers"]
    n_h, n_kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd

    x = jax.vmap(lambda e, t: e[t])(params["tok"]["embed"], toks)  # [M,R,d]
    pos = (lens - 1).astype(jnp.int32)                             # [M,R]
    flat_table = tables.reshape(M * R, W)
    flat_pos = pos.reshape(M * R)
    flat_lens = lens.reshape(M * R)

    # per-layer semantics (projections, bias, qk_norm, rope, SwiGLU,
    # final logits) come from the SAME helpers the serial path uses,
    # vmapped over the stacked model axis — the fused path cannot
    # drift from models/layers.py
    for li in range(cfg.n_layers):
        def qkv_m(xm, lpm, posm, li=li):
            h = rms_norm(xm, lpm["ln1"][li], cfg.rms_eps)
            q, k, v = attn_qkv(h[:, None, :], lpm, li, cfg, posm[:, None])
            return q[:, 0], k[:, 0], v[:, 0]                  # [R,{H,KV},hd]

        def post_m(xm, om, lpm, li=li):
            xm = xm + om.reshape(om.shape[0], -1) @ lpm["wo"][li]
            h = rms_norm(xm, lpm["ln2"][li], cfg.rms_eps)
            return xm + mlp(h, lpm, li)

        q, k, v = jax.vmap(qkv_m)(x, lp, pos)
        pool_k, pool_v = cache_ops.write_tokens(
            pool_k, pool_v, k.reshape(M * R, 1, n_kv, hd),
            v.reshape(M * R, 1, n_kv, hd), flat_table, flat_pos, li, n_kv)
        phys = cache_ops.resolve_physical_blocks(flat_table, li, n_kv)
        o = cache_ops.fused_paged_decode_attention(
            q.reshape(M * R, n_h, hd), pool_k, pool_v, phys, flat_lens)
        x = jax.vmap(post_m)(x, o.reshape(M, R, n_h, hd), lp)

    logits = jax.vmap(lambda xm, tokm: lm_logits(xm, tokm, cfg))(
        x, params["tok"])
    return pool_k, pool_v, logits[..., :cfg.vocab_size]


def _fused_prefill_chunk_impl(params, toks, offs, clens, pool_k, pool_v,
                              tables, *, cfg: ModelConfig):
    """Fused multi-LLM chunked-prefill sweep (DESIGN.md §2).

    One jitted step advances every in-flight prompt chunk of every
    colocated same-architecture engine: projections/MLP are batched
    contractions over the stacked model axis M, while KV writes and
    chunk attention flatten all M×R rows over per-row-resolved physical
    block ids — the prefill-phase mirror of ``_fused_decode_impl``.

    params: engine param trees stacked on a leading [M] axis
    toks: [M, R, C] int32 chunk tokens (zero on padded rows)
    offs: [M, R] absolute chunk start positions (0 on padded rows)
    clens: [M, R] true chunk lengths (0 on padded rows)
    tables: [M, R, W] int32 group bases (−1 on padded rows, so their
        KV writes drop; their attention reads are discarded host-side)
    Returns (pool_k, pool_v, logits [M, R, vocab]).
    """
    _note_trace("fused_prefill_chunk")
    M, R, C = toks.shape
    W = tables.shape[2]
    lp = params["layers"]
    n_h, n_kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd

    x = jax.vmap(lambda e, t: e[t])(params["tok"]["embed"], toks)  # [M,R,C,d]
    positions = offs[..., None] + jnp.arange(C)[None, None, :]     # [M,R,C]
    flat_table = tables.reshape(M * R, W)
    flat_offs = offs.reshape(M * R)

    for li in range(cfg.n_layers):
        def qkv_m(xm, lpm, posm, li=li):
            h = rms_norm(xm, lpm["ln1"][li], cfg.rms_eps)
            return attn_qkv(h, lpm, li, cfg, posm)       # [R,C,{H,KV},hd]

        def post_m(xm, om, lpm, li=li):
            xm = xm + om.reshape(R, C, -1) @ lpm["wo"][li]
            h = rms_norm(xm, lpm["ln2"][li], cfg.rms_eps)
            return xm + mlp(h, lpm, li)

        q, k, v = jax.vmap(qkv_m)(x, lp, positions)
        pool_k, pool_v = cache_ops.write_tokens(
            pool_k, pool_v, k.reshape(M * R, C, n_kv, hd),
            v.reshape(M * R, C, n_kv, hd), flat_table, flat_offs, li, n_kv)
        phys = cache_ops.resolve_physical_blocks(flat_table, li, n_kv)
        o = cache_ops.fused_paged_chunk_attention(
            q.reshape(M * R, C, n_h, hd), pool_k, pool_v, phys, flat_offs)
        x = jax.vmap(post_m)(x, o.reshape(M, R, C, n_h, hd), lp)

    idx = jnp.maximum(clens - 1, 0)                                # [M,R]
    x_last = jnp.take_along_axis(x, idx[..., None, None], axis=2)[:, :, 0]
    logits = jax.vmap(lambda xm, tokm: lm_logits(xm, tokm, cfg))(
        x_last, params["tok"])
    return pool_k, pool_v, logits[..., :cfg.vocab_size]


# ---------------------------------------------------------------------------
# shared jit cache
# ---------------------------------------------------------------------------
# (impl, donated arg positions).  Donated buffers are the pool arena
# (or the SSM carry for the ssm chunk step) — consumed and returned.
_IMPL_TABLE = {
    "prefill": (_prefill_impl, (4, 5)),
    "decode": (_decode_impl, (4, 5)),
    "chunk": (_prefill_chunk_impl, (5, 6)),
    "chunk_ssm": (_prefill_chunk_ssm_impl, (4, 5)),
    "fused_decode": (_fused_decode_impl, (3, 4)),
    "fused_prefill_chunk": (_fused_prefill_chunk_impl, (4, 5)),
}


@lru_cache(maxsize=None)
def jitted_step(kind: str, cfg_key: ModelConfig):
    """Memoized jitted step, shared by every engine with the same
    *geometry* (``Engine.cfg_key`` strips the model name).  Without
    this cache each engine owns a private ``jax.jit`` wrapper and
    colocated instances of one architecture recompile identical
    programs N times."""
    impl, donate = _IMPL_TABLE[kind]
    # keep the impl's name on the partial: jit names the program (and
    # its compile events and profiler spans) after it
    step = partial(impl, cfg=cfg_key)
    step.__name__ = impl.__name__
    return jax.jit(step, donate_argnums=donate)
