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
(constant-size) lives in per-slot dense arrays.  Those arrays stay
whole on the device: every step that reads or writes state takes them
donated, reads and writes its rows' slots inside the program and
returns them in place, so no step copies them.
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
from repro.models import granite as G
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
#   prep    host work before the jitted call (tables, padding, the rows'
#           next-token reservations, transfers to the device)
#   launch  the jitted call until it returns (it dispatches, asynchronously)
#   sync    the host blocked on the step's sampled tokens
#   commit  ``apply_*_result``
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
    # rows whose next-token block ``Engine.reserve_decode`` could not
    # reserve: their step commits nothing and keeps their SSM state
    held: List[int] = field(default_factory=list)

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
        Attention models chunk against the pool, pure-SSM models carry
        the mixer's state across chunks, and layer-pattern models
        (``ModelConfig.layer_types``) do both; a zamba2-style hybrid
        (shared attention every ``attn_every`` layers) keeps
        whole-prompt prefill."""
        self.cfg = cfg
        # host spans of this LLM's steps and admissions
        self.decode_spans = step_spans("decode", cfg.name)
        self.prefill_spans = step_spans("prefill", cfg.name)
        self.admit_span = span(f"mux.admit.{cfg.name}")
        self.moe_event = f"mux.moe.{cfg.name}"
        self.held_event = f"mux.ssm_held.{cfg.name}"
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
        self.chunk_tokens = (None if cfg.family == "hybrid"
                             and cfg.layer_types is None else chunk_tokens)
        self.slots: List[Optional[Request]] = [None] * max_slots
        self.slot_seq: np.ndarray = np.full(max_slots, -1, np.int64)
        self.finished: List[Request] = []
        self.preempted: List[Request] = []      # evicted by stall escape
        self._prefilling: Dict[int, int] = {}   # slot → next prompt pos
        self._stall_ticks = 0
        self._next_seq = 0
        self._rng = np.random.default_rng(rng_seed)
        # token-emission hook (serving/frontend.py): called as
        # ``emit(event, request, token)`` at every COMMITTED progress
        # point — "token" (an output token whose row reserved its next
        # block; a held row's token never emits), "finish" (request
        # finalized), "reset" (an eviction cleared the request's
        # progress; previously streamed tokens are void).  Installed by
        # ``MuxScheduler.set_emit`` (which re-applies it to engines
        # rebuilt by crash recovery); None = no streaming consumer.
        self.emit: Optional[Callable[[str, Request, int], None]] = None

        # SSM per-slot state, one entry per SSM layer, held whole: the
        # steps take it donated and index their rows' slots inside.  A
        # slot's conv tail is one flat row of its K-1 positions, so the
        # chip lays the array out row-major (a [.., K-1, conv] array
        # gets its slots laid minor to the K-1 axis, which every step
        # would copy out of and back)
        if cfg.ssm:
            sc = cfg.ssm
            conv_dim = cfg.d_inner + 2 * sc.n_groups * sc.d_state
            self.ssm_state = jnp.zeros(
                (cfg.n_ssm_layers, max_slots, cfg.n_ssm_heads, sc.head_dim,
                 sc.d_state), jnp.float32)
            self.conv_tail = jnp.zeros(
                (cfg.n_ssm_layers, max_slots, (sc.conv_kernel - 1) * conv_dim),
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
                (self.pool.k, self.pool.v, logits, self.ssm_state,
                 self.conv_tail) = self._prefill_fn(
                    self.params, self.model_index, *args)
            # sample first token
            with sync:
                nxt = np.asarray(jnp.argmax(logits[:len(admitted)],
                                            axis=-1))
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

        toks, lens = _pad_rows(Bp, (toks, 0), (lens, 0))
        table = self._row_table(
            self.view.block_table(seq_ids, self.max_blocks), slot_ids, Bp)
        return ((jnp.asarray(toks), jnp.asarray(lens), self.pool.k,
                 self.pool.v, jnp.asarray(table)) + self._state(),
                slot_ids, seq_ids)

    def _row_table(self, table: np.ndarray, slots, rows: int) -> np.ndarray:
        """The step's rows' cache addresses, padded to ``rows``: the KV
        block ``table`` (−1 padded), and for a model with SSM layers
        each row's state slot as one more column (``max_slots`` for a
        padded row: past the state arrays, so the step's read of it
        clamps and its write drops)."""
        table, = _pad_rows(rows, (table, -1))
        if self.cfg.ssm:
            slots, = _pad_rows(rows, (np.asarray(slots, np.int32),
                                      self.max_slots))
            table = np.concatenate([table, slots[:, None]], axis=1)
        return table

    def _state(self) -> tuple:
        """The SSM state and conv tail, whole, as a step's last two
        arguments (it consumes them and returns them updated); none for
        a model without SSM layers."""
        return (self.ssm_state, self.conv_tail) if self.cfg.ssm else ()

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
        """Advance one chunk job serially: one jitted step over a
        power-of-2 row bucket.  ``job`` defaults to the engine's
        in-flight chunks (``export_prefill_job``).  Attention layers
        write and attend the pool's KV; a model with SSM layers carries
        its rows' state through the chunk inside the step (a row whose
        chunk starts its prompt starts from zero state)."""
        step, prep, launch, sync, commit = self.prefill_spans
        with step:
            with prep:
                job = job or self.export_prefill_job()
                B = len(job)
                Bp = _next_pow2(B)
                toks, offs, clens = _pad_rows(
                    Bp, (job.toks, 0), (job.offs, 0), (job.clens, 0))
                table = self._row_table(self.view.block_table(
                    job.seq_ids, self.max_blocks), job.slots, Bp)
                # a pure-SSM model's chunk step has no KV to write
                arena = () if self.cfg.family == "ssm" else (self.pool.k,
                                                             self.pool.v)
                args = (jnp.asarray(toks), jnp.asarray(offs),
                        jnp.asarray(clens), *arena, jnp.asarray(table),
                        *self._state())
            with launch:
                out = self._chunk_fn(self.params, self.model_index, *args)
            if arena:
                self.pool.k, self.pool.v = out[:2]
            logits = out[len(arena)]
            if self.cfg.ssm:
                self.ssm_state, self.conv_tail = out[-2:]
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
        return self.run_chunk_job()

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

    def reserve_decode(self, job: DecodeJob) -> None:
        """Reserve each row's next-token block before its step runs, in
        row order; a row the step will finish reserves nothing.  A row
        that cannot reserve is held (``job.held``): quota overcommit
        (admitted sequences' future growth is not reserved, and
        adapt_quotas may shrink the quota) must not desync lens/pos and
        corrupt the sequence's KV on the next step, so its token is
        dropped after the step and retried next tick at the same
        position — lens is unchanged, the KV rewrite is idempotent,
        greedy gives the same token once another sequence frees blocks.
        Its SSM state is not written (the step sees its slot as
        ``max_slots``): the carry is not idempotent, and re-advancing
        it on retry would change the eventually committed token."""
        job.held = [i for i, r in enumerate(job.reqs)
                    if len(r.output) + 1 < r.max_new_tokens
                    and not self.view.append_tokens(job.seq_ids[i], 1)]
        if job.held and self.cfg.ssm:
            event(self.held_event, len(job.held))

    def apply_decode_result(self, job: DecodeJob, nxt: np.ndarray) -> int:
        """Commit one decode step's sampled tokens back into engine and
        pool bookkeeping (shared by the serial and fused paths).  The
        rows ``reserve_decode`` held commit nothing."""
        done_tokens = 0
        held = set(job.held)
        for i, r in enumerate(job.reqs):
            if i in held:
                continue
            r.output.append(int(nxt[i]))
            done_tokens += 1
            if r.first_token < 0:
                # prefill's first token rolled back on overcommit and
                # decode regenerated it — TTFT ends here
                r.first_token = self.clock()
            if self.emit is not None:
                self.emit("token", r, int(nxt[i]))
            if r.done:
                self._finish_slot(job.slots[i], r)
        # stall escape: if EVERY row was held and nothing finished,
        # no sequence can ever free blocks for the others — after two
        # such ticks, preempt the youngest sequence (evict its cache,
        # restart it from scratch via the scheduler queue; greedy ⇒ it
        # regenerates the same tokens) so the rest can proceed.
        if held and len(held) == len(job.reqs):
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
                # lengths include the reserved current token; read
                # before the next one is reserved
                lens = self.view.seq_lens(job.seq_ids)
                table = self.view.block_table(job.seq_ids, self.max_blocks)
                self.reserve_decode(job)
                slots = np.array(job.slots, np.int32)
                slots[job.held] = self.max_slots
                # power-of-2 row bucket (padded rows: len 1, table −1 —
                # one masked garbage softmax, discarded below)
                Bp = _next_pow2(B)
                last_tok, lens = _pad_rows(Bp, (job.last_tok, 0), (lens, 1))
                args = (jnp.asarray(last_tok), jnp.asarray(lens),
                        self.pool.k, self.pool.v,
                        jnp.asarray(self._row_table(table, slots, Bp)),
                        self.ssm_state, self.conv_tail)
            with launch:
                (self.pool.k, self.pool.v, logits, self.ssm_state,
                 self.conv_tail) = self._decode_fn(
                    self.params, self.model_index, *args)
            with sync:
                if self.cfg.moe:
                    # with the tokens, in one transfer: the tokens routed
                    # to each held expert of each layer, kept beside the
                    # step's spans
                    logits, load = logits
                    nxt, load = jax.device_get(
                        (jnp.argmax(logits[:B], axis=-1), load))
                    event(self.moe_event, load)
                else:
                    nxt = np.asarray(jnp.argmax(logits[:B], axis=-1))
            with commit:
                return self.apply_decode_result(job, nxt)

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
# SSM state inside a step.  A model with SSM layers keeps each layer's
# state [L, slots, H, P, N] and conv tail [L, slots, (K-1)·conv] whole;
# the step gets its rows' slots as the block table's last column
# (``Engine._row_table``), reads each layer's rows where they lie and
# writes them back in place.  A slot past the arrays (a padded row, or a
# row whose step is held) reads clamped and writes nothing.
def _split_table(table):
    """(KV block table, state slots) of an SSM model's row table."""
    return table[:, :-1], table[:, -1]


def _read_state(ssm_state, conv_tail, i, slots, cfg: ModelConfig,
                fresh=None):
    """SSM layer ``i``'s state and conv tail [rows, K-1, conv] at
    ``slots``; zero in the rows where ``fresh`` (a chunk that starts its
    prompt)."""
    st = ssm_state.at[i, slots].get(mode="clip")
    tail = conv_tail.at[i, slots].get(mode="clip").reshape(
        len(slots), cfg.ssm.conv_kernel - 1, -1)
    if fresh is not None:
        st = jnp.where(fresh[:, None, None, None], 0, st)
        tail = jnp.where(fresh[:, None, None], 0, tail)
    return st, tail


def _write_state(ssm_state, conv_tail, i, slots, st, tail):
    """SSM layer ``i``'s new rows written back at ``slots``."""
    tail = tail.reshape(len(slots), -1).astype(conv_tail.dtype)
    return (ssm_state.at[i, slots].set(st, mode="drop"),
            conv_tail.at[i, slots].set(tail, mode="drop"))


def _prefill_chunk_impl(params, midx, toks, offs, clens, pool_k, pool_v,
                        table, ssm_state=None, conv_tail=None, *,
                        cfg: ModelConfig):
    """One chunked-prefill step: process C prompt tokens per sequence at
    absolute positions offs+i, writing KV into the pool and attending
    against everything written so far.  Garbage KV at padded positions
    (i ≥ clens) lands on future decode slots, which decode overwrites
    before attending — harmless by construction.  A layer-pattern model
    also carries its SSM state (``_pattern_chunk``)."""
    _note_trace("prefill_chunk")
    p = _select_model(params, midx)
    if cfg.layer_types is not None:
        return _pattern_chunk(p, toks, offs, clens, pool_k, pool_v,
                              *_split_table(table), ssm_state, conv_tail,
                              cfg)
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
            keep = jnp.arange(C)[None, :] < clens[:, None]
            x = x + MoE.moe_ffn_held(h, lp, li, cfg, keep)[0]
        else:
            x = x + mlp(h, lp, li)

    idx = jnp.maximum(clens - 1, 0)
    x_last = jnp.take_along_axis(x, idx[:, None, None], axis=1)[:, 0]
    logits = lm_logits(x_last, p["tok"], cfg)[..., :cfg.vocab_size]
    return pool_k, pool_v, logits


def _pattern_chunk(p, toks, offs, clens, pool_k, pool_v, table, slots,
                   ssm_state, conv_tail, cfg: ModelConfig):
    """Chunk step of a layer-pattern model (``models/granite.py``): each
    layer's mixer by the pattern — Mamba-2 carries its rows' SSM state
    and conv tail at its SSM-layer ordinal through the chunk (from zero
    where the chunk starts the prompt; ``clens`` freezes them past a
    row's true length), attention writes the chunk's KV at its
    attention-layer ordinal and attends everything written so far —
    then the MoE FFN, padded positions routed nowhere.
    Returns (pool_k, pool_v, logits, ssm_state, conv_tail)."""
    B, C = toks.shape
    x = G.embed(p, cfg, toks)
    positions = offs[:, None] + jnp.arange(C)[None, :]
    keep = jnp.arange(C)[None, :] < clens[:, None]
    fresh = offs == 0
    for li, (kind, oi) in enumerate(zip(cfg.layer_types,
                                        G.mixer_ordinals(cfg))):
        h = G.pre(x, p, li, cfg)
        if kind == "mamba":
            st, tail = _read_state(ssm_state, conv_tail, oi, slots, cfg,
                                   fresh)
            mixed, st, tail = M2.mamba2_mixer(
                h, p["mamba"], oi, cfg, conv_tail=tail, ssm_state=st,
                return_cache=True, length_mask=keep)
            ssm_state, conv_tail = _write_state(ssm_state, conv_tail, oi,
                                                slots, st, tail)
        else:
            pa = p["attn"]
            q, k, v = attn_qkv(h, pa, oi, cfg, positions)
            pool_k, pool_v = cache_ops.write_tokens(
                pool_k, pool_v, k, v, table, offs, oi, cfg.n_kv_heads)
            o = cache_ops.paged_chunk_attention(
                q, pool_k, pool_v, table, offs, oi, cfg.n_kv_heads)
            mixed = o.reshape(B, C, -1) @ pa["wo"][oi]
        x, _ = G.post(x, mixed, p, li, cfg, keep)
    idx = jnp.maximum(clens - 1, 0)
    x_last = jnp.take_along_axis(x, idx[:, None, None], axis=1)[:, 0]
    return pool_k, pool_v, G.logits(p, cfg, x_last), ssm_state, conv_tail


def _pattern_decode(p, last_tok, lens, pool_k, pool_v, table, slots,
                    ssm_state, conv_tail, cfg: ModelConfig):
    """Decode step of a layer-pattern model: the chunk step's layers
    for one token per row.  Padded rows (table −1) route nowhere.
    Returns (pool_k, pool_v, (logits, tokens routed to each held
    expert [layers, held]), ssm_state, conv_tail)."""
    B = last_tok.shape[0]
    x = G.embed(p, cfg, last_tok)                         # [B,d]
    pos = (lens - 1).astype(jnp.int32)
    keep = table[:, 0] >= 0
    loads = []
    for li, (kind, oi) in enumerate(zip(cfg.layer_types,
                                        G.mixer_ordinals(cfg))):
        h = G.pre(x, p, li, cfg)
        if kind == "mamba":
            st, tail = _read_state(ssm_state, conv_tail, oi, slots, cfg)
            mixed, tail, st = M2.mamba2_decode_step(
                h, p["mamba"], oi, cfg, tail, st)
            ssm_state, conv_tail = _write_state(ssm_state, conv_tail, oi,
                                                slots, st, tail)
        else:
            pa = p["attn"]
            q, k, v = attn_qkv(h[:, None, :], pa, oi, cfg, pos[:, None])
            pool_k, pool_v = cache_ops.write_tokens(
                pool_k, pool_v, k, v, table, pos, oi, cfg.n_kv_heads)
            o = cache_ops.paged_decode_attention(
                q[:, 0], pool_k, pool_v, table, lens, oi, cfg.n_kv_heads)
            mixed = o.reshape(B, -1) @ pa["wo"][oi]
        x, load = G.post(x, mixed, p, li, cfg, keep)
        loads.append(load)
    return (pool_k, pool_v, (G.logits(p, cfg, x), jnp.stack(loads)),
            ssm_state, conv_tail)


def _prefill_chunk_ssm_impl(params, midx, toks, offs, clens, table,
                            ssm_state, conv_tail, *, cfg: ModelConfig):
    """Chunked prefill for pure-SSM models: the mixer's conv-tail +
    state carry IS the chunk boundary.  Of ``table`` only the rows'
    slots are read (``_split_table``: no KV blocks); a chunk at offset 0
    starts from zero state.  ``clens`` masks padded chunk positions
    (dt=0 ⇒ state frozen past the true chunk length)."""
    _note_trace("prefill_chunk_ssm")
    p = _select_model(params, midx)
    B, C = toks.shape
    x = p["tok"]["embed"][toks]
    mask = jnp.arange(C)[None, :] < clens[:, None]
    _, slots = _split_table(table)
    lp = p["layers"]
    for li in range(cfg.n_layers):
        h = rms_norm(x, lp["ln1"][li], cfg.rms_eps)
        st, tail = _read_state(ssm_state, conv_tail, li, slots, cfg,
                               offs == 0)
        out, st, tail = M2.mamba2_mixer(
            h, lp, li, cfg, conv_tail=tail, ssm_state=st,
            return_cache=True, length_mask=mask)
        x = x + out
        ssm_state, conv_tail = _write_state(ssm_state, conv_tail, li, slots,
                                            st, tail)
    idx = jnp.maximum(clens - 1, 0)
    x_last = jnp.take_along_axis(x, idx[:, None, None], axis=1)[:, 0]
    logits = lm_logits(x_last, p["tok"], cfg)[..., :cfg.vocab_size]
    return logits, ssm_state, conv_tail


def _prefill_impl(params, midx, toks, lens, pool_k, pool_v, table,
                  ssm_state=None, conv_tail=None, *, cfg: ModelConfig):
    """Prefill: full causal forward, write KV/state caches, last logits.
    A model with SSM layers writes its rows' state at their slots
    (``_split_table``); a layer-pattern model runs its chunk step over
    the whole prompt from zero state."""
    _note_trace("prefill")
    p = _select_model(params, midx)
    B, S = toks.shape
    x = p["tok"]["embed"][toks]
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    lp = p["layers"]

    if cfg.ssm:
        table, slots = _split_table(table)
    if cfg.layer_types is not None:
        return _pattern_chunk(p, toks, jnp.zeros((B,), jnp.int32), lens,
                              pool_k, pool_v, table, slots, ssm_state,
                              conv_tail, cfg)

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
                keep = positions < lens[:, None]
                x = x + MoE.moe_ffn_held(h, lp, li, cfg, keep)[0]
            else:
                x = x + mlp(h, lp, li)
        else:  # ssm / hybrid
            h = rms_norm(x, lp["ln1"][li], cfg.rms_eps)
            out, fstate, tail = M2.mamba2_mixer(
                h, lp, li, cfg, return_cache=True,
                length_mask=positions < lens[:, None])
            x = x + out
            ssm_state, conv_tail = _write_state(ssm_state, conv_tail, li,
                                                slots, fstate, tail)
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
    return pool_k, pool_v, logits, ssm_state, conv_tail


def _decode_impl(params, midx, last_tok, lens, pool_k, pool_v, table,
                 ssm_state, conv_tail, *, cfg: ModelConfig):
    """One decode step: write KV of current token, attend, next logits.

    ``lens`` includes the current token (its slot is already reserved);
    its position is lens-1.  An MoE model returns, in place of the
    logits, (logits, tokens routed to each held expert [MoE layers,
    held]); padded rows (table −1) route nowhere.  A model with SSM
    layers advances its rows' state at their slots (``_split_table``);
    a layer-pattern model runs ``_pattern_decode``.
    """
    _note_trace("decode")
    p = _select_model(params, midx)
    if cfg.ssm:
        table, slots = _split_table(table)
    if cfg.layer_types is not None:
        return _pattern_decode(p, last_tok, lens, pool_k, pool_v, table,
                               slots, ssm_state, conv_tail, cfg)
    B = last_tok.shape[0]
    x = p["tok"]["embed"][last_tok]                         # [B,d]
    pos = (lens - 1).astype(jnp.int32)
    lp = p["layers"]

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
    loads = []
    for li in range(cfg.n_layers):
        if cfg.family in ("dense", "vlm", "audio", "moe"):
            x, pool_k, pool_v = attn_layer(x, li, attn_li, lp, pool_k, pool_v)
            attn_li += 1
            h = rms_norm(x, lp["ln2"][li], cfg.rms_eps)
            if cfg.family == "moe":
                out, _, load = MoE.moe_ffn_held(h, lp, li, cfg,
                                                table[:, 0] >= 0)
                x = x + out
                loads.append(load)
            else:
                x = x + mlp(h, lp, li)
        else:
            h = rms_norm(x, lp["ln1"][li], cfg.rms_eps)
            st, tail = _read_state(ssm_state, conv_tail, li, slots, cfg)
            out, tail, st = M2.mamba2_decode_step(h, lp, li, cfg, tail, st)
            x = x + out
            ssm_state, conv_tail = _write_state(ssm_state, conv_tail, li,
                                                slots, st, tail)
            if cfg.family == "hybrid" and (li + 1) % cfg.attn_every == 0:
                sa = p["shared_attn"]
                x, pool_k, pool_v = attn_layer(x, 0, attn_li, sa,
                                               pool_k, pool_v)
                attn_li += 1
                h2 = rms_norm(x, sa["ln2"][0], cfg.rms_eps)
                x = x + mlp(h2, sa, 0)

    logits = lm_logits(x, p["tok"], cfg)[..., :cfg.vocab_size]
    if loads:
        logits = (logits, jnp.stack(loads))
    return pool_k, pool_v, logits, ssm_state, conv_tail


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
# (impl, donated arg positions).  Donated buffers are the pool arena —
# consumed and returned.
_IMPL_TABLE = {
    "prefill": (_prefill_impl, (4, 5)),
    "decode": (_decode_impl, (4, 5)),
    "chunk": (_prefill_chunk_impl, (5, 6)),
    "chunk_ssm": (_prefill_chunk_ssm_impl, ()),
    "fused_decode": (_fused_decode_impl, (3, 4)),
    "fused_prefill_chunk": (_fused_prefill_chunk_impl, (4, 5)),
}
# a model with SSM layers: every step that reads or writes state also
# consumes the whole SSM state and conv tail and returns them, written
# in place (1.21 GB for granite's 16 slots: a copy would crowd the chip)
_STATE_ARGS = {"prefill": (7, 8), "decode": (7, 8), "chunk": (8, 9),
               "chunk_ssm": (6, 7)}


@lru_cache(maxsize=None)
def jitted_step(kind: str, cfg_key: ModelConfig):
    """Memoized jitted step, shared by every engine with the same
    *geometry* (``Engine.cfg_key`` strips the model name).  Without
    this cache each engine owns a private ``jax.jit`` wrapper and
    colocated instances of one architecture recompile identical
    programs N times."""
    impl, donate = _IMPL_TABLE[kind]
    if cfg_key.ssm:
        donate += _STATE_ARGS.get(kind, ())
    # keep the impl's name on the partial: jit names the program (and
    # its compile events and profiler spans) after it
    step = partial(impl, cfg=cfg_key)
    step.__name__ = impl.__name__
    return jax.jit(step, donate_argnums=donate)
