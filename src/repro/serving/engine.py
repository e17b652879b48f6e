"""Continuous-batching serving engine with paged KV (the vLLM role, JAX-native).

Implements the paper's deployment story: an FP16/bf16 checkpoint is handed
in, SmoothQuant+ PTQ runs once (quantize-on-load), and requests are served
from a fixed-slot continuous batcher backed by a **paged KV cache**:

- the decode cache is a pool of fixed-size pages shared by all slots
  (``serving/kv_cache.py``); a host-side pager hands pages to requests on
  admission and reclaims them on finish, so cache memory tracks live tokens;
- arriving requests are admitted *in batches*: the scheduler
  (``serving/scheduler.py``) assigns slots and pages to the runnable queue
  prefix; the prompt tokens themselves prefill in **chunks** under a token
  budget (``max_prefill_tokens``, vLLM/Sarathi-style **mixed steps**): every
  engine step packs up to the budget in prefill-chunk rows *and* decodes all
  active slots, so a long arriving prompt never stalls in-flight decodes.
  Chunks scatter their KV straight into the pages and attend the cached
  prefix + earlier chunks *through the page table* — the same paged
  machinery decode uses (Pallas chunked-prefill grid on TPU, jnp gather
  oracle on CPU — ``cfg.paged_attn_impl``); a slot's final chunk yields its
  first-token logits.  ``max_prefill_tokens=None`` prefills each prompt in
  one chunk (stop-the-world baseline);
- every engine step decodes ONE token for all slots past their prefill
  target straight against the pages (W4A16 matmuls), sampling **per-slot**
  temperatures;
- with ``cfg.kv_quant`` the pools are int8 + per-row f32 scales: prefix rows
  are quantized on admission, decode tokens before their pool write;
- finished slots free their pages immediately and are refilled from the
  queue — no head-of-line blocking, the continuous-batching win;
- **lazy page growth** (default): admission reserves only the pages covering
  the prompt + 1 decode token; the decode loop grows a slot's page table
  exactly when its write position crosses a page boundary, so pool occupancy
  tracks *live* tokens and concurrency is bounded by real memory, not the
  worst case (the single-A100 deployment headline of the paper).  On pool
  exhaustion the engine **preempts** the youngest active slot(s): their live
  *private* pool rows are swapped to a host buffer (raw codes + scales,
  bit-exact; the device→host copy is started asynchronously and only awaited
  at swap-in) and the request requeues at the *queue head* (FCFS preserved);
  it resumes by swap-in — page realloc + row scatter — never by
  re-prefilling.  An admission watermark (one free page per decoding slot)
  keeps preemption a rare pressure-relief valve.  ``reservation="worstcase"``
  restores the old up-front ``prompt + max_tokens`` reservation as the
  benchmark baseline.
- **shared-prefix KV cache** (``prefix_cache=True``): full prompt pages are
  block-hash-indexed (``serving/prefix_cache.py``); a request whose prompt
  extends a cached prefix *attaches* the matched pages (refcounted, shared,
  read-only — copy-on-write guards any write) and prefills **only its
  uncached suffix**, with prefill attention reading the cached prefix pages
  through the same paged machinery decode uses.  Finished slots index their
  generated full pages too, so multi-turn continuations match.  Unreferenced
  cached pages stay resident as an LRU pool reserve and are evicted exactly
  when an allocation needs them.  Shared pages are never swapped out with a
  preemption victim — swap-in re-acquires them.  Cache-hit requests emit
  greedy tokens identical to a cold run (asserted in tests; note the
  identity is at the argmax level — a warm suffix prefill reads the prefix
  through the pools, so under ``kv_quant`` its logits match the cold run's
  only to within int8 quantization error, exactly like paged decode steps
  already do).

**State leaves** (``api.state_leaves``): a slot's device state is one or
more typed leaves of one state tree (``self.pools``, from
``api.init_paged_state``).  Every family launches through the same two step
programs, which take, donate and return that tree whole; the per-row inputs
a leaf needs ride in one ``aux`` dict built by :meth:`_launch_rows`.  Every
lifecycle primitive (admit, grow, preempt, swap, resume, free) handles each
kind by its own invariants:

- ``kv_pages`` — the paged attention pools above, under ``"layers"`` (every
  config has them; hybrid configs page only their shared-attention
  applications);
- ``fixed_rows`` — per-layer recurrent state rows ``[M, B, ...]`` under
  ``"fixed"`` for hybrid SSM configs (zamba2): O(1) per slot, never paged,
  zeroed at admission, round-tripped bit-exactly through the host swap
  buffer next to the KV rows under one combined CRC-32;
- ``shared_ro`` — read-only encoder K/V pages under ``"enc"`` for enc-dec
  configs (whisper): allocated once per request in the pager's ``"enc"``
  page group, deduplicated across requests by an exact-match
  (whole-sequence) prefix-cache index, never host-swapped — preemption
  detaches them under swap holds and resume reattaches.

The *token* prefix cache stays attention-only: KV pages cannot capture an
SSM boundary state and cross-attention depends on the encoder input, so
hybrid/enc-dec engines reject ``prefix_cache=True`` with a clear error
(enc-dec reuses the machinery for encoder pages instead).
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Any, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig, QuantConfig
from repro.models import api
from repro.models.attention import rows_to_pages
from repro.serving import kv_cache as KV
from repro.serving.faults import (FaultPlan, SimulatedDeviceError,
                                  TransientFault, corrupt_host_image)
from repro.serving.metrics import MetricsRegistry, format_pending
from repro.serving.prefix_cache import PrefixCache
from repro.serving.sampling import sample_per_slot
from repro.serving.scheduler import Scheduler
from repro.serving.trace import TraceRecorder

#: Terminal states every submitted request reaches exactly one of:
#:   completed — decoded its EOS token
#:   length    — hit max_tokens or the max_seq cache cap
#:   deadline  — expired a TTFT/total deadline (queued, swapped, or active)
#:   cancelled — explicit :meth:`ServingEngine.cancel`
#:   rejected  — refused at :meth:`ServingEngine.submit` (validation or
#:               bounded-queue backpressure); never entered the queue
#:   failed    — gave up after exhausting its fault-retry budget, or was
#:               quarantined by a non-strict engine (invariant violation /
#:               admission stall)
FINISH_REASONS = ("completed", "length", "deadline", "cancelled", "rejected",
                  "failed")


class UnsupportedModelError(NotImplementedError):
    """Raised at :class:`ServingEngine` construction for a config whose
    mixer/family has no paged serving path (e.g. a pure-RNN family with no
    fixed-rows adapter).  Subclasses :class:`NotImplementedError` so older
    callers that caught that still work; the point is failing *at engine
    build* with the reason, never mid-step with an ``AttributeError``."""


class RejectedRequest(ValueError):
    """Raised by :meth:`ServingEngine.submit` for *invalid* requests (empty
    prompt, non-positive ``max_tokens``, over-long prompt).  The request is
    marked terminal (``finish_reason="rejected"``, ``error`` says why) before
    the raise, so callers that catch still see a structured outcome.
    Bounded-queue backpressure does **not** raise — a full queue is an
    operational condition, not a caller bug — it returns ``False``."""


@dataclasses.dataclass
class Request:
    uid: int
    prompt: np.ndarray            # [T] int32
    max_tokens: int = 16
    temperature: float = 0.0
    top_k: int = 0                # 0 disables (per-request, incl. first token)
    top_p: float = 1.0            # 1.0 disables
    arrival_t: float = 0.0
    deadline_s: Optional[float] = None       # total wall budget from arrival
    ttft_deadline_s: Optional[float] = None  # first-token budget from arrival
    frames: Optional[np.ndarray] = None      # [T_enc, d_model] (enc-dec only)
    # filled by the engine:
    output: List[int] = dataclasses.field(default_factory=list)
    first_token_t: Optional[float] = None
    done_t: Optional[float] = None
    finish_reason: Optional[str] = None      # one of FINISH_REASONS when done
    error: Optional[str] = None              # detail for rejected/failed
    retries: int = 0              # transient-fault retries charged so far
    reprefills: int = 0           # swap-corruption re-prefills (budget: 1)
    submit_seq: int = -1          # FCFS age; youngest (max) is preempted first
    # swap-corruption replay: the token that must feed the next decode step
    # after the re-prefill lands (instead of sampling a duplicate), and how
    # many output tokens were folded into the prompt by the re-prefill
    _replay_tok: Optional[int] = None
    _gen_in_prompt: int = 0


@dataclasses.dataclass
class _SwapState:
    """Host-side image of a preempted slot: everything needed to resume it
    bit-exactly without re-prefilling.  Shared/cached pages are *not* part of
    the image — they stay resident in the pool under a swap hold and resume
    re-acquires them (``kept``); only private pages round-trip as rows.
    Fixed-rows slots (hybrid SSM) additionally carry their per-layer state
    rows in the same image (one combined checksum); enc-dec slots carry no
    encoder bytes at all — their read-only pages stay resident under swap
    holds (``enc_pages``) and resume reattaches them."""
    rows: Any                     # pytree [L, n_private, PS, ...] (or None)
    kept: List[Tuple[int, int]]   # (logical_idx, page) left resident
    private_lis: List[int]        # logical idxs of the swapped rows
    pos: int                      # next write position
    last_tok: int                 # token feeding the next decode step
    nbytes: int                   # KV swap-buffer bytes (stats)
    fbytes: int = 0               # fixed-rows (SSM state) bytes in the image
    on_host: bool = False         # rows materialized to numpy (device freed)
    checksum: Optional[int] = None  # CRC-32 of the host image (drain time)
    fixed_rows: Any = None        # pytree [M, 1, ...] SSM state (or None)
    enc_pages: Optional[List[int]] = None  # detached read-only enc pages
    enc_len: int = 0              # valid encoder rows to restore
    corrupted: bool = False       # injected rot already applied (flip once —
                                  # a second XOR would flip the byte *back*)


@dataclasses.dataclass
class EngineStats:
    decoded_tokens: int = 0
    prefilled_tokens: int = 0
    steps: int = 0
    completed: int = 0
    prefill_batches: int = 0      # joint prefill launches (≤ admitted reqs)
    preemptions: int = 0          # slots swapped out under pool pressure
    resumes: int = 0              # swapped slots re-admitted (swap-in)
    grown_pages: int = 0          # pages added by lazy decode growth
    swapped_out_bytes: int = 0    # pool bytes copied device -> host
    swapped_in_bytes: int = 0     # pool bytes copied host -> device
    swapped_fixed_bytes: int = 0  # of swapped_out: fixed-rows state bytes
    swapped_fixed_in_bytes: int = 0  # of swapped_in: fixed-rows state bytes
    enc_hits: int = 0             # admissions reusing cached encoder pages
    enc_encodes: int = 0          # admissions that ran the encoder
    idle_steps: int = 0           # drain iterations with nothing decodable
    max_active: int = 0           # peak concurrent decoding slots
    active_slot_steps: int = 0    # sum of active slots over steps (mean = /steps)
    # shared-prefix cache:
    admitted: int = 0             # requests admitted (incl. resumes? no: fresh)
    prefix_hits: int = 0          # admissions that matched >= 1 cached page
    prefix_matched_tokens: int = 0  # prompt tokens served from the cache
    pages_shared: int = 0         # page attachments (shared, not allocated)
    pages_inserted: int = 0       # pages newly indexed by the cache
    pages_evicted: int = 0        # unreferenced cached pages reclaimed (LRU)
    cow_copies: int = 0           # copy-on-write page duplications
    # request lifecycle / graceful degradation:
    rejected: int = 0             # refused at submit (validation/backpressure)
    expired: int = 0              # terminal by TTFT/total deadline
    cancelled: int = 0            # terminal by cancel()
    failed: int = 0               # terminal by retry exhaustion / quarantine
    retries: int = 0              # fault recoveries attempted (all kinds)
    faults_injected: int = 0      # FaultPlan fires observed (mirror of plan)


class ServingEngine:
    def __init__(
        self,
        params,
        cfg: ModelConfig,
        *,
        batch_size: int = 8,
        max_seq: int = 256,
        page_size: int = 16,
        num_pages: Optional[int] = None,
        eos_id: int = 1,
        backend: str = "auto",
        seed: int = 0,
        max_prefill_tokens: Optional[int] = None,
        reservation: str = "lazy",
        prefix_cache: bool = False,
        max_queue: Optional[int] = None,
        strict: bool = True,
        fault_plan: Optional[FaultPlan] = None,
        retry_budget: int = 3,
        metrics: bool = True,
    ):
        ok, why = api.paged_supported(cfg)
        if not ok:
            raise UnsupportedModelError(f"paged serving: {why}")
        if cfg.act_quant not in ("a16", "a8_prefill"):
            raise ValueError(
                f"act_quant={cfg.act_quant!r}: expected 'a16' or 'a8_prefill' "
                "(a8_prefill routes prefill-chunk GEMMs on A8-eligible layers "
                "through the int8-activation kernel body; decode stays A16)")
        self.cfg = cfg
        self.params = params
        self.B = batch_size
        self.PS = page_size
        self.P = -(-max_seq // page_size)          # pages per slot
        self.S = self.P * page_size                # max_seq rounded to pages
        if cfg.sliding_window is not None and self.S > cfg.sliding_window:
            raise ValueError(
                f"{cfg.name}: max_seq={max_seq} (pages hold {self.S}) "
                f"reaches past the model's sliding window of "
                f"{cfg.sliding_window} tokens, and no attention path masks "
                "behind a window")
        self.eos = eos_id
        self.backend = backend
        self.key = jax.random.PRNGKey(seed)

        # which typed state leaves a slot of this config owns — every
        # lifecycle primitive below branches on these, nothing else does
        self.leaves = api.state_leaves(cfg)
        self.has_fixed = api.FIXED_ROWS in self.leaves
        self.has_enc = api.SHARED_RO in self.leaves

        # +1: page 0 is the pager's trash page, never handed to a slot.
        # Enc-dec slots additionally page their encoder K/V ("enc" group),
        # so the default pool and the one-request floor both double.
        slot_pages = self.P * (2 if self.has_enc else 1)
        num_pages = num_pages or (batch_size * slot_pages + 1)
        if num_pages - 1 < slot_pages:
            # one max-size request must always be admittable once the pool
            # drains, or run_until_drained could spin on an empty batch
            raise ValueError(
                f"num_pages={num_pages} cannot hold one max_seq request "
                f"({slot_pages} pages of {page_size} tokens + trash page)")
        groups = ("kv", "enc") if self.has_enc else ("kv",)
        self.pager = KV.PagePool(num_pages, page_size, batch_size, self.P,
                                 groups=groups)
        if prefix_cache and (self.has_fixed or self.has_enc):
            raise ValueError(
                "prefix_cache=True is attention-only: KV pages cannot "
                "capture an SSM boundary state (hybrid) and cross-attention "
                "depends on the encoder input (enc-dec); enc-dec engines "
                "deduplicate encoder pages automatically instead")
        self.cache: Optional[PrefixCache] = (
            PrefixCache(self.pager, page_size,
                        mode=f"kvq={int(bool(cfg.kv_quant))}")
            if prefix_cache else None)
        # exact-match index over read-only encoder pages (same machinery,
        # whole-sequence keys): identical frames across requests share one
        # resident page set.  Registers as the pool's (only) evictor.
        self.enc_cache: Optional[PrefixCache] = (
            PrefixCache(self.pager, page_size, mode="enc")
            if self.has_enc else None)
        # the one state tree (module docstring); swap moves its KV pages,
        # under "layers" for every family, and hybrid's "fixed" rows
        self.pools = api.init_paged_state(cfg, num_pages, page_size,
                                          batch_size)
        self.enc_len = np.zeros(batch_size, np.int32)   # valid enc rows/slot
        self.reservation = reservation
        self.sched = Scheduler(page_size=page_size, max_seq=self.S,
                               max_prefill_tokens=max_prefill_tokens,
                               reservation=reservation)

        self.slots: List[Optional[Request]] = [None] * batch_size
        self.pos = np.zeros(batch_size, np.int32)      # next position per slot
        self.last_tok = np.zeros(batch_size, np.int32)
        # prompt length each slot must reach before decoding: a slot is
        # *prefilling* while pos < pref_target (its chunk cursor is pos) and
        # *decoding* once pos >= pref_target
        self.pref_target = np.zeros(batch_size, np.int32)
        self.queue: deque[Request] = deque()
        self.stats = EngineStats()
        self._swapped: dict[int, _SwapState] = {}   # submit_seq -> swap image
        self._next_seq = 0                             # FCFS submission clock

        # ----- request lifecycle / graceful degradation -----
        # max_queue bounds the waiting line: submit() rejects (structured,
        # finish_reason="rejected") instead of growing without bound.  strict
        # governs the abnormal paths: True (default) keeps every invariant
        # violation / admission stall a hard raise (what tests want); False
        # quarantines the offending request (finish_reason="failed") and
        # keeps serving everyone else (what production wants).
        self.max_queue = max_queue
        self.strict = strict
        self.retry_budget = retry_budget
        self.faults = fault_plan
        self.pager.faults = fault_plan
        if self.cache is not None:
            self.cache.faults = fault_plan
        if self.enc_cache is not None:
            self.enc_cache.faults = fault_plan
        self._clock = time.perf_counter     # swappable in tests (deadlines)
        self._step_idx = 0                  # all engine steps (idle included)
        self._retry_pending = False         # last step skipped work on a fault

        # ----- observability: metrics registry + lifecycle/journal trace ---
        # One clock rules everything: the registry and recorder late-bind
        # to self._clock, so a test that swaps the engine clock gets
        # deterministic histograms, timelines, and trace exports for free.
        # All recording is host-side bookkeeping (ints, floats, deques) —
        # it never touches jax, the pools, or the RNG stream, which is what
        # makes the metrics-on/off greedy token-identity guarantee hold.
        # ``metrics=False`` strips every observe from the hot path (what
        # ``serve.py --no-metrics`` and the metrics on/off identity tests
        # run); the registry still exists so metrics_snapshot() stays
        # well-formed (empty histograms).
        self._obs = metrics
        self.metrics = MetricsRegistry(clock=lambda: self._clock())
        self.trace = TraceRecorder(lambda: self._clock(), enabled=metrics)
        self._h_ttft = self.metrics.histogram(
            "ttft_s", "submit -> first token (engine-internal)")
        # ITL gaps cluster within a decade (one step vs a stalled step), so
        # this histogram gets 4x the bucket resolution (~5%/bucket) — the
        # mixed-prefill benchmark discriminates stalls through it
        self._h_itl = self.metrics.histogram(
            "itl_s", "gap between consecutive tokens of one request",
            per_decade=48)
        self._h_e2e = self.metrics.histogram(
            "e2e_s", "submit -> terminal state")
        self._h_qwait = self.metrics.histogram(
            "queue_wait_s", "submit -> first admission to a slot")
        self._h_swap = self.metrics.histogram(
            "swap_stall_s", "preempt (swap-out) -> swap-in resume")
        self._fault_ctr = self.metrics.counter(
            "faults_fired_total", "fault-plan probes fired, by site")
        if fault_plan is not None:
            fault_plan.sink = self._on_fault
        self._last_dec: List[int] = []      # decode slots of the last step

        # One launch signature for every family.  The whole state tree is
        # donated, so a step's output aliases its input buffers instead of
        # allocating a second pool every token.  ``aux`` is empty for
        # attention-only state and otherwise one dict of the per-row inputs
        # the other leaves need (:meth:`_launch_rows`); it is passed as
        # ``*aux``, so an attention-only launch is positionally
        # ``(p, pools, tok, pos, table)`` and never lowers a second program.
        # Each step program is a named function, so a device trace names its
        # module (``jit_decode_step``, ``jit_prefill_chunk_step``).
        def decode_step(p, pools, tok, pos, table, *aux):
            return api.decode_paged_fn(
                p, {"token": tok, "position": pos}, pools, table, cfg, *aux,
                backend=backend)

        # joint length-bucketed chunk prefill: each row is one [blen] prompt
        # chunk at logical positions start_len[r] + t; KV scatters into the
        # pages and attention reads every earlier token (cached prefix and
        # prior chunks alike) through the table.  jit re-specializes per
        # (n, bucket_len); the scheduler's power-of-two buckets keep that
        # trace count O(log max_seq).
        def prefill_chunk_step(p, toks, last_idx, starts, lens, table, pools,
                               *aux):
            return api.prefill_chunk_fn(
                p, {"tokens": toks}, pools, table, starts, lens, cfg, *aux,
                backend=backend, last_idx=last_idx)

        # admission programs of the non-KV leaves: the encoder pass of an
        # enc-dec request, and zeroing a hybrid slot's stale recurrent state
        def encode_step(p, fr):
            return api.encode_kv_fn(p, fr, cfg, backend=backend)

        def fixed_zero(f, slot):
            return jax.tree.map(lambda a: a.at[:, slot].set(0), f)

        self._decode = jax.jit(decode_step, donate_argnums=(1,))
        self._prefill_chunk = jax.jit(prefill_chunk_step, donate_argnums=(6,))
        self._encode = jax.jit(encode_step)
        self._fixed_zero = jax.jit(fixed_zero, donate_argnums=(0,))
        self._sample = jax.jit(sample_per_slot)

    # ----------------------------------------------------- observability ---
    def _on_fault(self, site: str) -> None:
        """FaultPlan sink: every probe that fires lands as a labeled counter
        increment + a journal mark, so chaos runs can reconcile the plan's
        own ``injected`` tally against engine-side counters."""
        if self._obs:
            self._fault_ctr.inc(site=site)
            self.trace.note_fault(site)

    def _note_finish(self, req: Request) -> None:
        """Close a request's timeline (any terminal reason except rejected —
        a rejected request never entered the queue and has no timeline)."""
        t = req.done_t
        tl = self.trace.timeline(req.uid)
        tl.add(t, "finish", reason=req.finish_reason)
        tl.finish_t = t
        self._h_e2e.observe(t - req.arrival_t)
        self.trace.finish(req.uid)

    # ------------------------------------------------------------- admin ---
    def _reject(self, req: Request, why: str, *, raise_: bool) -> bool:
        """Structured rejection: the request turns terminal *now* — it never
        enters the queue, never holds a page, and its ``finish_reason``
        tells the caller exactly why."""
        req.finish_reason = "rejected"
        req.error = why
        req.done_t = self._clock()
        self.stats.rejected += 1
        if raise_:
            raise RejectedRequest(why)
        return False

    def submit(self, req: Request) -> bool:
        """Enqueue ``req``; returns True on admission to the queue.

        Invalid requests (empty prompt, ``max_tokens <= 0``, prompt longer
        than ``max_seq - 1``) raise :class:`RejectedRequest` — a caller bug.
        A full bounded queue (``max_queue``) rejects *without* raising and
        returns False — backpressure is an operational signal the caller
        handles by retrying later or shedding load.  Both paths mark the
        request terminal with ``finish_reason="rejected"``.
        """
        if len(req.prompt) == 0:
            return self._reject(req, "empty prompt", raise_=True)
        if req.max_tokens <= 0:
            return self._reject(
                req, f"max_tokens must be >= 1, got {req.max_tokens}",
                raise_=True)
        if len(req.prompt) > self.S - 1:
            return self._reject(
                req, f"prompt of {len(req.prompt)} tokens exceeds "
                     f"max_seq-1={self.S - 1}", raise_=True)
        if self.has_enc:
            if req.frames is None:
                return self._reject(
                    req, "enc-dec config: request must carry encoder frames",
                    raise_=True)
            fr = np.asarray(req.frames)
            if fr.ndim != 2 or fr.shape[0] < 1 \
                    or fr.shape[1] != self.cfg.d_model:
                return self._reject(
                    req, f"frames must be [T_enc>=1, {self.cfg.d_model}], "
                         f"got {fr.shape}", raise_=True)
            if fr.shape[0] > self.S:
                return self._reject(
                    req, f"{fr.shape[0]} encoder frames exceed the "
                         f"{self.S}-row page budget", raise_=True)
            req.frames = fr
        elif req.frames is not None:
            return self._reject(
                req, "frames on a decoder-only config", raise_=True)
        if self.max_queue is not None and len(self.queue) >= self.max_queue:
            return self._reject(
                req, f"queue full ({self.max_queue} waiting)", raise_=False)
        req.arrival_t = req.arrival_t or self._clock()
        req.submit_seq = self._next_seq
        self._next_seq += 1
        self.queue.append(req)
        if self._obs:
            tl = self.trace.timeline(req.uid)
            tl.submit_t = req.arrival_t
            tl.add(req.arrival_t, "submit", prompt=len(req.prompt))
        return True

    def cancel(self, uid: int) -> bool:
        """Cancel the request with ``uid`` wherever it lives — waiting in the
        queue, swapped out, or actively prefilling/decoding.  Its pages (and
        any swap-hold pins) free immediately; tokens already generated stay
        on ``req.output``.  Returns False when no live request has ``uid``
        (already finished, or never submitted)."""
        for r in list(self.queue):
            if r.uid == uid:
                self.queue.remove(r)
                self._finish_abnormal(r, "cancelled")
                return True
        for i, r in enumerate(self.slots):
            if r is not None and r.uid == uid:
                self._evict_slot(i, "cancelled")
                return True
        return False

    # ------------------------------------------- terminal abnormal paths ---
    def _finish_abnormal(self, req: Request, reason: str,
                         error: Optional[str] = None) -> None:
        """Turn a request terminal off the happy path (deadline / cancelled /
        failed).  Cleans up any swap state it holds: the host image is
        dropped and every kept-page swap hold released, so the pool sees the
        pages again immediately."""
        st = self._swapped.pop(req.submit_seq, None)
        if st is not None:
            for _, p in st.kept:
                self.pager.drop_hold(p)
            if st.enc_pages:
                self.pager.drop_group_holds(st.enc_pages)
        req.finish_reason = reason
        req.error = error
        req.done_t = self._clock()
        counter = {"deadline": "expired", "cancelled": "cancelled",
                   "failed": "failed"}[reason]
        setattr(self.stats, counter, getattr(self.stats, counter) + 1)
        if self._obs:
            self._note_finish(req)

    def _evict_slot(self, slot: int, reason: str,
                    error: Optional[str] = None) -> None:
        """Terminate the request occupying ``slot`` abnormally and free the
        slot + its pages — the degradation primitive behind deadlines,
        cancellation, and quarantine."""
        req = self.slots[slot]
        self.slots[slot] = None
        self.pos[slot] = 0
        self.last_tok[slot] = 0
        self.pref_target[slot] = 0
        self.enc_len[slot] = 0
        self.pager.free_slot(slot)
        self._finish_abnormal(req, reason, error)

    def _deadline_hit(self, req: Request, now: float) -> bool:
        age = now - req.arrival_t
        if req.deadline_s is not None and age > req.deadline_s:
            return True
        return (req.ttft_deadline_s is not None
                and req.first_token_t is None
                and age > req.ttft_deadline_s)

    def _expire_deadlines(self) -> None:
        """Per-request TTFT/total deadlines, checked every step: an expired
        request turns terminal (``finish_reason="deadline"``) with its pages
        freed — wherever it is (queued, swapped out, prefilling, decoding) —
        instead of burning compute on an answer nobody is waiting for."""
        if not any(r.deadline_s is not None or r.ttft_deadline_s is not None
                   for r in list(self.queue) + self.slots if r is not None):
            return
        now = self._clock()
        for r in [r for r in self.queue if self._deadline_hit(r, now)]:
            self.queue.remove(r)
            self._finish_abnormal(r, "deadline")
        for i in self._active_slots():
            if self._deadline_hit(self.slots[i], now):
                self._evict_slot(i, "deadline")

    def _active_slots(self) -> List[int]:
        return [i for i, r in enumerate(self.slots) if r is not None]

    def _sample_reqs(self, logits, sk, temps, reqs):
        """Per-row sampling for a list of Requests (None for idle rows).
        The per-row top-k/top-p arrays are only passed when some request in
        the batch actually filters; the all-default call hits
        ``sample_per_slot``'s static fast path, keeping the two full-vocab
        sorts out of the compiled greedy/temperature-only decode step."""
        if not any(r is not None and (r.top_k or r.top_p < 1.0) for r in reqs):
            return self._sample(logits, sk, temps)
        tks = jnp.asarray([r.top_k if r else 0 for r in reqs], jnp.int32)
        tps = jnp.asarray([r.top_p if r else 1.0 for r in reqs], jnp.float32)
        return self._sample(logits, sk, temps, tks, tps)

    # -------------------------------------------------- prefix-cache glue --
    def _written_tokens(self, slot: int) -> np.ndarray:
        """Token ids at every written position of ``slot`` (prompt followed
        by the generated tokens whose KV has landed in the pages)."""
        req = self.slots[slot]
        n_gen = int(self.pos[slot]) - len(req.prompt)
        if n_gen <= 0:
            return np.asarray(req.prompt, np.int32)
        # after a swap-corruption re-prefill the first _gen_in_prompt output
        # tokens already live inside req.prompt; only the rest are "written
        # beyond the prompt"
        off = req._gen_in_prompt
        return np.concatenate([np.asarray(req.prompt, np.int32),
                               np.asarray(req.output[off:off + n_gen],
                                          np.int32)])

    def _cache_insert_slot(self, slot: int) -> None:
        """Index every full written page of ``slot`` (idempotent).  The
        scheduler memoized the prompt's chain hashes on the request; only
        pages of generated tokens hash fresh here."""
        toks = self._written_tokens(slot)
        req = self.slots[slot]
        head = getattr(req, "_block_hashes", ())
        hashes = self.cache.block_hashes(toks, head=head)
        self.stats.pages_inserted += self.cache.insert(
            toks, self.pager.slot_pages(slot), len(toks) // self.PS,
            hashes=hashes)

    # ---------------------------------------------------- swap-out / -in ---
    def _preempt(self, slot: int) -> None:
        """Swap ``slot`` out and requeue its request at the queue *head* (it
        was admitted before anything still queued, so FCFS order is
        preserved).  Only the slot's *private* pages round-trip through the
        host buffer — pages shared with other slots or resident in the prefix
        cache stay in the pool under a swap hold (they were read-only full
        pages anyway) and resume re-acquires them.  The swap buffer holds the
        private pool rows verbatim — fp16 K/V or int8 codes + f32 scale
        leaves — so resume is bit-exact and preemption is a pure scheduling
        effect.  The device→host copy is kicked off asynchronously and
        overlaps the following decode step, after which the rows are
        materialized to host and the device-side gather buffer dropped
        (:meth:`_drain_swap_buffers`).

        Non-KV leaves ride the same preemption: a hybrid slot's fixed-rows
        state is gathered into the image next to the KV rows (same async
        copy, one combined checksum); an enc-dec slot's read-only encoder
        pages never leave the device — they detach under swap holds and
        resume reattaches them."""
        req = self.slots[slot]
        kept, private = self.pager.split_for_swap(slot)
        rows, nbytes = None, 0
        if private:
            rows = api.gather_pool_rows(
                self.pools["layers"],
                jnp.asarray([p for _, p in private], jnp.int32))
            # start the device->host transfer without blocking the step loop
            jax.tree.map(lambda a: a.copy_to_host_async(), rows)
            nbytes = sum(a.nbytes for a in jax.tree.leaves(rows))
        frows, fbytes = None, 0
        if self.has_fixed:
            frows = api.gather_pool_rows(
                self.pools["fixed"], jnp.asarray([slot], jnp.int32))
            jax.tree.map(lambda a: a.copy_to_host_async(), frows)
            fbytes = sum(a.nbytes for a in jax.tree.leaves(frows))
        enc_pages, enc_len = None, 0
        if self.has_enc:
            enc_pages = self.pager.detach_group(slot, "enc")
            enc_len = int(self.enc_len[slot])
            self.enc_len[slot] = 0
        self.pager.swap_out(slot, (kept, private))
        self._swapped[req.submit_seq] = _SwapState(
            rows=rows, kept=kept, private_lis=[li for li, _ in private],
            pos=int(self.pos[slot]), last_tok=int(self.last_tok[slot]),
            nbytes=nbytes, fbytes=fbytes, fixed_rows=frows,
            enc_pages=enc_pages, enc_len=enc_len)
        self.queue.appendleft(req)
        self.slots[slot] = None
        self.pos[slot] = 0
        self.last_tok[slot] = 0
        self.pref_target[slot] = 0
        self.stats.preemptions += 1
        # KV and fixed-state bytes are accounted symmetrically with
        # _resume: both sides charge nbytes + fbytes, so a drained engine
        # always shows swapped_out_bytes == swapped_in_bytes
        self.stats.swapped_out_bytes += nbytes + fbytes
        self.stats.swapped_fixed_bytes += fbytes
        if self._obs:
            t = self.trace.event(req.uid, "preempt", slot=slot,
                                 bytes=nbytes + fbytes)
            self.trace.timeline(req.uid).preempt_t = t
            self.trace.note_preempt(req.uid, slot)

    def _resume(self, slot: int, req: Request) -> None:
        """Swap a preempted request back in: re-acquire its held shared
        pages, realloc the private ones, scatter the host rows into them
        (first touch of the async swap buffer), restore the decode cursor."""
        st = self._swapped.pop(req.submit_seq)
        fresh = self.pager.swap_in(slot, st.kept, st.private_lis)
        if st.rows is not None:
            rows = jax.device_get(st.rows)     # no-op once drained to host
            self.pools = {**self.pools, "layers": api.scatter_pool_rows(
                self.pools["layers"], rows, jnp.asarray(fresh, jnp.int32))}
        if st.fixed_rows is not None:
            # same dtypes both ways (f32 h, model-dtype conv tails), so the
            # restored recurrent state is bit-identical to the preempted one
            self.pools = {**self.pools, "fixed": api.scatter_pool_rows(
                self.pools["fixed"], jax.device_get(st.fixed_rows),
                jnp.asarray([slot], jnp.int32))}
        if st.enc_pages is not None:
            self.pager.reattach_group(slot, "enc", st.enc_pages)
            self.enc_len[slot] = st.enc_len
        self.slots[slot] = req
        self.pos[slot] = st.pos
        self.last_tok[slot] = st.last_tok
        # a slot preempted mid-prefill resumes mid-prefill: its chunk cursor
        # (pos) restores below pref_target and chunking picks it back up
        self.pref_target[slot] = len(req.prompt)
        self.stats.resumes += 1
        self.stats.swapped_in_bytes += st.nbytes + st.fbytes
        self.stats.swapped_fixed_in_bytes += st.fbytes
        if self._obs:
            t = self.trace.event(req.uid, "swap_in", slot=slot,
                                 bytes=st.nbytes + st.fbytes)
            tl = self.trace.timeline(req.uid)
            if tl.preempt_t is not None:
                self._h_swap.observe(t - tl.preempt_t)
                tl.preempt_t = None
            self.trace.note_resume(req.uid, slot)

    def _charge_retry(self, slot: int, why: str) -> None:
        """Charge one fault retry against the request in ``slot``; exhausting
        the budget turns it terminal (``failed``) instead of livelocking."""
        req = self.slots[slot]
        req.retries += 1
        self.stats.retries += 1
        self._retry_pending = True
        if self._obs:
            self.trace.event(req.uid, "retry", slot=slot, why=why,
                             n=req.retries)
        if req.retries > self.retry_budget:
            self._evict_slot(
                slot, "failed",
                f"fault-retry budget exhausted ({self.retry_budget}): {why}")

    def _ensure_pages(self) -> set:
        """Lazy growth: every active slot must own the pages covering its next
        write position before the decode step runs.  Oldest slots are grown
        first; on pool exhaustion the *youngest* active slot is preempted
        (repeatedly, until the grow fits) — possibly the growing slot itself,
        which then simply leaves the batch until pages free up.

        Returns the set of slots whose growth hit an injected transient
        fault this step: they must sit out the decode launch (their table
        doesn't cover the write position) and retry next step, each attempt
        charged against the request's bounded retry budget."""
        stalled: set = set()
        if self.reservation != "lazy":
            return stalled             # worst-case reservation never grows
        for i in sorted(self._active_slots(),
                        key=lambda j: self.slots[j].submit_seq):
            while self.slots[i] is not None:
                need = int(self.pos[i]) // self.PS + 1
                if len(self.pager.slot_pages(i)) >= need:
                    break
                if self.pager.can_alloc(1):
                    try:
                        self.pager.grow(i, 1)
                        self.stats.grown_pages += 1
                    except TransientFault as e:
                        self._charge_retry(i, str(e))
                        stalled.add(i)
                        break
                else:
                    victim = max(self._active_slots(),
                                 key=lambda j: self.slots[j].submit_seq)
                    self._preempt(victim)
        return stalled

    def _verify_swap_image(self, req: Request) -> bool:
        """Checksum-verify a drained swap image before its rows ever reach
        the pool.  On mismatch the image is discarded (holds released) and
        the request converts to a **re-prefill**: its written tokens (prompt
        + generated) become the prefill target, and the decode resumes from
        the restored last token — degraded (recompute) but never poisoned.
        Returns False when the request must not resume by swap-in."""
        st = self._swapped[req.submit_seq]
        has_img = st.rows is not None or st.fixed_rows is not None
        if (not has_img or not st.on_host or st.checksum is None
                or api.swap_image_checksum(
                    {"kv": st.rows, "fixed": st.fixed_rows}) == st.checksum):
            return True
        # poisoned host buffer detected — never scatter it (KV rows and
        # fixed state rows alike; the SSM state re-derives from the token
        # replay exactly like the KV pages do)
        self._swapped.pop(req.submit_seq)
        for _, p in st.kept:
            self.pager.drop_hold(p)
        if st.enc_pages:
            # the detached encoder pages are clean (they never entered the
            # host image) and stay indexed — dropping the holds makes them
            # evictable, and the re-admission's exact-match lookup normally
            # re-attaches them without re-encoding
            self.pager.drop_group_holds(st.enc_pages)
        req.reprefills += 1
        self.stats.retries += 1
        self._retry_pending = True
        if req.reprefills > 1:      # re-prefill at most once
            self.queue.remove(req)
            self._finish_abnormal(
                req, "failed", "swap image corrupted twice — giving up")
            return False
        n_gen = st.pos - len(req.prompt)
        if n_gen > 0:
            # replay prompt + generated tokens through prefill; the next
            # decode must feed the already-sampled last token, not sample a
            # duplicate from the final chunk's logits
            req._replay_tok = st.last_tok
            off = req._gen_in_prompt
            req.prompt = np.concatenate(
                [np.asarray(req.prompt, np.int32),
                 np.asarray(req.output[off:off + n_gen], np.int32)])
            req._gen_in_prompt = off + n_gen
            if hasattr(req, "_block_hashes"):
                del req._block_hashes   # memoized over the old prompt
        # req stays at the queue head, now unswapped: plan() admits it as a
        # fresh prefill (FCFS preserved — it was admitted first)
        return False

    def _admit(self):
        free = [i for i, s in enumerate(self.slots) if s is None]
        # preempted requests sit at the queue head (FCFS); resume them by
        # swap-in before planning fresh prefills — and if the head can't be
        # resumed yet, nothing behind it may jump the line
        while self.queue and self.queue[0].submit_seq in self._swapped:
            if not free:
                return
            if not self._verify_swap_image(self.queue[0]):
                break               # corrupted: head re-prefills (or failed)
            st = self._swapped[self.queue[0].submit_seq]
            reserve = self.B - len(free)          # watermark: active slots
            if not self.pager.can_alloc(len(st.private_lis) + reserve):
                return
            self._resume(free.pop(0), self.queue.popleft())
        if not free or not self.queue:
            return
        # the planner must never see a swap-resumable request — they resume
        # by swap-in only.  Normally they form a queue prefix fully handled
        # above, but a corruption-converted head leaves its still-swapped
        # siblings *behind* a plannable request: pull them out for the
        # duration of the plan and splice them back in FCFS order after.
        parked = [r for r in self.queue if r.submit_seq in self._swapped]
        for r in parked:
            self.queue.remove(r)
        reserve = (self.B - len(free)) if self.reservation == "lazy" else 0
        for bkt in self.sched.plan(self.queue, free, self.pager, reserve,
                                   self.cache):
            pfx = np.asarray(bkt.prefix_lens, np.int32)
            # COW first: a page-aligned full match re-prefills the last
            # prompt token into a private copy of the final matched page,
            # so the copies must exist before any chunk reads/writes them.
            # The planner left a hold on each src pinning it against reuse
            # until its rows are duplicated here (one batched dispatch).
            pairs = [p for p in bkt.cow if p is not None]
            if pairs:
                self.pools = {**self.pools, "layers": api.copy_pool_page(
                    self.pools["layers"],
                    jnp.asarray([s for s, _ in pairs], jnp.int32),
                    jnp.asarray([d for _, d in pairs], jnp.int32))}
                for src, _ in pairs:
                    self.pager.drop_hold(src)
                self.stats.cow_copies += len(pairs)
            # admission stops here: the slot's chunk cursor starts at the
            # cached-prefix length and the prompt tokens themselves prefill
            # in budgeted chunks (:meth:`_prefill_chunks`), interleaved with
            # decode steps
            for r, (slot, req) in enumerate(zip(bkt.slots, bkt.reqs)):
                self.slots[slot] = req
                self.pos[slot] = int(pfx[r])
                self.pref_target[slot] = len(req.prompt)
                self.last_tok[slot] = 0
                if self.has_fixed:
                    # the previous occupant's recurrent state is stale, not
                    # trash-maskable like KV pages — zero it before chunk 1
                    self.pools = {**self.pools, "fixed": self._fixed_zero(
                        self.pools["fixed"], jnp.asarray(slot, jnp.int32))}
                if self.has_enc:
                    self._admit_enc(slot, req)
                self.stats.admitted += 1
                self.stats.prefix_matched_tokens += int(pfx[r])
                self.stats.prefix_hits += int(pfx[r] > 0)
                self.stats.pages_shared += bkt.shared[r]
                if self._obs:
                    t = self.trace.event(req.uid, "admit", slot=slot,
                                         cached_tokens=int(pfx[r]))
                    tl = self.trace.timeline(req.uid)
                    if tl.admit_t is None:   # first admission = queue wait
                        tl.admit_t = t
                        self._h_qwait.observe(t - req.arrival_t)
        if self.sched.last_plan_aborted and self.queue:
            # a transient grow fault aborted the plan mid-admission; the
            # scheduler rolled the victim back to the queue head.  Charge its
            # bounded retry budget so an endlessly-faulting admission fails
            # the request instead of livelocking the drain loop.
            head = self.queue[0]
            head.retries += 1
            self.stats.retries += 1
            self._retry_pending = True
            if head.retries > self.retry_budget:
                self.queue.popleft()
                self._finish_abnormal(
                    head, "failed",
                    f"admission fault-retry budget exhausted "
                    f"({self.retry_budget})")
        if parked:
            merged = sorted(list(self.queue) + parked,
                            key=lambda r: r.submit_seq)
            self.queue.clear()
            self.queue.extend(merged)

    def _admit_enc(self, slot: int, req: Request) -> None:
        """Fill ``slot``'s encoder pages at admission: the scheduler already
        grew the fresh page set ("enc" group, charged in its plan), so
        either an exact-match cache hit swaps them for the shared resident
        copy (free fresh, attach cached — the conservative charge is
        returned here), or the encoder runs once and its K/V rows scatter
        into the fresh pages, which are then indexed for the next request
        with identical frames."""
        fr = req.frames
        npg = self.pager.pages_needed(len(fr))
        hashes = getattr(req, "_enc_hashes", None)
        if hashes is None:
            hashes = self.enc_cache.data_hashes(fr, npg)
            req._enc_hashes = hashes
        cached = self.enc_cache.match_exact(hashes)
        if cached:
            self.pager.free_group(slot, "enc")
            self.pager.attach(slot, cached, group="enc")
            self.stats.enc_hits += 1
            self.stats.pages_shared += len(cached)
        else:
            pages = self.pager.slot_pages(slot, "enc")
            kv = self._encode(self.params,
                              jnp.asarray(fr, self.cfg.jdtype)[None])
            s = int(kv["xk"].shape[2])
            pad = npg * self.PS - s
            rows = jax.tree.map(
                lambda a: rows_to_pages(
                    jnp.pad(a[:, 0], ((0, 0), (0, pad), (0, 0), (0, 0))),
                    self.PS),
                kv)
            self.pools = {**self.pools, "enc": api.scatter_pool_rows(
                self.pools["enc"], rows, jnp.asarray(pages, jnp.int32))}
            self.enc_cache.insert_exact(hashes, pages)
            self.stats.enc_encodes += 1
        self.enc_len[slot] = len(fr)

    def _prefill_chunks(self) -> int:
        """Advance every prefilling slot by its scheduled chunk: pack up to
        ``max_prefill_tokens`` chunk rows into power-of-two buckets (FCFS by
        admission age), launch one fused ``[n, blen]`` chunk prefill per
        bucket, and sample the first token on rows whose chunk completes the
        prompt.  Returns the number of chunk rows worked."""
        items = [(i, int(self.pos[i]), int(self.pref_target[i]))
                 for i in sorted(
                     (j for j in self._active_slots()
                      if self.pos[j] < self.pref_target[j]),
                     key=lambda j: self.slots[j].submit_seq)]
        if not items:
            return 0
        if self.faults is not None and self.faults.fires("prefill_launch"):
            # the launch died before any KV write (SimulatedDeviceError
            # semantics) — every scheduled chunk simply retries next step;
            # the charge is bounded so a permanently failing launch turns
            # the oldest victim terminal instead of spinning
            self._charge_retry(items[0][0], "prefill launch faulted")
            return 0
        worked = 0
        phase = self.trace.phase
        with phase("plan_chunks"):
            buckets = self.sched.plan_chunks(items)
        for bkt in buckets:
            with phase("prefill_inputs"):
                n, blen = len(bkt.slots), bkt.pad_len
                starts = np.asarray(bkt.starts, np.int32)
                lens = np.asarray(bkt.lens, np.int32)
                toks = np.zeros((n, blen), np.int32)
                for r, slot in enumerate(bkt.slots):
                    req = self.slots[slot]
                    toks[r, : lens[r]] = \
                        req.prompt[starts[r]: starts[r] + lens[r]]
                table, aux = self._launch_rows(bkt.slots, np.ones(n, bool))
                args = (jnp.asarray(toks), jnp.asarray(lens - 1),
                        jnp.asarray(starts), jnp.asarray(lens), table)
            with phase("prefill_dispatch"):
                logits, self.pools = self._prefill_chunk(
                    self.params, *args, self.pools, *aux)
                # drop the chunk's uploads once the launch holds them, so
                # they are not kept alive while the next bucket allocates
                del args, aux
                finals = [self.slots[s] if f else None
                          for s, f in zip(bkt.slots, bkt.final)]
                if any(bkt.final):
                    self.key, sk = jax.random.split(self.key)
                    temps = jnp.asarray(
                        [r.temperature if r else 0.0 for r in finals],
                        jnp.float32)
                    firsts = self._sample_reqs(logits, sk, temps, finals)
            if any(bkt.final):
                with phase("first_token_wait"):
                    firsts = np.asarray(firsts)
                    now = self._clock()
            with phase("emit"):
                if self._obs:
                    self.trace.note_prefill(blen, bkt.starts, bkt.lens,
                                            bkt.final)
                for r, slot in enumerate(bkt.slots):
                    self.pos[slot] += int(lens[r])
                    self.stats.prefilled_tokens += int(lens[r])
                    worked += 1
                    if self._obs:
                        self.trace.note_chunk(slot, self.slots[slot].uid,
                                              int(lens[r]))
                    if bkt.final[r]:
                        req = self.slots[slot]
                        if req._replay_tok is not None:
                            # swap-corruption re-prefill just replayed
                            # already-generated tokens: the "first token" of
                            # this prefill was sampled long ago — restore
                            # the decode feed instead of appending a
                            # duplicate
                            self.last_tok[slot] = req._replay_tok
                            req._replay_tok = None
                        else:
                            first = int(firsts[r])
                            req.output.append(first)
                            req.first_token_t = now
                            self.last_tok[slot] = first
                            if self._obs:
                                tl = self.trace.timeline(req.uid)
                                tl.add(now, "first_token", slot=slot)
                                tl.first_token_t = now
                                tl.last_emit_t = now
                                self._h_ttft.observe(now - req.arrival_t)
                        if self.cache is not None:
                            self._cache_insert_slot(slot)
                self.stats.prefill_batches += 1
        return worked

    def _sync_cache_stats(self) -> None:
        """Mirror the prefix cache's eviction counter into the engine stats.
        Must run on *every* step exit — evictions happen during admission
        (page alloc under pressure), so syncing only after a decode leaves
        ``stats.pages_evicted`` stale on steps that admit + chunk-prefill but
        have nothing to decode yet."""
        if self.cache is not None:
            self.stats.pages_evicted = self.cache.stats.evicted_pages

    # -------------------------------------------------------------- step ---
    def step(self) -> int:
        """One mixed engine step: expire deadlines, admit waiting requests,
        grow/preempt page tables as needed, advance prefilling slots by one
        budgeted chunk round, decode one token for every slot past its
        prefill target.  Returns the number of rows worked (decode slots +
        chunk rows)."""
        self._step_idx += 1
        self.trace.begin_step(self._step_idx)
        phase = self.trace.phase
        with phase("step"):
            self._retry_pending = False
            pre_injected = 0
            if self.faults is not None:
                self.faults.begin_step(self._step_idx)
                pre_injected = self.faults.total_injected
            with phase("expire"):
                self._expire_deadlines()
            worked = self._step_inner()
            self._sync_cache_stats()
            if self.faults is not None:
                self.stats.faults_injected = self.faults.total_injected
                # any fire this step (e.g. a page_alloc outage rejecting an
                # otherwise-fine admission) or an active pressure window
                # means a zero-work step is fault-induced back-off, not a
                # livelock — the drain guard must keep stepping instead of
                # raising a stall
                if (self.faults.total_injected > pre_injected
                        or self.faults.pressure_active()):
                    self._retry_pending = True
            with phase("drain_swap"):
                self._drain_swap_buffers()
            if self._obs:
                with phase("journal"):
                    used = (self.pager.num_pages - 1) - self.pager.free_pages
                    self.metrics.gauge("pool_used_pages").set(used)
                    self.metrics.gauge("active_slots").set(
                        sum(s is not None for s in self.slots))
                    self.trace.end_step(self._last_dec, pages_used=used,
                                        pages_free=self.pager.free_pages)
        return worked

    def _step_inner(self) -> int:
        self._last_dec = []
        phase = self.trace.phase
        with phase("admit"):
            self._admit()
        with phase("grow"):
            stalled = self._ensure_pages()
        with phase("prefill"):
            chunked = self._prefill_chunks()
        # decode set AFTER chunking: a slot whose final chunk just sampled
        # its first token decodes this same step (parity with the old
        # admit-then-decode flow).  Slots whose lazy growth hit an injected
        # fault sit the launch out — their tables don't cover the write
        # position yet — and retry next step on their bounded budget.
        dec = [i for i in self._active_slots()
               if i not in stalled and self.pos[i] >= self.pref_target[i]]
        if not dec:
            return chunked
        # pager tripwires: no active slot may point at the trash page, every
        # refcount must match the tables + swap holds, and the page under
        # each write cursor must be private (shared pages are read-only)
        with phase("check_tables"):
            try:
                KV.assert_live_tables(
                    self.pager.table(), self.pos, self.PS,
                    [s is not None and i not in stalled
                     for i, s in enumerate(self.slots)],
                    refs=self.pager.refs(), held=self.pager.held(),
                    cached=self.pager.cached_mask(),
                    aux_tables=tuple(self.pager.table(g)
                                     for g in self.pager.groups
                                     if g != "kv"))
            except KV.PagerInvariantError as e:
                if self.strict or e.slot is None:
                    raise
                # quarantine: fail the offending request, free what it
                # held, keep serving everyone else.  Skip this launch
                # (tables may be mid-repair); the next step re-checks from
                # scratch.
                self._evict_slot(int(e.slot), "failed",
                                 f"pager invariant violated: {e}")
                self._retry_pending = True
                return chunked
        with phase("decode"):
            return self._decode_slots(dec) + chunked

    def _launch_rows(self, rows, live) -> Tuple[jax.Array, tuple]:
        """The page table and ``aux`` of a step launch whose row ``r`` serves
        slot ``rows[r]``.  Rows with ``live[r]`` false do work that lands
        nowhere: their table rows point at the trash page, which absorbs the
        dummy KV write; their encoder reads see the trash page with zero
        valid rows (clamped to one masked row inside the model); and their
        fixed rows keep their state (``active`` false).  ``aux`` is ``()``
        for attention-only state and otherwise ``(dict,)``, so the launch
        passes it as ``*aux``."""
        rows = np.asarray(rows, np.int32)
        dead = ~np.asarray(live, bool)
        table = self.pager.table()[rows]
        table[dead] = KV.TRASH_PAGE
        aux = {}
        if self.has_fixed:
            aux["slots"] = jnp.asarray(rows)
            aux["active"] = jnp.asarray(~dead)
        if self.has_enc:
            etbl = self.pager.table("enc")[rows]
            elen = self.enc_len[rows]
            etbl[dead] = KV.TRASH_PAGE
            elen[dead] = 0
            aux["enc_table"] = jnp.asarray(etbl)
            aux["enc_len"] = jnp.asarray(elen)
        return jnp.asarray(table), ((aux,) if aux else ())

    def _decode_slots(self, dec: List[int]) -> int:
        """Decode one token for every slot in ``dec``; returns ``len(dec)``
        (0 when an injected launch fault skips the step)."""
        phase = self.trace.phase
        if self.faults is not None and self.faults.fires("decode_launch"):
            # the launch died before dispatch — no KV write, no sample, no
            # cursor moved — so retrying next step is always sound; the
            # oldest decode slot carries the bounded charge
            self._charge_retry(min(dec, key=lambda j: self.slots[j].submit_seq),
                               "decode launch faulted")
            return 0
        with phase("decode_inputs"):
            # rows not decoding (empty, or mid-prefill) are masked out of the
            # launch (:meth:`_launch_rows`) and their logits are discarded
            live = np.zeros(self.B, bool)
            live[dec] = True
            pos_np = self.pos.copy()
            tok_np = self.last_tok.copy()
            pos_np[~live] = 0
            tok_np[~live] = 0
            table, aux = self._launch_rows(np.arange(self.B), live)
            args = (jnp.asarray(tok_np[:, None]), jnp.asarray(pos_np), table)
        with phase("decode_dispatch"):
            logits, self.pools = self._decode(
                self.params, self.pools, *args, *aux)
            self.key, sk = jax.random.split(self.key)
            rows = [self.slots[i] if live[i] else None
                    for i in range(self.B)]
            temps = jnp.asarray([
                r.temperature if r else 0.0 for r in rows
            ], jnp.float32)
            nxt = self._sample_reqs(logits, sk, temps, rows)
        with phase("token_wait"):
            nxt = np.asarray(nxt)
        with phase("emit"):
            self._emit_tokens(dec, nxt)
        return len(dec)

    def _emit_tokens(self, dec: List[int], nxt: np.ndarray) -> None:
        """Append each decoded slot's sampled token, and finish and free the
        slots that reached their end."""
        self.stats.steps += 1
        self.stats.max_active = max(self.stats.max_active, len(dec))
        self.stats.active_slot_steps += len(dec)
        self._last_dec = dec
        # one clock reading covers every token this step emitted (they left
        # the same launch) — the ITL anchor and done_t share it
        now = self._clock() if self._obs else None
        if self._obs:
            self.trace.note_decode(int(self.pos[i]) + 1 for i in dec)
        for i in dec:
            req = self.slots[i]
            t = int(nxt[i])
            req.output.append(t)
            self.pos[i] += 1
            self.last_tok[i] = t
            self.stats.decoded_tokens += 1
            if self._obs:
                tl = self.trace.timeline(req.uid)
                if tl.last_emit_t is not None:
                    self._h_itl.observe(now - tl.last_emit_t)
                tl.last_emit_t = now
            hit_len = len(req.output) >= req.max_tokens
            hit_eos = t == self.eos
            # pos is the *next* write position; all S cache rows (0..S-1) are
            # writable, so the cap trips only at pos == S.  (`>= S - 1` here
            # was an off-by-one that left the last pool row of a max-length
            # request unwritten and cost it one token of budget.)
            hit_cap = self.pos[i] >= self.S
            if hit_len or hit_eos or hit_cap:
                req.done_t = now if now is not None else self._clock()
                req.finish_reason = "completed" if hit_eos else "length"
                self.stats.completed += 1
                if self._obs:
                    self._note_finish(req)
                if self.cache is not None:
                    # index the generated full pages too before the refs
                    # drop: identical continuations (multi-turn) now match
                    self._cache_insert_slot(i)
                self.slots[i] = None   # slot freed → continuous batching
                self.pos[i] = 0
                self.last_tok[i] = 0
                self.pref_target[i] = 0
                self.enc_len[i] = 0
                self.pager.free_slot(i)

    def _drain_swap_buffers(self) -> None:
        """Finish pending swap-out transfers: the async device→host copy
        started at preemption has had this whole decode step to complete, so
        materialize the rows to numpy now and drop the device-side gather
        buffer — otherwise a long-preempted request would keep its entire
        private-page image alive in device memory, which is exactly what
        swap-out exists to release.

        Fault sites: ``swap_drain`` leaves an image "in flight" another step
        (resume then device_gets it directly — correct, just not yet freed);
        ``swap_corrupt`` flips a byte of a drained image *after* its CRC-32
        was recorded, modelling host-buffer rot — the mismatch is caught at
        swap-in (:meth:`_verify_swap_image`) and the victim re-prefills.
        ``fixed_drain`` is the fixed-rows twin of ``swap_drain``: it only
        targets images carrying SSM state rows, so hybrid-specific
        resume-before-drain runs don't perturb the attention-only chaos
        suites' probe sequences."""
        for st in self._swapped.values():
            has_img = st.rows is not None or st.fixed_rows is not None
            if has_img and not st.on_host:
                site = "fixed_drain" if st.fixed_rows is not None \
                    else "swap_drain"
                if self.faults is not None and self.faults.fires(site):
                    continue                    # transfer "still in flight"
                if st.rows is not None:
                    st.rows = jax.device_get(st.rows)
                if st.fixed_rows is not None:
                    st.fixed_rows = jax.device_get(st.fixed_rows)
                st.on_host = True
                st.checksum = api.swap_image_checksum(
                    {"kv": st.rows, "fixed": st.fixed_rows})
            if (st.on_host and has_img and not st.corrupted
                    and self.faults is not None
                    and self.faults.fires("swap_corrupt")):
                img = corrupt_host_image(
                    {"kv": st.rows, "fixed": st.fixed_rows})
                st.rows, st.fixed_rows = img["kv"], img["fixed"]
                st.corrupted = True

    def _deadline_left_s(self, r: Request, now: float) -> Optional[float]:
        """Tightest remaining deadline of ``r`` in seconds: negative means
        already past due (the expiry sweep will catch it next step); ``None``
        when the request carries no deadline at all."""
        rem = []
        age = now - r.arrival_t
        if r.deadline_s is not None:
            rem.append(r.deadline_s - age)
        if r.ttft_deadline_s is not None and r.first_token_t is None:
            rem.append(r.ttft_deadline_s - age)
        return min(rem) if rem else None

    def metrics_snapshot(self) -> dict:
        """The one structured view of engine state — latency histograms
        (TTFT / ITL / e2e / queue wait / swap stall, with p50/p90/p99 under
        the documented percentile rule), cumulative :class:`EngineStats`,
        labeled counters/gauges, scheduler and pager counters, pager
        occupancy, and the live pending set (uid, phase, progress, remaining
        deadline).  ``launch/serve.py`` stat lines,
        ``scripts/trace_viewer.py`` and the stall/max_steps diagnostics (via
        :func:`repro.serving.metrics.format_pending`) all read from here;
        nothing formats engine internals on its own."""
        now = self._clock()
        pending = []
        for r in self.queue:
            pending.append({
                "uid": r.uid,
                "phase": ("swapped" if r.submit_seq in self._swapped
                          else "queued"),
                "slot": None, "pos": None, "prompt": len(r.prompt),
                "out": len(r.output), "max_tokens": r.max_tokens,
                "retries": r.retries,
                "deadline_left_s": self._deadline_left_s(r, now)})
        for i, r in enumerate(self.slots):
            if r is None:
                continue
            pending.append({
                "uid": r.uid,
                "phase": ("prefilling" if self.pos[i] < self.pref_target[i]
                          else "decoding"),
                "slot": i, "pos": int(self.pos[i]), "prompt": len(r.prompt),
                "out": len(r.output), "max_tokens": r.max_tokens,
                "retries": r.retries,
                "deadline_left_s": self._deadline_left_s(r, now)})
        m = self.metrics.snapshot()
        return {
            "step": self._step_idx,
            "engine": dataclasses.asdict(self.stats),
            "latency": {
                name: self.metrics.histogram(name).summary()
                for name in ("ttft_s", "itl_s", "e2e_s", "queue_wait_s",
                             "swap_stall_s")},
            "counters": m["counters"],
            "gauges": m["gauges"],
            "scheduler": dict(self.sched.counts),
            "pager": {
                "free_pages": self.pager.free_pages,
                "total_pages": self.pager.num_pages - 1,
                "held": int(self.pager.held().sum()),
                "evictable": self.pager.evictable_pages(),
                "swapped_images": len(self._swapped),
                "counts": dict(self.pager.counts),
            },
            "pending": pending,
        }

    def _pending_report(self) -> str:
        """Stall/max_steps diagnostic text — a rendering of
        :meth:`metrics_snapshot`, not a second formatting path."""
        return format_pending(self.metrics_snapshot())

    def run_until_drained(self, max_steps: int = 10_000) -> EngineStats:
        """Step until queue and slots are empty.  ``max_steps`` bounds *all*
        iterations, idle ones included.  An iteration that works nothing
        while requests still wait means admission is stalled — the drain is
        single-threaded and deterministic, so no later iteration could do
        better — *unless* an injected/transient fault ate the step's work
        (``_retry_pending``), where the bounded retry budgets guarantee
        progress or a terminal ``failed``.  A genuine stall raises
        immediately under ``strict`` (naming every pending request), and
        under ``strict=False`` quarantines the blocked head
        (``finish_reason="failed"``) and keeps draining everyone else.
        Hitting the ceiling with work still pending always raises: a silent
        return here used to hand back truncated outputs that looked
        complete."""
        iters = 0
        while (self.queue or any(s is not None for s in self.slots)):
            if iters >= max_steps:
                raise RuntimeError(
                    f"run_until_drained hit max_steps={max_steps} with work "
                    f"left: {len(self.queue)} queued, "
                    f"{sum(s is not None for s in self.slots)} active "
                    f"slot(s) — raise max_steps or shrink the workload; "
                    f"pending:\n{self._pending_report()}")
            iters += 1
            if self.step() == 0 and self.queue:
                self.stats.idle_steps += 1
                if self._retry_pending:
                    continue    # fault ate this step; budgets bound the spin
                head = self.queue[0]
                swapped = head.submit_seq in self._swapped
                need = (len(self._swapped[head.submit_seq].private_lis)
                        if swapped
                        else self.sched.pages_needed(head, self.pager,
                                                     self.cache))
                free_slots = sum(s is None for s in self.slots)
                msg = (
                    f"admission stalled: queue head request uid={head.uid} "
                    f"(prompt {len(head.prompt)} tokens, "
                    f"{'swapped-out, ' if swapped else ''}"
                    f"needs {need} pages) cannot be admitted with "
                    f"free_pages={self.pager.free_pages}/"
                    f"{self.pager.num_pages - 1} "
                    f"(+{self.pager.evictable_pages()} evictable), "
                    f"free_slots={free_slots}/"
                    f"{self.B}, and no active slot can unblock it; "
                    f"pending:\n{self._pending_report()}")
                if not self.strict:
                    # degrade: the head alone is unservable — fail it, keep
                    # the engine alive for everything behind it
                    self.queue.popleft()
                    self._finish_abnormal(head, "failed", msg)
                    continue
                raise RuntimeError(msg)
        return self.stats


def load_or_quantize(
    params_fp,
    cfg: ModelConfig,
    calibration_batches,
    qcfg: QuantConfig = QuantConfig(),
    *,
    artifact_dir=None,
    refresh: bool = False,
):
    """Load-*or*-quantize engine boot (quantize once, serve many).

    If ``artifact_dir`` holds a PTQ artifact whose config hash matches
    ``(cfg, qcfg)``, the quantized pytree + report deserialize straight from
    disk — zero calibration batches consumed, zero α-search steps.  Otherwise
    (no artifact, or a stale one from a changed config) the full SmoothQuant+
    recipe runs on ``params_fp`` and, when ``artifact_dir`` is given, the
    result is persisted for the next boot.  The hash covers the *configs*,
    not the weight values — after swapping checkpoints under an unchanged
    config, pass ``refresh=True`` (CLI: ``--ptq-refresh``) to force
    re-quantization."""
    from repro.core import apply as AP

    import zipfile

    if artifact_dir is not None and not refresh and AP.has_ptq(artifact_dir):
        try:
            return AP.load_ptq(artifact_dir, cfg, qcfg)
        except (ValueError, KeyError, OSError, zipfile.BadZipFile):
            # stale config hash, unknown format version, or a corrupt /
            # truncated meta.json / arrays.npz: every recoverable-by-
            # requantizing failure falls through to the full recipe (and
            # re-saves below) — unless there are no fp params to requantize
            # from (artifact-only warm boot), where hiding the load error
            # would just crash later inside calibration
            if params_fp is None:
                raise
    qp, rep = AP.smoothquant_plus(params_fp, cfg, calibration_batches, qcfg)
    if artifact_dir is not None:
        AP.save_ptq(artifact_dir, qp, rep, cfg, qcfg)
    return qp, rep


def load_and_quantize(
    params_fp, cfg: ModelConfig, calibration_batches, qcfg: QuantConfig = QuantConfig()
):
    """Quantize-on-load (paper §2.3): FP params in, W4A16 params out.
    Kept as the artifact-free entry; see :func:`load_or_quantize`."""
    return load_or_quantize(params_fp, cfg, calibration_batches, qcfg)
