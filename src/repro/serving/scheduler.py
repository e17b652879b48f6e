"""Admission scheduler: length-bucketed batched prefill planning.

The seed engine prefilled one request at a time (one compiled B=1 trace per
prompt length).  This scheduler instead admits *every* runnable queued
request in one engine step and groups them into **length buckets** (powers of
two of the page size), so each bucket compiles one joint ``[n, bucket_len]``
prefill and the number of distinct traces stays O(log max_seq) instead of
O(#prompt lengths).

Admission is strict FCFS: the queue head is admitted only if a free slot and
enough free pages exist; nothing behind it jumps ahead (no starvation).

**Mixed steps** (:meth:`Scheduler.plan_chunks`): admission only assigns slots
and pages — the prompt tokens themselves prefill in *chunks*.  Every engine
step packs up to ``max_prefill_tokens`` actual chunk tokens across the slots
still prefilling (per-slot chunk cursor = tokens already written) alongside
the step's decode batch, vLLM/Sarathi-style, so long prompts drain across
consecutive steps while decode inter-token latency stays bounded.  Non-final
chunks end on page boundaries (later chunks start page-aligned); the head
always makes progress even when the budget is smaller than a page.

**Page reservation** (``reservation=``): ``"lazy"`` (default) reserves only
the pages covering the prompt plus one decode token — the engine grows the
page table during decode and preempts on pool pressure, so pool occupancy
tracks *live* tokens and concurrency is bounded by real memory, not by the
worst case.  ``"worstcase"`` reserves ``prompt + max_tokens`` pages up front
(no growth or preemption ever needed) — kept as the benchmark baseline the
paper's single-A100 deployment story argues against.

**Watermark**: under lazy reservation the head is admitted only while
``free_pages >= need + reserve``, where ``reserve`` starts at the number of
already-decoding slots (passed by the engine) and rises by one per admitted
request.  Each live slot thus keeps about one page of growth headroom, so
preemption is the rare pressure-relief valve, not a steady-state tax.  The
reserve is waived when nothing is active (``reserve=0``) so an empty engine
can always admit its head and never deadlocks on its own watermark.

**Prefix cache** (``cache=`` on :meth:`Scheduler.plan`): the head's prompt
is matched against the block-hash index first.  Matched whole pages are
*attached* (shared, refcounted — no allocation, no prefill) and admission is
charged only for the **uncached suffix**; buckets are keyed by the suffix's
bucket length, so a 2000-token prompt behind a warm system prefix competes
for prefill budget like the 20-token suffix it actually is.  At least one
token is always prefilled (the engine needs last-token logits to sample):
when the whole prompt is cached (a page-aligned full match) the plan takes a
**copy-on-write** of the final matched page and re-prefills just the last
prompt token into the private copy.
"""
from __future__ import annotations

import dataclasses
from collections import deque
from typing import Deque, List, Optional, Tuple

from repro.serving.faults import TransientFault
from repro.serving.kv_cache import PagePool


@dataclasses.dataclass
class PrefillBucket:
    pad_len: int          # joint prefill length (suffix tokens)
    reqs: list            # admitted Requests, FCFS order
    slots: List[int]      # slot id per request
    needs: List[int]      # fresh pages allocated per request
    prefix_lens: List[int] = dataclasses.field(default_factory=list)
    # matched prefix tokens per request (0 = cold)
    shared: List[int] = dataclasses.field(default_factory=list)
    # pages attached (shared, not allocated) per request
    cow: List[Optional[Tuple[int, int]]] = dataclasses.field(
        default_factory=list)
    # (src, dst) pool pages whose rows the engine must copy before prefill


@dataclasses.dataclass
class ChunkBucket:
    """One fused ``[n, pad_len]`` prefill-chunk launch of a mixed step."""
    pad_len: int          # padded chunk length (power of two of page_size)
    slots: List[int]      # engine slot per row
    starts: List[int]     # tokens already written per row (chunk cursor)
    lens: List[int]       # valid chunk tokens per row (<= pad_len)
    final: List[bool]     # True when this chunk completes the row's prompt


@dataclasses.dataclass
class _AdmissionCost:
    """Page arithmetic for admitting one request — the single source shared
    by :meth:`Scheduler.plan` and its diagnostic twin
    :meth:`Scheduler.pages_needed`, so the admission-stall report can never
    drift from what admission actually charges."""
    total: int            # pages covering _tokens_wanted, ignoring the cache
    matched: list         # cached whole pages the prefix cache matched
    mtok: int             # tokens those pages cover
    full_match: bool      # page-aligned whole-prompt match (needs a COW)
    fresh: int            # pages to allocate (incl. the COW destination)
    pinned: int           # matched-but-unreferenced pages the attach pins
    enc: int = 0          # read-only encoder pages (enc-dec requests only)


class Scheduler:
    def __init__(self, *, page_size: int, max_seq: int,
                 max_prefill_tokens: Optional[int] = None,
                 reservation: str = "lazy"):
        if reservation not in ("lazy", "worstcase"):
            raise ValueError(f"unknown page reservation {reservation!r}")
        if max_prefill_tokens is not None and max_prefill_tokens < 1:
            raise ValueError(
                f"max_prefill_tokens must be >= 1, got {max_prefill_tokens}")
        self.page_size = page_size
        self.max_seq = max_seq
        self.max_prefill_tokens = max_prefill_tokens
        self.reservation = reservation
        # True when the last plan() aborted an admission on an injected
        # transient fault (rolled back, request back at the queue head) —
        # the engine reads this to count a retry and to distinguish a
        # fault-induced idle step from a genuine admission stall
        self.last_plan_aborted = False
        # cumulative planning counters, surfaced by the engine's
        # metrics_snapshot(): how many plan passes ran, requests admitted,
        # chunk rounds launched and chunk tokens scheduled, and plans
        # aborted mid-admission by an injected fault
        self.counts = {"plans": 0, "admitted": 0, "chunk_rounds": 0,
                       "chunk_tokens": 0, "aborted_plans": 0}

    def bucket_len(self, prompt_len: int) -> int:
        b = self.page_size
        while b < prompt_len:
            b *= 2
        return min(b, self.max_seq)

    def _tokens_wanted(self, req) -> int:
        if self.reservation == "worstcase":
            return min(len(req.prompt) + req.max_tokens, self.max_seq)
        # lazy: cover the prompt plus the first decode write only; the
        # engine grows the table page-by-page as decode proceeds
        return min(len(req.prompt) + 1, self.max_seq)

    def _admission_cost(self, req, pool: PagePool, cache=None,
                        probe_faults: bool = True) -> _AdmissionCost:
        """The one admission page-arithmetic path (used by both :meth:`plan`
        and :meth:`pages_needed`): cold total, cache-matched prefix credit,
        the full-match COW page, and the matched-but-unreferenced pages the
        attach is about to pin (which must not double as evictable headroom
        for the fresh allocation).  Enc-dec requests (``req.frames``) are
        additionally charged the read-only encoder pages their frames cover
        — conservatively assumed fresh here; on an encoder-cache hit the
        engine frees them again and attaches the shared pages instead.
        ``probe_faults=False`` marks the diagnostic twin's call: it must not
        consume fault-plan budget."""
        total = pool.pages_needed(self._tokens_wanted(req))
        frames = getattr(req, "frames", None)
        enc = pool.pages_needed(len(frames)) if frames is not None else 0
        if cache is None:
            return _AdmissionCost(total, [], 0, False, total, 0, enc)
        # chain hashes are pure in the prompt tokens: compute them once per
        # request, not once per engine step while blocked
        hs = getattr(req, "_block_hashes", None)
        if hs is None:
            hs = req._block_hashes = cache.block_hashes(req.prompt)
        matched, mtok = cache.match(req.prompt, hashes=hs,
                                    probe_faults=probe_faults)
        full_match = bool(matched) and mtok == len(req.prompt)
        fresh = total - len(matched) + (1 if full_match else 0)
        pinned = sum(1 for p in matched if pool.page_ref(p) == 0)
        return _AdmissionCost(total, matched, mtok, full_match, fresh, pinned,
                              enc)

    def pages_needed(self, req, pool: PagePool, cache=None) -> int:
        """Pages that must be allocatable to admit ``req`` — the diagnostic
        twin of :meth:`plan`, sharing its arithmetic via
        :meth:`_admission_cost` (fresh pages plus the matched-but-unreferenced
        pages the attach would pin, plus an enc-dec request's encoder
        pages)."""
        cost = self._admission_cost(req, pool, cache, probe_faults=False)
        return cost.fresh + cost.pinned + cost.enc

    def plan(self, queue: Deque, free_slots: List[int], pool: PagePool,
             reserve: int = 0, cache=None) -> List[PrefillBucket]:
        """Pop admissible requests off ``queue`` and bucket them.

        Reserves pages in ``pool`` for every admitted request (so a later
        bucket in the same step can't oversubscribe) and assigns slots.
        ``reserve`` is the admission watermark: free pages that must remain
        after each admit (one growth page per decoding slot — the engine
        passes its active-slot count, and each admission here adds one).
        With ``cache`` (a ``PrefixCache``), matched whole-page prefixes are
        attached shared and only the uncached suffix is charged/prefilled.
        """
        slots = deque(free_slots)
        budget = self.max_prefill_tokens
        buckets: dict = {}
        spent = 0
        self.last_plan_aborted = False
        self.counts["plans"] += 1
        while queue and slots:
            req = queue[0]
            t = len(req.prompt)
            # cheap pre-filter before hashing the prompt: no match can need
            # fewer than one fresh page, so a drained pool blocks the head
            # without re-chain-hashing a long prompt every engine step
            if not pool.can_alloc(1 + reserve):
                break
            cost = self._admission_cost(req, pool, cache)
            matched, full_match = cost.matched, cost.full_match
            fresh = cost.fresh
            # never admit a zero-token prefill: the engine samples the first
            # output from the last prompt token's logits, so a page-aligned
            # full match re-prefills that one token into a COW'd private
            # copy of the final matched page
            suffix = 1 if full_match else t - cost.mtok
            prefix = t - suffix
            # matched-but-unreferenced pages are about to be *pinned* by the
            # attach below, so they must not be double-counted as evictable
            # headroom for the fresh allocation — otherwise attach + grow
            # would blow up on a pool whose only evictable pages are the very
            # ones this request is re-using
            if not pool.can_alloc(fresh + reserve + cost.pinned + cost.enc):
                break                       # FCFS: head blocks the line
            blen = self.bucket_len(suffix)
            if budget is not None and spent and spent + blen > budget:
                break                       # chunk the backlog across steps
            queue.popleft()
            slot = slots.popleft()
            cow_pair = None
            try:
                if matched:
                    pool.attach(slot, matched)
                # hold_src: the engine performs the src→dst device copy later
                # (per bucket, before its prefill); the hold pins src so no
                # allocation in the rest of this plan can reclaim + overwrite
                # it first — the engine drops the hold right after the copy
                cow_pair = (pool.cow(slot, len(matched) - 1, hold_src=True)
                            if full_match else None)
                if fresh - (1 if full_match else 0):
                    pool.grow(slot, fresh - (1 if full_match else 0))
                if cost.enc:
                    # read-only encoder pages, allocated fresh here; on an
                    # encoder-cache hit the engine frees them and attaches
                    # the shared cached pages instead
                    pool.grow(slot, cost.enc, group="enc")
            except TransientFault:
                # injected grow fault mid-admission: roll the whole admission
                # back (release attached pages + the COW copy and its hold,
                # requeue at the head — FCFS preserved) and stop planning;
                # the head simply retries next step
                if cow_pair is not None:
                    pool.drop_hold(cow_pair[0])
                pool.free_slot(slot)
                queue.appendleft(req)
                self.last_plan_aborted = True
                self.counts["aborted_plans"] += 1
                break
            if self.reservation == "lazy":
                reserve += 1                # growth headroom for the new slot
            shared = len(matched) - (1 if full_match else 0)
            key = (blen, prefix > 0)
            bkt = buckets.get(key)
            if bkt is None:
                bkt = buckets[key] = PrefillBucket(blen, [], [], [])
            bkt.reqs.append(req)
            bkt.slots.append(slot)
            bkt.needs.append(fresh)
            self.counts["admitted"] += 1
            bkt.prefix_lens.append(prefix)
            bkt.shared.append(shared)
            bkt.cow.append(cow_pair)
            spent += blen
        return list(buckets.values())

    def plan_chunks(self, prefilling: List[Tuple[int, int, int]],
                    budget: Optional[int] = None) -> List[ChunkBucket]:
        """Token-budget mixed-step chunk planning (vLLM/Sarathi-style).

        ``prefilling`` is ``[(slot, written, target)]`` in FCFS order:
        ``written`` counts tokens already in the slot's pages (cached prefix
        plus earlier chunks — the per-slot chunk cursor), ``target`` the
        prompt length the prefill must reach.  Each call packs up to
        ``budget`` actual chunk tokens (default ``max_prefill_tokens``;
        ``None`` = everything) and groups the chunks into power-of-two
        buckets like :meth:`plan`, so one engine step launches O(1) fused
        ``[n, pad_len]`` chunk prefills alongside its decode batch.

        Non-final chunks end on a page boundary, keeping every later chunk's
        start page-aligned (whole prefix pages for the kernel grid, clean
        scatter).  When the budget is smaller than the distance to the next
        boundary, the unaligned chunk is taken anyway — progress beats
        alignment, and the next call re-aligns.  The queue head always gets
        at least one token, so a budget below every chunk size still drains.
        """
        if budget is None:
            budget = self.max_prefill_tokens
        left = budget
        buckets: dict = {}
        for slot, written, target in prefilling:
            remaining = target - written
            if remaining <= 0:
                continue
            c = remaining if left is None else min(remaining, left)
            if c <= 0:
                break                       # budget exhausted: FCFS tail waits
            if c < remaining:
                aligned = ((written + c) // self.page_size) * self.page_size
                if aligned > written:
                    c = aligned - written
            blen = self.bucket_len(c)
            bkt = buckets.get(blen)
            if bkt is None:
                bkt = buckets[blen] = ChunkBucket(blen, [], [], [], [])
            bkt.slots.append(slot)
            bkt.starts.append(written)
            bkt.lens.append(c)
            bkt.final.append(written + c == target)
            self.counts["chunk_tokens"] += c
            if left is not None:
                left -= c
        if buckets:
            self.counts["chunk_rounds"] += 1
        return list(buckets.values())
