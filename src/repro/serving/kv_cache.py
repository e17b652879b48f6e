"""Paged KV cache: fixed-size pages + per-slot page tables (the vLLM idea).

The decode cache is a single device-resident *pool* per layer —
``k[num_pages, Hkv, page_size, Dh]`` — instead of one contiguous
``[B, Smax, ...]`` slab.  A host-side :class:`PagePool` hands out pages to
slots on admission and reclaims them when a request finishes, so cache memory
scales with *live tokens*, not ``batch_size × max_seq``.

Logical position ``t`` of slot ``s`` lives at
``pool[table[s, t // page_size], :, t % page_size]`` (the page layout
lives with the attention code: ``models.attention.init_page_pool``).

Page 0 is reserved as a **trash page**: every unused page-table entry points
at it, so idle slot rows in the batched decode step scatter their garbage
writes somewhere harmless and gathers from idle slots read masked-out data.

**Sharing (shared-prefix KV cache)**: pages carry a per-page *refcount*.  The
old "owned by at most one slot" invariant relaxes to "a *full, read-only*
page may be listed in several slots' tables"; the page covering a slot's
write position is always private (refcount 1, not cache-resident).  The
prefix cache (``serving/prefix_cache.py``) registers itself as the pool's
*evictor*: pages it indexes stay resident after their last slot reference
drops (refcount 0 + cached = evictable) and are reclaimed lazily, LRU-first,
when an allocation would otherwise fail.  ``cow`` gives a slot a private
copy of a shared page before it writes into it (copy-on-write), and
``swap_out``/``swap_in`` keep shared pages resident across preemption (they
are never swapped to host with a victim — resume re-acquires them).

**Page groups (state-leaf kinds)**: the pool can serve several *groups* of
per-slot page tables over one shared free list / refcount space — ``"kv"``
(the default: read-write paged KV, everything above) plus read-only groups
like ``"enc"`` (whisper encoder K/V pages, written once at admission and
shared via the prefix-cache refcount machinery).  Every page id is owned by
at most one group at a time; read-only groups never grow during decode,
never take COWs, and survive preemption as holds
(:meth:`detach_group` / :meth:`reattach_group`) instead of host swaps.
Fixed-rows state (SSM) is *not* paged at all — it lives in per-slot device
rows owned by the engine; the pool's job there ends at the slot gate.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.serving.faults import TransientFault

TRASH_PAGE = 0


class PagerInvariantError(RuntimeError):
    """A pager tripwire fired (stale table, refcount drift, shared-page write
    hazard).  ``slot`` names the offending slot when one is identifiable, so
    a non-strict engine can quarantine that request and keep serving; it is
    ``None`` for pool-global violations (refcount drift), which only a hard
    stop can handle safely."""

    def __init__(self, msg: str, slot: Optional[int] = None):
        super().__init__(msg)
        self.slot = slot


class PagePool:
    """Host-side page allocator over the device pools.

    Invariants (checked by :meth:`check_invariants`):
      - the trash page (page 0) is never allocated, cached, or held;
      - ``ref[p]`` equals the number of slot-table listings of ``p`` plus its
        swap holds; pages listed by several slots (or cached) are the shared
        read-only prefix pages;
      - ``free``, ``{ref > 0}``, and ``{ref == 0, cached}`` (the evictable
        set, mirrored by the evictor's LRU) partition ``{1, .., num_pages-1}``;
      - a page id is listed by at most one *group*'s tables (a kv page never
        doubles as an encoder page and vice versa).
    """

    def __init__(self, num_pages: int, page_size: int, batch_size: int,
                 max_pages_per_slot: int,
                 groups: Tuple[str, ...] = ("kv",),
                 group_max_pages: Optional[Dict[str, int]] = None):
        if num_pages < 2:
            raise ValueError("need >= 2 pages (page 0 is the trash page)")
        if page_size < 1 or max_pages_per_slot < 1:
            raise ValueError("page_size/max_pages_per_slot must be >= 1")
        if groups[0] != "kv":
            raise ValueError(f"group 'kv' must come first, got {groups!r}")
        self.num_pages = num_pages
        self.page_size = page_size
        self.batch_size = batch_size
        self.max_pages_per_slot = max_pages_per_slot
        self.groups = tuple(groups)
        self._maxp: Dict[str, int] = {g: max_pages_per_slot for g in groups}
        if group_max_pages:
            self._maxp.update(group_max_pages)
        self._free: List[int] = list(range(num_pages - 1, 0, -1))
        self._slot_pages_g: Dict[str, List[List[int]]] = {
            g: [[] for _ in range(batch_size)] for g in groups}
        self._table_g: Dict[str, np.ndarray] = {
            g: np.full((batch_size, self._maxp[g]), TRASH_PAGE, np.int32)
            for g in groups}
        # the "kv" group keeps its historical attribute names: every
        # read-write path (COW, swap, growth) is kv-only and indexes these
        self._slot_pages = self._slot_pages_g["kv"]
        self._table = self._table_g["kv"]
        self._ref = np.zeros(num_pages, np.int32)   # slot listings + holds
        self._held: Dict[int, int] = {}             # page -> swap-hold count
        self._cached: set = set()                   # prefix-cache resident
        self._evictor = None                        # PrefixCache (or None)
        self.faults = None                          # FaultPlan (or None)
        # cumulative page-event counters for observability
        # (grown/COW'd/attached/freed/evicted), reported by
        # ``metrics_snapshot()``.  Pure host-side ints — recording never
        # touches device state.
        self.counts: Dict[str, int] = {
            "grown": 0, "cow": 0, "attached": 0, "freed": 0, "evicted": 0}

    # ------------------------------------------------------------- queries --
    @property
    def free_pages(self) -> int:
        return len(self._free)

    def pages_needed(self, tokens: int) -> int:
        return max(1, -(-tokens // self.page_size))

    def evictable_pages(self) -> int:
        return self._evictor.evictable_count() if self._evictor else 0

    def can_alloc(self, n: int) -> bool:
        """Whether ``n`` pages are obtainable (free now or via LRU eviction
        of unreferenced cached pages).

        Fault sites: ``page_alloc`` reports a transient allocator outage
        (pages exist but the probe says no — admission/growth backs off and
        retries), ``pool_pressure`` withholds phantom pages for the spike's
        duration.  Both degrade through the *existing* "not enough pages"
        paths, so no caller needs fault-specific handling."""
        avail = len(self._free) + self.evictable_pages()
        if self.faults is not None:
            if self.faults.fires("page_alloc"):
                return False
            avail -= self.faults.pressure_pages()
        return n <= avail

    def slot_pages(self, slot: int, group: str = "kv") -> List[int]:
        return list(self._slot_pages_g[group][slot])

    def table(self, group: str = "kv") -> np.ndarray:
        """[B, max_pages_per_slot(group)] int32 page ids (trash-padded)."""
        return self._table_g[group]

    def page_ref(self, page: int) -> int:
        return int(self._ref[page])

    def is_cached(self, page: int) -> bool:
        return page in self._cached

    def refs(self) -> np.ndarray:
        """[num_pages] int32 refcounts (slot listings + swap holds)."""
        return self._ref

    def held(self) -> np.ndarray:
        """[num_pages] int32 swap-hold counts."""
        h = np.zeros(self.num_pages, np.int32)
        for p, n in self._held.items():
            h[p] = n
        return h

    def cached_mask(self) -> np.ndarray:
        """[num_pages] bool: page is registered (read-only) in the cache."""
        m = np.zeros(self.num_pages, bool)
        m[list(self._cached)] = True
        return m

    # --------------------------------------------------- evictor / caching --
    def set_evictor(self, evictor) -> None:
        """Register the prefix cache: it keeps unreferenced cached pages
        resident (LRU) and gives them back through :meth:`release_cached`."""
        self._evictor = evictor

    def mark_cached(self, page: int) -> None:
        """Prefix cache registered ``page`` (full, read-only from now on)."""
        if page == TRASH_PAGE:
            raise ValueError("cannot cache the trash page")
        self._cached.add(page)

    def release_cached(self, page: int) -> None:
        """Evictor reclaimed an unreferenced cached page → back to free."""
        if self._ref[page] != 0 or page not in self._cached:
            raise RuntimeError(f"page {page} is not an evictable cached page")
        self._cached.discard(page)
        self._free.append(page)
        self.counts["evicted"] += 1

    def _take_free(self, n: int) -> List[int]:
        """Pop ``n`` free pages, evicting LRU cached pages as needed."""
        while len(self._free) < n and self._evictor is not None \
                and self._evictor.evict_one():
            pass
        if n > len(self._free):
            raise RuntimeError(f"out of pages: need {n}, free {len(self._free)}")
        return [self._free.pop() for _ in range(n)]

    def _release(self, page: int) -> None:
        """Drop one reference; an unreferenced page returns to the free list
        unless the prefix cache still indexes it (→ evictable, LRU)."""
        self._ref[page] -= 1
        assert self._ref[page] >= 0, f"refcount underflow on page {page}"
        if self._ref[page] == 0:
            if page in self._cached:
                self._evictor.on_unreferenced(page)
            else:
                self._free.append(page)
                self.counts["freed"] += 1

    # ------------------------------------------------------- alloc / free ---
    def alloc(self, slot: int, n: int) -> List[int]:
        """Give ``slot`` ``n`` pages.  The slot must currently own none."""
        if self._slot_pages[slot]:
            raise RuntimeError(f"slot {slot} already owns pages")
        return self.grow(slot, n)

    def grow(self, slot: int, n: int = 1, group: str = "kv") -> List[int]:
        """Append ``n`` fresh private pages to ``slot`` (which may already
        own some).

        This is what lazy decode growth calls when a slot's write position
        crosses a page boundary: the new pages extend the slot's page-table
        prefix, so already-written logical positions keep their mapping.
        Non-``"kv"`` groups use the same path at admission time only (an
        encoder allocation is a one-shot grow, never incremental).
        """
        sp, tab = self._slot_pages_g[group], self._table_g[group]
        owned = len(sp[slot])
        if owned + n > self._maxp[group]:
            raise ValueError(
                f"slot {slot} would own {owned + n} {group} pages > "
                f"max={self._maxp[group]}")
        if self.faults is not None and self.faults.fires("page_grow"):
            # raised before any allocation, so the pool is untouched: the
            # engine retries next step (bounded budget) and a mid-plan fault
            # is rolled back by the scheduler's admission abort
            raise TransientFault(
                f"injected page_grow fault (slot {slot}, n={n})")
        pages = self._take_free(n)
        for p in pages:
            self._ref[p] = 1
        sp[slot].extend(pages)
        tab[slot, owned : owned + n] = pages
        self.counts["grown"] += n
        return pages

    def attach(self, slot: int, pages: List[int], group: str = "kv") -> None:
        """Share resident pages into ``slot``'s table (prefix-cache hit).

        The pages must be resident — referenced by another slot, held by a
        swapped-out request, or cache-resident — and are appended to the
        slot's logical page list in order.  Each gains one reference; an
        evictable page becomes pinned (leaves the evictor's LRU).
        """
        sp, tab = self._slot_pages_g[group], self._table_g[group]
        owned = len(sp[slot])
        if owned + len(pages) > self._maxp[group]:
            raise ValueError(
                f"slot {slot} would own {owned + len(pages)} {group} pages "
                f"> max={self._maxp[group]}")
        for p in pages:
            if p == TRASH_PAGE:
                raise ValueError("cannot attach the trash page")
            if self._ref[p] == 0:
                if p not in self._cached:
                    raise RuntimeError(f"page {p} is not resident (freed?)")
                self._evictor.on_referenced(p)
            self._ref[p] += 1
        sp[slot].extend(pages)
        tab[slot, owned : owned + len(pages)] = pages
        self.counts["attached"] += len(pages)

    def cow(self, slot: int, logical_idx: int, *,
            hold_src: bool = False) -> Tuple[int, int]:
        """Copy-on-write: replace ``slot``'s shared logical page with a fresh
        private one.  Returns ``(src, dst)`` pool page ids — the caller must
        copy the device rows ``src → dst`` before the slot reads or writes
        that logical page.

        With ``hold_src`` the slot's reference on ``src`` becomes a *hold*
        instead of being released, pinning the page (un-evictable, un-
        reallocatable) until the caller performs the device copy and calls
        :meth:`drop_hold`.  Without it, a released ``src`` whose refcount
        hits 0 is immediately evictable — a later allocation in the same
        planning pass could reclaim and overwrite it before a deferred copy
        reads it."""
        old = self._slot_pages[slot][logical_idx]
        new = self._take_free(1)[0]
        self._ref[new] = 1
        self.counts["cow"] += 1
        self._slot_pages[slot][logical_idx] = new
        self._table[slot, logical_idx] = new
        if hold_src:
            self._held[old] = self._held.get(old, 0) + 1
        else:
            self._release(old)
        return old, new

    def drop_hold(self, page: int) -> None:
        """Release one hold on ``page`` (COW source copied, or a swap image
        discarded): the reference it kept alive is dropped normally."""
        held = self._held[page] - 1
        if held:
            self._held[page] = held
        else:
            del self._held[page]
        self._release(page)

    def free_slot(self, slot: int) -> None:
        """Release every page ``slot`` lists, across *all* groups.  Read-only
        group pages registered in the cache simply become evictable; private
        ones return to the free list."""
        for g in self.groups:
            self.free_group(slot, g)

    def free_group(self, slot: int, group: str) -> None:
        """Release just ``slot``'s pages of one group (e.g. drop the fresh
        encoder pages an admission pre-allocated before its cache hit)."""
        sp, tab = self._slot_pages_g[group], self._table_g[group]
        for p in sp[slot]:
            self._release(p)
        sp[slot] = []
        tab[slot, :] = TRASH_PAGE

    def detach_group(self, slot: int, group: str) -> List[int]:
        """Preempt a read-only group: the slot's references on its pages
        become *swap holds* (pinned — not evictable, not reallocatable) and
        the table row clears.  The page data never leaves the device (the
        group is read-only), so there is nothing to host-swap; resume calls
        :meth:`reattach_group` with the returned page list."""
        sp, tab = self._slot_pages_g[group], self._table_g[group]
        pages = sp[slot]
        for p in pages:
            self._held[p] = self._held.get(p, 0) + 1
        sp[slot] = []
        tab[slot, :] = TRASH_PAGE
        return pages

    def reattach_group(self, slot: int, group: str, pages: List[int]) -> None:
        """Resume a read-only group: each hold from :meth:`detach_group`
        converts back into a slot reference, in order."""
        sp, tab = self._slot_pages_g[group], self._table_g[group]
        if sp[slot]:
            raise RuntimeError(f"slot {slot} already owns {group} pages")
        for p in pages:
            held = self._held[p] - 1
            if held:
                self._held[p] = held
            else:
                del self._held[p]
        sp[slot] = list(pages)
        tab[slot, : len(pages)] = pages

    def drop_group_holds(self, pages: List[int]) -> None:
        """Abandon a detached read-only group (its request finished or was
        re-admitted from scratch): drop each hold; cached pages turn
        evictable, uncached ones free."""
        for p in pages:
            self.drop_hold(p)

    # ------------------------------------------------------- swap support ---
    def split_for_swap(self, slot: int) -> Tuple[List[Tuple[int, int]],
                                                 List[Tuple[int, int]]]:
        """Partition ``slot``'s pages into ``(kept, private)`` lists of
        ``(logical_idx, page)``.  *Kept* pages are shared (refcount > 1) or
        cache-resident: they are never swapped to host with a victim — they
        stay in the pool and resume re-acquires them.  *Private* pages are
        the ones whose rows must round-trip through the host swap buffer."""
        kept, private = [], []
        for li, p in enumerate(self._slot_pages[slot]):
            if self._ref[p] > 1 or p in self._cached:
                kept.append((li, p))
            else:
                private.append((li, p))
        return kept, private

    def swap_out(self, slot: int,
                 split: Optional[Tuple[List[Tuple[int, int]],
                                       List[Tuple[int, int]]]] = None
                 ) -> Tuple[List[Tuple[int, int]], List[Tuple[int, int]]]:
        """Preemption: release ``slot``'s private pages (their rows must
        already be captured) and convert its references on shared/cached
        pages into *swap holds* so they cannot be evicted or freed while the
        request waits off-device.  Returns the :meth:`split_for_swap`
        partition.

        ``split`` is the caller's earlier :meth:`split_for_swap` result (the
        engine computes it first to gather the private rows): it is validated
        against the slot's current pages, so a pager mutation sneaking in
        between the gather and the swap-out fails loudly instead of freeing
        pages whose rows were never captured."""
        kept, private = split if split is not None else self.split_for_swap(slot)
        if sorted(kept + private) != list(enumerate(self._slot_pages[slot])):
            raise RuntimeError(
                f"swap_out partition is stale for slot {slot}: the pager "
                "changed between split_for_swap and swap_out")
        for _, p in kept:
            # the slot's reference becomes a hold: _ref stays, accounting moves
            self._held[p] = self._held.get(p, 0) + 1
        for _, p in private:
            self._release(p)
        self._slot_pages[slot] = []
        self._table[slot, :] = TRASH_PAGE
        return kept, private

    def swap_in(self, slot: int, kept: List[Tuple[int, int]],
                private_lis: List[int]) -> List[int]:
        """Resume a preempted request into ``slot``: re-acquire its held
        shared pages (hold → slot reference) and allocate fresh private pages
        at the given logical indices.  Returns the fresh page ids in
        ``private_lis`` order, ready for the swap-buffer scatter."""
        if self._slot_pages[slot]:
            raise RuntimeError(f"slot {slot} already owns pages")
        fresh = self._take_free(len(private_lis))
        entries: Dict[int, int] = {}
        for li, p in kept:
            held = self._held[p] - 1
            if held:
                self._held[p] = held
            else:
                del self._held[p]
            entries[li] = p
        for li, p in zip(private_lis, fresh):
            self._ref[p] = 1
            entries[li] = p
        if sorted(entries) != list(range(len(entries))):
            raise RuntimeError(f"swap-in logical pages not contiguous: "
                               f"{sorted(entries)}")
        pages = [entries[li] for li in range(len(entries))]
        self._slot_pages[slot] = pages
        self._table[slot, : len(pages)] = pages
        return fresh

    # ---------------------------------------------------------- invariants --
    def check_invariants(self) -> None:
        counts = np.zeros(self.num_pages, np.int64)
        group_of: Dict[int, str] = {}
        for g in self.groups:
            for sp in self._slot_pages_g[g]:
                for p in sp:
                    counts[p] += 1
                    other = group_of.setdefault(p, g)
                    assert other == g, (
                        f"page {p} listed by both {other!r} and {g!r} "
                        "group tables")
        held = self.held()
        assert counts[TRASH_PAGE] == 0, "trash page was allocated"
        assert TRASH_PAGE not in self._free, "trash page in free list"
        assert TRASH_PAGE not in self._cached, "trash page cached"
        assert held[TRASH_PAGE] == 0, "trash page held"
        assert (self._ref == counts + held).all(), (
            "refcounts out of sync with slot tables + swap holds")
        free = set(self._free)
        assert len(free) == len(self._free), "free list duplicate"
        referenced = set(np.nonzero(self._ref)[0].tolist())
        evictable = {p for p in self._cached if self._ref[p] == 0}
        assert not (free & referenced), "free page still referenced"
        assert not (free & self._cached), "free page still cached"
        assert free | referenced | evictable == set(
            range(1, self.num_pages)), "page leak / invention"
        if self._evictor is not None:
            assert set(self._evictor.evictable_page_ids()) == evictable, (
                "evictor LRU out of sync with unreferenced cached pages")
        else:
            assert not evictable, "cached pages with no evictor registered"
        for g in self.groups:
            tab = self._table_g[g]
            for s, sp in enumerate(self._slot_pages_g[g]):
                assert tab[s, : len(sp)].tolist() == sp, \
                    f"{g} table out of sync"
                assert (tab[s, len(sp):] == TRASH_PAGE).all(), \
                    f"{g} table out of sync (tail)"
                assert len(set(sp)) == len(sp), \
                    f"slot {s} lists a {g} page twice"


# ------------------------------------------------------- device-side ops ----
def prefix_write_plan(lens: np.ndarray, table_rows: np.ndarray,
                      page_size: int, pad_len: int,
                      starts: Optional[np.ndarray] = None):
    """Destination (page, offset) for each (row, t) of a padded prefill.

    ``lens[n]`` are true written lengths, ``table_rows[n, P]`` the page-table
    rows of the slots the tokens land in.  ``starts[n]`` (default 0) is the
    logical position of each row's *first* written token — a suffix-only
    prefill behind a cached prefix passes the per-row matched prefix length,
    so token ``t`` of row ``n`` lands at logical position ``starts[n] + t``.
    Padding positions (``t >= len``) are routed to the trash page.  Returns
    int32 ``(page[n, T], off[n, T])``.
    """
    n = len(lens)
    t_idx = np.arange(pad_len)[None, :]
    mask = t_idx < np.asarray(lens)[:, None]
    pos = t_idx if starts is None else t_idx + np.asarray(starts)[:, None]
    slot_pg = np.minimum(pos // page_size, table_rows.shape[1] - 1)
    page = np.where(mask, table_rows[np.arange(n)[:, None], slot_pg], TRASH_PAGE)
    off = np.broadcast_to(pos % page_size, (n, pad_len))
    return page.astype(np.int32), off.astype(np.int32)


@functools.partial(jax.jit, donate_argnums=(0,))
def write_prefix(pools: Any, kv: Any, page: jax.Array, off: jax.Array) -> Any:
    """Scatter raw prefix KV into the pools.

    ``pools`` leaves are ``[L, num_pages, H, page_size, ...]``; ``kv`` leaves
    are the matching raw prefill caches ``[L, n, T, H, ...]``;
    ``page``/``off`` are ``[n, T]`` from :func:`prefix_write_plan`.
    """
    put = jax.vmap(lambda pool, new: scatter_rows(pool, page, off, new))
    return jax.tree.map(put, pools, kv)


def assert_live_tables(table, write_pos, page_size: int, active, *,
                       refs=None, held=None, cached=None,
                       aux_tables=()) -> None:
    """Pager tripwires, vectorized (pure numpy — this runs every engine step).

    Stale-table detection: an *active* slot's live page-table prefix must
    never reference the trash page — table[s, p] == 0 for p within the pages
    covering positions ``0..write_pos[s]`` means the slot's pages were freed
    (or never allocated) while it is still decoding, i.e. a pager
    use-after-free.

    With ``refs`` (+ optional ``held``/``cached`` from the pool), refcounts
    are validated too: every non-trash table entry must be counted by
    ``refs`` (``refs == table occurrences + swap holds``), and the page an
    active slot is about to write (logical page ``write_pos // page_size``)
    must be *private and writable* — exactly one reference, no swap hold, and
    not registered read-only in the prefix cache (shared pages take a
    copy-on-write before any write reaches them).

    ``aux_tables`` carries the pool's non-KV page-group tables (e.g. the
    read-only encoder group): their listings join the refcount census —
    every group's references share one counter — but they are exempt from
    the stale/write-cursor checks, which are about the decode write path
    and only KV pages are ever written mid-decode.

    Raises :class:`PagerInvariantError` (a ``RuntimeError``) naming the
    slot/page instead of letting the decode silently read or clobber shared
    state; slot-attributable violations carry ``.slot`` so a non-strict
    engine can quarantine the one offending request and keep serving.
    """
    table = np.asarray(table)
    write_pos = np.asarray(write_pos)
    active = np.asarray(active, bool)
    b, p_max = table.shape
    need = write_pos // page_size + 1           # pages covering 0..write_pos
    cols = np.arange(p_max)[None, :]
    live = active[:, None] & (cols < need[:, None])
    stale = live & (table == TRASH_PAGE)
    if stale.any():
        s, lp = np.argwhere(stale)[0]
        raise PagerInvariantError(
            f"stale page table: active slot {int(s)} (write position "
            f"{int(write_pos[s])}) references the freed/trash page at "
            f"logical page {int(lp)} — pages were reclaimed while "
            "the slot was still decoding", slot=int(s))
    if refs is None:
        return
    refs = np.asarray(refs)
    held = np.zeros_like(refs) if held is None else np.asarray(held)
    # every table listing is counted: refs == occurrences + swap holds
    occ = np.bincount(table[table != TRASH_PAGE].ravel(),
                      minlength=refs.shape[0])
    for aux in aux_tables:
        aux = np.asarray(aux)
        occ += np.bincount(aux[aux != TRASH_PAGE].ravel(),
                           minlength=refs.shape[0])
    bad = np.nonzero(refs != occ + held)[0]
    bad = bad[bad != TRASH_PAGE]
    if bad.size:
        p = int(bad[0])
        raise PagerInvariantError(
            f"refcount out of sync: page {p} has ref={int(refs[p])} but "
            f"{int(occ[p])} table listings + {int(held[p])} swap holds")
    # the page under each active slot's write cursor must be private
    wp_page = table[np.arange(b), np.minimum(write_pos // page_size,
                                             p_max - 1)]
    not_private = active & (refs[wp_page] - held[wp_page] != 1)
    not_private |= active & (held[wp_page] != 0)
    if cached is not None:
        not_private |= active & np.asarray(cached)[wp_page]
    if not_private.any():
        s = int(np.argmax(not_private))
        p = int(wp_page[s])
        raise PagerInvariantError(
            f"shared-page write hazard: active slot {s} would write position "
            f"{int(write_pos[s])} into page {p} (ref={int(refs[p])}, "
            f"held={int(held[p])}"
            + (f", cached={bool(np.asarray(cached)[p])}" if cached is not None
               else "")
            + ") — shared/cached pages are read-only and need copy-on-write",
            slot=s)


# canonical page gather lives next to the attention decode paths that
# consume it (the jnp reference for the Pallas paged-attention kernel);
# re-exported here so pager users/tests need only this module
from repro.models.attention import gather_pages, scatter_rows  # noqa: E402,F401
