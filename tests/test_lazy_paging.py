"""Lazy page growth + preemption/swap: pager grow semantics, randomized
pager stress, engine token-identity under pool pressure, the decode-cap and
drain-guard regressions, and per-request top-k/top-p plumbing."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import api
from repro.serving import kv_cache as KV
from repro.serving.engine import Request, ServingEngine
from repro.serving.scheduler import Scheduler


# ---------------------------------------------------------------- pager -----
def test_pager_grow_appends_pages():
    pool = KV.PagePool(num_pages=9, page_size=4, batch_size=2,
                       max_pages_per_slot=6)
    a = pool.alloc(0, 2)
    g = pool.grow(0, 1)
    pool.check_invariants()
    assert pool.slot_pages(0) == a + g
    # table prefix extends in place: old logical pages keep their mapping
    assert pool.table()[0, :3].tolist() == a + g
    assert (pool.table()[0, 3:] == KV.TRASH_PAGE).all()
    # alloc still refuses a slot that owns pages; grow is the append path
    with pytest.raises(RuntimeError):
        pool.alloc(0, 1)
    pool.grow(0, 1)                        # slot 0 owns 4, 4 free
    pool.alloc(1, 4)                       # pool drained
    with pytest.raises(RuntimeError):
        pool.grow(1, 1)                    # exhausted
    with pytest.raises(ValueError):
        pool.grow(1, 3)                    # would exceed max_pages_per_slot
    pool.check_invariants()


@pytest.mark.parametrize("faulted", [False, True], ids=["clean", "faulted"])
def test_pager_randomized_stress_interleaved_ops(faulted):
    """Random admit (with prefix-cache match/attach/COW) / decode-grow /
    finish (cache insert) / preempt-swap / swap-in / explicit COW / LRU evict
    sequences hold the pager + cache invariants after every single operation.

    Token sequences are drawn from a tiny alphabet with page-aligned shared
    stems, so block-hash matches, shared attachments, full-aligned-match COW,
    and held-page swaps all actually occur.  The faulted variant runs the
    same walk under a seeded FaultPlan — allocator outages, grow faults (the
    harness rolls back like the scheduler does), forced prefix evictions, and
    a pool-pressure window — and the invariants must still hold after every
    op.

    Slots carry randomly assigned state-leaf kinds beyond paged KV: *hybrid*
    slots own a fixed-rows payload that rides the swap image through
    preempt/abandon/resume, and *encdec* slots own read-only enc-group pages
    that detach under swap holds and reattach on resume — the refcount
    census (refs == listings-across-groups + holds) must hold after every
    op."""
    from repro.serving.faults import FaultPlan, FaultSpec, TransientFault
    from repro.serving.prefix_cache import PrefixCache

    rng = np.random.default_rng(0)
    B, PS, NP, MAXP = 5, 4, 25, 8
    pool = KV.PagePool(num_pages=NP, page_size=PS, batch_size=B,
                       max_pages_per_slot=MAXP, groups=("kv", "enc"))
    cache = PrefixCache(pool, PS, mode="stress")
    sched = Scheduler(page_size=PS, max_seq=MAXP * PS)
    plan = None
    if faulted:
        plan = FaultPlan([
            FaultSpec("page_alloc", prob=0.10, times=None),
            FaultSpec("page_grow", prob=0.15, times=None),
            FaultSpec("prefix_evict", prob=0.20, times=None),
            FaultSpec("pool_pressure", step=100, value=4, duration=80),
        ], seed=1)
        pool.faults = plan
        cache.faults = plan
    stems = [list(rng.integers(0, 3, 8)) for _ in range(3)]   # shared prefixes
    live: dict[int, dict] = {}             # slot -> {tokens, written, kind}
    swapped: list[dict] = []               # swap states
    fixed: dict[int, float] = {}           # hybrid slots' fixed-rows payload
    roundtrips = {"hybrid": 0, "encdec": 0}

    def admit(slot):
        toks = stems[int(rng.integers(0, 3))] + list(
            rng.integers(0, 3, int(rng.integers(0, 9))))
        t = len(toks)
        evicts_before = plan.injected["prefix_evict"] if plan else 0
        matched, mtok = cache.match(toks)
        full = bool(matched) and mtok == t
        total = pool.pages_needed(t + 1)
        fresh = total - len(matched) + (1 if full else 0)
        pinned = sum(1 for p in matched if pool.page_ref(p) == 0)
        # the scheduler's diagnostic twin must charge exactly what this
        # admission takes from the pool (fresh allocations plus the
        # matched-but-unreferenced pages the attach pins) — pages_needed
        # and plan() share one arithmetic path, asserted against the
        # harness's independent bookkeeping at every admission state.
        # A fired prefix_evict fault voids the twin: the harness saw a
        # forced miss, while referenced matched pages survive in the index
        # for the (non-probing) diagnostic to find.
        req = Request(uid=slot, prompt=np.asarray(toks, np.int32),
                      max_tokens=1)
        if plan is None or plan.injected["prefix_evict"] == evicts_before:
            assert sched.pages_needed(req, pool, cache) == fresh + pinned
        if total > MAXP or not pool.can_alloc(fresh):
            return
        if matched:
            pool.attach(slot, matched)
        if full:
            # last page goes private; the hold mirrors the engine pinning
            # the src until its device rows are copied
            src, _ = pool.cow(slot, len(matched) - 1, hold_src=True)
            pool.check_invariants()
            pool.drop_hold(src)
        if fresh - (1 if full else 0):
            try:
                pool.grow(slot, fresh - (1 if full else 0))
            except TransientFault:
                # mirror the scheduler's mid-plan rollback: release whatever
                # this aborted admission attached/copied and walk away
                pool.free_slot(slot)
                return
        # state leaves beyond paged KV: a hybrid slot carries a fixed-rows
        # payload (not paged — it rides swap images), an encdec slot owns
        # read-only enc-group pages next to its KV pages
        kind = ("kv", "hybrid", "encdec")[int(rng.integers(0, 3))]
        if kind == "encdec":
            enc = 1 + int(rng.integers(0, 2))
            if not pool.can_alloc(enc):
                kind = "kv"
            else:
                try:
                    pool.grow(slot, enc, group="enc")
                except TransientFault:
                    pool.free_slot(slot)
                    return
        if kind == "hybrid":
            fixed[slot] = float(rng.standard_normal())
        cache.insert(toks, pool.slot_pages(slot), t // PS)
        live[slot] = {"tokens": list(toks), "written": t, "kind": kind}

    ops_hit = set()
    for i in range(500):
        if plan is not None:
            plan.begin_step(i)
        op = rng.choice(["admit", "decode", "finish", "preempt", "swap_in",
                         "cow", "evict", "abandon"])
        slot = int(rng.integers(0, B))
        if op == "admit" and slot not in live:
            admit(slot)
        elif op == "decode" and slot in live:
            st = live[slot]
            cap = len(pool.slot_pages(slot)) * PS
            if st["written"] + 1 > cap:
                if cap // PS >= MAXP or not pool.can_alloc(1):
                    continue
                try:
                    pool.grow(slot, 1)
                except TransientFault:
                    continue               # engine behavior: retry next step
            st["tokens"].append(int(rng.integers(0, 3)))
            st["written"] += 1
            if st["kind"] == "hybrid":
                fixed[slot] += 1.0         # recurrent state advances
        elif op == "finish" and slot in live:
            st = live.pop(slot)
            fixed.pop(slot, None)
            cache.insert(st["tokens"], pool.slot_pages(slot),
                         st["written"] // PS)
            pool.free_slot(slot)           # releases every group's pages
            assert pool.slot_pages(slot, "enc") == []
        elif op == "preempt" and live:
            victim = max(live)             # any deterministic choice works
            kept, private = pool.split_for_swap(victim)
            # shared / cached pages are never part of the swap image
            assert all(pool.page_ref(p) > 1 or pool.is_cached(p)
                       for _, p in kept)
            pool.swap_out(victim, (kept, private))
            for _, p in kept:              # ...and stay pinned (un-evictable)
                assert pool.page_ref(p) > 0
            st = dict(live.pop(victim), kept=kept,
                      private_lis=[li for li, _ in private])
            if st["kind"] == "hybrid":
                # fixed rows ride the host swap image, not the pager
                st["fx"] = fixed.pop(victim)
            elif st["kind"] == "encdec":
                # read-only pages never leave the device: refs become holds
                st["enc_held"] = pool.detach_group(victim, "enc")
                assert pool.slot_pages(victim, "enc") == []
                for p in st["enc_held"]:
                    assert pool.held()[p] > 0
            swapped.append(st)
        elif op == "swap_in" and swapped:
            st = swapped[0]
            idle = [s for s in range(B) if s not in live]
            if idle and pool.can_alloc(len(st["private_lis"])):
                pool.swap_in(idle[0], st["kept"], st["private_lis"])
                if st["kind"] == "encdec":
                    pool.reattach_group(idle[0], "enc", st["enc_held"])
                    assert pool.slot_pages(idle[0], "enc") == st["enc_held"]
                elif st["kind"] == "hybrid":
                    fixed[idle[0]] = st["fx"]     # bit-exact round trip
                if st["kind"] != "kv":
                    roundtrips[st["kind"]] += 1
                live[idle[0]] = {"tokens": st["tokens"],
                                 "written": st["written"],
                                 "kind": st["kind"]}
                swapped.pop(0)
        elif op == "abandon" and swapped:
            # a swapped request dies (deadline expiry / cancel): kept pages
            # lose their swap holds, detached enc pages too — cached pages
            # turn evictable, uncached ones return to the free list
            st = swapped.pop(int(rng.integers(0, len(swapped))))
            for _, p in st["kept"]:
                pool.drop_hold(p)
            pool.drop_group_holds(st.get("enc_held", []))
        elif op == "cow" and live:
            # explicit COW of any shared/cached page a live slot lists
            cands = [(s, li, p) for s in live
                     for li, p in enumerate(pool.slot_pages(s))
                     if pool.page_ref(p) > 1 or pool.is_cached(p)]
            if cands and pool.can_alloc(1):
                s, li, p = cands[int(rng.integers(0, len(cands)))]
                old, new = pool.cow(s, li)
                assert old == p and pool.page_ref(new) == 1
                assert not pool.is_cached(new)
        elif op == "evict":
            cache.evict_one()
        ops_hit.add(op)
        pool.check_invariants()
    # the randomized walk must actually exercise the whole op surface,
    # including fixed-rows and enc-group slots through full swap cycles
    assert ops_hit == {"admit", "decode", "finish", "preempt", "swap_in",
                       "cow", "evict", "abandon"}
    assert roundtrips["hybrid"] > 0 and roundtrips["encdec"] > 0
    assert cache.stats.hits > 0 and cache.stats.evicted_pages > 0
    if plan is not None:
        # the chaos actually happened — and every fire is in the diff log
        for site in ("page_alloc", "page_grow", "prefix_evict"):
            assert plan.injected[site] > 0, f"{site} never fired"
        assert len(plan.log) == plan.total_injected
    # conservation: every page is free, referenced (any group, incl. pages
    # held by in-flight swap states), or evictable-cached
    referenced = {p for s in range(B) for g in pool.groups
                  for p in pool.slot_pages(s, g)}
    referenced |= {p for st in swapped for _, p in st["kept"]}
    referenced |= {p for st in swapped for p in st.get("enc_held", [])}
    evictable = cache.evictable_count()
    assert len(referenced) + pool.free_pages + evictable == pool.num_pages - 1


def test_scheduler_lazy_reserves_prompt_plus_one():
    from collections import deque
    pool = KV.PagePool(33, 4, batch_size=4, max_pages_per_slot=8)
    lazy = Scheduler(page_size=4, max_seq=32)                  # default lazy
    worst = Scheduler(page_size=4, max_seq=32, reservation="worstcase")
    req = Request(uid=0, prompt=np.arange(2, 9, dtype=np.int32),  # 7 tokens
                  max_tokens=16)
    assert lazy.pages_needed(req, pool) == 2                   # 8 tokens
    assert worst.pages_needed(req, pool) == 6                  # 23 tokens
    # watermark: with reserve=3 the head must leave 3 free pages behind
    tight = KV.PagePool(5, 4, batch_size=4, max_pages_per_slot=4)  # 4 free
    q = deque([req])
    assert lazy.plan(q, [0, 1], tight, reserve=3) == []
    assert len(q) == 1
    buckets = lazy.plan(q, [0, 1], tight, reserve=2)
    assert sum(len(b.reqs) for b in buckets) == 1


# ------------------------------------------------------- engine pressure ----
@pytest.fixture(scope="module")
def setup():
    cfg = get_config("codellama-7b", smoke=True)
    params = api.init_model(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _mixed_reqs(cfg, n=6, max_tokens=8, seed=5):
    rng = np.random.default_rng(seed)
    lens = (3, 7, 10, 5)
    return [Request(uid=i,
                    prompt=rng.integers(2, cfg.vocab_size,
                                        size=lens[i % 4]).astype(np.int32),
                    max_tokens=max_tokens)
            for i in range(n)]


def test_preempting_engine_token_identical_to_roomy(setup):
    """Acceptance: under a pool too small for the batch's worst case, the
    lazy engine preempts (swap-out + requeue at head) yet produces greedy
    outputs token-identical to an unconstrained engine — preemption is a pure
    scheduling effect, never a correctness one."""
    cfg, params = setup
    roomy_reqs = _mixed_reqs(cfg)
    roomy = ServingEngine(params, cfg, batch_size=3, max_seq=24, page_size=4,
                          backend="xla")
    for r in roomy_reqs:
        roomy.submit(r)
    st_roomy = roomy.run_until_drained()
    assert st_roomy.preemptions == 0           # default pool: no pressure
    assert st_roomy.grown_pages > 0            # but growth is exercised

    tight_reqs = _mixed_reqs(cfg)
    tight = ServingEngine(params, cfg, batch_size=3, max_seq=24, page_size=4,
                          num_pages=1 + 7, backend="xla")
    for r in tight_reqs:
        tight.submit(r)
    st = tight.run_until_drained()
    assert st.completed == len(tight_reqs)
    assert st.preemptions > 0 and st.resumes == st.preemptions
    assert st.swapped_out_bytes == st.swapped_in_bytes > 0
    for a, b in zip(roomy_reqs, tight_reqs):
        assert a.output == b.output
    tight.pager.check_invariants()
    assert tight.pager.free_pages == tight.pager.num_pages - 1


def test_preempting_engine_int8_pools_bit_exact(setup):
    """Swap-out/swap-in round-trips the int8 codes + f32 scale leaves
    verbatim: the kv_quant engine under pressure stays token-identical."""
    cfg, _ = setup
    cfg = cfg.with_(dtype="float32", kv_quant=True)
    params = api.init_model(jax.random.PRNGKey(0), cfg)
    ref_reqs, tight_reqs = _mixed_reqs(cfg, n=5), _mixed_reqs(cfg, n=5)
    ref = ServingEngine(params, cfg, batch_size=3, max_seq=24, page_size=4,
                        backend="xla")
    for r in ref_reqs:
        ref.submit(r)
    ref.run_until_drained()
    tight = ServingEngine(params, cfg, batch_size=3, max_seq=24, page_size=4,
                          num_pages=1 + 7, backend="xla")
    for r in tight_reqs:
        tight.submit(r)
    st = tight.run_until_drained()
    assert st.preemptions > 0
    for a, b in zip(ref_reqs, tight_reqs):
        assert a.output == b.output


def test_lazy_engine_mla_pressure_smoke():
    """Growth + preemption also covers the MLA latent page pools."""
    cfg = get_config("deepseek-v2-236b", smoke=True)
    params = api.init_model(jax.random.PRNGKey(0), cfg)
    reqs = _mixed_reqs(cfg, n=4, max_tokens=6)
    eng = ServingEngine(params, cfg, batch_size=2, max_seq=16, page_size=4,
                        num_pages=1 + 5, backend="xla")
    for r in reqs:
        eng.submit(r)
    st = eng.run_until_drained()
    assert st.completed == 4
    assert st.grown_pages > 0
    eng.pager.check_invariants()


def test_worstcase_reservation_mode_never_grows(setup):
    cfg, params = setup
    reqs = _mixed_reqs(cfg, n=4)
    eng = ServingEngine(params, cfg, batch_size=2, max_seq=24, page_size=4,
                        backend="xla", reservation="worstcase")
    for r in reqs:
        eng.submit(r)
    st = eng.run_until_drained()
    assert st.completed == 4
    assert st.grown_pages == 0 and st.preemptions == 0


def test_lazy_reservation_runs_more_at_once_at_half_pool(setup):
    """At a pool of half the worst-case reservation, lazy growth with
    preemption runs strictly more requests at once than reserving
    ``prompt + max_tokens`` up front, with the same greedy outputs."""
    cfg, params = setup
    b, ps, max_tokens = 4, 4, 12
    rng = np.random.default_rng(0)
    lens = [int(rng.integers(2, 5)) for _ in range(8)]       # <= 1 page each
    prompts = [rng.integers(2, cfg.vocab_size, n).astype(np.int32)
               for n in lens]
    max_seq = max(lens) + max_tokens
    pages = -(-max_seq // ps)
    num_pages = max(pages, b * pages // 2) + 1
    peak, outs = {}, {}
    for reservation in ("worstcase", "lazy"):
        eng = ServingEngine(params, cfg, batch_size=b, max_seq=max_seq,
                            page_size=ps, num_pages=num_pages, backend="xla",
                            reservation=reservation)
        reqs = [Request(uid=i, prompt=p.copy(), max_tokens=max_tokens)
                for i, p in enumerate(prompts)]
        for r in reqs:
            eng.submit(r)
        st = eng.run_until_drained()
        assert st.completed == len(reqs)
        eng.pager.check_invariants()
        peak[reservation] = st.max_active
        outs[reservation] = [r.output for r in reqs]
    assert peak["lazy"] > peak["worstcase"], peak
    assert outs["lazy"] == outs["worstcase"]


# ------------------------------------------------------------ regressions ---
def test_decode_cap_request_fills_all_positions(setup):
    """Regression (off-by-one): a request may write every one of the S cache
    positions.  prompt = S-2 leaves two decode writes (positions S-2 and
    S-1), so with the first prefill-sampled token the output is 3 tokens —
    the old ``pos >= S - 1`` cap freed the slot one write early."""
    cfg, params = setup
    S = 16
    eng = ServingEngine(params, cfg, batch_size=1, max_seq=S, page_size=8,
                        backend="xla", eos_id=-1)          # eos can't trip
    req = Request(uid=0, prompt=np.arange(2, 2 + S - 2).astype(np.int32),
                  max_tokens=8)
    eng.submit(req)
    st = eng.run_until_drained()
    assert st.completed == 1
    assert len(req.output) == 3                 # first token + 2 decode steps
    # the longest admissible prompt (S-1, submit's bound) still gets 2 tokens
    eng2 = ServingEngine(params, cfg, batch_size=1, max_seq=S, page_size=8,
                         backend="xla", eos_id=-1)
    req2 = Request(uid=1, prompt=np.arange(2, 2 + S - 1).astype(np.int32),
                   max_tokens=8)
    eng2.submit(req2)
    eng2.run_until_drained()
    assert len(req2.output) == 2


def test_run_until_drained_raises_on_stalled_admission(setup):
    """Regression (livelock): a head that can never be admitted used to spin
    forever because ``stats.steps`` only counted decoding steps.  The drain
    now detects the idle iteration and raises, naming the blocked request."""
    cfg, params = setup
    eng = ServingEngine(params, cfg, batch_size=2, max_seq=16, page_size=4,
                        num_pages=9, backend="xla")
    eng.pager._free = eng.pager._free[:1]      # simulate a page leak: 1 left
    eng.submit(Request(uid=42, prompt=np.arange(2, 9).astype(np.int32),
                       max_tokens=2))          # needs 2 pages
    with pytest.raises(RuntimeError, match="uid=42"):
        eng.run_until_drained()
    assert eng.stats.idle_steps == 1


# ----------------------------------------------------------- top-k / top-p --
def test_sample_per_slot_per_row_top_k_top_p():
    from repro.serving.sampling import sample_per_slot

    logits = jnp.asarray(np.random.default_rng(0).normal(size=(3, 64)) * 3,
                         jnp.float32)
    temps = jnp.asarray([1.5, 1.5, 1.5], jnp.float32)
    tks = jnp.asarray([1, 0, 0], jnp.int32)
    tps = jnp.asarray([1.0, 1e-6, 1.0], jnp.float32)
    draws = np.array([
        np.asarray(sample_per_slot(logits, k, temps, tks, tps))
        for k in jax.random.split(jax.random.PRNGKey(0), 64)
    ])
    argmax = np.asarray(jnp.argmax(logits, -1))
    # row 0: top_k=1 collapses a hot distribution to argmax
    assert (draws[:, 0] == argmax[0]).all()
    # row 1: top_p→0 keeps only the nucleus head == argmax
    assert (draws[:, 1] == argmax[1]).all()
    # row 2: unfiltered hot row actually samples
    assert len(set(draws[:, 2].tolist())) > 1


def test_scalar_and_per_row_filters_agree():
    from repro.serving.sampling import sample, sample_per_slot

    key = jax.random.PRNGKey(3)
    logits = jnp.asarray(np.random.default_rng(1).normal(size=(4, 32)) * 2,
                         jnp.float32)
    a = sample(logits, key, temperature=0.7, top_k=5, top_p=0.9)
    b = sample_per_slot(logits, key, jnp.full(4, 0.7),
                        jnp.full(4, 5, jnp.int32), jnp.full(4, 0.9))
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_engine_plumbs_top_k_including_first_token(setup):
    """End-to-end: a hot-temperature request with top_k=1 must be
    token-identical to greedy — only possible if the engine forwards the
    request's top_k to both the prefill first-token sample and every decode
    sample."""
    cfg, params = setup
    prompt = np.arange(3, 11).astype(np.int32)
    ref = ServingEngine(params, cfg, batch_size=1, max_seq=32, backend="xla")
    greedy = Request(uid=0, prompt=prompt.copy(), max_tokens=5,
                     temperature=0.0)
    ref.submit(greedy)
    ref.run_until_drained()

    eng = ServingEngine(params, cfg, batch_size=1, max_seq=32, backend="xla",
                        seed=9)
    hot = Request(uid=0, prompt=prompt.copy(), max_tokens=5, temperature=2.0,
                  top_k=1)
    eng.submit(hot)
    eng.run_until_drained()
    assert hot.output == greedy.output
    # and an unfiltered hot request does diverge (the plumbing isn't a no-op)
    eng2 = ServingEngine(params, cfg, batch_size=1, max_seq=32, backend="xla",
                         seed=9)
    wild = Request(uid=0, prompt=prompt.copy(), max_tokens=5, temperature=2.0)
    eng2.submit(wild)
    eng2.run_until_drained()
    assert wild.output != greedy.output
