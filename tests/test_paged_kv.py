"""Paged KV cache: pager alloc/free invariants, page write/gather round trip,
scheduler bucketing, and end-to-end equivalence of the paged engine with a
monolithic-cache greedy reference."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import api
from repro.serving import kv_cache as KV
from repro.serving.engine import Request, ServingEngine, UnsupportedModelError
from repro.serving.scheduler import Scheduler


# ------------------------------------------------------------------ pager ---
def test_pager_alloc_free_invariants():
    pool = KV.PagePool(num_pages=9, page_size=4, batch_size=3,
                       max_pages_per_slot=4)
    assert pool.free_pages == 8                    # page 0 reserved as trash
    a = pool.alloc(0, 3)
    b = pool.alloc(1, 2)
    pool.check_invariants()
    assert KV.TRASH_PAGE not in a + b
    assert set(a).isdisjoint(b)
    assert pool.free_pages == 3
    # table rows carry the allocation, trash-padded
    assert pool.table()[0, :3].tolist() == a
    assert (pool.table()[0, 3:] == KV.TRASH_PAGE).all()
    pool.free_slot(0)
    pool.check_invariants()
    assert pool.free_pages == 6
    assert (pool.table()[0] == KV.TRASH_PAGE).all()
    # freed pages are reusable by another slot
    c = pool.alloc(2, 4)
    pool.check_invariants()
    assert set(c).isdisjoint(pool.slot_pages(1))


def test_pager_rejects_double_alloc_and_exhaustion():
    pool = KV.PagePool(num_pages=5, page_size=4, batch_size=2,
                       max_pages_per_slot=4)
    pool.alloc(0, 2)
    with pytest.raises(RuntimeError):
        pool.alloc(0, 1)                           # slot already owns pages
    with pytest.raises(RuntimeError):
        pool.alloc(1, 3)                           # only 2 pages left
    assert pool.can_alloc(2) and not pool.can_alloc(3)
    pool.free_slot(0)
    pool.alloc(1, 4)
    pool.check_invariants()


def test_admit_decode_finish_cycles_conserve_pages():
    pool = KV.PagePool(num_pages=13, page_size=4, batch_size=4,
                       max_pages_per_slot=3)
    rng = np.random.default_rng(0)
    live = {}
    for step in range(200):
        slot = int(rng.integers(0, 4))
        if slot in live:
            pool.free_slot(slot)
            del live[slot]
        else:
            n = int(rng.integers(1, 4))
            if pool.can_alloc(n):
                live[slot] = pool.alloc(slot, n)
        pool.check_invariants()
    owned = [p for pages in live.values() for p in pages]
    assert len(owned) + pool.free_pages == pool.num_pages - 1


# --------------------------------------------------- write / gather round ---
def test_write_prefix_then_gather_recovers_tokens():
    ps, n_pages, pps = 4, 9, 3
    pool_host = KV.PagePool(n_pages, ps, batch_size=2, max_pages_per_slot=pps)
    pool_host.alloc(0, 3)
    pool_host.alloc(1, 2)
    lens = np.array([10, 6], np.int32)
    pad = 12
    kv = jax.random.normal(jax.random.PRNGKey(0), (2, 2, pad, 1, 3),
                           jnp.float32)                     # [L, n, T, H, D]
    pools = jnp.zeros((2, n_pages, 1, ps, 3), jnp.float32)  # [L, NP, H, PS, D]
    page, off = KV.prefix_write_plan(lens, pool_host.table(), ps, pad)
    out = KV.write_prefix(pools, kv, jnp.asarray(page), jnp.asarray(off))
    for row in range(2):
        rows = KV.gather_pages(
            out[0], jnp.asarray(pool_host.table()))[row]    # layer 0
        got = np.asarray(rows[: lens[row]])
        np.testing.assert_array_equal(got, np.asarray(kv[0, row, : lens[row]]))
    # padding beyond each row's length went to the trash page, not its pages
    tail = KV.gather_pages(out[0], jnp.asarray(pool_host.table()))[1]
    assert np.asarray(tail[lens[1]: 8]).sum() == 0


# -------------------------------------------------------------- scheduler ---
def _req(uid, n, max_tokens=4):
    return Request(uid=uid, prompt=np.arange(2, 2 + n, dtype=np.int32),
                   max_tokens=max_tokens)


def test_scheduler_buckets_by_length_and_reserves_pages():
    from collections import deque
    pool = KV.PagePool(33, 4, batch_size=4, max_pages_per_slot=8)
    sched = Scheduler(page_size=4, max_seq=32)
    q = deque([_req(0, 3), _req(1, 4), _req(2, 9), _req(3, 2)])
    buckets = sched.plan(q, [0, 1, 2, 3], pool)
    assert not q
    by_len = {b.pad_len: b for b in buckets}
    # 3, 4, 2 → bucket 4; 9 → bucket 16
    assert sorted(by_len) == [4, 16]
    assert [r.uid for r in by_len[4].reqs] == [0, 1, 3]
    assert [r.uid for r in by_len[16].reqs] == [2]
    pool.check_invariants()
    assert pool.free_pages == 32 - sum(n for b in buckets for n in b.needs)


def test_scheduler_fcfs_blocks_on_page_pressure():
    from collections import deque
    pool = KV.PagePool(5, 4, batch_size=4, max_pages_per_slot=4)   # 4 free
    sched = Scheduler(page_size=4, max_seq=16)
    q = deque([_req(0, 12, max_tokens=4), _req(1, 2, max_tokens=2)])
    buckets = sched.plan(q, [0, 1, 2, 3], pool)
    # head needs 4 pages → admitted; next would need 1 but 0 remain → waits
    assert sum(len(b.reqs) for b in buckets) == 1
    assert len(q) == 1 and q[0].uid == 1


def test_scheduler_prefill_token_budget_chunks_backlog():
    from collections import deque
    pool = KV.PagePool(65, 4, batch_size=8, max_pages_per_slot=8)
    sched = Scheduler(page_size=4, max_seq=32, max_prefill_tokens=8)
    q = deque([_req(i, 4) for i in range(4)])
    buckets = sched.plan(q, list(range(8)), pool)
    # 4-token buckets, budget 8 → two requests this step, two wait
    assert sum(len(b.reqs) for b in buckets) == 2
    assert len(q) == 2


# ------------------------------------------------------------ end-to-end ----
@pytest.fixture(scope="module")
def setup():
    cfg = get_config("codellama-7b", smoke=True)
    params = api.init_model(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _greedy_ref(params, cfg, prompt, max_tokens, smax, eos=1):
    logits, cache = api.prefill_fn(
        params, {"tokens": jnp.asarray(prompt)[None]}, cfg, smax, backend="xla")
    out = [int(jnp.argmax(logits, -1)[0])]
    pos = len(prompt)
    while len(out) < max_tokens and out[-1] != eos and pos < smax - 1:
        lg, cache = api.decode_fn(
            params, {"token": jnp.asarray([[out[-1]]], jnp.int32),
                     "position": jnp.asarray([pos], jnp.int32)},
            cache, cfg, backend="xla")
        out.append(int(jnp.argmax(lg, -1)[0]))
        pos += 1
    return out


def test_paged_engine_matches_monolithic_greedy(setup):
    """Acceptance: mixed-length 7-request queue, batch_size=3, paged engine
    outputs token-identical to the monolithic-cache greedy reference."""
    cfg, params = setup
    rng = np.random.default_rng(0)
    lens = (5, 9, 7, 12)
    reqs = [Request(uid=i,
                    prompt=rng.integers(2, cfg.vocab_size,
                                        size=lens[i % 4]).astype(np.int32),
                    max_tokens=6)
            for i in range(7)]
    eng = ServingEngine(params, cfg, batch_size=3, max_seq=48, page_size=8,
                        backend="xla")
    for r in reqs:
        eng.submit(r)
    stats = eng.run_until_drained()
    assert stats.completed == 7
    # joint prefill actually batched: fewer launches than requests
    assert stats.prefill_batches < 7
    for r in reqs:
        assert r.output == _greedy_ref(params, cfg, r.prompt, r.max_tokens, 48)
    eng.pager.check_invariants()
    assert eng.pager.free_pages == eng.pager.num_pages - 1   # all reclaimed


def test_paged_engine_bucket_padding_is_harmless(setup):
    """A prompt whose length is far off the bucket boundary must sample its
    first token from the true last position, not the padded one."""
    cfg, params = setup
    prompt = np.arange(3, 8).astype(np.int32)            # len 5 → bucket 8
    eng = ServingEngine(params, cfg, batch_size=2, max_seq=32, page_size=8,
                        backend="xla")
    req = Request(uid=0, prompt=prompt, max_tokens=4)
    eng.submit(req)
    eng.run_until_drained()
    assert req.output == _greedy_ref(params, cfg, prompt, 4, 32)


def test_paged_engine_page_pressure_defers_admission(setup):
    """With pages for only ~one request, the engine must still drain the
    queue by recycling pages between requests."""
    cfg, params = setup
    eng = ServingEngine(params, cfg, batch_size=3, max_seq=32, page_size=8,
                        num_pages=1 + 4, backend="xla")    # one slot's worth
    rng = np.random.default_rng(1)
    reqs = [Request(uid=i, prompt=rng.integers(2, cfg.vocab_size, 6).astype(np.int32),
                    max_tokens=4) for i in range(3)]
    for r in reqs:
        eng.submit(r)
    stats = eng.run_until_drained()
    assert stats.completed == 3
    eng.pager.check_invariants()


def test_paged_engine_rejects_oversized_prompt(setup):
    cfg, params = setup
    eng = ServingEngine(params, cfg, batch_size=1, max_seq=16, backend="xla")
    with pytest.raises(ValueError):
        eng.submit(Request(uid=0, prompt=np.zeros(16, np.int32), max_tokens=2))


def test_paged_engine_mla_smoke():
    """Paged decode also covers the MLA latent cache (deepseek family): the
    logits every engine step samples from match a teacher-forced contiguous
    reference fed the engine's own tokens.

    Tolerance: the smoke config computes in bf16, and the engine runs MLA
    absorbed (scores in the latent space, chunk prefill off the pages) while
    the reference prefill runs the expanded form — the two round their bf16
    activations at different points, so logits drift by a few bf16 ulps
    (2^-8 relative).  Eight ulps of the largest logit bound it; greedy
    tokens are not compared because near-tied logits flip under that drift."""
    cfg = get_config("deepseek-v2-236b", smoke=True)
    params = api.init_model(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    eng = ServingEngine(params, cfg, batch_size=2, max_seq=32, page_size=8,
                        backend="xla")
    sampled = {}
    sample = eng._sample_reqs

    def record(logits, key, temps, reqs):
        lg = np.asarray(logits, np.float32)
        for row, r in enumerate(reqs):
            if r is not None:
                sampled.setdefault(r.uid, []).append(lg[row])
        return sample(logits, key, temps, reqs)

    eng._sample_reqs = record
    reqs = [Request(uid=i, prompt=rng.integers(2, cfg.vocab_size,
                                               size=(5, 9)[i % 2]).astype(np.int32),
                    max_tokens=4) for i in range(3)]
    for r in reqs:
        eng.submit(r)
    stats = eng.run_until_drained()
    assert stats.completed == 3
    for r in reqs:
        logits, cache = api.prefill_fn(
            params, {"tokens": jnp.asarray(r.prompt)[None]}, cfg, 32,
            backend="xla")
        want = [np.asarray(logits[0], np.float32)]
        for pos, tok in enumerate(r.output[:-1], start=len(r.prompt)):
            logits, cache = api.decode_fn(
                params, {"token": jnp.asarray([[tok]], jnp.int32),
                         "position": jnp.asarray([pos], jnp.int32)},
                cache, cfg, backend="xla")
            want.append(np.asarray(logits[0], np.float32))
        got = sampled[r.uid]
        assert len(got) == len(want) == len(r.output)
        for g, w in zip(got, want):
            assert np.isfinite(g).all()
            np.testing.assert_allclose(g, w, rtol=0,
                                       atol=2.0 ** -5 * np.abs(w).max())


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_int8_prefill_slab_matches_paged_admission(dtype):
    """ROADMAP closeout: the *contiguous* prefill slab now quantizes per-row
    (codes + f32 scale rows, matching the page pools' layout) instead of
    casting — bit-for-bit the same int8 codes the paged admission path
    (quantize_raw_paged) writes, under any cfg dtype."""
    cfg = get_config("codellama-7b", smoke=True).with_(
        dtype=dtype, kv_quant=True)
    params = api.init_model(jax.random.PRNGKey(0), cfg)
    prompt = jnp.arange(3, 12)[None]                      # [1, 9]
    t = prompt.shape[1]
    # contiguous slab prefill
    _, slab = api.prefill_fn(params, {"tokens": prompt}, cfg, 16,
                             backend="xla")
    assert slab["layers"]["k"].dtype == jnp.int8
    # paged-admission reference: raw prefix KV, quantized per row
    _, raw = api.prefill_fn(params, {"tokens": prompt}, cfg, 16,
                            backend="xla", raw_cache=True)
    raw = {"layers": {k: v for k, v in raw["layers"].items() if k != "lens"}}
    qraw = api.quantize_raw_paged(raw, cfg)
    for leaf in ("k", "v"):  # int8 codes: bitwise identical
        np.testing.assert_array_equal(
            np.asarray(slab["layers"][leaf][:, :, :t]),
            np.asarray(qraw["layers"][leaf]))
    for leaf in ("k_s", "v_s"):  # f32 scales: same rows modulo XLA fusion ulps
        np.testing.assert_allclose(
            np.asarray(slab["layers"][leaf][:, :, :t]),
            np.asarray(qraw["layers"][leaf]), rtol=1e-6, atol=0)
    # and decode off that slab works end to end
    lg, _ = api.decode_fn(
        params, {"token": jnp.asarray([[5]], jnp.int32),
                 "position": jnp.asarray([t], jnp.int32)},
        slab, cfg, backend="xla")
    assert bool(jnp.isfinite(lg.astype(jnp.float32)).all())


def test_int8_engine_greedy_matches_contiguous_reference():
    """With kv_quant on, the paged engine and the contiguous-slab greedy
    reference see identical int8 codes+scales → identical tokens."""
    cfg = get_config("codellama-7b", smoke=True).with_(
        dtype="float32", kv_quant=True)
    params = api.init_model(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(2)
    reqs = [Request(uid=i, prompt=rng.integers(2, cfg.vocab_size,
                                               size=(5, 9)[i % 2]).astype(np.int32),
                    max_tokens=4) for i in range(3)]
    eng = ServingEngine(params, cfg, batch_size=2, max_seq=32, page_size=8,
                        backend="xla")
    for r in reqs:
        eng.submit(r)
    stats = eng.run_until_drained()
    assert stats.completed == 3
    for r in reqs:
        assert r.output == _greedy_ref(params, cfg, r.prompt, r.max_tokens, 32)


def test_paged_unsupported_families_raise():
    cfg = get_config("rwkv6-7b", smoke=True)
    params = api.init_model(jax.random.PRNGKey(0), cfg)
    # the named construction-time error (a NotImplementedError subclass, so
    # pre-existing callers catching that still work)
    with pytest.raises(UnsupportedModelError, match="paged serving"):
        ServingEngine(params, cfg, batch_size=2, max_seq=32)
