"""Chunked paged prefill: the Pallas chunk kernels (interpret mode) vs the
dense-gather oracle (GQA and MLA, fp16 and int8 pools, ragged prefixes,
partial pages, dead-page poisoning), mixed-step engine greedy identity
(cold / warm / chunked, gather vs kernel), and the drain / stats regression
fixes that rode along."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import api
from repro.models import attention as A
from repro.serving import kv_cache as KV
from repro.serving.engine import Request, ServingEngine

ATOL = 1e-2  # bf16 activations; fp32 checks below are much tighter in practice

# chunk cursors: cold slot, mid-page partial prefix, two exact full pages
STARTS = [0, 5, 16]
CHUNKS = [4, 3, 2]          # ragged valid chunk lengths (T_pad = 4)
T = 4


def _paged_state(batch, pages_per_slot, page_size):
    pool_host = KV.PagePool(1 + batch * pages_per_slot, page_size, batch,
                            pages_per_slot)
    for s in range(batch):
        pool_host.alloc(s, pages_per_slot)
    return pool_host, jnp.asarray(pool_host.table())


def _fill(pool, seed):
    """Random pool contents (all pages, including trash-page garbage)."""
    out = {}
    for i, (k, v) in enumerate(sorted(pool.items())):
        kk = jax.random.fold_in(jax.random.PRNGKey(seed), i)
        if v.dtype == jnp.int8:
            out[k] = jax.random.randint(kk, v.shape, -127, 128, jnp.int8)
        elif k.endswith("_s"):
            out[k] = jax.random.uniform(kk, v.shape, jnp.float32, 1e-3, 2e-2)
        else:
            out[k] = jax.random.normal(kk, v.shape, jnp.float32).astype(v.dtype)
    return out


def _chunk_args(cfg, b=len(STARTS), ps=8, pages=4, seed=2):
    x = jax.random.normal(jax.random.PRNGKey(seed), (b, T, cfg.d_model),
                          cfg.jdtype)
    _, table = _paged_state(b, pages, ps)
    starts = jnp.asarray(STARTS, jnp.int32)
    chunks = jnp.asarray(CHUNKS, jnp.int32)
    return x, table, starts, chunks


@pytest.mark.parametrize("kv_quant", [False, True])
def test_gqa_chunk_kernel_matches_gather(kv_quant):
    cfg = get_config("codellama-7b", smoke=True).with_(kv_quant=kv_quant)
    b, ps, pages = len(STARTS), 8, 4
    p = A.init_gqa(jax.random.PRNGKey(0), cfg)
    pool = _fill(A.init_gqa_page_pool(cfg, 1 + b * pages, ps), seed=1)
    x, table, starts, chunks = _chunk_args(cfg)
    y_ref, pool_ref = A.gqa_prefill_chunk(
        p, x, pool, table, starts, chunks,
        cfg.with_(paged_attn_impl="gather"), backend="xla")
    y_ker, pool_ker = A.gqa_prefill_chunk(
        p, x, pool, table, starts, chunks,
        cfg.with_(paged_attn_impl="pallas_interpret"), backend="xla")
    np.testing.assert_allclose(
        np.asarray(y_ker, np.float32), np.asarray(y_ref, np.float32),
        atol=ATOL, rtol=ATOL)
    # the chunk scatter path is shared: updated pools must be identical
    for key in pool_ref:
        np.testing.assert_array_equal(np.asarray(pool_ref[key]),
                                      np.asarray(pool_ker[key]))


@pytest.mark.parametrize("kv_quant", [False, True])
def test_mla_chunk_kernel_matches_gather(kv_quant):
    cfg = get_config("deepseek-v2-236b", smoke=True).with_(kv_quant=kv_quant)
    b, ps, pages = len(STARTS), 8, 4
    p = A.init_mla(jax.random.PRNGKey(0), cfg)
    pool = _fill(A.init_mla_page_pool(cfg, 1 + b * pages, ps), seed=1)
    x, table, starts, chunks = _chunk_args(cfg)
    y_ref, pool_ref = A.mla_prefill_chunk(
        p, x, pool, table, starts, chunks,
        cfg.with_(paged_attn_impl="gather"), backend="xla")
    y_ker, pool_ker = A.mla_prefill_chunk(
        p, x, pool, table, starts, chunks,
        cfg.with_(paged_attn_impl="pallas_interpret"), backend="xla")
    np.testing.assert_allclose(
        np.asarray(y_ker, np.float32), np.asarray(y_ref, np.float32),
        atol=ATOL, rtol=ATOL)
    for key in pool_ref:
        np.testing.assert_array_equal(np.asarray(pool_ref[key]),
                                      np.asarray(pool_ker[key]))


def test_gqa_chunk_kernel_ignores_dead_page_garbage():
    """Pool rows past each slot's prefix — dead pages, the trash page, and
    the dead tail *inside* a live partial page — are poisoned with huge
    values; the chunk kernel's masks/guards must keep them out bit-exactly."""
    cfg = get_config("codellama-7b", smoke=True)
    b, ps, pages = len(STARTS), 8, 4
    p = A.init_gqa(jax.random.PRNGKey(0), cfg)
    pool = _fill(A.init_gqa_page_pool(cfg, 1 + b * pages, ps), seed=1)
    x, table, starts, chunks = _chunk_args(cfg)
    impl = cfg.with_(paged_attn_impl="pallas_interpret")
    y0, _ = A.gqa_prefill_chunk(p, x, pool, table, starts, chunks, impl,
                                backend="xla")
    dead = np.ones((1 + b * pages, ps), bool)     # poison everything...
    tbl = np.asarray(table)
    for bi, start in enumerate(STARTS):
        for pos in range(start):                  # ...except live prefix rows
            dead[tbl[bi, pos // ps], pos % ps] = False
    mask = jnp.asarray(dead)[:, None, :, None]    # pools are [NP, H, PS, D]
    poisoned = dict(pool, k=jnp.where(mask, 1e4, pool["k"]),
                    v=jnp.where(mask, 1e4, pool["v"]))
    y1, _ = A.gqa_prefill_chunk(p, x, poisoned, table, starts, chunks, impl,
                                backend="xla")
    np.testing.assert_array_equal(np.asarray(y0, np.float32),
                                  np.asarray(y1, np.float32))


# ------------------------------------------------------------ engine level --
ENGINE_CASES = [("codellama-7b", False), ("codellama-7b", True),
                ("deepseek-v2-236b", False), ("deepseek-v2-236b", True)]


@pytest.mark.parametrize("arch,kv_quant", ENGINE_CASES)
def test_engine_greedy_identity_cold_warm_mixed(arch, kv_quant):
    """Greedy outputs are token-identical across every serving path a prompt
    can take: stop-the-world single-chunk prefill (cold), token-budget mixed
    chunks, the Pallas chunk kernel vs the gather oracle, and warm chunked
    prefill behind a cached prefix."""
    cfg = get_config(arch, smoke=True).with_(kv_quant=kv_quant)
    params = api.init_model(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(3)
    p1 = rng.integers(2, cfg.vocab_size, size=12).astype(np.int32)
    # p2 extends p1's first full page -> a warm admission matches 8 tokens
    p2 = np.concatenate(
        [p1[:8], rng.integers(2, cfg.vocab_size, size=11).astype(np.int32)])

    def run(impl="gather", budget=None, cache=False):
        eng = ServingEngine(params, cfg.with_(paged_attn_impl=impl),
                            batch_size=2, max_seq=32, page_size=8,
                            backend="xla", max_prefill_tokens=budget,
                            prefix_cache=cache)
        outs = []
        for i, pr in enumerate((p1, p2)):    # sequential: p2 can hit p1's pages
            r = Request(uid=i, prompt=pr, max_tokens=3)
            eng.submit(r)
            eng.run_until_drained()
            outs.append(r.output)
        if cache:
            assert eng.stats.prefix_matched_tokens >= 8
        if budget is not None:
            # 12- and 19-token prompts under an 8-token budget must chunk
            assert eng.stats.prefill_batches > 2
        eng.pager.check_invariants()
        return outs

    cold = run()
    assert run(budget=8) == cold                           # mixed, oracle
    assert run(impl="pallas_interpret", budget=8) == cold  # mixed, kernel
    assert run(budget=8, cache=True) == cold               # warm chunks


def test_engine_mixed_overlap_decode_identity():
    """Decode steps interleaved *between* a long prompt's chunks (the mixed
    step: budgeted chunk rows + all decoding slots in one plan) leave every
    request's greedy output identical to the stop-the-world run."""
    cfg = get_config("codellama-7b", smoke=True)
    params = api.init_model(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(7)
    lens = (5, 20, 9, 24)

    def run(budget):
        reqs = [Request(uid=i,
                        prompt=rng.integers(2, cfg.vocab_size,
                                            size=lens[i % 4]).astype(np.int32),
                        max_tokens=6)
                for i in range(5)]
        eng = ServingEngine(params, cfg, batch_size=3, max_seq=32, page_size=8,
                            backend="xla", max_prefill_tokens=budget)
        for r in reqs:
            eng.submit(r)
        stats = eng.run_until_drained()
        assert stats.completed == len(reqs)
        eng.pager.check_invariants()
        return [r.output for r in reqs], stats

    rng = np.random.default_rng(7)
    base, _ = run(None)
    rng = np.random.default_rng(7)
    mixed, st = run(8)
    assert mixed == base
    # chunking actually happened: more prefill launches than stop-the-world
    assert st.prefill_batches > 3


# ------------------------------------------------------------ regressions ---
@pytest.fixture(scope="module")
def setup():
    cfg = get_config("codellama-7b", smoke=True)
    params = api.init_model(jax.random.PRNGKey(0), cfg)
    return cfg, params


def test_run_until_drained_raises_at_max_steps(setup):
    """Regression: hitting ``max_steps`` with work still pending used to
    ``break`` silently, handing back truncated outputs that looked complete
    (stats said fewer completions, but nothing failed loudly)."""
    cfg, params = setup
    eng = ServingEngine(params, cfg, batch_size=1, max_seq=32, page_size=8,
                        backend="xla")
    eng.submit(Request(uid=7, prompt=np.arange(2, 8).astype(np.int32),
                       max_tokens=8))
    with pytest.raises(RuntimeError, match="max_steps=2"):
        eng.run_until_drained(max_steps=2)
    # the unfinished request is still live, not silently dropped
    assert any(s is not None for s in eng.slots) or eng.queue


def test_pages_evicted_synced_on_chunk_only_step(setup):
    """Regression: ``stats.pages_evicted`` was synced only after a decode
    launch, so a step that admits (evicting cached pages for the allocation)
    and runs a non-final chunk — nothing decodable yet — returned with the
    counter stale."""
    cfg, params = setup
    eng = ServingEngine(params, cfg, batch_size=2, max_seq=16, page_size=8,
                        num_pages=3, backend="xla", prefix_cache=True,
                        max_prefill_tokens=8)
    r1 = Request(uid=0, prompt=np.arange(2, 10).astype(np.int32), max_tokens=1)
    eng.submit(r1)
    eng.run_until_drained()
    assert eng.stats.pages_inserted > 0 and eng.stats.pages_evicted == 0
    # different tokens -> no cache credit; 2 pages needed, 1 free: the alloc
    # must evict r1's cached page during admission
    r2 = Request(uid=1, prompt=np.arange(50, 62).astype(np.int32), max_tokens=1)
    eng.submit(r2)
    worked = eng.step()     # admit + first (non-final) chunk, no decode rows
    assert worked > 0
    assert eng.stats.pages_evicted > 0      # synced on the chunk-only return
    eng.run_until_drained()
    assert eng.stats.completed == 2
