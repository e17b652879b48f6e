"""Pallas paged-attention decode kernel (interpret mode) vs the jnp
dense-gather reference: GQA and MLA, fp16 and int8 pools, ragged lengths,
partial last pages and blocks, rows on the trash page, batch > 1; the GQA
page grid against the block walk; plus the int8-pool engine end-to-end and
the stale-page-table guard."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.kernels import paged_attention as PA
from repro.models import api
from repro.models import attention as A
from repro.serving import kv_cache as KV
from repro.serving.engine import Request, ServingEngine

ATOL = 1e-2  # bf16 activations; fp32 checks below are much tighter in practice


def _paged_state(batch, pages_per_slot, page_size, dead=()):
    """Pager + table with every slot allocated but the ``dead`` ones, whose
    rows point at the trash page, as idle slots' rows do."""
    pool_host = KV.PagePool(1 + batch * pages_per_slot, page_size, batch,
                            pages_per_slot)
    for s in range(batch):
        if s not in dead:
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


# ragged: mid-page, page boundary - 1, full table - 1 (partial/full last page)
WRITE_POS = [4, 15, 23]

# GQA decode tables: (page size, pages per slot, KV heads, write positions,
# dead rows).  "smoke" is narrower than one block (ppb falls to the table
# width, 3), one query head per KV head; "blocks" puts lengths (write_pos +
# 1) at 1, PS-1, PS, ppb·PS-1, ppb·PS, ppb·PS+1 and the full table, on 11
# pages of 16: 8-page blocks, and a width that is not a multiple of them;
# "dead_rows" mixes rows on the trash page (length 1) among live ones, as
# idle slots are.  Both of those share each KV head among two query heads.
GQA_TABLES = {
    "smoke": (8, 3, 4, WRITE_POS, ()),
    "blocks": (16, 11, 2, [0, 14, 15, 126, 127, 128, 175], ()),
    "dead_rows": (16, 11, 2, [0, 127, 0, 175, 0, 40, 0], (0, 2, 4, 6)),
}


def _gqa_case(table_case, kv_quant):
    ps, pages, kv_heads, write_pos, dead = GQA_TABLES[table_case]
    cfg = get_config("codellama-7b", smoke=True).with_(
        kv_quant=kv_quant, num_kv_heads=kv_heads)
    b = len(write_pos)
    p = A.init_gqa(jax.random.PRNGKey(0), cfg)
    _, table = _paged_state(b, pages, ps, dead)
    pool = _fill(A.init_gqa_page_pool(cfg, 1 + b * pages, ps), seed=1)
    x = jax.random.normal(jax.random.PRNGKey(2), (b, 1, cfg.d_model), cfg.jdtype)
    return cfg, p, table, pool, x, jnp.asarray(write_pos)


@pytest.mark.parametrize("table_case,kv_quant", [
    pytest.param("smoke", False, id="False"),
    pytest.param("smoke", True, id="True"),
    *(pytest.param(c, q, id=f"{c}-{q}")
      for c in ("blocks", "dead_rows") for q in (False, True)),
])
def test_gqa_paged_kernel_matches_gather(table_case, kv_quant):
    cfg, p, table, pool, x, wp = _gqa_case(table_case, kv_quant)
    y_ref, pool_ref = A.gqa_decode_paged(
        p, x, wp[:, None], pool, table, wp,
        cfg.with_(paged_attn_impl="gather"), backend="xla")
    y_ker, pool_ker = A.gqa_decode_paged(
        p, x, wp[:, None], pool, table, wp,
        cfg.with_(paged_attn_impl="pallas_interpret"), backend="xla")
    np.testing.assert_allclose(
        np.asarray(y_ker, np.float32), np.asarray(y_ref, np.float32),
        atol=ATOL, rtol=ATOL)
    # the token write path is shared: updated pools must be identical
    for key in pool_ref:
        np.testing.assert_array_equal(np.asarray(pool_ref[key]),
                                      np.asarray(pool_ker[key]))


@pytest.mark.parametrize("kv_quant", [False, True])
def test_mla_paged_kernel_matches_gather(kv_quant):
    cfg = get_config("deepseek-v2-236b", smoke=True).with_(kv_quant=kv_quant)
    b, ps, pages = len(WRITE_POS), 8, 3
    p = A.init_mla(jax.random.PRNGKey(0), cfg)
    _, table = _paged_state(b, pages, ps)
    pool = _fill(A.init_mla_page_pool(cfg, 1 + b * pages, ps), seed=1)
    x = jax.random.normal(jax.random.PRNGKey(2), (b, 1, cfg.d_model), cfg.jdtype)
    wp = jnp.asarray(WRITE_POS)
    y_ref, _ = A.mla_decode_paged(
        p, x, wp[:, None], pool, table, wp,
        cfg.with_(paged_attn_impl="gather"), backend="xla")
    y_ker, _ = A.mla_decode_paged(
        p, x, wp[:, None], pool, table, wp,
        cfg.with_(paged_attn_impl="pallas_interpret"), backend="xla")
    np.testing.assert_allclose(
        np.asarray(y_ker, np.float32), np.asarray(y_ref, np.float32),
        atol=ATOL, rtol=ATOL)


def test_gqa_kernel_ignores_trash_page_garbage():
    """Rows past each sequence's length live on dead/trash pages; poisoning
    them with huge values must not leak into the live rows' output, neither
    on a one-block table nor through the block buffers that dead rows (on
    the trash page) share with live rows whose last block is partial."""
    for table_case in ("smoke", "dead_rows"):
        cfg, p, table, pool, x, wp = _gqa_case(table_case, False)
        live = [r for r in range(len(wp))
                if r not in GQA_TABLES[table_case][-1]]
        impl = cfg.with_(paged_attn_impl="pallas_interpret")
        y0, _ = A.gqa_decode_paged(p, x, wp[:, None], pool, table, wp, impl,
                                   backend="xla")
        poisoned = dict(pool, k=pool["k"].at[KV.TRASH_PAGE].set(1e4),
                        v=pool["v"].at[KV.TRASH_PAGE].set(1e4))
        y1, _ = A.gqa_decode_paged(p, x, wp[:, None], poisoned, table, wp,
                                   impl, backend="xla")
        np.testing.assert_array_equal(np.asarray(y0, np.float32)[live],
                                      np.asarray(y1, np.float32)[live])


@pytest.mark.parametrize("kv_quant", [False, True])
@pytest.mark.parametrize("table_case", ["smoke", "dead_rows"])
def test_gqa_page_grid_matches_block_walk(table_case, kv_quant):
    """The page grid, which the TPU runs where a page cannot be DMA'd by hand
    (head dims off the 128-lane tile), agrees with the block walk."""
    ps, pages, _, write_pos, dead = GQA_TABLES[table_case]
    b, hkv, grp, d = len(write_pos), 2, 3, 32
    _, table = _paged_state(b, pages, ps, dead)
    pool = _fill(A.init_page_pool({"k": d, "v": d}, 1 + b * pages, hkv, ps,
                                  jnp.bfloat16, kv_quant), seed=1)
    q = jax.random.normal(jax.random.PRNGKey(2), (b, hkv, grp, d),
                          jnp.bfloat16)
    args = (q, pool["k"], pool["v"], table, jnp.asarray(write_pos) + 1,
            pool.get("k_s"), pool.get("v_s"))
    grid = PA._gqa_page_grid(*args, sm_scale=d ** -0.5, interpret=True)
    walk = PA._gqa_block_walk(*args, sm_scale=d ** -0.5, interpret=True)
    np.testing.assert_allclose(np.asarray(grid), np.asarray(walk),
                               atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("pages,ps,hkv,itemsize,want", [
    (320, 16, 8, 2, 8),     # Mistral-Large, bf16: 128 rows, 256 KB a K block
    (128, 16, 4, 2, 8),     # StarCoder2-15B, bf16
    (128, 16, 4, 1, 8),     # int8 pools
    (3, 8, 2, 2, 3),        # smoke widths: capped by the table width
    (64, 256, 8, 2, 1),     # pages of 256 rows: one page already fills it
    (320, 16, 64, 4, 2),    # 1 MiB pages of K+V: capped by the VMEM budget
])
def test_gqa_pages_per_block(pages, ps, hkv, itemsize, want):
    """Pages per block follow the shapes: >= 128 rows a block, at most the
    table's width, and two buffered blocks within the VMEM budget."""
    page_bytes = hkv * ps * 2 * 128 * itemsize
    ppb = PA._pages_per_block(pages, ps, page_bytes)
    assert ppb == want
    assert 2 * ppb * page_bytes <= PA.KV_BLOCK_VMEM or ppb == 1


def test_int8_pool_shapes_and_prefix_quantization():
    """init_paged_cache allocates int8 + scale pools under kv_quant, and
    quantize_raw_paged produces a matching tree that round-trips ~exactly."""
    cfg = get_config("codellama-7b", smoke=True).with_(kv_quant=True)
    pools = api.init_paged_cache(cfg, num_pages=5, page_size=4)
    lay = pools["layers"]
    assert lay["k"].dtype == jnp.int8 and lay["v"].dtype == jnp.int8
    assert lay["k_s"].dtype == jnp.float32
    assert lay["k_s"].shape == lay["k"].shape[:-1]
    raw = {"layers": {
        "k": jax.random.normal(jax.random.PRNGKey(0), (2, 1, 4, 2, 8)),
        "v": jax.random.normal(jax.random.PRNGKey(1), (2, 1, 4, 2, 8)),
    }}
    q = api.quantize_raw_paged(raw, cfg)["layers"]
    assert set(q) == {"k", "k_s", "v", "v_s"}
    deq = q["k"].astype(jnp.float32) * q["k_s"][..., None]
    np.testing.assert_allclose(np.asarray(deq), np.asarray(raw["layers"]["k"]),
                               atol=2e-2)
    # tree structure matches the pools → write_prefix scatters leaf-for-leaf
    assert set(q) == set(lay)


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


def test_engine_kv_quant_greedy_token_identical_across_impls():
    """int8 KV paged serving end to end: the engine no longer raises under
    kv_quant, and the Pallas kernel path emits token-identical output to the
    jnp gather path over a mixed-length continuous-batching run."""
    cfg = get_config("codellama-7b", smoke=True).with_(kv_quant=True)
    params = api.init_model(jax.random.PRNGKey(0), cfg)

    def run(impl):
        rng = np.random.default_rng(0)
        reqs = [Request(uid=i,
                        prompt=rng.integers(2, cfg.vocab_size,
                                            size=(5, 9, 7, 12)[i % 4]
                                            ).astype(np.int32),
                        max_tokens=5)
                for i in range(5)]
        eng = ServingEngine(params, cfg.with_(paged_attn_impl=impl),
                            batch_size=3, max_seq=32, page_size=8,
                            backend="xla")
        for r in reqs:
            eng.submit(r)
        stats = eng.run_until_drained()
        assert stats.completed == len(reqs)
        eng.pager.check_invariants()
        return [r.output for r in reqs]

    assert run("gather") == run("pallas_interpret")


def test_engine_fp16_kernel_impl_matches_monolithic_greedy():
    """Non-quantized engine on the kernel path stays token-identical to the
    contiguous-cache greedy reference (the PR-1 acceptance bar)."""
    cfg = get_config("codellama-7b", smoke=True).with_(
        paged_attn_impl="pallas_interpret")
    params = api.init_model(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    reqs = [Request(uid=i,
                    prompt=rng.integers(2, cfg.vocab_size,
                                        size=(5, 9)[i % 2]).astype(np.int32),
                    max_tokens=5)
            for i in range(3)]
    eng = ServingEngine(params, cfg, batch_size=2, max_seq=32, page_size=8,
                        backend="xla")
    for r in reqs:
        eng.submit(r)
    assert eng.run_until_drained().completed == 3
    base = cfg.with_(paged_attn_impl="auto")
    for r in reqs:
        assert r.output == _greedy_ref(params, base, r.prompt, r.max_tokens, 32)


# ------------------------------------------------------- stale-table guard --
def test_stale_table_guard_raises_on_freed_active_slot():
    pool = KV.PagePool(num_pages=9, page_size=4, batch_size=2,
                       max_pages_per_slot=4)
    pool.alloc(0, 2)
    write_pos = np.array([5, 0], np.int32)
    active = [True, False]
    KV.assert_live_tables(pool.table(), write_pos, 4, active)   # fine
    pool.free_slot(0)                                            # use-after-free
    with pytest.raises(RuntimeError, match="stale page table"):
        KV.assert_live_tables(pool.table(), write_pos, 4, active)
    # idle slots pointing at trash are fine
    KV.assert_live_tables(pool.table(), write_pos, 4, [False, False])
