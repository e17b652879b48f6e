"""The paged layer scans carry the stacked KV pools as one ``[L·NP, ...]``
view, layer ``l`` at page base ``l·NP``.  Against the xs/ys scan they
replace (kept here as the reference: each layer's ``[NP, ...]`` pool sliced
out, written, stacked back), one decode step and one padded prefill chunk
of a 3-layer GQA and a 3-layer MLA model, through the interpret kernels,
with bf16 and int8 pools, give bit-identical logits and pools.  Every
layer's trash page takes the same cells as the reference's, so padded and
idle rows of layer ``l`` land on page ``l·NP`` and on no other layer's
pages."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import layers as L
from repro.models import lm as LM
from repro.serving import kv_cache as KV

N_LAYERS, B, PS, P, T = 3, 4, 8, 3, 8
NP = 1 + B * P
BACKEND = "interpret"


def _cfg(arch, kv_quant):
    return get_config(arch, smoke=True).with_(num_layers=N_LAYERS,
                                              kv_quant=kv_quant)


def _filled_pools(cfg, seed):
    """Stacked pools with random contents on every page, trash included."""
    pools = LM.init_paged_cache(cfg, NP, PS)["layers"]
    out = {}
    for i, (name, a) in enumerate(sorted(pools.items())):
        key = jax.random.fold_in(jax.random.PRNGKey(seed), i)
        if a.dtype == jnp.int8:
            out[name] = jax.random.randint(key, a.shape, -127, 128, jnp.int8)
        elif name.endswith("_s"):
            out[name] = jax.random.uniform(key, a.shape, jnp.float32, 1e-3,
                                           2e-2)
        else:
            out[name] = jax.random.normal(key, a.shape).astype(a.dtype)
    return {"layers": out}


def _table(rng, idle=0):
    """Distinct slots own distinct live pages; the last ``idle`` slots are
    parked on the trash page, as the engine parks idle slots."""
    pages = rng.permutation(np.arange(1, NP))[:B * P].reshape(B, P)
    pages[B - idle:] = KV.TRASH_PAGE
    return jnp.asarray(pages, jnp.int32)


def _ref_decode(p, token, cache, position, table, cfg):
    """Decode step with the pools as the layer scan's xs/ys."""
    pos = position[:, None]
    x = L.apply_embedding(p["embed"], token)

    def step(x, inp):
        lp, pool = inp
        return LM._block_decode_paged(lp, x, pos, position, pool, table, cfg,
                                      backend=BACKEND)

    x, pools = jax.lax.scan(step, x, (p["layers"], cache["layers"]))
    return LM._lm_head(p, x, cfg, BACKEND)[:, 0], {"layers": pools}


def _ref_chunk(p, tokens, cache, start_len, chunk_len, table, cfg, last_idx):
    """Prefill chunk with the pools as the layer scan's xs/ys."""
    x = LM._embed_in(p, tokens, cfg, None)

    def body(x, inp):
        lp, pool = inp
        return LM._block_prefill_chunk(lp, x, start_len, chunk_len, pool,
                                       table, cfg, backend=BACKEND)

    x, pools = jax.lax.scan(body, x, (p["layers"], cache["layers"]))
    x_last = x[jnp.arange(tokens.shape[0]), last_idx][:, None]
    return LM._lm_head(p, x_last, cfg, BACKEND)[:, 0], {"layers": pools}


def _bits(a):
    a = np.asarray(a)
    return a.view(np.dtype(f"u{a.dtype.itemsize}"))


def _written(new, old):
    """[L, NP, H, PS] mask of the rows a step wrote (any element changed)."""
    diff = _bits(new) != _bits(old)
    return diff.reshape(*diff.shape[:4], -1).any(-1)


def _assert_same_pools(got, want, before):
    for name in want["layers"]:
        g, w, b0 = (t["layers"][name] for t in (got, want, before))
        assert g.shape == w.shape == (N_LAYERS, NP, *w.shape[2:])
        # every page but each layer's trash page is bit-identical
        np.testing.assert_array_equal(_bits(g)[:, KV.TRASH_PAGE + 1:],
                                      _bits(w)[:, KV.TRASH_PAGE + 1:])
        # the trash page of layer l takes exactly layer l's discarded rows
        wrote, ref_wrote = _written(g, b0), _written(w, b0)
        np.testing.assert_array_equal(wrote, ref_wrote)
        assert ref_wrote[:, KV.TRASH_PAGE].any(axis=(1, 2)).all()


@pytest.mark.parametrize("kv_quant", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("arch", ["codellama-7b", "deepseek-v2-236b"],
                         ids=["gqa", "mla"])
@pytest.mark.parametrize("step", ["decode", "chunk"])
def test_carried_pools_match_xs_ys_scan(step, arch, kv_quant):
    cfg = _cfg(arch, kv_quant)
    rng = np.random.default_rng(0)
    params = LM.init_lm(jax.random.PRNGKey(0), cfg)
    cache = _filled_pools(cfg, 1)
    if step == "decode":
        table = _table(rng, idle=1)
        token = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, 1)),
                            jnp.int32)
        position = jnp.asarray(rng.integers(0, P * PS, B), jnp.int32)
        args = (params, token, cache, position, table)
        got = jax.jit(lambda *a: LM.lm_decode_paged(*a, cfg,
                                                    backend=BACKEND))(*args)
        want = jax.jit(lambda *a: _ref_decode(*a, cfg))(*args)
    else:
        table = _table(rng)
        tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, T)),
                             jnp.int32)
        # cold, mid-page and page-aligned starts; every row has padding
        start_len = jnp.asarray([0, 5, 8, 13], jnp.int32)
        chunk_len = jnp.asarray([7, 3, 1, 6], jnp.int32)
        last_idx = chunk_len - 1
        args = (params, tokens, cache, start_len, chunk_len, table)
        got = jax.jit(lambda *a: LM.lm_prefill_chunk(
            *a, cfg, backend=BACKEND, last_idx=last_idx))(*args)
        want = jax.jit(lambda *a: _ref_chunk(*a, cfg, last_idx))(*args)
    np.testing.assert_array_equal(_bits(got[0]), _bits(want[0]))
    _assert_same_pools(got[1], want[1], cache)
