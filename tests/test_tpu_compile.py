"""Every main-path Pallas kernel compiles for a TPU v5e at published widths.

Interpret mode (the rest of the suite) never checks the TPU lowering's block
rules or fast-memory limits, so each kernel is compiled here ahead of time
for one chip of a *described* ``v5e:2x2`` topology — no chip needed, about a
second per kernel.  Widths: StarCoder2-15B (d_model 6144, d_ff 24576, 48
query / 4 KV heads of 128, group size 128) for the dense GEMMs and GQA
attention; DeepSeek-V2 (128 heads, latent rank 512, rope dim 64, 160
experts of 1536 over d_model 5120) for MLA and the grouped GEMM.  The last
tests read the compiled text of a paged KV write feeding the GQA kernels,
alone and inside a 2-layer paged decode step and prefill chunk: the pools
must be updated in place, with no whole-pool relayout copy and no per-layer
pool slice or write-back.
"""
import importlib
import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.core.quantize import QuantizedTensor
from repro.models import lm as LM
from repro.models.attention import _scatter_chunk, _write_token

W4 = importlib.import_module("repro.kernels.w4a16_matmul")
W4G = importlib.import_module("repro.kernels.w4a16_grouped")
PA = importlib.import_module("repro.kernels.paged_attention")
FA = importlib.import_module("repro.kernels.flash_attention")

BF16, F32, I8, I32 = jnp.bfloat16, jnp.float32, jnp.int8, jnp.int32
D, FF, HQ, HKV, DH, G = 6144, 24576, 48, 4, 128, 128
MLA_H, MLA_R, MLA_DR = 128, 512, 64
PS, PAGES, B = 16, 16, 8
NP = 1 + B * PAGES


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")  # else the compiler logs to /tmp
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc


@pytest.fixture(scope="module")
def one_chip(topo):
    # compiles for a described chip are written to the persistent cache but
    # can never be read back without one: keep the cache off around them
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _compile(fn, *shapes):
    text = jax.jit(fn).lower(*shapes).compile().as_text()
    assert "tpu_custom_call" in text


def _qt(sds, *lead, ci, co):
    return QuantizedTensor(sds((*lead, ci // 2, co), jnp.uint8),
                           sds((*lead, ci // G, co), BF16),
                           sds((*lead, ci // G, co), BF16))


@pytest.fixture(scope="module")
def sds(one_chip):
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=one_chip)


@pytest.mark.parametrize("ci,co", [(D, FF), (FF, D)])
@pytest.mark.parametrize("t", [8, 512])      # decode batch, prefill chunk
@pytest.mark.parametrize("act", ["a16", "a8"])
def test_w4a16_matmul(sds, act, t, ci, co):
    _compile(lambda x, w: W4.w4a16_matmul(x, w, act=act),
             sds((t, ci), BF16), _qt(sds, ci=ci, co=co))


@pytest.mark.parametrize("e,c,d,f", [
    (MLA_H, B, 128, MLA_R),      # MLA absorbed query, heads as experts
    (160, 64, 5120, 1536),       # routed-expert FFN up-projection
])
@pytest.mark.parametrize("act", ["a16", "a8"])
def test_w4a16_grouped(sds, act, e, c, d, f):
    _compile(lambda x, w: W4G.w4a16_grouped_matmul(x, w, act=act),
             sds((e, c, d), BF16), _qt(sds, e, ci=d, co=f))


def _pool(sds, heads, dim, quant):
    data = sds((NP, heads, PS, dim), I8 if quant else BF16)
    scale = (sds((NP, heads, PS), F32),) if quant else ()
    return data, scale


@pytest.mark.parametrize("quant,b,pages,hkv,grp,dh", [
    pytest.param(False, B, PAGES, HKV, HQ // HKV, DH, id="False"),
    pytest.param(True, B, PAGES, HKV, HQ // HKV, DH, id="True"),
    # the cells' decode launches, each on its worst-case pool (block walk):
    # sc2-code-sat (StarCoder2-15B, batch 64, max_seq 2048) and mistral-chat
    # (Mistral-Large, 8 KV heads, batch 32, max_seq 5120); then head dims
    # that are not whole lane tiles (page grid): Whisper-medium, Zamba2-7B
    *(pytest.param(q, b, p, h, g, d, id=f"{name}-{q}") for q in (False, True)
      for name, b, p, h, g, d in (("sc2-code-sat", 64, 128, HKV, 12, DH),
                                  ("mistral-chat", 32, 320, 8, 12, DH),
                                  ("whisper-medium", 8, 28, 16, 1, 64),
                                  ("zamba2-7b", 8, 64, 32, 1, 112))),
])
def test_gqa_paged_decode(sds, quant, b, pages, hkv, grp, dh):
    n_pages = 1 + b * pages
    k = sds((n_pages, hkv, PS, dh), I8 if quant else BF16)
    ks = (sds((n_pages, hkv, PS), F32),) if quant else ()
    _compile(lambda q, k, v, t, n, *s: PA.gqa_paged_attention(
                 q, k, v, t, n, *s, sm_scale=dh ** -0.5),
             sds((b, hkv, grp, dh), BF16), k, k,
             sds((b, pages), I32), sds((b,), I32), *ks, *ks)


@pytest.mark.parametrize("quant", [False, True])
def test_gqa_paged_prefill(sds, quant):
    t = 512
    k, ks = _pool(sds, HKV, DH, quant)
    _compile(lambda q, ksf, vsf, k, v, tb, pf, cl, *s: PA.gqa_paged_prefill(
                 q, ksf, vsf, k, v, tb, pf, cl, *s, sm_scale=DH ** -0.5),
             sds((1, t, HKV, HQ // HKV, DH), BF16), sds((1, t, HKV, DH), BF16),
             sds((1, t, HKV, DH), BF16), k, k, sds((1, PAGES), I32),
             sds((1,), I32), sds((1,), I32), *ks, *ks)


@pytest.mark.parametrize("quant", [False, True])
def test_mla_paged_decode(sds, quant):
    c, cs = _pool(sds, 1, MLA_R, quant)
    p, pss = _pool(sds, 1, MLA_DR, quant)
    _compile(lambda ql, qp, c, p, t, n, *s: PA.mla_paged_attention(
                 ql, qp, c, p, t, n, *s, sm_scale=0.1),
             sds((B, MLA_H, MLA_R), F32), sds((B, MLA_H, MLA_DR), BF16), c, p,
             sds((B, PAGES), I32), sds((B,), I32), *cs, *pss)


@pytest.mark.parametrize("quant", [False, True])
def test_mla_paged_prefill(sds, quant):
    t = 64
    c, cs = _pool(sds, 1, MLA_R, quant)
    p, pss = _pool(sds, 1, MLA_DR, quant)
    _compile(lambda ql, qp, csf, psf, c, p, tb, pf, cl, *s:
             PA.mla_paged_prefill(ql, qp, csf, psf, c, p, tb, pf, cl, *s,
                                  sm_scale=0.1),
             sds((1, t, MLA_H, MLA_R), F32), sds((1, t, MLA_H, MLA_DR), BF16),
             sds((1, t, MLA_R), BF16), sds((1, t, MLA_DR), BF16), c, p,
             sds((1, PAGES), I32), sds((1,), I32), sds((1,), I32), *cs, *pss)


def test_flash_attention(sds):
    t = 2048
    _compile(lambda q, k, v: FA.flash_attention(q, k, v, causal=True),
             sds((1, t, HQ, DH), BF16), sds((1, t, HKV, DH), BF16),
             sds((1, t, HKV, DH), BF16))


def _pool_copies(text, pool_elems):
    """Compiled ``copy`` / ``copy_bitcast_fusion`` ops whose result holds
    as many elements as a whole pool leaf (in any shape)."""
    found = []
    for m in re.finditer(r"^\s*(?:ROOT )?%(\S+) = \w+\[([\d,]*)\]\S* (\S+)\(",
                         text, re.M):
        name, dims, op = m.groups()
        size = math.prod(int(d) for d in dims.split(",") if d)
        if size == pool_elems and (op == "copy" or
                                   name.startswith("copy_bitcast_fusion")):
            found.append(m.group(0).strip())
    return found


@pytest.mark.parametrize("step", ["decode", "chunk"])
def test_kv_pool_write_in_place(sds, step):
    """The paged KV write updates the pools in their own layout: a decode
    step and a 512-token prefill chunk (one layer, pools donated) compile
    with no pool-sized relayout copy around the scatter and the kernel."""
    k, _ = _pool(sds, HKV, DH, False)
    if step == "decode":
        def fn(q, kn, vn, k, v, table, pos):
            pool = _write_token({"k": k, "v": v}, {"k": kn, "v": vn}, table,
                                pos, False)
            return PA.gqa_paged_attention(q, pool["k"], pool["v"], table,
                                          pos + 1, sm_scale=DH ** -0.5), pool
        args = (sds((B, HKV, HQ // HKV, DH), BF16), sds((B, HKV, DH), BF16),
                sds((B, HKV, DH), BF16), k, k, sds((B, PAGES), I32),
                sds((B,), I32))
    else:
        t = 512

        def fn(q, ksf, vsf, k, v, table, start, clen):
            pool = _scatter_chunk({"k": k, "v": v}, {"k": ksf, "v": vsf},
                                  table, start, clen, False)
            return PA.gqa_paged_prefill(q, ksf, vsf, pool["k"], pool["v"],
                                        table, start, clen,
                                        sm_scale=DH ** -0.5), pool
        args = (sds((1, t, HKV, HQ // HKV, DH), BF16),
                sds((1, t, HKV, DH), BF16), sds((1, t, HKV, DH), BF16), k, k,
                sds((1, PAGES), I32), sds((1,), I32), sds((1,), I32))
    text = jax.jit(fn, donate_argnums=(3, 4)).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    assert _pool_copies(text, math.prod(k.shape)) == []


def _pool_moves(text, sizes):
    """Compiled copies, dynamic slices and dynamic-update-slices (plain ops,
    or fusions named after them) with a result holding as many elements as
    one of ``sizes`` (in any shape)."""
    found = []
    for m in re.finditer(r"^\s*(?:ROOT )?%(\S+) = (.*)$", text, re.M):
        name, rest = m.groups()
        op = re.search(r" ([\w.-]+)\(%", rest)
        if op is None:
            continue
        kind = (op.group(1) in ("copy", "dynamic-slice", "dynamic-update-slice")
                or name.startswith("copy_bitcast_fusion")
                or "dynamic-slice" in name or "dynamic-update-slice" in name)
        results = re.findall(r"\w+\[([\d,]*)\]", rest[:op.start()])
        if kind and any(math.prod(int(d) for d in dims.split(",") if d)
                        in sizes for dims in results):
            found.append(m.group(0).strip())
    return found


@pytest.mark.parametrize("kv_quant", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("step", ["decode", "chunk"])
def test_kv_pool_carried_through_layer_scan(sds, step, kv_quant):
    """A 2-layer paged decode step and 512-token prefill chunk (small widths
    but heads of 128, pools donated, Pallas kernels) update the stacked pools
    in place: no copy, dynamic slice or dynamic-update-slice the size of a
    whole stacked pool leaf or of one layer's share of it.  The f32 scale
    leaves of int8 pools are left out: their 16-wide minor axis is relaid
    out around the kernels at any depth, one layer included."""
    cfg = get_config("codellama-7b", smoke=True).with_(
        num_layers=2, head_dim=DH, kv_quant=kv_quant, paged_attn_impl="pallas")
    to_sds = lambda a: sds(a.shape, a.dtype)
    params = jax.tree.map(to_sds, jax.eval_shape(
        lambda: LM.init_lm(jax.random.PRNGKey(0), cfg)))
    cache = jax.tree.map(to_sds, jax.eval_shape(
        lambda: LM.init_paged_cache(cfg, NP, PS)))
    sizes = {n for name, a in cache["layers"].items() if not name.endswith("_s")
             for n in (math.prod(a.shape), math.prod(a.shape[1:]))}
    if step == "decode":
        fn = lambda p, c, tok, pos, table: LM.lm_decode_paged(
            p, tok, c, pos, table, cfg, backend="pallas")
        args = (params, cache, sds((B, 1), I32), sds((B,), I32),
                sds((B, PAGES), I32))
    else:
        fn = lambda p, c, tok, start, clen, table: LM.lm_prefill_chunk(
            p, tok, c, start, clen, table, cfg, backend="pallas")
        args = (params, cache, sds((1, 512), I32), sds((1,), I32),
                sds((1,), I32), sds((1, PAGES), I32))
    text = jax.jit(fn, donate_argnums=1).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    assert _pool_moves(text, sizes) == []
