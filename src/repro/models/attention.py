"""Attention mixers: GQA (with chunked online-softmax "flash" prefill) and
DeepSeek-V2 MLA (with compressed-latent decode, the memory-saving absorbed
form).

Cache convention (decode): ``{"k": [B,S,Hkv,Dh], "v": [B,S,Hkv,Dh],
"lens": [B] int32}`` — ``lens[b]`` is the number of valid cache entries.
MLA caches the latent instead: ``{"ckv": [B,S,r], "kpe": [B,S,dr], "lens"}``.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.sharding.hints import shard_hint

NEG_INF = -1e30


# =========================================================== GQA attention ==
def init_gqa(key, cfg: ModelConfig) -> Dict[str, Any]:
    d, h, hkv, dh = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.hdim
    ks = jax.random.split(key, 4)
    dt = cfg.jdtype
    return {
        "wq": L.init_linear(ks[0], d, h * dh, dt, bias=cfg.attn_bias),
        "wk": L.init_linear(ks[1], d, hkv * dh, dt, bias=cfg.attn_bias),
        "wv": L.init_linear(ks[2], d, hkv * dh, dt, bias=cfg.attn_bias),
        "wo": L.init_linear(ks[3], h * dh, d, dt, bias=cfg.attn_bias),
    }


def _qkv(p, x, positions, cfg: ModelConfig, backend: str):
    b, t, _ = x.shape
    h, hkv, dh = cfg.num_heads, cfg.num_kv_heads, cfg.hdim
    q = L.apply_linear(p["wq"], x, backend=backend, act=cfg.act_kernel).reshape(b, t, h, dh)
    k = L.apply_linear(p["wk"], x, backend=backend, act=cfg.act_kernel).reshape(b, t, hkv, dh)
    v = L.apply_linear(p["wv"], x, backend=backend, act=cfg.act_kernel).reshape(b, t, hkv, dh)
    q = L.apply_rope(q, positions, theta=cfg.rope_theta, variant=cfg.rope)
    k = L.apply_rope(k, positions, theta=cfg.rope_theta, variant=cfg.rope)
    # anchor layouts: batch on data, heads on model (dropped if indivisible)
    dp = ("pod", "data")
    q = shard_hint(q, dp, None, "model", None)
    k = shard_hint(k, dp, None, "model", None)
    v = shard_hint(v, dp, None, "model", None)
    return q, k, v


def chunked_attention(
    q: jax.Array,          # [B, T, H, Dh]
    k: jax.Array,          # [B, S, Hkv, Dh]
    v: jax.Array,          # [B, S, Hkv, Dh]
    q_pos: jax.Array,      # [B, T]
    k_pos: jax.Array,      # [B, S]
    k_valid: Optional[jax.Array] = None,  # [B, S] bool
    *,
    causal: bool = True,
    q_chunk: int = 2048,
    kv_chunk: int = 1024,
) -> jax.Array:
    """Online-softmax attention, O(q_chunk·kv_chunk) score blocks in memory."""
    b, t, h, dh = q.shape
    s, hkv = k.shape[1], k.shape[2]
    dv = v.shape[-1]                 # may differ from dh (MLA)
    grp = h // hkv
    scale = dh ** -0.5

    q_chunk = min(q_chunk, t)
    kv_chunk = min(kv_chunk, s)
    # pad to multiples
    tp = -(-t // q_chunk) * q_chunk
    sp = -(-s // kv_chunk) * kv_chunk
    if k_valid is None:
        k_valid = jnp.ones((b, s), bool)
    if tp != t:
        q = jnp.pad(q, ((0, 0), (0, tp - t), (0, 0), (0, 0)))
        q_pos = jnp.pad(q_pos, ((0, 0), (0, tp - t)))
    if sp != s:
        k = jnp.pad(k, ((0, 0), (0, sp - s), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, sp - s), (0, 0), (0, 0)))
        k_pos = jnp.pad(k_pos, ((0, 0), (0, sp - s)))
        k_valid = jnp.pad(k_valid, ((0, 0), (0, sp - s)))

    nq, nk = tp // q_chunk, sp // kv_chunk
    # [B, nq, qc, Hkv, grp, Dh] view of q
    qb = q.reshape(b, nq, q_chunk, hkv, grp, dh)
    qpb = q_pos.reshape(b, nq, q_chunk)
    kb = k.reshape(b, nk, kv_chunk, hkv, dh)
    vb = v.reshape(b, nk, kv_chunk, hkv, dv)
    kpb = k_pos.reshape(b, nk, kv_chunk)
    kvb = k_valid.reshape(b, nk, kv_chunk)

    def q_block(carry, qi):
        del carry
        qq = qb[:, qi]            # [B,qc,Hkv,grp,Dh]
        qp = qpb[:, qi]           # [B,qc]

        def kv_block(state, ki):
            m, l, acc = state
            kk = kb[:, ki]        # [B,kc,Hkv,Dh]
            vv = vb[:, ki]
            kp = kpb[:, ki]       # [B,kc]
            kval = kvb[:, ki]
            # scores [B,Hkv,grp,qc,kc]
            sc = jnp.einsum(
                "bqhgd,bkhd->bhgqk", qq.astype(jnp.float32), kk.astype(jnp.float32)
            ) * scale
            mask = kval[:, None, None, None, :]
            if causal:
                mask = mask & (
                    kp[:, None, None, None, :] <= qp[:, None, None, :, None]
                )
            sc = jnp.where(mask, sc, NEG_INF)
            m_new = jnp.maximum(m, sc.max(-1))
            p = jnp.exp(sc - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bhgqk,bkhd->bhgqd", p, vv.astype(jnp.float32)
            )
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((b, hkv, grp, q_chunk), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, hkv, grp, q_chunk), jnp.float32)
        a0 = jnp.zeros((b, hkv, grp, q_chunk, dv), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(kv_block, (m0, l0, a0), jnp.arange(nk))
        out = acc / jnp.maximum(l[..., None], 1e-30)  # [B,Hkv,grp,qc,Dh]
        return None, out.transpose(0, 3, 1, 2, 4)     # [B,qc,Hkv,grp,Dh]

    _, blocks = jax.lax.scan(q_block, None, jnp.arange(nq))
    # blocks: [nq, B, qc, Hkv, grp, Dv]
    out = blocks.transpose(1, 0, 2, 3, 4, 5).reshape(b, tp, h, dv)
    return out[:, :t].astype(q.dtype)


def _full_attention(q, k, v, positions, cfg: ModelConfig, causal: bool):
    """Dispatch full-sequence attention: Pallas flash (TPU / interpret) or
    the jnp chunked online-softmax path (CPU, dry-run lowering)."""
    if cfg.attn_impl in ("flash", "flash_interpret"):
        from repro.kernels.flash_attention import flash_attention

        return flash_attention(
            q, k, v, causal=causal,
            interpret=(cfg.attn_impl == "flash_interpret"),
        )
    return chunked_attention(q, k, v, positions, positions, causal=causal)


def gqa_prefill(
    p, x, positions, cfg: ModelConfig, *, backend: str = "auto", causal: bool = True
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    b, t, _ = x.shape
    q, k, v = _qkv(p, x, positions, cfg, backend)
    out = _full_attention(q, k, v, positions, cfg, causal)
    y = L.apply_linear(p["wo"], out.reshape(b, t, -1), backend=backend, act=cfg.act_kernel)
    return y, {"k": k, "v": v, "lens": jnp.full((b,), t, jnp.int32)}


def _dequant_pages(rows: jax.Array, scales: Optional[jax.Array]) -> jax.Array:
    """Dequantize gathered int8 page rows in-flight (``scales`` broadcast over
    the trailing feature dim); identity when the pool is fp."""
    if scales is None:
        return rows
    return rows.astype(jnp.float32) * scales.astype(jnp.float32)[..., None]


def _chunk_positions(start_len: jax.Array, t: int) -> jax.Array:
    """True logical positions of a ``[B, T]`` chunk whose row ``b`` starts at
    ``start_len[b]`` tokens already written."""
    return start_len[:, None] + jnp.arange(t, dtype=jnp.int32)[None, :]


def _scatter_chunk(pool: Dict[str, jax.Array], updates: Dict[str, jax.Array],
                   table_rows: jax.Array, start_len: jax.Array,
                   chunk_len: jax.Array, kv_quant: bool, page_base=0):
    """Scatter a ``[B, T, H, D]`` chunk of raw KV rows into the paged pools at
    logical positions ``start_len[b] + t`` (quantizing per row under
    ``kv_quant``).  Padded rows (``t >= chunk_len[b]``) land on the trash
    page ``page_base`` (the first page of this layer's range in a carried
    pool view), exactly like ``prefix_write_plan`` routes invalid rows."""
    b, t = next(iter(updates.values())).shape[:2]
    ps = pool[next(iter(updates))].shape[2]
    n_pages = table_rows.shape[1]
    pos = _chunk_positions(start_len, t)
    valid = jnp.arange(t, dtype=jnp.int32)[None, :] < chunk_len[:, None]
    lpage = jnp.minimum(pos // ps, n_pages - 1)
    pg = jnp.where(valid, table_rows[jnp.arange(b)[:, None], lpage],
                   page_base)
    return _write_rows(pool, updates, pg, pos % ps, kv_quant)


def gqa_prefill_chunk(
    p, x, pool: Dict[str, jax.Array], table_rows: jax.Array,
    start_len: jax.Array, chunk_len: jax.Array, cfg: ModelConfig, *,
    backend: str = "auto", page_base=0
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Chunked prefill straight against the paged pools.

    ``x[B, T, D]`` holds one prompt chunk per slot; row ``b``'s token ``t``
    sits at logical position ``start_len[b] + t`` (rope applied there), where
    ``start_len`` counts every token already in the pages — cached prefix
    pages and earlier chunks alike.  The chunk's own KV is scattered into the
    slot's pages first (quantized under ``kv_quant``); attention then reads
    the ``start_len`` prefix rows *from the pools* — through the Pallas
    chunked-prefill grid on the kernel impls, or the dense ``gather_pages``
    oracle under ``paged_attn_impl="gather"`` — while the chunk attends its
    own suffix K/V raw (pre-quantization), keeping slab-prefill numerics.
    Rows with ``t >= chunk_len[b]`` are padding: scattered to the trash page
    ``page_base`` (see the page-pool notes below), masked out as keys.
    """
    b, t, _ = x.shape
    h, hkv, dh = cfg.num_heads, cfg.num_kv_heads, cfg.hdim
    grp = h // hkv
    positions = _chunk_positions(start_len, t)
    q, k, v = _qkv(p, x, positions, cfg, backend)
    new_pool = _scatter_chunk(pool, {"k": k, "v": v}, table_rows, start_len,
                              chunk_len, cfg.kv_quant, page_base)
    scale = dh ** -0.5
    impl = _resolve_paged_impl(cfg, backend)

    if impl in ("pallas", "pallas_interpret"):
        from repro.kernels import ops as K

        out = K.gqa_paged_prefill(
            q.reshape(b, t, hkv, grp, dh), k, v,
            new_pool["k"], new_pool["v"], table_rows, start_len, chunk_len,
            new_pool.get("k_s"), new_pool.get("v_s"), sm_scale=scale,
            backend="interpret" if impl == "pallas_interpret" else "pallas",
        ).reshape(b, t, h, -1)
    else:
        # XLA oracle: dense gather of the prefix pages (the copy the kernel
        # exists to kill), suffix raw — identical masks to the kernel grid
        pk = _dequant_pages(gather_pages(new_pool["k"], table_rows),
                            gather_pages(new_pool["k_s"], table_rows)
                            if cfg.kv_quant else None)
        pv = _dequant_pages(gather_pages(new_pool["v"], table_rows),
                            gather_pages(new_pool["v_s"], table_rows)
                            if cfg.kv_quant else None)
        s = pk.shape[1]
        kpos_pre = jnp.broadcast_to(
            jnp.arange(s, dtype=jnp.int32)[None], (b, s))
        k_valid = jnp.concatenate(
            [kpos_pre < start_len[:, None],
             jnp.arange(t, dtype=jnp.int32)[None, :] < chunk_len[:, None]],
            axis=1)
        out = chunked_attention(
            q,
            jnp.concatenate([pk.astype(k.dtype), k], axis=1),
            jnp.concatenate([pv.astype(v.dtype), v], axis=1),
            positions,
            jnp.concatenate([kpos_pre, positions], axis=1),
            k_valid,
            causal=True,
        )
    y = L.apply_linear(p["wo"], out.reshape(b, t, -1).astype(x.dtype),
                       backend=backend, act=cfg.act_kernel)
    return y, new_pool


def _attend_rows(qh, k_rows, v_rows, valid, scale, k_s=None, v_s=None):
    """One-token attention of ``qh[B,Hkv,grp,Dh]`` against gathered rows
    ``k/v[B,S,Hkv,D*]`` with validity mask ``valid[B,S]``.

    With int8 rows, ``k_s``/``v_s[B,S,Hkv]`` are the per-row dequant scales:
    the dot streams the int8 codes and the scale is applied to the (tiny)
    score/probability tensors instead of a dense dequantized copy."""
    sc = jnp.einsum(
        "bhgd,bshd->bhgs", qh.astype(jnp.float32), k_rows.astype(jnp.float32)
    ) * scale
    if k_s is not None:
        sc = sc * k_s.astype(jnp.float32).transpose(0, 2, 1)[:, :, None, :]
    sc = jnp.where(valid[:, None, None, :], sc, NEG_INF)
    pattn = jax.nn.softmax(sc, axis=-1)
    if v_s is not None:
        pattn = pattn * v_s.astype(jnp.float32).transpose(0, 2, 1)[:, :, None, :]
    return jnp.einsum("bhgs,bshd->bhgd", pattn, v_rows.astype(jnp.float32))


def gqa_decode(
    p, x, positions, cache: Dict[str, jax.Array], cfg: ModelConfig, *, backend: str = "auto"
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """One-token decode against a [B, Smax] cache.  x: [B, 1, D]."""
    b, t, _ = x.shape
    assert t == 1, "decode processes one token"
    h, hkv, dh = cfg.num_heads, cfg.num_kv_heads, cfg.hdim
    grp = h // hkv
    q, k, v = _qkv(p, x, positions, cfg, backend)
    lens = cache["lens"]                                   # [B]
    smax = cache["k"].shape[1]
    slot = lens                                            # insert position
    bidx = jnp.arange(b)
    kpos = jnp.arange(smax)[None, :]                       # [1,S]
    valid = kpos <= slot[:, None]
    scale = dh ** -0.5
    qh = q.reshape(b, hkv, grp, dh)

    if cfg.kv_quant:
        kq, ks = kv_quantize_rows(k[:, 0])
        vq, vs = kv_quantize_rows(v[:, 0])
        k_cache = cache["k"].at[bidx, slot].set(kq)
        v_cache = cache["v"].at[bidx, slot].set(vq)
        k_sc = cache["k_s"].at[bidx, slot].set(ks.astype(cache["k_s"].dtype))
        v_sc = cache["v_s"].at[bidx, slot].set(vs.astype(cache["v_s"].dtype))
        out = _attend_rows(qh, k_cache, v_cache, valid, scale,
                           k_s=k_sc, v_s=v_sc)
        new_cache = {"k": k_cache, "v": v_cache, "k_s": k_sc, "v_s": v_sc,
                     "lens": lens + 1}
    else:
        k_cache = cache["k"].at[bidx, slot].set(k[:, 0])
        v_cache = cache["v"].at[bidx, slot].set(v[:, 0])
        out = _attend_rows(qh, k_cache, v_cache, valid, scale)
        new_cache = {"k": k_cache, "v": v_cache, "lens": lens + 1}
    y = L.apply_linear(
        p["wo"], out.reshape(b, 1, h * dh).astype(x.dtype), backend=backend, act=cfg.act_kernel
    )
    return y, new_cache


def init_gqa_cache(cfg: ModelConfig, batch: int, smax: int) -> Dict[str, jax.Array]:
    hkv, dh = cfg.num_kv_heads, cfg.hdim
    if cfg.kv_quant:
        # int8 cache + per-(position, head) scales: halves HBM traffic of the
        # memory-bound decode step (beyond-paper; weights are already int4).
        # Scales stay f32 like the page pools', so the contiguous slab and
        # paged caches hold bit-identical rows under any cfg dtype.
        return {
            "k": jnp.zeros((batch, smax, hkv, dh), jnp.int8),
            "v": jnp.zeros((batch, smax, hkv, dh), jnp.int8),
            "k_s": jnp.zeros((batch, smax, hkv), jnp.float32),
            "v_s": jnp.zeros((batch, smax, hkv), jnp.float32),
            "lens": jnp.zeros((batch,), jnp.int32),
        }
    return {
        "k": jnp.zeros((batch, smax, hkv, dh), cfg.jdtype),
        "v": jnp.zeros((batch, smax, hkv, dh), cfg.jdtype),
        "lens": jnp.zeros((batch,), jnp.int32),
    }


# ------------------------------------------------------------ page pools ---
# Every paged KV leaf, GQA and MLA alike, is laid out per page as
#   data  [num_pages, H, page_size, D]   (H = Hkv; 1 for MLA latents)
#   scale [num_pages, H, page_size]      (int8 pools only)
# so one page of one layer is one contiguous block, and the Pallas kernels'
# page blocks end in (page_size, D) / (H, page_size): full extents or tile
# multiples, which the TPU lowering requires (a [.., page_size, H, D] pool
# would need a block of 1 on its head axis).  Rows enter and leave in
# logical order as [..., H, *D]; only the helpers below know the layout.
# Writes go through scatter_rows, which addresses each (page, head, offset)
# row of the flat [num_pages·H·page_size, *D] view, so a step updates the
# pools in place in this layout, the one the kernels read.
# A model stacks one pool per layer, [L, num_pages, ...].  Its paged layer
# scans (models/lm.py) carry each leaf as one [L·num_pages, ...] view in
# which layer l owns pages [l·num_pages, (l+1)·num_pages), and hand every
# function here the page table offset by the page base l·num_pages.  Page
# numbers are then global to the view: reads, writes and the trash page
# (page_base, where padded chunk rows land) all address layer l's range,
# and the whole stack is updated in place.  At L = 1 the base is 0 and the
# view is the pool itself.
def init_page_pool(dims: Dict[str, int], num_pages: int, heads: int,
                   page_size: int, dtype,
                   kv_quant: bool) -> Dict[str, jax.Array]:
    """Zeroed pool leaves ``{name: [NP, H, PS, D]}`` (+ ``name_s`` f32
    ``[NP, H, PS]`` scale leaves and int8 codes under ``kv_quant``)."""
    pool = {}
    for name, d in dims.items():
        shp = (num_pages, heads, page_size)
        pool[name] = jnp.zeros((*shp, d), jnp.int8 if kv_quant else dtype)
        if kv_quant:
            pool[name + "_s"] = jnp.zeros(shp, jnp.float32)
    return pool


def scatter_rows(leaf: jax.Array, page: jax.Array, off: jax.Array,
                 rows: jax.Array) -> jax.Array:
    """Write rows ``[*I, H, *D]`` into ``leaf`` at pool pages ``page[*I]``,
    in-page offsets ``off[*I]``.

    Each head's row is one whole minor row of a flat ``[NP·H·PS, *D]`` view
    of the leaf, at ``(page·H + h)·PS + off``, so the scatter updates the
    pool in place in its own layout.  A scatter whose window spans the head
    axis but skips the page-offset axis (``leaf.at[page, :, off]``) makes
    XLA pick a transposed layout for the leaf instead: on the TPU that cost
    three whole-pool copies per leaf per step."""
    n_pages, heads, page_size, *d = leaf.shape
    idx = ((page[..., None] * heads + jnp.arange(heads, dtype=page.dtype))
           * page_size + off[..., None])                       # [*I, H]
    flat = leaf.reshape(n_pages * heads * page_size, *d)
    flat = flat.at[idx.reshape(-1)].set(
        rows.reshape(-1, *d).astype(leaf.dtype))
    return flat.reshape(leaf.shape)


def gather_pages(pool: jax.Array, table_rows: jax.Array) -> jax.Array:
    """``pool[num_pages, H, page_size, ...]`` + page table ``table_rows[B, P]``
    → dense ``[B, P*page_size, H, ...]`` rows in logical-position order.

    This is the jnp *reference* gather (``paged_attn_impl="gather"``): it
    materializes the full trash-padded table in HBM every step.  The Pallas
    paged-attention kernel (``kernels/paged_attention.py``) indexes the pool
    inside the grid instead and never builds this array.  Re-exported by
    ``serving.kv_cache`` for the pager tests."""
    g = jnp.swapaxes(pool[table_rows], 2, 3)        # [B, P, PS, H, ...]
    return g.reshape(g.shape[0], g.shape[1] * g.shape[2], *g.shape[3:])


def rows_to_pages(rows: jax.Array, page_size: int) -> jax.Array:
    """Logical rows ``[..., n·page_size, H, D]`` → whole pages
    ``[..., n, H, page_size, D]`` (the inverse of :func:`gather_pages`'
    flattening), ready for a whole-page scatter."""
    *lead, s, h, d = rows.shape
    pages = rows.reshape(*lead, s // page_size, page_size, h, d)
    return jnp.swapaxes(pages, -3, -2)


def _write_rows(pool: Dict[str, jax.Array], rows: Dict[str, jax.Array],
                pg: jax.Array, off: jax.Array,
                kv_quant: bool) -> Dict[str, jax.Array]:
    """Scatter ``{name: [*I, H, D]}`` rows at pool pages ``pg[*I]`` /
    offsets ``off[*I]``, quantizing per row (codes + ``name_s`` scales)
    under ``kv_quant``."""
    new_pool = dict(pool)
    for name, r in rows.items():
        if kv_quant:
            codes, scl = kv_quantize_rows(r)
            new_pool[name] = scatter_rows(pool[name], pg, off, codes)
            new_pool[name + "_s"] = scatter_rows(pool[name + "_s"], pg, off,
                                                 scl)
        else:
            new_pool[name] = scatter_rows(pool[name], pg, off, r)
    return new_pool


def _write_token(pool: Dict[str, jax.Array], rows: Dict[str, jax.Array],
                 table_rows: jax.Array, write_pos: jax.Array,
                 kv_quant: bool) -> Dict[str, jax.Array]:
    """Write one decode token's rows ``{name: [B, H, D]}`` at logical
    position ``write_pos[B]``."""
    page_size = pool[next(iter(rows))].shape[2]
    pg = table_rows[jnp.arange(table_rows.shape[0]), write_pos // page_size]
    return _write_rows(pool, rows, pg, write_pos % page_size, kv_quant)


def _resolve_paged_impl(cfg: ModelConfig, backend: str) -> str:
    """Map (cfg.paged_attn_impl, kernel backend) to a concrete decode impl."""
    impl = cfg.paged_attn_impl
    if impl != "auto":
        return impl
    if backend == "interpret":
        return "pallas_interpret"
    if backend == "pallas" or (
        backend == "auto" and jax.default_backend() == "tpu"
    ):
        return "pallas"
    return "gather"


def gqa_decode_paged(
    p, x, positions, pool: Dict[str, jax.Array], table_rows: jax.Array,
    write_pos: jax.Array, cfg: ModelConfig, *, backend: str = "auto"
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """One-token decode against a *paged* pool.

    ``pool``: ``{"k"/"v": [num_pages, Hkv, page_size, Dh]}`` shared across
    slots; ``table_rows[B, P]`` maps each slot's logical pages to pool pages
    (unused entries point at the trash page); ``write_pos[B]`` is the logical
    position the new token lands at.  Rows are gathered back into logical
    order, so the math is identical to :func:`gqa_decode` on a contiguous
    ``[B, P*page_size]`` cache.
    """
    b, t, _ = x.shape
    assert t == 1, "decode processes one token"
    h, hkv, dh = cfg.num_heads, cfg.num_kv_heads, cfg.hdim
    q, k, v = _qkv(p, x, positions, cfg, backend)
    # distinct slots own distinct pages → scatter indices collide only for
    # idle slots, whose table rows all point at the trash page
    new_pool = _write_token(pool, {"k": k[:, 0], "v": v[:, 0]}, table_rows,
                            write_pos, cfg.kv_quant)
    qh = q.reshape(b, hkv, h // hkv, dh)
    scale = dh ** -0.5
    impl = _resolve_paged_impl(cfg, backend)

    if impl in ("pallas", "pallas_interpret"):
        from repro.kernels import ops as K

        out = K.gqa_paged_attention(
            qh, new_pool["k"], new_pool["v"], table_rows, write_pos + 1,
            new_pool.get("k_s"), new_pool.get("v_s"), sm_scale=scale,
            backend="interpret" if impl == "pallas_interpret" else "pallas",
        )
    else:
        # XLA reference: dense gather of the pool rows (int8 rows gather
        # their scale rows too; _attend_rows dequantizes in-flight)
        k_rows = gather_pages(new_pool["k"], table_rows)    # [B,P*PS,Hkv,Dh]
        v_rows = gather_pages(new_pool["v"], table_rows)
        valid = jnp.arange(k_rows.shape[1])[None, :] <= write_pos[:, None]
        out = _attend_rows(
            qh, k_rows, v_rows, valid, scale,
            k_s=gather_pages(new_pool["k_s"], table_rows) if cfg.kv_quant else None,
            v_s=gather_pages(new_pool["v_s"], table_rows) if cfg.kv_quant else None,
        )
    y = L.apply_linear(
        p["wo"], out.reshape(b, 1, h * dh).astype(x.dtype), backend=backend, act=cfg.act_kernel
    )
    return y, new_pool


def init_gqa_page_pool(cfg: ModelConfig, num_pages: int, page_size: int):
    # int8 rows + per-(head, position) f32 scale pool under kv_quant: halves
    # KV page bytes on the memory-bound decode path (scales are Dh× smaller)
    return init_page_pool({"k": cfg.hdim, "v": cfg.hdim}, num_pages,
                          cfg.num_kv_heads, page_size, cfg.jdtype,
                          cfg.kv_quant)


def kv_quantize_rows(x: jax.Array):
    """Symmetric per-row int8 over the trailing dim:
    ``x[..., D] -> (int8[..., D], f32 scale[...])``.  Used for the contiguous
    int8 KV cache (per position, head), the int8 page pools, and the raw
    prefill KV quantized on paged admission."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1) + 1e-8
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / amax[..., None] * 127.0),
                 -127, 127).astype(jnp.int8)
    return q, (amax / 127.0)


# ===================================================================== MLA ==
def init_mla(key, cfg: ModelConfig) -> Dict[str, Any]:
    m = cfg.mla
    d, h = cfg.d_model, cfg.num_heads
    dt = cfg.jdtype
    ks = jax.random.split(key, 5)
    qk_dim = m.qk_nope_head_dim + m.qk_rope_head_dim
    return {
        "wq_a": L.init_linear(ks[0], d, m.q_lora_rank, dt),
        "norm_q": L.init_norm(m.q_lora_rank, "rmsnorm", dt),
        "wq_b": L.init_linear(ks[1], m.q_lora_rank, h * qk_dim, dt),
        "wkv_a": L.init_linear(ks[2], d, m.kv_lora_rank + m.qk_rope_head_dim, dt),
        "norm_kv": L.init_norm(m.kv_lora_rank, "rmsnorm", dt),
        "wkv_b": L.init_linear(
            ks[3], m.kv_lora_rank, h * (m.qk_nope_head_dim + m.v_head_dim), dt
        ),
        "wo": L.init_linear(ks[4], h * m.v_head_dim, d, dt),
    }


def _mla_q(p, x, positions, cfg: ModelConfig, backend: str):
    m = cfg.mla
    b, t, _ = x.shape
    h = cfg.num_heads
    qk = m.qk_nope_head_dim + m.qk_rope_head_dim
    q = L.apply_linear(p["wq_a"], x, backend=backend, act=cfg.act_kernel)
    q = L.apply_norm(p["norm_q"], q)
    q = L.apply_linear(p["wq_b"], q, backend=backend, act=cfg.act_kernel).reshape(b, t, h, qk)
    q_nope, q_pe = q[..., : m.qk_nope_head_dim], q[..., m.qk_nope_head_dim :]
    q_pe = L.apply_rope(q_pe, positions, theta=cfg.rope_theta, variant="standard")
    return q_nope, q_pe


def _mla_latent(p, x, positions, cfg: ModelConfig, backend: str):
    m = cfg.mla
    kv = L.apply_linear(p["wkv_a"], x, backend=backend, act=cfg.act_kernel)
    ckv, k_pe = kv[..., : m.kv_lora_rank], kv[..., m.kv_lora_rank :]
    ckv = L.apply_norm(p["norm_kv"], ckv)
    k_pe = L.apply_rope(
        k_pe[:, :, None, :], positions, theta=cfg.rope_theta, variant="standard"
    )[:, :, 0, :]
    return ckv, k_pe


def mla_prefill(
    p, x, positions, cfg: ModelConfig, *, backend: str = "auto", causal: bool = True
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Expanded (compute-friendly) MLA for prefill; caches the latent."""
    m = cfg.mla
    b, t, _ = x.shape
    h = cfg.num_heads
    q_nope, q_pe = _mla_q(p, x, positions, cfg, backend)
    ckv, k_pe = _mla_latent(p, x, positions, cfg, backend)
    kvb = L.apply_linear(p["wkv_b"], ckv, backend=backend, act=cfg.act_kernel).reshape(
        b, t, h, m.qk_nope_head_dim + m.v_head_dim
    )
    k_nope, v = kvb[..., : m.qk_nope_head_dim], kvb[..., m.qk_nope_head_dim :]
    q = jnp.concatenate([q_nope, q_pe], -1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_pe[:, :, None, :], q_pe.shape)], -1
    )
    # the concat of head-sharded k_nope with replicated broadcast k_pe leaves
    # GSPMD free to split the contraction dim (-> giant score all-reduces in
    # the chunk scans); pin q/k/v to batch-on-data, heads-on-model
    dp = ("pod", "data")
    q = shard_hint(q, dp, None, "model", None)
    k = shard_hint(k, dp, None, "model", None)
    v = shard_hint(v, dp, None, "model", None)
    out = chunked_attention(q, k, v, positions, positions, causal=causal)
    y = L.apply_linear(p["wo"], out.reshape(b, t, -1), backend=backend, act=cfg.act_kernel)
    return y, {"ckv": ckv, "kpe": k_pe, "lens": jnp.full((b,), t, jnp.int32)}


def mla_prefill_chunk(
    p, x, pool: Dict[str, jax.Array], table_rows: jax.Array,
    start_len: jax.Array, chunk_len: jax.Array, cfg: ModelConfig, *,
    backend: str = "auto", page_base=0
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Chunked MLA prefill against the paged latent pools, absorbed form.

    Same chunk contract as :func:`gqa_prefill_chunk` — positions are
    ``start_len[b] + t``, the chunk's raw latents scatter into the slot's
    pages, padding rows go to the trash page ``page_base``.  Attention runs
    absorbed (scores in the latent space, like decode) so the prefix pages
    stream through the Pallas grid without ever re-expanding ``wkv_b`` over
    a dense gathered copy; the chunk's own latents are attended raw
    (pre-quantization).  The absorbed projections ride the grouped W4A16
    kernel when ``wkv_b`` is int4.
    """
    m = cfg.mla
    b, t, _ = x.shape
    h = cfg.num_heads
    positions = _chunk_positions(start_len, t)
    q_nope, q_pe = _mla_q(p, x, positions, cfg, backend)
    ckv_suf, kpe_suf = _mla_latent(p, x, positions, cfg, backend)
    new_pool = _scatter_chunk(pool, {"ckv": ckv_suf[:, :, None],
                                     "kpe": kpe_suf[:, :, None]},
                              table_rows, start_len, chunk_len, cfg.kv_quant,
                              page_base)
    q_lat = _mla_absorb_q_lat(
        p, q_nope.reshape(b * t, h, m.qk_nope_head_dim), cfg, backend
    ).reshape(b, t, h, -1)
    scale = (m.qk_nope_head_dim + m.qk_rope_head_dim) ** -0.5
    impl = _resolve_paged_impl(cfg, backend)

    if impl in ("pallas", "pallas_interpret"):
        from repro.kernels import ops as K

        o_lat = K.mla_paged_prefill(
            q_lat, q_pe, ckv_suf, kpe_suf,
            new_pool["ckv"], new_pool["kpe"], table_rows, start_len,
            chunk_len, new_pool.get("ckv_s"), new_pool.get("kpe_s"),
            sm_scale=scale,
            backend="interpret" if impl == "pallas_interpret" else "pallas",
        )
    else:
        # XLA oracle: dense gather + in-flight dequant of the latent prefix,
        # suffix raw — same masks as the kernel grid
        pckv = _dequant_pages(gather_pages(new_pool["ckv"], table_rows),
                              gather_pages(new_pool["ckv_s"], table_rows)
                              if cfg.kv_quant else None)[:, :, 0]
        pkpe = _dequant_pages(gather_pages(new_pool["kpe"], table_rows),
                              gather_pages(new_pool["kpe_s"], table_rows)
                              if cfg.kv_quant else None)[:, :, 0]
        s = pckv.shape[1]
        ckv_all = jnp.concatenate(
            [pckv.astype(jnp.float32), ckv_suf.astype(jnp.float32)], axis=1)
        kpe_all = jnp.concatenate(
            [pkpe.astype(jnp.float32), kpe_suf.astype(jnp.float32)], axis=1)
        kpos_pre = jnp.broadcast_to(
            jnp.arange(s, dtype=jnp.int32)[None], (b, s))
        k_pos = jnp.concatenate([kpos_pre, positions], axis=1)
        k_valid = jnp.concatenate(
            [kpos_pre < start_len[:, None],
             jnp.arange(t, dtype=jnp.int32)[None, :] < chunk_len[:, None]],
            axis=1)
        sc = (
            jnp.einsum("bthr,bsr->bhts", q_lat.astype(jnp.float32), ckv_all)
            + jnp.einsum("bthd,bsd->bhts", q_pe.astype(jnp.float32), kpe_all)
        ) * scale
        mask = (k_valid[:, None, None, :]
                & (k_pos[:, None, None, :] <= positions[:, None, :, None]))
        sc = jnp.where(mask, sc, NEG_INF)
        attn = jax.nn.softmax(sc, axis=-1)
        o_lat = jnp.einsum("bhts,bsr->bthr", attn, ckv_all)
    out = _mla_absorb_out(
        p, o_lat.reshape(b * t, h, -1), cfg, backend
    ).reshape(b, t, h * m.v_head_dim)
    y = L.apply_linear(p["wo"], out.astype(x.dtype), backend=backend, act=cfg.act_kernel)
    return y, new_pool


def _mla_absorb_weights(p, cfg: ModelConfig):
    """Split an *fp* ``wkv_b`` into the absorbed key / value projections
    ``(w_k[r,H,nope], w_v[r,H,vdim])``.

    Quantized params never take this path: PTQ (``core.apply.quantize_params``)
    derives stacked int4 absorbed projections ``p["wkv_b_absorbed"]`` instead,
    and :func:`_mla_absorb_q_lat` / :func:`_mla_absorb_out` contract them
    through the grouped W4A16 kernel — a dense dequantized ``wkv_b`` is never
    materialized on a serving path."""
    m = cfg.mla
    h = cfg.num_heads
    from repro.core.quantize import QuantizedTensor

    wkv_b = p["wkv_b"]["w"]
    if isinstance(wkv_b, QuantizedTensor):
        raise TypeError(
            "quantized MLA decode needs p['wkv_b_absorbed'] (stacked int4 "
            "absorbed weights from core.apply.quantize_params); wholesale "
            "dequantization on the serving path is not supported")
    wkv_b = wkv_b.reshape(m.kv_lora_rank, h, m.qk_nope_head_dim + m.v_head_dim)
    return wkv_b[..., : m.qk_nope_head_dim], wkv_b[..., m.qk_nope_head_dim :]


def _mla_absorb_q_lat(p, q_nope1, cfg: ModelConfig, backend: str) -> jax.Array:
    """Absorb the query: ``q_lat[b,h,r] = q_nope[b,h,n] · w_k[·]`` — heads
    ride the grouped kernel's expert grid axis when the weight is int4."""
    if "wkv_b_absorbed" in p:
        from repro.kernels import ops as K

        wk_t = p["wkv_b_absorbed"]["wk_t"]               # int4 [H, nope, r]
        x = q_nope1.astype(jnp.float32).transpose(1, 0, 2)  # [H, B, nope]
        return K.w4a16_grouped_matmul(x, wk_t, backend=backend, act=cfg.act_kernel).transpose(
            1, 0, 2)
    w_k, _ = _mla_absorb_weights(p, cfg)
    return jnp.einsum(
        "bhn,rhn->bhr", q_nope1.astype(jnp.float32), w_k.astype(jnp.float32)
    )


def _mla_absorb_out(p, o_lat, cfg: ModelConfig, backend: str) -> jax.Array:
    """Project latent attention output back: ``out[b,h,v] = o_lat[b,h,r] ·
    w_v[·]`` — same head-as-expert grouped contraction for int4."""
    if "wkv_b_absorbed" in p:
        from repro.kernels import ops as K

        wv = p["wkv_b_absorbed"]["wv"]                   # int4 [H, r, v]
        x = o_lat.astype(jnp.float32).transpose(1, 0, 2)    # [H, B, r]
        return K.w4a16_grouped_matmul(x, wv, backend=backend, act=cfg.act_kernel).transpose(
            1, 0, 2)
    _, w_v = _mla_absorb_weights(p, cfg)
    return jnp.einsum("bhr,rhv->bhv", o_lat, w_v.astype(jnp.float32))


def _mla_absorbed_attend(p, q_nope, q_pe, ckv, kpe, valid, cfg: ModelConfig,
                         backend: str):
    """Absorbed-form latent attention of a single query token against gathered
    latent rows ``ckv[B,S,r]`` / ``kpe[B,S,dr]`` with mask ``valid[B,S]``."""
    m = cfg.mla
    b = q_nope.shape[0]
    h = cfg.num_heads

    q_lat = _mla_absorb_q_lat(p, q_nope[:, 0], cfg, backend)
    scale = (m.qk_nope_head_dim + m.qk_rope_head_dim) ** -0.5
    sc = (
        jnp.einsum("bhr,bsr->bhs", q_lat, ckv.astype(jnp.float32))
        + jnp.einsum(
            "bhd,bsd->bhs", q_pe[:, 0].astype(jnp.float32), kpe.astype(jnp.float32)
        )
    ) * scale
    sc = jnp.where(valid[:, None, :], sc, NEG_INF)
    attn = jax.nn.softmax(sc, axis=-1)
    o_lat = jnp.einsum("bhs,bsr->bhr", attn, ckv.astype(jnp.float32))
    out = _mla_absorb_out(p, o_lat, cfg, backend)
    return out.reshape(b, 1, h * m.v_head_dim)


def mla_decode(
    p, x, positions, cache, cfg: ModelConfig, *, backend: str = "auto"
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Absorbed-form decode: attention runs in the latent space, so the cache
    stays compressed ([B,S,r] instead of [B,S,H,Dh]) — MLA's entire point."""
    b, t, _ = x.shape
    assert t == 1
    q_nope, q_pe = _mla_q(p, x, positions, cfg, backend)    # [B,1,H,*]
    ckv_new, kpe_new = _mla_latent(p, x, positions, cfg, backend)
    lens = cache["lens"]
    bidx = jnp.arange(b)
    ckv = cache["ckv"].at[bidx, lens].set(ckv_new[:, 0])
    kpe = cache["kpe"].at[bidx, lens].set(kpe_new[:, 0])
    smax = ckv.shape[1]
    valid = jnp.arange(smax)[None, :] <= lens[:, None]
    out = _mla_absorbed_attend(p, q_nope, q_pe, ckv, kpe, valid, cfg, backend)
    y = L.apply_linear(p["wo"], out.astype(x.dtype), backend=backend, act=cfg.act_kernel)
    return y, {"ckv": ckv, "kpe": kpe, "lens": lens + 1}


def mla_decode_paged(
    p, x, positions, pool: Dict[str, jax.Array], table_rows: jax.Array,
    write_pos: jax.Array, cfg: ModelConfig, *, backend: str = "auto"
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Absorbed-form decode against a paged latent pool
    (``{"ckv": [NP,1,PS,r], "kpe": [NP,1,PS,dr]}``); see
    :func:`gqa_decode_paged` for the page-table convention."""
    b, t, _ = x.shape
    assert t == 1
    m = cfg.mla
    h = cfg.num_heads
    q_nope, q_pe = _mla_q(p, x, positions, cfg, backend)
    ckv_new, kpe_new = _mla_latent(p, x, positions, cfg, backend)
    # latents carry the pools' unit head axis: [B, 1, r]
    new_pool = _write_token(pool, {"ckv": ckv_new[:, :1],
                                   "kpe": kpe_new[:, :1]},
                            table_rows, write_pos, cfg.kv_quant)
    impl = _resolve_paged_impl(cfg, backend)
    scale = (m.qk_nope_head_dim + m.qk_rope_head_dim) ** -0.5

    if impl in ("pallas", "pallas_interpret"):
        from repro.kernels import ops as K

        kernel_backend = "interpret" if impl == "pallas_interpret" else "pallas"
        q_lat = _mla_absorb_q_lat(p, q_nope[:, 0], cfg, kernel_backend)
        o_lat = K.mla_paged_attention(
            q_lat, q_pe[:, 0], new_pool["ckv"], new_pool["kpe"], table_rows,
            write_pos + 1, new_pool.get("ckv_s"), new_pool.get("kpe_s"),
            sm_scale=scale,
            backend=kernel_backend,
        )
        out = _mla_absorb_out(p, o_lat, cfg, kernel_backend).reshape(
            b, 1, h * m.v_head_dim)
    else:
        # XLA reference: dense gather (+ in-flight dequant of int8 rows) of
        # the latent pages, head axis dropped
        ckv = _dequant_pages(gather_pages(new_pool["ckv"], table_rows),
                             gather_pages(new_pool["ckv_s"], table_rows)
                             if cfg.kv_quant else None)[:, :, 0]
        kpe = _dequant_pages(gather_pages(new_pool["kpe"], table_rows),
                             gather_pages(new_pool["kpe_s"], table_rows)
                             if cfg.kv_quant else None)[:, :, 0]
        valid = jnp.arange(ckv.shape[1])[None, :] <= write_pos[:, None]
        out = _mla_absorbed_attend(p, q_nope, q_pe, ckv, kpe, valid, cfg,
                                   backend)
    y = L.apply_linear(p["wo"], out.astype(x.dtype), backend=backend, act=cfg.act_kernel)
    return y, new_pool


def init_mla_page_pool(cfg: ModelConfig, num_pages: int, page_size: int):
    m = cfg.mla
    return init_page_pool({"ckv": m.kv_lora_rank, "kpe": m.qk_rope_head_dim},
                          num_pages, 1, page_size, cfg.jdtype, cfg.kv_quant)


def init_mla_cache(cfg: ModelConfig, batch: int, smax: int) -> Dict[str, jax.Array]:
    m = cfg.mla
    return {
        "ckv": jnp.zeros((batch, smax, m.kv_lora_rank), cfg.jdtype),
        "kpe": jnp.zeros((batch, smax, m.qk_rope_head_dim), cfg.jdtype),
        "lens": jnp.zeros((batch,), jnp.int32),
    }
