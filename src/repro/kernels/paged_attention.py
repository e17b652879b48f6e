"""Pallas TPU paged-attention decode kernel: page-table gather fused into
flash attention.

The jnp paged decode path materializes ``gather_pages(pool, table)`` as a
dense ``[B, P*PS, ...]`` array in HBM every step — the pool rows are read,
written back out as the gathered copy, then read *again* by the attention
einsum: ~3× the KV bytes of a single streaming pass, plus an O(batch ×
max-pages) allocation on the memory-bound decode hot path.  These kernels
index the pool *inside* the kernel instead: the block table rides in as a
scalar-prefetch operand (``pltpu.PrefetchScalarGridSpec``) and picks the
pool pages that are DMA'd into VMEM.  No intermediate gather ever exists in
HBM, and only pages that hold valid rows are copied — bytes moved scale with
*live tokens*, not ``batch × max_pages``.

Pool layout (``models/attention.py`` owns it): every page leaf is
``[NP, H, PS, D]`` (``H = Hkv`` for GQA, ``1`` for MLA latents) and every
int8 scale leaf ``[NP, H, PS]``.  A page's DMA moves all its heads, one
contiguous block whose trailing dims are ``(PS, D)`` and ``(H, PS)``: full
extents or tile multiples, as the TPU lowering needs.

Grids
  GQA decode: ``(B,)``, one step per batch row, whose loop walks only the
  row's live pages: ``cdiv(cdiv(lengths[b], PS), ppb)`` blocks (at least
  one) of ``ppb`` pages (:func:`_pages_per_block`: 128 rows a block, capped
  by the table width and a VMEM budget).  The K/V pools stay in HBM
  (``pl.ANY``); each block's live pages are copied by
  ``pltpu.make_async_copy``, one DMA per page and leaf, into one of two VMEM
  buffers, so block ``j+1``'s copies (or, from a row's last block, the next
  row's first) fly while block ``j`` computes.  A partial last block copies
  only its live pages; its other slots hold stale (finite) rows that the
  ``pos < length`` mask drops.  Each KV head's ``grp`` query rows score
  the whole block as one ``[grp, ppb·PS]`` tile.  Int8 scale leaves cannot
  be sliced per page by a DMA (an HBM minor axis of ``PS`` lanes does not
  tile), so each row's scales arrive gathered as ``[Hkv, ppb·cdiv(P, ppb)·PS]``
  rows, one BlockSpec block per grid step.  Pools whose head dim is not
  whole 128-lane tiles cannot be sliced by such a DMA and keep the grid
  ``(B, P)``, one page a step through the BlockSpec pipeline.
  MLA decode (absorbed form): ``(B, P)``; scores run in the latent space
  (``q_lat·ckv + q_pe·kpe``) so the per-page work covers all H heads.
  Chunked prefill: ``(B, R/bq, P+1)`` — the chunk's ``R = T·grp`` (GQA) or
  ``T·H`` (MLA) query rows tiled by ``bq`` (row ``r`` is token ``r // grp``
  at true position ``prefix_len[b] + r // grp``).  Steps ``0..P-1`` stream
  the cached prefix pages (masked ``kv_pos < prefix_len``, no causal term
  needed since every chunk query postdates the prefix); the final step
  attends the chunk's own raw-fp K/V with a causal-within-chunk mask and
  flushes.

Online-softmax state (m, l, acc) lives in VMEM scratch, one slice per KV
head, initialized at a row's first page (block) and flushed after its last.
The MLA decode and both prefill grids step one page at a time; their dead
pages re-map to the last live page, so the pipeline elides the copy.

Int8 pools: when scale operands are passed, K/V pages are int8 with per-row
(head, position) f32 scales.  Scores are computed on the raw int8 codes
(cast to f32 for the MXU) and the scale is applied to the score/probability
row — identical math to the jnp reference, half the page bytes.

Scalar-prefetch contract (shared with ``serving/kv_cache.py``):
  ``table[B*P]``  flattened block table; entry ``b*P + p`` is the pool page
                  holding logical page ``p`` of batch row ``b`` (freed /
                  unused entries point at the trash page 0);
  ``lengths[B]``  valid rows per batch row, *including* the token written
                  this step (``write_pos + 1``); sets the in-page validity
                  mask and which pages are copied — in GQA decode also the
                  row's block count, the loop's trip count.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
# query rows per chunked-prefill grid step: bounds the VMEM held by the q
# block, the f32 accumulator and the score tile for long chunks
MAX_Q_ROWS = 512
# pool rows per block of the GQA decode walk: one 128-row MXU tile of keys
BLOCK_TOKENS = 128
# TPU lane width: a hand-written DMA slices an HBM array in whole lane tiles
LANES = 128
# VMEM for the GQA decode walk's two buffered blocks of K and V pages
KV_BLOCK_VMEM = 4 << 20


def _live_pages(length, page_size):
    """Number of pages holding valid rows (length >= 1 on every decode)."""
    return (length + page_size - 1) // page_size


def _row_block(rows: int) -> int:
    """Query rows per prefill step: all of them up to :data:`MAX_Q_ROWS`,
    else the largest multiple of 8 that divides ``rows`` within it."""
    if rows <= MAX_Q_ROWS:
        return rows
    for bq in range(MAX_Q_ROWS, 7, -8):
        if rows % bq == 0:
            return bq
    return rows


def _scores(q, k):
    """``q[R, D] · k[S, D]ᵀ -> [R, S]`` in f32."""
    return jax.lax.dot_general(
        q.astype(jnp.float32), k.astype(jnp.float32),
        (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)


def _init_state(m_ref, l_ref, acc_ref):
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)


def _softmax_step(m_ref, l_ref, acc_ref, h, s, valid, v, p_scale=None):
    """Fold one score tile ``s[R, S]`` (masked by ``valid``) and its values
    ``v[S, Dv]`` into head ``h``'s online-softmax state.  ``p_scale[1, S]``
    carries int8 value pools' per-row scales onto the probabilities."""
    s = jnp.where(valid, s, NEG_INF)
    m_prev = m_ref[h]
    m_new = jnp.maximum(m_prev, s.max(-1, keepdims=True))
    pexp = jnp.where(valid, jnp.exp(s - m_new), 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_ref[h] = l_ref[h] * corr + pexp.sum(-1, keepdims=True)
    if p_scale is not None:
        pexp = pexp * p_scale
    acc_ref[h] = acc_ref[h] * corr + jax.lax.dot_general(
        pexp, v.astype(jnp.float32), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[h] = m_new


def _flush(o_ref, l_ref, acc_ref):
    o_ref[0] = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)


def _state_scratch(heads: int, rows: int, dv: int):
    return [pltpu.VMEM((heads, rows, 1), jnp.float32),
            pltpu.VMEM((heads, rows, 1), jnp.float32),
            pltpu.VMEM((heads, rows, dv), jnp.float32)]


def _page_maps(pages: int, ps: int, prefill: bool):
    """Index maps (data leaf, scale leaf) of the pool page a grid step reads.

    Decode grids ``(B, P)`` clamp to the slot's valid length; prefill grids
    ``(B, R/bq, P+1)`` clamp to its cached prefix, kept >= 1 page so cold
    rows (prefix 0) and the suffix step P stay in range.  Dead steps
    re-map to the last live page: the pipeline sees the same block index
    as the previous step and elides the DMA entirely."""
    def page(bi, pi, tbl, n_valid):
        live = _live_pages(n_valid, ps)
        if prefill:
            live = jnp.maximum(live, 1)
        return tbl[bi * pages + jnp.minimum(pi, live - 1)]

    if prefill:
        def data_map(bi, qi, pi, tbl, pfx, cln):
            return (page(bi, pi, tbl, pfx[bi]), 0, 0, 0)

        def scale_map(bi, qi, pi, tbl, pfx, cln):
            return (page(bi, pi, tbl, pfx[bi]), 0, 0)
    else:
        def data_map(bi, pi, tbl, lens):
            return (page(bi, pi, tbl, lens[bi]), 0, 0, 0)

        def scale_map(bi, pi, tbl, lens):
            return (page(bi, pi, tbl, lens[bi]), 0, 0)
    return data_map, scale_map


# ============================================================== GQA decode ==
def _pages_per_block(pages: int, page_size: int, page_bytes: int) -> int:
    """Pool pages the GQA decode walk copies per block: enough for
    :data:`BLOCK_TOKENS` rows, capped by the table width and by
    :data:`KV_BLOCK_VMEM` for two buffered blocks of ``page_bytes`` each
    page."""
    ppb = min(pages, -(-BLOCK_TOKENS // page_size))
    return max(1, min(ppb, KV_BLOCK_VMEM // (2 * page_bytes)))


def _gqa_kernel(tbl_ref, len_ref, q_ref, k_hbm, v_hbm, *rest, page_size: int,
                n_pages: int, ppb: int, scale: float, quant: bool):
    if quant:
        ks_ref, vs_ref, o_ref, k_buf, v_buf, sem, slot_ref, m_ref, l_ref, \
            acc_ref = rest
    else:
        o_ref, k_buf, v_buf, sem, slot_ref, m_ref, l_ref, acc_ref = rest
    b = pl.program_id(0)
    rows = pl.num_programs(0)
    blk_rows = ppb * page_size

    def copies(row, blk, slot, op):
        """Start (or wait for) block ``blk`` of ``row``: one DMA per live
        page and leaf, all signalling the slot's semaphore."""
        live = _live_pages(len_ref[row], page_size)
        for j in range(ppb):
            @pl.when(blk * ppb + j < live)
            def _page():
                page = tbl_ref[row * n_pages + blk * ppb + j]
                for pool, buf in ((k_hbm, k_buf), (v_hbm, v_buf)):
                    getattr(pltpu.make_async_copy(
                        pool.at[page], buf.at[slot, j], sem.at[slot]), op)()

    @pl.when(b == 0)
    def _first():
        # a partial block leaves its dead pages' buffer slots unwritten: keep
        # them finite, as masked probabilities (0) still meet their values
        k_buf[...] = jnp.zeros_like(k_buf)
        v_buf[...] = jnp.zeros_like(v_buf)
        slot_ref[0] = 0
        copies(0, 0, 0, "start")

    _init_state(m_ref, l_ref, acc_ref)
    length = len_ref[b]
    # >= 1 block, so every row has a first block to prefetch
    last = jnp.maximum(pl.cdiv(_live_pages(length, page_size), ppb), 1) - 1

    def block(i, slot):
        nxt = 1 - slot

        @pl.when(i < last)
        def _next_block():
            copies(b, i + 1, nxt, "start")

        @pl.when((i == last) & (b + 1 < rows))
        def _next_row():
            copies(b + 1, 0, nxt, "start")

        copies(b, i, slot, "wait")
        start = pl.multiple_of(i * blk_rows, blk_rows)
        pos = start + jax.lax.broadcasted_iota(
            jnp.int32, (q_ref.shape[2], blk_rows), 1)
        for h in range(q_ref.shape[1]):
            # [ppb, PS, D] -> [ppb·PS, D]: one score tile for the block
            k = k_buf[slot, :, h].reshape(blk_rows, -1)
            v = v_buf[slot, :, h].reshape(blk_rows, -1)
            s = _scores(q_ref[0, h], k) * scale             # [grp, ppb·PS]
            if quant:
                # int8 codes hit the MXU; the per-row scale lands on the
                # score row — mirrors the jnp int8 reference exactly
                s = s * ks_ref[0, h:h + 1, pl.ds(start, blk_rows)]
            _softmax_step(m_ref, l_ref, acc_ref, h, s, pos < length, v,
                          vs_ref[0, h:h + 1, pl.ds(start, blk_rows)]
                          if quant else None)
        return nxt

    slot_ref[0] = jax.lax.fori_loop(0, last + 1, block, slot_ref[0])
    _flush(o_ref, l_ref, acc_ref)


def _scale_rows(scale, table_rows, width):
    """Row-major scale rows ``[B, Hkv, width·PS]`` of each row's pages, the
    table padded with trash pages to ``width`` entries."""
    b, pages = table_rows.shape
    tbl = jnp.pad(table_rows, ((0, 0), (0, width - pages)))
    rows = scale[tbl]                                   # [B, width, Hkv, PS]
    return rows.transpose(0, 2, 1, 3).reshape(b, scale.shape[1], -1)


@functools.partial(jax.jit, static_argnames=("sm_scale", "interpret"))
def gqa_paged_attention(
    q: jax.Array,               # [B, Hkv, grp, Dh] one decode token
    k_pool: jax.Array,          # [NP, Hkv, PS, Dh] (bf16/f32 or int8)
    v_pool: jax.Array,          # [NP, Hkv, PS, Dv]
    table_rows: jax.Array,      # [B, P] int32 pool page per logical page
    lengths: jax.Array,         # [B] int32 valid rows incl. this step's token
    k_scale: jax.Array | None = None,   # [NP, Hkv, PS] f32 (int8 pools)
    v_scale: jax.Array | None = None,
    *,
    sm_scale: float,
    interpret: bool = False,
) -> jax.Array:                 # [B, Hkv, grp, Dv] f32
    """GQA decode attention over the paged pools: the block walk, or the
    page grid where the TPU cannot DMA a page by hand — Mosaic slices no
    page out of an HBM array whose minor axis is not whole 128-lane tiles,
    so head dims such as 64 or 112 (Whisper, Zamba2) keep one grid step
    per page."""
    fn = (_gqa_block_walk
          if interpret or (q.shape[-1] % LANES == 0
                           and v_pool.shape[-1] % LANES == 0)
          else _gqa_page_grid)
    return fn(q, k_pool, v_pool, table_rows, lengths, k_scale, v_scale,
              sm_scale=sm_scale, interpret=interpret)


def _gqa_block_walk(q, k_pool, v_pool, table_rows, lengths, k_scale,
                    v_scale, *, sm_scale, interpret):
    b, hkv, grp, dh = q.shape
    ps = k_pool.shape[2]
    dv = v_pool.shape[-1]
    pages = table_rows.shape[1]
    quant = k_scale is not None
    ppb = _pages_per_block(pages, ps, hkv * ps * (
        dh * k_pool.dtype.itemsize + dv * v_pool.dtype.itemsize))

    def fixed(bi, tbl, lens):
        return (bi, 0, 0, 0)

    in_specs = [pl.BlockSpec((1, hkv, grp, dh), fixed),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY)]
    operands = [q, k_pool, v_pool]
    if quant:
        # a page's [Hkv, PS] scales are too narrow a slice for a DMA (HBM
        # minor dims tile by 128 lanes), so each row's scales come gathered
        width = pl.cdiv(pages, ppb) * ppb
        in_specs += [pl.BlockSpec((1, hkv, width * ps),
                                  lambda bi, tbl, lens: (bi, 0, 0))] * 2
        operands += [_scale_rows(k_scale, table_rows, width),
                     _scale_rows(v_scale, table_rows, width)]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, hkv, grp, dv), fixed),
        scratch_shapes=[
            pltpu.VMEM((2, ppb, *k_pool.shape[1:]), k_pool.dtype),
            pltpu.VMEM((2, ppb, *v_pool.shape[1:]), v_pool.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SMEM((1,), jnp.int32),
        ] + _state_scratch(hkv, grp, dv),
    )
    return pl.pallas_call(
        functools.partial(
            _gqa_kernel, page_size=ps, n_pages=pages, ppb=ppb, scale=sm_scale,
            quant=quant,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, grp, dv), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            # a row's last block prefetches the next row's first
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
    )(table_rows.reshape(-1).astype(jnp.int32), lengths.astype(jnp.int32),
      *operands)


def _gqa_page_kernel(tbl_ref, len_ref, q_ref, k_ref, v_ref, *rest,
                     page_size: int, n_pages: int, scale: float, quant: bool):
    if quant:
        ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref = rest
    else:
        o_ref, m_ref, l_ref, acc_ref = rest
    b = pl.program_id(0)
    p = pl.program_id(1)

    @pl.when(p == 0)
    def _init():
        _init_state(m_ref, l_ref, acc_ref)

    length = len_ref[b]
    page_start = p * page_size

    @pl.when(page_start < length)
    def _compute():
        for h in range(q_ref.shape[1]):
            s = _scores(q_ref[0, h], k_ref[0, h]) * scale     # [grp, PS]
            if quant:
                s = s * ks_ref[0, h:h + 1, :]
            pos = page_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            _softmax_step(m_ref, l_ref, acc_ref, h, s, pos < length,
                          v_ref[0, h],
                          vs_ref[0, h:h + 1, :] if quant else None)

    @pl.when(p == n_pages - 1)
    def _out():
        _flush(o_ref, l_ref, acc_ref)


def _gqa_page_grid(q, k_pool, v_pool, table_rows, lengths, k_scale, v_scale,
                   *, sm_scale, interpret):
    """Grid ``(B, P)``, one pool page a step through the BlockSpec
    pipeline; dead pages re-map to the last live one, eliding their DMA."""
    b, hkv, grp, dh = q.shape
    ps = k_pool.shape[2]
    dv = v_pool.shape[-1]
    pages = table_rows.shape[1]
    quant = k_scale is not None
    data_map, scale_map = _page_maps(pages, ps, prefill=False)

    def fixed(bi, pi, tbl, lens):
        return (bi, 0, 0, 0)

    in_specs = [
        pl.BlockSpec((1, hkv, grp, dh), fixed),
        pl.BlockSpec((1, hkv, ps, dh), data_map),
        pl.BlockSpec((1, hkv, ps, dv), data_map),
    ]
    operands = [q, k_pool, v_pool]
    if quant:
        in_specs += [pl.BlockSpec((1, hkv, ps), scale_map)] * 2
        operands += [k_scale, v_scale]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, pages),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, hkv, grp, dv), fixed),
        scratch_shapes=_state_scratch(hkv, grp, dv),
    )
    return pl.pallas_call(
        functools.partial(
            _gqa_page_kernel, page_size=ps, n_pages=pages, scale=sm_scale,
            quant=quant,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, grp, dv), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(table_rows.reshape(-1).astype(jnp.int32), lengths.astype(jnp.int32),
      *operands)


# ============================================================== MLA decode ==
def _mla_kernel(tbl_ref, len_ref, qlat_ref, qpe_ref, ckv_ref, kpe_ref, *rest,
                page_size: int, n_pages: int, scale: float, quant: bool):
    if quant:
        cs_ref, ps_ref, o_ref, m_ref, l_ref, acc_ref = rest
    else:
        o_ref, m_ref, l_ref, acc_ref = rest
    b = pl.program_id(0)
    p = pl.program_id(1)

    @pl.when(p == 0)
    def _init():
        _init_state(m_ref, l_ref, acc_ref)

    length = len_ref[b]
    page_start = p * page_size

    @pl.when(page_start < length)
    def _compute():
        ckv = ckv_ref[0, 0]                            # [PS, r]
        s_lat = _scores(qlat_ref[0], ckv)              # [H, PS]
        s_pe = _scores(qpe_ref[0], kpe_ref[0, 0])
        if quant:
            s_lat = s_lat * cs_ref[0]
            s_pe = s_pe * ps_ref[0]
        s = (s_lat + s_pe) * scale
        pos = page_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        # o_lat = Σ p·(s_j·ckv_j) = (p ⊙ s) @ ckv_int8
        _softmax_step(m_ref, l_ref, acc_ref, 0, s, pos < length, ckv,
                      cs_ref[0] if quant else None)

    @pl.when(p == n_pages - 1)
    def _out():
        o_ref[0] = acc_ref[0] / jnp.maximum(l_ref[0], 1e-30)


@functools.partial(jax.jit, static_argnames=("sm_scale", "interpret"))
def mla_paged_attention(
    q_lat: jax.Array,           # [B, H, r] absorbed query (q_nope · w_k)
    q_pe: jax.Array,            # [B, H, dr] rope query
    ckv_pool: jax.Array,        # [NP, 1, PS, r] latent pool (bf16/f32 or int8)
    kpe_pool: jax.Array,        # [NP, 1, PS, dr]
    table_rows: jax.Array,      # [B, P] int32
    lengths: jax.Array,         # [B] int32 valid rows incl. this token
    ckv_scale: jax.Array | None = None,  # [NP, 1, PS] f32 (int8 pools)
    kpe_scale: jax.Array | None = None,
    *,
    sm_scale: float,
    interpret: bool = False,
) -> jax.Array:                 # [B, H, r] f32 latent output
    b, h, r = q_lat.shape
    dr = q_pe.shape[-1]
    ps = ckv_pool.shape[2]
    pages = table_rows.shape[1]
    quant = ckv_scale is not None
    data_map, scale_map = _page_maps(pages, ps, prefill=False)

    in_specs = [
        pl.BlockSpec((1, h, r), lambda bi, pi, tbl, lens: (bi, 0, 0)),
        pl.BlockSpec((1, h, dr), lambda bi, pi, tbl, lens: (bi, 0, 0)),
        pl.BlockSpec((1, 1, ps, r), data_map),
        pl.BlockSpec((1, 1, ps, dr), data_map),
    ]
    operands = [q_lat, q_pe, ckv_pool, kpe_pool]
    if quant:
        in_specs += [pl.BlockSpec((1, 1, ps), scale_map)] * 2
        operands += [ckv_scale, kpe_scale]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, pages),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, h, r), lambda bi, pi, tbl, lens: (bi, 0, 0)),
        scratch_shapes=_state_scratch(1, h, r),
    )
    return pl.pallas_call(
        functools.partial(
            _mla_kernel, page_size=ps, n_pages=pages, scale=sm_scale,
            quant=quant,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, r), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(table_rows.reshape(-1).astype(jnp.int32), lengths.astype(jnp.int32),
      *operands)


# ================================================== chunked prefill (GQA) ==
def _gqa_prefill_kernel(tbl_ref, pfx_ref, cln_ref, q_ref, ksuf_ref, vsuf_ref,
                        k_ref, v_ref, *rest, page_size: int, n_pages: int,
                        grp: int, block_rows: int, scale: float, quant: bool):
    if quant:
        ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref = rest
    else:
        o_ref, m_ref, l_ref, acc_ref = rest
    b = pl.program_id(0)
    qi = pl.program_id(1)
    p = pl.program_id(2)
    hkv = q_ref.shape[1]

    @pl.when(p == 0)
    def _init():
        _init_state(m_ref, l_ref, acc_ref)

    prefix = pfx_ref[b]
    chunk = cln_ref[b]

    # grid steps 0..P-1: the cached prefix, one pool page per step — every
    # chunk query sits at position >= prefix, so the only mask is raggedness
    @pl.when((p < n_pages) & (p * page_size < prefix))
    def _pages():
        for h in range(hkv):
            s = _scores(q_ref[0, h], k_ref[0, h]) * scale     # [bq, PS]
            if quant:
                s = s * ks_ref[0, h:h + 1, :]
            pos = p * page_size + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1)
            _softmax_step(m_ref, l_ref, acc_ref, h, s, pos < prefix,
                          v_ref[0, h],
                          vs_ref[0, h:h + 1, :] if quant else None)

    # final grid step: the chunk attends its own raw-fp K/V (the rows being
    # written this step) with a causal-within-chunk mask — no int8 scale, so
    # cold/warm chunks keep the slab-prefill numerics bit-for-bit.  Row r is
    # token r // grp, so key j is visible iff j·grp <= r.
    @pl.when(p == n_pages)
    def _suffix():
        for h in range(hkv):
            s = _scores(q_ref[0, h], ksuf_ref[0, h]) * scale  # [bq, T]
            row = qi * block_rows + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 0)
            j = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            _softmax_step(m_ref, l_ref, acc_ref, h, s,
                          (j * grp <= row) & (j < chunk), vsuf_ref[0, h])
        _flush(o_ref, l_ref, acc_ref)


@functools.partial(jax.jit, static_argnames=("sm_scale", "interpret"))
def gqa_paged_prefill(
    q: jax.Array,               # [B, T, Hkv, grp, Dh] chunk queries
    k_suf: jax.Array,           # [B, T, Hkv, Dh] raw chunk keys (pre-quant)
    v_suf: jax.Array,           # [B, T, Hkv, Dv]
    k_pool: jax.Array,          # [NP, Hkv, PS, Dh] (bf16/f32 or int8)
    v_pool: jax.Array,          # [NP, Hkv, PS, Dv]
    table_rows: jax.Array,      # [B, P] int32 pool page per logical page
    prefix_len: jax.Array,      # [B] int32 tokens already in the pages
    chunk_len: jax.Array,       # [B] int32 valid rows of this chunk (<= T)
    k_scale: jax.Array | None = None,   # [NP, Hkv, PS] f32 (int8 pools)
    v_scale: jax.Array | None = None,
    *,
    sm_scale: float,
    interpret: bool = False,
) -> jax.Array:                 # [B, T, Hkv, grp, Dv] f32
    """Chunked-prefill attention straight off the paged pools.

    Grid ``(B, T·grp/bq, P+1)``: steps ``0..P-1`` DMA prefix pages by block
    table (dead pages clamp to the last live one, eliding the copy — same
    contract as the decode grid); the final step attends the chunk's own
    raw-fp suffix K/V with a causal mask ``j <= t`` and flushes.  Every
    query row ``t`` sits at true position ``prefix_len[b] + t``, which is
    >= any prefix position, so prefix steps need no causal term.  Queries
    and suffix K/V are moved head-major (``[B, Hkv, rows, D]``) so each KV
    head's rows are one contiguous ``(rows, D)`` tile.
    """
    b, t, hkv, grp, dh = q.shape
    ps = k_pool.shape[2]
    dv = v_pool.shape[-1]
    pages = table_rows.shape[1]
    quant = k_scale is not None
    rows = t * grp
    bq = _row_block(rows)
    qh = q.transpose(0, 2, 1, 3, 4).reshape(b, hkv, rows, dh)
    kh = k_suf.transpose(0, 2, 1, 3)
    vh = v_suf.transpose(0, 2, 1, 3)
    data_map, scale_map = _page_maps(pages, ps, prefill=True)

    def fixed(bi, qi, pi, tbl, pfx, cln):
        return (bi, 0, 0, 0)

    in_specs = [
        pl.BlockSpec((1, hkv, bq, dh),
                     lambda bi, qi, pi, tbl, pfx, cln: (bi, 0, qi, 0)),
        pl.BlockSpec((1, hkv, t, dh), fixed),
        pl.BlockSpec((1, hkv, t, dv), fixed),
        pl.BlockSpec((1, hkv, ps, dh), data_map),
        pl.BlockSpec((1, hkv, ps, dv), data_map),
    ]
    operands = [qh, kh, vh, k_pool, v_pool]
    if quant:
        in_specs += [pl.BlockSpec((1, hkv, ps), scale_map)] * 2
        operands += [k_scale, v_scale]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, rows // bq, pages + 1),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            (1, hkv, bq, dv),
            lambda bi, qi, pi, tbl, pfx, cln: (bi, 0, qi, 0)),
        scratch_shapes=_state_scratch(hkv, bq, dv),
    )
    out = pl.pallas_call(
        functools.partial(
            _gqa_prefill_kernel, page_size=ps, n_pages=pages, grp=grp,
            block_rows=bq, scale=sm_scale, quant=quant,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, rows, dv), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(table_rows.reshape(-1).astype(jnp.int32), prefix_len.astype(jnp.int32),
      chunk_len.astype(jnp.int32), *operands)
    return out.reshape(b, hkv, t, grp, dv).transpose(0, 2, 1, 3, 4)


# ================================================== chunked prefill (MLA) ==
def _mla_prefill_kernel(tbl_ref, pfx_ref, cln_ref, qlat_ref, qpe_ref,
                        csuf_ref, psuf_ref, ckv_ref, kpe_ref, *rest,
                        page_size: int, n_pages: int, heads: int,
                        block_rows: int, scale: float, quant: bool):
    if quant:
        cs_ref, pscl_ref, o_ref, m_ref, l_ref, acc_ref = rest
    else:
        o_ref, m_ref, l_ref, acc_ref = rest
    b = pl.program_id(0)
    qi = pl.program_id(1)
    p = pl.program_id(2)

    @pl.when(p == 0)
    def _init():
        _init_state(m_ref, l_ref, acc_ref)

    prefix = pfx_ref[b]
    chunk = cln_ref[b]

    @pl.when((p < n_pages) & (p * page_size < prefix))
    def _pages():
        ckv = ckv_ref[0, 0]                             # [PS, r]
        s_lat = _scores(qlat_ref[0], ckv)               # [bq, PS]
        s_pe = _scores(qpe_ref[0], kpe_ref[0, 0])
        if quant:
            s_lat = s_lat * cs_ref[0]
            s_pe = s_pe * pscl_ref[0]
        s = (s_lat + s_pe) * scale
        pos = p * page_size + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        _softmax_step(m_ref, l_ref, acc_ref, 0, s, pos < prefix, ckv,
                      cs_ref[0] if quant else None)

    @pl.when(p == n_pages)
    def _suffix():
        ckv_s = csuf_ref[0]                             # [T, r] raw latent
        s = (_scores(qlat_ref[0], ckv_s)
             + _scores(qpe_ref[0], psuf_ref[0])) * scale  # [bq, T]
        row = qi * block_rows + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        j = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        _softmax_step(m_ref, l_ref, acc_ref, 0, s,
                      (j * heads <= row) & (j < chunk), ckv_s)
        o_ref[0] = acc_ref[0] / jnp.maximum(l_ref[0], 1e-30)


@functools.partial(jax.jit, static_argnames=("sm_scale", "interpret"))
def mla_paged_prefill(
    q_lat: jax.Array,           # [B, T, H, r] absorbed chunk queries
    q_pe: jax.Array,            # [B, T, H, dr]
    ckv_suf: jax.Array,         # [B, T, r] raw chunk latent (pre-quant)
    kpe_suf: jax.Array,         # [B, T, dr]
    ckv_pool: jax.Array,        # [NP, 1, PS, r] (bf16/f32 or int8)
    kpe_pool: jax.Array,        # [NP, 1, PS, dr]
    table_rows: jax.Array,      # [B, P] int32
    prefix_len: jax.Array,      # [B] int32 tokens already in the pages
    chunk_len: jax.Array,       # [B] int32 valid rows of this chunk (<= T)
    ckv_scale: jax.Array | None = None,  # [NP, 1, PS] f32 (int8 pools)
    kpe_scale: jax.Array | None = None,
    *,
    sm_scale: float,
    interpret: bool = False,
) -> jax.Array:                 # [B, T, H, r] f32 latent output
    """MLA chunked prefill in absorbed form, same grid story as GQA —
    ``(B, T·H/bq, P+1)``, latent scores cover all H heads per page step."""
    b, t, h, r = q_lat.shape
    dr = q_pe.shape[-1]
    ps = ckv_pool.shape[2]
    pages = table_rows.shape[1]
    quant = ckv_scale is not None
    rows = t * h
    bq = _row_block(rows)
    data_map, scale_map = _page_maps(pages, ps, prefill=True)

    def q_map(bi, qi, pi, tbl, pfx, cln):
        return (bi, qi, 0)

    def fixed(bi, qi, pi, tbl, pfx, cln):
        return (bi, 0, 0)

    in_specs = [
        pl.BlockSpec((1, bq, r), q_map),
        pl.BlockSpec((1, bq, dr), q_map),
        pl.BlockSpec((1, t, r), fixed),
        pl.BlockSpec((1, t, dr), fixed),
        pl.BlockSpec((1, 1, ps, r), data_map),
        pl.BlockSpec((1, 1, ps, dr), data_map),
    ]
    operands = [q_lat.reshape(b, rows, r), q_pe.reshape(b, rows, dr),
                ckv_suf, kpe_suf, ckv_pool, kpe_pool]
    if quant:
        in_specs += [pl.BlockSpec((1, 1, ps), scale_map)] * 2
        operands += [ckv_scale, kpe_scale]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, rows // bq, pages + 1),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, bq, r), q_map),
        scratch_shapes=_state_scratch(1, bq, r),
    )
    out = pl.pallas_call(
        functools.partial(
            _mla_prefill_kernel, page_size=ps, n_pages=pages, heads=h,
            block_rows=bq, scale=sm_scale, quant=quant,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, rows, r), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(table_rows.reshape(-1).astype(jnp.int32), prefix_len.astype(jnp.int32),
      chunk_len.astype(jnp.int32), *operands)
    return out.reshape(b, t, h, r)
