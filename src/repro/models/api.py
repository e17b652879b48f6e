"""Family-dispatching facade over the model zoo.

Gives every arch the same five entry points, so the trainer, serving engine,
and dry-run never branch on family:

  init_model(key, cfg)                          -> params
  loss_fn(params, batch, cfg, ...)              -> scalar loss
  forward_fn(params, batch, cfg, ...)           -> logits
  prefill_fn(params, batch, cfg, smax, ...)     -> (logits, cache)
  decode_fn(params, batch, cache, cfg, ...)     -> (logits, cache)

plus ``input_specs(cfg, shape)`` returning ShapeDtypeStruct stand-ins for the
dry-run (never allocates), and ``init_decode_cache`` / ``cache_specs``.

Batch dicts:
  train   {tokens, labels[, frames][, embeds]}
  prefill {tokens[, frames][, embeds]}
  decode  {token, position}
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, ShapeConfig
from repro.models import lm as LM
from repro.models import whisper as W

VLM_PATCHES = 256  # stub: fixed vision-prefix length for qwen2-vl


def init_model(key, cfg: ModelConfig):
    if cfg.encdec:
        return W.init_whisper(key, cfg)
    return LM.init_lm(key, cfg)


def _lm_kw(batch):
    kw = {}
    if "embeds" in batch:
        kw["embeds"] = batch["embeds"]
    return kw


def loss_fn(params, batch: Dict[str, Any], cfg: ModelConfig, *,
            backend: str = "auto", remat: bool = False) -> jax.Array:
    if cfg.encdec:
        return W.whisper_loss(
            params, batch["frames"], batch["tokens"], batch["labels"], cfg,
            backend=backend, remat=remat,
        )
    return LM.lm_loss(
        params, batch["tokens"], batch["labels"], cfg, backend=backend,
        remat=remat, **_lm_kw(batch),
    )


def forward_fn(params, batch, cfg: ModelConfig, *, backend: str = "auto"):
    if cfg.encdec:
        return W.whisper_forward(params, batch["frames"], batch["tokens"], cfg,
                                 backend=backend)
    logits, _ = LM.lm_forward(params, batch["tokens"], cfg, backend=backend,
                              **_lm_kw(batch))
    return logits


def prefill_fn(params, batch, cfg: ModelConfig, smax: int, *,
               backend: str = "auto", last_idx=None, raw_cache: bool = False):
    if cfg.encdec:
        if last_idx is not None or raw_cache:
            raise NotImplementedError("bucketed/raw prefill is decoder-only")
        return W.whisper_prefill(params, batch["frames"], batch["tokens"], cfg,
                                 smax, backend=backend)
    return LM.lm_prefill(params, batch["tokens"], cfg, smax, backend=backend,
                         last_idx=last_idx, raw_cache=raw_cache,
                         **_lm_kw(batch))


def decode_fn(params, batch, cache, cfg: ModelConfig, *,
              backend: str = "auto"):
    if cfg.encdec:
        return W.whisper_decode(params, batch["token"], cache,
                                batch["position"], cfg, backend=backend)
    return LM.lm_decode(params, batch["token"], cache, batch["position"], cfg,
                        backend=backend)


def init_decode_cache(cfg: ModelConfig, batch: int, smax: int, enc_len: int = 0):
    if cfg.encdec:
        return W.init_whisper_cache(cfg, batch, smax, enc_len or smax)
    return LM.init_cache(cfg, batch, smax)


# ---------------------------------------------------------- paged serving ---
# State-leaf kinds a slot can own (see serving/ — the engine generalizes
# "slot state" beyond KV pages):
#   kv_pages   read-write paged KV (attention / MLA / hybrid shared-attn)
#   fixed_rows per-layer O(1) SSM state rows, swapped alongside KV pages
#   shared_ro  refcounted read-only pages (encoder cross-attn K/V)
KV_PAGES = "kv_pages"
FIXED_ROWS = "fixed_rows"
SHARED_RO = "shared_ro"


def state_leaves(cfg: ModelConfig) -> Tuple[str, ...]:
    """Which state-leaf kinds a slot of this config owns."""
    if cfg.encdec:
        return (KV_PAGES, SHARED_RO)
    if cfg.family == "hybrid":
        return (KV_PAGES, FIXED_ROWS)
    return (KV_PAGES,)


def paged_supported(cfg: ModelConfig) -> Tuple[bool, str]:
    """Whether the paged serving cache covers this config (reason if not)."""
    return LM.paged_supported(cfg)


def init_paged_cache(cfg: ModelConfig, num_pages: int, page_size: int):
    """Per-layer KV pools ``[L, num_pages, H, page_size, ...]`` for the serving
    engine's block-table pager (``repro.serving.kv_cache``).  With
    ``cfg.kv_quant`` the pools are int8 plus per-row f32 scale pools.
    Enc-dec configs additionally carry the read-only encoder page pool
    under ``"enc"``; hybrid configs page only the shared-attention
    applications (one pool layer per group)."""
    if cfg.encdec:
        return W.init_whisper_paged_cache(cfg, num_pages, page_size)
    return LM.init_paged_cache(cfg, num_pages, page_size)


def init_fixed_state(cfg: ModelConfig, batch: int):
    """Fixed-rows state tree ``[M, B, ...]`` (slot axis second) for configs
    whose :func:`state_leaves` include ``fixed_rows``; the same
    :func:`gather_pool_rows` / :func:`scatter_pool_rows` helpers move a
    slot's rows for swap because the slot axis matches the pools' page
    axis."""
    return LM.init_fixed_state(cfg, batch)


def encode_kv_fn(params, frames, cfg: ModelConfig, *, backend: str = "auto"):
    """Encoder pass + per-decoder-layer cross K/V rows
    (``{"xk"/"xv": [L, B, T_enc, Hkv, Dh]}``) for admission into the
    read-only encoder page pool."""
    if not cfg.encdec:
        raise NotImplementedError("encoder K/V is enc-dec only")
    return W.whisper_enc_kv(params, frames, cfg, backend=backend)


def quantize_raw_paged(raw, cfg: ModelConfig):
    """Quantize raw prefill KV to match int8 page pools (no-op unless
    ``cfg.kv_quant``); run before ``serving.kv_cache.write_prefix``."""
    return LM.quantize_raw_paged(raw, cfg)


@jax.jit
def gather_pool_rows(pools, pages: jax.Array):
    """Gather whole pool pages for a slot swap-out.

    ``pools`` leaves are ``[L, num_pages, H, page_size, ...]`` (any dtype —
    fp16 K/V, MLA latents, int8 codes and their f32 ``*_s`` scale leaves
    alike); ``pages[n]`` are the pool page ids the slot owns.  Returns the
    matching ``[L, n, H, page_size, ...]`` tree of whole pages, ready for
    ``jax.device_get`` into a host swap buffer.  jit re-specializes per page
    count; preemption is rare, so the handful of traces is cheap."""
    return jax.tree.map(lambda leaf: leaf[:, pages], pools)


@functools.partial(jax.jit, donate_argnums=(0,))
def scatter_pool_rows(pools, rows, pages: jax.Array):
    """Inverse of :func:`gather_pool_rows`: write swapped-out rows back into
    freshly allocated pool pages (swap-in).  ``rows`` leaves are
    ``[L, n, H, page_size, ...]``; dtypes already match the pools bit-for-bit
    (the swap buffer stores raw codes + scales, never dequantized copies), so
    a resumed slot's cache is exactly what it was when preempted."""
    return jax.tree.map(
        lambda leaf, r: leaf.at[:, pages].set(r.astype(leaf.dtype)),
        pools, rows)


def swap_image_checksum(rows) -> int:
    """CRC-32 over a *host-materialized* swap image (the
    :func:`gather_pool_rows` tree after ``jax.device_get``).

    Folded leaf-by-leaf in ``jax.tree.leaves`` order, so the checksum covers
    every leaf of every pool kind — fp16 K/V, MLA latents, int8 codes and
    their f32 scale leaves alike.  The engine records it when the swap-out
    drain lands and re-verifies at swap-in: a mismatch means the host buffer
    was corrupted while the request waited off-device, and the victim
    re-prefills from tokens instead of resuming poisoned KV state.

    Host-only by design — call it on numpy trees; hashing a live device
    array would force a blocking transfer in the middle of the step loop.
    """
    import zlib

    import numpy as np

    crc = 0
    for leaf in jax.tree.leaves(rows):
        a = np.asarray(leaf)
        crc = zlib.crc32(a.tobytes(), crc)
    return crc


@functools.partial(jax.jit, donate_argnums=(0,))
def copy_pool_page(pools, src: jax.Array, dst: jax.Array):
    """Copy-on-write helper: duplicate pool page(s) ``src`` into ``dst``
    across every leaf (codes and scale pools alike), in place (pools
    donated).  ``src``/``dst`` are int32 scalars or matching ``[n]`` arrays
    (one dispatch covers a whole admission plan's COW set; destinations are
    distinct fresh pages, so the scatter never collides).  The pager's
    ``PagePool.cow`` picks the pages; this moves the device rows so a slot
    gets a private, bit-identical copy of a shared page before writing into
    it."""
    return jax.tree.map(lambda leaf: leaf.at[:, dst].set(leaf[:, src]), pools)


def prefill_chunk_fn(params, batch, cache, table_rows, start_len, chunk_len,
                     cfg: ModelConfig, *, backend: str = "auto",
                     last_idx=None, fixed=None, slots=None,
                     enc_table=None, enc_len=None):
    """Chunked prefill straight into the paged pools: one ``[B, T]`` prompt
    chunk per slot at logical positions ``start_len[b] + t``; KV scatters
    per chunk, attention reads every earlier token (cached prefix and prior
    chunks alike) through ``table_rows``.  Returns (per-row last-token
    logits — meaningful on final chunks — and the updated pools).

    Hybrid configs additionally take/return the fixed-rows state tree
    (``fixed`` + the bucket's ``slots``) — a 3-tuple result; enc-dec
    configs take the slot's encoder page table + valid length."""
    if cfg.encdec:
        return W.whisper_prefill_chunk(
            params, batch["tokens"], cache, start_len, chunk_len, table_rows,
            enc_table, enc_len, cfg, backend=backend, last_idx=last_idx)
    if cfg.family == "hybrid":
        return LM.hybrid_prefill_chunk(
            params, batch["tokens"], cache, fixed, slots, start_len,
            chunk_len, table_rows, cfg, backend=backend, last_idx=last_idx)
    return LM.lm_prefill_chunk(params, batch["tokens"], cache, start_len,
                               chunk_len, table_rows, cfg, backend=backend,
                               last_idx=last_idx, **_lm_kw(batch))


def decode_paged_fn(params, batch, cache, table_rows, cfg: ModelConfig, *,
                    backend: str = "auto", fixed=None, active=None,
                    enc_table=None, enc_len=None):
    """One decode step against paged pools; ``table_rows[B, P]`` maps each
    slot's logical pages to pool pages.  The attention impl is picked by
    ``cfg.paged_attn_impl`` (+ ``backend``): the fused Pallas page-gather
    kernel on TPU / interpret, the jnp dense gather as the XLA reference.

    Hybrid configs take/return the fixed-rows tree plus an ``active[B]``
    mask (rows not decoding keep their SSM state) — a 3-tuple result;
    enc-dec configs take the encoder page table + valid length."""
    if cfg.encdec:
        return W.whisper_decode_paged(
            params, batch["token"], cache, batch["position"], table_rows,
            enc_table, enc_len, cfg, backend=backend)
    if cfg.family == "hybrid":
        return LM.hybrid_decode_paged(
            params, batch["token"], cache, fixed, batch["position"],
            table_rows, active, cfg, backend=backend)
    return LM.lm_decode_paged(params, batch["token"], cache, batch["position"],
                              table_rows, cfg, backend=backend)


# --------------------------------------------------------------- dry-run ----
def input_specs(cfg: ModelConfig, shape: ShapeConfig) -> Dict[str, Any]:
    """ShapeDtypeStruct stand-ins for one (arch × shape) cell.

    ``decode`` cells: one new token against a ``seq_len`` cache (cache specs
    come from :func:`cache_specs`).  ``audio``/``vlm``: modality frontend is a
    stub — frames/patch embeddings arrive precomputed.
    """
    b, s = shape.global_batch, shape.seq_len
    i32 = jnp.int32
    dt = cfg.jdtype
    tok = lambda *sh: jax.ShapeDtypeStruct(sh, i32)
    emb = lambda *sh: jax.ShapeDtypeStruct(sh, dt)

    if shape.kind == "train":
        if cfg.encdec:
            h = s // 2
            return {"frames": emb(b, h, cfg.d_model), "tokens": tok(b, h),
                    "labels": tok(b, h)}
        spec = {"tokens": tok(b, s), "labels": tok(b, s)}
        if cfg.family == "vlm":
            spec["embeds"] = emb(b, VLM_PATCHES, cfg.d_model)
        return spec
    if shape.kind == "prefill":
        if cfg.encdec:
            h = s // 2
            return {"frames": emb(b, h, cfg.d_model), "tokens": tok(b, h)}
        spec = {"tokens": tok(b, s)}
        if cfg.family == "vlm":
            spec["embeds"] = emb(b, VLM_PATCHES, cfg.d_model)
        return spec
    if shape.kind == "decode":
        return {"token": tok(b, 1), "position": jax.ShapeDtypeStruct((b,), i32)}
    raise ValueError(shape.kind)


def cache_specs(cfg: ModelConfig, shape: ShapeConfig):
    """Decode-cache ShapeDtypeStruct tree for a decode cell (no allocation)."""
    b, s = shape.global_batch, shape.seq_len
    if cfg.encdec:
        fn = lambda: W.init_whisper_cache(cfg, b, s // 2, s // 2)
    else:
        fn = lambda: LM.init_cache(cfg, b, s)
    return jax.eval_shape(fn)


def supports_shape(cfg: ModelConfig, shape: ShapeConfig) -> Tuple[bool, str]:
    """Assignment skip rules (see DESIGN.md §Arch-applicability)."""
    if shape.name == "long_500k" and not cfg.subquadratic:
        return False, "long_500k needs sub-quadratic attention; dense-attention arch skipped per assignment"
    return True, ""
