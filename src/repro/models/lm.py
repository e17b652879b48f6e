"""Decoder-only LM assembly for all assigned families.

Layer stacks are ``lax.scan``s over stacked per-layer params (compact HLO,
fast compiles even for 88-layer models).  Three stack styles:

- homogeneous (dense / moe / ssm): one scan over ``num_layers`` blocks;
- hybrid (zamba2): scan over groups of ``attn_every`` mamba blocks followed by
  one application of a *weight-shared* attention+MLP block (per-application
  KV caches), plus an unscanned tail of remainder mamba blocks;
- enc-dec (whisper) lives in ``models/whisper.py``.

Entry points: :func:`init_lm`, :func:`lm_forward` (teacher-forced logits),
:func:`lm_loss`, :func:`lm_prefill`, :func:`lm_decode`, :func:`init_cache`.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as A
from repro.models import layers as L
from repro.models import mlp as M
from repro.models import ssm as S

Params = Dict[str, Any]

# named scopes of the paged decode and prefill-chunk steps: each op's
# metadata (``op_name`` in the compiled HLO) says whether it belongs to the
# layer stack or the head (final norm + lm_head).  Metadata only: the
# compiled ops are the same with or without them.
SCOPE_LAYERS = "lm.layers"
SCOPE_HEAD = "lm.head"


# ------------------------------------------------------------ block defs ----
def _init_mixer(key, cfg: ModelConfig):
    if cfg.mixer == "attention":
        return A.init_gqa(key, cfg)
    if cfg.mixer == "mla":
        return A.init_mla(key, cfg)
    if cfg.mixer == "mamba2":
        return S.init_mamba2(key, cfg)
    if cfg.mixer == "rwkv6":
        return S.init_rwkv6(key, cfg)
    raise ValueError(cfg.mixer)


def _init_block(key, cfg: ModelConfig, *, mixer: Optional[str] = None) -> Params:
    """One decoder block.  ``mixer`` overrides cfg.mixer (hybrid stacks)."""
    mixer = mixer or cfg.mixer
    k1, k2, k3 = jax.random.split(key, 3)
    dt = cfg.jdtype
    p: Params = {"norm1": L.init_norm(cfg.d_model, cfg.norm, dt)}
    sub = cfg.with_(mixer=mixer)
    p["mixer"] = _init_mixer(k1, sub)
    if mixer in ("attention", "mla"):
        p["norm2"] = L.init_norm(cfg.d_model, cfg.norm, dt)
        p["mlp"] = M.init_moe(k2, cfg) if cfg.moe else M.init_mlp(k2, cfg)
    elif mixer == "rwkv6":
        p["norm2"] = L.init_norm(cfg.d_model, cfg.norm, dt)
        p["mlp"] = S.init_rwkv_channel_mix(k3, cfg)
    # mamba2 blocks: mixer only (Zamba2-style), no separate MLP
    return p


def _block_forward(
    p: Params,
    x: jax.Array,
    positions: jax.Array,
    cfg: ModelConfig,
    *,
    mixer: Optional[str] = None,
    backend: str = "auto",
) -> Tuple[jax.Array, jax.Array]:
    """Full-sequence (train / prefill-no-cache) block.  Returns (x, aux)."""
    mixer = mixer or cfg.mixer
    aux = jnp.zeros((), jnp.float32)
    h = L.apply_norm(p["norm1"], x)
    if mixer == "attention":
        y, _ = A.gqa_prefill(p["mixer"], h, positions, cfg, backend=backend)
    elif mixer == "mla":
        y, _ = A.mla_prefill(p["mixer"], h, positions, cfg, backend=backend)
    elif mixer == "mamba2":
        y = S.mamba2_forward(p["mixer"], h, cfg, backend=backend)
    elif mixer == "rwkv6":
        y = S.rwkv6_forward(p["mixer"], h, cfg, backend=backend)
    else:
        raise ValueError(mixer)
    x = x + y
    if "mlp" in p:
        h2 = L.apply_norm(p["norm2"], x)
        if mixer == "rwkv6":
            h2_prev = jnp.pad(h2, ((0, 0), (1, 0), (0, 0)))[:, :-1]
            y2 = S.rwkv_channel_mix(p["mlp"], h2, h2_prev, backend=backend)
        elif cfg.moe is not None and mixer in ("attention", "mla"):
            y2, aux = M.apply_moe(p["mlp"], h2, cfg, backend=backend)
        else:
            y2 = M.apply_mlp(p["mlp"], h2, backend=backend, act=cfg.act_kernel)
        x = x + y2
    return x, aux


def _block_prefill_cache(p, x, positions, cfg, *, mixer=None, backend="auto"):
    """Like _block_forward but also returns the mixer cache/state for decode."""
    mixer = mixer or cfg.mixer
    h = L.apply_norm(p["norm1"], x)
    if mixer == "attention":
        y, cache = A.gqa_prefill(p["mixer"], h, positions, cfg, backend=backend)
    elif mixer == "mla":
        y, cache = A.mla_prefill(p["mixer"], h, positions, cfg, backend=backend)
    elif mixer == "mamba2":
        y, cache = S.mamba2_forward(
            p["mixer"], h, cfg, backend=backend, return_state=True
        )
    elif mixer == "rwkv6":
        y, cache = S.rwkv6_forward(
            p["mixer"], h, cfg, backend=backend, return_state=True
        )
    else:
        raise ValueError(mixer)
    x = x + y
    if "mlp" in p:
        h2 = L.apply_norm(p["norm2"], x)
        if mixer == "rwkv6":
            h2_prev = jnp.pad(h2, ((0, 0), (1, 0), (0, 0)))[:, :-1]
            y2 = S.rwkv_channel_mix(p["mlp"], h2, h2_prev, backend=backend)
            cache = dict(cache, ffn_prev=h2[:, -1])
        elif cfg.moe is not None and mixer in ("attention", "mla"):
            y2, _ = M.apply_moe(p["mlp"], h2, cfg, backend=backend)
        else:
            y2 = M.apply_mlp(p["mlp"], h2, backend=backend, act=cfg.act_kernel)
        x = x + y2
    return x, cache


def _block_decode(p, x, positions, cache, cfg, *, mixer=None, backend="auto"):
    mixer = mixer or cfg.mixer
    h = L.apply_norm(p["norm1"], x)
    if mixer == "attention":
        y, cache = A.gqa_decode(p["mixer"], h, positions, cache, cfg, backend=backend)
    elif mixer == "mla":
        y, cache = A.mla_decode(p["mixer"], h, positions, cache, cfg, backend=backend)
    elif mixer == "mamba2":
        y, cache = S.mamba2_decode(p["mixer"], h, cache, cfg, backend=backend)
    elif mixer == "rwkv6":
        ffn_prev = cache.get("ffn_prev")
        y, tcache = S.rwkv6_decode(
            p["mixer"], h, {k: cache[k] for k in ("wkv", "x_prev")}, cfg, backend=backend
        )
        cache = dict(tcache, ffn_prev=ffn_prev)
    else:
        raise ValueError(mixer)
    x = x + y
    if "mlp" in p:
        h2 = L.apply_norm(p["norm2"], x)
        if mixer == "rwkv6":
            y2 = S.rwkv_channel_mix(
                p["mlp"], h2, cache["ffn_prev"][:, None, :], backend=backend
            )
            cache = dict(cache, ffn_prev=h2[:, 0])
        elif cfg.moe is not None:
            y2, _ = M.apply_moe(p["mlp"], h2, cfg, backend=backend)
        else:
            y2 = M.apply_mlp(p["mlp"], h2, backend=backend, act=cfg.act_kernel)
        x = x + y2
    return x, cache


def _block_decode_paged(p, x, rope_pos, write_pos, pool, table_rows, cfg,
                        *, mixer=None, backend="auto"):
    """Attention-mixer block decode against a paged KV pool (see
    ``models/attention.py`` for the page-table convention; in a layer scan
    ``table_rows`` is already offset to this layer's pages).  ``mixer``
    overrides ``cfg.mixer`` (the hybrid stack's weight-shared attention)."""
    mixer = mixer or cfg.mixer
    h = L.apply_norm(p["norm1"], x)
    if mixer == "attention":
        y, pool = A.gqa_decode_paged(
            p["mixer"], h, rope_pos, pool, table_rows, write_pos, cfg,
            backend=backend)
    elif mixer == "mla":
        y, pool = A.mla_decode_paged(
            p["mixer"], h, rope_pos, pool, table_rows, write_pos, cfg,
            backend=backend)
    else:
        raise ValueError(f"paged decode needs an attention mixer, got {mixer}")
    x = x + y
    h2 = L.apply_norm(p["norm2"], x)
    if cfg.moe is not None:
        y2, _ = M.apply_moe(p["mlp"], h2, cfg, backend=backend)
    else:
        y2 = M.apply_mlp(p["mlp"], h2, backend=backend, act=cfg.act_kernel)
    return x + y2, pool


def _block_prefill_chunk(p, x, start_len, chunk_len, pool, table_rows, cfg,
                         *, mixer=None, backend="auto", page_base=0):
    """Attention-mixer block chunked prefill straight against a paged KV pool
    (see ``models/attention.py`` for the chunk contract; in a layer scan
    ``table_rows`` is already offset by ``page_base``, this layer's trash
    page).  ``mixer`` overrides ``cfg.mixer`` (the hybrid stack's
    weight-shared attention).  Returns (x, updated pool)."""
    mixer = mixer or cfg.mixer
    h = L.apply_norm(p["norm1"], x)
    if mixer == "attention":
        y, pool = A.gqa_prefill_chunk(
            p["mixer"], h, pool, table_rows, start_len, chunk_len, cfg,
            backend=backend, page_base=page_base)
    elif mixer == "mla":
        y, pool = A.mla_prefill_chunk(
            p["mixer"], h, pool, table_rows, start_len, chunk_len, cfg,
            backend=backend, page_base=page_base)
    else:
        raise ValueError(f"paged prefill needs an attention mixer, got {mixer}")
    x = x + y
    h2 = L.apply_norm(p["norm2"], x)
    if cfg.moe is not None:
        y2, _ = M.apply_moe(p["mlp"], h2, cfg, backend=backend)
    else:
        y2 = M.apply_mlp(p["mlp"], h2, backend=backend, act=cfg.act_kernel)
    return x + y2, pool


def lm_prefill_chunk(
    p: Params,
    tokens: jax.Array,            # [B, T] int32 chunk tokens (right-padded)
    cache: Any,                   # pools from init_paged_cache
    start_len: jax.Array,         # [B] int32 tokens already in the pages
    chunk_len: jax.Array,         # [B] int32 valid rows of this chunk (<= T)
    table_rows: jax.Array,        # [B, P] int32 page table
    cfg: ModelConfig,
    *,
    backend: str = "auto",
    last_idx=None,
    embeds: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Any]:
    """Chunked prefill: process one ``[B, T]`` prompt chunk per slot, KV
    scattered straight into the paged pools, attention reading every earlier
    token (cached prefix pages and prior chunks alike) through the page
    table.  Row ``b``'s chunk token ``t`` sits at logical position
    ``start_len[b] + t``.  Returns per-row logits gathered at ``last_idx``
    (only meaningful on a prompt's final chunk) and the updated pools.  The
    pools ride the layer scan's carry as one ``[L·NP, ...]`` view, layer
    ``l`` at page base ``l·NP`` (:func:`_scan_paged_layers`), exactly like
    :func:`lm_decode_paged`.
    """
    b, t = tokens.shape[:2]
    x = _embed_in(p, tokens, cfg, embeds)

    def body(x, lp, pools, base):
        x, pools = _block_prefill_chunk(
            lp, x, start_len, chunk_len, pools, table_rows + base, cfg,
            backend=backend, page_base=base)
        return x, pools, None

    with jax.named_scope(SCOPE_LAYERS):
        x, pools, _ = _scan_paged_layers(body, x, p["layers"],
                                         cache["layers"])
    idx = last_idx if last_idx is not None else jnp.full((b,), t - 1, jnp.int32)
    x_last = x[jnp.arange(b), idx][:, None]
    with jax.named_scope(SCOPE_HEAD):
        logits = _lm_head(p, x_last, cfg, backend)[:, 0]
    return logits, {"layers": pools}


def _scan_paged_layers(body, x, xs, pools):
    """Scan ``body`` over the layer axis of ``xs`` with the stacked KV pools
    ``[L, NP, ...]`` in the carry.

    Each pool leaf rides the scan as one flat ``[L·NP, ...]`` view (merging
    the two leading axes is a bitcast) in which layer ``l`` owns pages
    ``[l·NP, (l+1)·NP)``, its trash page first.  ``body(x, xs_l, pools,
    base)`` returns ``(x, pools, ys_l)`` and addresses layer ``l``'s pages
    through ``table_rows + base`` with ``base = l·NP``, so its writes update
    the one carried buffer in place.  (As the scan's xs/ys, the pools would
    be sliced out per layer, written back into a new stack, and the whole
    stack copied again after the scan.)  Returns ``(x, pools, ys)`` with the
    pools back in their ``[L, NP, ...]`` shape."""
    n_layers, n_pages = jax.tree.leaves(pools)[0].shape[:2]
    flat = jax.tree.map(
        lambda a: a.reshape(n_layers * n_pages, *a.shape[2:]), pools)

    def step(carry, inp):
        x, flat = carry
        layer, xs_l = inp
        x, flat, ys = body(x, xs_l, flat, layer * n_pages)
        return (x, flat), ys

    (x, flat), ys = jax.lax.scan(
        step, (x, flat), (jnp.arange(n_layers, dtype=jnp.int32), xs))
    return x, jax.tree.map(lambda f, a: f.reshape(a.shape), flat, pools), ys


# ------------------------------------------------------------- LM wiring ----
def _hybrid_layout(cfg: ModelConfig) -> Tuple[int, int, int]:
    k = cfg.hybrid.attn_every
    groups = cfg.num_layers // k
    tail = cfg.num_layers - groups * k
    return groups, k, tail


def init_lm(key, cfg: ModelConfig) -> Params:
    ks = jax.random.split(key, 8)
    dt = cfg.jdtype
    p: Params = {
        "embed": L.init_embedding(ks[0], cfg.vocab_size, cfg.d_model, dt),
        "final_norm": L.init_norm(cfg.d_model, cfg.norm, dt),
    }
    if not cfg.tie_embeddings:
        p["lm_head"] = L.init_linear(ks[1], cfg.d_model, cfg.vocab_size, dt)

    def stack(init_fn, n, base_key):
        leaves = [init_fn(jax.random.fold_in(base_key, i)) for i in range(n)]
        return jax.tree.map(lambda *xs: jnp.stack(xs), *leaves)

    if cfg.family == "hybrid":
        g, k, tail = _hybrid_layout(cfg)
        p["groups"] = stack(
            lambda kk: stack(
                lambda k2: _init_block(k2, cfg, mixer="mamba2"), k, kk
            ),
            g,
            ks[2],
        )
        p["shared"] = _init_block(ks[3], cfg.with_(moe=None), mixer="attention")
        if tail:
            p["tail"] = stack(
                lambda kk: _init_block(kk, cfg, mixer="mamba2"), tail, ks[4]
            )
    else:
        p["layers"] = stack(lambda kk: _init_block(kk, cfg), cfg.num_layers, ks[2])
    return p


def _embed_in(p, tokens, cfg, embeds):
    x = L.apply_embedding(p["embed"], tokens)
    if embeds is not None:
        # modality stub: precomputed frame/patch embeddings added at the
        # (fixed) prefix positions — tokens there are pad (0)
        n = embeds.shape[1]
        x = x.at[:, :n, :].add(embeds.astype(x.dtype))
    return x


def _lm_head(p, x, cfg, backend):
    x = L.apply_norm(p["final_norm"], x)
    if cfg.tie_embeddings:
        return L.logits_from_embedding(p["embed"], x)
    return jnp.dot(
        x, p["lm_head"]["w"].astype(x.dtype), preferred_element_type=jnp.float32
    )


def _default_positions(cfg, b, t, positions):
    if positions is not None:
        return positions
    pos = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32)[None], (b, t))
    return pos


def lm_forward(
    p: Params,
    tokens: jax.Array,            # [B, T] int32
    cfg: ModelConfig,
    *,
    positions: Optional[jax.Array] = None,
    embeds: Optional[jax.Array] = None,
    backend: str = "auto",
    remat: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Teacher-forced forward.  Returns (logits [B,T,V], moe_aux)."""
    b, t = tokens.shape[:2]
    pos = _default_positions(cfg, b, t, positions)
    x = _embed_in(p, tokens, cfg, embeds)

    if cfg.family == "hybrid":
        shared = p["shared"]

        def mamba_body(carry, lp):
            x = carry
            x, _ = _block_forward(lp, x, pos, cfg, mixer="mamba2", backend=backend)
            return x, None

        mamba_body_ = jax.checkpoint(mamba_body) if remat else mamba_body

        def group_body(carry, gp):
            x = carry
            x, _ = jax.lax.scan(mamba_body_, x, gp)
            x, _ = _block_forward(
                shared, x, pos, cfg.with_(moe=None), mixer="attention",
                backend=backend,
            )
            return x, None

        group_body_ = jax.checkpoint(group_body) if remat else group_body
        x, _ = jax.lax.scan(group_body_, x, p["groups"])
        if "tail" in p:
            x, _ = jax.lax.scan(mamba_body_, x, p["tail"])
        aux = jnp.zeros((), jnp.float32)
    else:
        def body(carry, lp):
            x, aux = carry
            x, a = _block_forward(lp, x, pos, cfg, backend=backend)
            return (x, aux + a), None

        body_ = jax.checkpoint(body) if remat else body
        (x, aux), _ = jax.lax.scan(body_, (x, jnp.zeros((), jnp.float32)), p["layers"])

    return _lm_head(p, x, cfg, backend), aux


def lm_loss(
    p: Params,
    tokens: jax.Array,
    labels: jax.Array,
    cfg: ModelConfig,
    *,
    positions=None,
    embeds=None,
    backend: str = "auto",
    remat: bool = False,
    aux_weight: float = 0.01,
) -> jax.Array:
    logits, aux = lm_forward(
        p, tokens, cfg, positions=positions, embeds=embeds, backend=backend,
        remat=remat,
    )
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    # one-hot-reduce (NOT take_along_axis): with vocab-sharded logits this
    # lowers to a local masked reduce + tiny [B,S] psum instead of an
    # all-gather/all-reduce of the full logits tensor under GSPMD
    v = logits.shape[-1]
    onehot = jax.nn.one_hot(labels, v, dtype=logits.dtype)
    gold = jnp.sum(logits * onehot, axis=-1)
    nll = (logz - gold).mean()
    return nll + aux_weight * aux


# ------------------------------------------------------- prefill / decode ---
def init_cache(cfg: ModelConfig, batch: int, smax: int) -> Any:
    """Decode cache pytree (stacked over layers)."""
    def one_attn():
        return (
            A.init_mla_cache(cfg, batch, smax)
            if cfg.mixer == "mla"
            else A.init_gqa_cache(cfg, batch, smax)
        )

    def one_ssm(mixer):
        if mixer == "mamba2":
            return S.init_mamba2_state(cfg, batch)
        st = S.init_rwkv6_state(cfg, batch)
        st["ffn_prev"] = jnp.zeros((batch, cfg.d_model), cfg.jdtype)
        return st

    def stackn(mk, n):
        return jax.tree.map(lambda *xs: jnp.stack(xs), *[mk() for _ in range(n)])

    if cfg.family == "hybrid":
        g, k, tail = _hybrid_layout(cfg)
        return {
            "groups": stackn(lambda: stackn(lambda: one_ssm("mamba2"), k), g),
            "shared": stackn(lambda: A.init_gqa_cache(cfg, batch, smax), g),
            "tail": stackn(lambda: one_ssm("mamba2"), tail) if tail else None,
        }
    if cfg.mixer in ("attention", "mla"):
        return {"layers": stackn(one_attn, cfg.num_layers)}
    return {"layers": stackn(lambda: one_ssm(cfg.mixer), cfg.num_layers)}


def paged_supported(cfg: ModelConfig) -> Tuple[bool, str]:
    """Whether the paged serving engine covers this config.

    Three state-leaf layouts are served: pure KV-page stacks (attention /
    MLA decoders), hybrid stacks (KV pages for the weight-shared attention
    applications + fixed SSM state rows swapped alongside them), and
    enc-dec (KV pages for decoder self-attention + read-only encoder
    pages for cross-attention)."""
    if cfg.encdec:
        return True, ""
    if cfg.family == "hybrid":
        return True, ""
    if cfg.mixer not in ("attention", "mla"):
        return False, f"{cfg.mixer} state is O(1) per slot; paging buys nothing"
    return True, ""


def init_paged_cache(cfg: ModelConfig, num_pages: int, page_size: int) -> Any:
    """Per-layer paged KV pools (stacked over layers, shared across slots).

    Hybrid stacks page only the shared-attention applications (one pool
    layer per group); their SSM state lives in the separate fixed-rows tree
    from :func:`init_fixed_state`.  Enc-dec pools live in
    ``models/whisper.py`` (dispatched by ``models/api.py``)."""
    ok, why = paged_supported(cfg)
    if not ok:
        raise NotImplementedError(why)
    if cfg.encdec:
        raise ValueError("enc-dec paged pools live in models/whisper.py")
    if cfg.family == "hybrid":
        g, _, _ = _hybrid_layout(cfg)
        return {"layers": jax.tree.map(
            lambda *xs: jnp.stack(xs),
            *[A.init_gqa_page_pool(cfg, num_pages, page_size)
              for _ in range(g)])}
    mk = (
        (lambda: A.init_mla_page_pool(cfg, num_pages, page_size))
        if cfg.mixer == "mla"
        else (lambda: A.init_gqa_page_pool(cfg, num_pages, page_size))
    )
    return {"layers": jax.tree.map(
        lambda *xs: jnp.stack(xs), *[mk() for _ in range(cfg.num_layers)])}


def init_fixed_state(cfg: ModelConfig, batch: int) -> Any:
    """Fixed-rows state leaves for hybrid stacks: per-layer Mamba2 state with
    the slot axis SECOND (``[M, B, ...]``) so the pool-row swap helpers
    (``api.gather_pool_rows`` / ``api.scatter_pool_rows``, axis 1) move a
    slot's rows without reshaping.  Layer order is group-major (the ``g*k``
    grouped mamba layers, then the tail)."""
    if cfg.family != "hybrid":
        raise ValueError(f"fixed-rows state is hybrid-only, got {cfg.family}")
    m = cfg.num_layers
    d_inner, hp, nh, n = S.mamba_dims(cfg)
    km1 = cfg.conv_kernel - 1
    return {
        "h": jnp.zeros((m, batch, nh, hp, n), jnp.float32),
        "conv_x": jnp.zeros((m, batch, km1, d_inner), cfg.jdtype),
        "conv_bc": jnp.zeros((m, batch, km1, 2 * n), cfg.jdtype),
    }


def quantize_raw_paged(raw: Any, cfg: ModelConfig) -> Any:
    """Quantize raw prefill KV (``{"layers": {leaf: [L, n, T, ...]}}``) to
    match the int8 page pools: every KV leaf becomes int8 codes plus a
    ``<leaf>_s`` f32 per-row scale leaf (per (layer, row, position[, head])),
    so the admission scatter (``serving.kv_cache.write_prefix``) maps 1:1
    onto the pool tree.  No-op when ``cfg.kv_quant`` is off."""
    if not cfg.kv_quant:
        return raw
    out = {}
    for name, leaf in raw["layers"].items():
        if name == "lens":
            continue
        codes, scales = A.kv_quantize_rows(leaf)
        out[name] = codes
        out[name + "_s"] = scales.astype(jnp.float32)
    return {"layers": out}


def lm_decode_paged(
    p: Params,
    token: jax.Array,             # [B, 1] int32
    cache: Any,                   # pools from init_paged_cache
    position: jax.Array,          # [B] int32 current position
    table_rows: jax.Array,        # [B, P] int32 page table
    cfg: ModelConfig,
    *,
    backend: str = "auto",
) -> Tuple[jax.Array, Any]:
    """One decode step against paged KV pools.  Returns (logits, new pools).

    The pools ride the layer scan's carry as one ``[L·NP, ...]`` view; layer
    ``l`` reads and writes its pages through ``table_rows + l·NP``
    (:func:`_scan_paged_layers`), so the step updates them in place."""
    b = token.shape[0]
    pos = position[:, None]
    if cfg.rope == "mrope":
        pos = jnp.broadcast_to(position[None, :, None], (3, b, 1))
    x = L.apply_embedding(p["embed"], token)

    def step(x, lp, pools, base):
        x, pools = _block_decode_paged(
            lp, x, pos, position, pools, table_rows + base, cfg,
            backend=backend)
        return x, pools, None

    with jax.named_scope(SCOPE_LAYERS):
        x, nst, _ = _scan_paged_layers(step, x, p["layers"], cache["layers"])
    with jax.named_scope(SCOPE_HEAD):
        logits = _lm_head(p, x, cfg, backend)[:, 0]
    return logits, {"layers": nst}


def _group_fixed(fixed, g, k):
    """Split the [M, B, ...] fixed-state tree into grouped [g, k, B, ...] and
    tail [tail, B, ...] trees (group-major layer order, tail last)."""
    grouped = jax.tree.map(lambda a: a[: g * k].reshape(g, k, *a.shape[1:]), fixed)
    tail_st = jax.tree.map(lambda a: a[g * k:], fixed)
    return grouped, tail_st


def _ungroup_fixed(grouped, tail_st, tail):
    flat = jax.tree.map(lambda a: a.reshape(-1, *a.shape[2:]), grouped)
    if tail:
        flat = jax.tree.map(
            lambda a, t_: jnp.concatenate([a, t_], axis=0), flat, tail_st)
    return flat


def hybrid_decode_paged(
    p: Params,
    token: jax.Array,             # [B, 1] int32
    cache: Any,                   # g shared-attn pools from init_paged_cache
    fixed: Any,                   # [M, B, ...] tree from init_fixed_state
    position: jax.Array,          # [B] int32 current position
    table_rows: jax.Array,        # [B, P] int32 page table
    active: jax.Array,            # [B] bool: rows actually decoding
    cfg: ModelConfig,
    *,
    backend: str = "auto",
) -> Tuple[jax.Array, Any, Any]:
    """One hybrid decode step: mamba layers update their fixed state rows,
    the weight-shared attention block hits one paged pool per group.  Rows
    with ``active=False`` keep their fixed state untouched (the trash-page
    convention masks their KV writes, but an SSM recurrence would otherwise
    corrupt a parked slot's state).  Returns (logits, pools, fixed)."""
    b = token.shape[0]
    pos = position[:, None]
    x = L.apply_embedding(p["embed"], token)
    g, k, tail = _hybrid_layout(cfg)
    grouped, tail_st = _group_fixed(fixed, g, k)
    shared = p["shared"]
    scfg = cfg.with_(moe=None)

    def mamba_step(x, inp):
        lp, st = inp
        x, st = _block_decode(lp, x, pos, st, cfg, mixer="mamba2", backend=backend)
        return x, st

    def group_step(x, inp, pools, base):
        gp, gst = inp
        x, new_gst = jax.lax.scan(mamba_step, x, (gp, gst))
        x, pools = _block_decode_paged(
            shared, x, pos, position, pools, table_rows + base, scfg,
            mixer="attention", backend=backend)
        return x, pools, new_gst

    x, npools, ngst = _scan_paged_layers(
        group_step, x, (p["groups"], grouped), cache["layers"])
    ntail = tail_st
    if tail:
        x, ntail = jax.lax.scan(mamba_step, x, (p["tail"], tail_st))
    new_fixed = _ungroup_fixed(ngst, ntail, tail)
    new_fixed = jax.tree.map(
        lambda new, old: jnp.where(
            active.reshape((1, b) + (1,) * (new.ndim - 2)), new, old),
        new_fixed, fixed)
    logits = _lm_head(p, x, cfg, backend)[:, 0]
    return logits, {"layers": npools}, new_fixed


def hybrid_prefill_chunk(
    p: Params,
    tokens: jax.Array,            # [B, T] int32 chunk tokens (right-padded)
    cache: Any,                   # g shared-attn pools
    fixed: Any,                   # [M, Bslots, ...] full fixed-state tree
    slots: jax.Array,             # [B] int32 slot ids of the bucket rows
    start_len: jax.Array,         # [B] int32 tokens already processed
    chunk_len: jax.Array,         # [B] int32 valid rows of this chunk
    table_rows: jax.Array,        # [B, P] int32 page table
    cfg: ModelConfig,
    *,
    backend: str = "auto",
    last_idx=None,
) -> Tuple[jax.Array, Any, Any]:
    """Chunked hybrid prefill: mamba layers run the chunked SSD with state-in
    (``mamba2_prefill_chunk``), the shared attention block scatters KV into
    its per-group pool.  Fixed rows are gathered at ``slots`` on the way in
    and scattered back on the way out — every bucket row is an actively
    prefilling slot, so the scatter is unconditional.
    Returns (last-chunk-token logits, pools, fixed)."""
    b, t = tokens.shape[:2]
    x = _embed_in(p, tokens, cfg, None)
    g, k, tail = _hybrid_layout(cfg)
    fx = jax.tree.map(lambda a: a[:, slots], fixed)
    grouped, tail_st = _group_fixed(fx, g, k)
    shared = p["shared"]
    scfg = cfg.with_(moe=None)

    def mamba_body(x, inp):
        lp, st = inp
        h = L.apply_norm(lp["norm1"], x)
        y, st = S.mamba2_prefill_chunk(
            lp["mixer"], h, st, chunk_len, cfg, backend=backend)
        return x + y, st

    def group_body(x, inp, pools, base):
        gp, gst = inp
        x, new_gst = jax.lax.scan(mamba_body, x, (gp, gst))
        x, pools = _block_prefill_chunk(
            shared, x, start_len, chunk_len, pools, table_rows + base, scfg,
            mixer="attention", backend=backend, page_base=base)
        return x, pools, new_gst

    x, npools, ngst = _scan_paged_layers(
        group_body, x, (p["groups"], grouped), cache["layers"])
    ntail = tail_st
    if tail:
        x, ntail = jax.lax.scan(mamba_body, x, (p["tail"], tail_st))
    new_fx = _ungroup_fixed(ngst, ntail, tail)
    new_fixed = jax.tree.map(
        lambda a, r: a.at[:, slots].set(r), fixed, new_fx)
    idx = last_idx if last_idx is not None else jnp.full((b,), t - 1, jnp.int32)
    x_last = x[jnp.arange(b), idx][:, None]
    return _lm_head(p, x_last, cfg, backend)[:, 0], {"layers": npools}, new_fixed


def lm_decode(
    p: Params,
    token: jax.Array,             # [B, 1] int32
    cache: Any,
    position: jax.Array,          # [B] int32 current position
    cfg: ModelConfig,
    *,
    backend: str = "auto",
) -> Tuple[jax.Array, Any]:
    """One decode step.  Returns (logits [B,V], new cache)."""
    b = token.shape[0]
    pos = position[:, None]
    if cfg.rope == "mrope":
        pos = jnp.broadcast_to(position[None, :, None], (3, b, 1))
    x = L.apply_embedding(p["embed"], token)

    if cfg.family == "hybrid":
        shared = p["shared"]

        def mamba_step(x, inp):
            lp, st = inp
            x, st = _block_decode(lp, x, pos, st, cfg, mixer="mamba2", backend=backend)
            return x, st

        def group_step(x, inp):
            gp, gst, sc = inp
            x, new_gst = jax.lax.scan(mamba_step, x, (gp, gst))
            x, new_sc = _block_decode(
                shared, x, pos, sc, cfg.with_(moe=None), mixer="attention",
                backend=backend,
            )
            return x, (new_gst, new_sc)

        x, (ngst, nsc) = jax.lax.scan(
            group_step, x, (p["groups"], cache["groups"], cache["shared"])
        )
        ntail = cache["tail"]
        if "tail" in p:
            x, ntail = jax.lax.scan(mamba_step, x, (p["tail"], cache["tail"]))
        new_cache = {"groups": ngst, "shared": nsc, "tail": ntail}
    else:
        def step(x, inp):
            lp, st = inp
            x, st = _block_decode(lp, x, pos, st, cfg, backend=backend)
            return x, st

        x, nst = jax.lax.scan(step, x, (p["layers"], cache["layers"]))
        new_cache = {"layers": nst}

    logits = _lm_head(p, x, cfg, backend)[:, 0]
    return logits, new_cache


def lm_prefill(
    p: Params,
    tokens: jax.Array,
    cfg: ModelConfig,
    smax: int,
    *,
    positions=None,
    embeds=None,
    backend: str = "auto",
    last_idx=None,
    raw_cache: bool = False,
) -> Tuple[jax.Array, Any]:
    """Process a prompt, building a decode cache padded to ``smax``.

    For attention archs the per-layer KV is computed in the scan and written
    into the preallocated cache; SSM/hybrid archs replay the prompt through
    the recurrent decode path chunk-free (their state is O(1)).
    Returns (last-token logits [B,V], cache).

    ``last_idx[B]``: per-row index of the real last prompt token — logits are
    gathered there instead of at ``[:, -1]``, so right-padded rows of a
    length-bucketed joint prefill sample from the correct position (causal
    masking already keeps padding out of the valid prefix's KV).
    ``raw_cache=True`` skips the ``smax`` slab: the returned attention caches
    are the raw prefix KV ``[L, B, T, ...]``, ready to be scattered into
    paged pools (no per-request slab allocation).
    """
    b, t = tokens.shape[:2]
    if (last_idx is not None or raw_cache) and cfg.family == "hybrid":
        raise NotImplementedError("bucketed/raw prefill not wired for hybrid")
    pos = _default_positions(cfg, b, t, positions)
    cache = None if raw_cache else init_cache(cfg, b, smax)
    x = _embed_in(p, tokens, cfg, embeds)

    def head_at(x):
        if last_idx is None:
            return _lm_head(p, x, cfg, backend)[:, -1]
        x_last = x[jnp.arange(b), last_idx][:, None]       # [B, 1, D]
        return _lm_head(p, x_last, cfg, backend)[:, 0]

    def pad_kv(ct, new):
        """Write freshly-built prefix cache into the smax-padded slab.

        With ``cfg.kv_quant`` the raw prefix rows are quantized per-(position,
        head) first — the same :func:`repro.models.attention.kv_quantize_rows`
        codes + scale rows the paged admission path writes
        (``quantize_raw_paged``), so the contiguous slab and the page pools
        agree bit-for-bit instead of casting f32 straight into int8."""
        upd = dict(ct)
        new = dict(new)
        if cfg.kv_quant and "k_s" in ct:
            for key in ("k", "v"):
                codes, scl = A.kv_quantize_rows(new[key])
                new[key] = codes
                new[key + "_s"] = scl
        for key in ct:
            if key == "lens":
                upd["lens"] = new["lens"]
            elif key in new and ct[key].ndim >= 2:
                upd[key] = jax.lax.dynamic_update_slice(
                    ct[key], new[key].astype(ct[key].dtype),
                    (0,) * ct[key].ndim,
                )
            elif key in new:
                upd[key] = new[key]
        return upd

    if cfg.family == "hybrid":
        shared = p["shared"]

        def mamba_body(x, inp):
            lp, st = inp
            x, new = _block_prefill_cache(lp, x, pos, cfg, mixer="mamba2", backend=backend)
            return x, new

        def group_body(x, inp):
            gp, gst, sc = inp
            x, new_gst = jax.lax.scan(mamba_body, x, (gp, gst))
            x, new_sc = _block_prefill_cache(
                shared, x, pos, cfg.with_(moe=None), mixer="attention",
                backend=backend,
            )
            return x, (new_gst, pad_kv(sc, new_sc))

        x, (ngr, nsh) = jax.lax.scan(
            group_body, x, (p["groups"], cache["groups"], cache["shared"])
        )
        ntail = cache["tail"]
        if "tail" in p:
            x, ntail = jax.lax.scan(mamba_body, x, (p["tail"], cache["tail"]))
        logits = _lm_head(p, x, cfg, backend)[:, -1]
        return logits, {"groups": ngr, "shared": nsh, "tail": ntail}

    if raw_cache:
        def body_raw(x, lp):
            x, new = _block_prefill_cache(lp, x, pos, cfg, backend=backend)
            return x, new

        x, layers_cache = jax.lax.scan(body_raw, x, p["layers"])
        return head_at(x), {"layers": layers_cache}

    def body(x, inp):
        lp, ct = inp
        x, new = _block_prefill_cache(lp, x, pos, cfg, backend=backend)
        if cfg.mixer in ("attention", "mla"):
            new = pad_kv(ct, new)
        return x, new

    x, layers_cache = jax.lax.scan(body, x, (p["layers"], cache["layers"]))
    return head_at(x), {"layers": layers_cache}
