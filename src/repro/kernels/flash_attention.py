"""Pallas TPU flash attention with causal block-skipping (prefill path).

The chunked jnp attention in ``models/attention.py`` masks the upper
triangle but still *computes* it — a 2× FLOP tax on causal prefill (visible
as useful/HLO ≈ 0.5 on prefill cells).  This kernel skips fully-masked
(q-block, kv-block) tiles with ``pl.when``, so causal prefill does ~half the
MXU work.  GQA is handled without materializing repeated KV heads: the K/V
BlockSpec index maps fold the query-group factor (head ``h`` reads KV head
``h // group``).

Grid: ``(B·H, T/bq, S/bkv)`` with the KV axis innermost; online-softmax
state (m, l, acc) lives in VMEM scratch and flushes on the last KV step.
Validated in interpret mode against the pure-jnp oracle (ref.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_KV = 512


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            n_kv: int, block_q: int, block_kv: int, causal: bool,
            scale: float):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # causal block skip: kv block strictly above the diagonal does nothing
    q_start = qi * block_q
    k_start = ki * block_kv
    live = (not causal) or (k_start <= q_start + block_q - 1)

    @pl.when(live)
    def _compute():
        q = q_ref[0].astype(jnp.float32)              # [bq, D]
        k = k_ref[0].astype(jnp.float32)              # [bkv, D]
        v = v_ref[0].astype(jnp.float32)              # [bkv, D]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale                                     # [bq, bkv]
        if causal:
            qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(kpos <= qpos, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[...] = m_new

    @pl.when(ki == n_kv - 1)
    def _flush():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(
            o_ref.dtype
        )


@functools.partial(
    jax.jit,
    static_argnames=("causal", "block_q", "block_kv", "interpret"),
)
def flash_attention(
    q: jax.Array,            # [B, T, H, D]
    k: jax.Array,            # [B, S, Hkv, D]
    v: jax.Array,            # [B, S, Hkv, D]
    *,
    causal: bool = True,
    block_q: int = DEFAULT_BLOCK_Q,
    block_kv: int = DEFAULT_BLOCK_KV,
    interpret: bool = False,
) -> jax.Array:
    b, t, h, d = q.shape
    s, hkv = k.shape[1], k.shape[2]
    if h % hkv != 0:
        raise ValueError(f"H={h} not a multiple of Hkv={hkv}")
    grp = h // hkv
    scale = d ** -0.5

    bq = min(block_q, t)
    bkv = min(block_kv, s)
    tp = -(-t // bq) * bq
    sp = -(-s // bkv) * bkv
    qh = q.transpose(0, 2, 1, 3).reshape(b * h, t, d)
    kh = k.transpose(0, 2, 1, 3).reshape(b * hkv, s, d)
    vh = v.transpose(0, 2, 1, 3).reshape(b * hkv, s, d)
    if tp != t:
        qh = jnp.pad(qh, ((0, 0), (0, tp - t), (0, 0)))
    if sp != s:
        # pad keys so padded positions never win the softmax
        kh = jnp.pad(kh, ((0, 0), (0, sp - s), (0, 0)),
                     constant_values=0)
        vh = jnp.pad(vh, ((0, 0), (0, sp - s), (0, 0)))
        # padded kv columns are masked via causal (they sit beyond any qpos)
        if not causal:
            raise ValueError("non-causal flash requires S divisible by block_kv")
    n_q, n_kv = tp // bq, sp // bkv

    out = pl.pallas_call(
        functools.partial(
            _kernel, n_kv=n_kv, block_q=bq, block_kv=bkv, causal=causal,
            scale=scale,
        ),
        grid=(b * h, n_q, n_kv),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda bh, i, j: (bh, i, 0)),
            # GQA: query head bh reads KV head bh//grp — no repeat in HBM
            pl.BlockSpec((1, bkv, d), lambda bh, i, j, grp=grp: (bh // grp, j, 0)),
            pl.BlockSpec((1, bkv, d), lambda bh, i, j, grp=grp: (bh // grp, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda bh, i, j: (bh, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, tp, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(qh, kh, vh)

    out = out[:, :t].reshape(b, h, t, d).transpose(0, 2, 1, 3)
    return out


def flash_vmem_bytes(block_q: int, block_kv: int, d: int,
                     dtype_bytes: int = 2) -> int:
    """Analytic VMEM working set per grid step (roofline notes)."""
    return (block_q * d * dtype_bytes          # q block
            + 2 * block_kv * d * dtype_bytes   # k + v blocks
            + block_q * d * 4                  # f32 acc
            + 2 * block_q * 4                  # m, l
            + block_q * d * dtype_bytes)       # out
