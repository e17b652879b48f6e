"""Plain reference of a dense decoder with grouped-query attention, as the
published models define it: StarCoder2 (LayerNorm, biases, tanh-GELU MLP)
and Mistral (RMSNorm, no biases, SiLU-gated MLP).  Both use rotary position
embedding on halves of each head (GPT-NeoX style) and causal softmax
attention scaled by ``head_dim ** -0.5``.

It runs one sequence at a time over its whole length, in float32 with every
matrix product at ``Precision.HIGHEST``, with no cache, no pages, no batching
and no kernels, and imports nothing of the program.  StarCoder2's sliding
window (4096) is longer than any sequence served here, so it is left out.

``control=True`` is the control: the same model with its activations one
precision step below the bfloat16 the configuration states, as a W4A8 path
would compute it.  Every input of a quantized linear layer (the int4 GEMMs)
is held in int8, symmetric per token: codes ``round(x * 127 / amax)``
times ``amax / 127``, ``amax`` the token's largest magnitude.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
Q_BLOCK = 512           # query rows per attention block (bounds the scores)


I8_MAX = 127.0


def int8_per_token(x):
    """``x`` held in int8, symmetric per row of its last axis."""
    amax = jnp.maximum(jnp.max(jnp.abs(x), axis=-1, keepdims=True), 1e-30)
    return jnp.round(x * (I8_MAX / amax)) * (amax / I8_MAX)


def linear(p, x, control: bool):
    if control:
        x = int8_per_token(x)
    y = jnp.dot(x, p["w"].astype(jnp.float32), precision=HI)
    if "b" in p:
        y = y + p["b"].astype(jnp.float32)
    return y


def norm(p, x, kind: str, eps: float):
    if kind == "layernorm":
        mu = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
        y = (x - mu) * jax.lax.rsqrt(var + eps)
        return y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(ms + eps) * p["scale"].astype(jnp.float32)


def rope(x, pos, theta: float):
    """x [T, h, dh]; rotate (first half, second half) pairs by pos·θ^(-2i/dh)."""
    dh = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = pos[:, None, None].astype(jnp.float32) * inv
    x1, x2 = x[..., : dh // 2], x[..., dh // 2:]
    c, s = jnp.cos(ang), jnp.sin(ang)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def attention(q, k, v):
    """Causal GQA: q [T, H, dh], k/v [T, Hkv, dh] -> [T, H*dh]."""
    t, h, dh = q.shape
    hkv = k.shape[1]
    qg = q.reshape(t // Q_BLOCK, Q_BLOCK, hkv, h // hkv, dh)
    kpos = jnp.arange(t)

    def block(args):
        qb, b = args
        s = jnp.einsum("qkgd,skd->kgqs", qb, k, precision=HI) * dh ** -0.5
        qpos = b * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = jnp.where(kpos[None, :] <= qpos[:, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("kgqs,skd->qkgd", p, v, precision=HI)

    o = jax.lax.map(block, (qg, jnp.arange(t // Q_BLOCK)))
    return o.reshape(t, h * dh)


@functools.partial(jax.jit, static_argnames=("d", "control"))
def layer(lp, x, d, control: bool = False):
    """One decoder layer over ``x [T, D]`` (T a multiple of Q_BLOCK; rows
    past the sequence are padding that no real row attends to)."""
    t = x.shape[0]
    pos = jnp.arange(t)
    a = lp["mixer"]
    h = norm(lp["norm1"], x, d.norm, d.eps)
    q = linear(a["wq"], h, control).reshape(t, d.heads, d.head_dim)
    k = linear(a["wk"], h, control).reshape(t, d.kv_heads, d.head_dim)
    v = linear(a["wv"], h, control).reshape(t, d.kv_heads, d.head_dim)
    q, k = rope(q, pos, d.rope_theta), rope(k, pos, d.rope_theta)
    x = x + linear(a["wo"], attention(q, k, v), control)
    h = norm(lp["norm2"], x, d.norm, d.eps)
    m = lp["mlp"]
    if d.mlp == "swiglu":
        u = jax.nn.silu(linear(m["gate"], h, control)) * linear(m["up"], h, control)
    else:
        u = jax.nn.gelu(linear(m["up"], h, control), approximate=True)
    return x + linear(m["down"], u, control)


@functools.partial(jax.jit, static_argnames=("d",))
def embed(table, tokens, d):
    del d
    return jnp.take(table, tokens, axis=0).astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("d",))
def logits(final_norm, head, x, d):
    """``x [n, D]`` -> f32 logits ``[n, V]``."""
    h = norm(final_norm, x, d.norm, d.eps)
    return jnp.dot(h, head.astype(jnp.float32), precision=HI)
