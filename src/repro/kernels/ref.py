"""Pure-jnp oracles for the kernels package."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.quantize import (
    QuantizedTensor,
    dequantize,
    quantize_acts_per_token,
    unpack_codes,
)


def w4a16_matmul_ref(x: jax.Array, qt: QuantizedTensor) -> jax.Array:
    """Oracle: dequantize the whole weight, then a plain matmul.

    x: [..., Ci] activation (bf16/f32); qt: packed int4 weight [Ci, Co].
    Returns [..., Co] in x.dtype, accumulated in f32.
    """
    w = dequantize(qt, jnp.float32)
    y = jnp.dot(
        x.astype(jnp.float32), w, preferred_element_type=jnp.float32
    )
    return y.astype(x.dtype)


def _folded_int_codes(qt: QuantizedTensor) -> jax.Array:
    """Zero-point-folded integer weight codes ``[..., G#, Gs, Co]`` (f32 but
    integer-valued), mirroring the kernels' A8 fold (``step_contribution``)
    exactly — including its int8 clip for pathological offset-only groups."""
    q = unpack_codes(qt.packed, qt.group_size).astype(jnp.float32)
    *lead, ci, co = q.shape
    g = qt.scales.shape[-2]
    qg = q.reshape(*lead, g, ci // g, co)
    z = jnp.round(qt.zeros.astype(jnp.float32))
    return jnp.clip(qg - z[..., None, :], -128, 127)


def w4a8_matmul_ref(x: jax.Array, qt: QuantizedTensor) -> jax.Array:
    """Oracle for the A8 kernel body: per-token symmetric int8 activations,
    integer contraction per quantization group, then the per-(token, group)
    rescale — the same association order as the Pallas kernel, so interpret
    vs XLA parity is tight.

    All integer arithmetic runs in f32: codes are ≤127·15 per product and
    group sums stay far below 2^24, so every intermediate is exact.
    """
    orig_shape = x.shape
    ci = orig_shape[-1]
    xq, xs = quantize_acts_per_token(x.reshape(-1, ci))
    wq = _folded_int_codes(qt)  # (G#, Gs, Co)
    g = wq.shape[-3]
    xg = xq.astype(jnp.float32).reshape(-1, g, ci // g)
    part = jnp.einsum(
        "tgi,gio->tgo", xg, wq, preferred_element_type=jnp.float32
    )
    y = jnp.sum(part * qt.scales.astype(jnp.float32)[None], axis=1) * xs
    return y.astype(x.dtype).reshape(*orig_shape[:-1], qt.packed.shape[-1])


def w4a16_grouped_ref(x: jax.Array, qt: QuantizedTensor) -> jax.Array:
    """Oracle for the expert-batched grouped kernel: dequantize the stacked
    ``[E, Ci, Co]`` weight, then a batched einsum.

    x: [E, C, Ci] per-expert activations; returns [E, C, Co] in x.dtype,
    accumulated in f32.  This is also the ``backend="xla"`` serving path on
    CPU hosts — XLA fuses the dequant into the contraction's producer.
    """
    w = dequantize(qt, jnp.float32)
    y = jnp.einsum(
        "ecd,edf->ecf", x.astype(jnp.float32), w,
        preferred_element_type=jnp.float32,
    )
    return y.astype(x.dtype)


def w4a8_grouped_ref(x: jax.Array, qt: QuantizedTensor) -> jax.Array:
    """A8 oracle for the expert-batched grouped kernel: per-(expert, row)
    int8 activations, integer contraction per group, per-(row, group)
    rescale.  Zero-padded capacity rows quantize to all-zero codes and keep
    contributing zero output rows."""
    e, c, d = x.shape
    xq, xs = quantize_acts_per_token(x)  # int8 [E,C,D], f32 [E,C,1]
    wq = _folded_int_codes(qt)  # (E, G#, Gs, Co)
    g = wq.shape[-3]
    xg = xq.astype(jnp.float32).reshape(e, c, g, d // g)
    part = jnp.einsum(
        "ecgi,egio->ecgo", xg, wq, preferred_element_type=jnp.float32
    )
    y = jnp.sum(
        part * qt.scales.astype(jnp.float32)[:, None], axis=2
    ) * xs
    return y.astype(x.dtype)
