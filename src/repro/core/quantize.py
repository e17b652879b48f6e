"""Group-wise 4-bit asymmetric RTN quantization (SmoothQuant+ §2.1, eq. 1).

Conventions
-----------
A linear layer weight is ``W[Ci, Co]`` (input channels × output channels), so
``Y = X @ W``.  Quantization groups are *along the input-channel (contraction)
axis*: group ``g`` covers rows ``[g*G, (g+1)*G)`` and has one ``scale``/``zero``
per output channel, i.e. ``scales[Ci//G, Co]``.

Packed storage: two int4 codes per uint8, packed along the input-channel axis
in a *group-split* layout chosen for the TPU kernel: within each quantization
group of ``G`` rows, packed row ``r`` (``r < G//2``) holds code
``q[g*G + r, o]`` in the low nibble and ``q[g*G + G//2 + r, o]`` in the high
nibble.  Unpacking a group is then ``concat([lo, hi], axis=0)`` — a sublane
concatenation, with no row interleave — which lowers cleanly on TPU and keeps
each group contiguous in VMEM next to its ``scales``/``zeros`` row.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Tuple

import jax
import jax.numpy as jnp

NBITS = 4
QMAX = (1 << NBITS) - 1  # 15
DEFAULT_GROUP_SIZE = 128


@functools.partial(
    jax.tree_util.register_dataclass,
    data_fields=("packed", "scales", "zeros"),
    meta_fields=("a8",),
)
@dataclasses.dataclass(frozen=True)
class QuantizedTensor:
    """A group-wise int4-quantized weight, packed 2 codes / uint8.

    Leading dims are first-class: a *stacked* quantized leaf (layer stacks
    ``[L, ...]``, MoE experts ``[E, Ci, Co]``, MLA absorbed heads
    ``[H, Ci, Co]``) carries the extra dims on all three arrays, quantized
    independently along each trailing ``[Ci, Co]`` plane — ``lax.scan`` and
    the EP sharding rules treat the leaves like any stacked fp weight.

    Attributes:
      packed: uint8[*lead, Ci//2, Co] — packed int4 codes (group-split rows).
      scales: dtype[*lead, Ci//G, Co] — per-group, per-out-channel step Δ.
      zeros:  dtype[*lead, Ci//G, Co] — per-group, per-out-channel zero point
              (stored in the *float* domain as ``zero_code`` so dequant is
              ``(q - zeros) * scales``).
      a8:     static (non-traced) A8 eligibility flag: calibration found this
              layer's post-smoothing inputs safe for per-token int8
              activations.  ``ops.w4a16_matmul``/``w4a16_grouped_matmul``
              only take the int8×int4 path when it is True; being tree
              *metadata*, a flip retraces rather than recompiles-per-step,
              and ``lax.scan`` over stacked leaves carries it unchanged.
    """

    packed: jax.Array
    scales: jax.Array
    zeros: jax.Array
    a8: bool = True

    @property
    def shape(self) -> Tuple[int, ...]:
        return (*self.packed.shape[:-2], self.packed.shape[-2] * 2, self.packed.shape[-1])

    @property
    def ndim(self) -> int:
        return self.packed.ndim

    @property
    def group_size(self) -> int:
        return (self.packed.shape[-2] * 2) // self.scales.shape[-2]

    @property
    def dtype(self):
        return self.scales.dtype

    def __getitem__(self, idx) -> "QuantizedTensor":
        """Index/slice *leading* (stack) dims, e.g. ``qt[e]`` → one expert's
        2-D tensor.  The packed/group planes themselves are not indexable."""
        if self.packed.ndim < 3:
            raise IndexError("QuantizedTensor[...] indexes leading stack dims "
                             "only; this tensor is 2-D")
        return QuantizedTensor(
            packed=self.packed[idx], scales=self.scales[idx],
            zeros=self.zeros[idx], a8=self.a8)

    def nbytes_quant(self) -> int:
        return (
            self.packed.size * self.packed.dtype.itemsize
            + self.scales.size * self.scales.dtype.itemsize
            + self.zeros.size * self.zeros.dtype.itemsize
        )


def _check_nd(w: jax.Array) -> None:
    if w.ndim < 2:
        raise ValueError(f"expected >=2-D weight, got shape {w.shape}")


def compute_qparams(
    w: jax.Array, group_size: int = DEFAULT_GROUP_SIZE
) -> Tuple[jax.Array, jax.Array]:
    """Per-(group, out-channel) asymmetric min/max qparams (eq. 1).

    Returns (scales, zeros), each ``[Ci//G, Co]`` in ``w.dtype``'s compute
    precision (f32 internally, cast back).
    """
    _check_nd(w)
    *lead, ci, co = w.shape
    if ci % group_size != 0:
        raise ValueError(f"Ci={ci} not divisible by group_size={group_size}")
    g = ci // group_size
    wf = w.astype(jnp.float32).reshape(*lead, g, group_size, co)
    wmax = jnp.max(wf, axis=-2)
    wmin = jnp.min(wf, axis=-2)
    scales = (wmax - wmin) / QMAX
    # Avoid 0 step for constant groups.
    scales = jnp.where(scales <= 0, jnp.ones_like(scales), scales)
    # Eq. 1 clamps the *codes* to [0, 2^N-1]; Z itself is unclamped (we store
    # it in float alongside the scales, so offset-only groups stay exact).
    zeros = jnp.round(-wmin / scales)
    return scales, zeros


def quantize_codes(
    w: jax.Array,
    scales: jax.Array,
    zeros: jax.Array,
    group_size: int = DEFAULT_GROUP_SIZE,
) -> jax.Array:
    """RTN: map ``w`` to int codes in [0, 15].  Returns uint8[..., Ci, Co] (unpacked)."""
    *lead, ci, co = w.shape
    g = ci // group_size
    wf = w.astype(jnp.float32).reshape(*lead, g, group_size, co)
    q = jnp.round(wf / scales[..., None, :]) + zeros[..., None, :]
    q = jnp.clip(q, 0, QMAX).astype(jnp.uint8)
    return q.reshape(*lead, ci, co)


def pack_codes(q: jax.Array, group_size: int = DEFAULT_GROUP_SIZE) -> jax.Array:
    """Pack uint8 codes (0..15) into uint8[..., Ci//2, Co], group-split layout."""
    *lead, ci, co = q.shape
    if ci % group_size != 0 or group_size % 2 != 0:
        raise ValueError(f"Ci={ci} / group_size={group_size} incompatible")
    h = group_size // 2
    qg = q.reshape(*lead, ci // group_size, 2, h, co)
    return (qg[..., 0, :, :] | (qg[..., 1, :, :] << 4)).astype(jnp.uint8).reshape(
        *lead, ci // 2, co
    )


def unpack_codes(packed: jax.Array, group_size: int = DEFAULT_GROUP_SIZE) -> jax.Array:
    """Inverse of :func:`pack_codes` → uint8[..., Ci, Co]."""
    *lead, ci2, co = packed.shape
    h = group_size // 2
    pg = packed.reshape(*lead, ci2 // h, h, co)
    lo = pg & 0x0F
    hi = (pg >> 4) & 0x0F
    return jnp.concatenate([lo, hi], axis=-2).reshape(*lead, ci2 * 2, co)


@functools.partial(jax.jit, static_argnames=("group_size", "dtype"))
def quantize(
    w: jax.Array,
    group_size: int = DEFAULT_GROUP_SIZE,
    dtype: jnp.dtype | None = None,
) -> QuantizedTensor:
    """Group-wise asymmetric 4-bit RTN quantization of ``W[Ci, Co]``.

    Jitted so the f32 working copies fuse away: at full width a stacked
    weight is gigabytes in f32, and op-by-op they would pile up in HBM."""
    _check_nd(w)
    dtype = dtype or w.dtype
    scales, zeros = compute_qparams(w, group_size)
    q = quantize_codes(w, scales, zeros, group_size)
    return QuantizedTensor(
        packed=pack_codes(q, group_size),
        scales=scales.astype(dtype),
        zeros=zeros.astype(dtype),
    )


def dequantize(qt: QuantizedTensor, dtype: jnp.dtype | None = None) -> jax.Array:
    """Ŵ = (q − zero) · Δ, back to ``[..., Ci, Co]``."""
    dtype = dtype or qt.dtype
    q = unpack_codes(qt.packed, qt.group_size).astype(jnp.float32)
    *lead, ci, co = q.shape
    g = qt.scales.shape[-2]
    qg = q.reshape(*lead, g, ci // g, co)
    w = (qg - qt.zeros[..., None, :].astype(jnp.float32)) * qt.scales[
        ..., None, :
    ].astype(jnp.float32)
    return w.reshape(*lead, ci, co).astype(dtype)


def fake_quantize(
    w: jax.Array, group_size: int = DEFAULT_GROUP_SIZE
) -> jax.Array:
    """quantize→dequantize round trip in one shot (used by the α search)."""
    _check_nd(w)
    *lead, ci, co = w.shape
    if ci % group_size != 0 or ci < group_size:
        raise ValueError(f"Ci={ci} incompatible with group_size={group_size}")
    g = ci // group_size
    wf = w.astype(jnp.float32).reshape(*lead, g, group_size, co)
    wmax = jnp.max(wf, axis=-2, keepdims=True)
    wmin = jnp.min(wf, axis=-2, keepdims=True)
    scales = (wmax - wmin) / QMAX
    scales = jnp.where(scales <= 0, jnp.ones_like(scales), scales)
    zeros = jnp.round(-wmin / scales)
    q = jnp.clip(jnp.round(wf / scales) + zeros, 0, QMAX)
    return ((q - zeros) * scales).reshape(*lead, ci, co).astype(w.dtype)


# --------------------------------------------------- A8 activations -------
# The W4A8 prefill path (FPTQ / arxiv 2311.05161 on top of SmoothQuant+'s
# smoothing): activations quantize per *token row* to symmetric int8 right
# before the GEMM, the kernel contracts int8×int4→int32 on the MXU, and the
# per-(token, group) rescale restores the float domain.  These helpers define
# the quantization semantics once — the Pallas kernels, the XLA oracles, and
# the calibration-time eligibility metric all share them.

ACT_QMAX = 127  # symmetric int8


def quantize_acts_per_token(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Per-token symmetric int8 activation quantization.

    ``x[..., Ci]`` → ``(codes int8[..., Ci], scales f32[..., 1])`` with
    ``x ≈ codes * scales``.  Symmetric per-row scaling never clips the row
    max; the error is pure rounding, which is what the calibration-time
    eligibility metric measures.
    """
    xf = x.astype(jnp.float32)
    amax = jnp.maximum(jnp.max(jnp.abs(xf), axis=-1, keepdims=True), 1e-8)
    scales = amax / ACT_QMAX
    # multiply by an explicit per-row reciprocal, never ``xf / scales``: XLA
    # rewrites ``x / (a / c)`` to ``x * c / a`` inside a jit but not when run
    # op by op, and the two round a code that sits on a .5 tie differently
    # — the jitted kernel wrapper and the eager oracle must agree exactly
    codes = jnp.clip(jnp.round(xf * (ACT_QMAX / amax)), -ACT_QMAX,
                     ACT_QMAX).astype(jnp.int8)
    return codes, scales


def a8_roundtrip_error(x: jax.Array) -> jax.Array:
    """Worst per-token relative RMS error of the int8 activation round trip.

    The per-layer A8-eligibility statistic: rows whose magnitude is dominated
    by a few surviving outlier channels lose most of their levels and score
    high; post-smoothing rows score ~``1/(127·√12)``.  Returns a scalar —
    ``max`` over token rows, so one bad row disqualifies the layer.
    """
    xf = x.astype(jnp.float32).reshape(-1, x.shape[-1])
    codes, scales = quantize_acts_per_token(xf)
    err = codes.astype(jnp.float32) * scales - xf
    num = jnp.sqrt(jnp.mean(err * err, axis=-1))
    den = jnp.sqrt(jnp.mean(xf * xf, axis=-1))
    return jnp.max(num / jnp.maximum(den, 1e-8))


def quantization_loss(
    w: jax.Array,
    x_stat: jax.Array,
    group_size: int = DEFAULT_GROUP_SIZE,
) -> jax.Array:
    """Activation-weighted quantization loss  E ≈ ||diag(x)·(W − Ŵ)||²  (eq. 4).

    ``x_stat[Ci]`` is a per-input-channel activation magnitude statistic
    (channel max over the calibration set); using it instead of the full X
    matrix makes the whole-model loss evaluation O(params) per α instead of
    O(calibration tokens × params), while preserving the outlier-amplification
    structure the paper exploits.
    """
    err = (w - fake_quantize(w, group_size)).astype(jnp.float32)
    return jnp.sum((err * x_stat.astype(jnp.float32)[..., :, None]) ** 2)
