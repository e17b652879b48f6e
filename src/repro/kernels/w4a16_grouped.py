"""Expert-batched grouped W4A16 Pallas TPU kernel.

Lifts the ``w4a16_matmul`` contract to *stacked* ``[E, Ci, Co]`` weights:
``x[E, C, D] @ dequant(qt)[E, D, F] -> [E, C, F]`` with the expert dim as the
outermost grid axis.  Each ``(e, i, j, k)`` grid step DMAs one packed block of
expert ``e`` into VMEM, expands to f32 *in VMEM* and feeds the MXU — int4 and
scales are the only weight bytes that ever cross HBM, which is the §2.3
roofline win applied per expert.  This is the serving path for MoE expert
FFNs (``models/mlp.py``, experts ride the grid) and for MLA's absorbed-form
decode projections (``models/attention.py``, heads ride the grid); both used
to re-inflate a dense f32 weight in HBM every step via ``dequantize``.

Layout contract is identical to ``w4a16_matmul``: group-split packing along
the contraction axis, each k step spanning whole quantization groups with a
``(gpk, bco)`` scales/zeros block, each group unpacked with one sublane
``concat`` and contracted with its own scale row.  Zero-padded capacity rows
(ragged MoE dispatch) are harmless: a zero activation row contributes a zero
output row regardless of the asymmetric zero-points.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.quantize import QuantizedTensor, quantize_acts_per_token
from repro.kernels.w4a16_matmul import (
    _fit_block_co,
    _round_up,
    groups_per_step,
    step_contribution,
    weight_specs,
)

DEFAULT_BLOCK_C = 256
DEFAULT_BLOCK_CO = 256


def _kernel(*refs, n_k, group, a8):
    if a8:
        x_ref, xs_ref, packed_ref, scales_ref, zeros_ref, o_ref, acc_ref = refs
        xs = xs_ref[0]
    else:
        x_ref, packed_ref, scales_ref, zeros_ref, o_ref, acc_ref = refs
        xs = None
    k = pl.program_id(3)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # this expert's block (see w4a16_matmul.step_contribution)
    acc_ref[...] += step_contribution(
        x_ref[0], xs, packed_ref[0], scales_ref[0], zeros_ref[0], group=group)

    @pl.when(k == n_k - 1)
    def _flush():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("block_c", "block_co", "interpret", "act")
)
def w4a16_grouped_matmul(
    x: jax.Array,
    qt: QuantizedTensor,
    *,
    block_c: int = DEFAULT_BLOCK_C,
    block_co: int = DEFAULT_BLOCK_CO,
    interpret: bool = False,
    act: str = "a16",
) -> jax.Array:
    """``x[E, C, D] @ dequant(qt)[E, D, F] -> [E, C, F]`` via Pallas.

    One grid cell touches one expert only, so sharding the expert axis (EP)
    shards the grid.  The contraction block spans whole quantization groups
    (one scales/zeros row per group).  ``act="a8"`` quantizes each
    ``(expert, row)`` to symmetric int8 outside the kernel and runs the
    int8×int4→int32 body; zero-padded capacity rows quantize to
    all-zero codes and still contribute zero output rows.
    """
    if qt.packed.ndim != 3:
        raise ValueError(
            f"grouped kernel needs stacked [E, Ci, Co] weights; got packed "
            f"shape {qt.packed.shape}")
    if x.ndim != 3:
        raise ValueError(f"expected x[E, C, D], got shape {x.shape}")
    if act not in ("a16", "a8"):
        raise ValueError(f"act must be 'a16' or 'a8', got {act!r}")
    e, c, d = x.shape
    if e != qt.packed.shape[0]:
        raise ValueError(f"x experts E={e} != weight experts {qt.packed.shape[0]}")
    if d != qt.shape[-2]:
        raise ValueError(f"x Ci={d} != weight Ci={qt.shape[-2]}")
    co = qt.packed.shape[-1]
    group = qt.group_size
    out_dtype = x.dtype

    a8 = act == "a8"
    if a8:
        x, xs = quantize_acts_per_token(x)  # int8 codes, (e, c, 1) f32

    # decode-sized c (< block_c, e.g. MLA absorbed B rows per head): bc pins
    # to the padded row count — one C-grid step, cached per shape.  int8
    # rows tile by 32 sublanes, float rows by 8.
    bc = min(block_c, _round_up(c, 32 if a8 else 8))
    c_pad = _round_up(c, bc)
    if c_pad != c:
        x = jnp.pad(x, ((0, 0), (0, c_pad - c), (0, 0)))
        if a8:
            xs = jnp.pad(xs, ((0, 0), (0, c_pad - c), (0, 0)))
    bco = _fit_block_co(co, block_co)
    gpk = groups_per_step(d // group, group)
    bci = gpk * group
    n_c, n_co, n_k = c_pad // bc, co // bco, d // bci

    in_specs = [pl.BlockSpec((1, bc, bci), lambda e, i, j, k: (e, i, k))]
    operands = [x]
    if a8:
        in_specs.append(pl.BlockSpec((1, bc, 1), lambda e, i, j, k: (e, i, 0)))
        operands.append(xs)
    in_specs += weight_specs(gpk, group, bco, lambda e, i, j, k: (e, k, j),
                             lead=(1,))
    operands += [qt.packed, qt.scales, qt.zeros]

    out = pl.pallas_call(
        functools.partial(_kernel, n_k=n_k, group=group, a8=a8),
        grid=(e, n_c, n_co, n_k),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, bc, bco), lambda e, i, j, k: (e, i, j)),
        out_shape=jax.ShapeDtypeStruct((e, c_pad, co), out_dtype),
        scratch_shapes=[pltpu.VMEM((bc, bco), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(*operands)

    return out[:, :c] if c_pad != c else out


def grouped_weight_bytes(
    e: int, d: int, f: int, group: int, scale_bytes: int = 2
) -> tuple[int, int]:
    """(packed int4 + scales/zeros bytes, dense bf16 bytes) one full pass over
    the stacked weight moves through HBM — the ~4x roofline claim the
    ``w4a16_moe`` bench suite tracks."""
    packed = e * (d // 2) * f
    sz = 2 * e * (d // group) * f * scale_bytes
    return packed + sz, e * d * f * 2
