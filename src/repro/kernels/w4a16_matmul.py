"""W4A16/W4A8 group-wise dequant-inside-matmul Pallas TPU kernel.

TPU adaptation of the paper's LMDeploy-derived CUDA W4A16 GEMM (§2.3): int4
weights stay packed in HBM; each grid step DMAs one packed block into VMEM,
expands it *in VMEM*, and feeds the MXU.  HBM traffic for weights is ~¼ of
bf16, which is the roofline win for memory-bound decode GEMMs.

The ``act="a8"`` body is the compute-bound *prefill* variant (FPTQ / arxiv
2311.05161): activations arrive pre-quantized to per-token symmetric int8
with their ``(bt, 1)`` scales riding along as a VMEM operand, the packed int4
block unpacks to zero-point-folded *int8 codes* instead of f32, and each
group contracts int8×int4→int32 on the MXU.  Weight scales differ per
quantization group, so the int32 partial product is rescaled by
``act_scale[bt,1] · weight_scale[1,bco]`` at each group boundary into the f32
VMEM accumulator — the integer accumulation spans exactly one group's
contraction, which is the widest span over which a single rescale is valid.

Layout contract (see ``repro.core.quantize``): packing is along the
contraction axis in group-split layout, so each quantization group of a
weight block unpacks with a single sublane ``concat`` — no row interleave —
and uses exactly one ``scales``/``zeros`` row.

Grid: ``(T/bt, Co/bco, Ci/bci)`` with the contraction axis innermost; partial
products accumulate in an f32 VMEM scratch and are written back once per
``(i, j)`` tile on the last ``k`` step.  One ``k`` step spans ``gpk`` whole
groups (``bci = gpk · group``), so the ``scales``/``zeros`` block is
``(gpk, bco)``: the TPU lowering needs a block's second-to-last dim to be a
multiple of 8 or the array's full extent, which a one-group ``(1, bco)``
block is not.  Within the step each group is contracted on its own (one
``group``-deep MXU pass) so the per-group scale — and the A8 body's
per-group integer rescale — apply exactly as in the XLA oracles.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.quantize import QuantizedTensor, quantize_acts_per_token

DEFAULT_BLOCK_T = 256
DEFAULT_BLOCK_CO = 256
# contraction depth one k step aims for: 8 groups of 128 — the smallest
# group count whose scales block is sublane-aligned
TARGET_BLOCK_CI = 1024


def _unpack_group(packed):
    """Codes ``[0, 15]`` (int32) of one packed group-split block: nibble
    split, sublane concat back to group order."""
    p = packed.astype(jnp.int32)
    return jnp.concatenate([p & 0x0F, (p >> 4) & 0x0F], axis=0)


def step_contribution(x, xs, packed, scales, zeros, *, group: int):
    """f32 ``(rows, bco)`` contribution of one k step spanning
    ``gpk = scales.shape[0]`` whole groups.

    ``x`` is ``(rows, gpk·group)`` — float (A16) or int8 codes (A8, with
    per-row scales ``xs (rows, 1)``; ``None`` selects A16).  Each group is
    dequantized and contracted on its own; shared by the 2-D and
    expert-grouped kernel bodies so the packing contract lives in exactly
    one place."""
    half = group // 2
    s = scales.astype(jnp.float32)
    z = zeros.astype(jnp.float32)
    out = None
    for g in range(scales.shape[0]):
        codes = _unpack_group(packed[g * half:(g + 1) * half])
        xg = x[:, g * group:(g + 1) * group]
        if xs is None:
            w = (codes.astype(jnp.float32) - z[g:g + 1]) * s[g:g + 1]
            part = jax.lax.dot_general(
                xg.astype(jnp.float32), w, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
        else:
            # zeros are float-domain but integer-valued (``round`` in
            # ``compute_qparams``); folding them keeps the block on the MXU's
            # int8 path.  Codes live in [0, 15] so ``codes − zero`` fits int8
            # for any zero in [-112, 127]; the clip guards pathological
            # offset-only groups, mirrored exactly by the XLA oracle.
            zi = jnp.round(z[g:g + 1]).astype(jnp.int32)
            wq = jnp.clip(codes - zi, -128, 127).astype(jnp.int8)
            acc = jax.lax.dot_general(
                xg, wq, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.int32)
            part = acc.astype(jnp.float32) * s[g:g + 1] * xs
        out = part if out is None else out + part
    return out


def _kernel(*refs, n_k, group, a8):
    if a8:
        x_ref, xs_ref, packed_ref, scales_ref, zeros_ref, o_ref, acc_ref = refs
        xs = xs_ref[...]
    else:
        x_ref, packed_ref, scales_ref, zeros_ref, o_ref, acc_ref = refs
        xs = None
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += step_contribution(
        x_ref[...], xs, packed_ref[...], scales_ref[...], zeros_ref[...],
        group=group)

    @pl.when(k == n_k - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _fit_block_co(co: int, block_co: int) -> int:
    """Largest power-of-two-reduced divisor of ``co`` that is ≤ ``block_co``
    — ragged output widths shrink the block instead of raising or copying
    the packed weight into a padded buffer every call."""
    bco = min(block_co, co)
    while bco > 1 and co % bco:
        bco //= 2
    if co % bco:
        raise ValueError(f"Co={co} has no usable block ≤ {block_co}")
    return bco


def groups_per_step(n_groups: int, group: int) -> int:
    """Groups per k step: the largest multiple-of-8 divisor of ``n_groups``
    whose depth stays within :data:`TARGET_BLOCK_CI` (at least 8 groups),
    else every group at once — the two block heights the TPU lowering
    accepts for the ``(gpk, bco)`` scales/zeros block."""
    cap = max(8, TARGET_BLOCK_CI // group)
    for gpk in range(min(cap, n_groups) // 8 * 8, 0, -8):
        if n_groups % gpk == 0:
            return gpk
    return n_groups


def weight_specs(gpk: int, group: int, bco: int, index_map, lead=()):
    """BlockSpecs of (packed, scales, zeros) for one k step; ``lead`` holds
    the block's leading unit dims (the expert axis of the grouped kernel)."""
    return [
        pl.BlockSpec((*lead, gpk * group // 2, bco), index_map),
        pl.BlockSpec((*lead, gpk, bco), index_map),
        pl.BlockSpec((*lead, gpk, bco), index_map),
    ]


@functools.partial(
    jax.jit, static_argnames=("block_t", "block_co", "interpret", "act")
)
def w4a16_matmul(
    x: jax.Array,
    qt: QuantizedTensor,
    *,
    block_t: int = DEFAULT_BLOCK_T,
    block_co: int = DEFAULT_BLOCK_CO,
    interpret: bool = False,
    act: str = "a16",
) -> jax.Array:
    """``x[..., Ci] @ dequant(qt)[Ci, Co] -> [..., Co]`` via Pallas.

    The contraction block spans whole quantization groups
    (:func:`groups_per_step`).  ``act="a8"`` quantizes ``x`` per token to
    symmetric int8 outside the kernel (one XLA pass) and runs the
    int8×int4→int32 body.
    """
    if qt.packed.ndim != 2:
        raise ValueError("pallas kernel handles 2-D weights; got leading dims")
    if act not in ("a16", "a8"):
        raise ValueError(f"act must be 'a16' or 'a8', got {act!r}")
    orig_shape = x.shape
    ci = orig_shape[-1]
    co = qt.packed.shape[1]
    group = qt.group_size
    if ci != qt.shape[0]:
        raise ValueError(f"x Ci={ci} != weight Ci={qt.shape[0]}")

    x2 = x.reshape(-1, ci)
    t = x2.shape[0]
    a8 = act == "a8"
    # decode-sized t (< block_t): bt pins to the padded batch, so the token
    # dim is one grid step with no padding up to block_t, and the jit cache —
    # keyed on (shape, blocks) — makes steady-state decode compile exactly
    # once (asserted by test_decode_tiny_t_no_recompile).  int8 rows tile by
    # 32 sublanes, float rows by 8.
    bt = min(block_t, _round_up(t, 32 if a8 else 8))
    bco = _fit_block_co(co, block_co)
    gpk = groups_per_step(ci // group, group)
    bci = gpk * group

    if a8:
        x2, xs = quantize_acts_per_token(x2)  # int8 codes, (t, 1) f32 scales

    t_pad = _round_up(t, bt)
    if t_pad != t:
        x2 = jnp.pad(x2, ((0, t_pad - t), (0, 0)))
        if a8:
            xs = jnp.pad(xs, ((0, t_pad - t), (0, 0)))
    n_t, n_co, n_k = t_pad // bt, co // bco, ci // bci

    in_specs = [pl.BlockSpec((bt, bci), lambda i, j, k: (i, k))]
    operands = [x2]
    if a8:
        in_specs.append(pl.BlockSpec((bt, 1), lambda i, j, k: (i, 0)))
        operands.append(xs)
    in_specs += weight_specs(gpk, group, bco, lambda i, j, k: (k, j))
    operands += [qt.packed, qt.scales, qt.zeros]

    out = pl.pallas_call(
        functools.partial(_kernel, n_k=n_k, group=group, a8=a8),
        grid=(n_t, n_co, n_k),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bt, bco), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((t_pad, co), x.dtype),
        scratch_shapes=[pltpu.VMEM((bt, bco), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(*operands)

    if t_pad != t:
        out = out[:t]
    return out.reshape(*orig_shape[:-1], co)


def _round_up(v: int, m: int) -> int:
    return ((v + m - 1) // m) * m


def vmem_bytes(block_t: int, block_co: int, group: int, dtype_bytes: int = 2) -> int:
    """Analytic VMEM working-set claim for one grid step at the target
    contraction depth (for roofline notes)."""
    bci = max(TARGET_BLOCK_CI, group)
    x_blk = block_t * bci * dtype_bytes
    w_blk = (bci // 2) * block_co  # uint8
    sz = 2 * (bci // group) * block_co * dtype_bytes  # scales+zeros rows
    acc = block_t * block_co * 4
    out = block_t * block_co * dtype_bytes
    return x_blk + w_blk + sz + acc + out
