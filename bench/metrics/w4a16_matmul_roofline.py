"""Percent of its roofline that the W4A16 GEMM kernel
(``kernels/w4a16_matmul.py``) reaches over the traced window.

Each launch of an engine step (the decode step, each fused prefill-chunk
bucket) runs every quantized linear of every layer once, over the rows
that carry requests.  A call's work, counted from the model's shapes so
that any implementation of the kernel is held to the same work:

* operations: ``2 * rows * Ci * Co``;
* bytes: ``Ci * Co / 2`` of int4 codes, one bf16 scale and one bf16 zero
  per group and output column, bf16 activations in (``rows * Ci``) and out
  (``rows * Co``).

Layer: kernels.  Moves ``itl_p50_ms``.
"""
from harness.readings import roofline_share, traced_steps
from harness.weights import linear_shapes

# the kernel's events in the device trace
KERNEL = r"^%w4a16_matmul\b"


def call_work(rows: int, ci: int, co: int, group: int):
    """``(operations, bytes)`` of one ``[rows, Ci] @ [Ci, Co]`` W4A16 call."""
    flops = 2 * rows * ci * co
    nbytes = ci * co // 2 + 2 * 2 * (ci // group) * co + 2 * rows * (ci + co)
    return flops, nbytes


def launch_rows(step):
    """Rows that carry requests in each launch of one engine step."""
    rows = [len(step.decode_ctx)] if step.decode_ctx else []
    return rows + [sum(lens) for _, _, lens, _ in step.prefill]


def calls(d, steps):
    shapes = linear_shapes(d)
    return [call_work(rows, ci, co, d.group)
            for st in steps for rows in launch_rows(st)
            for _ in range(d.layers) for _, ci, co in shapes]


def read(r):
    steps = traced_steps(r)
    if steps is None:
        return None
    return roofline_share(r, calls(r.dims, steps), KERNEL)
