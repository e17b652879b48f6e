"""Percent of its roofline that the paged GQA decode kernel
(``_gqa_kernel`` in ``kernels/paged_attention.py``) reaches over the
traced window.

One call per layer per decode step, over the rows that carry requests.
For a row whose context holds ``ctx`` tokens (the new one included):

* operations: ``4 * ctx * Hq * Dh`` (scores and the weighted sum);
* bytes: its K and V, ``2 * ctx * Hkv * Dh`` bf16 values, plus the bf16
  query and output, ``2 * Hq * Dh`` values.

Layer: kernels.  Moves ``itl_p50_ms``.
"""
from harness.readings import roofline_share, traced_steps

KERNEL = r"^%gqa_paged_attention\b"
BYTES = 2          # bf16 pages, queries and outputs


def row_work(ctx: int, d):
    flops = 4 * ctx * d.heads * d.head_dim
    nbytes = BYTES * (2 * ctx * d.kv_heads + 2 * d.heads) * d.head_dim
    return flops, nbytes


def calls(d, steps):
    out = []
    for st in steps:
        if not st.decode_ctx:
            continue
        work = [row_work(c, d) for c in st.decode_ctx]
        call = (sum(f for f, _ in work), sum(b for _, b in work))
        out += [call] * d.layers
    return out


def read(r):
    steps = traced_steps(r)
    if steps is None:
        return None
    return roofline_share(r, calls(r.dims, steps), KERNEL)
