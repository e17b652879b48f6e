"""Percent of its roofline that the paged GQA chunked-prefill kernel
(``_gqa_prefill_kernel`` in ``kernels/paged_attention.py``) reaches over
the traced window.

One call per layer per prefill-chunk bucket.  For a row whose chunk holds
``L`` tokens after ``s`` already in its pages, causal:

* operations: ``4 * Hq * Dh * (L * s + L * (L + 1) / 2)``;
* bytes: K and V of all ``s + L`` positions, ``2 * (s + L) * Hkv * Dh``
  bf16 values, plus the chunk's bf16 queries and outputs,
  ``2 * L * Hq * Dh``.

Layer: kernels.  Moves ``ttft_p95_ms``.
"""
from harness.readings import roofline_share, traced_steps

KERNEL = r"^%gqa_paged_prefill\b"
BYTES = 2


def row_work(start: int, length: int, d):
    pairs = length * start + length * (length + 1) // 2
    flops = 4 * d.heads * d.head_dim * pairs
    nbytes = BYTES * d.head_dim * (2 * (start + length) * d.kv_heads
                                   + 2 * length * d.heads)
    return flops, nbytes


def calls(d, steps):
    out = []
    for st in steps:
        for _, starts, lens, _ in st.prefill:
            work = [row_work(s, n, d) for s, n in zip(starts, lens)]
            call = (sum(f for f, _ in work), sum(b for _, b in work))
            out += [call] * d.layers
    return out


def read(r):
    steps = traced_steps(r)
    if steps is None:
        return None
    return roofline_share(r, calls(r.dims, steps), KERNEL)
