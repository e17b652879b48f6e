"""Percent of the chip's bf16 peak that the served model's work reaches
over the traced window: model operations of every token the window's
steps processed, over (window seconds x peak).

Per token: 2 x the quantized linears' parameters of every layer, plus
attention over its context, ``4 * ctx * Hq * Dh`` per layer (``ctx`` keys,
itself included; causal inside a prefill chunk).  Per row that yields the
logits the engine uses (each decode row, each final prefill chunk row):
the bf16 lm_head, ``2 * d_model * vocab``.  Padding and idle slots count
for nothing.  Layer: model step.  Moves ``itl_p50_ms``.
"""
from harness.readings import traced_steps
from harness.weights import linear_shapes


def step_flops(d, step) -> int:
    per_tok = 2 * d.layers * sum(ci * co for _, ci, co in linear_shapes(d))
    attn = 4 * d.layers * d.heads * d.head_dim
    head = 2 * d.d_model * d.vocab
    f = 0
    for ctx in step.decode_ctx:
        f += per_tok + attn * ctx + head
    for _, starts, lens, final in step.prefill:
        for s, n, last in zip(starts, lens, final):
            f += per_tok * n + attn * (n * s + n * (n + 1) // 2)
            f += head if last else 0
    return f


def read(r):
    steps = traced_steps(r)
    if steps is None:
        return None
    flops = sum(step_flops(r.dims, st) for st in steps)
    chips = len(r.trace.devices)
    return 100.0 * flops / (r.trace.window_s * chips
                            * r.peaks["bf16_flops_per_s"])
