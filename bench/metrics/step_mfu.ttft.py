"""``step_mfu`` (the whole step's share of the bf16 peak over the traced
window) as it bounds ``paged_gqa_prefill_roofline``: the same reading,
moving ``ttft_p95_ms``.  Layer: model step."""
from harness import spec


def read(r):
    return spec.load_metric_reader("step_mfu").read(r)
