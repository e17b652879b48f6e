"""Median gap between consecutive output tokens of one request, over every
gap that ends inside the window (host-clock stamps, see
``harness.serving``)."""
from harness.readings import itl_gaps_ms, percentile


def read(r):
    return percentile(itl_gaps_ms(r.window), 50)
