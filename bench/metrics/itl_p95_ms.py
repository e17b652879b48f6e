"""95th percentile of the gaps of ``itl_p50_ms``."""
from harness.readings import itl_gaps_ms, percentile


def read(r):
    return percentile(itl_gaps_ms(r.window), 95)
