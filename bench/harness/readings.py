"""What one run hands to the per-layer metric readers.

A reader is ``bench/metrics/<name>.py`` with ``read(r: Readings)``.  It
returns a number, or ``None`` where the run has nothing for it to read (the
harness then leaves the metric out of the line).  A reader whose kernel or
span should be there and is not raises :class:`Absent`, and the run fails
in the cells that list the metric.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional

import numpy as np


class Absent(Exception):
    """What a metric reads is missing from a run that should hold it."""


@dataclasses.dataclass
class Readings:
    dims: Any                      # harness.weights.Dims
    engine: Dict[str, Any]         # the traffic file's engine settings
    peaks: Dict[str, float]        # bench/harness/peaks.json, this device
    ptq_s: float                   # quantize-on-load, host clock
    warm_s: float                  # warm-up compiles, host clock
    setup_s: float                 # process start to the window's start
    window: Any                    # harness.serving.Window
    trace: Optional[Any] = None    # harness.trace.Trace (--trace 1 runs)


def percentile(values: List[float], q: float) -> Optional[float]:
    """Exact ``q``-th percentile (linear between order statistics)."""
    if not len(values):
        return None
    return float(np.percentile(np.asarray(values, np.float64), q))


def itl_gaps_ms(w) -> List[float]:
    """Gaps between consecutive token stamps of each request, for the gaps
    whose later token is stamped inside the window."""
    out = []
    for tk in w.tracks:
        st = tk.stamps
        out += [(b - a) * 1e3 for a, b in zip(st, st[1:]) if w.t0 <= b <= w.t1]
    return out


def traced_steps(r: Readings):
    """The engine steps launched inside the traced window, or ``Absent``."""
    if r.trace is None:
        return None
    if not r.window.steps:
        raise Absent("no engine step ran inside the traced window")
    return r.window.steps


def kernel_seconds(r: Readings, pattern: str) -> float:
    """Device seconds of the events matching ``pattern``, summed over the
    chips; ``Absent`` where the kernel never ran in the traced window."""
    from harness import trace as TR

    ns = sum(TR.kernel_ns(dev, pattern) for dev in r.trace.devices)
    if ns <= 0:
        raise Absent(f"no device event matches {pattern!r} in the trace")
    return ns * 1e-9


def roofline_share(r: Readings, calls, pattern: str) -> Optional[float]:
    """Percent of the roofline: the least time the calls' work could take
    on this chip (the larger of operations over peak and bytes over
    bandwidth, per call), over the kernel's device time."""
    if r.trace is None:
        return None
    least = sum(max(f / r.peaks["bf16_flops_per_s"],
                    b / r.peaks["hbm_bytes_per_s"]) for f, b in calls)
    if least <= 0:
        raise Absent(f"no work counted for {pattern!r} in the traced window")
    return 100.0 * least / kernel_seconds(r, pattern)
