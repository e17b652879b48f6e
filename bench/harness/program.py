"""The program's own marks in a traced run: the engine's phase spans
(``engine.step``, ``engine.admit``, ... on the host plane) and the step
programs' module events (``jit_decode_step``, ... on each device plane's
module line).

``trace.load`` keeps the harness's ``bench.*`` spans and the op line only.
Where a later harness hands them over as ``Trace.program`` and
``Trace.modules`` they are read from there; until then they are read from
the run's profile under ``.bench_traces/``, the one whose ``bench.traced``
span is the run's window.  A program that emits no phase spans (one older
than them) reads as having none, never as an error.
"""
from __future__ import annotations

import dataclasses
import glob
import os
from typing import Dict, List, Optional, Sequence

from harness import spec
from harness import trace as TR
from harness.serving import SPAN_TRACED

SPAN_PREFIX = "engine."
STEP = "engine.step"
MODULES_LINE = "XLA Modules"

# each engine span belongs to one layer's idle share; every other
# ``engine.*`` span (the waits, ``engine.emit``, ``engine.journal``, the
# self time of ``engine.step``, ``engine.prefill`` and ``engine.decode``)
# falls to the engine step's
LAYERS = {
    "scheduler": ("engine.expire", "engine.admit", "engine.plan_chunks"),
    "pager": ("engine.grow", "engine.check_tables", "engine.drain_swap"),
    "launch": ("engine.prefill_inputs", "engine.prefill_dispatch",
               "engine.decode_inputs", "engine.decode_dispatch"),
}
OUTSIDE = "outside bench spans"     # trace.idle_by_host's label


@dataclasses.dataclass
class Program:
    spans: List[TR.Event]               # engine.* spans, clipped to window
    modules: List[List[TR.Event]]       # module events per device, in window
    idle: Optional[Dict[str, float]] = None   # idle_phases of the run, memo


_cache: Dict[tuple, Optional[Program]] = {}


def _profiles() -> List[str]:
    paths = glob.glob(os.path.join(str(spec.ROOT), ".bench_traces", "*",
                                   "plugins", "profile", "*", "*.xplane.pb"))
    return sorted(paths, key=os.path.getmtime, reverse=True)


def read_profile(path: str, window=None) -> Optional[Program]:
    """The program's spans and module events in the profile at ``path``,
    in its ``bench.traced`` window; ``None`` where that window is not
    ``window`` (when given)."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    spans, marks, modules = [], [], []
    for plane in pd.planes:
        if TR.DEVICE_PLANE.match(plane.name):
            lines = {ln.name: ln for ln in plane.lines}
            ln = lines.get(MODULES_LINE)
            modules.append([] if ln is None else [
                TR.Event(e.name, float(e.start_ns), float(e.duration_ns))
                for e in ln.events])
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for e in ln.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append(TR.Event(e.name, float(e.start_ns),
                                              float(e.duration_ns)))
                    elif e.name == SPAN_TRACED:
                        marks.append((float(e.start_ns), float(e.start_ns)
                                      + float(e.duration_ns)))
    if len(marks) != 1 or (window is not None
                           and tuple(marks[0]) != tuple(window)):
        return None
    t0, t1 = marks[0]
    return Program(spans=TR.clip(spans, t0, t1),
                   modules=[[e for e in m if t0 <= e.start < t1]
                            for m in modules])


def of(r) -> Optional[Program]:
    """The program's marks in run ``r``'s traced window, or ``None`` where
    the run was not traced or its profile is not found."""
    tr = r.trace
    if tr is None:
        return None
    if hasattr(tr, "program"):
        return Program(spans=list(tr.program),
                       modules=list(getattr(tr, "modules", [])))
    for path in _profiles():
        key = (path, tuple(tr.window))
        if key not in _cache:
            _cache[key] = read_profile(path, tr.window)
        if _cache[key] is not None:
            return _cache[key]
    return None


def phase_spans(r) -> Optional[Program]:
    """Run ``r``'s program marks where its program emits phase spans."""
    prog = of(r)
    if prog is None or not any(e.name == STEP for e in prog.spans):
        return None
    return prog


def idle_phases(device: Sequence[TR.Event], spans: Sequence[TR.Event],
                t0: float, t1: float) -> Dict[str, float]:
    """Device idle seconds by the innermost engine span covering them (the
    latest to start; of two that start together, the shorter), or
    ``OUTSIDE``: ``trace.idle_by_host``'s rule, every name kept, swept in
    one pass over spans that nest."""
    spans = sorted(spans, key=lambda e: (e.start, -e.dur))
    cuts = sorted({t0, t1} | {x for e in spans for x in (e.start, e.end)
                              if t0 < x < t1})
    segs, active, k = [], [], 0
    for a, b in zip(cuts, cuts[1:]):
        mid = (a + b) / 2
        while k < len(spans) and spans[k].start <= mid:
            active.append(spans[k])
            k += 1
        active = [e for e in active if mid < e.end]
        segs.append((a, b, active[-1].name if active else OUTSIDE))
    tot: Dict[str, float] = {}
    i = 0
    for g0, g1 in TR.gaps(device, t0, t1):
        while i < len(segs) and segs[i][1] <= g0:
            i += 1
        j = i
        while j < len(segs) and segs[j][0] < g1:
            a, b, name = segs[j]
            tot[name] = tot.get(name, 0.0) + (min(b, g1) - max(a, g0)) * 1e-9
            j += 1
    return tot


def layer_of(name: str) -> str:
    for layer, names in LAYERS.items():
        if name in names:
            return layer
    return "engine step" if name.startswith(SPAN_PREFIX) else OUTSIDE


def idle_share(r, layer: str) -> Optional[float]:
    """Percent of the traced window in which the device idles under the
    engine spans of ``layer``; ``None`` where there are no phase spans."""
    prog = phase_spans(r)
    if prog is None:
        return None
    if prog.idle is None:
        prog.idle = idle_phases(r.trace.devices[0], prog.spans,
                                *r.trace.window)
    s = sum(sec for name, sec in prog.idle.items() if layer_of(name) == layer)
    return 100.0 * s / r.trace.window_s


def module_events(prog: Program, pattern: str) -> List[TR.Event]:
    return [e for dev in prog.modules for e in TR.matching(dev, pattern)]
