"""Reduce a profiler trace to the numbers the per-layer metrics read.

The JAX profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``.
``jax.profiler.ProfileData`` reads it: planes (one per device, one for the
host), their lines, and events with a start and a duration in nanoseconds
on one clock.  On a TPU the device plane is ``/device:TPU:<n>`` and its
``XLA Ops`` line holds one event per operation that ran, Pallas kernels
among them; the host plane holds the harness's own ``bench.*`` spans.

Everything here is plain arithmetic over intervals, kept apart from the
profiler so that the tests can feed it recorded events.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

OPS_LINE = "XLA Ops"
DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
# the stats of an op event that may carry a kernel's own name
NAME_STATS = ("long_name", "hlo_op", "tf_op", "name", "kernel_name")


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start: float          # ns
    dur: float            # ns
    text: str = ""        # name plus the name-bearing stats, for matching

    @property
    def end(self) -> float:
        return self.start + self.dur


@dataclasses.dataclass
class Trace:
    window: Tuple[float, float]            # the traced span, ns
    devices: List[List[Event]]             # op events per device, in window
    host: List[Event]                      # the harness's bench.* spans

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def _text(ev) -> str:
    parts = [ev.name]
    for k, v in ev.stats:
        if k in NAME_STATS and isinstance(v, str):
            parts.append(v)
    return " ".join(parts)


def load(trace_dir: str, window_span: str, host_prefix: str = "bench.") -> Trace:
    """Device op events and host spans of the newest trace in ``trace_dir``,
    clipped to the one host span named ``window_span``."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(find_xplane(trace_dir))
    devices, host = [], []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            lines = {ln.name: ln for ln in plane.lines}
            if OPS_LINE not in lines:
                raise ValueError(f"{plane.name} has no {OPS_LINE!r} line: "
                                 f"{sorted(lines)}")
            devices.append([Event(e.name, float(e.start_ns),
                                  float(e.duration_ns), _text(e))
                            for e in lines[OPS_LINE].events])
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for e in ln.events:
                    if e.name.startswith(host_prefix):
                        host.append(Event(e.name, float(e.start_ns),
                                          float(e.duration_ns)))
    spans = [e for e in host if e.name == window_span]
    if len(spans) != 1:
        raise ValueError(f"expected one {window_span!r} span, found "
                         f"{len(spans)}")
    if not devices:
        raise ValueError(f"no device plane in the trace: "
                         f"{[p.name for p in pd.planes]}")
    w = (spans[0].start, spans[0].end)
    return Trace(window=w, devices=[clip(d, *w) for d in devices],
                 host=[e for e in clip(host, *w) if e.name != window_span])


def clip(events: Iterable[Event], t0: float, t1: float) -> List[Event]:
    """The parts of ``events`` inside ``[t0, t1]``."""
    out = []
    for e in events:
        s, t = max(e.start, t0), min(e.end, t1)
        if t > s or (e.dur == 0 and t0 <= e.start <= t1):
            out.append(Event(e.name, s, max(t - s, 0.0), e.text))
    return out


def union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    merged: List[List[float]] = []
    for s, t in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], t)
        else:
            merged.append([s, t])
    return [(s, t) for s, t in merged]


def busy_ns(events: Sequence[Event]) -> float:
    return sum(t - s for s, t in union((e.start, e.end) for e in events))


def gaps(events: Sequence[Event], t0: float, t1: float) -> List[Tuple[float, float]]:
    """Intervals of ``[t0, t1]`` in which no event runs."""
    out, cur = [], t0
    for s, t in union((e.start, e.end) for e in events):
        if s > cur:
            out.append((cur, s))
        cur = max(cur, t)
    if t1 > cur:
        out.append((cur, t1))
    return out


def matching(events: Sequence[Event], pattern: str) -> List[Event]:
    rx = re.compile(pattern)
    return [e for e in events if rx.search(e.text or e.name)]


def kernel_ns(events: Sequence[Event], pattern: str) -> float:
    return sum(e.dur for e in matching(events, pattern))


def op_name(name: str) -> str:
    """``%w4a16_matmul.41 = bf16[...] custom-call(...)`` -> ``w4a16_matmul``:
    the HLO instruction's name without its ``%`` and numeric suffix."""
    head = name.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"(\.\d+)+$", "", head)


def leaves(events: Sequence[Event]) -> List[Event]:
    """The events that enclose no other event (a ``while`` loop's op event
    spans the ops of its body on the same line)."""
    evs = sorted(events, key=lambda e: (e.start, -e.dur))
    out = []
    for i, e in enumerate(evs):
        nxt = evs[i + 1] if i + 1 < len(evs) else None
        if nxt is None or nxt.start >= e.end or nxt.dur == 0:
            out.append(e)
    return out


def top_ops(events: Sequence[Event], k: int = 10) -> List[Tuple[str, float]]:
    """The ``k`` operations (by :func:`op_name`, enclosing loops left out)
    that took most device time, in seconds."""
    tot: Dict[str, float] = {}
    for e in leaves(events):
        n = op_name(e.name)
        tot[n] = tot.get(n, 0.0) + e.dur
    return [[n, t * 1e-9] for n, t in
            sorted(tot.items(), key=lambda kv: -kv[1])[:k]]


def idle_by_host(events: Sequence[Event], host: Sequence[Event], t0: float,
                 t1: float, k: int = 10) -> List[Tuple[str, float]]:
    """Device idle time split by what the host was doing: each idle
    nanosecond goes to the innermost harness span covering it (the latest
    to start), or to ``outside bench spans``.  Seconds, largest first."""
    # label each elementary segment between span boundaries with the
    # innermost span covering it, then sweep the gaps across the segments
    cuts = sorted({t0, t1} | {x for e in host for x in (e.start, e.end)
                              if t0 < x < t1})
    segs = []
    for a, b in zip(cuts, cuts[1:]):
        mid = (a + b) / 2
        inner: Optional[Event] = None
        for e in host:
            if e.start <= mid < e.end and (inner is None
                                           or e.start >= inner.start):
                inner = e
        segs.append((a, b, inner.name if inner else "outside bench spans"))
    tot: Dict[str, float] = {}
    i = 0
    for g0, g1 in gaps(events, t0, t1):
        while i < len(segs) and segs[i][1] <= g0:
            i += 1
        j = i
        while j < len(segs) and segs[j][0] < g1:
            a, b, name = segs[j]
            tot[name] = tot.get(name, 0.0) + min(b, g1) - max(a, g0)
            j += 1
    return [[n, t * 1e-9] for n, t in
            sorted(tot.items(), key=lambda kv: -kv[1])[:k]]
