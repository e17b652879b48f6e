#!/usr/bin/env python3
"""Print what a profiler trace holds: its planes and lines, and on each
device's op line the operations that took most time, with their stats.

    python3 bench/tools/inspect_trace.py .bench_traces/<cell> [top]

Use it to see how kernels are named before writing a metric's pattern.
"""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from harness import trace as TR  # noqa: E402


def main(trace_dir: str, top: int = 40) -> None:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(TR.find_xplane(trace_dir))
    for plane in pd.planes:
        lines = list(plane.lines)
        print(f"plane {plane.name!r}: lines {[ln.name for ln in lines]}")
        for ln in lines:
            evs = list(ln.events)
            if not evs:
                continue
            print(f"  line {ln.name!r}: {len(evs)} events, first start "
                  f"{evs[0].start_ns}, last end "
                  f"{evs[-1].start_ns + evs[-1].duration_ns}")
            if not TR.DEVICE_PLANE.match(plane.name) or ln.name != TR.OPS_LINE:
                continue
            tot, example = {}, {}
            for e in evs:
                tot[e.name] = tot.get(e.name, 0.0) + e.duration_ns
                example.setdefault(e.name, [(k, str(v)[:160])
                                            for k, v in e.stats])
            for name, ns in sorted(tot.items(), key=lambda kv: -kv[1])[:top]:
                print(f"    {ns * 1e-6:10.3f} ms  {name}  {example[name]}")


if __name__ == "__main__":
    main(sys.argv[1], int(sys.argv[2]) if len(sys.argv) > 2 else 40)
