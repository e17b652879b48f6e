#!/usr/bin/env python3
"""Time one engine phase span (``TraceRecorder.phase``: a
``jax.profiler.TraceAnnotation`` with the step index) on this host, with no
profiler attached and with one recording:

    python3 bench/tools/span_cost.py [n]

Prints microseconds per span, enter and exit together.  The profile it
records goes to ``.bench_traces/span_cost`` and is deleted after.
"""
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "src"))


def per_span_us(phase, n: int) -> float:
    t = time.perf_counter()
    for _ in range(n):
        with phase("decode_inputs"):
            pass
    return (time.perf_counter() - t) / n * 1e6


def main(n: int = 50_000) -> None:
    import jax

    from repro.serving.trace import TraceRecorder

    rec = TraceRecorder(time.perf_counter, enabled=False)
    rec.begin_step(1)
    per_span_us(rec.phase, n // 10)            # warm
    off = per_span_us(rec.phase, n)
    out = ROOT / ".bench_traces" / "span_cost"
    jax.profiler.start_trace(str(out))
    try:
        on = per_span_us(rec.phase, n)
    finally:
        jax.profiler.stop_trace()
        shutil.rmtree(out, ignore_errors=True)
    print(f"span cost: {off:.3f} us with no profiler, {on:.3f} us with one "
          f"recording ({n} spans, {jax.devices()[0].platform})")


if __name__ == "__main__":
    main(int(sys.argv[1]) if len(sys.argv) > 1 else 50_000)
