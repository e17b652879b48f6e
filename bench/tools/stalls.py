#!/usr/bin/env python3
"""Run one cell untraced, as ``bench/run.py`` does, and report its longest
engine steps with what overlapped them on the host: GC pauses and
``jax.monitoring`` events (tracing, lowering, compiling).

    python3 bench/tools/stalls.py --workload <cell> --seed <n> [--seconds 50]

Use it where a run's generator lateness shows a stall of seconds: a step
that overlapped neither names a cause outside the host's Python.
"""
import argparse
import gc
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import run  # noqa: E402
from harness import serving, spec  # noqa: E402

SLOW_S = 0.3                  # a step this long keeps its journal record


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=50.0)
    ap.add_argument("--top", type=int, default=6)
    args = ap.parse_args()
    steps, slow, gcs, mon = [], {}, [], []
    serve = serving.serve_window

    def timed_serve(eng, *a, **kw):
        step = eng.step

        def timed():
            t = time.perf_counter()
            n = step()
            d = time.perf_counter() - t
            steps.append((t, d, eng._step_idx))
            if d > SLOW_S and eng.trace.journal:
                r = eng.trace.journal[-1]
                slow[eng._step_idx] = {
                    "decode_rows": len(r.decode_slots), "prefill": r.prefill,
                    "pages_used": r.pages_used, "queued": len(eng.queue)}
            return n

        eng.step = timed
        return serve(eng, *a, **kw)

    serving.serve_window = timed_serve
    g0 = [0.0]

    def on_gc(phase, info):
        if phase == "start":
            g0[0] = time.perf_counter()
        else:
            gcs.append((g0[0], time.perf_counter() - g0[0],
                        info["generation"]))

    gc.callbacks.append(on_gc)
    import jax

    jax.monitoring.register_event_duration_secs_listener(
        lambda ev, d, **kw: mon.append(
            (time.perf_counter(), d, ev.rsplit("/", 1)[-1],
             str(kw.get("fun_name")))))
    out = run.run_cell(spec.load_cell(args.workload), args.seed,
                       args.seconds, False)
    print(json.dumps({"correct": out["correct"],
                      "metrics": {k: v["value"]
                                  for k, v in out["metrics"].items()},
                      "steps": len(steps),
                      "gc_max_s": max([g[1] for g in gcs] or [0.0])}))
    t_first = steps[0][0] if steps else 0.0
    for t, d, idx in sorted(steps, key=lambda s: -s[1])[:args.top]:
        events = {}
        for m in mon:
            if t <= m[0] <= t + d:
                k = f"{m[2]}:{m[3]}"
                events[k] = events.get(k, 0.0) + m[1]
        print(json.dumps({
            "step": idx, "seconds": d, "at_s": t - t_first,
            "work": slow.get(idx),
            "gc": [(g[1], g[2]) for g in gcs if t <= g[0] <= t + d
                   and g[1] > 0.005],
            "events": dict(sorted(events.items(),
                                  key=lambda kv: -kv[1])[:8])}))


if __name__ == "__main__":
    main()
