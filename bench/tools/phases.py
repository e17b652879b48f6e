#!/usr/bin/env python3
"""Print where a traced run's device idle time and device time went, by the
program's own marks:

    python3 bench/tools/phases.py .bench_traces/<cell>

* the device's idle time by the innermost engine span (``idle_phases``),
  beside the harness's ``idle_gaps`` split by ``bench.*`` span;
* host time per engine span per step;
* each step program's module events (count, median ms), and the device
  ops inside each program's events (which program holds which ops);
* the clock check: how far each ``engine.token_wait`` ends after the end
  of its step's ``jit_decode_step`` module event.

The last line on stdout is the whole result as JSON.
"""
import json
import re
import statistics
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from harness import program as PG  # noqa: E402
from harness import trace as TR  # noqa: E402
from harness.serving import SPAN_TRACED  # noqa: E402

DECODE = r"^jit_decode_step\b"


def module_name(name: str) -> str:
    """``jit_decode_step(123)`` -> ``jit_decode_step``."""
    return re.sub(r"\(\d+\)$", "", name)


def ops_by_module(ops, modules, k=6):
    """Device seconds of each op (enclosing loops left out) under each
    module event; ops outside every module event go to ``none``."""
    mods = sorted(modules, key=lambda e: e.start)
    out, j = {}, 0
    for e in sorted(TR.leaves(ops), key=lambda e: e.start):
        while j < len(mods) and mods[j].end <= e.start:
            j += 1
        m = (module_name(mods[j].name)
             if j < len(mods) and mods[j].start <= e.start else "none")
        per = out.setdefault(m, {})
        n = TR.op_name(e.name)
        per[n] = per.get(n, 0.0) + e.dur * 1e-9
    return {m: sorted(per.items(), key=lambda kv: -kv[1])[:k]
            for m, per in out.items()}


def clock_leads(waits, decodes):
    """Lead of each token wait's end over the end of the decode module event
    that ends nearest to it, in ms."""
    ends = sorted(e.end for e in decodes)
    out = []
    for w in waits:
        near = min(ends, key=lambda t: abs(w.end - t))
        out.append((w.end - near) * 1e-6)
    return out


def main(trace_dir: str) -> dict:
    tr = TR.load(trace_dir, SPAN_TRACED)
    prog = PG.read_profile(TR.find_xplane(trace_dir))
    t0, t1 = tr.window
    dev = tr.devices[0]
    idle = PG.idle_phases(dev, prog.spans, t0, t1)
    gaps = dict(TR.idle_by_host(dev, tr.host, t0, t1))
    layers = {}
    for name, s in idle.items():
        layer = PG.layer_of(name)
        layers[layer] = layers.get(layer, 0.0) + s
    steps = sum(e.name == PG.STEP for e in prog.spans)
    host = {}
    for e in prog.spans:
        host[e.name] = host.get(e.name, 0.0) + e.dur * 1e-6
    mods = prog.modules[0] if prog.modules else []
    by_mod = {}
    for e in mods:
        by_mod.setdefault(module_name(e.name), []).append(e.dur * 1e-6)
    waits = [e for e in prog.spans if e.name == "engine.token_wait"]
    decodes = TR.matching(mods, DECODE)
    leads = clock_leads(waits, decodes) if decodes else []
    res = {
        "window_s": tr.window_s,
        "device_idle_s": sum(b - a for a, b in TR.gaps(dev, t0, t1)) * 1e-9,
        "idle_gaps": gaps,
        "idle_phases": dict(sorted(idle.items(), key=lambda kv: -kv[1])),
        "idle_layers_pct": {k: 100 * v / tr.window_s
                            for k, v in layers.items()},
        "steps": steps,
        "host_ms_per_step": {k: v / max(steps, 1) for k, v in
                             sorted(host.items(), key=lambda kv: -kv[1])},
        "spans_per_step": len(prog.spans) / max(steps, 1),
        "modules": {m: {"n": len(v), "median_ms": statistics.median(v),
                        "total_ms": sum(v)} for m, v in by_mod.items()},
        "token_waits": len(waits),
        "ops_by_module": ops_by_module(dev, mods),
        "clock_lead_ms": ({"median": statistics.median(leads),
                           "max": max(leads), "min": min(leads)}
                          if leads else None),
    }
    covered = sum(v for k, v in layers.items() if k != PG.OUTSIDE)
    print(f"window {res['window_s']:.3f} s, device idle "
          f"{res['device_idle_s']:.4f} s, {steps} engine steps; idle in "
          f"engine spans {covered:.4f} s, bench.step "
          f"{gaps.get('bench.step', 0.0):.4f} s")
    for k, v in res["idle_phases"].items():
        print(f"  idle {k:28s} {v * 1e3:9.3f} ms")
    for k, v in res["modules"].items():
        print(f"  module {k:34s} n={v['n']:4d} median {v['median_ms']:.3f} ms")
    print(f"  token waits {len(waits)}, clock lead {res['clock_lead_ms']}")
    print(json.dumps(res))
    return res


if __name__ == "__main__":
    main(sys.argv[1])
