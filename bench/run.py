#!/usr/bin/env python3
"""Run one benchmark cell on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process does the whole run: it checks that JAX sees a TPU (and as many
chips as the cell asks for), turns on the persistent compile cache, makes
the cell's weights on the device from the seed, runs SmoothQuant+
quantize-on-load, builds the serving engine as ``launch/serve.py`` builds
it, warms the programs the cell's traffic reaches, ramps the traffic up,
and serves the measured window open loop.  Then it frees the program's
state, compares a sample of the served requests with the plain reference
(``correct``), and prints one JSON line last on stdout.

``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics, read from a profiler trace of a few seconds inside the
window.  ``--control`` puts the reference's int8-activation control in the
program's place in the comparison (it must come out not correct).

Cells, configurations, traffic mixes and metrics are found by the names in
``BENCHMARK.json``: ``bench/configs``, ``bench/traffic`` and
``bench/metrics`` hold one file each.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Any, Dict  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

from harness import spec  # noqa: E402

TRACE_S = 4.0                       # seconds profiled in a --trace 1 run
FAILED = ("rejected", "failed", "expired", "cancelled")


def say(*a) -> None:
    print(*a, flush=True)


def _ms(xs, q):
    from harness.readings import percentile
    v = percentile(xs, q)
    return "n/a" if v is None else f"{v * 1e3:.3f}ms"


def require_tpu(devs, chips: int) -> None:
    """Refuse to run unless JAX sees a TPU with at least ``chips`` chips."""
    dev = devs[0]
    if dev.platform != "tpu":
        raise SystemExit(f"bench: JAX found no TPU (platform "
                         f"{dev.platform!r}); nothing was run")
    if len(devs) < chips:
        raise SystemExit(f"bench: the cell needs {chips} chips, JAX sees "
                         f"{len(devs)}")


def run_cell(cell: spec.Cell, seed: int, seconds: float, trace: bool, *,
             control: bool = False,
             t_start: float = T_START) -> Dict[str, Any]:
    """One run of ``cell``; returns the result line's object."""
    src = spec.ROOT / "src"
    if not (src / "repro").is_dir():
        raise SystemExit(f"bench: the program's sources ({src}) are missing")
    if str(src) not in sys.path:
        sys.path.insert(0, str(src))
    import jax

    devs = jax.devices()
    dev = devs[0]
    say(f"[device] platform={dev.platform} device_kind={dev.device_kind} "
        f"count={len(devs)}")
    require_tpu(devs, cell.chips)
    peaks = spec.peaks(dev.device_kind) if trace else {}

    from repro.launch import serve

    from harness import check, serving
    from harness import trace as TR
    from harness import traffic as T
    from harness import weights as W
    from harness.readings import Absent, Readings

    say(f"[cache] persistent compile cache at {serve.enable_compile_cache()}")
    tr, config = cell.traffic, cell.config
    built = serving.build(config, tr["engine"], seed, say)
    pairs = [tuple(p) for p in tr["warm"]["prefill"]]
    warm_s = serving.warm(built.eng, pairs)
    say(f"[warm] {warm_s:.3f}s: decode [{built.eng.B} rows], sampler, "
        f"prefill (rows, bucket) pairs {pairs}")
    arrivals = T.schedule(tr, seed, tr["ramp_s"] + seconds,
                          built.cfg.vocab_size)
    trace_dir = None
    if trace:
        trace_dir = str(spec.ROOT / ".bench_traces" / cell.name)
        shutil.rmtree(trace_dir, ignore_errors=True)
    win = serving.serve_window(built.eng, arrivals, tr["ramp_s"], seconds,
                              trace_s=min(TRACE_S, seconds / 2) if trace
                              else 0.0, trace_dir=trace_dir)
    setup_s = win.t0 - t_start
    stats = dev.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    in_use = stats.get("bytes_in_use")

    sent = [tk for tk in win.tracks if win.t0 <= tk.sched_t < win.t1]
    done = [tk for tk in sent if tk.req.finish_reason is not None
            and tk.req.finish_reason not in FAILED]
    failed = [tk for tk in sent if tk.req.finish_reason in FAILED]
    say(f"[window] {seconds:.1f}s after a {tr['ramp_s']}s ramp at "
        f"{tr['arrivals']['rate_rps']} req/s: sent {len(sent)}, completed "
        f"{len(done)}, failed {len(failed)}, unfinished "
        f"{len(sent) - len(done) - len(failed)}; queue at close "
        f"{len(built.eng.queue)}")
    say(f"[generator] lateness (submit - scheduled) p50 "
        f"{_ms(win.lateness, 50)} p95 {_ms(win.lateness, 95)} max "
        f"{max(win.lateness) * 1e3 if win.lateness else 0:.3f}ms")
    say(f"[compiles] inside the window: {win.compiles['lowered']} lowered, "
        f"{win.compiles['compiled']} compiled by XLA {win.compiled}")
    seen = sorted(win.pairs)
    say(f"[prefill] (rows, bucket) pairs launched {seen}; not warmed "
        f"{sorted(set(seen) - set(pairs))}")
    say(f"[memory] peak device bytes in use {peak} (set by quantize-on-load); "
        f"in use at the window's close {in_use} (weights, KV pool, "
        f"buffers)")

    finished = [check.Served(tk.req.prompt, list(tk.req.output))
                for tk in win.tracks if tk.req.finish_reason == "length"]
    ptq_s = built.ptq_s
    eng = built.eng
    for leaf in jax.tree.leaves((eng.params, eng.pools)):
        leaf.delete()
    del eng, built
    gc.collect()

    t0 = time.perf_counter()
    chk = tr["check"]
    served = check.sample(finished, seed, chk["min_tokens"],
                          chk["max_requests"])
    res = check.compare(config, seed, served, control=control)
    numbers = check.verdict(config["check"], res, control=control)
    correct = bool(numbers and all(c["ok"] for c in numbers.values())
                   and res["tokens_compared"] > 0)
    say(f"[check] {json.dumps(res)} in {time.perf_counter() - t0:.2f}s"
        + (" (control in the program's place)" if control else ""))

    traced = TR.load(trace_dir, serving.SPAN_TRACED) if trace else None
    r = Readings(dims=W.dims(config), engine=tr["engine"],
                 peaks=peaks, ptq_s=ptq_s, warm_s=warm_s, setup_s=setup_s,
                 window=win, trace=traced)
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        try:
            v = spec.load_metric_reader(m["name"]).read(r)
        except Absent as e:
            raise SystemExit(f"bench: metric {m['name']}: {e}")
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    out = {"correct": correct, "attempted": len(sent), "failed": len(failed),
           "metrics": metrics, "device": device}
    if traced is not None:
        busy = [TR.busy_ns(d) for d in traced.devices]
        device["busy_s"] = sum(busy) / len(busy) * 1e-9
        device["window_s"] = traced.window_s
        ops = [e for d in traced.devices for e in d]
        out["breakdown"] = {
            "device_ops": TR.top_ops(ops),
            "idle_gaps": TR.idle_by_host(traced.devices[0], traced.host,
                                         *traced.window)}
        say(f"[trace] {len(ops)} device ops, {len(r.window.steps)} engine "
            f"steps in {traced.window_s:.3f}s traced")
    out["reference"] = res
    out["checks"] = {
        **{n: {"value": c["value"], "limit": c["limit"]}
           for n, c in numbers.items()},
        "tokens_compared": {"value": res["tokens_compared"], "limit": 1}}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", action="store_true",
                    help="compare the int8-activation control in place of "
                         "the program (expected: not correct)")
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    out = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                   control=args.control)
    for name, c in out["checks"].items():
        rel = ">=" if name == "tokens_compared" else "<="
        print(f"check {name} {c['value']} limit {rel} {c['limit']}",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
