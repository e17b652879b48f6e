#!/usr/bin/env python3
"""Sweep the offered rate of one cell's traffic on one chip, to find the
knee: the highest rate the engine sustains without a growing queue.

    python3 bench/tools/sweep.py --workload <cell> --seed <n> \
        --rates 4,8,12 --seconds 30 [--warm 1x16,1x32,...]

One process builds the cell's model and engine once, warms the traffic
file's prefill pairs (and any given with ``--warm``), then serves the
cell's traffic at each rate for a ramp and a window, cancelling what is
left before the next rate.  Per rate it prints the offered and completed
rates, the queue at the close, output tokens per second, TTFT and ITL
percentiles, decode occupancy, compiles inside the window, and the
prefill (rows, bucket) pairs launched (the union goes into the traffic
file's warm list).
"""
from __future__ import annotations

import argparse
import copy
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from harness import spec  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--ramp", type=float, default=None)
    ap.add_argument("--warm", default="")
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    sys.path.insert(0, str(spec.ROOT / "src"))
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit("sweep: no TPU")
    from repro.launch import serve

    from harness import serving
    from harness import traffic as T
    from harness.readings import itl_gaps_ms, percentile

    serve.enable_compile_cache()
    tr = cell.traffic
    built = serving.build(cell.config, tr["engine"], args.seed, print)
    eng = built.eng
    pairs = [tuple(p) for p in tr["warm"]["prefill"]]
    pairs += [tuple(int(x) for x in w.split("x"))
              for w in args.warm.split(",") if w]
    print(f"[warm] {serving.warm(eng, pairs):.2f}s for {len(pairs)} pairs",
          flush=True)
    plan, dec = eng.sched.plan_chunks, eng._decode
    ramp = tr["ramp_s"] if args.ramp is None else args.ramp
    union = {}
    uid0 = 0
    for rate in [float(x) for x in args.rates.split(",")]:
        t = copy.deepcopy(tr)
        t["arrivals"]["rate_rps"] = rate
        arrivals = T.schedule(t, args.seed, ramp + args.seconds,
                              built.cfg.vocab_size)
        for a in arrivals:
            a.uid += uid0
        uid0 += len(arrivals) + 1
        w = serving.serve_window(eng, arrivals, ramp, args.seconds)
        eng.sched.plan_chunks, eng._decode = plan, dec
        sent = [tk for tk in w.tracks if w.t0 <= tk.sched_t < w.t1]
        done = [tk for tk in w.tracks if tk.req.done_t is not None
                and w.t0 <= tk.req.done_t <= w.t1]
        ntok = sum(1 for tk in w.tracks for s in tk.stamps
                   if w.t0 <= s <= w.t1)
        ttft = [((tk.stamps[0] if tk.stamps else w.t1) - tk.sched_t) * 1e3
                for tk in sent]
        gaps = itl_gaps_ms(w)
        s0, s1 = w.stats0, w.stats1
        steps = s1["steps"] - s0["steps"]
        occ = (s1["active_slot_steps"] - s0["active_slot_steps"]) / max(
            steps * eng.B, 1)
        for k, v in w.pairs.items():
            union[k] = union.get(k, 0) + v
        row = {"rate": rate, "sent_rps": len(sent) / args.seconds,
               "done_rps": len(done) / args.seconds,
               "queue_at_close": len(eng.queue),
               "out_tok_per_s": ntok / args.seconds,
               "ttft_p50_ms": percentile(ttft, 50),
               "ttft_p95_ms": percentile(ttft, 95),
               "itl_p50_ms": percentile(gaps, 50),
               "itl_p95_ms": percentile(gaps, 95),
               "decode_occupancy": occ, "steps": steps,
               "compiles": w.compiles,
               "late_p95_ms": (percentile(w.lateness, 95) or 0) * 1e3,
               "pairs": sorted(w.pairs)}
        print("[rate] " + json.dumps(row), flush=True)
        for tk in w.tracks:
            if tk.req.finish_reason is None:
                eng.cancel(tk.req.uid)
    print("[pairs] " + json.dumps(sorted(
        ([k[0], k[1], v] for k, v in union.items()), key=lambda x: -x[2])))
    return 0


if __name__ == "__main__":
    sys.exit(main())
