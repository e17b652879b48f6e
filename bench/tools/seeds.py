#!/usr/bin/env python3
"""Read the numbers ``correct`` compares, for the program and for the
control, on many seeds of one cell in one process (set-up is most of a
run, and this shares the process start and the in-process compile caches).

    python3 bench/tools/seeds.py --workload <cell> --seconds <s> \
        --seeds 11,12,13 [--out seeds.jsonl]

Each seed is a whole run of the cell as ``bench/run.py`` makes it (weights
from the seed, quantize-on-load, warm-up, ramp, window), followed by the
reference with the control beside it.  One JSON line per seed: the
program's widest and mean logit gaps, the control's, and the cell's
end-to-end metrics (``setup_s`` here is not a cold process's).
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import run  # noqa: E402
from harness import check, spec  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    for seed in [int(s) for s in args.seeds.split(",")]:
        out = run.run_cell(cell, seed, args.seconds, False, control=True,
                           t_start=time.perf_counter())
        ref = out["reference"]
        row = {"seed": seed, "correct": out["correct"],
               "attempted": out["attempted"], "failed": out["failed"],
               **{k: ref.get(k) for n in check.NUMBERS
                  for k in (n, "control_" + n)},
               "agree": ref.get("agree_share"),
               "control_agree": ref.get("control_agree_share"),
               "tokens": ref.get("tokens_compared"),
               "requests": ref.get("requests_compared"),
               "metrics": {k: v["value"] for k, v in out["metrics"].items()},
               "peak": out["device"]["memory_peak_bytes"]}
        line = json.dumps(row)
        print("[seed] " + line, flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
