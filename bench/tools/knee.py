#!/usr/bin/env python3
"""Read a ``sweep.py`` output and print the knee and the cell's rate.

    python3 bench/tools/knee.py <sweep.out> <times> [ramp_s]

The knee is the highest swept rate the engine sustained: the queue at the
window's close holds at most 5% of what was offered over ramp and window,
plus one.  Prints ``<times * knee> <pairs>``, the pairs being every prefill
(rows, bucket) pair the sweep launched, as ``RxB`` joined by commas.
"""
import json
import sys


def main(path, times, ramp=15.0):
    rows, pairs = [], []
    for line in open(path):
        if line.startswith("[rate] "):
            rows.append(json.loads(line[7:]))
        elif line.startswith("[pairs] "):
            pairs = json.loads(line[8:])
    secs = None
    ok = []
    for r in rows:
        offered = r["rate"] * (ramp + 30.0)
        if r["queue_at_close"] <= 0.05 * offered + 1:
            ok.append(r["rate"])
    knee = max(ok) if ok else min(r["rate"] for r in rows)
    print(f"{times * knee:.4g}", ",".join(f"{n}x{b}" for n, b, _ in pairs))
    print(f"knee {knee} from {[(r['rate'], r['queue_at_close']) for r in rows]}",
          file=sys.stderr)


if __name__ == "__main__":
    main(sys.argv[1], float(sys.argv[2]),
         float(sys.argv[3]) if len(sys.argv) > 3 else 15.0)
