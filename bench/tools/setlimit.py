#!/usr/bin/env python3
"""Set a cell's limits on the numbers ``correct`` compares, from
``seeds.py`` readings.

    python3 bench/tools/setlimit.py <cell> <seeds.jsonl> [--write]

For each number (``harness.check.NUMBERS``) the lower reading is the
largest the program's sound runs gave, the upper the smallest the control
gave.  Where the upper is three times the lower or more, the limit is
``lower + 0.6 * (upper - lower)``: room on both sides, the more of it above
the lower.  A number whose readings do not separate so gets no limit and
is not compared.  ``--write`` stores the limits in the cell's
configuration file.
"""
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from harness import spec  # noqa: E402
from harness.check import NUMBERS  # noqa: E402


def main(cell, path, write=False):
    rows = [json.loads(x) for x in open(path) if x.strip()]
    limits = {}
    for n in NUMBERS:
        prog = sorted(r[n] for r in rows)
        ctl = sorted(r["control_" + n] for r in rows)
        lower, upper = prog[-1], ctl[0]
        print(f"{cell} {n}: {len(rows)} seeds, program "
              f"{[round(x, 4) for x in prog]}, control "
              f"{[round(x, 4) for x in ctl]}")
        if upper < 3 * lower or upper <= 0:
            print(f"  no limit: control {upper:.4f} < 3 x program "
                  f"{lower:.4f}")
            limits[n] = None
            continue
        limits[n] = float(f"{lower + 0.6 * (upper - lower):.4g}")
        print(f"  lower {lower:.4f} upper {upper:.4f} limit {limits[n]}")
    if not any(v is not None for v in limits.values()):
        return 1
    if write:
        bench = spec.load_json(spec.ROOT / "BENCHMARK.json")
        w = next(x for x in bench["workloads"] if x["name"] == cell)
        f = spec.ROOT / next(c["file"] for c in bench["configs"]
                             if c["name"] == w["config"])
        cfg = json.loads(f.read_text())
        cfg["check"] = limits
        f.write_text(json.dumps(cfg, indent=2) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1], sys.argv[2], "--write" in sys.argv[3:]))
