"""Find a cell's files by the names ``BENCHMARK.json`` gives.

A cell (an entry of ``workloads``) names a configuration and a traffic mix.
The configuration is ``bench/configs/<name>.json`` (the file the
``configs`` entry names), the traffic mix ``bench/traffic/<traffic>.json``,
and each metric the cell reports is an entry of ``end_to_end`` or
``per_layer``; a per-layer metric's reader is ``bench/metrics/<name>.py``.
Adding a cell means adding files and entries, never editing these.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from typing import Any, Dict, List

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]


def load_json(path: Path) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def applies(metric: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    bench = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    confs = {c["name"]: c for c in bench["configs"]}
    config = load_json(root / confs[w["config"]]["file"])
    traffic = load_json(root / "bench" / "traffic" / f"{w['traffic']}.json")
    return Cell(
        name=name, chips=int(w["chips"]), config=config, traffic=traffic,
        end_to_end=[m for m in bench["end_to_end"] if applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if applies(m, name)])


def load_metric_reader(name: str, root: Path = ROOT):
    """The module ``bench/metrics/<name>.py`` (names may hold dots, so it
    is loaded by path)."""
    path = root / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def peaks(device_kind: str, root: Path = ROOT) -> Dict[str, float]:
    """Published peaks of one chip, by ``device_kind``.  An unknown kind is
    an error, never a default."""
    table = load_json(root / "bench" / "harness" / "peaks.json")
    if device_kind not in table:
        raise SystemExit(f"no peak figures for device kind {device_kind!r} "
                         f"in bench/harness/peaks.json ({sorted(table)})")
    return table[device_kind]
