"""``BENCHMARK.json`` against the benchmark's contract, and the files it
names.  A later cell, configuration or metric adds files and entries; these
tests then cover it too."""
import json
import re
import subprocess
import sys

import pytest

from harness import spec

ROOT = spec.ROOT
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_keys_names_and_units():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in BENCH[k]]
    assert all(NAME.match(n) for n in names)
    assert len(set(names)) == len(names)
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        for c in m.get("workloads", CELLS):
            assert spec.applies(next(x for x in BENCH["end_to_end"]
                                     if x["name"] == m["moves"]), c)


@pytest.mark.parametrize("cell", CELLS)
def test_cell_files_and_metrics(cell):
    c = spec.load_cell(cell)
    assert c.chips in (1, 4)
    assert {m["name"] for m in c.end_to_end} > {"setup_s"}
    assert c.per_layer
    for m in c.end_to_end + c.per_layer:
        assert callable(spec.load_metric_reader(m["name"]).read)
    tr = c.traffic
    for key in ("arrivals", "prompt_tokens", "output_tokens", "engine",
                "warm", "check", "ramp_s", "sizes_seed"):
        assert key in tr
    assert any(v is not None for v in c.config["check"].values())


@pytest.mark.parametrize("cell", CELLS)
def test_config_matches_the_program(cell):
    from harness import serving
    from harness import weights as W

    c = spec.load_cell(cell)
    entry = next(x for x in BENCH["configs"]
                 if x["name"] == next(w["config"] for w in BENCH["workloads"]
                                      if w["name"] == cell))
    assert set(entry["reduced"]) == set(c.config["reduced"])
    cfg = serving.program_config(c.config)
    d = W.dims(c.config)
    assert cfg.num_layers == d.layers and cfg.d_model == d.d_model


def test_refuses_without_a_tpu(tmp_path):
    env = {"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin",
           "HOME": str(tmp_path)}
    p = subprocess.run([sys.executable, str(ROOT / "bench" / "run.py"),
                        "--workload", CELLS[0], "--seed", str(2**33 + 1),
                        "--seconds", "1", "--trace", "0"],
                       cwd=ROOT, env=env, capture_output=True, text=True,
                       timeout=300)
    assert p.returncode != 0
    assert '"correct"' not in p.stdout
    assert "no TPU" in p.stderr
