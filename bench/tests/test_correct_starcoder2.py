"""The comparison that decides ``correct`` for a biased model, driven
through a whole run of a tiny cell on the CPU (the program's StarCoder2-15B
smoke widths: LayerNorm, a bias on every linear, tanh-GELU MLP; XLA paths in
place of the Pallas kernels).  The look for a chip is skipped; everything
else is the run the benchmark makes on the chip.

* the program's run is correct;
* a run whose timed path is broken underneath is not: a served token
  altered where it is sampled, and a decode step whose KV writes and reads
  go to the trash page;
* nor is a run whose quantize-on-load leaves the value bias unscaled, the
  fault SmoothQuant+ carries for this model.  At these smoke widths the
  int8 control cannot be told from the program (``tiny-starcoder2.json``
  gives the readings), so the limits are set against this fault instead.
"""
import json
import time
from pathlib import Path

import pytest

import run
from harness import spec
from test_correct import alter_token, stale_cache

DATA = Path(__file__).parent / "data"
SEED = 2
CELL = "sc2-code-sat"


@pytest.fixture(scope="module")
def cell():
    bench = spec.load_json(spec.ROOT / "BENCHMARK.json")
    return spec.Cell(name=CELL, chips=1,
                     config=spec.load_json(DATA / "tiny-starcoder2.json"),
                     traffic=spec.load_json(DATA / "tiny-traffic.json"),
                     end_to_end=[m for m in bench["end_to_end"]
                                 if spec.applies(m, CELL)],
                     per_layer=[m for m in bench["per_layer"]
                                if spec.applies(m, CELL)])


def go(cell, **kw):
    out = run.run_cell(cell, SEED, 4.0, False, t_start=time.perf_counter(),
                       **kw)
    json.dumps(out)                      # the line is valid JSON
    return out


def test_program_is_correct_and_reports_its_metrics(cell, off_chip):
    out = go(cell)
    assert out["correct"] is True
    for name, c in out["checks"].items():
        if name != "tokens_compared":
            assert c["value"] <= c["limit"], name
    assert out["checks"]["tokens_compared"]["value"] >= 400
    assert out["failed"] == 0 and out["attempted"] > 0
    assert set(out["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert list(out)[-1] == "checks"


def test_unscaled_value_bias_is_not_correct(cell, off_chip, monkeypatch):
    """Smoothing divides the value weight's columns but not its bias, so
    the served model computes ``x Wv / s + b`` for ``(x Wv + b) / s``."""
    from repro.core import smoothing

    monkeypatch.setattr(smoothing, "_bias_path", lambda params, wp: None)
    out = go(cell)
    assert out["correct"] is False
    assert any(c["value"] > c["limit"] for n, c in out["checks"].items()
               if n != "tokens_compared")


@pytest.mark.parametrize("fault", [alter_token, stale_cache])
def test_a_broken_path_is_not_correct(cell, off_chip, break_engine, fault):
    break_engine(fault)
    out = go(cell)
    assert out["correct"] is False
