"""The comparison that decides ``correct``, driven through a whole run of
a tiny cell on the CPU (the program's Mistral-Large smoke widths; XLA paths
in place of the Pallas kernels).  The look for a chip is skipped; everything
else is the run the benchmark makes on the chip.

* the program's run is correct;
* the control (the reference with each linear layer's input held in int8
  per token) in the program's place is not;
* so is a run whose timed path is broken underneath: a served token
  altered where it is sampled, and a decode step whose KV writes and reads
  go to the trash page (the cache left as it was).
"""
import json
import time
from pathlib import Path

import jax.numpy as jnp
import pytest

import run
from harness import spec

DATA = Path(__file__).parent / "data"
SEED = 2
CELL = "mistral-chat"


@pytest.fixture(scope="module")
def cell():
    bench = spec.load_json(spec.ROOT / "BENCHMARK.json")
    return spec.Cell(name=CELL, chips=1,
                     config=spec.load_json(DATA / "tiny-mistral.json"),
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


def test_control_is_not_correct(cell, off_chip):
    out = go(cell, control=True)
    assert out["correct"] is False
    assert any(c["value"] > c["limit"] for n, c in out["checks"].items()
               if n != "tokens_compared")


def alter_token(eng):
    """Every sampled decode token moves to its neighbour in the vocab."""
    sample = eng._sample

    def bad(logits, key, temps, *a):
        out = sample(logits, key, temps, *a)
        return (out + 1) % eng.cfg.vocab_size if out.shape[0] == eng.B else out

    eng._sample = bad


def stale_cache(eng):
    """Decode steps write and read the trash page: the cache keeps its
    state from before the step."""
    decode = eng._decode

    def bad(params, pools, tok, pos, table):
        return decode(params, pools, tok, pos, jnp.zeros_like(table))

    eng._decode = bad


@pytest.mark.parametrize("fault", [alter_token, stale_cache])
def test_a_broken_path_is_not_correct(cell, off_chip, break_engine, fault):
    break_engine(fault)
    out = go(cell)
    assert out["correct"] is False
