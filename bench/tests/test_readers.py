"""The per-layer readers on the recorded v5e trace slice: one step with a
512-token prefill chunk and one 64-row decode step of StarCoder2-15B (4 of
40 layers, ``data/starcoder2-15b-4L.json``) under code-completion traffic.
The slice does not record how many rows carried requests, so the work
given here is the most those launches can hold; a share computed from it
is the highest the reader could report, and must stay within 100%."""
import json
from pathlib import Path

import pytest

from harness import spec
from harness import trace as TR
from harness import weights as W
from harness.serving import StepWork, Window
from harness.readings import Absent, Readings

HERE = Path(__file__).parent
PEAKS = spec.peaks("TPU v5 lite")


@pytest.fixture(scope="module")
def readings():
    d = json.loads((HERE / "data" / "v5e_trace_slice.json").read_text())
    trace = TR.Trace(window=tuple(d["window"]),
                     devices=[[TR.Event(n, s, t, n) for n, s, t in d["device"]]],
                     host=[TR.Event(n, s, t) for n, s, t in d["host"]])
    cfg = json.loads((HERE / "data" / "starcoder2-15b-4L.json")
                     .read_text())
    steps = [StepWork(prefill=[(512, [0], [512], [True])]),
             StepWork(decode_ctx=[2048] * 64)]
    win = Window(t0=0, t1=1, tracks=[], stats0={"steps": 0,
                 "active_slot_steps": 0}, stats1={"steps": 10,
                 "active_slot_steps": 320}, compiles={"lowered": 1,
                 "compiled": 0}, compiled=["jit__lambda 0.5s"], lateness=[],
                 pairs={}, steps=steps)
    return Readings(dims=W.dims(cfg), engine={},
                    peaks=PEAKS, ptq_s=1.0, warm_s=1.0, setup_s=1.0,
                    window=win, trace=trace)


@pytest.mark.parametrize("name", ["w4a16_matmul_roofline",
                                  "paged_gqa_decode_roofline",
                                  "paged_gqa_prefill_roofline", "step_mfu"])
def test_shares_stay_within_the_peak(readings, name):
    v = spec.load_metric_reader(name).read(readings)
    assert 0 < v <= 100


def test_device_idle(readings):
    v = spec.load_metric_reader("device_idle").read(readings)
    busy = TR.busy_ns(readings.trace.devices[0])
    assert v == pytest.approx(100 * (1 - busy / 70e6))
    assert 0 < v < 50


def test_a_missing_kernel_fails_the_reading(readings):
    gone = TR.Trace(window=readings.trace.window,
                    devices=[[e for e in readings.trace.devices[0]
                              if "gqa_paged_attention" not in e.name]],
                    host=readings.trace.host)
    r = Readings(**{**readings.__dict__, "trace": gone})
    with pytest.raises(Absent):
        spec.load_metric_reader("paged_gqa_decode_roofline").read(r)


def test_untraced_runs_read_nothing(readings):
    r = Readings(**{**readings.__dict__, "trace": None})
    for name in ("w4a16_matmul_roofline", "device_idle", "step_mfu"):
        assert spec.load_metric_reader(name).read(r) is None


def test_every_per_layer_reader_gives_a_number(readings):
    bench = spec.load_json(spec.ROOT / "BENCHMARK.json")
    engine = {"batch_size": 64}
    r = Readings(**{**readings.__dict__, "engine": engine})
    for m in bench["per_layer"]:
        v = spec.load_metric_reader(m["name"]).read(r)
        # a number, or nothing where the run holds nothing to read (no
        # request was sent in this window)
        assert v is None or isinstance(float(v), float), m["name"]
