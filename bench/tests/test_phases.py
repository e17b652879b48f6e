"""The readers of the program's phase spans and step-program module events:
on a trace built by hand whose idle split is known, on a profile written on
the CPU, and beside the readers that were there before them (unchanged on
the recorded v5e slice)."""
import json
from pathlib import Path

import pytest

from harness import program as PG
from harness import spec
from harness import trace as TR
from harness import weights as W
from harness.readings import Absent, Readings
from harness.serving import StepWork, Window

HERE = Path(__file__).parent
US = 1000.0                           # ns
IDLE = ("idle_scheduler", "idle_pager", "idle_launch", "idle_step_other")
READERS = IDLE + ("decode_step_device_ms",)


def ev(name, a, b):
    return TR.Event(name, a * US, (b - a) * US)


# two engine steps in a 1000 us window, 100 us outside any step between them
SPANS = [
    ev("engine.step", 0, 500),
    ev("engine.admit", 10, 60),
    ev("engine.prefill", 60, 200),
    ev("engine.plan_chunks", 60, 80),
    ev("engine.prefill_inputs", 80, 120),
    ev("engine.prefill_dispatch", 120, 140),
    ev("engine.first_token_wait", 140, 180),
    ev("engine.emit", 180, 200),
    ev("engine.check_tables", 200, 230),
    ev("engine.decode", 230, 450),
    ev("engine.decode_inputs", 230, 260),
    ev("engine.decode_dispatch", 260, 280),
    ev("engine.token_wait", 280, 420),
    ev("engine.emit", 420, 440),
    ev("engine.drain_swap", 450, 470),
    ev("engine.journal", 470, 490),
    ev("engine.step", 600, 1000),
    ev("engine.expire", 600, 620),
    ev("engine.grow", 620, 650),
    ev("engine.decode", 650, 1000),
    ev("engine.decode_dispatch", 650, 700),
    ev("engine.token_wait", 700, 990),
]
# the device idles in 0-130, 410-705 and 980-1000 us
OPS = [ev("%fusion.1", 130, 275), ev("%w4a16_matmul.2", 275, 410),
       ev("%w4a16_matmul.3", 705, 980)]
MODULES = [ev("jit_prefill_chunk_step(7)", 130, 275),
           ev("jit_decode_step(9)", 275, 410),
           ev("jit_decode_step(9)", 705, 980)]
# by hand, in us: scheduler admit 50 + plan_chunks 20 + expire 20; pager
# grow 30 + drain_swap 20; launch prefill_inputs 40 + prefill_dispatch 10 +
# decode_dispatch 50; the rest: step self 10 + 10, token_wait 10 + 5 + 10,
# emit 20, decode self 10 + 10, journal 20; outside any step 100
WANT = {"idle_scheduler": 9.0, "idle_pager": 5.0, "idle_launch": 10.0,
        "idle_step_other": 10.5}
OUTSIDE = 10.0


def readings(program=SPANS, modules=(MODULES,), host=(), trace=True):
    tr = TR.Trace(window=(0.0, 1000 * US), devices=[list(OPS)],
                  host=list(host))
    if program is not None:
        tr.program, tr.modules = list(program), [list(m) for m in modules]
    win = Window(t0=0, t1=1, tracks=[], stats0={}, stats1={}, compiles={},
                 compiled=[], lateness=[], pairs={}, steps=[StepWork()])
    return Readings(dims=None, engine={}, peaks={}, ptq_s=0, warm_s=0,
                    setup_s=0, window=win, trace=tr if trace else None)


def read(name, r):
    return spec.load_metric_reader(name).read(r)


def test_idle_split_by_hand():
    r = readings()
    for name, want in WANT.items():
        assert read(name, r) == pytest.approx(want, abs=1e-9), name
    assert read("decode_step_device_ms", r) == pytest.approx(0.205)


def test_idle_shares_and_outside_make_up_device_idle():
    r = readings()
    total = sum(read(n, r) for n in IDLE) + OUTSIDE
    assert total == pytest.approx(read("device_idle", r), abs=1e-9)
    assert read("device_idle", r) == pytest.approx(44.5)
    parts = PG.idle_phases(OPS, SPANS, 0.0, 1000 * US)
    assert parts[PG.OUTSIDE] == pytest.approx(100e-6)


def test_sweep_matches_the_host_span_rule():
    for dev, host, (t0, t1) in [
            (OPS, SPANS, (0.0, 1000 * US)),
            (OPS, SPANS, (50 * US, 900 * US)),
            (*slice_events(), slice_window())]:
        want = dict(TR.idle_by_host(
            dev, sorted(host, key=lambda e: (e.start, -e.dur)), t0, t1,
            k=100))
        got = PG.idle_phases(dev, host, t0, t1)
        assert got.keys() == want.keys()
        for k in want:
            assert got[k] == pytest.approx(want[k], rel=1e-12, abs=1e-15)


@pytest.mark.parametrize("name", READERS)
def test_no_phase_spans_read_nothing(name):
    assert read(name, readings(program=[], modules=())) is None
    older = [e for e in SPANS if e.name != "engine.step"]
    assert read(name, readings(program=older)) is None
    assert read(name, readings(trace=False)) is None


def test_missing_decode_modules_fail_the_reading():
    with pytest.raises(Absent):
        read("decode_step_device_ms", readings(modules=([MODULES[0]],)))
    with pytest.raises(Absent):
        read("decode_step_device_ms", readings(modules=()))


def test_a_profile_is_found_by_its_window(tmp_path, monkeypatch):
    """On the CPU: phase spans and the window span written by the profiler
    under ``.bench_traces/<cell>``, found by the traced window."""
    import jax

    out = tmp_path / ".bench_traces" / "cell"
    jax.profiler.start_trace(str(out))
    with jax.profiler.TraceAnnotation("bench.traced"):
        for step in (1, 2):
            with jax.profiler.TraceAnnotation("engine.step", step=step):
                with jax.profiler.TraceAnnotation("engine.admit", step=step):
                    pass
    jax.profiler.stop_trace()
    prog = PG.read_profile(TR.find_xplane(str(out)))
    assert [e.name for e in prog.spans].count("engine.step") == 2
    assert [e.name for e in prog.spans].count("engine.admit") == 2
    monkeypatch.setattr(spec, "ROOT", tmp_path)
    win = (prog.spans[0].start - 1.0, prog.spans[-1].end + 1.0)
    r = readings(program=None)
    r.trace.window = win
    assert PG.of(r) is None              # no profile holds this window
    t0, t1 = window_of(TR.find_xplane(str(out)))
    r.trace.window = (t0, t1)
    r.trace.devices = [[TR.Event("op", t0, 1.0)]]
    got = PG.of(r)
    assert got is not None and len(got.spans) == 4
    assert read("idle_scheduler", r) > 0
    with pytest.raises(Absent):          # the CPU has no module line
        read("decode_step_device_ms", r)


def window_of(path):
    from jax.profiler import ProfileData

    (span,) = [e for p in ProfileData.from_file(path).planes
               for ln in p.lines for e in ln.events
               if e.name == "bench.traced"]
    return float(span.start_ns), float(span.start_ns) + float(
        span.duration_ns)


def slice_data():
    return json.loads((HERE / "data" / "v5e_trace_slice.json").read_text())


def slice_events():
    d = slice_data()
    return ([TR.Event(n, s, t, n) for n, s, t in d["device"]],
            [TR.Event(n, s, t) for n, s, t in d["host"]])


def slice_window():
    return tuple(slice_data()["window"])


def test_existing_readers_unchanged_on_the_v5e_slice():
    """The readers that were there before the phase spans, and the
    ``idle_gaps`` split, give the values they gave then."""
    dev, host = slice_events()
    trace = TR.Trace(window=slice_window(), devices=[dev], host=host)
    cfg = json.loads((HERE / "data" / "starcoder2-15b-4L.json").read_text())
    steps = [StepWork(prefill=[(512, [0], [512], [True])]),
             StepWork(decode_ctx=[2048] * 64)]
    win = Window(t0=0, t1=1, tracks=[], stats0={}, stats1={}, compiles={},
                 compiled=[], lateness=[], pairs={}, steps=steps)
    r = Readings(dims=W.dims(cfg), engine={}, peaks=spec.peaks("TPU v5 lite"),
                 ptq_s=1.0, warm_s=1.0, setup_s=1.0, window=win, trace=trace)
    want = {"device_idle": 10.25029, "step_mfu": 13.2959132644525,
            "step_mfu.ttft": 13.2959132644525,
            "w4a16_matmul_roofline": 60.39500502395495,
            "paged_gqa_decode_roofline": 24.524894177632888,
            "paged_gqa_prefill_roofline": 5.452587255580369}
    for name, v in want.items():
        assert read(name, r) == pytest.approx(v, rel=1e-12), name
    assert TR.idle_by_host(dev, host, *trace.window) == [
        ["bench.step", pytest.approx(0.007175203)]]
    # the slice's program emitted no phase spans: the new readers are silent
    for name in READERS:
        assert read(name, r) is None
