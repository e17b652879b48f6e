"""The readers of the decode step's named scopes (``decode_layer_ms``,
``decode_head_ms``): on a trace built by hand whose per-launch times are
known, with op events named as the v5e profiler names them (the HLO
instruction, no metadata) and each instruction's scope given beside them;
the scopes read from a profile written on the CPU; and silent on the
recorded v5e slice, which holds no profile."""
import json
from pathlib import Path

import pytest

from harness import op_scopes as OS
from harness import spec
from harness import trace as TR
from harness import weights as W
from harness.readings import Absent, Readings
from harness.serving import StepWork, Window

HERE = Path(__file__).parent
US = 1000.0                           # ns
READERS = ("decode_layer_ms", "decode_head_ms")
DIMS = W.dims(json.loads((HERE / "data" / "starcoder2-15b-4L.json")
                         .read_text()))          # 4 layers
DEC, PRE = "jit(decode_step)", "jit(prefill_chunk_step)"
# each module's instructions and the scope paths they were traced under;
# the copies XLA adds carry none
SCOPES = {
    "jit_decode_step": {
        "while.12": f"{DEC}/lm.layers/while",
        "w4a16_matmul.2": f"{DEC}/lm.layers/while/body/pallas_call",
        "gqa_paged_attention.3": f"{DEC}/lm.layers/while/body/pallas_call",
        "fusion.37": f"{DEC}/lm.head/dot_general",
        "copy.36": "",
    },
    "jit_prefill_chunk_step": {
        "while.3": f"{PRE}/lm.layers/while",
        "fusion.8": f"{PRE}/lm.head/dot_general",
    },
}


def op(name, a, b):
    text = f"%{name} = bf16[64,6144] fusion(bf16[64,6144] %x.1)"
    return TR.Event(text, a * US, (b - a) * US, text)


# two decode launches and one prefill chunk between them; the loop's event
# spans its body's ops and is not counted twice
OPS = [op("while.12", 110, 490), op("w4a16_matmul.2", 110, 200),
       op("gqa_paged_attention.3", 200, 490), op("fusion.37", 500, 560),
       op("copy.36", 560, 600),
       op("while.3", 700, 850), op("fusion.8", 850, 880),
       op("while.12", 1000, 1300), op("w4a16_matmul.2", 1000, 1300),
       op("fusion.37", 1300, 1350)]
MODULES = [TR.Event("jit_decode_step(9)", 100 * US, 500 * US),
           TR.Event("jit_prefill_chunk_step(7)", 700 * US, 200 * US),
           TR.Event("jit_decode_step(9)", 1000 * US, 400 * US)]


def readings(scopes=SCOPES, modules=MODULES, trace=True):
    tr = TR.Trace(window=(0.0, 2000 * US), devices=[list(OPS)], host=[])
    tr.program, tr.modules = [], [list(modules)]
    tr.op_scopes = scopes
    win = Window(t0=0, t1=1, tracks=[], stats0={}, stats1={}, compiles={},
                 compiled=[], lateness=[], pairs={}, steps=[StepWork()])
    return Readings(dims=DIMS, engine={}, peaks={}, ptq_s=0, warm_s=0,
                    setup_s=0, window=win, trace=tr if trace else None)


def read(name, r):
    return spec.load_metric_reader(name).read(r)


def test_scoped_time_per_decode_launch_by_hand():
    r = readings()
    # layers: 380 and 300 us in the two decode launches (the loop's own
    # event left out), median 340, over 4 layers
    assert read("decode_layer_ms", r) == pytest.approx(340e-3 / 4)
    # head: 60 and 50 us, median 55; the prefill chunk's head left out
    assert read("decode_head_ms", r) == pytest.approx(55e-3)


@pytest.mark.parametrize("name", READERS)
def test_no_scope_or_no_trace_reads_nothing(name):
    plain = {m: {i: s.replace("/lm.layers", "").replace("/lm.head", "")
                 for i, s in ops.items()} for m, ops in SCOPES.items()}
    assert read(name, readings(scopes=plain)) is None
    assert read(name, readings(scopes={})) is None
    assert read(name, readings(trace=False)) is None


@pytest.mark.parametrize("name", READERS)
def test_scopes_without_a_decode_launch_fail_the_reading(name):
    with pytest.raises(Absent):
        read(name, readings(modules=[MODULES[1]]))


@pytest.mark.parametrize("name", READERS)
def test_silent_on_the_v5e_slice(name):
    d = json.loads((HERE / "data" / "v5e_trace_slice.json").read_text())
    trace = TR.Trace(window=tuple(d["window"]),
                     devices=[[TR.Event(n, s, t, n) for n, s, t in d["device"]]],
                     host=[TR.Event(n, s, t) for n, s, t in d["host"]])
    win = Window(t0=0, t1=1, tracks=[], stats0={}, stats1={}, compiles={},
                 compiled=[], lateness=[], pairs={},
                 steps=[StepWork(decode_ctx=[2048] * 64)])
    r = Readings(dims=DIMS, engine={}, peaks={}, ptq_s=0, warm_s=0,
                 setup_s=0, window=win, trace=trace)
    assert read(name, r) is None


def test_scopes_are_read_from_a_profile(tmp_path, monkeypatch):
    """On the CPU: a program with the two scopes, profiled under
    ``.bench_traces/<cell>``; its module's HLO in the profile names each
    op's scope, and the run's window finds the profile."""
    import jax
    import jax.numpy as jnp

    def step(x, w):
        with jax.named_scope("lm.layers"):
            y, _ = jax.lax.scan(lambda c, wi: (jnp.tanh(c @ wi), None), x, w)
        with jax.named_scope("lm.head"):
            return (y * 2.0).sum()

    f = jax.jit(step)
    x, w = jnp.ones((8, 128)), jnp.full((3, 128, 128), 0.01)
    f(x, w).block_until_ready()
    out = tmp_path / ".bench_traces" / "cell"
    jax.profiler.start_trace(str(out))
    with jax.profiler.TraceAnnotation("bench.traced"):
        f(x, w).block_until_ready()
    jax.profiler.stop_trace()
    path = TR.find_xplane(str(out))
    ops = OS.module_op_names(path)["jit_step"]
    scoped = [s for s in ops.values() if "/lm." in s]
    assert any("/lm.layers/while/body/" in s for s in scoped)
    assert any("/lm.head/" in s for s in scoped)
    assert OS.instruction("%fusion.37 = f32[64] fusion(f32[8] %p.1)") == \
        "fusion.37"

    (span,) = [e for p in jax.profiler.ProfileData.from_file(path).planes
               for ln in p.lines for e in ln.events
               if e.name == "bench.traced"]
    monkeypatch.setattr(spec, "ROOT", tmp_path)
    r = readings()
    del r.trace.op_scopes, r.trace.program, r.trace.modules
    r.trace.window = (float(span.start_ns),
                      float(span.start_ns) + float(span.duration_ns))
    assert OS.of(r)["jit_step"] == ops
