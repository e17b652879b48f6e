"""The trace reduction, on a slice of a trace recorded on a TPU v5e."""
import json
from pathlib import Path

import pytest

from harness import trace as TR

DATA = Path(__file__).parent / "data" / "v5e_trace_slice.json"


@pytest.fixture(scope="module")
def rec():
    d = json.loads(DATA.read_text())
    dev = [TR.Event(n, s, t, n) for n, s, t in d["device"]]
    host = [TR.Event(n, s, t) for n, s, t in d["host"]]
    return d["window"], dev, host


def sweep_busy(events, t0, t1):
    """Busy time by counting open intervals at each boundary (a different
    method from the merge in ``TR.union``)."""
    pts = sorted([(max(e.start, t0), 1) for e in events]
                 + [(min(e.end, t1), -1) for e in events])
    busy, depth, last = 0.0, 0, None
    for t, d in pts:
        if depth > 0:
            busy += t - last
        depth += d
        last = t
    return busy


def test_busy_and_idle_match_a_sweep(rec):
    (t0, t1), dev, _ = rec
    busy = TR.busy_ns(dev)
    assert busy == pytest.approx(sweep_busy(dev, t0, t1), abs=1.0)
    idle = sum(b - a for a, b in TR.gaps(dev, t0, t1))
    assert busy + idle == pytest.approx(t1 - t0, abs=1.0)
    # the slice holds a prefill-chunk step and a decode step back to back:
    # the device is busy most of it
    assert 0.5 < busy / (t1 - t0) < 1.0


def test_kernel_sums_match_plain_prefix_sums(rec):
    _, dev, _ = rec
    for prefix, pattern in [("%w4a16_matmul.", r"^%w4a16_matmul\b"),
                            ("%gqa_paged_attention.",
                             r"^%gqa_paged_attention\b"),
                            ("%gqa_paged_prefill.", r"^%gqa_paged_prefill\b")]:
        want = sum(e.dur for e in dev if e.name.startswith(prefix))
        assert want > 0
        assert TR.kernel_ns(dev, pattern) == pytest.approx(want)
    # 4 layers x 6 GEMMs in each of the two steps, and the first GEMM of
    # the next step where the slice ends
    assert len(TR.matching(dev, r"^%w4a16_matmul\b")) == 49
    assert len(TR.matching(dev, r"^%gqa_paged_attention\b")) == 4
    assert len(TR.matching(dev, r"^%gqa_paged_prefill\b")) == 4


def test_leaves_drop_the_layer_loops(rec):
    _, dev, _ = rec
    names = {TR.op_name(e.name) for e in TR.leaves(dev)}
    assert "while" not in names
    assert {"w4a16_matmul", "gqa_paged_attention",
            "gqa_paged_prefill"} <= names
    top = TR.top_ops(dev, 3)
    assert len(top) == 3 and all(s > 0 for _, s in top)
    assert top[0][1] >= top[1][1] >= top[2][1]


def test_op_name():
    assert TR.op_name("%w4a16_matmul.41 = bf16[512,6144] custom-call(x)") \
        == "w4a16_matmul"
    assert TR.op_name("%copy_dynamic-update-slice_fusion.4") \
        == "copy_dynamic-update-slice_fusion"
    assert TR.op_name("fusion") == "fusion"


def test_idle_is_split_by_host_span(rec):
    (t0, t1), dev, host = rec
    parts = TR.idle_by_host(dev, host, t0, t1)
    idle = sum(b - a for a, b in TR.gaps(dev, t0, t1)) * 1e-9
    assert sum(s for _, s in parts) == pytest.approx(idle, rel=1e-9)
    assert {n for n, _ in parts} <= {"bench.step", "bench.submit",
                                     "bench.stamp", "outside bench spans"}


def test_intervals_by_hand():
    ev = [TR.Event("a", 0, 10), TR.Event("b", 5, 10), TR.Event("c", 30, 5)]
    assert TR.union((e.start, e.end) for e in ev) == [(0, 15), (30, 35)]
    assert TR.busy_ns(ev) == 20
    assert TR.gaps(ev, 0, 40) == [(15, 30), (35, 40)]
    assert [e.dur for e in TR.clip(ev, 8, 32)] == [2, 7, 2]
    host = [TR.Event("bench.step", 0, 20), TR.Event("bench.stamp", 12, 4),
            TR.Event("bench.wait", 25, 10)]
    got = dict(TR.idle_by_host(ev, host, 0, 40))
    # gap 15-30: 15-16 under the stamp inside the step, 16-20 the step,
    # 20-25 no span, 25-30 the wait; gap 35-40: no span
    assert got == pytest.approx({"bench.stamp": 1e-9, "bench.step": 4e-9,
                                 "bench.wait": 5e-9,
                                 "outside bench spans": 10e-9})
