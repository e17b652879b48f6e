"""Observability subsystem: metrics core percentile math (hand-computed
against the documented rule), snapshot-delta percentiles, engine latency
histograms under a fake clock (TTFT/ITL/e2e vs hand-derived values),
bounded trace recording, golden Chrome trace export, the pending-report
fold into ``metrics_snapshot``, fault-counter wiring, swap byte-accounting
symmetry on the hybrid model, and metrics on/off greedy identity."""
import json
import os

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import api
from repro.serving.engine import Request, ServingEngine
from repro.serving.faults import FaultPlan, FaultSpec
from repro.serving.metrics import (Counter, Gauge, Histogram, HistSnap,
                                   MetricsRegistry, format_pending,
                                   percentile_from_counts)
from repro.serving.trace import TraceRecorder, to_chrome_trace

DATA = os.path.join(os.path.dirname(__file__), "data")


# ==================================================== metrics core (pure) ==
def test_histogram_percentile_rule_hand_computed():
    """One decade per bucket so the rule is checkable on paper.  Bounds:
    1e-3, 1e-2, 1e-1, 1, 10, 100, 1000."""
    h = Histogram("t", lo=1e-3, hi=1e3, per_decade=1)
    assert h.bounds == tuple(10.0 ** e for e in range(-3, 4))
    for v in (0.0005, 0.05, 5.0):
        h.observe(v)
    # count=3.  p50: rank=ceil(.5*3)=2 -> cumulative reaches 2 in the
    # bucket holding 0.05 (first bound >= 0.05 is 0.1) -> report 0.1
    assert h.percentile(0.50) == 0.1
    # p99: rank=3 -> bucket bound 10, clamped to observed max 5.0
    assert h.percentile(0.99) == 5.0
    # p01: rank=1 -> first bucket bound 1e-3 (observed min 5e-4 is below
    # the bound; the clamp only pulls into [min,max], 1e-3 is inside)
    assert h.percentile(0.01) == 1e-3
    s = h.summary()
    assert s["count"] == 3 and s["min"] == 0.0005 and s["max"] == 5.0
    assert s["sum"] == pytest.approx(5.0505)


def test_histogram_single_value_is_exact_everywhere():
    h = Histogram("t")
    for _ in range(5):
        h.observe(0.0123)
    for q in (0.01, 0.5, 0.9, 0.99, 1.0):
        assert h.percentile(q) == 0.0123   # [min,max] clamp collapses


def test_histogram_overflow_reports_observed_max():
    h = Histogram("t", lo=1e-3, hi=1.0, per_decade=1)
    h.observe(0.5)
    h.observe(7500.0)                      # > hi: overflow bucket
    assert h.percentile(0.99) == 7500.0
    assert h.counts().buckets[-1] == 1


def test_histogram_bucket_edges_are_exact():
    """observe(bound) lands IN that bound's bucket (<=), not the next."""
    h = Histogram("t", lo=1e-3, hi=1e3, per_decade=1)
    h.observe(0.01)
    snap = h.counts()
    assert snap.buckets[h.bounds.index(0.01)] == 1


def test_histsnap_delta_percentiles():
    """Subtracting snapshots isolates the observations in between."""
    h = Histogram("t", lo=1e-3, hi=1e3, per_decade=1)
    h.observe(0.5)
    s0 = h.counts()
    for v in (0.05, 0.05, 0.05, 20.0):
        h.observe(v)
    d = h.counts() - s0
    assert d.count == 4 and d.sum == pytest.approx(20.15)
    # rank=ceil(.5*4)=2 -> bucket bound 0.1 (no min/max clamp in deltas)
    assert d.percentile(0.50) == 0.1
    # rank=4 -> the 20.0 landed in the 100-bound bucket
    assert d.percentile(0.99) == 100.0
    assert d.vmin is None and d.vmax is None
    with pytest.raises(ValueError, match="different bounds"):
        d - Histogram("u", lo=1e-2, hi=1e2, per_decade=1).counts()


def test_percentile_from_counts_empty():
    assert percentile_from_counts((1.0,), (0, 0), 0.5) == 0.0


def test_counter_gauge_labels_and_registry():
    reg = MetricsRegistry(clock=lambda: 42.0)
    assert reg.now() == 42.0
    c = reg.counter("faults")
    c.inc(site="page_alloc")
    c.inc(2, site="page_alloc")
    c.inc(site="swap_drain")
    assert c.value(site="page_alloc") == 3 and c.total() == 4
    assert c.snapshot() == {"site=page_alloc": 3, "site=swap_drain": 1}
    with pytest.raises(ValueError, match="< 0"):
        c.inc(-1)
    g = reg.gauge("pool")
    g.set(7, group="kv")
    g.set(9, group="kv")               # gauges overwrite
    assert g.value(group="kv") == 9
    assert reg.counter("faults") is c  # same name -> same instrument
    with pytest.raises(TypeError, match="already registered"):
        reg.gauge("faults")
    snap = reg.snapshot()
    assert set(snap) == {"counters", "gauges", "histograms"}
    assert snap["counters"]["faults"]["site=swap_drain"] == 1


# =========================================================== trace (pure) ==
def test_trace_ring_buffers_bound_memory():
    t = [0.0]
    rec = TraceRecorder(lambda: t[0], journal_len=4, keep_finished=2)
    for step in range(10):
        rec.begin_step(step)
        t[0] += 0.001
        rec.end_step([0], pages_used=1, pages_free=7)
    assert len(rec.journal) == 4
    assert [s.step for s in rec.journal] == [6, 7, 8, 9]
    for uid in range(5):
        rec.event(uid, "submit")
        rec.finish(uid)
    assert len(rec.finished) == 2
    assert [tl.uid for tl in rec.finished] == [3, 4]
    assert not rec.live


def test_trace_disabled_records_nothing_but_still_tells_time():
    t = [5.0]
    rec = TraceRecorder(lambda: t[0], enabled=False)
    assert rec.event(1, "submit") == 5.0   # callers still get a timestamp
    rec.begin_step(0)
    rec.note_chunk(0, 1, 8)
    rec.end_step([0], pages_used=0, pages_free=0)
    assert not rec.journal and not rec.live and not rec.finished


def _golden_recorder():
    """Deterministic recorder: two steps, one request that prefills, emits a
    token, is preempted, swaps back in, and finishes — every export shape
    (slices, counters, instants, flow arrows) in one small trace."""
    t = [0.0]
    rec = TraceRecorder(lambda: t[0])
    tl = rec.timeline(7)
    tl.add(0.0, "submit", prompt=5)
    rec.begin_step(0)
    rec.note_chunk(0, 7, 5)
    tl.add(0.0005, "admit", slot=0, cached_tokens=0)
    rec.note_fault("page_alloc")
    t[0] = 0.001
    rec.end_step([], pages_used=2, pages_free=6)
    tl.add(0.001, "first_token", slot=0)
    rec.begin_step(1)
    tl.add(0.0015, "preempt", slot=0, bytes=1024)
    rec.note_preempt(7, 0)
    tl.add(0.0018, "swap_in", slot=1)
    rec.note_resume(7, 1)
    t[0] = 0.002
    rec.end_step([1], pages_used=3, pages_free=5)
    tl.add(0.002, "finish", reason="completed")
    tl.finish_t = 0.002
    rec.finish(7)
    return rec


def test_chrome_trace_golden():
    """Byte-stable export: field order, µs rounding, flow-event pairing all
    pinned by a golden file."""
    obj = to_chrome_trace(_golden_recorder(), base=0.0, n_slots=2)
    got = json.dumps(obj, indent=1) + "\n"
    with open(os.path.join(DATA, "golden_trace.json")) as f:
        want = f.read()
    assert got == want


def test_chrome_trace_structure():
    obj = to_chrome_trace(_golden_recorder(), base=0.0, n_slots=2)
    evs = obj["traceEvents"]
    by_ph = {}
    for e in evs:
        by_ph.setdefault(e["ph"], []).append(e)
    # metadata: process + queue track + 2 slot tracks
    assert len(by_ph["M"]) == 4
    # the preempt->resume flow start/finish pair shares id and category
    (s,), (f,) = by_ph["s"], by_ph["f"]
    assert s["id"] == f["id"] == 7 and s["cat"] == f["cat"] == "swap"
    assert f["bp"] == "e"
    # flow endpoints sit on the tracks the request moved between
    assert s["tid"] == 1 and f["tid"] == 2
    # counter samples carry pool occupancy
    assert by_ph["C"][0]["args"] == {"used": 2, "free": 6}
    # fault probes are emitted as instants on the step track
    assert any(e["name"] == "fault:page_alloc" for e in by_ph["i"])
    # timestamps are µs since base, ns-rounded
    first_chunk = next(e for e in by_ph["X"] if e["name"] == "prefill_chunk")
    assert first_chunk["ts"] == 0.0 and first_chunk["dur"] == 1000.0


# ================================================= engine, faked clock ==
@pytest.fixture(scope="module")
def setup():
    cfg = get_config("codellama-7b", smoke=True)
    return cfg, api.init_model(jax.random.PRNGKey(0), cfg)


def test_engine_latency_histograms_hand_computed(setup):
    """Fake clock ticking 1s per step: every latency the engine derives is an
    exact integer count of steps, checkable by hand.  TTFT/e2e must match
    the request's own engine-recorded timestamps, single-value exactness
    makes p50==p99, and the ITL gaps are [0, 1, 1] (the first decode shares
    the prefill-completion mixed step, then one token per step at B=1)."""
    cfg, params = setup
    eng = ServingEngine(params, cfg, batch_size=1, max_seq=32, backend="xla")
    t = [0.0]
    eng._clock = lambda: t[0]
    r = Request(uid=1, prompt=np.arange(2, 8).astype(np.int32), max_tokens=4)
    eng.submit(r)
    assert r.arrival_t == 0.0
    while r.done_t is None:
        t[0] += 1.0
        eng.step()
    snap = eng.metrics_snapshot()
    lat = snap["latency"]

    # TTFT: one observation == the engine's own first_token stamp
    ttft = r.first_token_t - r.arrival_t
    assert ttft >= 1.0 and ttft == int(ttft)
    assert lat["ttft_s"]["count"] == 1
    assert lat["ttft_s"]["p50"] == lat["ttft_s"]["p99"] == ttft
    assert lat["ttft_s"]["mean"] == ttft

    # ITL: max_tokens-1 gaps.  The first decode shares the mixed step that
    # completed the prefill (gap 0); every later token is one clock tick.
    assert lat["itl_s"]["count"] == len(r.output) - 1 == 3
    assert lat["itl_s"]["p50"] == lat["itl_s"]["p99"] == 1.0
    assert lat["itl_s"]["min"] == 0.0 and lat["itl_s"]["max"] == 1.0
    assert lat["itl_s"]["mean"] == pytest.approx(2 / 3)

    # the finished timeline moved to the bounded archive, in event order
    tls = [tl for tl in eng.trace.finished if tl.uid == 1]
    assert len(tls) == 1

    # e2e and queue wait close the loop on the same clock
    assert lat["e2e_s"]["count"] == 1
    assert lat["e2e_s"]["p50"] == r.done_t - r.arrival_t
    assert lat["queue_wait_s"]["count"] == 1
    assert lat["queue_wait_s"]["p50"] == tls[0].admit_t - r.arrival_t == 1.0

    names = [n for _, n, _ in tls[0].events]
    assert names[0] == "submit" and names[-1] == "finish"
    assert "first_token" in names and "admit" in names


def test_pending_report_folds_metrics_snapshot(setup):
    """_pending_report is a rendering of metrics_snapshot, not a second
    formatting path: same text, and the snapshot carries phase + remaining
    deadline for queued and running requests alike."""
    cfg, params = setup
    eng = ServingEngine(params, cfg, batch_size=1, max_seq=32, backend="xla")
    t = [0.0]
    eng._clock = lambda: t[0]
    r1 = Request(uid=1, prompt=np.arange(2, 8).astype(np.int32), max_tokens=8)
    r2 = Request(uid=2, prompt=np.arange(2, 9).astype(np.int32), max_tokens=4,
                 deadline_s=9.0)
    eng.submit(r1)
    eng.submit(r2)
    t[0] = 1.0
    eng.step()                 # r1 runs, r2 queued with 8s left
    snap = eng.metrics_snapshot()
    by_uid = {p["uid"]: p for p in snap["pending"]}
    assert by_uid[1]["slot"] == 0 and by_uid[1]["phase"] in ("prefilling",
                                                            "decoding")
    assert by_uid[2]["phase"] == "queued" and by_uid[2]["slot"] is None
    assert by_uid[2]["deadline_left_s"] == 8.0
    assert by_uid[1]["deadline_left_s"] is None
    report = eng._pending_report()
    assert report == format_pending(snap)  # frozen clock -> identical text
    assert "uid=2 phase=queued prompt=7 out=0/4 retries=0 deadline=8.000s" \
        in report
    assert "pager: free=" in report


def test_fault_sink_feeds_labeled_counter(setup):
    """Every FaultPlan fire lands in the ``faults_fired_total`` counter under
    its site label — per-site counts reconcile with the plan's own ledger."""
    cfg, params = setup
    plan = FaultPlan([FaultSpec("page_alloc", every=3, times=2),
                      FaultSpec("page_grow", op=0, times=1)], seed=0)
    eng = ServingEngine(params, cfg, batch_size=2, max_seq=24, page_size=4,
                        num_pages=1 + 7, backend="xla", fault_plan=plan,
                        max_prefill_tokens=8)
    rng = np.random.default_rng(0)
    for i in range(4):
        eng.submit(Request(uid=i,
                           prompt=rng.integers(2, cfg.vocab_size,
                                               5 + i).astype(np.int32),
                           max_tokens=6))
    eng.run_until_drained(max_steps=600)
    assert plan.total_injected > 0, "plan never fired — sizing broke"
    ctr = eng.metrics.counter("faults_fired_total")
    for site, n in plan.injected.items():
        assert ctr.value(site=site) == n, (site, n, ctr.snapshot())
    assert ctr.total() == plan.total_injected == eng.stats.faults_injected
    # and the step journal marked every fault's step
    journal_faults = [s for rec in eng.trace.journal for s in rec.faults]
    assert len(journal_faults) == plan.total_injected


def test_swap_byte_accounting_symmetry_hybrid():
    """Satellite regression: swap-in must count the same bytes swap-out did,
    including the fixed-rows (SSM) state — the two sides of EngineStats
    accounting stay equal after every image round-trips."""
    cfg = get_config("zamba2-7b", smoke=True)
    params = api.init_model(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(3)
    lens = (5, 9, 7, 12)
    reqs = [Request(uid=i,
                    prompt=rng.integers(2, cfg.vocab_size,
                                        lens[i % 4]).astype(np.int32),
                    max_tokens=6)
            for i in range(5)]
    eng = ServingEngine(params, cfg, batch_size=3, max_seq=24, page_size=4,
                        num_pages=1 + 7, backend="xla", max_prefill_tokens=8)
    for r in reqs:
        eng.submit(r)
    stats = eng.run_until_drained(max_steps=600)
    assert stats.preemptions > 0 and stats.resumes > 0
    assert stats.swapped_out_bytes == stats.swapped_in_bytes > 0
    assert stats.swapped_fixed_bytes == stats.swapped_fixed_in_bytes > 0
    # KV bytes alone are symmetric too (fixed split accounted both sides)
    assert (stats.swapped_out_bytes - stats.swapped_fixed_bytes
            == stats.swapped_in_bytes - stats.swapped_fixed_in_bytes)
    eng.pager.check_invariants()


def test_metrics_on_off_greedy_identity(setup):
    """The whole observability subsystem is host-side bookkeeping: switching
    it off changes no token anywhere (preemption pressure included), and the
    off engine records nothing."""
    cfg, params = setup
    rng = np.random.default_rng(0)
    prompts = [rng.integers(2, cfg.vocab_size, 4 + i % 5).astype(np.int32)
               for i in range(5)]

    def drive(metrics):
        eng = ServingEngine(params, cfg, batch_size=3, max_seq=24,
                            page_size=4, num_pages=1 + 7, backend="xla",
                            max_prefill_tokens=8, metrics=metrics)
        reqs = [Request(uid=i, prompt=p.copy(), max_tokens=8)
                for i, p in enumerate(prompts)]
        for r in reqs:
            eng.submit(r)
        eng.run_until_drained(max_steps=600)
        return eng, [list(r.output) for r in reqs]

    eng_on, out_on = drive(True)
    eng_off, out_off = drive(False)
    assert out_on == out_off
    assert eng_on.stats.preemptions == eng_off.stats.preemptions
    # on: full recording; off: nothing retained
    assert len(eng_on.trace.journal) == eng_on.stats.steps
    assert eng_on.metrics_snapshot()["latency"]["ttft_s"]["count"] == 5
    assert not eng_off.trace.journal and not eng_off.trace.finished
    assert eng_off.metrics_snapshot()["latency"]["ttft_s"]["count"] == 0
    # the snapshot itself stays well-formed with metrics off
    assert eng_off.metrics_snapshot()["engine"]["completed"] == 5
