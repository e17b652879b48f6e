"""The engine's phase spans on the JAX profiler's clock, and its named step
programs.

A smoke engine with lazy page reservation and a small chunk budget (one
prompt prefills in three chunks) serves the same requests three times: with
no profiler attached, then under one profiler session with metrics on and
with metrics off.  The trace is read back with ``ProfileData``; every
profiler use of the test suite lives in this file.
"""
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs import get_config
from repro.models import api
from repro.serving.engine import Request, ServingEngine

#: every phase span and the span it opens inside
PARENT = {
    "engine.step": None,
    "engine.expire": "engine.step",
    "engine.admit": "engine.step",
    "engine.grow": "engine.step",
    "engine.prefill": "engine.step",
    "engine.plan_chunks": "engine.prefill",
    "engine.prefill_inputs": "engine.prefill",
    "engine.prefill_dispatch": "engine.prefill",
    "engine.first_token_wait": "engine.prefill",
    "engine.check_tables": "engine.step",
    "engine.decode": "engine.step",
    "engine.decode_inputs": "engine.decode",
    "engine.decode_dispatch": "engine.decode",
    "engine.token_wait": "engine.decode",
    "engine.drain_swap": "engine.step",
    "engine.journal": "engine.step",
}
EMIT_PARENTS = {"engine.prefill", "engine.decode"}
OFF = "test.metrics_off"          # marks the metrics-off engine's run
PROMPTS = (3, 19, 7, 11)          # 19 tokens prefill in chunks of 8, 8, 3


def _requests(cfg):
    rng = np.random.default_rng(3)
    return [Request(uid=i, prompt=rng.integers(2, cfg.vocab_size, size=n)
                    .astype(np.int32), max_tokens=5)
            for i, n in enumerate(PROMPTS)]


def _engine(cfg, params, metrics=True):
    return ServingEngine(params, cfg, batch_size=3, max_seq=32, page_size=4,
                         max_prefill_tokens=8, backend="xla",
                         metrics=metrics)


def _serve(eng, cfg):
    reqs = _requests(cfg)
    for r in reqs:
        eng.submit(r)
    eng.run_until_drained()
    return [r.output for r in reqs]


def _span(e):
    return {"name": e.name, "start": e.start_ns,
            "end": e.start_ns + e.duration_ns, "step": dict(e.stats)["step"]}


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    cfg = get_config("codellama-7b", smoke=True)
    params = api.init_model(jax.random.PRNGKey(0), cfg)
    eng = _engine(cfg, params)
    plain = _serve(eng, cfg)
    first_traced = eng._step_idx + 1
    off = _engine(cfg, params, metrics=False)
    out = str(tmp_path_factory.mktemp("profile"))
    jax.profiler.start_trace(out)
    try:
        traced = _serve(eng, cfg)
        with jax.profiler.TraceAnnotation(OFF):
            traced_off = _serve(off, cfg)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(out, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    spans, marks = [], []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("engine."):
                    spans.append(_span(e))
                elif e.name == OFF:
                    marks.append((e.start_ns, e.start_ns + e.duration_ns))
    (mark,) = marks
    inside = lambda s: mark[0] <= s["start"] and s["end"] <= mark[1]
    journal = [r for r in eng.trace.journal if r.step >= first_traced]
    return dict(eng=eng, cfg=cfg, plain=plain, traced=traced,
                traced_off=traced_off, journal=journal,
                on=[s for s in spans if not inside(s)],
                off=[s for s in spans if inside(s)])


@pytest.mark.parametrize("which", ["on", "off"])
def test_every_phase_is_on_the_host_plane(served, which):
    names = {s["name"] for s in served[which]}
    want = set(PARENT) | {"engine.emit"}
    if which == "off":
        want.discard("engine.journal")   # nothing to record with metrics off
    assert names == want


@pytest.mark.parametrize("which", ["on", "off"])
def test_every_span_lies_inside_its_step(served, which):
    spans = served[which]
    steps = {s["step"]: s for s in spans if s["name"] == "engine.step"}
    assert len(steps) == len([s for s in spans if s["name"] == "engine.step"])
    by_step = {}
    for s in spans:
        by_step.setdefault(s["step"], []).append(s)
    for s in spans:
        st = steps[s["step"]]
        assert st["start"] <= s["start"] <= s["end"] <= st["end"], s
        if s["name"] == "engine.step":
            continue
        # the innermost span of the same step that encloses this one
        outer = [o for o in by_step[s["step"]] if o is not s
                 and o["start"] <= s["start"] and s["end"] <= o["end"]
                 and o["name"] != s["name"]]
        inner = max(outer, key=lambda o: (o["start"], -o["end"]))
        if s["name"] == "engine.emit":
            assert inner["name"] in EMIT_PARENTS
        else:
            assert inner["name"] == PARENT[s["name"]], s


def test_token_wait_once_per_decoding_step(served):
    waits = [s["step"] for s in served["on"]
             if s["name"] == "engine.token_wait"]
    decoding = [r.step for r in served["journal"] if r.decode_slots]
    assert decoding and sorted(waits) == decoding
    # the metrics-off engine decodes on the same steps of its own count
    off = sorted(s["step"] for s in served["off"]
                 if s["name"] == "engine.token_wait")
    first = served["journal"][0].step
    assert off == [d - first + 1 for d in decoding]


def test_step_programs_are_named(served):
    eng = served["eng"]
    z = lambda *shape: jnp.zeros(shape, jnp.int32)
    dec = eng._decode.lower(eng.params, eng.pools, z(eng.B, 1), z(eng.B),
                            z(eng.B, eng.P))
    assert "jit_decode_step" in dec.as_text()
    pre = eng._prefill_chunk.lower(eng.params, z(2, 8), z(2), z(2), z(2),
                                   z(2, eng.P), eng.pools)
    assert "jit_prefill_chunk_step" in pre.as_text()


def test_profiler_leaves_greedy_tokens_alone(served):
    assert all(len(o) == 5 for o in served["plain"])
    assert served["traced"] == served["plain"]
    assert served["traced_off"] == served["plain"]


def test_journal_carries_the_work_of_each_step(served):
    """Decode contexts and prefill buckets, against the step's decode slots
    and chunk rows."""
    journal = served["journal"]
    chunks = {}
    for r in journal:
        assert len(r.decode_ctx) == len(r.decode_slots)
        assert all(c >= 2 for c in r.decode_ctx)
        rows = [(s, n) for _, starts, lens, _ in r.prefill
                for s, n in zip(starts, lens)]
        assert [n for _, n in rows] == [c[2] for c in r.chunks]
        assert all(n <= pad for pad, _, lens, _ in r.prefill for n in lens)
        for slot, uid, n in r.chunks:
            chunks.setdefault(uid, []).append(n)
    assert sorted(sum(c) for c in chunks.values()) == sorted(PROMPTS)
    assert chunks[1] == [8, 8, 3]       # the 19-token prompt
    finals = [f for r in journal for _, _, _, fin in r.prefill for f in fin]
    assert sum(finals) == len(PROMPTS)
