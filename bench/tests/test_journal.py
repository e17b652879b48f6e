"""The engine's step journal carries the work the harness counts: on the
harness's own CPU path (the tiny cell of ``test_correct.py``, a traced
window), each traced step's ``StepRecord`` holds the decode contexts and
prefill buckets that ``serving._record_work`` saw that step launch."""
from pathlib import Path

from harness import serving
from harness import spec
from harness import traffic as T

DATA = Path(__file__).parent / "data"
SEED = 3


def test_journal_equals_the_harness_work(tmp_path, monkeypatch):
    config = spec.load_json(DATA / "tiny-mistral.json")
    tr = spec.load_json(DATA / "tiny-traffic.json")
    built = serving.build(config, tr["engine"], SEED, lambda *a: None)
    eng = built.eng
    serving.warm(eng, [tuple(p) for p in tr["warm"]["prefill"]])

    work = serving.StepWork

    def tagged():
        """A traced step's work, tagged with the engine step it precedes."""
        w = work()
        w.step = eng._step_idx + 1
        return w

    monkeypatch.setattr(serving, "StepWork", tagged)
    arrivals = T.schedule(tr, SEED, tr["ramp_s"] + 3.0, built.cfg.vocab_size)
    win = serving.serve_window(eng, arrivals, tr["ramp_s"], 3.0, trace_s=2.0,
                               trace_dir=str(tmp_path / "trace"))
    journal = {r.step: r for r in eng.trace.journal}
    assert len(win.steps) > 5
    assert any(w.prefill for w in win.steps)
    assert any(w.decode_ctx for w in win.steps)
    for w in win.steps:
        rec = journal[w.step]
        assert list(rec.decode_ctx) == w.decode_ctx
        assert [(p, list(s), list(n), list(f)) for p, s, n, f in rec.prefill] \
            == w.prefill
