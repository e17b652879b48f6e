"""StarCoder2 served on the normal path agrees with the plain reference,
logit for logit.

Smoke widths (``bench/tests/data/tiny-starcoder2.json``: LayerNorm, a bias
on every linear, tanh-GELU MLP) with the benchmark's seeded weights: every
quantized linear on an exact 4-bit grid and a drawn bias on every linear
(``bench/harness/weights.py``), so SmoothQuant+ at its searched alpha of 0
and int4 quantization are both exact and the served W4A16 model is the
reference's model.  The weights go through quantize-on-load and a paged
``ServingEngine`` (XLA backend; prompts longer than the 16-token chunk
budget, so chunked prefill, then decode).  The logits each request was
sampled from are compared with those of the float32 reference
(``bench/references/dense_gqa.py``) teacher-forced on the prompt and the
served tokens.

A SmoothQuant+ that leaves the value bias unscaled (the value projection's
columns divided by ``s`` and its bias not) must fail the comparison.
"""
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1] / "bench"
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

from harness import weights as W  # noqa: E402
from harness.serving import program_config  # noqa: E402
from references import dense_gqa as REF  # noqa: E402

from repro.core import smoothing as SM  # noqa: E402
from repro.launch import serve  # noqa: E402
from repro.models import api  # noqa: E402
from repro.serving.engine import Request, ServingEngine  # noqa: E402

CONFIG = json.loads((BENCH / "tests" / "data" / "tiny-starcoder2.json")
                    .read_text())
SEED = 15
PROMPTS = (37, 21, 9)          # tokens; the first two take several chunks
NEW = 12                       # tokens served per request
# The served model computes in bfloat16 (activations, the residual stream,
# weights, the bias adds; the lm_head accumulates in f32), the reference in
# float32 at the highest matmul precision.  bf16 keeps 8 significant bits,
# so each rounding is up to 2^-9 of its value; through two layers of
# rounded residual adds, norms and GELU the hidden state drifts by about 1%,
# and logits of up to ~4 by up to ~0.1 at the worst position (measured over
# seeds 1, 2, 3, 15: 0.038-0.094).  The unscaled value bias moves the worst
# by 0.58-0.91 on the same seeds.
MAX_ABS = 0.25
# over every compared position and the whole vocabulary the same rounding
# averages 0.007-0.016 (same seeds); the unscaled value bias 0.089-0.135
MEAN_ABS = 0.04


def serve_logits(params, cfg, group):
    """Quantize-on-load, then serve ``PROMPTS`` greedily; returns the
    requests and ``{(uid, i): logits}`` that token ``i`` was sampled from."""
    qparams, _, _, _ = serve.quantize_on_load(params, cfg, group)
    eng = ServingEngine(qparams, cfg, batch_size=2, max_seq=64,
                        page_size=16, backend="xla", max_prefill_tokens=16,
                        eos_id=cfg.vocab_size)
    seen = {}
    sample = eng._sample_reqs

    def recording(logits, sk, temps, reqs):
        rows = np.asarray(logits, np.float32)
        for i, r in enumerate(reqs):
            if r is not None:
                seen[(r.uid, len(r.output))] = rows[i]
        return sample(logits, sk, temps, reqs)

    eng._sample_reqs = recording
    rng = np.random.default_rng(SEED)
    reqs = [Request(uid=i, prompt=rng.integers(2, cfg.vocab_size, n)
                    .astype(np.int32), max_tokens=NEW)
            for i, n in enumerate(PROMPTS)]
    for r in reqs:
        assert eng.submit(r)
    eng.run_until_drained()
    assert all(len(r.output) == NEW for r in reqs)
    return reqs, seen


def reference_logits(d, seed, prompt, output):
    """float32 logits of the reference at every served position."""
    k_embed, k_head, k_layers = W.keys(seed)
    seq = np.concatenate([prompt, np.asarray(output[:-1], np.int32)])
    t = REF.Q_BLOCK
    x = REF.embed(W.embed_table_jit(d, k_embed),
                  jnp.asarray(np.pad(seq, (0, t - len(seq)))), d)
    for i in range(d.layers):
        x = REF.layer(W.layer_params_jit(d, jax.random.fold_in(k_layers, i)),
                      x, d)
    p0 = len(prompt) - 1
    return np.asarray(REF.logits(W.norm_params(d), W.lm_head_jit(d, k_head),
                                 x[p0:p0 + len(output)], d))


def logit_errors(seed=SEED):
    d = W.dims(CONFIG)
    cfg = program_config(CONFIG)
    params = W.make_params(d, seed)
    reqs, seen = serve_logits(params, cfg, d.group)
    errs = []
    for r in reqs:
        ref = reference_logits(d, seed, r.prompt, r.output)
        got = np.stack([seen[(r.uid, i)] for i in range(len(r.output))])
        errs.append(np.abs(got - ref))
    return np.concatenate(errs)


def test_served_logits_match_the_reference():
    err = logit_errors()
    assert err.max() <= MAX_ABS, err.max()
    assert err.mean() <= MEAN_ABS, err.mean()


def test_an_unscaled_value_bias_fails(monkeypatch):
    """SmoothQuant+ with the provider's bias left as it was: the served
    value is ``x Wv / s + b`` where the model computes ``(x Wv + b) / s``."""
    apply = SM.apply_group

    def unscaled_bias(params, cfg, group, s):
        out = apply(params, cfg, group, s)
        bp = (SM._bias_path(params, group.provider.path)
              if group.provider.kind == "linear_out" else None)
        return out if bp is None else SM.tset(out, bp, SM.tget(params, bp))

    monkeypatch.setattr(SM, "apply_group", unscaled_bias)
    err = logit_errors()
    assert err.max() > MAX_ABS, err.max()
    assert err.mean() > MEAN_ABS, err.mean()


def test_max_seq_past_the_sliding_window_is_refused():
    cfg = program_config(CONFIG).with_(sliding_window=32)
    params = api.init_model(jax.random.PRNGKey(0), cfg)
    with pytest.raises(ValueError, match="sliding window of 32"):
        ServingEngine(params, cfg, batch_size=2, max_seq=48, page_size=16,
                      backend="xla")
    ServingEngine(params, cfg, batch_size=2, max_seq=32, page_size=16,
                  backend="xla")
