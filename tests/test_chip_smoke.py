"""CPU rehearsal of ``chip_smoke.py``: its phases at smoke size with the
Pallas kernels in interpret mode, and its refusal to run without a TPU."""
import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch import serve
from repro.models import api

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_phases_rehearse_at_smoke_size(chip_smoke):
    """Quantize-on-load, compile both step programs, serve every request,
    and compare the kernel engine's first-step logits with the XLA
    reference engine — the script's phases, on the interpreter."""
    cs = chip_smoke
    cfg = serve.model_config("starcoder2-15b", smoke=True).with_(
        dtype="bfloat16")
    params = api.init_model(jax.random.PRNGKey(cs.SEED), cfg)
    params, rep, mode, secs = serve.quantize_on_load(params, cfg, 16)
    assert mode == "quantize-on-load" and secs > 0 and rep.quant_bytes > 0

    sizes = dict(batch_size=4, max_seq=32, page_size=8)
    eng = cs.build_engine(params, cfg, backend="interpret", **sizes)
    reqs = serve.make_requests(cfg, 4, prompt_len=12, max_tokens=4)
    prompts = [r.prompt for r in reqs]
    lower_s, compile_s, counts = cs.compile_steps(eng, prompts)
    assert lower_s > 0 and compile_s > 0
    # interpret mode lowers the kernels to plain HLO: no custom call on the
    # CPU, but the step programs must still trace through pallas_call
    assert set(counts) == {"decode", "prefill_chunk"}
    a = cs._first_step_inputs(eng, prompts)
    jaxpr = jax.make_jaxpr(eng._decode)(
        eng.params, eng.pools, jnp.zeros((eng.B, 1), jnp.int32), a["lens"],
        a["table"])
    assert "pallas_call" in str(jaxpr)

    accepted, _ = serve.serve_requests(eng, reqs)
    assert accepted == eng.stats.completed == len(reqs)
    assert eng.stats.decoded_tokens == sum(len(r.output) - 1 for r in reqs)

    pre, first, dec = cs.first_step_logits(eng, prompts)
    ref = cs.build_engine(params, cfg.with_(paged_attn_impl="gather"),
                          backend="xla", **sizes)
    pre_ref, first_ref, dec_ref = cs.first_step_logits(ref, prompts, first)
    assert first_ref is first
    assert dec.shape == (len(prompts), cfg.vocab_size)
    for got, want in ((pre, pre_ref), (dec, dec_ref)):
        assert np.isfinite(got).all() and np.isfinite(want).all()
        assert cs.logit_gap(got, want) <= cs.LOGIT_TOL


def test_exits_nonzero_without_a_tpu(chip_smoke, capsys):
    assert jax.devices()[0].platform != "tpu"
    with pytest.raises(SystemExit) as e:
        chip_smoke.main()
    assert e.value.code not in (0, None)
    assert '"ok"' not in capsys.readouterr().out
