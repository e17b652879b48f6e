"""Every family launches through one decode program and one prefill-chunk
program, and serving lowers nothing that a warm-up at the served shapes did
not.  The chip benchmark's warm-up (``bench/harness/serving.py`` ``warm``)
calls the attention-only programs positionally, as
``_decode(params, pools, tok, pos, table)`` and
``_prefill_chunk(params, toks, last_idx, starts, lens, table, pools)``: were
the engine to pass anything more, every measured window would lower its
step programs again."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import api
from repro.serving.engine import Request, ServingEngine

ARCHS = {"gqa": "codellama-7b", "hybrid": "zamba2-7b",
         "encdec": "whisper-medium"}
ROWS, BUCKET = 2, 8          # the one (rows, bucket) pair the prompts fall in


@pytest.mark.parametrize("family", list(ARCHS))
def test_serving_reuses_the_warmed_step_programs(family):
    cfg = get_config(ARCHS[family], smoke=True)
    params = api.init_model(jax.random.PRNGKey(0), cfg)
    eng = ServingEngine(params, cfg, batch_size=ROWS, max_seq=16,
                        page_size=4, backend="xla")
    # warm at the engine's shapes, in the benchmark's positional form; the
    # other families append the aux of rows that write nowhere
    _, dec_aux = eng._launch_rows(np.arange(eng.B), np.zeros(eng.B, bool))
    _, pre_aux = eng._launch_rows(np.arange(ROWS), np.zeros(ROWS, bool))
    if family == "gqa":
        assert dec_aux == pre_aux == ()
    lens = np.full(ROWS, BUCKET, np.int32)
    _, eng.pools = eng._prefill_chunk(
        eng.params, jnp.asarray(np.zeros((ROWS, BUCKET), np.int32)),
        jnp.asarray(lens - 1), jnp.asarray(np.zeros(ROWS, np.int32)),
        jnp.asarray(lens), jnp.asarray(np.zeros((ROWS, eng.P), np.int32)),
        eng.pools, *pre_aux)
    zeros = np.zeros(eng.B, np.int32)
    _, eng.pools = eng._decode(
        eng.params, eng.pools, jnp.asarray(zeros[:, None]),
        jnp.asarray(zeros), jnp.asarray(np.zeros((eng.B, eng.P), np.int32)),
        *dec_aux)
    assert eng._decode._cache_size() == eng._prefill_chunk._cache_size() == 1

    rng = np.random.default_rng(0)
    reqs = [Request(uid=i, max_tokens=4,
                    prompt=rng.integers(2, cfg.vocab_size, n).astype(np.int32),
                    frames=(rng.standard_normal((6, cfg.d_model)) * 0.1
                            ).astype(np.float32) if cfg.encdec else None)
            for i, n in enumerate((5, 8))]      # one chunk each, bucket 8
    for r in reqs:
        eng.submit(r)
    stats = eng.run_until_drained(max_steps=100)
    assert stats.completed == len(reqs) and stats.prefill_batches == 1
    assert stats.decoded_tokens > 0
    assert eng._decode._cache_size() == 1
    assert eng._prefill_chunk._cache_size() == 1
