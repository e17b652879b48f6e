#!/usr/bin/env python3
"""Drive the served path once on one TPU chip and check what comes out.

StarCoder2-15B at its published widths, depth cut to 4 of 40 layers, random
weights from a seed: SmoothQuant+ quantize-on-load (group size 128), then the
paged continuous-batching engine serves 8 requests to completion through the
Pallas W4A16 and paged-attention kernels — the same phases
``python -m repro.launch.serve`` runs.  Then, on the same chip:

* the compiled decode step and prefill-chunk step must each contain Pallas
  kernels (``tpu_custom_call`` in their compiled text);
* the logits of the first decode step (and of the prefill before it) must
  match the XLA reference engine (dequantize-then-matmul GEMMs, dense
  page-gather attention) fed the same tokens, within :data:`LOGIT_TOL`, and
  every logit must be finite.

Both sides of that comparison serve one PTQ'd model, so a fault in PTQ
itself (a smoothing that changes what the model computes) passes here
unseen.  The benchmark's ``correct`` (``bench/harness/check.py``) compares
the served tokens with a plain float32 reference of the unquantized model,
and sees it.

    python3 chip_smoke.py

It runs in one process and catches no exception: a failing phase exits
non-zero.  Without a TPU it exits non-zero before doing any work, and prints
no result.  The last line of stdout is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}``.
"""
from __future__ import annotations

import json
import sys
import time
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"

ARCH = "starcoder2-15b"
NUM_LAYERS = 4        # depth cut only: a dense stack's period is one layer
GROUP_SIZE = 128
REQUESTS = 8
BATCH = 8             # every request admitted at once: one prefill bucket
PROMPT_LEN = 24
MAX_TOKENS = 8
MAX_SEQ = 64
PAGE_SIZE = 16
SEED = 0
# Logits of the first decode step and of the prefill before it, Pallas
# engine vs XLA reference engine on the same chip: max |Δ| <= LOGIT_TOL ·
# max |reference logit|.  Both engines keep bf16 activations and accumulate
# in f32, but in different orders (per-group MXU passes inside the kernels,
# one fused dequant-GEMM in XLA; online vs dense softmax), so a hidden value
# now and then rounds to the neighbouring bf16 value (2^-8 relative) and
# the flips compound over the layers.  Eight bf16 ulps of the largest logit
# bound that; a wrong page, block or scale moves the logits by their own
# magnitude.
LOGIT_TOL = 2.0 ** -5


def require_tpu():
    """The first JAX device, or exit non-zero when it is not a TPU."""
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: JAX found no TPU (platform "
                         f"{dev.platform!r}); nothing was run")
    return dev


def build_engine(params, cfg, *, backend: str, batch_size: int = BATCH,
                 max_seq: int = MAX_SEQ, page_size: int = PAGE_SIZE):
    from repro.serving.engine import ServingEngine

    return ServingEngine(params, cfg, batch_size=batch_size, max_seq=max_seq,
                         page_size=page_size, backend=backend)


def _first_step_inputs(eng, prompts):
    """Inputs of one prefill chunk per prompt (fresh private pages, the
    bucket the engine's scheduler picks) and of the decode step after it."""
    import jax.numpy as jnp
    import numpy as np

    n = len(prompts)
    if n != eng.B:
        raise ValueError(f"{n} prompts for an engine of batch {eng.B}")
    lens = np.asarray([len(p) for p in prompts], np.int32)
    toks = np.zeros((n, eng.sched.bucket_len(int(lens.max()))), np.int32)
    for i, p in enumerate(prompts):
        toks[i, :len(p)] = p
    # page 0 is the trash page; each row owns the next eng.P pages
    table = np.arange(1, 1 + n * eng.P, dtype=np.int32).reshape(n, eng.P)
    return dict(toks=jnp.asarray(toks), last=jnp.asarray(lens - 1),
                starts=jnp.zeros(n, jnp.int32), lens=jnp.asarray(lens),
                table=jnp.asarray(table))


def compile_steps(eng, prompts):
    """Compile the engine's decode and prefill-chunk step programs at the
    shapes :func:`first_step_logits` (and a batch of ``prompts`` served
    together) runs.  Returns ``(lower_s, compile_s, {program:
    tpu_custom_call count})`` — both times are set-up time.  Tracing and
    lowering run on every start; the XLA compile is near zero once the
    persistent compile cache holds both programs."""
    import jax.numpy as jnp

    a = _first_step_inputs(eng, prompts)
    tok = jnp.zeros((eng.B, 1), jnp.int32)
    t0 = time.perf_counter()
    lowered = {
        "decode": eng._decode.lower(
            eng.params, eng.pools, tok, a["lens"], a["table"]),
        "prefill_chunk": eng._prefill_chunk.lower(
            eng.params, a["toks"], a["last"], a["starts"], a["lens"],
            a["table"], eng.pools),
    }
    t1 = time.perf_counter()
    compiled = {name: low.compile() for name, low in lowered.items()}
    t2 = time.perf_counter()
    return t1 - t0, t2 - t1, {name: c.as_text().count("tpu_custom_call")
                              for name, c in compiled.items()}


def first_step_logits(eng, prompts, first_tokens=None):
    """Prefill ``prompts`` (one chunk each) into fresh pools through the
    engine's own step programs, then run one decode step.

    The decode step is fed ``first_tokens`` when given (so two engines can
    be compared on identical inputs), else the greedy token of this
    engine's prefill.  Returns ``(prefill_logits, first_tokens,
    decode_logits)`` as f32 numpy arrays."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models import api

    a = _first_step_inputs(eng, prompts)
    pools = api.init_paged_cache(eng.cfg, eng.pager.num_pages, eng.PS)
    logits, pools = eng._prefill_chunk(
        eng.params, a["toks"], a["last"], a["starts"], a["lens"], a["table"],
        pools)
    if first_tokens is None:
        first_tokens = np.asarray(jnp.argmax(logits, axis=-1), np.int32)
    dec, _ = eng._decode(eng.params, pools,
                         jnp.asarray(first_tokens)[:, None], a["lens"],
                         a["table"])
    jax.block_until_ready(dec)
    return (np.asarray(logits, np.float32), first_tokens,
            np.asarray(dec, np.float32))


def logit_gap(got, want) -> float:
    """max |got − want| relative to max |want| (compared to LOGIT_TOL)."""
    import numpy as np

    return float(np.abs(got - want).max() / np.abs(want).max())


def main(argv=None) -> int:
    if not SRC.is_dir():
        raise SystemExit(f"chip_smoke: {SRC} not found — run from a "
                         "checkout of the repository")
    sys.path.insert(0, str(SRC))
    dev = require_tpu()

    import jax
    import numpy as np

    from repro.launch import serve
    from repro.models import api

    print(f"[device] {dev.platform} {dev.device_kind} x{len(jax.devices())}")
    print(f"[cache] compile cache at {serve.enable_compile_cache()}")
    cfg = serve.model_config(ARCH, num_layers=NUM_LAYERS)
    print(serve.describe_cut(cfg, ARCH))

    params = api.init_model(jax.random.PRNGKey(SEED), cfg)
    params, rep, mode, ptq_s = serve.quantize_on_load(params, cfg, GROUP_SIZE)
    print(f"[{mode}] group={GROUP_SIZE} alpha={rep.alpha:.2f} "
          f"{rep.fp_bytes / 1e9:.2f}GB -> {rep.quant_bytes / 1e9:.2f}GB "
          f"in {ptq_s:.1f}s (set-up)")

    eng = build_engine(params, cfg, backend="auto")
    reqs = serve.make_requests(cfg, REQUESTS, prompt_len=PROMPT_LEN,
                               max_tokens=MAX_TOKENS)
    prompts = [r.prompt for r in reqs]
    lower_s, compile_s, kernels = compile_steps(eng, prompts)
    print(f"[compile] decode + prefill-chunk step programs: trace+lower "
          f"{lower_s:.1f}s, compile {compile_s:.1f}s (set-up); "
          f"tpu_custom_call counts {kernels}")
    if min(kernels.values()) == 0:
        raise SystemExit(f"chip_smoke: a step program runs no Pallas "
                         f"kernel: {kernels}")

    accepted, serve_s = serve.serve_requests(eng, reqs)
    st = eng.stats
    print(f"[serve] {st.completed}/{REQUESTS} requests completed "
          f"({accepted} accepted), {st.decoded_tokens} tokens decoded "
          f"in {serve_s:.1f}s incl. first-call compiles")
    if st.completed != REQUESTS or accepted != REQUESTS:
        raise SystemExit("chip_smoke: not every request completed")

    pre, first, dec = first_step_logits(eng, prompts)
    ref = build_engine(params, cfg.with_(paged_attn_impl="gather"),
                       backend="xla")
    pre_ref, _, dec_ref = first_step_logits(ref, prompts, first)
    finite = all(bool(np.isfinite(x).all()) for x in (pre, dec, pre_ref,
                                                        dec_ref))
    gap_dec, gap_pre = logit_gap(dec, dec_ref), logit_gap(pre, pre_ref)
    print(f"[check] max |pallas - xla| / max |logit|: first decode step "
          f"{gap_dec:.3e}, prefill {gap_pre:.3e} (tolerance {LOGIT_TOL:.3e}, "
          f"logits {dec.shape}); all finite: {finite}")
    peak = dev.memory_stats().get("peak_bytes_in_use")
    if peak is not None:
        print(f"[memory] peak device bytes in use {peak / 2**30:.2f} GiB")
    if not finite:
        raise SystemExit("chip_smoke: non-finite logits")
    if max(gap_dec, gap_pre) > LOGIT_TOL:
        raise SystemExit("chip_smoke: Pallas engine disagrees with the XLA "
                         "reference beyond the tolerance")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
