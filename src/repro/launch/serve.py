"""Serving driver: FP checkpoint → SmoothQuant+ quantize-on-load →
continuous-batching engine (the paper's vLLM deployment flow).

    PYTHONPATH=src python -m repro.launch.serve --arch codellama-7b --smoke \
        --requests 12 [--no-quant] [--ptq-artifact DIR]

Beyond attention-only decoders the same flow serves hybrid SSM
(``--arch zamba2-7b``: per-layer fixed-rows state next to the paged
attention KV) and encoder-decoder (``--arch whisper-medium``: synthetic
encoder frames per request, deduplicated read-only encoder pages).

At full size (no ``--smoke``) the published widths are served in the
config's own dtype; ``--num-layers N`` cuts depth only.  On a TPU the
engine runs the Pallas W4A16 and paged-attention kernels (``chip_smoke.py``
at the repo root drives this path once and checks it).

``--ptq-artifact DIR`` makes boot load-*or*-quantize: the first run saves the
quantized pytree there; later runs deserialize it and skip calibration + the
α search entirely (a config change invalidates the artifact via its hash).
"""
from __future__ import annotations

import argparse
import os
import time
from pathlib import Path

import jax
import numpy as np

from repro.configs import get_config
from repro.configs.base import ModelConfig, QuantConfig
from repro.core.calibration import synthetic_calibration_set
from repro.models import api
from repro.serving.engine import Request, ServingEngine, load_or_quantize

REPO_ROOT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Keep JAX's persistent compilation cache across runs.

    ``$JAX_COMPILATION_CACHE_DIR`` wins when set (JAX reads it itself, so
    nothing is set here); otherwise the cache lives at the fixed in-checkout
    path ``<repo>/.jax_cache`` — the path is part of the cache key, so it
    must not move between runs.  Returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(REPO_ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def model_config(arch: str, *, smoke: bool = False,
                 num_layers: int | None = None, act_quant: str = "a16",
                 quant: bool = True) -> ModelConfig:
    """The served config: published widths, depth optionally cut to the
    first ``num_layers`` layers (never a width).  Full-size configs keep
    their own dtype — the PTQ math upcasts per tensor — while smoke configs
    run PTQ in f32."""
    cfg = get_config(arch, smoke=smoke).with_(act_quant=act_quant)
    if num_layers is not None:
        if not 1 <= num_layers <= cfg.num_layers:
            raise ValueError(f"--num-layers {num_layers} outside "
                             f"1..{cfg.num_layers} for {cfg.name}")
        cfg = cfg.with_(num_layers=num_layers)
    if quant and smoke:
        cfg = cfg.with_(dtype="float32")
    return cfg


def describe_cut(cfg: ModelConfig, arch: str, smoke: bool = False) -> str:
    """One line naming the depth cut and the (untouched) widths."""
    full = get_config(arch, smoke=smoke).num_layers
    return (f"[model] {cfg.name}: {cfg.num_layers} of {full} layers (depth "
            f"cut only) d_model={cfg.d_model} heads={cfg.num_heads}/"
            f"{cfg.num_kv_heads}kv head_dim={cfg.hdim} d_ff={cfg.d_ff} "
            f"vocab={cfg.vocab_size} dtype={cfg.dtype}")


def quantize_on_load(params, cfg: ModelConfig, group_size: int, *,
                     artifact_dir=None, refresh: bool = False):
    """SmoothQuant+ quantize-on-load (or a matching PTQ artifact's load).
    Returns ``(qparams, report, mode, seconds)``; the seconds cover the
    recipe up to quantized params resident on the device."""
    from repro.core.apply import ptq_matches

    calib = synthetic_calibration_set(cfg, n_seqs=2, seq_len=24)
    qcfg = QuantConfig(group_size=group_size)
    # a present-but-stale artifact still re-quantizes: label the boot by
    # the path load_or_quantize will actually take
    loaded = (artifact_dir is not None and not refresh
              and ptq_matches(artifact_dir, cfg, qcfg))
    t0 = time.perf_counter()
    params, rep = load_or_quantize(params, cfg, calib, qcfg,
                                   artifact_dir=artifact_dir, refresh=refresh)
    jax.block_until_ready(params)
    mode = "artifact-load" if loaded else "quantize-on-load"
    return params, rep, mode, time.perf_counter() - t0


def make_requests(cfg: ModelConfig, n: int, *, prompt_len: int = 10,
                  max_tokens: int = 8, rate: float = 50.0,
                  shared_prefix_len: int = 0, deadline_s=None,
                  ttft_deadline_s=None, with_frames: bool = False) -> list:
    """Seeded synthetic requests: Poisson arrivals at ``rate`` req/s on
    the engine's clock (``time.perf_counter``), ``prompt_len`` random tokens
    behind an optional shared prefix.  Enc-dec requests carry synthetic
    encoder frames; every third repeats the first one's audio so the
    exact-match encoder page cache has something to deduplicate."""
    rng = np.random.default_rng(0)
    # deadlines are wall-clock budgets from arrival: rebase the synthetic
    # Poisson offsets onto the engine's clock, or every request would look
    # minutes old at its first deadline check
    base = time.perf_counter()
    arrive = base + np.cumsum(rng.exponential(1.0 / rate, n))
    sys_p = rng.integers(2, cfg.vocab_size, shared_prefix_len).astype(np.int32)

    def frames(i):
        if not with_frames:
            return None
        r = np.random.default_rng(1000 + (0 if i % 3 == 0 else i))
        t = 6 + (0 if i % 3 == 0 else i % 5)
        return (r.standard_normal((t, cfg.d_model)) * 0.1).astype(np.float32)

    return [Request(uid=i,
                    prompt=np.concatenate(
                        [sys_p, rng.integers(2, cfg.vocab_size,
                                             prompt_len).astype(np.int32)]),
                    max_tokens=max_tokens, arrival_t=float(arrive[i]),
                    deadline_s=deadline_s, ttft_deadline_s=ttft_deadline_s,
                    frames=frames(i))
            for i in range(n)]


def serve_requests(eng: ServingEngine, reqs) -> tuple:
    """Submit every request and drain the engine.  Returns
    ``(accepted, seconds)``."""
    t0 = time.perf_counter()
    accepted = sum(eng.submit(r) for r in reqs)
    eng.run_until_drained()
    return accepted, time.perf_counter() - t0


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="codellama-7b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--num-layers", type=int, default=None,
                    help="serve only the first N layers (a depth cut; "
                         "every width stays as published)")
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--batch-size", type=int, default=4)
    ap.add_argument("--max-seq", type=int, default=64)
    ap.add_argument("--max-tokens", type=int, default=8)
    ap.add_argument("--rate", type=float, default=50.0, help="req/s (Poisson)")
    ap.add_argument("--no-quant", action="store_true")
    ap.add_argument("--act-quant", choices=("a16", "a8_prefill"),
                    default="a16",
                    help="activation quantization: a16 (default, bf16/f32 "
                         "activations) or a8_prefill (per-token int8 "
                         "activations on prefill-chunk GEMMs for A8-eligible "
                         "layers; decode stays A16)")
    ap.add_argument("--group-size", type=int, default=None)
    ap.add_argument("--ptq-artifact", default=None,
                    help="dir for the PTQ artifact: save on first boot, "
                         "load (skip calibration + alpha search) after")
    ap.add_argument("--ptq-refresh", action="store_true",
                    help="force re-quantization even if a matching artifact "
                         "exists (use after swapping checkpoints — the "
                         "artifact hash covers configs, not weight values)")
    ap.add_argument("--page-size", type=int, default=16,
                    help="KV cache page size (tokens)")
    ap.add_argument("--prefill-mode", choices=("bucketed", "slotwise"),
                    default="bucketed")
    ap.add_argument("--max-prefill-tokens", type=int, default=None,
                    help="padded-token budget per engine step (chunked prefill)")
    ap.add_argument("--reservation", choices=("lazy", "worstcase"),
                    default="lazy",
                    help="page reservation: lazy growth + preemption "
                         "(default) or up-front prompt+max_tokens pages")
    ap.add_argument("--num-pages", type=int, default=None,
                    help="KV pool size in pages (default: worst case + trash)")
    ap.add_argument("--prefix-cache", choices=("on", "off"), default="on",
                    help="shared-prefix KV cache: block-hash reuse of full "
                         "prompt pages + suffix-only prefill (default on)")
    ap.add_argument("--shared-prefix-len", type=int, default=0,
                    help="give every synthetic request this many identical "
                         "leading prompt tokens (a shared system prompt) so "
                         "the prefix cache has something to hit")
    ap.add_argument("--max-queue", type=int, default=None,
                    help="bounded waiting line: submit() sheds load "
                         "(finish_reason='rejected') past this depth")
    ap.add_argument("--deadline-s", type=float, default=None,
                    help="per-request total wall budget from arrival; "
                         "expired requests free their pages immediately")
    ap.add_argument("--ttft-deadline-s", type=float, default=None,
                    help="per-request first-token budget from arrival")
    ap.add_argument("--chaos", action="store_true",
                    help="install a seeded FaultPlan firing at every "
                         "injection site and serve non-strict (graceful "
                         "degradation demo: the drain must survive)")
    ap.add_argument("--fault-seed", type=int, default=0,
                    help="seed for the --chaos fault plan")
    ap.add_argument("--no-metrics", action="store_true",
                    help="disable engine observability (timelines, "
                         "histograms, step journal) — the overhead-"
                         "benchmark baseline configuration")
    ap.add_argument("--trace-out", default=None,
                    help="write the served batch's step journal + request "
                         "timelines as Chrome trace_event JSON (open in "
                         "https://ui.perfetto.dev or chrome://tracing)")
    args = ap.parse_args(argv)
    if args.trace_out and args.no_metrics:
        ap.error("--trace-out needs metrics enabled (drop --no-metrics)")

    enable_compile_cache()
    cfg = model_config(args.arch, smoke=args.smoke, num_layers=args.num_layers,
                       act_quant=args.act_quant, quant=not args.no_quant)
    print(describe_cut(cfg, args.arch, args.smoke))
    params = api.init_model(jax.random.PRNGKey(0), cfg)

    if not args.no_quant:
        gs = args.group_size or (16 if args.smoke else 128)
        params, rep, mode, secs = quantize_on_load(
            params, cfg, gs, artifact_dir=args.ptq_artifact,
            refresh=args.ptq_refresh)
        print(f"[{mode}] alpha={rep.alpha:.2f} "
              f"{rep.fp_bytes/1e6:.1f}MB -> {rep.quant_bytes/1e6:.1f}MB "
              f"in {secs:.1f}s")

    fault_plan = None
    if args.chaos:
        from repro.serving.faults import FaultPlan, FaultSpec
        fault_plan = FaultPlan([
            FaultSpec("page_alloc", every=11, times=3),
            FaultSpec("page_grow", prob=0.05, times=3),
            FaultSpec("pool_pressure", step=4, value=2, duration=3),
            FaultSpec("swap_drain", op=0, times=1),
            FaultSpec("swap_corrupt", op=1, times=1),
            FaultSpec("prefix_evict", every=5, times=2),
            FaultSpec("decode_launch", step=6, times=2),
            FaultSpec("prefill_launch", op=2, times=1),
            FaultSpec("fixed_drain", op=0, times=1),
            FaultSpec("enc_evict", op=1, times=1),
        ], seed=args.fault_seed)
    # the token prefix cache is attention-only (the engine rejects it for
    # hybrid SSM / enc-dec configs — see state leaves in serving/engine.py)
    leaves = api.state_leaves(cfg)
    prefix_cache = (args.prefix_cache == "on" and leaves == (api.KV_PAGES,))
    if args.prefix_cache == "on" and not prefix_cache:
        print(f"[note] token prefix cache disabled: {cfg.family} slots carry "
              f"state leaves {leaves}")
    # backend "auto": the Pallas kernels on a TPU, the XLA references on
    # any other host
    eng = ServingEngine(params, cfg, batch_size=args.batch_size,
                        max_seq=args.max_seq, backend="auto",
                        page_size=args.page_size,
                        num_pages=args.num_pages,
                        prefill_mode=args.prefill_mode,
                        max_prefill_tokens=args.max_prefill_tokens,
                        reservation=args.reservation,
                        prefix_cache=prefix_cache,
                        max_queue=args.max_queue,
                        fault_plan=fault_plan,
                        strict=not args.chaos,
                        metrics=not args.no_metrics)
    reqs = make_requests(cfg, args.requests, max_tokens=args.max_tokens,
                         rate=args.rate,
                         shared_prefix_len=args.shared_prefix_len,
                         deadline_s=args.deadline_s,
                         ttft_deadline_s=args.ttft_deadline_s,
                         with_frames=eng.has_enc)
    accepted, dt = serve_requests(eng, reqs)

    # every stat line below renders engine.metrics_snapshot() — the single
    # structured source for operator reporting (benchmarks read it too)
    snap = eng.metrics_snapshot()
    st = snap["engine"]

    def ms(h, k="p50"):
        return f"{snap['latency'][h][k] * 1e3:.1f}ms"

    print(f"served {st['completed']}/{args.requests} requests "
          f"({accepted} accepted), "
          f"{st['decoded_tokens']} tokens in {dt:.2f}s  "
          f"({st['decoded_tokens'] / dt:.1f} tok/s)")
    if not args.no_metrics:
        print(f"latency: ttft p50={ms('ttft_s')} p99={ms('ttft_s', 'p99')}  "
              f"itl p50={ms('itl_s')} p99={ms('itl_s', 'p99')}  "
              f"e2e p50={ms('e2e_s')} p99={ms('e2e_s', 'p99')}  "
              f"queue-wait p50={ms('queue_wait_s')}  "
              f"swap-stall p50={ms('swap_stall_s')}")
    print(f"lifecycle: rejected={st['rejected']} expired={st['expired']} "
          f"cancelled={st['cancelled']} failed={st['failed']} "
          f"retries={st['retries']} faults_injected={st['faults_injected']}")
    if fault_plan is not None:
        print(f"chaos: fault counters "
              f"{snap['counters'].get('faults_fired_total', {})}")
    pg = snap["pager"]
    print(f"pager: peak concurrency {st['max_active']}/{args.batch_size}, "
          f"{st['grown_pages']} pages grown lazily, "
          f"{st['preemptions']} preemptions "
          f"({st['swapped_out_bytes'] / 1e6:.1f}MB swapped out, "
          f"of which {st['swapped_fixed_bytes'] / 1e6:.1f}MB fixed-rows "
          f"state, {st['swapped_in_bytes'] / 1e6:.1f}MB back in); "
          f"free={pg['free_pages']}/{pg['total_pages']} "
          f"counts={pg['counts']}")
    if eng.has_enc:
        print(f"encoder pages: {st['enc_encodes']} encodes, "
              f"{st['enc_hits']} exact-match hits")
    if prefix_cache:
        hit = st["prefix_hits"] / max(st["admitted"], 1)
        print(f"prefix-cache: hit-rate {hit:.0%} "
              f"({st['prefix_hits']}/{st['admitted']} admissions, "
              f"{st['prefix_matched_tokens']} prompt tokens served from "
              f"cache), {st['pages_shared']} pages shared, "
              f"{st['pages_inserted']} inserted, "
              f"{st['pages_evicted']} evicted, "
              f"{st['cow_copies']} copy-on-writes")
    if args.trace_out:
        from repro.serving.trace import write_chrome_trace
        obj = write_chrome_trace(args.trace_out, eng.trace,
                                 n_slots=args.batch_size)
        print(f"trace: {len(obj['traceEvents'])} events -> {args.trace_out} "
              f"(open in https://ui.perfetto.dev)")


if __name__ == "__main__":
    main()
