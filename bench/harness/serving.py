"""Drive the program's served path: build the engine as ``launch/serve.py``
builds it, warm the shapes the traffic reaches, then serve an open-loop
schedule and stamp every token on the host clock.

One engine step blocks on its sampled tokens (``engine.py``: the step reads
them back before it returns), so the clock reading taken when ``step()``
returns is when every token of that step became visible.  All the latency
metrics are computed from these stamps, not from the engine's histograms.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from harness import traffic as T
from harness import weights as W

SPAN_STEP = "bench.step"          # around engine.step()
SPAN_SUBMIT = "bench.submit"      # around engine.submit() of due requests
SPAN_WAIT = "bench.wait"          # the generator sleeping for the next arrival
SPAN_STAMP = "bench.stamp"        # the harness stamping tokens after a step
SPAN_TRACED = "bench.traced"      # the profiled part of the window


@dataclasses.dataclass
class Built:
    eng: Any
    cfg: Any
    ptq_s: float


def program_config(config: Dict[str, Any], act_quant: str = "a16"):
    """The program's own config for this model, cut as the file says; every
    width must agree with the file."""
    from repro.launch import serve

    cfg = serve.model_config(config["arch"], smoke=config.get("smoke", False),
                             num_layers=config["num_hidden_layers"],
                             act_quant=act_quant)
    cfg = cfg.with_(dtype="bfloat16")
    d = W.dims(config)
    want = dict(d_model=d.d_model, num_heads=d.heads,
                num_kv_heads=d.kv_heads, hdim=d.head_dim, d_ff=d.d_ff,
                vocab_size=d.vocab, num_layers=d.layers,
                norm=d.norm, rope_theta=d.rope_theta,
                mlp={"gelu_tanh": "gelu", "swiglu": "swiglu"}[d.mlp],
                attn_bias=d.bias)
    got = {k: getattr(cfg, k) for k in want}
    if got != want:
        raise SystemExit(f"program config {cfg.name} differs from "
                         f"{config['name']}: {got} != {want}")
    return cfg


def build(config: Dict[str, Any], engine: Dict[str, Any], seed: int,
          log) -> Built:
    from repro.launch import serve
    from repro.models import api
    from repro.serving.engine import ServingEngine

    cfg = program_config(config, engine["act_quant"])
    d = W.dims(config)
    t0 = time.perf_counter()
    params = W.make_params(d, seed)
    jax.block_until_ready(params)
    params_s = time.perf_counter() - t0
    want = jax.eval_shape(lambda: api.init_model(jax.random.PRNGKey(0), cfg))
    same = jax.tree.map(lambda a, b: (a.shape, a.dtype) == (b.shape, b.dtype),
                        params, want)
    if not all(jax.tree.leaves(same)):
        raise SystemExit("bench weights do not match the program's layout")
    params, rep, mode, ptq_s = serve.quantize_on_load(
        params, cfg, d.group)
    log(f"[weights] {d.layers} layers made from the seed in {params_s:.2f}s; "
        f"{mode} group={d.group} alpha={rep.alpha:.2f} "
        f"{rep.fp_bytes / 1e9:.2f}GB -> {rep.quant_bytes / 1e9:.2f}GB "
        f"in {ptq_s:.2f}s")
    eng = ServingEngine(
        params, cfg, batch_size=engine["batch_size"],
        max_seq=engine["max_seq"], page_size=engine["page_size"],
        backend="auto", max_prefill_tokens=engine["max_prefill_tokens"],
        prefix_cache=engine["prefix_cache"],
        reservation=engine["reservation"], eos_id=cfg.vocab_size)
    return Built(eng, cfg, ptq_s)


def warm(eng, prefill: List[Tuple[int, int]]) -> float:
    """Make ready (compile, or load from the persistent cache) every program
    the window will call: the decode step, the prefill-chunk step at each
    ``(rows, bucket)`` pair, the sampler on each of their logits, and the
    key split.  Inputs point every row at the trash page, so no slot's
    pages are written.  Returns seconds."""
    t0 = time.perf_counter()
    key, _ = jax.random.split(eng.key)
    temps = lambda n: jnp.zeros((n,), jnp.float32)
    sampled = []
    for n, b in prefill:
        lens = np.full(n, b, np.int32)
        logits, eng.pools = eng._prefill_chunk(
            eng.params, jnp.asarray(np.zeros((n, b), np.int32)),
            jnp.asarray(lens - 1), jnp.asarray(np.zeros(n, np.int32)),
            jnp.asarray(lens), jnp.asarray(np.zeros((n, eng.P), np.int32)),
            eng.pools)
        if n not in sampled:
            sampled.append(n)
            eng._sample(logits, key, temps(n))
    zeros = np.zeros(eng.B, np.int32)
    logits, eng.pools = eng._decode(
        eng.params, eng.pools, jnp.asarray(zeros[:, None]),
        jnp.asarray(zeros), jnp.asarray(np.zeros((eng.B, eng.P), np.int32)))
    jax.block_until_ready(eng._sample(logits, key, temps(eng.B)))
    jax.block_until_ready(eng.pools)
    return time.perf_counter() - t0


@dataclasses.dataclass
class Track:
    """What the harness saw of one request."""
    sched_t: float                      # scheduled send time (host clock)
    req: Any
    stamps: List[float] = dataclasses.field(default_factory=list)
    submit_t: Optional[float] = None
    admit_t: Optional[float] = None


@dataclasses.dataclass
class StepWork:
    """Work one traced engine step launched (for the work counts)."""
    decode_ctx: List[int] = dataclasses.field(default_factory=list)
    # per prefill launch: (pad_len, starts, lens, final)
    prefill: List[Tuple[int, List[int], List[int], List[bool]]] = \
        dataclasses.field(default_factory=list)


@dataclasses.dataclass
class Window:
    t0: float                           # window start (host clock)
    t1: float                           # window end
    tracks: List[Track]                 # every request sent, ramp included
    stats0: Dict[str, int]              # engine counters at t0
    stats1: Dict[str, int]              # and at t1
    compiles: Dict[str, int]            # lowered / compiled inside the window
    compiled: List[str]                 # name and lowering time of each
    lateness: List[float]               # submit - scheduled, window requests
    pairs: Dict[Tuple[int, int], int]   # prefill (rows, bucket) launches seen
    traced: Optional[Tuple[float, float]] = None
    steps: List[StepWork] = dataclasses.field(default_factory=list)


class _CompileCounter:
    """Counts programs lowered (``jaxpr -> MLIR``, every new specialisation,
    cached or not) and XLA backend compiles while ``on``."""

    def __init__(self):
        from jax._src import dispatch
        self.events = {dispatch.JAXPR_TO_MLIR_MODULE_EVENT: "lowered",
                       dispatch.BACKEND_COMPILE_EVENT: "compiled"}
        self.on = False
        self.n = {"lowered": 0, "compiled": 0}
        self.names: List[str] = []
        jax.monitoring.register_event_duration_secs_listener(self)

    def __call__(self, event, duration, **kw):
        if self.on and event in self.events:
            self.n[self.events[event]] += 1
            if self.events[event] == "lowered":
                self.names.append(f"{kw.get('fun_name')} {duration:.3f}s")


def _stats(eng) -> Dict[str, int]:
    return dataclasses.asdict(eng.stats)


def _record_work(eng, pairs: Dict[Tuple[int, int], int],
                 rec: List[StepWork], tracing):
    """Wrap the engine's chunk planner and decode launch so that the harness
    sees what each step launched (host state only): every prefill
    ``(rows, bucket)`` pair, and in traced steps the work of each launch."""
    plan, dec = eng.sched.plan_chunks, eng._decode

    def plan_rec(items, budget=None):
        bks = plan(items, budget)
        for b in bks:
            key = (len(b.slots), b.pad_len)
            pairs[key] = pairs.get(key, 0) + 1
        if tracing[0]:
            rec[-1].prefill.extend((b.pad_len, list(b.starts), list(b.lens),
                                    list(b.final)) for b in bks)
        return bks

    def dec_rec(*args):
        if tracing[0]:
            rec[-1].decode_ctx.extend(
                int(eng.pos[i]) + 1 for i, s in enumerate(eng.slots)
                if s is not None and eng.pos[i] >= eng.pref_target[i])
        return dec(*args)

    eng.sched.plan_chunks = plan_rec
    eng._decode = dec_rec


def serve_window(eng, arrivals: List[T.Arrival], ramp_s: float,
                 seconds: float, *, trace_s: float = 0.0,
                 trace_dir: Optional[str] = None) -> Window:
    """Serve ``arrivals`` open loop: ``ramp_s`` seconds to reach steady
    state, then the measured window of ``seconds``.  With ``trace_s`` the
    profiler records that many seconds in the middle of the window, from
    and to a point where the device has finished every launched step."""
    from repro.serving.engine import Request

    span = jax.profiler.TraceAnnotation
    counter = _CompileCounter()
    steps: List[StepWork] = []
    pairs: Dict[Tuple[int, int], int] = {}
    tracing = [False]
    _record_work(eng, pairs, steps, tracing)
    start = time.perf_counter()
    tw0, tw1 = start + ramp_s, start + ramp_s + seconds
    tr0 = tw0 + max(0.0, (seconds - trace_s) / 2)
    tr1 = tr0 + trace_s
    traced = None
    tspan = None
    tracks = [Track(start + a.t, Request(
        uid=a.uid, prompt=a.prompt, max_tokens=a.max_tokens,
        arrival_t=start + a.t)) for a in arrivals]
    active: Dict[int, Track] = {}
    queued: Dict[int, Track] = {}
    nxt = 0
    stats0 = None
    while True:
        now = time.perf_counter()
        if stats0 is None and now >= tw0:
            stats0, counter.on = _stats(eng), True
        if trace_s and traced is None and now >= tr0:
            jax.block_until_ready(eng.pools)
            jax.profiler.start_trace(trace_dir)
            tspan = span(SPAN_TRACED)
            tspan.__enter__()
            traced = [time.perf_counter(), None]
            tracing[0] = True
        if tracing[0] and now >= tr1:
            jax.block_until_ready(eng.pools)
            traced[1] = time.perf_counter()
            tspan.__exit__(None, None, None)
            tracing[0] = False
            jax.profiler.stop_trace()
        if now >= tw1:
            break
        if nxt < len(tracks) and tracks[nxt].sched_t <= now:
            with span(SPAN_SUBMIT):
                while nxt < len(tracks) and tracks[nxt].sched_t <= now:
                    tk = tracks[nxt]
                    eng.submit(tk.req)
                    tk.submit_t = time.perf_counter()
                    queued[tk.req.uid] = tk
                    nxt += 1
        if eng.queue or any(s is not None for s in eng.slots):
            if tracing[0]:
                steps.append(StepWork())
            with span(SPAN_STEP):
                eng.step()
            t = time.perf_counter()
            with span(SPAN_STAMP):
                for s in eng.slots:
                    if s is not None and s.uid in queued:
                        tk = active[s.uid] = queued.pop(s.uid)
                        tl = eng.trace.live.get(s.uid)
                        tk.admit_t = tl.admit_t if tl is not None else t
                for uid in list(active):
                    tk = active[uid]
                    new = len(tk.req.output) - len(tk.stamps)
                    if new:
                        tk.stamps.extend([t] * new)
                    if tk.req.finish_reason is not None:
                        del active[uid]
        else:
            wake = min(tracks[nxt].sched_t if nxt < len(tracks) else tw1, tw1)
            if stats0 is None:
                wake = min(wake, tw0)
            if trace_s and traced is None:
                wake = min(wake, tr0)
            elif tracing[0]:
                wake = min(wake, tr1)
            with span(SPAN_WAIT):
                time.sleep(max(0.0, wake - time.perf_counter()))
    if tracing[0]:                       # a trace longer than the window
        jax.block_until_ready(eng.pools)
        traced[1] = time.perf_counter()
        tspan.__exit__(None, None, None)
        jax.profiler.stop_trace()
    counter.on = False
    stats1 = _stats(eng)
    lateness = [tk.submit_t - tk.sched_t for tk in tracks[:nxt]
                if tw0 <= tk.sched_t < tw1]
    return Window(t0=tw0, t1=tw1, tracks=tracks[:nxt], stats0=stats0,
                  stats1=stats1, compiles=dict(counter.n),
                  compiled=counter.names, lateness=lateness,
                  pairs=pairs, traced=tuple(traced) if traced else None,
                  steps=steps)
