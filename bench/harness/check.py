"""The comparison that decides ``correct``.

After the window has closed and the program's state is freed, a sample of
the requests the window finished — drawn from the seed, the longest always
in it — goes through the plain reference (``bench/references/<name>.py``)
once each: prompt and served tokens together, teacher-forced, layer by
layer, with each layer's weights made anew from the seed.  At every
position where the engine served a token, the number read is how far the
served token's reference logit lies below the reference's best logit.  Two
numbers are read from these gaps (``NUMBERS``), and each that has a limit in
the configuration's ``check`` is compared with it: the widest gap over the
sample, which a single wrong token moves, and the mean gap, which grows
with the square of the arithmetic's noise (a token flips where its margin
is under the noise, and then misses by about that much), so that it sets
one precision step well apart from the next.

The control (``control=True``) reads, at the same positions, how far the
token that the reference computed with int8 activations puts first lies
below the reference's best.
"""
from __future__ import annotations

import dataclasses
import importlib
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from harness import weights as W


@dataclasses.dataclass
class Served:
    prompt: np.ndarray          # int32
    output: List[int]           # served tokens, in order


def sample(finished: List[Served], seed: int, min_tokens: int,
           max_requests: int) -> List[Served]:
    """The longest finished request (prompt + output), then others in an
    order drawn from the seed, until ``min_tokens`` served tokens."""
    if not finished:
        return []
    order = sorted(range(len(finished)),
                   key=lambda i: -(len(finished[i].prompt)
                                   + len(finished[i].output)))
    rest = order[1:]
    np.random.default_rng([int(seed), 7]).shuffle(rest)
    out, n = [], 0
    for i in [order[0]] + rest:
        if n >= min_tokens or len(out) >= max_requests:
            break
        out.append(finished[i])
        n += len(finished[i].output)
    return out


def _pad(n: int, block: int) -> int:
    """``n`` rounded up to ``block``, then to a multiple of ``2 * block``:
    few shapes, so the reference compiles (or loads from the persistent
    cache) a handful of programs."""
    if n <= block:
        return block
    return -(-n // (2 * block)) * 2 * block


def reference_gaps(config: Dict, seed: int, reqs: List[Served],
                   control: bool = False) -> Dict[str, np.ndarray]:
    """Per served position: ``gap`` (reference best − reference logit of the
    served token), ``margin`` (reference best − its runner-up) and, with
    ``control``, ``control_gap`` (reference best − reference logit of the
    int8-activation reference's first choice)."""
    ref = importlib.import_module(f"references.{config['reference']}")
    d = W.dims(config)
    k_embed, k_head, k_layers = W.keys(seed)
    seqs = [np.concatenate([r.prompt, np.asarray(r.output[:-1], np.int32)])
            for r in reqs]
    table = W.embed_table_jit(d, k_embed)
    xs = []
    for s in seqs:
        t = _pad(len(s), ref.Q_BLOCK)
        toks = jnp.asarray(np.pad(s, (0, t - len(s))))
        xs.append(ref.embed(table, toks, d))
    del table
    variants = {"ref": False, "ctl": True} if control else {"ref": False}
    xs = {name: list(xs) for name in variants}
    for i in range(d.layers):
        lp = W.layer_params_jit(d, jax.random.fold_in(k_layers, i))
        for name, low in variants.items():
            xs[name] = [ref.layer(lp, x, d, low) for x in xs[name]]
        del lp
    head = W.lm_head_jit(d, k_head)
    final_norm = W.norm_params(d)
    gaps, ctl, margin, best_l = [], [], [], []
    for j, r in enumerate(reqs):
        p0, n = len(r.prompt) - 1, len(r.output)
        lg = np.asarray(ref.logits(final_norm, head, xs["ref"][j][p0:p0 + n], d))
        best = lg.max(axis=-1)
        served = np.asarray(r.output, np.int64)
        gaps.append(best - lg[np.arange(n), served])
        top2 = np.sort(lg, axis=-1)[:, -2:]
        margin.append(top2[:, 1] - top2[:, 0])
        best_l.append(best)
        if control:
            lc = np.asarray(ref.logits(final_norm, head,
                                       xs["ctl"][j][p0:p0 + n], d))
            first = lc.argmax(axis=-1)
            ctl.append(best - lg[np.arange(n), first])
    out = {"gap": np.concatenate(gaps), "margin": np.concatenate(margin),
           "best": np.concatenate(best_l),
           "request": np.concatenate([np.full(len(g), j)
                                      for j, g in enumerate(gaps)]),
           "position": np.concatenate([np.arange(len(g)) for g in gaps])}
    if control:
        out["control_gap"] = np.concatenate(ctl)
    return out


NUMBERS = ("max_logit_gap", "mean_logit_gap")


def _numbers(gap: np.ndarray, prefix: str = "") -> Dict[str, float]:
    if not np.isfinite(gap).all():
        return {prefix + n: float("inf") for n in NUMBERS}
    return {prefix + "max_logit_gap": float(np.max(gap)),
            prefix + "mean_logit_gap": float(np.mean(gap))}


def compare(config: Dict, seed: int, reqs: List[Served],
            control: bool = False) -> Dict[str, Optional[float]]:
    """The numbers ``correct`` rests on."""
    if not reqs:
        return {**{n: None for n in NUMBERS}, "tokens_compared": 0}
    g = reference_gaps(config, seed, reqs, control=control)
    gap = g["gap"]
    res = {**_numbers(gap),
           "tokens_compared": int(gap.size),
           "requests_compared": len(reqs),
           "agree_share": float(np.mean(gap == 0.0)),
           "median_margin": float(np.median(g["margin"]))}
    w = int(np.argmax(gap))
    j = int(g["request"][w])
    res["widest_at"] = {"request_len": [len(reqs[j].prompt),
                                        len(reqs[j].output)],
                        "output_index": int(g["position"][w]),
                        "best_logit": float(g["best"][w]),
                        "margin": float(g["margin"][w])}
    if control:
        cg = g["control_gap"]
        res.update(_numbers(cg, "control_"))
        res["control_agree_share"] = float(np.mean(cg == 0.0))
    return res


def verdict(limits: Dict[str, Optional[float]], res: Dict,
            control: bool = False) -> Dict[str, Dict]:
    """Each number that has a limit, beside it; the program's numbers, or
    with ``control`` the control's."""
    out = {}
    for name, limit in limits.items():
        if limit is not None:
            v = res.get(("control_" if control else "") + name)
            out[name] = {"value": v, "limit": limit,
                         "ok": v is not None and v <= limit}
    return out
