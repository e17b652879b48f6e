"""The one traffic generator: an open-loop arrival schedule from a traffic
file's parameters and the run's seed.

A traffic file (``bench/traffic/<name>.json``) gives

* ``arrivals``: ``{"process": "poisson" | "gamma", "rate_rps": r}`` and,
  for ``gamma``, the inter-arrival coefficient of variation ``cv``
  (``cv`` 1 is Poisson; above 1 arrivals come in bursts);
* ``prompt_tokens`` / ``output_tokens``: lognormal lengths
  ``{"median": m, "sigma": s, "min": lo, "max": hi}``;
* ``sizes_seed``: the seed of the size and gap draws;
* ``strata``: the block size of the stratified orders below.

Every run seed gets the same schedule: the gaps, prompt lengths and output
lengths are drawn once from ``sizes_seed`` and put in one order, also drawn
from ``sizes_seed``; the run seed draws the prompts' token ids (and, in the
harness, the weights).  The tail of a bursty mix depends on the order
itself, on which long prompts land in a burst: drawn anew for each seed,
the order alone moved ``ttft_p95_ms`` by a third between seeds, where two
runs of one seed agreed within a tenth.  So two seeds do the same work at
the same times, and the spread between seeds is the system's.  The order
is stratified: each run of ``strata`` consecutive arrivals holds one value
from each of ``strata`` quantile bands, in a shuffled order, so that any
stretch of the schedule holds about the same work.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List

import numpy as np


@dataclasses.dataclass
class Arrival:
    uid: int
    t: float              # scheduled send time, seconds after the schedule starts
    prompt: np.ndarray    # int32 token ids
    max_tokens: int       # the request runs to exactly this many tokens


def lognormal_lengths(spec: Dict[str, Any], n: int,
                      rng: np.random.Generator) -> np.ndarray:
    z = rng.standard_normal(n)
    x = np.round(spec["median"] * np.exp(spec["sigma"] * z))
    return np.clip(x, spec["min"], spec["max"]).astype(np.int64)


def gaps(arrivals: Dict[str, Any], n: int,
         rng: np.random.Generator) -> np.ndarray:
    rate = float(arrivals["rate_rps"])
    kind = arrivals["process"]
    if kind == "poisson":
        return rng.exponential(1.0 / rate, n)
    if kind == "gamma":
        shape = 1.0 / float(arrivals["cv"]) ** 2
        return rng.gamma(shape, 1.0 / (rate * shape), n)
    raise ValueError(f"unknown arrival process {kind!r}")


def size_pool(traffic: Dict[str, Any], horizon_s: float):
    """The seed-independent multiset: gaps that sum past ``horizon_s`` (so
    every order of them covers it), and one prompt and output length per
    gap."""
    base = np.random.default_rng(int(traffic["sizes_seed"]))
    arr = traffic["arrivals"]
    n = int(math.ceil(float(arr["rate_rps"]) * horizon_s * 1.5)) + 8
    g = gaps(arr, n, base)
    while g.sum() < horizon_s:
        g = np.concatenate([g, gaps(arr, n, base)])
    n = len(g)
    return (g, lognormal_lengths(traffic["prompt_tokens"], n, base),
            lognormal_lengths(traffic["output_tokens"], n, base))


def stratified(values: np.ndarray, k: int,
               rng: np.random.Generator) -> np.ndarray:
    """``values`` in an order whose every block of ``k`` consecutive entries
    takes one entry from each of ``k`` bands of the sorted values."""
    bands = np.array_split(np.argsort(values, kind="stable"), k)
    for b in bands:
        rng.shuffle(b)
    order = []
    for j in range(max(len(b) for b in bands)):
        block = [b[j] for b in bands if j < len(b)]
        rng.shuffle(block)
        order += block
    return values[np.asarray(order)]


def schedule(traffic: Dict[str, Any], seed: int, horizon_s: float,
             vocab: int) -> List[Arrival]:
    """Arrivals whose send time falls before ``horizon_s``."""
    g, plen, olen = size_pool(traffic, horizon_s)
    order = np.random.default_rng([int(traffic["sizes_seed"]), 1])
    k = int(traffic["strata"])
    g, plen, olen = (stratified(a, k, order) for a in (g, plen, olen))
    rng = np.random.default_rng(seed)
    t = np.cumsum(g)
    out = []
    for i in range(len(g)):
        if t[i] >= horizon_s:
            break
        out.append(Arrival(
            uid=i, t=float(t[i]),
            prompt=rng.integers(2, vocab, int(plen[i])).astype(np.int32),
            max_tokens=int(olen[i])))
    return out
