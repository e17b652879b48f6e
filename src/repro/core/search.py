"""Global smoothing-strength (alpha) grid search — SmoothQuant+ §2.2/§3.1.3.

Unlike AWQ's per-layer search, a SINGLE alpha is searched for the WHOLE model
by minimizing the total activation-weighted quantization loss

    E(alpha) = Σ_linears || diag(x̂) (W_s − Q(W_s)) ||²,   x̂ = stats / s

over a grid (default 0→1 step 0.05, the paper's recommendation).  Because the
loss is evaluated directly on (smoothed weights, smoothed stats) it accounts
for the whole model at once — no per-layer error accumulation — and one grid
point costs one fake-quant sweep of the weights (this is why the paper's
search is ~5× faster than AWQ's).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core import smoothing as SM
from repro.core.calibration import StatsCollector
from repro.core.quantize import fake_quantize


@dataclasses.dataclass
class SearchResult:
    alpha: float
    loss: float
    losses: Dict[float, float]          # full grid → loss curve (paper Tab. 4)


@functools.partial(jax.jit, static_argnames=("group_size",))
def _weighted_sq_err(w, sal, xb, group_size: int) -> jax.Array:
    """``Σ ((W·s − Q(W·s)) · x̂)²`` for one weight, fused by XLA so no
    weight-sized f32 temporaries pile up (a full-width stacked weight is
    gigabytes in f32)."""
    ws = w.astype(jnp.float32) * sal
    err = ws - fake_quantize(ws, group_size)
    return jnp.sum((err * xb) ** 2)


def _group_quant_loss(
    params, cfg: ModelConfig, col: StatsCollector, group: SM.Group,
    alpha: float, group_size: int,
) -> float:
    """Activation-weighted loss for one group at one alpha (eq. 4 proxy)."""
    act = SM.assemble_stats(col, group.stats_block, group.stats_sub)
    s = SM.compute_group_s(params, cfg, col, group, alpha)
    x_hat = jnp.asarray(act / s)        # smoothed activation stats
    total = 0.0
    for wp in group.weights:
        w = SM.tget(params, wp)
        extra = w.ndim - 1 - x_hat.ndim
        xb = x_hat.reshape(*x_hat.shape[:-1], *([1] * extra), x_hat.shape[-1], 1)
        total += float(_weighted_sq_err(w, SM._align(s, w), xb, group_size))
    return total


def model_quant_loss(
    params, cfg: ModelConfig, col: StatsCollector, alpha: float,
    group_size: int = 128,
) -> float:
    total = 0.0
    for g in SM.smoothing_groups(cfg):
        try:
            total += _group_quant_loss(params, cfg, col, g, alpha, group_size)
        except KeyError:
            continue
    return total


def search_alpha(
    params,
    cfg: ModelConfig,
    col: StatsCollector,
    *,
    step: float = 0.05,
    group_size: int = 128,
    verbose: bool = False,
) -> SearchResult:
    """Grid-search alpha ∈ {0, step, …, 1} minimizing the whole-model loss."""
    grid = np.round(np.arange(0.0, 1.0 + 1e-9, step), 10)
    losses: Dict[float, float] = {}
    for a in grid:
        losses[float(a)] = model_quant_loss(params, cfg, col, float(a), group_size)
        if verbose:
            print(f"  alpha={a:.2f}  loss={losses[float(a)]:.6f}")
    best = min(losses, key=losses.get)
    return SearchResult(alpha=best, loss=losses[best], losses=losses)
