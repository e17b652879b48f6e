"""SmoothQuant+ smoothing: per-channel scale transfer with exact fusion.

For every *smoothing group* — a set of linear weights sharing one input
activation — we compute (paper eq. 6)::

    s_j = max|X_j|^alpha / max|W_j|^(1-alpha)

and apply ``W <- diag(s) W`` (rows scaled).  The matching ``X <- X diag(s)^-1``
is *fused into the provider* of that activation so inference sees zero extra
ops (paper §2.2, Fig. 5):

  kind "norm"            divide the preceding (RMS/Layer)Norm scale (and bias)
  kind "linear_out"      divide the preceding linear's output columns and
                         its bias (exact when the op between them is
                         per-channel linear: attention·V, SwiGLU's ⊙up,
                         Mamba2's gate)
  kind "linear_out_sqrt" divide columns and bias by sqrt(s) — for RWKV6's
                         channel-mix where the intermediate is relu(·)² (so
                         col scale c → c²)
  kind "linear_out_mla_v" divide only the V-columns of DeepSeek's wkv_b
  kind "none"            no smoothing possible (e.g. GELU MLP down-proj whose
                         producer is non-linear) — the weight is still
                         quantized, with s = 1

``tie="kv"`` handles GQA's o-proj: its input has H·Dh channels but the fusion
target (wv output) only Hkv·Dh; s is reduced (max) over each KV-head's query
group first, which keeps the transform exact at slightly reduced freedom.

Weight-shared blocks (Zamba2 shared attention) appear once in the group list;
their calibration stats already hold the channel-max over all call sites.

``row_compensations`` lists *non-quantized* consumers of the same activation
(MoE router, RWKV6 decay-LoRA A-matrix): their rows are scaled by ``s`` so the
model stays mathematically equivalent, but they are not quantized.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.calibration import StatsCollector

Path = Tuple[Any, ...]


@dataclasses.dataclass(frozen=True)
class Provider:
    kind: str                       # norm | linear_out | linear_out_sqrt | linear_out_mla_v | none
    path: Path = ()
    extra: Any = None


@dataclasses.dataclass(frozen=True)
class Group:
    name: str
    weights: Tuple[Path, ...]       # quantized + smoothed (path to the ARRAY)
    provider: Provider
    stats_block: Tuple[str, ...]    # collector block key
    stats_sub: Tuple[str, ...]      # collector weight subpath
    row_compensations: Tuple[Path, ...] = ()
    tie: Optional[str] = None       # None | "kv"
    layer_reduce: bool = False      # share s across the stacked layer dim


# ------------------------------------------------------------- tree utils ---
def tget(tree, path: Path):
    for k in path:
        tree = tree[k]
    return tree


def tset(tree, path: Path, val, *, create: bool = False):
    """Functionally set ``path`` to ``val``.  A missing segment raises
    KeyError (a mistyped path must fail loudly, not graft a dead branch)
    unless ``create=True`` — used only where growing the tree is the point
    (quantize_params inserting ``wkv_b_absorbed`` next to ``wkv_b``)."""
    if not path:
        return val
    out = dict(tree)
    if create and isinstance(tree, dict) and path[0] not in tree:
        sub = {}
    else:
        sub = tree[path[0]]
    out[path[0]] = tset(sub, path[1:], val, create=create)
    return out


# ------------------------------------------------------------ group tables --
def _attn_groups(cfg: ModelConfig, blk: Tuple[str, ...], mixer_key="mixer",
                 norm1="norm1") -> List[Group]:
    m = blk + (mixer_key,)
    return [
        Group(
            name="/".join(map(str, blk)) + ".qkv",
            weights=(m + ("wq", "w"), m + ("wk", "w"), m + ("wv", "w")),
            provider=Provider("norm", blk + (norm1,)),
            stats_block=(blk[0],), stats_sub=m[1:] + ("wq", "w"),
        ),
        Group(
            name="/".join(map(str, blk)) + ".wo",
            weights=(m + ("wo", "w"),),
            provider=Provider("linear_out", m + ("wv", "w")),
            stats_block=(blk[0],), stats_sub=m[1:] + ("wo", "w"),
            tie="kv",
        ),
    ]


def _mlp_groups(cfg: ModelConfig, blk: Tuple[str, ...], norm2="norm2") -> List[Group]:
    mlp = blk + ("mlp",)
    gs: List[Group] = []
    if cfg.moe is not None:
        ex = mlp + ("experts",)
        weights = [ex + ("gate",), ex + ("up",)]
        comps = [mlp + ("router", "w")]
        if cfg.moe.num_shared_experts:
            weights += [mlp + ("shared", "gate", "w"), mlp + ("shared", "up", "w")]
        gs.append(Group(
            name="moe.in", weights=tuple(weights),
            provider=Provider("norm", blk + (norm2,)),
            stats_block=(blk[0],), stats_sub=("mlp", "router", "w"),
            row_compensations=tuple(comps),
        ))
        gs.append(Group(
            name="moe.down", weights=(ex + ("down",),),
            provider=Provider("linear_out", ex + ("up",)),
            stats_block=(blk[0],), stats_sub=("mlp", "experts", "down"),
        ))
        if cfg.moe.num_shared_experts:
            gs.append(Group(
                name="moe.shared.down", weights=(mlp + ("shared", "down", "w"),),
                provider=Provider("linear_out", mlp + ("shared", "up", "w")),
                stats_block=(blk[0],), stats_sub=("mlp", "shared", "down", "w"),
            ))
        return gs
    if cfg.mlp == "swiglu":
        gs.append(Group(
            name="mlp.in", weights=(mlp + ("gate", "w"), mlp + ("up", "w")),
            provider=Provider("norm", blk + (norm2,)),
            stats_block=(blk[0],), stats_sub=("mlp", "gate", "w"),
        ))
        gs.append(Group(
            name="mlp.down", weights=(mlp + ("down", "w"),),
            provider=Provider("linear_out", mlp + ("up", "w")),
            stats_block=(blk[0],), stats_sub=("mlp", "down", "w"),
        ))
    else:  # gelu: up smoothable; down has a non-linear producer → s=1
        gs.append(Group(
            name="mlp.in", weights=(mlp + ("up", "w"),),
            provider=Provider("norm", blk + (norm2,)),
            stats_block=(blk[0],), stats_sub=("mlp", "up", "w"),
        ))
        gs.append(Group(
            name="mlp.down", weights=(mlp + ("down", "w"),),
            provider=Provider("none"),
            stats_block=(blk[0],), stats_sub=("mlp", "down", "w"),
        ))
    return gs


def _mla_groups(cfg: ModelConfig, blk: Tuple[str, ...]) -> List[Group]:
    m = blk + ("mixer",)
    mla = cfg.mla
    return [
        Group("mla.a", (m + ("wq_a", "w"), m + ("wkv_a", "w")),
              Provider("norm", blk + ("norm1",)),
              (blk[0],), ("mixer", "wq_a", "w")),
        Group("mla.qb", (m + ("wq_b", "w"),),
              Provider("norm", m + ("norm_q",)),
              (blk[0],), ("mixer", "wq_b", "w")),
        Group("mla.kvb", (m + ("wkv_b", "w"),),
              Provider("norm", m + ("norm_kv",)),
              (blk[0],), ("mixer", "wkv_b", "w")),
        Group("mla.wo", (m + ("wo", "w"),),
              Provider("linear_out_mla_v", m + ("wkv_b", "w"),
                       (cfg.num_heads, mla.qk_nope_head_dim, mla.v_head_dim)),
              (blk[0],), ("mixer", "wo", "w")),
    ]


def _mamba_groups(cfg: ModelConfig, blk: Tuple[str, ...]) -> List[Group]:
    m = blk + ("mixer",)
    return [
        Group("mamba.in",
              (m + ("in_z", "w"), m + ("in_x", "w"), m + ("in_bc", "w"),
               m + ("in_dt", "w")),
              Provider("norm", blk + ("norm1",)),
              (blk[0],), ("mixer", "in_z", "w")),
        Group("mamba.out", (m + ("out_proj", "w"),),
              Provider("norm", m + ("norm",)),
              (blk[0],), ("mixer", "out_proj", "w")),
    ]


def _rwkv_groups(cfg: ModelConfig, blk: Tuple[str, ...]) -> List[Group]:
    m = blk + ("mixer",)
    mlp = blk + ("mlp",)
    return [
        Group("rwkv.in",
              (m + ("wr", "w"), m + ("wk", "w"), m + ("wv", "w"), m + ("wg", "w")),
              Provider("norm", blk + ("norm1",)),
              (blk[0],), ("mixer", "wr", "w"),
              row_compensations=(m + ("w_lora_a",),)),
        Group("rwkv.wo", (m + ("wo", "w"),),
              Provider("norm", m + ("ln_x",)),
              (blk[0],), ("mixer", "wo", "w")),
        Group("rwkv.cm.in", (mlp + ("wk", "w"), mlp + ("wr", "w")),
              Provider("norm", blk + ("norm2",)),
              (blk[0],), ("mlp", "wk", "w")),
        Group("rwkv.cm.v", (mlp + ("wv", "w"),),
              Provider("linear_out_sqrt", mlp + ("wk", "w")),
              (blk[0],), ("mlp", "wv", "w")),
    ]


def smoothing_groups(cfg: ModelConfig) -> List[Group]:
    gs: List[Group] = []
    if cfg.encdec:
        for side, n_attn in (("enc", "self_attn"), ("dec", "self_attn")):
            blk = (side, "layers")
            m = blk + (n_attn,)
            gs.append(Group(
                f"{side}.qkv",
                (m + ("wq", "w"), m + ("wk", "w"), m + ("wv", "w")),
                Provider("norm", blk + ("norm1",)),
                (side,), (n_attn, "wq", "w")))
            gs.append(Group(
                f"{side}.wo", (m + ("wo", "w"),),
                Provider("linear_out", m + ("wv", "w")),
                (side,), (n_attn, "wo", "w"), tie="kv"))
        # decoder cross-attn: q fed by norm2; k/v fed by (shared) enc output
        c = ("dec", "layers", "cross_attn")
        gs.append(Group("dec.xq", (c + ("wq", "w"),),
                        Provider("norm", ("dec", "layers", "norm2")),
                        ("dec",), ("cross_attn", "wq", "w")))
        gs.append(Group("dec.xkv", (c + ("wk", "w"), c + ("wv", "w")),
                        Provider("norm", ("enc", "final_norm")),
                        ("dec",), ("cross_attn", "wk", "w"),
                        layer_reduce=True))
        gs.append(Group("dec.xo", (c + ("wo", "w"),),
                        Provider("linear_out", c + ("wv", "w")),
                        ("dec",), ("cross_attn", "wo", "w"), tie="kv"))
        # MLPs (gelu) — enc norm2, dec norm3
        for side, nrm in (("enc", "norm2"), ("dec", "norm3")):
            mlp = (side, "layers", "mlp")
            gs.append(Group(f"{side}.mlp.in", (mlp + ("up", "w"),),
                            Provider("norm", (side, "layers", nrm)),
                            (side,), ("mlp", "up", "w")))
            gs.append(Group(f"{side}.mlp.down", (mlp + ("down", "w"),),
                            Provider("none"), (side,), ("mlp", "down", "w")))
        return gs

    if cfg.family == "hybrid":
        for blk in (("groups",), ("tail",)):
            gs += _mamba_groups(cfg, blk)
        gs += _attn_groups(cfg, ("shared",))
        gs += _mlp_groups(cfg.with_(moe=None), ("shared",))
        return gs

    blk = ("layers",)
    if cfg.mixer == "attention":
        gs += _attn_groups(cfg, blk)
        gs += _mlp_groups(cfg, blk)
    elif cfg.mixer == "mla":
        gs += _mla_groups(cfg, blk)
        gs += _mlp_groups(cfg, blk)
    elif cfg.mixer == "mamba2":
        gs += _mamba_groups(cfg, blk)
    elif cfg.mixer == "rwkv6":
        gs += _rwkv_groups(cfg, blk)
    return gs


# ----------------------------------------------------------- s computation --
def assemble_stats(col: StatsCollector, block: Tuple[str, ...],
                   sub: Tuple[str, ...]) -> np.ndarray:
    """Gather per-layer stats into a stacked array [*lead, Ci]."""
    entries = {k[1]: v for k, v in col.stats.items()
               if k[0] == block and k[2] == sub}
    if not entries:
        raise KeyError(f"no calibration stats for {block}+{sub}")
    idxs = sorted(entries)
    if idxs == [()]:
        return entries[()]
    depth = len(idxs[0])
    if depth == 1:
        return np.stack([entries[(i,)] for i in range(len(idxs))])
    # depth 2 (hybrid groups): [G, K, ...]
    g = max(i[0] for i in idxs) + 1
    k = max(i[1] for i in idxs) + 1
    return np.stack([
        np.stack([entries[(gi, ki)] for ki in range(k)]) for gi in range(g)
    ])


# weight-sized elementwise passes run fused under jit: op by op, a stacked
# full-width weight would materialize several gigabyte-sized f32 copies
@jax.jit
def _row_absmax(w: jax.Array) -> jax.Array:
    return jnp.max(jnp.abs(w.astype(jnp.float32)), axis=-1)


@jax.jit
def _scale_rows(w: jax.Array, sal: jax.Array) -> jax.Array:
    return (w.astype(jnp.float32) * sal).astype(w.dtype)


def _w_absmax_in(w: jax.Array, stat_shape: Tuple[int, ...]) -> np.ndarray:
    """max_j |W[..., i, j]| reduced to ``stat_shape`` (= [*stat_lead, Ci])."""
    a = np.asarray(_row_absmax(w))
    while a.ndim > len(stat_shape):       # reduce extra middle dims (e.g. E)
        ax = a.ndim - 2                   # innermost lead dim
        a = a.max(axis=ax)
    return a


def compute_group_s(
    params, cfg: ModelConfig, col: StatsCollector, group: Group, alpha: float
) -> np.ndarray:
    """Smoothing factors for one group, shape [*stat_lead, Ci]."""
    act = assemble_stats(col, group.stats_block, group.stats_sub)
    if group.provider.kind == "none":
        return np.ones_like(act)
    if group.layer_reduce:
        # one shared s across the stacked layer dim (the provider is shared,
        # e.g. whisper's enc.final_norm feeding every decoder cross-attn)
        act = np.broadcast_to(act.max(axis=0), act.shape).copy()
    wmax = None
    for wp in group.weights:
        wm = _w_absmax_in(tget(params, wp), act.shape)
        wmax = wm if wmax is None else np.maximum(wmax, wm)
    if group.layer_reduce and wmax is not None:
        wmax = np.broadcast_to(wmax.max(axis=0), wmax.shape).copy()
    eps = 1e-8
    s = np.power(np.maximum(act, eps), alpha) / np.power(
        np.maximum(wmax, eps), 1.0 - alpha
    )
    s = np.where((act > eps) & (wmax > eps), s, 1.0)
    s = np.clip(s, 1e-4, 1e4)
    if group.tie == "kv":
        hkv, grp = cfg.num_kv_heads, cfg.num_heads // cfg.num_kv_heads
        dh = s.shape[-1] // (hkv * grp)
        sr = s.reshape(*s.shape[:-1], hkv, grp, dh).max(axis=-2)
        s = np.broadcast_to(
            sr[..., :, None, :], (*s.shape[:-1], hkv, grp, dh)
        ).reshape(s.shape)
    return s.astype(np.float32)


def _align(s: np.ndarray, w: jax.Array) -> jnp.ndarray:
    """Broadcast s [*stat_lead, Ci] against w [*w_lead, Ci, Co] rows."""
    extra = w.ndim - 1 - s.ndim
    shape = (*s.shape[:-1], *([1] * extra), s.shape[-1], 1)
    return jnp.asarray(s.reshape(shape))


def _bias_path(params, wp: Path) -> Optional[Path]:
    """The bias beside the linear weight at ``wp`` (``{"w", "b"}``), if any."""
    if wp[-1] != "w" or "b" not in tget(params, wp[:-1]):
        return None
    return wp[:-1] + ("b",)


def apply_group(params, cfg: ModelConfig, group: Group, s: np.ndarray):
    """Scale group weights by s (rows) and fuse 1/s into the provider."""
    if group.provider.kind == "none":
        return params
    for wp in group.weights + group.row_compensations:
        w = tget(params, wp)
        params = tset(params, wp, _scale_rows(w, _align(s, w)))
    pk, pp = group.provider.kind, group.provider.path
    # a layer_reduce group has one shared s; its provider is a single
    # (unstacked) module, so drop the stacked layer dim before fusing
    s_prov = s[0] if group.layer_reduce else s
    if group.tie == "kv":
        # s is constant over each KV head's query group (built that way);
        # the provider (wv) has only Hkv·Dh output cols — take one per group
        hkv, grp = cfg.num_kv_heads, cfg.num_heads // cfg.num_kv_heads
        dh = s_prov.shape[-1] // (hkv * grp)
        s_prov = s_prov.reshape(*s_prov.shape[:-1], hkv, grp, dh)[..., :, 0, :]
        s_prov = s_prov.reshape(*s.shape[:-1], hkv * dh) if not group.layer_reduce \
            else s_prov.reshape(hkv * dh)
    if pk == "norm":
        nrm = tget(params, pp)
        sn = jnp.asarray(s_prov)
        new = dict(nrm, scale=(nrm["scale"].astype(jnp.float32) / sn).astype(nrm["scale"].dtype))
        if "bias" in nrm:
            new["bias"] = (nrm["bias"].astype(jnp.float32) / sn).astype(nrm["bias"].dtype)
        params = tset(params, pp, new)
    elif pk in ("linear_out", "linear_out_sqrt"):
        w = tget(params, pp)
        sd = jnp.asarray(np.sqrt(s_prov) if pk == "linear_out_sqrt" else s_prov)
        extra = w.ndim - 1 - sd.ndim
        cols = sd.reshape(*sd.shape[:-1], *([1] * extra), 1, sd.shape[-1])
        params = tset(params, pp, (w.astype(jnp.float32) / cols).astype(w.dtype))
        # the provider's output is x W + b: its bias columns take the same
        # factor, or the consumer sees x W / s + b for (x W + b) / s
        bp = _bias_path(params, pp)
        if bp is not None:
            b = tget(params, bp)
            params = tset(params, bp, (b.astype(jnp.float32)
                                       / cols[..., 0, :]).astype(b.dtype))
    elif pk == "linear_out_mla_v":
        h, nope, v = group.provider.extra
        if _bias_path(params, pp) is not None:
            raise ValueError(f"{group.name}: a biased wkv_b would need its "
                             "V bias columns scaled too")
        w = tget(params, pp)                        # [*lead, r, H*(nope+v)]
        lead = w.shape[:-2]
        r = w.shape[-2]
        wr = w.astype(jnp.float32).reshape(*lead, r, h, nope + v)
        sv = jnp.asarray(s_prov).reshape(*s_prov.shape[:-1], h, v)  # [*lead, H, v]
        wv_part = wr[..., nope:] / sv[..., None, :, :]
        wr = wr.at[..., nope:].set(wv_part)
        params = tset(params, pp, wr.reshape(w.shape).astype(w.dtype))
    else:
        raise ValueError(pk)
    return params


def smooth_model(
    params, cfg: ModelConfig, col: StatsCollector, alpha: float
) -> Tuple[Any, Dict[str, np.ndarray]]:
    """Apply SmoothQuant+ smoothing at strength alpha.  Returns (params, {group: s})."""
    s_map: Dict[str, np.ndarray] = {}
    for g in smoothing_groups(cfg):
        try:
            s = compute_group_s(params, cfg, col, g, alpha)
        except KeyError:
            continue  # block absent (e.g. no "tail" in this hybrid layout)
        params = apply_group(params, cfg, g, s)
        s_map[g.name] = s
    return params, s_map
