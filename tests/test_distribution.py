"""Sharding rules, roofline analytics, HLO collective parsing, and a
mini end-to-end pjit train step on a local 1-device mesh."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import get_config
from repro.configs.base import SHAPES_BY_NAME, TrainConfig, QuantConfig
from repro.launch import hlo_analysis as HA
from repro.launch import jaxpr_cost as JC
from repro.launch import roofline as RL
from repro.models import api
from repro.optim import adamw
from repro.sharding import rules


def _mesh16():
    # 16x16 spec-building only needs axis names/sizes, not real devices
    from jax.sharding import AbstractMesh
    return AbstractMesh((16, 16), ("data", "model"))


def test_param_specs_dense_tp():
    cfg = get_config("mistral-large-123b")
    shapes = jax.eval_shape(lambda: api.init_model(jax.random.PRNGKey(0), cfg))
    specs = rules.param_specs(shapes, _mesh16(), cfg)
    flat = _flatten_specs(specs)
    assert flat["embed/table"] == P("model", None)
    assert flat["layers/mixer/wq/w"] == P(None, None, "model")
    assert flat["layers/mixer/wo/w"] == P(None, "model", None)
    # mistral kv=8 < 16 → kv replicated
    assert flat["layers/mixer/wk/w"] == P()
    assert flat["layers/mlp/down/w"] == P(None, "model", None)


def _flatten_specs(specs):
    return {
        rules._path_str(p): s
        for p, s in jax.tree_util.tree_flatten_with_path(specs)[0]
    }


def test_param_specs_zamba_kv_sharded():
    cfg = get_config("zamba2-7b")  # kv=32 divisible by 16 → sharded
    shapes = jax.eval_shape(lambda: api.init_model(jax.random.PRNGKey(0), cfg))
    specs = rules.param_specs(shapes, _mesh16(), cfg)
    flat = _flatten_specs(specs)
    assert flat["shared/mixer/wk/w"] == P(None, "model")
    assert flat["groups/mixer/in_x/w"] == P(None, None, None, "model")
    # replicated (padded spec is all-None)
    assert all(a is None for a in flat["groups/mixer/in_bc/w"])


def test_quantized_param_specs_follow_fp():
    from repro.core.apply import quantize_params

    cfg = get_config("mistral-large-123b")
    shapes = jax.eval_shape(lambda: api.init_model(jax.random.PRNGKey(0), cfg))
    qshapes = jax.eval_shape(lambda p: quantize_params(p, cfg, QuantConfig())[0], shapes)
    specs = rules.param_specs(qshapes, _mesh16(), cfg)
    flat = _flatten_specs(specs)
    assert flat["layers/mixer/wq/w/packed"] == P(None, None, "model")
    # scales keep only the output-axis sharding
    assert flat["layers/mixer/wq/w/scales"] == P(None, None, "model")
    assert flat["layers/mlp/down/w/scales"] == P(None, None, None)


def test_quantized_moe_specs_ep_and_cosharded():
    """Stacked quantized expert leaves shard the expert dim (EP) and the
    packed/scales/zeros trio stays co-sharded on every non-group axis."""
    from repro.core.apply import quantize_params

    cfg = get_config("deepseek-v2-236b")
    shapes = jax.eval_shape(lambda: api.init_model(jax.random.PRNGKey(0), cfg))
    qshapes = jax.eval_shape(
        lambda p: quantize_params(p, cfg, QuantConfig())[0], shapes)
    specs = rules.param_specs(qshapes, _mesh16(), cfg)
    flat = _flatten_specs(specs)
    # experts [L, E, Ci(/2|/G), Co]: E=160 on model (EP); contraction unsharded
    for leaf in ("gate", "up", "down"):
        for field in ("packed", "scales", "zeros"):
            assert flat[f"layers/mlp/experts/{leaf}/{field}"] == P(
                None, "model", None, None), (leaf, field)
    # MLA absorbed decode weights [L, H, Ci', Co']: heads on model (TP)
    for leaf in ("wk_t", "wv"):
        for field in ("packed", "scales", "zeros"):
            assert flat[f"layers/mixer/wkv_b_absorbed/{leaf}/{field}"] == P(
                None, "model", None, None), (leaf, field)


def test_quantized_trio_cosharded_everywhere():
    """Property over ALL quantized leaves: scales/zeros == packed's spec with
    only the group axis (second-to-last) dropped — never a lead-axis or
    output-axis divergence (a mis-coshard would misalign dequant groups)."""
    from repro.core.apply import quantize_params

    for arch in ("deepseek-v2-236b", "mistral-large-123b"):
        cfg = get_config(arch)
        shapes = jax.eval_shape(
            lambda: api.init_model(jax.random.PRNGKey(0), cfg))
        qshapes = jax.eval_shape(
            lambda p: quantize_params(p, cfg, QuantConfig())[0], shapes)
        flat = _flatten_specs(rules.param_specs(qshapes, _mesh16(), cfg))
        packed = {k[: -len("/packed")]: v for k, v in flat.items()
                  if k.endswith("/packed")}
        assert packed, arch
        for base, pspec in packed.items():
            for field in ("scales", "zeros"):
                fspec = flat[f"{base}/{field}"]
                assert len(fspec) == len(pspec) or not tuple(fspec), base
                if tuple(fspec):
                    assert tuple(fspec)[:-2] == tuple(pspec)[:-2], base
                    assert tuple(fspec)[-1] == tuple(pspec)[-1], base


def test_opt_specs_zero_shards_over_data():
    cfg = get_config("llama3.2-3b")
    shapes = jax.eval_shape(lambda: api.init_model(jax.random.PRNGKey(0), cfg))
    tc = TrainConfig()
    opt_shape = jax.eval_shape(lambda p: adamw.init_opt_state(p, tc), shapes)
    pspecs = rules.param_specs(shapes, _mesh16(), cfg)
    ospecs = rules.opt_specs(opt_shape, pspecs, _mesh16())
    flat = _flatten_specs(ospecs.mu)
    # wq moment: (28, 3072, 3072) param spec (None,None,model) → data on dim1
    assert flat["layers/mixer/wq/w"] == P(None, "data", "model")


def test_cache_specs_decode():
    cfg = get_config("mistral-large-123b")
    shape = SHAPES_BY_NAME["decode_32k"]
    cache = api.cache_specs(cfg, shape)
    specs = rules.cache_specs_tree(cache, _mesh16())
    flat = _flatten_specs(specs)
    # [L, B, S, Hkv, Dh]: batch 128 → data, seq 32768 → model (SP decode)
    assert flat["layers/k"] == P(None, ("data",), ("model",), None, None)


def test_cache_specs_long_context_batch1():
    cfg = get_config("rwkv6-7b")
    shape = SHAPES_BY_NAME["long_500k"]
    cache = api.cache_specs(cfg, shape)
    specs = rules.cache_specs_tree(cache, _mesh16())
    flat = _flatten_specs(specs)
    # rwkv state [L, B=1, H=64, K, V] → heads on model
    assert flat["layers/wkv"] == P(None, None, "model", None, None)


# ------------------------------------------------------------- analytics ----
def test_jaxpr_cost_counts_scan_trips():
    def f(x, w):
        def body(c, wi):
            return c @ wi, None
        return jax.lax.scan(body, x, w)[0]

    x = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    w = jax.ShapeDtypeStruct((10, 128, 128), jnp.float32)
    c = JC.jaxpr_cost(f, x, w)
    assert c["flops"] == 10 * 2 * 128**3


def test_jaxpr_cost_sees_remat_recompute():
    def f(x, w):
        def body(c, wi):
            return jax.checkpoint(lambda c, wi: jnp.tanh(c @ wi))(c, wi), None
        return jnp.sum(jax.lax.scan(body, x, w)[0])

    x = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    w = jax.ShapeDtypeStruct((4, 64, 64), jnp.float32)
    fwd = JC.jaxpr_cost(f, x, w)["flops"]
    grad = JC.jaxpr_cost(lambda x, w: jax.grad(
        lambda x: f(x, w))(x), x, w)["flops"]
    # backward with remat ≥ 3× forward matmul flops (fwd recompute + 2 bwd)
    assert grad >= 2.9 * fwd


def test_hlo_collective_parser_toy():
    hlo = """
HLO module m
%cond (p: (s32[], f32[4])) -> pred[] {
  %c = s32[] constant(7)
  ROOT %lt = pred[] compare(s32[] %iv, s32[] %c), direction=LT
}
%body (p: (s32[], f32[4])) -> (s32[], f32[4]) {
  %ar = f32[4]{0} all-reduce(f32[4]{0} %x), to_apply=%add
  ROOT %t = (s32[], f32[4]) tuple(%iv, %ar)
}
ENTRY %main () -> f32[4] {
  %w = (s32[], f32[4]) while((s32[], f32[4]) %init), condition=%cond, body=%body
  %ag = f32[8]{0} all-gather(f32[4]{0} %y), dimensions={0}
  ROOT %r = f32[4] get-tuple-element(%w), index=1
}
"""
    coll = HA.collective_bytes(hlo)
    assert coll["all-reduce"] == 16 * 7   # inside while ×7
    assert coll["all-gather"] == 32       # entry ×1


def test_roofline_terms_and_bottleneck():
    rl = RL.Roofline(flops=1e15, hbm_bytes=1e12, coll_bytes=1e13, chips=256,
                     model_flops=8e14)
    d = rl.to_dict()
    assert abs(d["t_compute_s"] - 1e15 / (256 * RL.PEAK_FLOPS)) < 1e-12
    assert d["bottleneck"] == "collective"
    assert 0 < d["roofline_fraction"] <= 1.0


def test_model_flops_moe_counts_active_only():
    cfg = get_config("deepseek-v2-236b")
    shape = SHAPES_BY_NAME["train_4k"]
    shapes = jax.eval_shape(lambda: api.init_model(jax.random.PRNGKey(0), cfg))
    ntot, nemb = RL.count_params(shapes)
    mf = RL.model_flops_estimate(cfg, shape, ntot, nemb)
    dense_equiv = 6 * (ntot - nemb) * shape.global_batch * shape.seq_len
    assert mf < 0.5 * dense_equiv  # top-6/160 is sparse


# ------------------------------------------------ 1-device pjit smoke -------
def test_pjit_train_step_local_mesh():
    from repro.train.trainer import make_train_step
    from jax.sharding import NamedSharding

    cfg = get_config("codellama-7b", smoke=True)
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    params = api.init_model(jax.random.PRNGKey(0), cfg)
    tc = TrainConfig()
    opt = adamw.init_opt_state(params, tc)
    pspecs = rules.param_specs(params, mesh, cfg)
    named = lambda t: jax.tree.map(
        lambda sp: NamedSharding(mesh, sp), t,
        is_leaf=lambda x: isinstance(x, P))
    step = jax.jit(make_train_step(cfg, tc, "xla"),
                   in_shardings=(named(pspecs), None, None),
                   out_shardings=(named(pspecs), None, None))
    toks = jnp.zeros((2, 16), jnp.int32)
    batch = {"tokens": toks, "labels": toks}
    with mesh:
        p2, o2, m = step(params, opt, batch)
    assert bool(jnp.isfinite(m["loss"]))
