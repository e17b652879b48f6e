"""SmoothQuant+ pipeline tests: calibration stats, smoothing equivalence
(the paper's eq. 5 must hold EXACTLY, modulo bf16 rounding), alpha search,
and end-to-end PTQ accuracy ordering (SQ+ <= RTN loss)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_config
from repro.configs.base import QuantConfig
from repro.core import apply as AP
from repro.core import calibration as C
from repro.core import search as SE
from repro.core import smoothing as SM
from repro.models import api

B, T = 1, 24
# run the full matrix on a representative subset (one per family)
FAMILIES = [
    "codellama-7b",        # dense (paper's model)
    "starcoder2-15b",      # gelu/layernorm/bias
    "granite-moe-1b-a400m",# moe
    "deepseek-v2-236b",    # mla + moe
    "zamba2-7b",           # hybrid
    "rwkv6-7b",            # rwkv
    "whisper-medium",      # enc-dec
]


def _setup(arch):
    cfg = get_config(arch, smoke=True)
    # f32 params so equivalence checks aren't drowned in bf16 rounding
    cfg = cfg.with_(dtype="float32")
    params = api.init_model(jax.random.PRNGKey(0), cfg)
    batches = C.synthetic_calibration_set(cfg, n_seqs=2, seq_len=T)
    return cfg, params, batches


@pytest.fixture(scope="module", params=FAMILIES)
def setup(request):
    cfg, params, batches = _setup(request.param)
    col = C.collect_stats(params, cfg, batches)
    return cfg, params, batches, col


def test_stats_cover_all_groups(setup):
    cfg, params, batches, col = setup
    for g in SM.smoothing_groups(cfg):
        try:
            st = SM.assemble_stats(col, g.stats_block, g.stats_sub)
        except KeyError:
            pytest.fail(f"no stats for group {g.name}")
        assert np.all(st >= 0) and np.isfinite(st).all()


def _draw_biases(params, key):
    """Every linear bias (``{"w", "b"}``) and every LayerNorm bias
    (``{"scale", "bias"}``) drawn from a seeded normal: ``init_linear`` and
    ``init_norm`` make them zero, and a zero bias hides a smoothing that
    forgets to scale it."""
    drawn = 0

    def walk(node):
        nonlocal drawn
        if not isinstance(node, dict):
            return node
        out = {k: walk(node[k]) for k in sorted(node)}
        for leaf, sib in (("b", "w"), ("bias", "scale")):
            if leaf in out and sib in out:
                b = out[leaf]
                drawn += 1
                out[leaf] = (0.5 * jax.random.normal(
                    jax.random.fold_in(key, drawn), b.shape)).astype(b.dtype)
        return out

    return walk(params)


def test_smoothing_is_mathematically_equivalent(setup):
    """Paper eq. 5: smoothed (unquantized) model output == original, with
    every linear and LayerNorm bias drawn (nonzero)."""
    cfg, params, batches, col = setup
    params = _draw_biases(params, jax.random.PRNGKey(1))
    smoothed, s_map = SM.smooth_model(params, cfg, col, alpha=0.5)
    assert s_map, "no groups smoothed"
    batch = batches[0]
    ref = api.forward_fn(params, batch, cfg, backend="xla").astype(jnp.float32)
    got = api.forward_fn(smoothed, batch, cfg, backend="xla").astype(jnp.float32)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(ref), rtol=2e-3, atol=2e-3
    )


def test_alpha_search_returns_grid_min(setup):
    cfg, params, batches, col = setup
    res = SE.search_alpha(params, cfg, col, step=0.25, group_size=16)
    assert set(res.losses) == {0.0, 0.25, 0.5, 0.75, 1.0}
    assert res.loss == min(res.losses.values())
    assert np.isfinite(res.loss)


def test_sqplus_loss_not_worse_than_rtn(setup):
    """Smoothing at the searched alpha must not increase the weighted quant
    loss vs no smoothing (alpha=0 ≈ weight-only scaling; the paper's claim)."""
    cfg, params, batches, col = setup
    res = SE.search_alpha(params, cfg, col, step=0.25, group_size=16)
    base = SE.model_quant_loss(params, cfg, col, 0.0, group_size=16)
    assert res.loss <= base * (1 + 1e-6)


def test_end_to_end_ptq_runs_and_shrinks(setup):
    cfg, params, batches, col = setup
    qp, rep = AP.smoothquant_plus(
        params, cfg, batches, QuantConfig(group_size=16), step=0.5
    )
    assert rep.quantized_paths, "nothing quantized"
    # smoke scale uses group_size=16 + f32 scales → ~0.5×; production
    # (group=128, bf16) hits ~0.27× (asserted in test_quantize)
    assert rep.quant_bytes < 0.6 * rep.fp_bytes
    batch = batches[0]
    logits = api.forward_fn(qp, batch, cfg, backend="xla")
    assert bool(jnp.isfinite(logits.astype(jnp.float32)).all())


def test_quantized_model_bounded_error(setup):
    """W4 output must stay within a bounded relative error of FP.

    NOTE: random-init smoke models have NO activation-outlier structure, so
    SQ+ ≈ RTN here; the paper's advantage is reproduced mechanistically in
    test_sqplus_beats_rtn_with_outlier_channels below."""
    cfg, params, batches, col = setup
    qp, rep = AP.smoothquant_plus(
        params, cfg, batches, QuantConfig(group_size=16), step=0.5
    )
    batch = batches[0]
    ref = np.asarray(api.forward_fn(params, batch, cfg, backend="xla"), np.float32)
    got = np.asarray(api.forward_fn(qp, batch, cfg, backend="xla"), np.float32)
    rel = np.linalg.norm(got - ref) / max(np.linalg.norm(ref), 1e-9)
    assert np.isfinite(got).all() and rel < 0.6, f"rel error {rel:.3f}"


def test_sqplus_beats_rtn_with_outlier_channels():
    """The paper's core mechanism: when activations have persistent per-
    channel outliers (the >6.7B-LLM regime, §2.2), smoothing before RTN must
    reduce the quantized model's output error vs plain RTN.

    We induce the outlier structure by scaling a few embedding channels ×40:
    every token then carries those hot channels down the residual stream,
    exactly the 'fixed channels across all tokens' pattern of Fig. 2."""
    cfg = get_config("codellama-7b", smoke=True).with_(dtype="float32")
    params = api.init_model(jax.random.PRNGKey(0), cfg)
    hot = np.zeros(cfg.d_model, np.float32) + 1.0
    hot[[7, 13, 21, 40]] = 40.0
    params["embed"]["table"] = params["embed"]["table"] * hot[None, :]
    batches = C.synthetic_calibration_set(cfg, n_seqs=2, seq_len=24)

    qp, rep = AP.smoothquant_plus(
        params, cfg, batches, QuantConfig(group_size=16), step=0.25
    )
    rtn = AP.rtn_baseline(params, cfg, QuantConfig(group_size=16))
    b = batches[0]
    ref = np.asarray(api.forward_fn(params, b, cfg, backend="xla"), np.float32)
    sq = np.asarray(api.forward_fn(qp, b, cfg, backend="xla"), np.float32)
    rt = np.asarray(api.forward_fn(rtn, b, cfg, backend="xla"), np.float32)
    err_sq = np.linalg.norm(sq - ref) / np.linalg.norm(ref)
    err_rt = np.linalg.norm(rt - ref) / np.linalg.norm(ref)
    assert err_sq < err_rt, (
        f"SmoothQuant+ ({err_sq:.4f}) must beat RTN ({err_rt:.4f}) "
        "in the outlier regime"
    )
    # and the searched alpha should be > 0 (it found smoothing useful)
    assert rep.alpha > 0.0
