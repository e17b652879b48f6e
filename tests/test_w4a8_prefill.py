"""W4A8 prefill path: A8 kernel bodies vs XLA oracles, dispatch gating,
calibrated eligibility, artifact round trip, and model-level closeness."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import QuantConfig
from repro.core import apply as AP
from repro.core import calibration as C
from repro.core import smoothing as SM
from repro.core import quantize as q
from repro.kernels import ops
from repro.kernels.ref import w4a8_matmul_ref, w4a8_grouped_ref, w4a16_matmul_ref
from repro.kernels.w4a16_matmul import w4a16_matmul
from repro.kernels.w4a16_grouped import w4a16_grouped_matmul
from repro.models import api


def _mk(t, ci, co, g, seed=0, dtype=jnp.float32):
    kx, kw = jax.random.split(jax.random.PRNGKey(seed))
    x = jax.random.normal(kx, (t, ci), jnp.float32).astype(dtype)
    w = jax.random.normal(kw, (ci, co), jnp.float32)
    return x, q.quantize(w, group_size=g)


# --------------------------------------------------- kernel parity (ragged) --
@pytest.mark.parametrize(
    "t,ci,co,g",
    [
        (16, 128, 128, 128),   # minimal A8-gated shape, aligned
        (64, 256, 512, 64),    # multi-block, non-default group
        (300, 384, 384, 128),  # T and Co not multiples of default blocks
        (33, 96, 112, 16),     # everything ragged, tiny groups
        (17, 48, 40, 48),      # Ci one group, Co forces block shrink
    ],
)
@pytest.mark.parametrize("act", ["a16", "a8"])
def test_kernel_ragged_parity(t, ci, co, g, act):
    """Interpret-mode kernel vs XLA oracle for BOTH bodies at ragged shapes
    (T, Co, Ci off the default blocks; non-default group sizes)."""
    x, qt = _mk(t, ci, co, g)
    got = w4a16_matmul(x, qt, block_t=128, block_co=128, interpret=True,
                       act=act)
    ref = w4a8_matmul_ref if act == "a8" else w4a16_matmul_ref
    want = ref(x, qt)
    assert got.shape == want.shape and got.dtype == want.dtype
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize(
    "e,c,d,f,g",
    [
        (2, 16, 64, 64, 16),
        (4, 37, 64, 80, 16),   # ragged capacity AND ragged Co
        (3, 21, 96, 48, 48),
    ],
)
@pytest.mark.parametrize("act", ["a16", "a8"])
def test_grouped_kernel_ragged_parity(e, c, d, f, g, act):
    kx, kw = jax.random.split(jax.random.PRNGKey(1))
    x = jax.random.normal(kx, (e, c, d), jnp.float32)
    w = jax.random.normal(kw, (e, d, f), jnp.float32)
    qt = q.quantize(w, group_size=g)
    got = w4a16_grouped_matmul(x, qt, interpret=True, act=act)
    from repro.kernels.ref import w4a16_grouped_ref
    ref = w4a8_grouped_ref if act == "a8" else w4a16_grouped_ref
    want = ref(x, qt)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-4)


def test_a8_oracle_is_exact_int8_math():
    """The XLA oracle's f32 einsum must equal true int32 integer math (all
    products/group-sums sit below 2^24) — the parity target is exact."""
    x, qt = _mk(32, 128, 64, 16, seed=5)
    xq, xs = q.quantize_acts_per_token(x)
    from repro.kernels.ref import _folded_int_codes
    wq = _folded_int_codes(qt)                      # [g, ci/g, co] f32 codes
    g = wq.shape[-3]
    xg = xq.astype(jnp.int32).reshape(32, g, -1)
    part = jnp.einsum("tgi,gio->tgo", xg, wq.astype(jnp.int32))
    y_int = (jnp.sum(part.astype(jnp.float32)
                     * qt.scales.astype(jnp.float32)[None], axis=1) * xs)
    np.testing.assert_array_equal(np.asarray(y_int, np.float32),
                                  np.asarray(w4a8_matmul_ref(x, qt), np.float32))


# ------------------------------------------------------------ ops gating ----
def test_small_t_request_falls_back_bit_identical():
    """Below ops.A8_MIN_TOKENS rows, an act="a8" request must return the
    bit-identical A16 result (decode stays on the memory-bound A16 body)."""
    x, qt = _mk(ops.A8_MIN_TOKENS - 1, 128, 128, 128, seed=2)
    a16 = ops.w4a16_matmul(x, qt, backend="xla")
    a8 = ops.w4a16_matmul(x, qt, backend="xla", act="a8")
    np.testing.assert_array_equal(np.asarray(a16), np.asarray(a8))


def test_ineligible_flag_falls_back_bit_identical():
    x, qt = _mk(64, 128, 128, 128, seed=3)
    qt_off = dataclasses.replace(qt, a8=False)
    a16 = ops.w4a16_matmul(x, qt, backend="xla")
    a8 = ops.w4a16_matmul(x, qt_off, backend="xla", act="a8")
    np.testing.assert_array_equal(np.asarray(a16), np.asarray(a8))


def test_a8_dispatch_xla_equals_interpret():
    x, qt = _mk(32, 256, 128, 128, seed=4)
    a = ops.w4a16_matmul(x, qt, backend="xla", act="a8")
    b = ops.w4a16_matmul(x, qt, backend="interpret", act="a8",
                         block_t=32, block_co=128)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-5, atol=1e-4)
    assert not np.array_equal(
        np.asarray(a), np.asarray(ops.w4a16_matmul(x, qt, backend="xla")))


def test_bad_act_rejected():
    x, qt = _mk(16, 128, 128, 128)
    with pytest.raises(ValueError, match="act"):
        ops.w4a16_matmul(x, qt, backend="xla", act="a4")


def test_a8_flag_is_static_metadata():
    """a8 rides tree metadata, not a traced leaf: jit must retrace on flip
    (kernel choice is trace-time) and tree_map must preserve the flag."""
    _, qt = _mk(16, 128, 128, 128)
    qt_off = dataclasses.replace(qt, a8=False)
    leaves, treedef = jax.tree_util.tree_flatten(qt)
    leaves_off, treedef_off = jax.tree_util.tree_flatten(qt_off)
    assert len(leaves) == len(leaves_off) == 3
    assert treedef != treedef_off
    assert jax.tree_util.tree_map(lambda a: a, qt_off).a8 is False


# ------------------------------------------- eligibility + artifact flags ----
def outlier_model(arch: str, seed: int = 0, hot_scale: float = 100.0):
    """A float32 smoke model whose embedding scales a few channels by
    ``hot_scale``: activation-outlier channels like those of models past
    6.7B parameters, which SmoothQuant+ exists for, at smoke size."""
    cfg = get_config(arch, smoke=True).with_(dtype="float32")
    params = api.init_model(jax.random.PRNGKey(seed), cfg)
    rng = np.random.default_rng(seed)
    hot = np.ones(cfg.d_model, np.float32)
    hot[rng.choice(cfg.d_model, size=max(2, cfg.d_model // 32),
                   replace=False)] = hot_scale
    params["embed"]["table"] = params["embed"]["table"] * hot[None, :]
    return cfg, params


@pytest.fixture(scope="module")
def outlier_ptq():
    cfg, params = outlier_model("codellama-7b")
    batches = C.synthetic_calibration_set(cfg, n_seqs=2, seq_len=24)
    qcfg = QuantConfig(group_size=16, alpha=0.5)
    qp, rep = AP.smoothquant_plus(params, cfg, batches, qcfg)
    return cfg, qcfg, params, qp, rep


def test_eligibility_map_mixed(outlier_ptq):
    """The injected hot channels must push at least one layer back to A16
    while well-behaved layers stay A8-eligible — and the tree flags must
    agree with the report."""
    cfg, qcfg, _, qp, rep = outlier_ptq
    flags = rep.a8_eligibility
    assert any(flags.values()), flags
    assert not all(flags.values()), flags
    for p in rep.quantized_paths:
        node = SM.tget(qp, p)
        key = "/".join(map(str, p))
        if isinstance(node, q.QuantizedTensor):
            assert node.a8 == flags[key]
    # every decided path has its deciding error recorded, and the decision
    # is exactly the threshold comparison
    for key, ok in flags.items():
        if key in rep.a8_errors:
            assert ok == (rep.a8_errors[key] <= qcfg.a8_threshold)


def test_artifact_roundtrip_preserves_flags(tmp_path, outlier_ptq):
    cfg, qcfg, _, qp, rep = outlier_ptq
    art = tmp_path / "a8art"
    AP.save_ptq(art, qp, rep, cfg, qcfg)
    tree2, rep2 = AP.load_ptq(art, cfg, qcfg)
    assert rep2.a8_eligibility == rep.a8_eligibility
    assert rep2.a8_errors == pytest.approx(rep.a8_errors)
    for p in rep.quantized_paths:
        n1, n2 = SM.tget(qp, p), SM.tget(tree2, p)
        if isinstance(n1, q.QuantizedTensor):
            assert n1.a8 == n2.a8, p
        else:
            assert all(n1[k].a8 == n2[k].a8 for k in n1), p


def test_threshold_change_invalidates_artifact(tmp_path, outlier_ptq):
    cfg, qcfg, _, qp, rep = outlier_ptq
    art = tmp_path / "a8stale"
    AP.save_ptq(art, qp, rep, cfg, qcfg)
    stale = dataclasses.replace(qcfg, a8_threshold=0.02)
    with pytest.raises(AP.StalePTQArtifactError):
        AP.load_ptq(art, cfg, stale)


def test_fingerprint_ignores_act_quant(outlier_ptq):
    """act_quant is a serving-time routing choice: one artifact must serve
    both A16 and A8-prefill engines without re-quantizing."""
    cfg, qcfg, *_ = outlier_ptq
    assert (AP.ptq_fingerprint(cfg, qcfg)
            == AP.ptq_fingerprint(cfg.with_(act_quant="a8_prefill"), qcfg))


# ------------------------------------------------------------- model level ---
def test_a8_prefill_logits_close_and_decode_untouched(outlier_ptq):
    cfg, qcfg, _, qp, rep = outlier_ptq
    a8cfg = cfg.with_(act_quant="a8_prefill")
    batch = C.synthetic_calibration_set(cfg, n_seqs=1, seq_len=32, seed=11)[0]
    l16 = np.asarray(api.forward_fn(qp, batch, cfg, backend="xla"), np.float32)
    l8 = np.asarray(api.forward_fn(qp, batch, a8cfg, backend="xla"), np.float32)
    rel = np.linalg.norm(l8 - l16) / np.linalg.norm(l16)
    n_elig = sum(v for k, v in rep.a8_eligibility.items()
                 if not k.endswith("wkv_b_absorbed"))
    assert 0 < rel <= qcfg.a8_threshold * n_elig * cfg.num_layers, rel
    # a 1-token forward sits under the token gate on every layer: A8 config
    # must produce bit-identical logits (the decode path is untouched)
    tiny = {"tokens": batch["tokens"][:, :1]}
    t16 = np.asarray(api.forward_fn(qp, tiny, cfg, backend="xla"))
    t8 = np.asarray(api.forward_fn(qp, tiny, a8cfg, backend="xla"))
    np.testing.assert_array_equal(t16, t8)


def test_engine_serves_a8_prefill_and_validates_act_quant(outlier_ptq):
    from repro.serving.engine import Request, ServingEngine

    cfg, qcfg, _, qp, _ = outlier_ptq
    with pytest.raises(ValueError, match="act_quant"):
        ServingEngine(qp, cfg.with_(act_quant="a8"), backend="xla")
    rng = np.random.default_rng(0)
    prompt = rng.integers(2, cfg.vocab_size, 40).astype(np.int32)

    def drain(c):
        eng = ServingEngine(qp, c, batch_size=2, max_seq=56, page_size=8,
                            backend="xla", max_prefill_tokens=16)
        r = Request(uid=0, prompt=prompt.copy(), max_tokens=4)
        eng.submit(r)
        eng.run_until_drained()
        assert r.finish_reason in ("completed", "length")
        return r.output

    out16 = drain(cfg)
    out8 = drain(cfg.with_(act_quant="a8_prefill"))
    assert len(out16) == len(out8)  # equal outputs at equal budgets
