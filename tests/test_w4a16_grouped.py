"""Expert-batched grouped W4A16 kernel vs the dequant-einsum oracle, the
model-level MoE / MLA-absorbed integration, and the tiny-t decode fast path
of the 2-D kernel (no recompile across steady-state decode steps)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import quantize as q
from repro.kernels import ops
from repro.kernels.ref import w4a16_grouped_ref
from repro.kernels.w4a16_grouped import (grouped_weight_bytes,
                                         w4a16_grouped_matmul)


def _mk(e, c, d, f, g, seed=0, dtype=jnp.float32):
    kx, kw = jax.random.split(jax.random.PRNGKey(seed))
    x = jax.random.normal(kx, (e, c, d), jnp.float32).astype(dtype)
    w = jax.random.normal(kw, (e, d, f), jnp.float32)
    return x, q.quantize(w, group_size=g)


# ------------------------------------------------------------ kernel level --
@pytest.mark.parametrize(
    "e,c,d,f,g",
    [
        (1, 8, 128, 128, 128),      # single expert == 2-D contract
        (8, 16, 128, 128, 128),     # full expert sweep
        (8, 24, 256, 128, 64),      # multi-group contraction, g=64
        (4, 8, 128, 256, 128),      # wide Co
        (2, 100, 128, 128, 128),    # c not a multiple of the block
    ],
)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_grouped_kernel_matches_oracle(e, c, d, f, g, dtype):
    x, qt = _mk(e, c, d, f, g, dtype=dtype)
    got = w4a16_grouped_matmul(x, qt, block_c=64, block_co=128, interpret=True)
    want = w4a16_grouped_ref(x, qt)
    assert got.shape == want.shape and got.dtype == want.dtype
    np.testing.assert_allclose(
        np.asarray(got, np.float32),
        np.asarray(want, np.float32),
        rtol=2e-2 if dtype == jnp.bfloat16 else 1e-5,
        atol=2e-1 if dtype == jnp.bfloat16 else 1e-4,
    )


def test_packed_expert_weights_hold_under_a_third_of_bf16():
    """Packed int4 codes plus per-group scales and zeros of a stacked expert
    weight hold less than 0.32 of its bf16 bytes, and
    ``grouped_weight_bytes`` counts exactly what is stored."""
    e, d, f, g = 4, 256, 256, 128
    _, qt = _mk(e, 8, d, f, g)
    stored = sum(a.nbytes for a in jax.tree.leaves(qt))
    packed, bf16 = grouped_weight_bytes(
        e, d, f, g, scale_bytes=qt.scales.dtype.itemsize)
    assert stored == packed
    assert stored / bf16 < 0.32


def test_grouped_kernel_ragged_capacity_rows_are_zero():
    """Zero-padded capacity slots (ragged MoE dispatch) must produce exactly
    zero output rows — the combine gather relies on it."""
    e, c, d, f, g = 4, 16, 128, 128, 64
    x, qt = _mk(e, c, d, f, g, seed=3)
    filled = jnp.asarray([16, 5, 0, 9])          # per-expert live rows
    mask = jnp.arange(c)[None, :] < filled[:, None]
    x = jnp.where(mask[..., None], x, 0.0)
    got = np.asarray(
        w4a16_grouped_matmul(x, qt, block_c=16, block_co=128, interpret=True))
    want = np.asarray(w4a16_grouped_ref(x, qt))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-4)
    for ei in range(e):
        assert (got[ei, int(filled[ei]):] == 0).all()


def test_grouped_dispatch_xla_equals_interpret():
    x, qt = _mk(2, 12, 128, 128, 128, seed=5)
    a = ops.w4a16_grouped_matmul(x, qt, backend="xla")
    b = ops.w4a16_grouped_matmul(x, qt, backend="interpret",
                                 block_c=16, block_co=128)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-5, atol=1e-4)


def test_grouped_kernel_rejects_2d_weight():
    kx, kw = jax.random.split(jax.random.PRNGKey(0))
    x = jax.random.normal(kx, (2, 8, 128), jnp.float32)
    qt = q.quantize(jax.random.normal(kw, (128, 128), jnp.float32),
                    group_size=128)
    with pytest.raises(ValueError):
        w4a16_grouped_matmul(x, qt, interpret=True)


def test_stacked_quantize_equals_per_expert_quantize():
    """Stacked [E, Ci, Co] quantization must be bitwise the stack of
    independent 2-D quantizations (first-class leading dims)."""
    w = jax.random.normal(jax.random.PRNGKey(7), (3, 128, 64), jnp.float32)
    qt = q.quantize(w, group_size=64)
    assert qt.shape == (3, 128, 64) and qt.ndim == 3 and qt.group_size == 64
    for ei in range(3):
        one = q.quantize(w[ei], group_size=64)
        np.testing.assert_array_equal(np.asarray(qt[ei].packed),
                                      np.asarray(one.packed))
        np.testing.assert_array_equal(np.asarray(qt[ei].scales),
                                      np.asarray(one.scales))
        np.testing.assert_array_equal(np.asarray(qt[ei].zeros),
                                      np.asarray(one.zeros))
        np.testing.assert_allclose(
            np.asarray(q.dequantize(qt, jnp.float32)[ei]),
            np.asarray(q.dequantize(one, jnp.float32)), atol=0)


# -------------------------------------------------------------- model level -
def test_apply_moe_quantized_interpret_matches_xla():
    """MoE expert compute with int4 stacked weights: the grouped Pallas
    kernel (interpret) must agree with the dequant-einsum XLA path."""
    from repro.configs import get_config
    from repro.models import api, mlp as M

    cfg = get_config("granite-moe-1b-a400m", smoke=True).with_(dtype="float32")
    key = jax.random.PRNGKey(0)
    p = M.init_moe(key, cfg)
    ew = p["experts"]
    p["experts"] = {k: q.quantize(v, group_size=16) for k, v in ew.items()}
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, cfg.d_model),
                          jnp.float32)
    y_x, _ = M.apply_moe(p, x, cfg, backend="xla")
    y_i, _ = M.apply_moe(p, x, cfg, backend="interpret")
    np.testing.assert_allclose(np.asarray(y_i), np.asarray(y_x),
                               rtol=2e-4, atol=2e-4)


def test_mla_absorbed_decode_never_dequantizes():
    """Quantized MLA decode must use the stacked absorbed int4 weights; a
    quantized ``wkv_b`` without them is a wiring bug and raises."""
    from repro.configs import get_config
    from repro.configs.base import QuantConfig
    from repro.core import calibration as C
    from repro.core.apply import smoothquant_plus
    from repro.models import api, attention as A

    cfg = get_config("deepseek-v2-236b", smoke=True).with_(dtype="float32")
    params = api.init_model(jax.random.PRNGKey(0), cfg)
    batches = C.synthetic_calibration_set(cfg, n_seqs=1, seq_len=12)
    qp, _ = smoothquant_plus(params, cfg, batches, QuantConfig(group_size=16),
                             step=0.5)
    mixer0 = jax.tree.map(lambda l: l[0], qp["layers"]["mixer"])
    assert "wkv_b_absorbed" in mixer0
    assert isinstance(mixer0["wkv_b_absorbed"]["wk_t"], q.QuantizedTensor)
    # decode works through the grouped op on both backends
    prompt = jnp.arange(3, 9)[None]
    _, cache = api.prefill_fn(qp, {"tokens": prompt}, cfg, 16, backend="xla")
    batch = {"token": jnp.asarray([[5]], jnp.int32),
             "position": jnp.asarray([6], jnp.int32)}
    dx, _ = api.decode_fn(qp, batch, cache, cfg, backend="xla")
    di, _ = api.decode_fn(qp, batch, cache, cfg, backend="interpret")
    np.testing.assert_allclose(np.asarray(di), np.asarray(dx),
                               rtol=2e-4, atol=2e-4)
    # the guard: quantized wkv_b with the absorbed weights stripped raises
    broken = dict(mixer0)
    del broken["wkv_b_absorbed"]
    with pytest.raises(TypeError):
        A._mla_absorb_weights(broken, cfg)


# ------------------------------------------------- tiny-t decode fast path --
def test_decode_tiny_t_no_recompile():
    """Steady-state decode (fixed [B, Ci] shape) must reuse one compiled
    trace; a second decode bucket adds exactly one more."""
    from repro.kernels.w4a16_matmul import w4a16_matmul

    kx, kw = jax.random.split(jax.random.PRNGKey(0))
    qt = q.quantize(jax.random.normal(kw, (128, 128), jnp.float32),
                    group_size=128)
    x8 = jax.random.normal(kx, (8, 128), jnp.float32)
    x16 = jax.random.normal(kx, (16, 128), jnp.float32)
    base = w4a16_matmul._cache_size()
    for _ in range(3):
        w4a16_matmul(x8, qt, interpret=True).block_until_ready()
    assert w4a16_matmul._cache_size() == base + 1, "decode step recompiled"
    for _ in range(2):
        w4a16_matmul(x16, qt, interpret=True).block_until_ready()
    assert w4a16_matmul._cache_size() == base + 2
    for _ in range(2):  # back to the first bucket: still cached
        w4a16_matmul(x8, qt, interpret=True).block_until_ready()
    assert w4a16_matmul._cache_size() == base + 2


def test_decode_tiny_t_matches_ref():
    """The pinned-bt fast path is numerically the same kernel."""
    from repro.kernels.ref import w4a16_matmul_ref
    from repro.kernels.w4a16_matmul import w4a16_matmul

    kx, kw = jax.random.split(jax.random.PRNGKey(1))
    qt = q.quantize(jax.random.normal(kw, (128, 128), jnp.float32),
                    group_size=64)
    for t in (1, 8, 13, 64):
        x = jax.random.normal(kx, (t, 128), jnp.float32)
        np.testing.assert_allclose(
            np.asarray(w4a16_matmul(x, qt, interpret=True)),
            np.asarray(w4a16_matmul_ref(x, qt)), rtol=1e-5, atol=1e-4)
