"""Paged KV pool writes: ``scatter_rows`` (one flat row scatter per leaf)
leaves every page but the trash page bit-identical to the plain
``leaf.at[page, :, off].set(rows)`` write, for every leaf kind the serving
paths write: bf16 GQA rows, MLA latents (one head), int8 codes with their
f32 scale rows, decode ``[B]`` and chunk ``[B, T]`` indices, and the
layer-vmapped ``write_prefix``."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.attention import kv_quantize_rows, scatter_rows
from repro.serving import kv_cache as KV

NP, PS, B, T, P = 41, 8, 5, 21, 4


def _reference(leaf, page, off, rows):
    return leaf.at[page, :, off].set(rows.astype(leaf.dtype))


def _bits(a):
    a = np.asarray(a)
    return a.view(np.dtype(f"u{a.dtype.itemsize}"))


def _assert_live_pages_equal(got, want):
    assert got.shape == want.shape and got.dtype == want.dtype
    np.testing.assert_array_equal(_bits(got)[KV.TRASH_PAGE + 1:],
                                  _bits(want)[KV.TRASH_PAGE + 1:])


def _tables(rng):
    """Distinct slots own distinct live pages (page 0 is the trash page)."""
    pages = rng.permutation(np.arange(1, NP))[:B * P]
    return pages.reshape(B, P).astype(np.int32)


def _decode_index(rng):
    """One token per slot; the last two slots are idle, so their writes
    collide on the trash page as the engine's idle slots do."""
    table = _tables(rng)
    pos = rng.integers(0, P * PS, size=B)
    page = table[np.arange(B), pos // PS]
    page[-2:] = KV.TRASH_PAGE
    return page.astype(np.int32), (pos % PS).astype(np.int32)


def _chunk_index(rng):
    """A ``[B, T]`` chunk per slot at its own start; rows past each slot's
    length are padding, routed to the trash page."""
    table = _tables(rng)
    lens = rng.integers(0, T + 1, size=B)
    starts = rng.integers(0, P * PS - T + 1, size=B)
    return KV.prefix_write_plan(lens, table, PS, T, starts)


def _rows(rng, shape, dtype):
    return jnp.asarray(rng.standard_normal(shape), dtype)


@pytest.mark.parametrize("index", [_decode_index, _chunk_index],
                         ids=["decode", "chunk"])
@pytest.mark.parametrize("heads,dim", [(4, 128), (1, 64)], ids=["gqa", "mla"])
def test_scatter_rows_matches_reference_bf16(index, heads, dim):
    rng = np.random.default_rng(0)
    page, off = map(jnp.asarray, index(rng))
    leaf = _rows(rng, (NP, heads, PS, dim), jnp.bfloat16)
    rows = _rows(rng, (*page.shape, heads, dim), jnp.float32)
    got = jax.jit(scatter_rows)(leaf, page, off, rows)
    _assert_live_pages_equal(got, _reference(leaf, page, off, rows))


@pytest.mark.parametrize("index", [_decode_index, _chunk_index],
                         ids=["decode", "chunk"])
def test_scatter_rows_matches_reference_int8_pool(index):
    rng = np.random.default_rng(1)
    page, off = map(jnp.asarray, index(rng))
    codes_leaf = jnp.asarray(rng.integers(-127, 128, (NP, 2, PS, 32)),
                             jnp.int8)
    scale_leaf = _rows(rng, (NP, 2, PS), jnp.float32)
    codes, scales = kv_quantize_rows(_rows(rng, (*page.shape, 2, 32),
                                           jnp.bfloat16))
    write = jax.jit(scatter_rows)
    _assert_live_pages_equal(write(codes_leaf, page, off, codes),
                             _reference(codes_leaf, page, off, codes))
    _assert_live_pages_equal(write(scale_leaf, page, off, scales),
                             _reference(scale_leaf, page, off, scales))


def test_write_prefix_matches_reference():
    rng = np.random.default_rng(2)
    layers = 3
    page, off = map(jnp.asarray, _chunk_index(rng))
    pools = {"k": _rows(rng, (layers, NP, 4, PS, 16), jnp.bfloat16),
             "k_s": _rows(rng, (layers, NP, 4, PS), jnp.float32)}
    kv = {"k": _rows(rng, (layers, *page.shape, 4, 16), jnp.bfloat16),
          "k_s": _rows(rng, (layers, *page.shape, 4), jnp.float32)}
    want = jax.tree.map(
        lambda pool, new: jax.vmap(
            lambda p, n: _reference(p, page, off, n))(pool, new), pools, kv)
    got = KV.write_prefix(dict(pools), kv, page, off)
    for name in pools:
        for layer in range(layers):
            _assert_live_pages_equal(got[name][layer], want[name][layer])
