"""Seeded weights for a configuration, made on the device in one jitted call.

The values are random, but every quantized linear weight lies on a 4-bit
grid that group-wise min/max quantization reproduces exactly: per group of
``group_size`` input rows and per output column the codes run 0..15, both
ends present, around a zero point of 8 (7 where the block's sign is
flipped), times a step that is a power of two
(the same step for every linear that shares an input).  SmoothQuant+'s
smoothing then finds its best strength at alpha 0, where every smoothing
factor of a group is the same power of two, so smoothing and int4
quantization are both exact and the served W4A16 model computes the very
model the plain reference computes.  A fault anywhere on the served path —
kernel, page table, scheduler, sampler, or the quantizer itself — then shows
as a gap between the two, not hidden in quantization noise.

Embedding, lm_head, norms and biases are bf16 draws (norms ones and zeros,
as the program initialises them).

The tree has the program's parameter layout (``models/lm.py``): the harness
checks it against ``jax.eval_shape(api.init_model)`` before use.  The
reference regenerates any layer alone from the same key, so it never takes
weights from the program.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class Dims:
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    layers: int
    group: int
    bias: bool
    norm: str             # "layernorm" | "rmsnorm"
    mlp: str              # "gelu_tanh" | "swiglu"
    eps: float
    rope_theta: float


_ARCH = {   # model_type -> (norm, mlp, biases) as the published model defines it
    "starcoder2": ("layernorm", "gelu_tanh", None),
    "mistral": ("rmsnorm", "swiglu", False),
}


def dims(config: Dict[str, Any]) -> Dims:
    norm, mlp, bias = _ARCH[config["model_type"]]
    if bias is None:
        bias = bool(config["use_bias"])
    eps = config.get("norm_epsilon", config.get("rms_norm_eps"))
    return Dims(
        d_model=config["hidden_size"], heads=config["num_attention_heads"],
        kv_heads=config["num_key_value_heads"], head_dim=config["head_dim"],
        d_ff=config["intermediate_size"], vocab=config["vocab_size"],
        layers=config["num_hidden_layers"],
        group=config["quantization"]["group_size"], bias=bias, norm=norm,
        mlp=mlp, eps=float(eps), rope_theta=float(config["rope_theta"]))


def linear_shapes(d: Dims):
    """``(name, Ci, Co)`` of each quantized linear of one layer, in the
    program's layout: the int4 GEMMs every layer runs once per launch."""
    qd, kvd = d.heads * d.head_dim, d.kv_heads * d.head_dim
    mlp = ([("gate", d.d_model, d.d_ff)] if d.mlp == "swiglu" else []) + [
        ("up", d.d_model, d.d_ff), ("down", d.d_ff, d.d_model)]
    return [("wq", d.d_model, qd), ("wk", d.d_model, kvd),
            ("wv", d.d_model, kvd), ("wo", qd, d.d_model)] + mlp


def seed_key(seed: int) -> jax.Array:
    """A threefry key from any whole-number seed (wider than 32 bits too)."""
    words = np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32))


def grid_step(fan_in: int) -> float:
    """Power-of-two step whose codes (std 4.61 about 8) give the weight
    about ``fan_in ** -0.5`` standard deviation."""
    return 2.0 ** round(math.log2(fan_in ** -0.5 / 4.61))


def grid_weight(key, ci: int, co: int, group: int) -> jax.Array:
    """Codes 0..15 about a zero point of 8 have mean -0.5: every block of
    ``group`` rows and one column takes a random sign, so that the weight
    has no common offset that would add the same value to every output (a
    rank-one direction that grows through the layer and leaves bf16 and
    int8 activations alike with few bits for the rest)."""
    kq, k0, k1, ks = jax.random.split(key, 4)
    g = ci // group
    q = jax.random.bits(kq, (g, group, co), jnp.uint8) & 15
    lo = jax.random.randint(k0, (g, 1, co), 0, group)
    hi = (lo + jax.random.randint(k1, (g, 1, co), 1, group)) % group
    row = jnp.arange(group)[None, :, None]
    q = jnp.where(row == lo, 0, jnp.where(row == hi, 15, q))
    sign = jnp.where(jax.random.bernoulli(ks, 0.5, (g, 1, co)), 1.0, -1.0)
    w = (q.astype(jnp.float32) - 8.0) * sign * grid_step(ci)
    return w.astype(jnp.bfloat16).reshape(ci, co)


def _bias(key, n: int, on: bool):
    if not on:
        return {}
    return {"b": (jax.random.normal(key, (n,)) * 0.02).astype(jnp.bfloat16)}


def norm_params(d: Dims):
    p = {"scale": jnp.ones((d.d_model,), jnp.bfloat16)}
    if d.norm == "layernorm":
        p["bias"] = jnp.zeros((d.d_model,), jnp.bfloat16)
    return p


def layer_params(d: Dims, key) -> Dict[str, Any]:
    """One decoder layer in the program's layout."""
    ks = jax.random.split(key, 14)
    qd, kvd = d.heads * d.head_dim, d.kv_heads * d.head_dim
    lin = lambda i, ci, co: {"w": grid_weight(ks[i], ci, co, d.group),
                             **_bias(ks[i + 7], co, d.bias)}
    mixer = {"wq": lin(0, d.d_model, qd), "wk": lin(1, d.d_model, kvd),
             "wv": lin(2, d.d_model, kvd), "wo": lin(3, qd, d.d_model)}
    if d.mlp == "swiglu":
        mlp = {"gate": lin(4, d.d_model, d.d_ff), "up": lin(5, d.d_model, d.d_ff),
               "down": lin(6, d.d_ff, d.d_model)}
    else:
        mlp = {"up": lin(5, d.d_model, d.d_ff), "down": lin(6, d.d_ff, d.d_model)}
    return {"norm1": norm_params(d), "mixer": mixer, "norm2": norm_params(d), "mlp": mlp}


def embed_table(d: Dims, key) -> jax.Array:
    return (jax.random.normal(key, (d.vocab, d.d_model)) * 0.02).astype(
        jnp.bfloat16)


def lm_head(d: Dims, key) -> jax.Array:
    return (jax.random.normal(key, (d.d_model, d.vocab))
            * d.d_model ** -0.5).astype(jnp.bfloat16)


def keys(seed: int):
    """``(embed, lm_head, layers)`` keys; layer ``i`` uses
    ``fold_in(layers, i)``."""
    k = jax.random.split(seed_key(seed), 3)
    return k[0], k[1], k[2]


@functools.partial(jax.jit, static_argnums=0)
def _make(d: Dims, k_embed, k_head, k_layers):
    layers = jax.lax.map(
        lambda i: layer_params(d, jax.random.fold_in(k_layers, i)),
        jnp.arange(d.layers))
    return {"embed": {"table": embed_table(d, k_embed)},
            "final_norm": norm_params(d),
            "lm_head": {"w": lm_head(d, k_head)},
            "layers": layers}


def make_params(d: Dims, seed: int):
    """The whole bf16 tree, on the device."""
    return _make(d, *keys(seed))


layer_params_jit = jax.jit(layer_params, static_argnums=0)
embed_table_jit = jax.jit(embed_table, static_argnums=0)
lm_head_jit = jax.jit(lm_head, static_argnums=0)
