"""Weights from ``--seed``, made on the device in one jitted call.

The benchmark makes the weights, not the program: the timed path and the
plain reference are both handed what this file makes, so neither takes
anything the other built. The tree has the layout the program's model code
reads (``embed``, ``lm_head``, ``out_norm``, ``layers[i]`` with
``ln1 wq wk wv wo ln2 w1 w3 w2``); a leaf's values depend only on the
seed, the layer index and the leaf's name.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

_LAYER_LEAVES = ("wq", "wk", "wv", "wo", "w1", "w3", "w2")


def model_dims(model: dict) -> dict:
    """The sizes the weights need, from a configuration's published keys."""
    d = model["hidden_size"]
    heads = model["num_attention_heads"]
    head_dim = model.get("head_dim", d // heads)
    return {
        "d": d, "heads": heads, "kv_heads": model["num_key_value_heads"],
        "head_dim": head_dim, "ff": model["intermediate_size"],
        "vocab": model["vocab_size"], "layers": model["num_hidden_layers"],
    }


def seed_key(seed: int) -> jax.Array:
    # --seed may exceed 2**31; fold it in as two 31-bit halves
    key = jax.random.key(int(seed) & 0x7FFFFFFF)
    return jax.random.fold_in(key, (int(seed) >> 31) & 0x7FFFFFFF)


def _normal(key, shape, fan_in, dtype):
    return (jax.random.normal(key, shape, jnp.float32)
            * (fan_in ** -0.5)).astype(dtype)


def _layer(key, dims: dict, dtype) -> dict:
    d, ff = dims["d"], dims["ff"]
    d_q = dims["heads"] * dims["head_dim"]
    d_kv = dims["kv_heads"] * dims["head_dim"]
    shapes = {"wq": (d, d_q), "wk": (d, d_kv), "wv": (d, d_kv),
              "wo": (d_q, d), "w1": (d, ff), "w3": (d, ff), "w2": (ff, d)}
    out = {"ln1": jnp.ones((d,), dtype), "ln2": jnp.ones((d,), dtype)}
    for j, name in enumerate(_LAYER_LEAVES):
        out[name] = _normal(jax.random.fold_in(key, j), shapes[name],
                            shapes[name][0], dtype)
    return out


def params_from_key(key, dims: dict, dtype) -> dict:
    """The whole tree, traced (call it inside a jit)."""
    d, v = dims["d"], dims["vocab"]
    return {
        "embed": _normal(jax.random.fold_in(key, 1), (v, d), d, dtype),
        "lm_head": _normal(jax.random.fold_in(key, 2), (d, v), d, dtype),
        "out_norm": jnp.ones((d,), dtype),
        "layers": [_layer(jax.random.fold_in(key, 16 + i), dims, dtype)
                   for i in range(dims["layers"])],
    }


@functools.partial(jax.jit, static_argnames=("dims_t", "dtype"))
def _make_all(key, dims_t, dtype):
    return params_from_key(key, dict(dims_t), dtype)


def make_params(seed: int, model: dict, dtype) -> dict:
    """The whole tree in one jitted call, on the device: what the timed
    path is given, and what the reference starts from."""
    return _make_all(seed_key(seed), tuple(sorted(model_dims(model).items())),
                     dtype)
