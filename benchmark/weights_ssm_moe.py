"""Weights of the hybrid described layer by layer - state-space mixers
beside attention without positions, every layer followed by the expert
share with its shared expert (``configs/granite-4.0-h-small-serve.json``)
- from ``--seed``, made on the device a layer a jitted call (one compiled
program a kind of layer; a whole model in one call would hold every
leaf's float32 draw beside 9.5 GB of results).

As ``weights.py``: the benchmark makes the weights, not the program; the
timed path and the plain reference are both handed this tree. Its layout
is the one the program's model code reads (``embed`` - the head is tied -
``out_norm``, ``layers[i]`` with ``ln1``, either ``ssm`` or ``wq`` / ``wk``
/ ``wv`` / ``wo``, ``ln2`` and ``moe`` with the held experts' stacks and
the shared expert's ``ws1`` / ``ws3`` / ``ws2``); a leaf's values depend
only on the seed, the layer index and the leaf's name.

Matrices are normal with std fan_in ** -0.5 and gains 1, but (the
configuration's ``assumed`` block says why): the recurrence's constants
take the Mamba-2 initialisation - ``A`` uniform in 1-16,
``softplus(dt_bias)`` log-uniform in 0.001-0.1, ``D`` ones - so that a
head remembers tens to thousands of tokens; the convolution's taps std
width ** -0.5, its bias 0; and the attention's ``wq`` and ``wk`` are drawn
:data:`QK_GAIN` times wider, so that scores of ``q . k / 128`` have a
standard deviation near 1.4 and the softmax over thousands of keys is
neither a flat mean (at fan-in std it would be: 0.09, and no fault of
position could show) nor an argmax.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmark.weights import _normal, seed_key

QK_GAIN = 4.0


def router_outputs(model: dict) -> int:
    return model.get("published", {}).get("num_local_experts",
                                          model["num_local_experts"])


def model_dims(model: dict) -> dict:
    """The sizes the weights need, from the configuration's keys. The
    router is as wide as the source's experts (``published``);
    ``experts_held`` says how many of them this chip holds."""
    heads, hd = model["mamba_n_heads"], model["mamba_d_head"]
    held = model.get("experts_held", (0, model["num_local_experts"]))[1]
    return {
        "d": model["hidden_size"], "heads": model["num_attention_heads"],
        "kv_heads": model["num_key_value_heads"],
        "head_dim": model["hidden_size"] // model["num_attention_heads"],
        "inner": heads * hd, "ssm_heads": heads,
        "state": model["mamba_d_state"], "conv": model["mamba_d_conv"],
        "expert_ff": model["intermediate_size"],
        "shared_ff": model["shared_intermediate_size"],
        "held": held, "outputs": router_outputs(model),
        "vocab": model["vocab_size"],
    }


def _matrices(key, shapes: dict, dtype, gain: float = 1.0) -> dict:
    return {name: (_normal(jax.random.fold_in(key, j), shape, shape[-2],
                           jnp.float32) * gain).astype(dtype)
            for j, (name, shape) in enumerate(sorted(shapes.items()))}


def ssm_from_key(key, m: dict, dtype) -> dict:
    """One state-space mixer, traced."""
    d, inner, n, heads = m["d"], m["inner"], m["state"], m["ssm_heads"]
    conv_dim = inner + 2 * n
    k = [jax.random.fold_in(key, j) for j in range(4)]
    step = jnp.exp(jax.random.uniform(
        k[2], (heads,), jnp.float32, math.log(1e-3), math.log(1e-1)))
    return {
        **_matrices(k[0], {"w_in": (d, 2 * inner + 2 * n + heads),
                           "w_out": (inner, d)}, dtype),
        "conv_w": (jax.random.normal(k[1], (conv_dim, m["conv"]),
                                     jnp.float32)
                   * m["conv"] ** -0.5).astype(dtype),
        "conv_b": jnp.zeros((conv_dim,), dtype),
        # the inverse of softplus, so that softplus(dt_bias) = step
        "dt_bias": step + jnp.log(-jnp.expm1(-step)),
        "a_log": jnp.log(jax.random.uniform(k[3], (heads,), jnp.float32,
                                            1.0, 16.0)),
        "d": jnp.ones((heads,), jnp.float32),
        "norm": jnp.ones((inner,), dtype),
    }


def layer_from_key(key, m: dict, kind: str, dtype) -> dict:
    """One layer, traced (call it inside a jit)."""
    d = m["d"]
    k = [jax.random.fold_in(key, j) for j in range(4)]
    layer = {"ln1": jnp.ones((d,), dtype), "ln2": jnp.ones((d,), dtype)}
    if kind == "mamba":
        layer["ssm"] = ssm_from_key(k[0], m, dtype)
    else:
        d_q, d_kv = m["heads"] * m["head_dim"], m["kv_heads"] * m["head_dim"]
        layer.update(_matrices(k[1], {"wq": (d, d_q), "wk": (d, d_kv)},
                               dtype, QK_GAIN))
        layer.update(_matrices(k[2], {"wv": (d, d_kv), "wo": (d_q, d)},
                               dtype))
    e, f, s = m["held"], m["expert_ff"], m["shared_ff"]
    layer["moe"] = {
        "bias": jnp.zeros((m["outputs"],), jnp.float32),
        **_matrices(k[3], {
            "router": (d, m["outputs"]),
            "we1": (e, d, f), "we3": (e, d, f), "we2": (e, f, d),
            "ws1": (d, s), "ws3": (d, s), "ws2": (s, d)}, dtype)}
    return layer


@functools.partial(jax.jit, static_argnames=("dims_t", "kind", "dtype"))
def _make_layer(key, dims_t, kind, dtype):
    return layer_from_key(key, dict(dims_t), kind, dtype)


@functools.partial(jax.jit, static_argnames=("dims_t", "dtype"))
def _make_ends(key, dims_t, dtype):
    m = dict(dims_t)
    d, v = m["d"], m["vocab"]
    return {"embed": _normal(jax.random.fold_in(key, 1), (v, d), d, dtype),
            "out_norm": jnp.ones((d,), dtype)}


def make_params(seed: int, model: dict, dtype) -> dict:
    """The whole tree on the device: what the timed path is given, and
    what the reference starts from."""
    key = seed_key(seed)
    dims_t = tuple(sorted(model_dims(model).items()))
    params = _make_ends(key, dims_t, dtype)
    params["layers"] = [
        _make_layer(jax.random.fold_in(key, 16 + i), dims_t, kind, dtype)
        for i, kind in enumerate(model["layer_types"])]
    return params
