"""Weights of a shortcut-connected double-layer model with latent
attention (``configs/longcat-flash-chat-serve.json``), from ``--seed``, made
on the device a double layer a jitted call (one compiled program for all
of them; a whole model in one call would hold every leaf's float32 draw
beside 10 GB of results).

As ``weights.py``: the benchmark makes the weights, not the program; the
timed path and the plain reference are both handed this tree. Its layout
is the one the program's model code reads (``embed``, ``lm_head``,
``out_norm``, ``layers[i]`` with ``mla`` and ``ffn``, two of each, and
``moe``); a leaf's values depend only on the seed, the layer index and the
leaf's name. Normal with std fan_in ** -0.5, gains 1, the selection bias 0.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmark.weights import _normal, seed_key


def model_dims(model: dict) -> dict:
    """The sizes the weights need, from the configuration's keys. The
    router is as wide as the source's experts (``published``) and its
    identity experts; ``n_routed_experts`` is what this chip holds."""
    real = model.get("published", {}).get("n_routed_experts",
                                          model["n_routed_experts"])
    return {
        "d": model["hidden_size"], "heads": model["num_attention_heads"],
        "q_rank": model["q_lora_rank"], "kv_rank": model["kv_lora_rank"],
        "nope": model["qk_nope_head_dim"], "rope": model["qk_rope_head_dim"],
        "v": model["v_head_dim"], "ff": model["ffn_hidden_size"],
        "expert_ff": model["expert_ffn_hidden_size"],
        "held": model["n_routed_experts"],
        "outputs": real + model["zero_expert_num"],
        "vocab": model["vocab_size"], "layers": model["num_layers"],
    }


def _mla(key, m: dict, dtype) -> dict:
    d, h = m["d"], m["heads"]
    shapes = {"wq_a": (d, m["q_rank"]),
              "wq_b": (m["q_rank"], h * (m["nope"] + m["rope"])),
              "wkv_a": (d, m["kv_rank"] + m["rope"]),
              "wkv_b": (m["kv_rank"], h * (m["nope"] + m["v"])),
              "wo": (h * m["v"], d)}
    out = {"ln": jnp.ones((d,), dtype),
           "q_norm": jnp.ones((m["q_rank"],), dtype),
           "kv_norm": jnp.ones((m["kv_rank"],), dtype)}
    for j, (name, shape) in enumerate(sorted(shapes.items())):
        # the two up-projections out of a low rank take the hidden size's
        # std: the init under which the lora scales (hidden / rank) ** 0.5
        # keep q and k at unit variance, which is what they are for
        fan_in = d if name in ("wq_b", "wkv_b") else shape[0]
        out[name] = _normal(jax.random.fold_in(key, j), shape, fan_in,
                            dtype)
    return out


def _ffn(key, d: int, ff: int, dtype, stack=None) -> dict:
    lead = () if stack is None else (stack,)
    names = ("w1", "w3", "w2") if stack is None else ("we1", "we3", "we2")
    shapes = ((d, ff), (d, ff), (ff, d))
    return {n: _normal(jax.random.fold_in(key, j), lead + s, s[0], dtype)
            for j, (n, s) in enumerate(zip(names, shapes))}


def layer_from_key(key, m: dict, dtype) -> dict:
    """One double layer, traced (call it inside a jit)."""
    d = m["d"]
    k = [jax.random.fold_in(key, j) for j in range(6)]
    return {
        "mla": [_mla(k[0], m, dtype), _mla(k[1], m, dtype)],
        "ffn": [{"ln": jnp.ones((d,), dtype), **_ffn(k[2 + j], d, m["ff"],
                                                     dtype)}
                for j in range(2)],
        "moe": {"router": _normal(k[4], (d, m["outputs"]), d, dtype),
                "bias": jnp.zeros((m["outputs"],), jnp.float32),
                **_ffn(k[5], d, m["expert_ff"], dtype, stack=m["held"])},
    }


@functools.partial(jax.jit, static_argnames=("dims_t", "dtype"))
def _make_layer(key, dims_t, dtype):
    return layer_from_key(key, dict(dims_t), dtype)


@functools.partial(jax.jit, static_argnames=("dims_t", "dtype"))
def _make_ends(key, dims_t, dtype):
    m = dict(dims_t)
    d, v = m["d"], m["vocab"]
    return {"embed": _normal(jax.random.fold_in(key, 1), (v, d), d, dtype),
            "lm_head": _normal(jax.random.fold_in(key, 2), (d, v), d, dtype),
            "out_norm": jnp.ones((d,), dtype)}


def make_params(seed: int, model: dict, dtype) -> dict:
    """The whole tree on the device: what the timed path is given, and
    what the reference starts from."""
    key = seed_key(seed)
    dims_t = tuple(sorted(model_dims(model).items()))
    params = _make_ends(key, dims_t, dtype)
    params["layers"] = [
        _make_layer(jax.random.fold_in(key, 16 + i), dims_t, dtype)
        for i in range(dict(dims_t)["layers"])]
    return params
