"""Weights of a model described layer by layer - latent attention over an
indexer's selection, sigmoid routing with a shared expert, leading dense
layers (``configs/glm-5.2-serve.json``) - from ``--seed``, made on the
device a layer a jitted call (one compiled program a kind of layer; a whole
model in one call would hold every leaf's float32 draw beside 8 GB of
results).

As ``weights.py``: the benchmark makes the weights, not the program; the
timed path and the plain reference are both handed this tree. Its layout
is the one the program's model code reads (``embed``, ``lm_head``,
``out_norm``, ``layers[i]`` with ``mla``, ``indexer`` in a full layer,
``ln2`` and either a dense FFN's ``w1`` / ``w3`` / ``w2`` or ``moe`` with
the held experts' stacks and the shared expert's ``ws1`` / ``ws3`` /
``ws2``); a leaf's values depend only on the seed, the layer index and the
leaf's name. Normal with std fan_in ** -0.5, gains 1, the LayerNorm's and
the selection bias 0.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmark.weights import _normal, seed_key


def model_dims(model: dict) -> dict:
    """The sizes the weights need, from the configuration's keys. The
    router is as wide as the source's experts (``published``);
    ``n_routed_experts`` is what this chip holds."""
    return {
        "d": model["hidden_size"], "heads": model["num_attention_heads"],
        "q_rank": model["q_lora_rank"], "kv_rank": model["kv_lora_rank"],
        "nope": model["qk_nope_head_dim"], "rope": model["qk_rope_head_dim"],
        "v": model["v_head_dim"], "ff": model["intermediate_size"],
        "expert_ff": model["moe_intermediate_size"],
        "shared_ff": model["n_shared_experts"]
        * model["moe_intermediate_size"],
        "held": model["n_routed_experts"],
        "outputs": router_outputs(model),
        "index_heads": model["index_n_heads"],
        "index_dim": model["index_head_dim"],
        "vocab": model["vocab_size"],
    }


def router_outputs(model: dict) -> int:
    return model.get("published", {}).get("n_routed_experts",
                                          model["n_routed_experts"])


def _matrices(key, shapes: dict, dtype) -> dict:
    return {name: _normal(jax.random.fold_in(key, j), shape, shape[-2],
                          dtype)
            for j, (name, shape) in enumerate(sorted(shapes.items()))}


def layer_from_key(key, m: dict, full: bool, sparse: bool, dtype) -> dict:
    """One layer, traced (call it inside a jit)."""
    d, h = m["d"], m["heads"]
    k = [jax.random.fold_in(key, j) for j in range(4)]
    layer = {"mla": {
        "ln": jnp.ones((d,), dtype),
        "q_norm": jnp.ones((m["q_rank"],), dtype),
        "kv_norm": jnp.ones((m["kv_rank"],), dtype),
        **_matrices(k[0], {
            "wq_a": (d, m["q_rank"]),
            "wq_b": (m["q_rank"], h * (m["nope"] + m["rope"])),
            "wkv_a": (d, m["kv_rank"] + m["rope"]),
            "wkv_b": (m["kv_rank"], h * (m["nope"] + m["v"])),
            "wo": (h * m["v"], d)}, dtype)},
        "ln2": jnp.ones((d,), dtype)}
    if full:
        layer["indexer"] = {
            "k_norm": jnp.ones((m["index_dim"],), dtype),
            "k_bias": jnp.zeros((m["index_dim"],), dtype),
            **_matrices(k[1], {
                "wq_b": (m["q_rank"], m["index_heads"] * m["index_dim"]),
                "wk": (d, m["index_dim"]),
                "ww": (d, m["index_heads"])}, dtype)}
    if not sparse:
        layer.update(_matrices(k[2], {"w1": (d, m["ff"]), "w3": (d, m["ff"]),
                                      "w2": (m["ff"], d)}, dtype))
        return layer
    e, f, s = m["held"], m["expert_ff"], m["shared_ff"]
    layer["moe"] = {
        "bias": jnp.zeros((m["outputs"],), jnp.float32),
        **_matrices(k[3], {
            "router": (d, m["outputs"]),
            "we1": (e, d, f), "we3": (e, d, f), "we2": (e, f, d),
            "ws1": (d, s), "ws3": (d, s), "ws2": (s, d)}, dtype)}
    return layer


@functools.partial(jax.jit, static_argnames=("dims_t", "full", "sparse",
                                             "dtype"))
def _make_layer(key, dims_t, full, sparse, dtype):
    return layer_from_key(key, dict(dims_t), full, sparse, dtype)


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
        _make_layer(jax.random.fold_in(key, 16 + i), dims_t,
                    indexer == "full", ffn == "sparse", dtype)
        for i, (indexer, ffn) in enumerate(zip(model["indexer_types"],
                                               model["mlp_layer_types"]))]
    return params
