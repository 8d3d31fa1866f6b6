"""Weights of latent attention over every position with a sigmoid expert
share and a multi-token-prediction module (``configs/glm-4.7-flash-train.
json``) from ``--seed``, made on the device a layer a jitted call.

As ``weights.py``: the benchmark makes the weights, not the program; the
timed path and the plain reference are both handed this tree. Its layout is
the one the program's model code reads (``embed``, ``lm_head``,
``out_norm``, ``layers[i]`` with ``mla``, ``ln2`` and either a dense FFN's
``w1`` / ``w3`` / ``w2`` or ``moe``, and ``mtp`` with ``enorm``,
``hnorm``, ``eh_proj``, ``layer`` (a sparse layer) and ``norm``); a leaf's
values depend only on the seed, the layer's index and the leaf's name.
Normal with std fan_in ** -0.5, gains 1, the selection bias 0. A layer's
leaves are ``weights_dsa_moe.layer_from_key``'s (no indexer).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmark.weights import _normal, seed_key
from benchmark.weights_dsa_moe import layer_from_key, router_outputs

MTP_LAYER = 64     # the key a layer folds in past any main layer's


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
        "vocab": model["vocab_size"],
    }


def sparse_layers(model: dict) -> list:
    """Whether each main layer is sparse: the first
    ``first_k_dense_replace`` are dense."""
    dense = model.get("first_k_dense_replace", 0)
    return [i >= dense for i in range(model["num_hidden_layers"])]


@functools.partial(jax.jit, static_argnames=("dims_t", "sparse", "dtype"))
def _make_layer(key, dims_t, sparse, dtype):
    return layer_from_key(key, dict(dims_t), False, sparse, dtype)


@functools.partial(jax.jit, static_argnames=("dims_t", "dtype"))
def _make_ends(key, dims_t, dtype):
    m = dict(dims_t)
    d, v = m["d"], m["vocab"]
    return {"embed": _normal(jax.random.fold_in(key, 1), (v, d), d, dtype),
            "lm_head": _normal(jax.random.fold_in(key, 2), (d, v), d, dtype),
            "out_norm": jnp.ones((d,), dtype)}


@functools.partial(jax.jit, static_argnames=("dims_t", "dtype"))
def _make_mtp_ends(key, dims_t, dtype):
    d = dict(dims_t)["d"]
    return {"enorm": jnp.ones((d,), dtype), "hnorm": jnp.ones((d,), dtype),
            "eh_proj": _normal(jax.random.fold_in(key, 3), (2 * d, d), 2 * d,
                               dtype),
            "norm": jnp.ones((d,), dtype)}


def _parts(key, model: dict):
    """(name, maker) of every part of the tree, in the tree's order."""
    dims_t = tuple(sorted(model_dims(model).items()))
    parts = [("ends", lambda dt: _make_ends(key, dims_t, dt))]
    for i, sparse in enumerate(sparse_layers(model)):
        parts.append((i, functools.partial(
            lambda k, s, dt: _make_layer(k, dims_t, s, dt),
            jax.random.fold_in(key, 16 + i), sparse)))
    if model.get("num_nextn_predict_layers", 0):
        parts.append(("mtp_ends", lambda dt: _make_mtp_ends(key, dims_t,
                                                            dt)))
        parts.append(("mtp", lambda dt: _make_layer(
            jax.random.fold_in(key, 16 + MTP_LAYER), dims_t, True, dt)))
    return parts


def make_params(seed: int, model: dict, dtype) -> dict:
    """The whole tree on the device: what the timed path is given, and
    what the reference starts from."""
    params = {"layers": []}
    for name, make in _parts(seed_key(seed), model):
        got = make(dtype)
        if name == "ends":
            params.update(got)
        elif name == "mtp_ends":
            params["mtp"] = dict(got)
        elif name == "mtp":
            params["mtp"]["layer"] = got
        else:
            params["layers"].append(got)
    return params


@jax.jit
def _gap_norms(new, old):
    return jax.tree.map(lambda a, b: jnp.sqrt(jnp.sum(jnp.square(
        a.astype(jnp.float32) - b.astype(jnp.float32)))), new, old)


def delta_norms(params: dict, seed: int, model: dict) -> dict:
    """The tree of per-leaf norms of ``params`` less the start made from
    ``seed``, a part at a time, so that no second copy of the weights is
    ever held."""
    out = {"layers": []}
    for name, make in _parts(seed_key(seed), model):
        if name == "ends":
            mine = {k: params[k] for k in ("embed", "lm_head", "out_norm")}
            out.update(_gap_norms(mine, make(jnp.float32)))
        elif name == "mtp_ends":
            mine = {k: v for k, v in params["mtp"].items() if k != "layer"}
            out["mtp"] = dict(_gap_norms(mine, make(jnp.float32)))
        elif name == "mtp":
            out["mtp"]["layer"] = _gap_norms(params["mtp"]["layer"],
                                             make(jnp.float32))
        else:
            out["layers"].append(_gap_norms(params["layers"][name],
                                            make(jnp.float32)))
    return out
