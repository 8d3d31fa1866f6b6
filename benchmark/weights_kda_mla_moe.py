"""Weights of the hybrid of delta-rule linear attention and latent attention
(``configs/ling-3.0-flash-serve.json``) from ``--seed``, made on the device
a layer a jitted call (one compiled program a kind of layer).

As ``weights.py``: the benchmark makes the weights, not the program; the
timed path and the plain reference are both handed this tree. Its layout is
the one the program's model code reads (``embed``, ``lm_head``,
``out_norm``, ``layers[i]`` with either ``ln1`` and ``kda`` - ``w_in``,
``conv_w``, ``a_log``, ``dt_bias``, ``norm``, ``w_out`` - or ``mla`` -
``ln``, ``wq``, ``q_norm``, ``wkv_a``, ``kv_norm``, ``wkv_b``, ``w_gate``,
``wo`` - then ``ln2`` and either ``w1`` / ``w3`` / ``w2`` or ``moe`` with
the held experts' stacks and the shared expert's ``ws1`` / ``ws3`` /
``ws2``); a leaf's values depend only on the seed.

Matrices are normal with std fan_in ** -0.5 and gains 1, but for the
routers, which are then cleared of their input's mean direction
(:func:`clear_routers`); the delta rule's
constants take the initialisation the public KDA layer keeps (the
configuration's ``assumed`` block): ``exp(A_log)`` uniform in 1-16 a head,
``softplus(dt_bias)`` log-uniform in 0.001-0.1 a channel, so that a channel
remembers a few to thousands of tokens; the convolutions' taps std width **
-0.5.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.weights import _normal, seed_key


def router_outputs(model: dict) -> int:
    return model.get("published", {}).get("num_experts",
                                          model["num_experts"])


def layer_kinds(model: dict) -> list:
    """(mixer, ffn) a layer: "kda" | "latent" by ``layer_group_size``,
    "dense" | "sparse" by ``first_k_dense_replace``."""
    group = model["layer_group_size"]
    dense = model.get("first_k_dense_replace", 0)
    return [("latent" if (i + 1) % group == 0 else "kda",
             "dense" if i < dense else "sparse")
            for i in range(model["num_hidden_layers"])]


def model_dims(model: dict) -> dict:
    """The sizes the weights need, from the configuration's keys. The
    router is as wide as the source's experts (``published``);
    ``experts_held`` says how many of them this chip holds."""
    heads = model["num_attention_heads"]
    return {
        "d": model["hidden_size"], "heads": heads, "hd": model["head_dim"],
        "conv": model["short_conv_kernel_size"],
        "rank": model["kv_lora_rank"], "nope": model["qk_nope_head_dim"],
        "rope": model["qk_rope_head_dim"], "vd": model["v_head_dim"],
        "ff": model["intermediate_size"],
        "expert_ff": model["moe_intermediate_size"],
        "shared_ff": model.get("num_shared_experts", 0)
        * model["moe_shared_expert_intermediate_size"],
        "held": model.get("experts_held", (0, model["num_experts"]))[1],
        "outputs": router_outputs(model), "vocab": model["vocab_size"],
    }


def _matrices(key, shapes: dict, dtype) -> dict:
    return {name: _normal(jax.random.fold_in(key, j), shape, shape[-2],
                          jnp.float32).astype(dtype)
            for j, (name, shape) in enumerate(sorted(shapes.items()))}


def kda_from_key(key, m: dict, dtype) -> dict:
    """One delta-rule mixer, traced."""
    d, heads, kd = m["d"], m["heads"], m["heads"] * m["hd"]
    k = [jax.random.fold_in(key, j) for j in range(4)]
    step = jnp.exp(jax.random.uniform(
        k[2], (kd,), jnp.float32, math.log(1e-3), math.log(1e-1)))
    return {
        **_matrices(k[0], {"w_in": (d, 5 * kd + heads), "w_out": (kd, d)},
                    dtype),
        "conv_w": (jax.random.normal(k[1], (3 * kd, m["conv"]), jnp.float32)
                   * m["conv"] ** -0.5).astype(dtype),
        "a_log": jnp.log(jax.random.uniform(k[3], (heads,), jnp.float32,
                                            1.0, 16.0)),
        # the inverse of softplus, so that softplus(dt_bias) = step
        "dt_bias": step + jnp.log(-jnp.expm1(-step)),
        "norm": jnp.ones((kd,), dtype),
    }


def latent_from_key(key, m: dict, dtype) -> dict:
    """One latent attention, traced."""
    d, h, qk = m["d"], m["heads"], m["nope"] + m["rope"]
    return {
        **_matrices(key, {"wq": (d, h * qk),
                          "wkv_a": (d, m["rank"] + m["rope"]),
                          "wkv_b": (m["rank"], h * (m["nope"] + m["vd"])),
                          "w_gate": (d, h), "wo": (h * m["vd"], d)}, dtype),
        "ln": jnp.ones((d,), dtype), "q_norm": jnp.ones((qk,), dtype),
        "kv_norm": jnp.ones((m["rank"],), dtype),
    }


def layer_from_key(key, m: dict, mixer: str, ffn: str, dtype) -> dict:
    """One layer, traced (call it inside a jit)."""
    d = m["d"]
    k = [jax.random.fold_in(key, j) for j in range(3)]
    layer = {"ln2": jnp.ones((d,), dtype)}
    if mixer == "kda":
        layer.update(ln1=jnp.ones((d,), dtype),
                     kda=kda_from_key(k[0], m, dtype))
    else:
        layer["mla"] = latent_from_key(k[0], m, dtype)
    if ffn == "dense":
        layer.update(_matrices(k[1], {"w1": (d, m["ff"]), "w3": (d, m["ff"]),
                                      "w2": (m["ff"], d)}, dtype))
        return layer
    e, f, s = m["held"], m["expert_ff"], m["shared_ff"]
    layer["moe"] = {
        "bias": jnp.zeros((m["outputs"],), jnp.float32),
        **_matrices(k[2], {
            "router": (d, m["outputs"]),
            "we1": (e, d, f), "we3": (e, d, f), "we2": (e, f, d),
            "ws1": (d, s), "ws3": (d, s), "ws2": (s, d)}, dtype)}
    return layer


@functools.partial(jax.jit,
                   static_argnames=("dims_t", "mixer", "ffn", "dtype"))
def _make_layer(key, dims_t, mixer, ffn, dtype):
    return layer_from_key(key, dict(dims_t), mixer, ffn, dtype)


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
        _make_layer(jax.random.fold_in(key, 16 + i), dims_t, mixer, ffn,
                    dtype)
        for i, (mixer, ffn) in enumerate(layer_kinds(model))]
    clear_routers(params, model, clearing_tokens(key, model))
    return params


# lanes x positions of the random prompts whose residual stream the routers
# are cleared of
CLEARING = (4, 512)


def clearing_tokens(key, model: dict):
    """(lanes, positions) int32 ids from the seed's key."""
    return jax.random.randint(jax.random.fold_in(key, 7), CLEARING, 0,
                              model["vocab_size"], jnp.int32)


@jax.jit
def _clear_of_mean(router, h):
    """``router`` (d, outputs) less each column's part along the mean of
    ``h`` (n, d), every column keeping its norm."""
    w = router.astype(jnp.float32)
    u = h.mean(0)
    u = u / jnp.linalg.norm(u)
    cleared = w - jnp.outer(u, jnp.matmul(u, w,
                                          precision=jax.lax.Precision.HIGHEST))
    cleared = cleared * (jnp.linalg.norm(w, axis=0)
                         / jnp.linalg.norm(cleared, axis=0))
    return cleared.astype(router.dtype)


def clear_routers(params: dict, model: dict, tokens) -> None:
    """Each expert layer's router, in place, less its columns' parts along
    the mean of the router's input over ``tokens`` (lanes, positions), the
    layers before it run by the plain reference with the routers already
    cleared. A delta-rule layer's output has a mean direction (its q, k
    and v pass a SiLU and its gate a sigmoid, all positive on average), a
    fifth of the normed stream's energy at the first expert layer; random
    columns read that direction as a fixed preference for some experts, so that
    ~185 of 512 experts take the load and the held quarter of them 22-28%
    of the rows, by seed. A load-balanced router (``noaux_tc``: the
    trained selection bias keeps the experts' loads level) routes by no
    such direction: cleared of it, the same tokens spread over ~500."""
    from benchmark.references import kda_mla_moe_lm as ref
    lanes, t = tokens.shape
    model_t, eps = ref._freeze(model), model["rms_norm_eps"]
    no_cut = (jnp.zeros((t,), jnp.int32),) * 2
    xs = [jnp.asarray(params["embed"], jnp.float32)[row] for row in tokens]
    cleared = {}
    for i, layer in enumerate(params["layers"]):
        if "kda" in layer:
            xs = [x + ref._kda_jit(layer["kda"], layer["ln1"], x, no_cut, t,
                                   model_t, None, ())[0] for x in xs]
        else:
            xs = [x + ref._latent_jit(layer["mla"], x, model_t, None, ())
                  for x in xs]
        h = ref._norm_jit(jnp.concatenate(xs), layer["ln2"], eps)
        if "moe" in layer:
            router = _clear_of_mean(layer["moe"]["router"], h)
            part, shared = ref.moe({**layer["moe"], "router": router}, h,
                                   model)
            out = part + shared
            cleared[i] = np.asarray(router)
        else:
            out = ref._swiglu_jit(layer["w1"], layer["w3"], layer["w2"], h,
                                  None)
        xs = [x + o for x, o in zip(xs, jnp.split(out, lanes))]
    del xs, h, out
    # the drawn routers are let go before the cleared ones are placed, so
    # that each cleared one takes the slot of the same size its drawn one
    # leaves: the pass leaves no buffer among the weights' or past them,
    # where it would split the free memory the serving programs' temporaries
    # take
    for i in cleared:
        params["layers"][i]["moe"].pop("router").delete()
    for i, router in cleared.items():
        params["layers"][i]["moe"]["router"] = jnp.asarray(router)
