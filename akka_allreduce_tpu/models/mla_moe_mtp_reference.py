"""Plain reference of latent attention over every position with a dropless
sigmoid expert share and a multi-token-prediction module, as a training
objective (GLM-4.7-Flash's block, ``model_type`` "glm4_moe_lite").
Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision:
expanded keys and values, a causal softmax over the full score matrix (a
block of query rows at a time, recomputed in the backward pass), a Python
loop over the held experts, each over every token. Nothing of the program
is imported; ``model`` is the configuration's dict (the source's key names)
and ``params`` the tree ``init_transformer`` makes. Used by
tests/test_mla_moe_mtp.py; the benchmark keeps a copy of the part between
the two markers (benchmark/references/mla_moe_mtp_lm.py, held equal by
benchmark/tests/test_mla_moe_mtp.py).

One pre-norm block a layer over tokens (B, T), ``h = rmsnorm(x)``::

    c_q = rmsnorm(h W_qa);  q = c_q W_qb          heads x (nope + rope)
    [c_kv | k_r] = h W_kva; c_kv = rmsnorm(c_kv)  k_r: one rotary key
    [k_nope | v] = c_kv W_kvb                     a head
    scores = (q_nope . k_nope + rope(q_r) . rope(k_r)) / (nope + rope) ** 0.5
    x = x + concat(causal_softmax(scores) v) W_o
    x = x + FFN(rmsnorm(x))

No lora scale. RoPE pairs (x[i], x[i + d/2]) at the positions 0..T-1. The
first ``first_k_dense_replace`` layers' FFN is a SwiGLU of
``intermediate_size``; a sparse layer's: ``s = sigmoid(h W_r)`` in
float32, the ``num_experts_per_tok`` largest of ``s + b`` picked, each
weighing ``routed_scaling_factor x s_e / (sum of the picked s)``, plus the
shared expert at weight 1. This chip's share is the sum over the picked
experts it HOLDS (``experts_held``) plus the shared expert; what the absent
experts would add is left out, here as in the program. After a step each
sparse layer's ``b_e`` moves by ``rate x sign(mean load - load_e)`` over
all the router's outputs.

The multi-token-prediction module reads the main stack's last hidden state
AFTER the final norm, ``h_i``, and the next token's embedding:
``x'_i = eh_proj [enorm(Emb(t_{i+1})) ; hnorm(h_i)]``, one sparse layer,
``norm``, the main model's head; its loss is the mean cross-entropy against
``t_{i+2}``. The objective is ``L_main + mtp_weight x L_mtp``.

``quant="fp8"`` is the control, the precision below bf16: every weight
matmul's operands rounded to e4m3 under an absmax scale a token and an
output channel, and in the backward pass the incoming gradient to e5m2 (a
scale a token); sums in float32 (the router stays in float32).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# -- the loss (benchmark/references/mla_moe_mtp_lm.py keeps a copy) --------

Q_BLOCK = 256        # query rows whose scores are alive at once
HEAD_CHUNK = 1024    # positions whose logits are alive at once
_HI = jax.lax.Precision.HIGHEST


def _hi(a, b):
    return jnp.matmul(a, b, precision=_HI)


def _round_fp8(x, axis, dtype):
    """Round to a float8 type under an absmax scale along ``axis``."""
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / float(
        jnp.finfo(dtype).max)
    scale = jnp.where(scale > 0, scale, 1.0)
    return (x / scale).astype(dtype).astype(jnp.float32) * scale


@jax.custom_vjp
def _mm_fp8(x, w):
    return _hi(_round_fp8(x, -1, jnp.float8_e4m3fn),
               _round_fp8(w, 0, jnp.float8_e4m3fn))


def _mm_fp8_fwd(x, w):
    xq = _round_fp8(x, -1, jnp.float8_e4m3fn)
    wq = _round_fp8(w, 0, jnp.float8_e4m3fn)
    return _hi(xq, wq), (xq, wq)


def _mm_fp8_bwd(res, dy):
    xq, wq = res
    dyq = _round_fp8(dy, -1, jnp.float8_e5m2)
    dw = _hi(xq.reshape(-1, xq.shape[-1]).T, dyq.reshape(-1, dyq.shape[-1]))
    return _hi(dyq, wq.T), dw


_mm_fp8.defvjp(_mm_fp8_fwd, _mm_fp8_bwd)


def _mm(x, w, quant=None):
    w = jnp.asarray(w, jnp.float32)
    if quant == "fp8":
        return _mm_fp8(x, w)
    if quant is not None:
        raise ValueError(f"unknown control precision {quant!r}")
    return _hi(x, w)


def _rmsnorm(x, gain, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * jnp.asarray(gain, jnp.float32)


def _rope(x, theta):
    # x (B, T, H, D); pairs are (x[i], x[i + D/2]); positions 0..T-1
    t, half = x.shape[1], x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], axis=-1)


def _attention(q, k, v):
    """Causal softmax attention, q/k (B, T, H, D), v (B, T, H, Dv), a block
    of query rows at a time (each recomputed in the backward pass)."""
    b, t, heads, d = q.shape
    qb = Q_BLOCK if t % Q_BLOCK == 0 else t

    @jax.checkpoint
    def block(args):
        rows, qs = args
        s = jnp.einsum("bqhd,bkhd->bhqk", qs, k, precision=_HI) * d ** -0.5
        live = jnp.arange(t)[None, :] <= rows[:, None]
        p = jax.nn.softmax(jnp.where(live[None, None], s, -jnp.inf), -1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v, precision=_HI)
    out = jax.lax.map(block, (jnp.arange(t).reshape(t // qb, qb),
                              jnp.moveaxis(q.reshape(b, t // qb, qb, heads,
                                                     d), 1, 0)))
    return jnp.moveaxis(out, 0, 1).reshape(b, t, heads, v.shape[-1])


def mla(p, x, model, quant=None):
    """Latent attention over every position of x (B, T, D), through
    ``wo``."""
    b, t, _ = x.shape
    heads, rank = model["num_attention_heads"], model["kv_lora_rank"]
    nope, rope_d = model["qk_nope_head_dim"], model["qk_rope_head_dim"]
    vd, eps = model["v_head_dim"], model["rms_norm_eps"]
    theta = float(model["rope_theta"])
    h = _rmsnorm(x, p["ln"], eps)
    c_q = _rmsnorm(_mm(h, p["wq_a"], quant), p["q_norm"], eps)
    q = _mm(c_q, p["wq_b"], quant).reshape(b, t, heads, nope + rope_d)
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], theta)], -1)
    down = _mm(h, p["wkv_a"], quant)
    c_kv = _rmsnorm(down[..., :rank], p["kv_norm"], eps)
    k_rope = _rope(down[:, :, None, rank:], theta)
    up = _mm(c_kv, p["wkv_b"], quant).reshape(b, t, heads, nope + vd)
    k = jnp.concatenate(
        [up[..., :nope], jnp.broadcast_to(k_rope, (b, t, heads, rope_d))], -1)
    o = _attention(q, k, up[..., nope:]).reshape(b, t, heads * vd)
    return _mm(o, p["wo"], quant)


def swiglu(w1, w3, w2, h, quant=None):
    """A SwiGLU, recomputed in the backward pass (one expert's
    intermediates alive at a time)."""
    @jax.checkpoint
    def run(w1, w3, w2, h):
        return _mm(jax.nn.silu(_mm(h, w1, quant)) * _mm(h, w3, quant), w2,
                   quant)
    return run(w1, w3, w2, h)


def route(moe, h, model):
    """-> (pick (..., k) int32, weight (..., k) float32): the router in
    float32 whatever the control."""
    scores = jax.nn.sigmoid(_mm(h, moe["router"]))
    _, pick = jax.lax.top_k(
        jax.lax.stop_gradient(scores)
        + jnp.asarray(moe["bias"], jnp.float32),
        model["num_experts_per_tok"])
    weight = jnp.take_along_axis(scores, pick, -1)
    if model.get("norm_topk_prob", True):
        weight = weight / (weight.sum(-1, keepdims=True) + 1e-20)
    return pick, weight * float(model["routed_scaling_factor"])


def held_of(model) -> tuple:
    return tuple(model.get("experts_held", (0, model["n_routed_experts"])))


def moe(m, h, model, quant=None):
    """This chip's share of the expert layer over h (B, T, D): (the held
    experts' part + the shared expert's, the assignments of each router
    output)."""
    offset, count = held_of(model)
    pick, weight = route(m, h, model)
    y = swiglu(m["ws1"], m["ws3"], m["ws2"], h, quant) if "ws1" in m \
        else jnp.zeros_like(h)
    for e in range(count):
        w = jnp.where(pick == offset + e, weight, 0.0).sum(-1, keepdims=True)
        y = y + w * swiglu(m["we1"][e], m["we3"][e], m["we2"][e], h, quant)
    load = jnp.zeros((model["n_routed_experts"],), jnp.int32).at[
        pick.reshape(-1)].add(1)
    return y, load


def layer(lyr, x, model, quant=None):
    """One layer (recomputed in the backward pass) -> (x, the expert
    layer's loads or None)."""
    @jax.checkpoint
    def run(lyr, x):
        x = x + mla(lyr["mla"], x, model, quant)
        h = _rmsnorm(x, lyr["ln2"], model["rms_norm_eps"])
        if "moe" in lyr:
            y, load = moe(lyr["moe"], h, model, quant)
            return x + y, load
        return x + swiglu(lyr["w1"], lyr["w3"], lyr["w2"], h, quant), None
    return run(lyr, x)


def _ce_sum(params, x, targets, weights, quant):
    """Summed weighted cross-entropy of the head over x (B, T, D), a chunk
    of positions at a time (recomputed in the backward pass)."""
    b, t, d = x.shape
    c = HEAD_CHUNK if t % HEAD_CHUNK == 0 else t

    @jax.checkpoint
    def part(args):
        xc, tc, wc = args
        logp = jax.nn.log_softmax(_mm(xc, params["lm_head"], quant), -1)
        return -(jnp.take_along_axis(logp, tc[..., None], -1)[..., 0]
                 * wc).sum()

    def split(a):
        return jnp.moveaxis(a.reshape((b, t // c, c) + a.shape[2:]), 1, 0)
    return jax.lax.map(part, (split(x), split(targets), split(weights))).sum()


def hidden(params, tokens, model, quant=None):
    """tokens (B, T) -> (the main stack's last hidden state after the
    final norm, the module's ahead of the shared head (None without one),
    the expert layers' loads: the main stack's, then the module's)."""
    eps = model["rms_norm_eps"]
    embed = jnp.asarray(params["embed"], jnp.float32)
    x = embed[tokens]
    loads = []
    for lyr in params["layers"]:
        x, load = layer(lyr, x, model, quant)
        if load is not None:
            loads.append(load)
    x = _rmsnorm(x, params["out_norm"], eps)
    if not model.get("num_nextn_predict_layers", 0):
        return x, None, jnp.stack(loads)
    p = params["mtp"]
    e = _rmsnorm(embed[jnp.roll(tokens, -1, axis=1)], p["enorm"], eps)
    hm = _mm(jnp.concatenate([e, _rmsnorm(x, p["hnorm"], eps)], -1),
             p["eh_proj"], quant)
    hm, load = layer(p["layer"], hm, model, quant)
    return x, _rmsnorm(hm, p["norm"], eps), jnp.stack(loads + [load])


def loss(params, tokens, model, mtp_weight, quant=None):
    """tokens (B, T) -> (L_main + mtp_weight x L_mtp, (L_main, L_mtp, the
    expert layers' loads))."""
    b, t = tokens.shape
    x, hm, loads = hidden(params, tokens, model, quant)
    at = jnp.arange(t)[None, :]
    w1 = jnp.broadcast_to(at < t - 1, (b, t)).astype(jnp.float32)
    main = _ce_sum(params, x, jnp.roll(tokens, -1, axis=1), w1,
                   quant) / w1.sum()
    l_mtp = jnp.zeros((), jnp.float32)
    if hm is not None:
        w2 = jnp.broadcast_to(at < t - 2, (b, t)).astype(jnp.float32)
        l_mtp = _ce_sum(params, hm, jnp.roll(tokens, -2, axis=1), w2,
                        quant) / w2.sum()
    return main + mtp_weight * l_mtp, (main, l_mtp, loads)


def bias_update(params, loads, rate):
    """Each sparse layer's selection bias after a step, ``loads`` in the
    order :func:`loss` gives them."""
    rows = iter(range(loads.shape[0]))

    def one(lyr):
        if "moe" not in lyr:
            return lyr
        load = loads[next(rows)].astype(jnp.float32)
        bias = jnp.asarray(lyr["moe"]["bias"], jnp.float32)
        return dict(lyr, moe=dict(lyr["moe"], bias=bias + rate * jnp.sign(
            load.mean() - load)))
    out = dict(params, layers=[one(lyr) for lyr in params["layers"]])
    if "mtp" in params:
        out["mtp"] = dict(params["mtp"], layer=one(params["mtp"]["layer"]))
    return out

# -- end of the loss --------------------------------------------------------


def value_and_grad(params, tokens, model, mtp_weight=0.3, quant=None):
    """(loss, (main, mtp, loads)), gradient of every leaf: float32,
    ``highest``."""
    with jax.default_matmul_precision("highest"):
        f32 = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), params)
        return jax.value_and_grad(loss, has_aux=True)(
            f32, jnp.asarray(tokens), model, mtp_weight, quant)


def train_step(params, m, v, tokens, t, model, lr, wd, mtp_weight=0.3,
               rate=1e-3, b1=0.9, b2=0.999, eps=1e-8):
    """One AdamW step (decay on matrices only) and the bias update:
    (params, m, v, loss)."""
    (value, (_main, _mtp, loads)), g = value_and_grad(params, tokens, model,
                                                      mtp_weight)

    def leaf(p, m_, v_, g_):
        m_ = b1 * m_ + (1 - b1) * g_
        v_ = b2 * v_ + (1 - b2) * g_ * g_
        upd = (m_ / (1 - b1 ** t)) / (jnp.sqrt(v_ / (1 - b2 ** t)) + eps)
        if p.ndim >= 2:
            upd = upd + wd * p
        return p - lr * upd, m_, v_
    out = jax.tree.map(leaf, params, m, v, g)
    pick = lambda i: jax.tree.map(lambda o: o[i], out,  # noqa: E731
                                  is_leaf=lambda o: isinstance(o, tuple))
    return bias_update(pick(0), loads, rate), pick(1), pick(2), value
