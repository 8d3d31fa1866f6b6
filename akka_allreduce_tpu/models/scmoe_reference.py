"""Plain reference of the shortcut double layer with latent attention:
the mathematics ``generate.py``'s ``_shortcut_cached_block`` serves, as a
full forward with no cache, in float32 at ``highest`` matmul precision,
with a Python loop over the experts and softmax attention over expanded
keys and values. Used by tests only (tests/test_scmoe_mla.py): nothing of
the program calls it, and it calls nothing of the program but reads the
parameter tree ``init_transformer`` makes and a ``TransformerConfig``'s
sizes. (The benchmark keeps its own copy, which imports nothing from the
program either: benchmark/references/scmoe_mla_lm.py.)

Per double layer, every norm an RMSNorm with a gain::

    x1 = x  + MLA_0(norm(x))
    h1 = norm(x1)
    m  = MoE(h1)                 # read here, added at the end
    x2 = x1 + FFN_0(h1)
    x3 = x2 + MLA_1(norm(x2))
    x4 = x3 + FFN_1(norm(x3)) + m

``faults`` plants departures a comparison has to see: ``no_held`` (the
held experts' part left out), ``no_identity``, ``no_scale`` (the routed
scaling factor), ``no_kv_scale`` (the latent's lora scale), ``renorm``
(the top-k weights renormalised to sum to the scale); ``fp8`` is the
control, the same mathematics with every weight matmul's operands rounded
to e4m3 under an absmax scale a token and an output channel (the router
stays in float32, as fp8 recipes keep it).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

_HI = jax.lax.Precision.HIGHEST


def _mm(a, b, faults=()):
    if "fp8" in faults:
        a, b = _round_e4m3(a, -1), _round_e4m3(b, 0)
    return jnp.matmul(a, b, precision=_HI)


def _round_e4m3(x, axis):
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / float(
        jnp.finfo(jnp.float8_e4m3fn).max)
    scale = jnp.where(scale > 0, scale, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _f32(x):
    return jnp.asarray(x, jnp.float32)


def _norm(x, gain, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * _f32(gain)


def _rope(x, theta):
    # x (t, heads, d); pairs are (x[i], x[i + d/2])
    t, half = x.shape[0], x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], axis=-1)


def mla(p, x, cfg, faults=()):
    """x (t, d) -> the attention's output (t, d)."""
    t = x.shape[0]
    heads, rank = cfg.n_heads, cfg.kv_lora_rank
    nope, rope_d, vd = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                        cfg.v_head_dim)
    s_q = (cfg.d_model / cfg.q_lora_rank) ** 0.5
    s_kv = 1.0 if "no_kv_scale" in faults else (cfg.d_model / rank) ** 0.5
    h = _norm(x, p["ln"], cfg.norm_eps)
    c_q = _norm(_mm(h, _f32(p["wq_a"]), faults), p["q_norm"],
                cfg.norm_eps)
    q = (_mm(c_q, _f32(p["wq_b"]), faults) * s_q).reshape(
        t, heads, nope + rope_d)
    down = _mm(h, _f32(p["wkv_a"]), faults)
    c_kv = _norm(down[:, :rank], p["kv_norm"], cfg.norm_eps) * s_kv
    k_rope = _rope(down[:, None, rank:], cfg.rope_theta)
    q_rope = _rope(q[..., nope:], cfg.rope_theta)
    up = _mm(c_kv, _f32(p["wkv_b"]), faults).reshape(t, heads, nope + vd)
    k = jnp.concatenate(
        [up[..., :nope], jnp.broadcast_to(k_rope, (t, heads, rope_d))], -1)
    qf = jnp.concatenate([q[..., :nope], q_rope], -1)
    s = jnp.einsum("qhd,khd->hqk", qf, k, precision=_HI) \
        * (nope + rope_d) ** -0.5
    causal = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    w = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
    o = jnp.einsum("hqk,khd->qhd", w, up[..., nope:], precision=_HI)
    return _mm(o.reshape(t, heads * vd), _f32(p["wo"]), faults)


def ffn(p, h, faults=(), e=None):
    """SwiGLU through ``w1 w3 w2`` (``we1 we3 we2`` of expert ``e``)."""
    w1, w3, w2 = (_f32(p[n] if e is None else p[n.replace("w", "we")][e])
                  for n in ("w1", "w3", "w2"))
    return _mm(jax.nn.silu(_mm(h, w1, faults)) * _mm(h, w3, faults), w2,
               faults)


def route(p, h, ex):
    """-> (pick (t, k), weight (t, k))."""
    scores = jax.nn.softmax(_mm(h, _f32(p["router"])), axis=-1)
    _, pick = jax.lax.top_k(scores + _f32(p["bias"]), ex.top_k)
    return pick, jnp.take_along_axis(scores, pick, -1) * ex.scale


def moe(p, h, ex, faults=(), held=None):
    """This share's expert layer for h (t, d): (held experts' part,
    identity part, counts). ``held`` = (offset, count) of the experts in
    ``p`` (default: the configuration's)."""
    offset, count = held or (ex.held_offset, ex.held_count)
    pick, weight = route(p, h, ex)
    if "no_scale" in faults:
        weight = weight / ex.scale
    if "renorm" in faults:
        weight = weight / weight.sum(-1, keepdims=True) * ex.scale
    n_real = ex.n_outputs - ex.n_identity
    part = jnp.zeros_like(h)
    on_held = jnp.zeros(pick.shape, bool)
    for e in range(count):                      # one expert at a time
        mine = pick == offset + e
        on_held |= mine
        w = jnp.where(mine, weight, 0.0).sum(-1, keepdims=True)
        part = part + w * ffn(p, h, faults, e)
    on_identity = pick >= n_real
    identity = jnp.where(on_identity, weight, 0.0).sum(-1, keepdims=True) * h
    if "no_held" in faults:
        part = jnp.zeros_like(part)
    if "no_identity" in faults:
        identity = jnp.zeros_like(identity)
    counts = {"held": on_held.sum(), "identity": on_identity.sum(),
              "absent": (~on_held & ~on_identity).sum(),
              "touched": sum((pick == offset + e).any()
                             for e in range(count))}
    return part, identity, counts


def double_layer(layer, x, cfg, faults=()):
    """x (t, d) -> (x (t, d), the expert layer's counts)."""
    x = x + mla(layer["mla"][0], x, cfg, faults)
    h = _norm(x, layer["ffn"][0]["ln"], cfg.norm_eps)
    part, identity, counts = moe(layer["moe"], h, cfg.experts, faults)
    x = x + ffn(layer["ffn"][0], h, faults)
    x = x + mla(layer["mla"][1], x, cfg, faults)
    h = _norm(x, layer["ffn"][1]["ln"], cfg.norm_eps)
    return x + ffn(layer["ffn"][1], h, faults) + part + identity, counts


def forward(params, tokens, cfg, faults=()):
    """tokens (t,) -> (logits (t, vocab) float32, the layers' counts
    summed)."""
    x = _f32(params["embed"])[tokens]
    total = None
    for layer in params["layers"]:
        x, counts = double_layer(layer, x, cfg, faults)
        total = counts if total is None else {
            k: total[k] + counts[k] for k in counts}
    h = _norm(x, params["out_norm"], cfg.norm_eps)
    return _mm(h, _f32(params["lm_head"]), faults), total
