"""Plain reference of the model described layer by layer: latent attention
that attends only the positions a learned indexer picks (an indexer in a
"full" layer, its choice reused by the "shared" layers after it), sigmoid
routing over the experts with a shared expert, and leading dense layers
(GLM-5.2's block, ``model_type`` "glm_moe_dsa"). Straightforward
``jax.numpy`` in float32 at ``highest`` matmul precision, a full forward
with no cache, no kernel and no batching: expanded keys and values, the
selection as a MASK over the full score matrix (computed a block of query
rows at a time, so that 24k positions fit), a Python loop over the experts.
Nothing of the program is imported; ``model`` is the configuration's dict
(the source's key names) and ``params`` the tree ``init_transformer``
makes. Used by tests/test_dsa_moe.py; the benchmark keeps a copy of the
part between the two markers (benchmark/references/dsa_moe_lm.py, held
equal by benchmark/tests/test_dsa_moe.py).

One pre-norm block a layer, ``h = rmsnorm(x)``::

    c_q = rmsnorm(h W_qa);  q = c_q W_qb          heads x (nope + rope)
    [c_kv | k_r] = h W_kva; c_kv = rmsnorm(c_kv)  k_r: one rotary key
    [k_nope | v] = c_kv W_kvb                     a head
    scores = (q_nope . k_nope + rope(q_r) . rope(k_r)) / (nope + rope) ** 0.5
    x = x + concat(softmax_over_the_chosen(scores) v) W_o
    x = x + FFN(rmsnorm(x))

No lora scale (the source has no ``mla_scale_*`` key). The indexer of a
full layer: ``q_i = c_q W_qb^I`` (index heads x index dim), ``k_i =
layernorm(h W_k^I)`` (one key a token), RoPE on the first ``rope`` columns
of both, ``w = (h W_w) x heads ** -0.5 x dim ** -0.5``; ``I[t, s] = sum_h
w[t, h] relu(q_i[t, h] . k_i[s])``; token t attends the ``index_topk``
positions s <= t of largest ``I[t, s]`` (all of them while t + 1 is
fewer). A shared layer attends, for the same token, what the nearest full
layer before it chose. The FFN of a dense layer is a SwiGLU of
``intermediate_size``; of a sparse layer ``s = sigmoid(h W_r)`` in float32,
the ``num_experts_per_tok`` largest of ``s + b`` picked, each weighing
``routed_scaling_factor x s_e / (sum of the picked s)``, plus the shared
expert at weight 1. This chip's share is the sum over the picked experts
it HOLDS (``experts_held``) plus the shared expert; what the absent
experts would add is left out, here as in the program.

``quant="fp8"`` is the control, the precision below bf16: every weight
matmul's operands rounded to e4m3 under an absmax scale a token and an
output channel, sums in float32 (the router stays in float32). ``FAULTS``
are departures a comparison has to see, each a switch of its own.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

# -- the forward pass (benchmark/references/dsa_moe_lm.py keeps a copy) ----

FAULTS = (
    "no_selection",       # the selection left out: attend everything
    "recent",             # the most recent index_topk positions instead
    "no_relu",            # the indexer's ReLU left out
    "no_index_weights",   # the per-head weights w left out (1)
    "shared_own_choice",  # a shared layer chooses for itself, with the
                          # indexer weights of the full layer before it
    "no_renorm",          # the picked scores not renormalised
    "softmax",            # softmax over the outputs in the sigmoid's place
    "no_shared",          # the shared expert left out
    "no_held",            # the held experts left out
    "no_scale",           # routed_scaling_factor left out
)
# which faults change which jitted piece (a piece compiles once a set)
ATTENTION_FAULTS = frozenset({"recent", "no_relu", "no_index_weights"})
ROUTE_FAULTS = frozenset({"no_renorm", "softmax", "no_scale"})
Q_BLOCK = 256        # query rows whose scores are alive at once
HEAD_GROUP = 8       # heads whose keys, values and scores are alive at once
INDEX_NORM_EPS = 1e-6
_HI = jax.lax.Precision.HIGHEST


def _round_fp8(x, axis):
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / float(
        jnp.finfo(jnp.float8_e4m3fn).max)
    scale = jnp.where(scale > 0, scale, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(x, w, quant=None):
    w = jnp.asarray(w, jnp.float32)
    if quant == "fp8":
        x, w = _round_fp8(x, -1), _round_fp8(w, 0)
    elif quant is not None:
        raise ValueError(f"unknown control precision {quant!r}")
    return jnp.matmul(x, w, precision=_HI)


def _rmsnorm(x, gain, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * jnp.asarray(gain, jnp.float32)


def _layernorm(x, gain, bias, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return ((x - mean) * jax.lax.rsqrt(var + eps)
            * jnp.asarray(gain, jnp.float32)
            + jnp.asarray(bias, jnp.float32))


def _rope(x, theta):
    # x (T, H, D); pairs are (x[i], x[i + D/2]); positions 0..T-1
    t, half = x.shape[0], x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], axis=-1)


def _freeze(model):
    return tuple(sorted((k, v) for k, v in model.items()
                        if isinstance(v, (int, float, str, bool,
                                          type(None)))))


def _theta(model) -> float:
    return float(model["rope_parameters"]["rope_theta"])


def _query_blocks(fn, t, *arrays):
    """``fn(rows, *blocks)`` over blocks of ``Q_BLOCK`` query rows (``rows``
    their positions), results concatenated: (T, ...)."""
    qb = min(Q_BLOCK, t)
    n = -(-t // qb)
    pad = n * qb - t

    def split(a):
        a = jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
        return a.reshape((n, qb) + a.shape[1:])
    rows = jnp.arange(n * qb).reshape(n, qb)
    out = jax.lax.map(lambda xs: fn(*xs),
                      (rows,) + tuple(split(a) for a in arrays))
    return out.reshape((n * qb,) + out.shape[2:])[:t]


def choose(idx, c_q, h, model, theta, quant=None, faults=()):
    """A full layer's indexer over h (T, D) and the query's bottleneck
    c_q (T, q_rank): the ``index_topk`` positions each token attends,
    (T, k) int32. Where t + 1 < k the rest lie past t (a mask over them
    and the causal mask leave the live ones)."""
    t = h.shape[0]
    heads, dim = model["index_n_heads"], model["index_head_dim"]
    rope_d = model["qk_rope_head_dim"]
    top = min(model["index_topk"], t)
    q = _mm(c_q, idx["wq_b"], quant).reshape(t, heads, dim)
    q = jnp.concatenate([_rope(q[..., :rope_d], theta), q[..., rope_d:]],
                        axis=-1)
    k = _layernorm(_mm(h, idx["wk"], quant), idx["k_norm"], idx["k_bias"],
                   INDEX_NORM_EPS)[:, None]
    k = jnp.concatenate([_rope(k[..., :rope_d], theta), k[..., rope_d:]],
                        axis=-1)[:, 0]
    w = _mm(h, idx["ww"], quant) * (heads ** -0.5 * dim ** -0.5)
    if "no_index_weights" in faults:
        w = jnp.ones_like(w)

    def block(rows, q, w):
        s = jnp.einsum("qhd,sd->qhs", q, k, precision=_HI)
        if "no_relu" not in faults:
            s = jax.nn.relu(s)
        score = jnp.einsum("qhs,qh->qs", s, w, precision=_HI)
        if "recent" in faults:
            score = jnp.broadcast_to(
                jnp.arange(t, dtype=jnp.float32), score.shape)
        live = jnp.arange(t)[None, :] <= rows[:, None]
        return jax.lax.top_k(jnp.where(live, score, -jnp.inf), top)[1]
    return _query_blocks(block, t, q, w).astype(jnp.int32)


def mla(p, h, c_q, chosen, model, theta, quant=None, everything=False):
    """Latent attention over the normed input h (T, D): token t attends
    the positions ``chosen[t]`` (T, k) that lie at or before t, or every
    position at or before t where ``everything`` holds (a flag, so that
    one compiled attention serves both), as a mask over the full scores:
    the mask is made once for all heads. A group of heads at a time (their
    expanded keys and values are alive together), a block of query rows at
    a time within it. Returns the attention's output through ``wo``."""
    t = h.shape[0]
    heads, rank = model["num_attention_heads"], model["kv_lora_rank"]
    nope, rope_d = model["qk_nope_head_dim"], model["qk_rope_head_dim"]
    vd, eps = model["v_head_dim"], model["rms_norm_eps"]
    down = _mm(h, p["wkv_a"], quant)
    c_kv = _rmsnorm(down[:, :rank], p["kv_norm"], eps)
    k_rope = _rope(down[:, None, rank:], theta)
    g = HEAD_GROUP if heads % HEAD_GROUP == 0 else heads
    at = jnp.arange(t)
    picked = jnp.zeros((t, t), bool).at[at[:, None], chosen].set(True)
    mask = (at[None, :] <= at[:, None]) & (picked | everything)

    def by_group(w):
        w = jnp.asarray(w, jnp.float32)
        return jnp.moveaxis(w.reshape(w.shape[0], heads // g, -1), 1, 0)

    wq_g, wkv_g = by_group(p["wq_b"]), by_group(p["wkv_b"])

    def group(gi, o):
        q = _mm(c_q, wq_g[gi], quant).reshape(t, g, nope + rope_d)
        qf = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], theta)],
                             -1)
        up = _mm(c_kv, wkv_g[gi], quant).reshape(t, g, nope + vd)
        k = jnp.concatenate(
            [up[..., :nope], jnp.broadcast_to(k_rope, (t, g, rope_d))], -1)
        v = up[..., nope:]

        def block(_rows, qb, mask_b):
            s = jnp.einsum("qhd,khd->hqk", qb, k, precision=_HI) \
                * (nope + rope_d) ** -0.5
            w = jax.nn.softmax(jnp.where(mask_b[None], s, -jnp.inf), axis=-1)
            return jnp.einsum("hqk,khd->qhd", w, v,
                              precision=_HI).reshape(qb.shape[0], g * vd)
        # the heads' outputs side by side, written where they lie
        return jax.lax.dynamic_update_slice(
            o, _query_blocks(block, t, qf, mask), (0, gi * g * vd))
    o = jax.lax.fori_loop(0, heads // g, group,
                          jnp.zeros((t, heads * vd), jnp.float32))
    return _mm(o, p["wo"], quant)


def swiglu(w1, w3, w2, h, quant=None):
    return _mm(jax.nn.silu(_mm(h, w1, quant)) * _mm(h, w3, quant), w2, quant)


def route(moe, h, model, faults=()):
    """-> (pick (T, k) int32, weight (T, k) float32): the router in
    float32 whatever the control."""
    logits = _mm(h, moe["router"])
    scores = (jax.nn.softmax(logits, axis=-1) if "softmax" in faults
              else jax.nn.sigmoid(logits))
    _, pick = jax.lax.top_k(scores + jnp.asarray(moe["bias"], jnp.float32),
                            model["num_experts_per_tok"])
    weight = jnp.take_along_axis(scores, pick, -1)
    if model.get("norm_topk_prob", True) and "no_renorm" not in faults:
        weight = weight / (weight.sum(-1, keepdims=True) + 1e-20)
    if "no_scale" not in faults:
        weight = weight * float(model["routed_scaling_factor"])
    return pick, weight


@functools.partial(jax.jit, static_argnames=("model_t", "theta", "quant",
                                             "faults"))
def _attend_jit(p, idx, x, chosen, own, everything, model_t, theta, quant,
                faults):
    """One layer's attention half: (x after it, the selection it attended).
    ``own``: the layer chooses with the indexer ``idx``; otherwise it
    attends ``chosen`` (a shared layer, or a full layer that is handed its
    choice). ``own`` and ``everything`` are flags and not static, so one
    compiled program serves every layer of a forward, with the selection
    and without."""
    model = dict(model_t)
    eps = model["rms_norm_eps"]
    h = _rmsnorm(x, p["ln"], eps)
    c_q = _rmsnorm(_mm(h, p["wq_a"], quant), p["q_norm"], eps)
    chosen = jax.lax.cond(
        own, lambda: choose(idx, c_q, h, model, theta, quant, faults),
        lambda: chosen)
    return (x + mla(p, h, c_q, chosen, model, theta, quant, everything),
            chosen, h, c_q)


@functools.partial(jax.jit, static_argnames=("quant",))
def _swiglu_jit(w1, w3, w2, h, quant):
    return swiglu(w1, w3, w2, h, quant)


@functools.partial(jax.jit, static_argnames=("model_t", "faults"))
def _route_jit(moe_router, h, model_t, faults):
    return route(moe_router, h, dict(model_t), faults)


@functools.partial(jax.jit, static_argnames=("eps",))
def _norm_jit(x, gain, eps):
    return _rmsnorm(x, gain, eps)


def held_of(model) -> tuple:
    return tuple(model.get("experts_held", (0, model["n_routed_experts"])))


def moe(layer_moe, h, model, quant=None, faults=()):
    """This chip's share of the expert layer for h (T, D): (the held
    experts' part, the shared expert's part, counts), one expert at a
    time."""
    offset, count = held_of(model)
    pick, weight = _route_jit(
        {"router": layer_moe["router"], "bias": layer_moe["bias"]}, h,
        _freeze(model), tuple(sorted(ROUTE_FAULTS & set(faults))))
    part = jnp.zeros_like(h)
    on_held = jnp.zeros(pick.shape, bool)
    touched = 0
    for e in range(count):
        mine = pick == offset + e
        on_held |= mine
        touched += int(mine.any())
        w = jnp.where(mine, weight, 0.0).sum(-1, keepdims=True)
        part = part + w * _swiglu_jit(
            layer_moe["we1"][e], layer_moe["we3"][e], layer_moe["we2"][e],
            h, quant)
    shared = jnp.zeros_like(h)
    if "ws1" in layer_moe and "no_shared" not in faults:
        shared = _swiglu_jit(layer_moe["ws1"], layer_moe["ws3"],
                             layer_moe["ws2"], h, quant)
    if "no_held" in faults:
        part = jnp.zeros_like(part)
    held = int(on_held.sum())
    counts = {"held": held, "absent": int(pick.size) - held,
              "touched": touched}
    return part, shared, counts


def layer_forward(layers, i, x, chosen, model, quant=None, faults=(),
                  choice=None):
    """Layer ``i`` of ``layers`` over x (T, D) -> (x, the selection its
    attention attended, info). ``chosen`` is what the layer before
    attended. ``choice`` (T, k) hands a full layer its selection in its
    own indexer's place (to hold the rest of the model to a program whose
    near ties fell the other way). ``info``: the attention's normed input
    ``h`` and bottleneck ``c_q``, and for a sparse layer its input
    ``h_moe`` and its held and shared parts."""
    layer = layers[i]
    model_t, eps = _freeze(model), model["rms_norm_eps"]
    own = "indexer" in layer or "shared_own_choice" in faults
    # a shared layer is handed the weights of the full layer before it,
    # which it uses only where the fault makes it choose for itself
    idx = [l["indexer"] for l in layers[:i + 1] if "indexer" in l][-1]
    if choice is not None:
        own, chosen = False, jnp.asarray(choice, jnp.int32)
    if chosen is None:
        chosen = jnp.zeros(
            (x.shape[0], min(model["index_topk"], x.shape[0])), jnp.int32)
    x, chosen, h, c_q = _attend_jit(
        layer["mla"], idx, x, chosen, own, "no_selection" in faults,
        model_t, _theta(model), quant,
        tuple(sorted(ATTENTION_FAULTS & set(faults))))
    info = {"h": h, "c_q": c_q}
    h2 = _norm_jit(x, layer["ln2"], eps)
    if "moe" in layer:
        part, shared, _counts = moe(layer["moe"], h2, model, quant, faults)
        info.update(h_moe=h2, part=part, shared=shared)
        return x + part + shared, chosen, info
    return (x + _swiglu_jit(layer["w1"], layer["w3"], layer["w2"], h2,
                            quant), chosen, info)


@functools.partial(jax.jit, static_argnames=("eps", "quant"))
def _head_jit(out_norm, lm_head, x, eps, quant):
    return _mm(_rmsnorm(x, out_norm, eps), lm_head, quant)


def forward(params, tokens, model, quant=None, faults=(), choices=None):
    """tokens (T,) -> logits (T, vocab) float32. ``choices``: {full
    layer: its selection (T, k)}, see :func:`layer_forward`."""
    x = jnp.asarray(params["embed"], jnp.float32)[tokens]
    chosen = None
    for i in range(len(params["layers"])):
        x, chosen, _info = layer_forward(
            params["layers"], i, x, chosen, model, quant, faults,
            choice=(choices or {}).get(i))
    return _head_jit(params["out_norm"], params["lm_head"], x,
                     model["rms_norm_eps"], quant)

# -- end of the forward pass ------------------------------------------------
