"""Plain reference of the hybrid described layer by layer: state-space
mixers (Mamba-2) beside GQA attention WITHOUT any position signal, every
layer followed by the same expert block with a shared expert
(Granite-4.0-H's block, ``model_type`` "granitemoehybrid"). Straightforward
``jax.numpy`` in float32 at ``highest`` matmul precision, a full forward
with no cache, no kernel, no batching and no chunked form: the recurrence
is a plain ``lax.scan`` over the tokens, the attention a mask over the full
scores (a block of query rows at a time), the experts a Python loop.
Nothing of the program is imported; ``model`` is the configuration's dict
(the source's key names) and ``params`` the tree ``init_transformer``
makes. Used by tests/test_ssm_moe.py; the benchmark keeps a copy of the
part between the two markers (benchmark/references/ssm_moe_lm.py, held
equal by benchmark/tests/test_ssm_moe.py).

The model, ``u = rmsnorm(x)`` (every norm an RMSNorm with a gain and
``rms_norm_eps``)::

    x_0 = embedding_multiplier x E[token]
    x <- x + residual_multiplier x mixer(rmsnorm(x))
    x <- x + residual_multiplier x (experts(h) + shared(h)),  h = rmsnorm(x)
    logits = rmsnorm(x_L) E^T / logits_scaling                (tied head)

A ``mamba`` mixer: ``[z | xBC | dt] = u W_in``; ``xBC_t <- silu(b + sum_j
w[:, j] xBC_{t-3+j})``, a causal depthwise convolution of width
``mamba_d_conv`` with zeros before the first token; split into ``x_t``
(heads x head size), ``B_t`` and ``C_t`` (``mamba_d_state`` each, shared by
all heads: one group); ``delta_t = softplus(dt_t + dt_bias)`` a head, ``A =
-exp(A_log)`` a head; ``H_t = exp(delta_t A) H_{t-1} + delta_t x_t (x) B_t``;
``y_t = H_t C_t + D x_t``; ``y <- rmsnorm(y * silu(z)) w`` (the gate BEFORE
the norm); ``out = y W_out``. An ``attention`` mixer: ``q, k, v = u W_q, u
W_k, u W_v``, NO rotary phase, scores ``q . k x attention_multiplier``,
causal softmax, ``W_o``. The expert block: logits ``h W_r`` in float32 over
all the router's outputs; the token takes the ``num_experts_per_tok``
largest and weighs them by a softmax over THOSE (the softmax over all,
renormalised over the picked); an expert is ``W_2 (silu(h W_1) * (h
W_3))``; the shared expert the same at its width, every token, weight 1.
This chip's share is the sum over the picked experts it HOLDS
(``experts_held``) plus the shared expert; what the absent experts would
add is left out, here as in the program.

``quant="fp8"`` is a control, the precision below bf16: every weight
matmul's operands rounded to e4m3 under an absmax scale a token and an
output channel, sums in float32 (the router stays in float32).
``quant="bf16_state"`` is the other: everything in float32 but the
recurrent state, which is rounded to bfloat16 after every token (the
configuration states a float32 state). ``FAULTS`` are departures a
comparison has to see, each a switch of its own; those of the served
path's bookkeeping (a lane's state at admission, padding, chunk
boundaries) need to know where the prompt ends and how the engine cuts it:
``prompt_len`` and the ``engine`` block of ``model``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

# -- the forward pass (benchmark/references/ssm_moe_lm.py keeps a copy) ----

FAULTS = (
    "stale_state",        # the lane's state not zeroed at admission: the
                          # scan starts from what the same tokens left
    "padding_advances",   # bucket / last-chunk padding advances the state
    "state_not_carried",  # the state starts from zeros at a chunk boundary
    "tail_dropped",       # the convolution's tail dropped at such a boundary
    "no_d",               # D * x left out
    "norm_before_gate",   # rmsnorm(y) * silu(z) in the place of
                          # rmsnorm(y * silu(z))
    "no_embed_mult",      # embedding_multiplier left out
    "no_residual_mult",   # residual_multiplier left out
    "no_logit_scale",     # logits_scaling left out
    "no_attn_mult",       # head_dim ** -0.5 in attention_multiplier's place
    "rope",               # rotary phases applied in the attention layers
    "no_renorm",          # the picked scores not renormalised
    "no_shared",          # the shared expert left out
    "half_held",          # the second half of the held experts left out
    "no_held",            # the held experts left out
)
# which faults change which jitted piece (a piece compiles once a set)
SSM_FAULTS = frozenset({"stale_state", "no_d", "norm_before_gate"})
ATTENTION_FAULTS = frozenset({"no_attn_mult", "rope"})
ROUTE_FAULTS = frozenset({"no_renorm"})
Q_BLOCK = 256        # query rows whose scores are alive at once
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
    elif quant not in (None, "bf16_state"):
        raise ValueError(f"unknown control precision {quant!r}")
    return jnp.matmul(x, w, precision=_HI)


def _rmsnorm(x, gain, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * jnp.asarray(gain, jnp.float32)


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


def held_of(model) -> tuple:
    return tuple(model.get("experts_held", (0, model["num_local_experts"])))


def ssm_mixer(p, u, model, cuts, snap_at, quant=None, faults=()):
    """A state-space mixer over its normed input u (T, D): (the mixer's
    output (T, D), the state after token ``snap_at - 1`` (heads, head size,
    state) float32). ``cuts``: two (T,) int32, the first position whose
    inputs a token's convolution may see, and the position at which the
    state that reaches the token started from zeros (both 0 everywhere in
    a sound forward; a chunk's first position where a fault cuts the
    sequence there)."""
    t = u.shape[0]
    heads, hd = model["mamba_n_heads"], model["mamba_d_head"]
    n, width = model["mamba_d_state"], model["mamba_d_conv"]
    inner = heads * hd
    proj = _mm(u, p["w_in"], quant)
    z, xbc, dt = (proj[:, :inner], proj[:, inner:inner + inner + 2 * n],
                  proj[:, inner + inner + 2 * n:])
    seq = jnp.pad(xbc, ((width - 1, 0), (0, 0)))
    at = jnp.arange(t)
    cut_tail, cut_state = cuts
    conv = jnp.asarray(p["conv_b"], jnp.float32)[None, :]
    for j in range(width):
        tap = seq[j:j + t]
        seen = (at - (width - 1) + j >= cut_tail)[:, None]
        conv = conv + jnp.where(seen, tap, 0.0) * jnp.asarray(
            p["conv_w"], jnp.float32)[None, :, j]
    xbc = jax.nn.silu(conv)
    x = xbc[:, :inner].reshape(t, heads, hd)
    bm, cm = xbc[:, inner:inner + n], xbc[:, inner + n:]
    a = -jnp.exp(jnp.asarray(p["a_log"], jnp.float32))
    delta = jax.nn.softplus(dt + jnp.asarray(p["dt_bias"], jnp.float32))
    reset = (cut_state == at) & (at > 0)

    def step(carry, xs):
        h, snap = carry
        x_t, b_t, c_t, d_t, reset_t, i = xs
        h = jnp.where(reset_t, 0.0, h)
        h = jnp.exp(d_t * a)[:, None, None] * h \
            + (d_t[:, None] * x_t)[:, :, None] * b_t[None, None, :]
        if quant == "bf16_state":
            # reduce_precision and not a cast there and back: the TPU's
            # compiler is allowed excess precision and drops such a pair
            h = jax.lax.reduce_precision(h, exponent_bits=8,
                                         mantissa_bits=7)
        snap = jnp.where(i == snap_at - 1, h, snap)
        return (h, snap), jnp.einsum("hpn,n->hp", h, c_t, precision=_HI)

    zeros = jnp.zeros((heads, hd, n), jnp.float32)
    xs = (x, bm, cm, delta, reset, at)
    if "stale_state" in faults:
        (zeros, _), _ = jax.lax.scan(step, (zeros, zeros), xs)
    (_, snap), y = jax.lax.scan(step, (zeros, zeros), xs)
    if "no_d" not in faults:
        y = y + jnp.asarray(p["d"], jnp.float32)[:, None] * x
    y = y.reshape(t, inner)
    if "norm_before_gate" in faults:
        y = _rmsnorm(y, p["norm"], model["rms_norm_eps"]) * jax.nn.silu(z)
    else:
        y = _rmsnorm(y * jax.nn.silu(z), p["norm"], model["rms_norm_eps"])
    return _mm(y, p["w_out"], quant), snap


def attention(p, u, model, real, quant=None, faults=()):
    """GQA over the normed input u (T, D) with no position signal: token t
    attends every position at or before its own, as a mask over the full
    scores, a block of query rows at a time. ``real`` (T,) bool: a key
    that is not real (planted padding) is seen by no real query."""
    t = u.shape[0]
    heads, kvh = model["num_attention_heads"], model["num_key_value_heads"]
    hd = model["hidden_size"] // heads
    scale = (hd ** -0.5 if "no_attn_mult" in faults
             else model["attention_multiplier"])
    q = _mm(u, p["wq"], quant).reshape(t, heads, hd)
    k = _mm(u, p["wk"], quant).reshape(t, kvh, hd)
    v = _mm(u, p["wv"], quant).reshape(t, kvh, hd)
    if "rope" in faults:
        q, k = (_rope(q, float(model.get("rope_theta", 10000))),
                _rope(k, float(model.get("rope_theta", 10000))))
    k = jnp.repeat(k, heads // kvh, axis=1)
    v = jnp.repeat(v, heads // kvh, axis=1)
    qb = min(Q_BLOCK, t)
    blocks = -(-t // qb)
    pad = blocks * qb - t
    at = jnp.arange(t)

    def block(args):
        rows, q_b, real_b = args
        s = jnp.einsum("qhd,khd->hqk", q_b, k, precision=_HI) * scale
        mask = (at[None, :] <= rows[:, None]) \
            & (real[None, :] | ~real_b[:, None])
        w = jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", w, v,
                          precision=_HI).reshape(qb, heads * hd)
    out = jax.lax.map(block, (
        jnp.arange(blocks * qb).reshape(blocks, qb),
        jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(blocks, qb, heads, hd),
        jnp.pad(real, (0, pad)).reshape(blocks, qb)))
    return _mm(out.reshape(blocks * qb, heads * hd)[:t], p["wo"], quant)


def swiglu(w1, w3, w2, h, quant=None):
    return _mm(jax.nn.silu(_mm(h, w1, quant)) * _mm(h, w3, quant), w2, quant)


def route(moe, h, model, faults=()):
    """-> (pick (T, k) int32, weight (T, k) float32): the router in
    float32 whatever the control; a softmax over the picked."""
    logits = _mm(h, moe["router"])
    scores = jax.nn.softmax(logits, axis=-1)
    _, pick = jax.lax.top_k(scores + jnp.asarray(moe["bias"], jnp.float32),
                            model["num_experts_per_tok"])
    weight = jnp.take_along_axis(scores, pick, -1)
    if "no_renorm" not in faults:
        weight = weight / (weight.sum(-1, keepdims=True) + 1e-20)
    return pick, weight


@functools.partial(jax.jit, static_argnames=("model_t", "quant", "faults"))
def _ssm_jit(p, ln, x, start, snap_at, model_t, quant, faults):
    model = dict(model_t)
    u = _rmsnorm(x, ln, model["rms_norm_eps"])
    return ssm_mixer(p, u, model, start, snap_at, quant, faults)


@functools.partial(jax.jit, static_argnames=("model_t", "quant", "faults"))
def _attention_jit(p, ln, x, real, model_t, quant, faults):
    model = dict(model_t)
    u = _rmsnorm(x, ln, model["rms_norm_eps"])
    return attention(p, u, model, real, quant, faults)


@functools.partial(jax.jit, static_argnames=("quant",))
def _swiglu_jit(w1, w3, w2, h, quant):
    return swiglu(w1, w3, w2, h, quant)


@functools.partial(jax.jit, static_argnames=("model_t", "faults"))
def _route_jit(moe_router, h, model_t, faults):
    return route(moe_router, h, dict(model_t), faults)


@functools.partial(jax.jit, static_argnames=("eps",))
def _norm_jit(x, gain, eps):
    return _rmsnorm(x, gain, eps)


def moe(layer_moe, h, model, quant=None, faults=()):
    """This chip's share of the expert block for h (T, D): (the held
    experts' part, the shared expert's part), one expert at a time."""
    offset, count = held_of(model)
    pick, weight = _route_jit(
        {"router": layer_moe["router"], "bias": layer_moe["bias"]}, h,
        _freeze(model), tuple(sorted(ROUTE_FAULTS & set(faults))))
    held = count // 2 if "half_held" in faults else count
    if "no_held" in faults:
        held = 0
    part = jnp.zeros_like(h)
    for e in range(held):
        w = jnp.where(pick == offset + e, weight, 0.0).sum(-1, keepdims=True)
        part = part + w * _swiglu_jit(
            layer_moe["we1"][e], layer_moe["we3"][e], layer_moe["we2"][e],
            h, quant)
    shared = jnp.zeros_like(h)
    if "ws1" in layer_moe and "no_shared" not in faults:
        shared = _swiglu_jit(layer_moe["ws1"], layer_moe["ws3"],
                             layer_moe["ws2"], h, quant)
    return part, shared


def _cuts(model, t, prompt_len, faults):
    """(tokens, ...) bookkeeping of the served path's faults: where a
    prompt of ``prompt_len`` tokens is cut by the engine (one bucket, or
    chunks of ``prefill_chunk`` where it is longer than the largest), as
    (padding planted after the prompt, start (T',) of the convolution,
    start (T',) of the state). A sound forward has no padding and both
    starts 0."""
    eng = model.get("engine", {})
    buckets = tuple(eng.get("prefill_buckets", ()))
    chunk = int(eng.get("prefill_chunk", 0))
    n = prompt_len
    if n is None:
        length = t
    elif chunk and n > (buckets[-1] if buckets else chunk):
        length = chunk
    else:
        length = next((b for b in buckets if b >= n), n)
    pad = 0
    if "padding_advances" in faults and n is not None:
        pad = -(-n // length) * length - n
    at = np.arange(t + pad)
    bound = np.zeros((t + pad,), np.int32)
    if n is not None:
        # a token of the prompt sees its chunk's first position; what comes
        # after the prompt sees the last chunk's
        bound = np.minimum(at, n - 1) // length * length
    zeros = np.zeros_like(bound)
    return (pad,
            jnp.asarray(bound if "tail_dropped" in faults else zeros),
            jnp.asarray(bound if "state_not_carried" in faults else zeros))


def forward(params, tokens, model, quant=None, faults=(), prompt_len=None,
            snap_at=None, each=None, rows=None):
    """tokens (T,) -> (logits (T, vocab) float32, the state-space layers'
    states after token ``snap_at - 1`` (layers, heads, head size, state)
    float32; after the last token by default). ``prompt_len``: where the
    prompt ends, for the faults of the served path's bookkeeping.
    ``each(i, info)`` sees every layer's record: its expert block's input
    ``h_moe`` and the held and shared parts. ``rows`` = (lo, n): the head
    is applied to positions lo .. lo + n alone (n, vocab)."""
    faults = tuple(faults)
    tokens = jnp.asarray(tokens, jnp.int32)
    t = tokens.shape[0]
    model_t, eps = _freeze(model), model["rms_norm_eps"]
    pad, cut_tail, cut_state = _cuts(model, t, prompt_len, faults)
    real = np.ones((t + pad,), bool)
    if pad:
        real[prompt_len:prompt_len + pad] = False
        tokens = jnp.concatenate([tokens[:prompt_len],
                                  jnp.zeros((pad,), jnp.int32),
                                  tokens[prompt_len:]])
    snap_at = t if snap_at is None else snap_at
    if pad and snap_at > prompt_len:
        snap_at += pad
    x = jnp.asarray(params["embed"], jnp.float32)[tokens]
    if "no_embed_mult" not in faults:
        x = x * float(model["embedding_multiplier"])
    res = 1.0 if "no_residual_mult" in faults \
        else float(model["residual_multiplier"])
    states = []
    for i, layer in enumerate(params["layers"]):
        if "ssm" in layer:
            out, snap = _ssm_jit(
                layer["ssm"], layer["ln1"], x, (cut_tail, cut_state),
                snap_at, model_t, quant,
                tuple(sorted(SSM_FAULTS & set(faults))))
            states.append(snap)
        else:
            out = _attention_jit(
                {k: layer[k] for k in ("wq", "wk", "wv", "wo")},
                layer["ln1"], x, jnp.asarray(real), model_t, quant,
                tuple(sorted(ATTENTION_FAULTS & set(faults))))
        x = x + res * out
        h = _norm_jit(x, layer["ln2"], eps)
        part, shared = moe(layer["moe"], h, model, quant, faults)
        if each is not None:
            each(i, {"h_moe": h, "part": part, "shared": shared})
        x = x + res * (part + shared)
    x = x[real]
    if rows is not None:
        x = x[rows[0]:rows[0] + rows[1]]
    logits = _head_jit(params["out_norm"], params["embed"], x, eps, quant)
    if "no_logit_scale" not in faults:
        logits = logits / float(model["logits_scaling"])
    return logits, jnp.stack(states)


@functools.partial(jax.jit, static_argnames=("eps", "quant"))
def _head_jit(out_norm, embed, x, eps, quant):
    return _mm(_rmsnorm(x, out_norm, eps),
               jnp.asarray(embed, jnp.float32).T, quant)

# -- end of the forward pass ------------------------------------------------
