"""Plain reference for a pre-norm decoder LM: RMSNorm, rotary positions
(half-split pairing), grouped-query causal attention with an optional
sliding window, SwiGLU, untied head. Straightforward ``jax.numpy`` in
float32 at ``highest`` matmul precision: no kernel, no cache, no batching
tricks, and nothing imported from the program.

Used for every configuration whose file says ``"reference": "decoder_lm"``
(InternLM2 and Mistral publish exactly this block). Three entries:

* :func:`served_gaps` - a served model: one full forward over each sampled
  prompt with the tokens the timed path served; for every served token the
  gap by which its logit lies below the reference's best.
* :func:`train_follow` - training: follows the first steps from the same
  weights and rows (loss, first gradient, AdamW update), row by row so it
  fits beside its own optimizer state.
* ``quant="fp8"`` on either - the control: the same mathematics with every
  weight matmul run as an fp8 recipe runs it (operands rounded to e4m3, the
  backward's incoming gradient to e5m2, each under an absmax scale a token
  or an output channel; sums in float32), the precision below the bf16
  the configurations state.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def _round_fp8(x, axis, dtype):
    """Round to a float8 type under an absmax scale along ``axis``."""
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / float(
        jnp.finfo(dtype).max)
    scale = jnp.where(scale > 0, scale, 1.0)
    return (x / scale).astype(dtype).astype(jnp.float32) * scale


def _hi(a, b):
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


@jax.custom_vjp
def _mm_fp8(x, w):
    """A matmul as an fp8 recipe runs it: operands rounded to e4m3 (a scale
    a token, a scale an output channel), sums in float32; in the backward
    pass the incoming gradient is rounded to e5m2 (a scale a token) and
    meets the same rounded operands."""
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


def _mm(x, w, quant):
    if quant == "fp8":
        return _mm_fp8(x, w)
    if quant is not None:
        raise ValueError(f"unknown control precision {quant!r}")
    return _hi(x, w)


def _rmsnorm(x, gain, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * gain


def _rope(x, theta):
    # x (B, T, H, D); pairs are (x[i], x[i + D/2])
    t, half = x.shape[1], x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], axis=-1)


def _attention(q, k, v, window):
    # q (B,T,H,D), k/v (B,T,Hkv,D): every query head reads its group's k/v
    b, t, h, d = q.shape
    g = h // k.shape[2]
    k = jnp.repeat(k, g, axis=2)
    v = jnp.repeat(v, g, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   precision=jax.lax.Precision.HIGHEST) * d ** -0.5
    qi, ki = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    ok = ki <= qi
    if window is not None:
        ok &= ki > qi - window
    p = jax.nn.softmax(jnp.where(ok[None, None], s, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v,
                      precision=jax.lax.Precision.HIGHEST)


def layer_forward(layer, x, model, quant=None):
    f32 = functools.partial(jnp.asarray, dtype=jnp.float32)
    b, t, _ = x.shape
    hd = model.get("head_dim",
                   model["hidden_size"] // model["num_attention_heads"])
    eps, theta = model["rms_norm_eps"], model["rope_theta"]
    h = _rmsnorm(x, f32(layer["ln1"]), eps)
    q = _mm(h, f32(layer["wq"]), quant).reshape(b, t, -1, hd)
    k = _mm(h, f32(layer["wk"]), quant).reshape(b, t, -1, hd)
    v = _mm(h, f32(layer["wv"]), quant).reshape(b, t, -1, hd)
    a = _attention(_rope(q, theta), _rope(k, theta), v,
                   model.get("sliding_window"))
    x = x + _mm(a.reshape(b, t, -1), f32(layer["wo"]), quant)
    h = _rmsnorm(x, f32(layer["ln2"]), eps)
    gate = jax.nn.silu(_mm(h, f32(layer["w1"]), quant))
    up = _mm(h, f32(layer["w3"]), quant)
    return x + _mm(gate * up, f32(layer["w2"]), quant)


def head_forward(params, x, model, quant=None):
    h = _rmsnorm(x, jnp.asarray(params["out_norm"], jnp.float32),
                 model["rms_norm_eps"])
    return _mm(h, jnp.asarray(params["lm_head"], jnp.float32), quant)


# -- a served model ------------------------------------------------------

def _freeze(model):
    return tuple(sorted((k, v) for k, v in model.items()
                        if isinstance(v, (int, float, str, type(None)))))


@functools.partial(jax.jit, static_argnames=("model_t", "quant"))
def _layer_jit(layer, x, model_t, quant):
    return layer_forward(layer, x, dict(model_t), quant)


@functools.partial(jax.jit, static_argnames=("model_t", "quant"))
def _gaps_jit(params, x, x_low, tokens, model_t, quant):
    """Per position: the reference's best logit minus the logit of the
    token that follows (``served``), and minus the logit of the token the
    lower precision puts first (``control``; equal inputs when no control
    is asked for)."""
    model = dict(model_t)
    ref = head_forward(params, x, model)
    best = ref.max(axis=-1)
    nxt = jnp.roll(tokens, -1, axis=1)
    served = best - jnp.take_along_axis(ref, nxt[..., None], -1)[..., 0]
    low = head_forward(params, x_low, model, quant)
    pick = jnp.argmax(low, axis=-1)
    control = best - jnp.take_along_axis(ref, pick[..., None], -1)[..., 0]
    return served, control


def served_gaps(params, model, samples, pad_to, control=None):
    """``samples``: list of (prompt, served) token tuples. One forward over
    prompt + served, padded to ``pad_to`` (causal, so padding after the end
    changes nothing before it), layer by layer so that only one layer is
    ever widened to float32. Returns ``(served_gap, control_gap)``: the
    widest gap over all served positions of all samples; ``control_gap`` is
    None unless ``control`` names a lower precision."""
    model_t = _freeze(model)
    rows = np.zeros((len(samples), pad_to), np.int32)
    mask = np.zeros((len(samples), pad_to), bool)
    for i, (prompt, served) in enumerate(samples):
        full = tuple(prompt) + tuple(served)
        rows[i, :len(full)] = full
        # position p predicts token p+1: served tokens sit at
        # len(prompt) .. len(full)-1, predicted from one before
        mask[i, len(prompt) - 1:len(full) - 1] = True
    tokens = jnp.asarray(rows)
    x = jnp.asarray(params["embed"], jnp.float32)[tokens]
    x_low = x
    for layer in params["layers"]:
        if control is not None:
            x_low = _layer_jit(layer, x_low, model_t, control)
        x = _layer_jit(layer, x, model_t, None)
    served, ctl = _gaps_jit(params, x, x_low if control else x, tokens,
                            model_t, control)
    served = float(np.asarray(served)[mask].max())
    ctl = float(np.asarray(ctl)[mask].max()) if control else None
    return served, ctl


# -- training ------------------------------------------------------------

HEAD_CHUNK = 1024  # positions whose logits are alive at once


def _row_loss_sum(params, row, model, quant):
    """Summed next-token cross-entropy of one row. The head runs over
    HEAD_CHUNK positions at a time (recomputed in the backward pass), so a
    wide vocabulary's logits never exist for the whole row at once."""
    x = jnp.asarray(params["embed"], jnp.float32)[row[None]]
    step = jax.checkpoint(functools.partial(layer_forward, model=model,
                                            quant=quant))
    for layer in params["layers"]:
        x = step(layer, x)
    t = row.shape[0]
    chunk = HEAD_CHUNK if t % HEAD_CHUNK == 0 else t
    targets = jnp.roll(row, -1)
    weights = (jnp.arange(t) < t - 1).astype(jnp.float32)  # last: no target

    @jax.checkpoint
    def part(xs):
        xc, tc, wc = xs
        logp = jax.nn.log_softmax(head_forward(params, xc, model, quant), -1)
        return -(jnp.take_along_axis(logp, tc[:, None], -1)[:, 0] * wc).sum()
    pieces = jax.lax.map(part, (x[0].reshape(t // chunk, chunk, -1),
                                targets.reshape(-1, chunk),
                                weights.reshape(-1, chunk)))
    return pieces.sum()


@functools.partial(jax.jit, static_argnames=("model_t", "quant"),
                   donate_argnums=(1,))
def _accumulate(params, acc, row, scale, model_t, quant):
    loss, g = jax.value_and_grad(_row_loss_sum)(params, row, dict(model_t),
                                                quant)
    return loss * scale, jax.tree.map(lambda a, b: a + b * scale, acc, g)


@functools.partial(jax.jit, donate_argnums=(0, 1, 2))
def _adamw(params, m, v, g, t, lr, wd, b1=0.9, b2=0.999, eps=1e-8):
    def leaf(p, m_, v_, g_):
        m_ = b1 * m_ + (1 - b1) * g_
        v_ = b2 * v_ + (1 - b2) * g_ * g_
        upd = (m_ / (1 - b1 ** t)) / (jnp.sqrt(v_ / (1 - b2 ** t)) + eps)
        if p.ndim >= 2:  # decay matrices only, as the published recipes do
            upd = upd + wd * p
        return p - lr * upd, m_, v_
    out = jax.tree.map(leaf, params, m, v, g)
    pick = lambda i: jax.tree.map(lambda o: o[i], out,  # noqa: E731
                                  is_leaf=lambda o: isinstance(o, tuple))
    return pick(0), pick(1), pick(2)


@jax.jit
def leaf_norms(tree):
    return jax.tree.map(lambda x: jnp.sqrt(jnp.sum(jnp.square(
        x.astype(jnp.float32)))), tree)


@jax.jit
def leaf_delta_norms(new, old):
    return jax.tree.map(lambda a, b: jnp.sqrt(jnp.sum(jnp.square(
        a.astype(jnp.float32) - b.astype(jnp.float32)))), new, old)


def train_follow(make_start, batches, model, hp, quant=None,
                 rows_used=None, devices=None):
    """Follow ``len(batches)`` steps of mean next-token cross-entropy under
    AdamW from ``make_start()`` (the weights from the seed; made again at
    the end for the change, so no second copy is held meanwhile: beside
    the parameters there are the two moments and the gradient, 16 bytes a
    parameter, as the program has). ``batches``: arrays (rows, seq) of
    token ids, taken a row at a time. ``devices``: where a cell holds
    several chips, each takes every n-th row against its own copy of the
    parameters and the row gradients are added up on the first - plain
    data parallelism, so the check costs a quarter of the time.
    ``rows_used`` plants the fault "part of the batch left out, the mean
    taken over the rest". Returns ``losses`` (list), ``grad_norms`` (first
    step's per-leaf norms) and ``delta_norms`` (per-leaf norm of the
    parameters' change over all the steps), both as flat dicts keyed by
    leaf path."""
    model_t = _freeze(model)
    devices = list(devices or jax.devices()[:1])
    home = devices[0]
    params = jax.tree.map(lambda x: x.astype(jnp.float32), make_start())
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    losses, grad_norms = [], None
    for t, batch in enumerate(batches, start=1):
        rows = np.asarray(batch)
        if rows_used is not None:
            rows = rows[:rows_used]
        scale = np.float32(1.0 / (rows.shape[0] * (rows.shape[1] - 1)))
        copies = [params] + [jax.device_put(params, d) for d in devices[1:]]
        accs = [jax.device_put(jax.tree.map(jnp.zeros_like, params), d)
                for d in devices]
        parts = []
        for i, row in enumerate(rows):
            k = i % len(devices)
            part, accs[k] = _accumulate(
                copies[k], accs[k], jax.device_put(row, devices[k]),
                jax.device_put(scale, devices[k]), model_t, quant)
            parts.append(part)
        del copies
        acc = accs.pop(0)
        while accs:
            acc = _add(acc, jax.device_put(accs.pop(0), home))
        losses.append(float(sum(float(p) for p in parts)))
        if grad_norms is None:
            grad_norms = flat(leaf_norms(acc))
        params, m, v = _adamw(params, m, v, acc, jnp.float32(t),
                              jnp.float32(hp["learning_rate"]),
                              jnp.float32(hp["weight_decay"]))
        del acc
    del m, v
    return {"losses": losses, "grad_norms": grad_norms,
            "delta_norms": flat(leaf_delta_norms(params, make_start()))}


@functools.partial(jax.jit, donate_argnums=(0,))
def _add(a, b):
    return jax.tree.map(jnp.add, a, b)


def flat(tree) -> dict:
    """{'layers/0/wq': float, ...} from a tree of scalars."""
    leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {"/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                     for k in path): float(x) for path, x in leaves}
