"""Plain reference for a decoder of shortcut-connected double layers with
multi-head latent attention and a share of a zero-computation-expert MoE
(LongCat-Flash's block): straightforward ``jax.numpy`` in float32 at
``highest`` matmul precision, full softmax attention over expanded keys and
values, a Python loop over the experts, no cache, no kernel, and nothing
imported from the program. A piece (one attention, one FFN, one expert) is
widened to float32 at a time, so the 20.7 GB that the held weights would
take in float32 never sit on the chip at once.

Per double layer, input ``x``, every norm an RMSNorm with a gain and the
configuration's ``rms_norm_eps``::

    x1 = x  + MLA_0(norm(x))
    h1 = norm(x1)
    m  = MoE(h1)                 # the shortcut: read here, added at the end
    x2 = x1 + FFN_0(h1)          # FFN(h) = (silu(h Wg) * (h Wu)) Wd
    x3 = x2 + MLA_1(norm(x2))
    x4 = x3 + FFN_1(norm(x3)) + m

``MLA``: ``c_q = norm(h W_qa)``; ``q = (c_q W_qb) * s_q``, heads x (nope +
rope); ``[c_kv, k_r] = h W_kva``; ``c_kv = norm(c_kv) * s_kv``; RoPE
(half-split pairing) on q's rope part and on ``k_r``, which all heads
share; ``[k_nope, v] = c_kv W_kvb``; scores ``(q_nope . k_nope + q_rope .
k_r) / (nope + rope) ** 0.5``, causal softmax, ``concat(P v) W_o``. The two
scales are ``(hidden / rank) ** 0.5``.

``MoE``: scores are a float32 softmax of ``h W_r`` over all the router's
outputs (the experts with weights, then the identity experts); the
``moe_topk`` largest of score + bias are picked; each weighs
``routed_scaling_factor`` x its score, not renormalised. This chip's share
is the sum over the picked experts it HOLDS (``n_routed_experts`` of them,
from ``experts_held[0]``) of weight x SwiGLU expert, plus (the sum of the
picked identity experts' weights) x h. What the experts held elsewhere
would add is left out, here as in the program.

Entries: :func:`served_numbers` (what the runner compares) and
:func:`served_gaps` with ``decoder_lm``'s signature. ``control="fp8"`` is
the precision below the configuration's bf16: every weight matmul's
operands rounded to e4m3 under an absmax scale a token and an output
channel, sums in float32 (the router stays in float32, as fp8 recipes keep
it). ``FAULTS`` are departures the comparison has to see.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

FAULTS = ("no_held", "no_identity", "no_scale", "no_kv_scale", "renorm")
HEAD_GROUP = 16      # heads whose scores are alive at once
PAD = 256            # a sample is padded to a multiple of this
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


def _rope(x, theta):
    # x (T, H, D); pairs are (x[i], x[i + D/2])
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


def mla(p, x, model, quant=None, kv_scale=True):
    """One latent attention over x (T, D): its output through ``wo``."""
    t, d = x.shape
    heads = model["num_attention_heads"]
    rank, q_rank = model["kv_lora_rank"], model["q_lora_rank"]
    nope, rope_d = model["qk_nope_head_dim"], model["qk_rope_head_dim"]
    vd, eps = model["v_head_dim"], model["rms_norm_eps"]
    theta = float(model["rope_theta"])
    s_q = (d / q_rank) ** 0.5
    s_kv = (d / rank) ** 0.5 if kv_scale else 1.0
    h = _rmsnorm(x, p["ln"], eps)
    c_q = _rmsnorm(_mm(h, p["wq_a"], quant), p["q_norm"], eps)
    q = (_mm(c_q, p["wq_b"], quant) * s_q).reshape(t, heads, nope + rope_d)
    down = _mm(h, p["wkv_a"], quant)
    c_kv = _rmsnorm(down[:, :rank], p["kv_norm"], eps) * s_kv
    k_rope = _rope(down[:, None, rank:], theta)
    q_rope = _rope(q[..., nope:], theta)
    up = _mm(c_kv, p["wkv_b"], quant).reshape(t, heads, nope + vd)
    k = jnp.concatenate(
        [up[..., :nope], jnp.broadcast_to(k_rope, (t, heads, rope_d))], -1)
    qf = jnp.concatenate([q[..., :nope], q_rope], -1)
    causal = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]

    def group(qkv):                      # a group of heads at a time
        qg, kg, vg = qkv
        s = jnp.einsum("qhd,khd->hqk", qg, kg, precision=_HI) \
            * (nope + rope_d) ** -0.5
        w = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", w, vg, precision=_HI)
    g = HEAD_GROUP if heads % HEAD_GROUP == 0 else heads
    split = lambda a: jnp.moveaxis(  # noqa: E731
        a.reshape(t, heads // g, g, a.shape[-1]), 1, 0)
    o = jax.lax.map(group, (split(qf), split(k), split(up[..., nope:])))
    o = jnp.moveaxis(o, 0, 1).reshape(t, heads * vd)
    return _mm(o, p["wo"], quant)


def swiglu(w1, w3, w2, h, quant=None):
    return _mm(jax.nn.silu(_mm(h, w1, quant)) * _mm(h, w3, quant), w2, quant)


def route(moe, h, model):
    """-> (pick (T, k) int32, weight (T, k) float32): the router in
    float32 whatever the control."""
    scores = jax.nn.softmax(_mm(h, moe["router"]), axis=-1)
    _, pick = jax.lax.top_k(scores + jnp.asarray(moe["bias"], jnp.float32),
                            model["moe_topk"])
    weight = jnp.take_along_axis(scores, pick, -1) \
        * float(model["routed_scaling_factor"])
    return pick, weight


@functools.partial(jax.jit, static_argnames=("model_t", "quant", "kv_scale"))
def _mla_jit(p, x, model_t, quant, kv_scale):
    return mla(p, x, dict(model_t), quant, kv_scale)


@functools.partial(jax.jit, static_argnames=("quant",))
def _swiglu_jit(w1, w3, w2, h, quant):
    return swiglu(w1, w3, w2, h, quant)


@functools.partial(jax.jit, static_argnames=("model_t",))
def _route_jit(moe_router, h, model_t):
    return route(moe_router, h, dict(model_t))


def held_of(model) -> tuple:
    return tuple(model.get("experts_held", (0, model["n_routed_experts"])))


def n_real(model) -> int:
    return model.get("published", {}).get("n_routed_experts",
                                          model["n_routed_experts"])


def moe(layer_moe, h, model, quant=None, faults=()):
    """This chip's share of the expert layer for h (T, D): (the held
    experts' part, the identity part, counts), one expert at a time."""
    offset, count = held_of(model)
    pick, weight = _route_jit(
        {"router": layer_moe["router"], "bias": layer_moe["bias"]}, h,
        _freeze(model))
    scale = float(model["routed_scaling_factor"])
    if "no_scale" in faults:
        weight = weight / scale
    if "renorm" in faults:
        weight = weight / weight.sum(-1, keepdims=True) * scale
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
    on_identity = pick >= n_real(model)
    identity = jnp.where(on_identity, weight, 0.0).sum(-1, keepdims=True) * h
    if "no_held" in faults:
        part = jnp.zeros_like(part)
    if "no_identity" in faults:
        identity = jnp.zeros_like(identity)
    counts = {"held": on_held.sum(-1), "identity": on_identity.sum(-1),
              "touched": touched}
    return part, identity, counts


def double_layer(layer, x, model, quant=None, faults=()):
    """x (T, D) -> (x, the expert layer's input h1, its held experts'
    part, its identity part)."""
    model_t, eps = _freeze(model), model["rms_norm_eps"]
    kv_scale = "no_kv_scale" not in faults

    def ffn(p, h):
        return _swiglu_jit(p["w1"], p["w3"], p["w2"], h, quant)
    x = x + _mla_jit(layer["mla"][0], x, model_t, quant, kv_scale)
    h1 = _rmsnorm(x, layer["ffn"][0]["ln"], eps)
    part, identity, _counts = moe(layer["moe"], h1, model, quant, faults)
    x = x + ffn(layer["ffn"][0], h1)
    x = x + _mla_jit(layer["mla"][1], x, model_t, quant, kv_scale)
    h = _rmsnorm(x, layer["ffn"][1]["ln"], eps)
    return x + ffn(layer["ffn"][1], h) + part + identity, h1, part, identity


@functools.partial(jax.jit, static_argnames=("eps", "quant"))
def _head_jit(out_norm, lm_head, x, eps, quant):
    return _mm(_rmsnorm(x, out_norm, eps), lm_head, quant)


def forward(params, tokens, model, quant=None, faults=()):
    """tokens (T,) -> logits (T, vocab) float32."""
    x = jnp.asarray(params["embed"], jnp.float32)[tokens]
    for layer in params["layers"]:
        x = double_layer(layer, x, model, quant, faults)[0]
    return _head_jit(params["out_norm"], params["lm_head"], x,
                     model["rms_norm_eps"], quant)


# -- a served model ------------------------------------------------------

def _stand_in_args(name):
    """A stand-in's (quant, faults): the control or one planted fault."""
    if name == "control":
        raise ValueError("name the control by its precision")
    if name.startswith("fault."):
        fault = name[len("fault."):]
        if fault not in FAULTS:
            raise ValueError(f"unknown fault {fault!r}; have {FAULTS}")
        return None, (fault,)
    return name, ()


class _Sums:
    """Squared norms of a difference and of what it is a difference from,
    over every position checked: their ratio's root is the gap."""

    def __init__(self):
        self.num = self.den = 0.0

    def add(self, got, want, mask):
        m = jnp.asarray(mask)[:, None]
        self.num += float(jnp.sum(jnp.where(m, jnp.square(got - want), 0.0)))
        self.den += float(jnp.sum(jnp.where(m, jnp.square(want), 0.0)))

    @property
    def gap(self):
        return (self.num / self.den) ** 0.5 if self.den > 0 else None


class _HeldPart:
    """How much of the held experts' part some logits carry. With ``want``
    the reference's logits, ``without`` the reference's with the held
    experts left out and ``d = want - without``: the projection of ``got -
    without`` on ``d`` over ``d . d``, summed over every position checked,
    is 1 where the part is carried whole and 0 where it is left out; the
    gap is its distance from 1. Rounding that is not aligned with ``d``
    averages out of the projection, which is how this number sees a part
    that is smaller than the rounding of the logits themselves."""

    def __init__(self):
        self.num = self.den = 0.0

    def add(self, got, without, want):
        d = want - without
        self.num += float(jnp.sum((got - without) * d))
        self.den += float(jnp.sum(jnp.square(d)))

    @property
    def gap(self):
        return abs(1.0 - self.num / self.den) if self.den > 0 else None


NO_HELD = "fault.no_held"


def served_numbers(params, model, samples, pad_to, stand_ins=(),
                   program_moe=None, program_logits=None) -> dict:
    """``samples``: list of (prompt, served) token tuples. One forward a
    sample over prompt + served, padded to the next multiple of ``PAD`` (at
    most ``pad_to``; causal, so padding after the end changes nothing
    before it; a few lengths, so a few compiled shapes). Returns a dict of dicts of
    numbers, all over the served positions of all samples:

    * ``"program"``: ``served_gap``, the widest gap by which a served
      token's logit lies below the reference's best; and, where
      ``program_moe(layer_index, h) -> (whole, held part)`` hands over the
      program's own expert layer (run on the reference's expert-layer
      inputs, rounded to the configuration's precision), ``moe_out_gap``
      and ``expert_out_gap``: the norm of its difference from the
      reference's output on that same input over the norm of that output,
      for the whole layer and for the held experts' part alone. The logit
      gap cannot see the held experts, which carry 1/32 of the routed
      mass; these two can, but they are of the layer's function under a
      jit of the check's own, not of what the served path compiled. Where
      ``program_logits`` hands over, a sample, the rows of logits (k,
      vocab) that the served path's own programs picked the sample's
      first k tokens from: ``held_part_gap`` (:class:`_HeldPart`) over
      those positions, the one number that holds the served path to its
      held experts.
    * one entry a stand-in (``"fp8"``: the control; ``"fault.<name>"``):
      the same numbers of a reference forward in that precision or
      with that fault in the program's place: the gap of ITS greedy pick
      below the reference's best, its expert layers' outputs (in its
      own forward) against the reference's, and its logits' share of the
      held part over the same positions.
    """
    eps = model["rms_norm_eps"]
    names = ("program",) + tuple(stand_ins)
    gaps = {n: 0.0 for n in names}
    sums = {n: (_Sums(), _Sums()) for n in names}
    carried = {n: _HeldPart() for n in names}
    # the forward with the held experts left out is what the held part is
    # measured from: run it whenever that number is read
    run_also = tuple(stand_ins)
    if (program_logits is not None or stand_ins) and NO_HELD not in run_also:
        run_also += (NO_HELD,)
    for si, (prompt, served) in enumerate(samples):
        full = tuple(prompt) + tuple(served)
        padded = min(pad_to, -(-len(full) // PAD) * PAD)
        row = np.zeros((padded,), np.int32)
        row[:len(full)] = full
        mask = np.zeros((padded,), bool)
        # position p predicts token p+1: served tokens sit at
        # len(prompt) .. len(full)-1, predicted from one before
        mask[len(prompt) - 1:len(full) - 1] = True
        tokens = jnp.asarray(row)
        x0 = jnp.asarray(params["embed"], jnp.float32)[tokens]
        streams = {n: x0 for n in run_also}
        x = x0
        for li, layer in enumerate(params["layers"]):
            x, h1, part, identity = double_layer(layer, x, model)
            if program_moe is not None:
                # the same input for both: the reference's, as the
                # program's precision holds it
                h_low = h1.astype(jnp.bfloat16)
                want_part, want_id, _c = moe(
                    layer["moe"], h_low.astype(jnp.float32), model)
                whole, held = program_moe(li, h_low)
                sums["program"][0].add(jnp.asarray(whole, jnp.float32),
                                       want_part + want_id, mask)
                sums["program"][1].add(jnp.asarray(held, jnp.float32),
                                       want_part, mask)
            for n in run_also:
                quant, faults = _stand_in_args(n)
                streams[n], _h, s_part, s_id = double_layer(
                    layer, streams[n], model, quant, faults)
                if n in sums:
                    sums[n][0].add(s_part + s_id, part + identity, mask)
                    sums[n][1].add(s_part, part, mask)
        ref = _head_jit(params["out_norm"], params["lm_head"], x, eps, None)
        best = ref.max(axis=-1)
        nxt = jnp.roll(tokens, -1)
        served_gap = best - jnp.take_along_axis(ref, nxt[:, None], -1)[:, 0]
        gaps["program"] = max(gaps["program"],
                              float(np.asarray(served_gap)[mask].max()))
        # the positions the held part is read over: the served path's
        # rows where it handed them over, else every served position
        lo, k = len(prompt) - 1, len(served)
        if program_logits is not None:
            k = len(program_logits[si])
        at = slice(lo, lo + k)
        if run_also:
            without = _head_jit(params["out_norm"], params["lm_head"],
                                streams[NO_HELD], eps, None)[at]
        if program_logits is not None and k:
            carried["program"].add(
                jnp.asarray(program_logits[si], jnp.float32), without,
                ref[at])
        for n in stand_ins:
            quant, _f = _stand_in_args(n)
            low = _head_jit(params["out_norm"], params["lm_head"],
                            streams[n], eps, quant)
            pick = jnp.argmax(low, axis=-1)
            gap = best - jnp.take_along_axis(ref, pick[:, None], -1)[:, 0]
            gaps[n] = max(gaps[n], float(np.asarray(gap)[mask].max()))
            carried[n].add(low[at], without, ref[at])
    out = {}
    for n in names:
        out[n] = {"served_gap": gaps[n]}
        if n != "program" or program_moe is not None:
            out[n]["moe_out_gap"] = sums[n][0].gap
            out[n]["expert_out_gap"] = sums[n][1].gap
        if n != "program" or program_logits is not None:
            out[n]["held_part_gap"] = carried[n].gap
    return out


def served_gaps(params, model, samples, pad_to, control=None):
    """``decoder_lm.served_gaps``'s signature: ``(served_gap,
    control_gap)``; ``control_gap`` is None unless ``control`` names a
    lower precision."""
    got = served_numbers(params, model, samples, pad_to,
                         stand_ins=(control,) if control else ())
    return (got["program"]["served_gap"],
            got[control]["served_gap"] if control else None)
