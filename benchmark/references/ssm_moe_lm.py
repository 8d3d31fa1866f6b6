"""Plain reference for the hybrid described layer by layer: state-space
mixers (Mamba-2) beside GQA attention WITHOUT any position signal, every
layer followed by the same expert block with a shared expert
(Granite-4.0-H's block, ``model_type`` "granitemoehybrid"). Straightforward
``jax.numpy`` in float32 at ``highest`` matmul precision: the recurrence a
plain ``lax.scan`` over the tokens (no chunked form), the attention a mask
over the full scores, a block of query rows at a time; a Python loop over
the experts; no cache, no kernel, no batching, and nothing imported from
the program. A piece (one mixer, one expert) is widened to float32 at a
time.

The model, ``u = rmsnorm(x)`` (every norm an RMSNorm with a gain and
``rms_norm_eps``)::

    x_0 = embedding_multiplier x E[token]
    x <- x + residual_multiplier x mixer(rmsnorm(x))
    x <- x + residual_multiplier x (experts(h) + shared(h)),  h = rmsnorm(x)
    logits = rmsnorm(x_L) E^T / logits_scaling                (tied head)

A ``mamba`` mixer: ``[z | xBC | dt] = u W_in``; ``xBC_t <- silu(b + sum_j
w[:, j] xBC_{t-3+j})``, a causal depthwise convolution with zeros before
the first token; ``x_t`` (heads x head size), ``B_t``, ``C_t`` (one group);
``delta_t = softplus(dt_t + dt_bias)``, ``A = -exp(A_log)`` a head; ``H_t =
exp(delta_t A) H_{t-1} + delta_t x_t (x) B_t``; ``y_t = H_t C_t + D x_t``;
``y <- rmsnorm(y * silu(z)) w`` (the gate BEFORE the norm); ``W_out``. An
``attention`` mixer: no rotary phase, scores ``q . k x
attention_multiplier``, causal softmax. The expert block: the router in
float32 over all its outputs, the ``num_experts_per_tok`` largest weighed
by a softmax over THOSE, plus the shared expert at weight 1. This chip's
share is the sum over the picked experts it HOLDS (``num_local_experts``
of them, from ``experts_held[0]``) plus the shared expert, which every
chip computes alike. What the experts held elsewhere would add is left
out, here as in the program.

Entries: :func:`served_numbers` (what the runner compares) and
:func:`served_gaps` with ``decoder_lm``'s signature. ``"fp8"`` is the
control, the precision below the configuration's bf16: every weight
matmul's operands rounded to e4m3 under an absmax scale a token and an
output channel, sums in float32 (the router stays in float32).
``"bf16_state"`` is the second control: the recurrent state, which the
configuration states as float32, rounded to bfloat16 after every token.
``FAULTS`` are departures the comparison has to see, each a switch of its
own.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

# -- the forward pass (akka_allreduce_tpu/models/ssm_moe_reference.py has the
# same text; benchmark/tests/test_ssm_moe.py holds the two equal) ------------

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

PAD = 1024           # a sample is padded to a multiple of this
CONTROLS = ("fp8", "bf16_state")
NO_HELD = "fault.no_held"


def _stand_in_args(name):
    """A stand-in's (quant, faults): a control or one planted fault."""
    if name.startswith("fault."):
        fault = name[len("fault."):]
        if fault not in FAULTS:
            raise ValueError(f"unknown fault {fault!r}; have {FAULTS}")
        return None, (fault,)
    if name not in CONTROLS:
        raise ValueError(f"unknown control {name!r}; have {CONTROLS}")
    return name, ()


class _Sums:
    """Squared norms of a difference and of what it is a difference from,
    over everything checked: their ratio's root is the gap."""

    def __init__(self):
        self.num = self.den = 0.0

    def add(self, got, want):
        self.num += float(jnp.sum(jnp.square(got - want)))
        self.den += float(jnp.sum(jnp.square(want)))

    @property
    def gap(self):
        return (self.num / self.den) ** 0.5 if self.den > 0 else None


class _HeadGaps:
    """The recurrent states' gap a head: the squared norm of a head's
    difference from the reference's state over the squared norm of that
    state, averaged over every head of every layer and sample checked; the
    gap is the root. A head that forgets in ten tokens holds a hundred
    times the mass of one that remembers a thousand (the stationary
    variance goes as dt / 2A), so a gap weighed by norm would see the
    short memories alone; a head at a time, the long memories count as
    much, and they are where a state kept in too few bits goes wrong (a
    decay of exp(dt A) closer to 1 than half a bfloat16 ulp is rounded
    away and the head never forgets)."""

    def __init__(self):
        self.sum = 0.0
        self.n = 0

    def add(self, got, want):
        """(layers, heads, head size, state) each."""
        num = jnp.sum(jnp.square(got - want), axis=(-2, -1))
        den = jnp.sum(jnp.square(want), axis=(-2, -1))
        ratio = np.asarray(num / jnp.maximum(den, 1e-30), np.float64)
        self.sum = self.sum + ratio.sum(axis=-1)         # a layer
        self.n += ratio.shape[-1]

    @property
    def by_layer(self):
        """The gap of each state-space layer's heads, in order."""
        return [float(v) for v in np.sqrt(self.sum / self.n)] \
            if self.n else None

    @property
    def gap(self):
        return float(np.sqrt(np.mean(self.sum / self.n))) \
            if self.n else None

    @property
    def first(self):
        return self.by_layer[0] if self.n else None


class _Part:
    """How much of the held experts' part some logits carry. With ``want``
    the reference's logits, ``without`` the reference's with the held
    experts left out and ``d = want - without``: the projection of ``got -
    without`` on ``d`` over ``d . d``, summed over every position checked,
    is 1 where the part is carried whole and 0 where it is left out; the
    gap is its distance from 1. Rounding that is not aligned with ``d``
    averages out of the projection."""

    def __init__(self):
        self.num = self.den = 0.0

    def add(self, got, without, want):
        d = want - without
        self.num += float(jnp.sum((got - without) * d))
        self.den += float(jnp.sum(jnp.square(d)))

    @property
    def gap(self):
        return abs(1.0 - self.num / self.den) if self.den > 0 else None


def served_numbers(params, model, samples, pad_to, stand_ins=(),
                   program_logits=None, program_states=None) -> dict:
    """``samples``: list of (prompt, served) token tuples. Forwards over
    prompt + served, padded to the next multiple of ``PAD`` (at most
    ``pad_to``; causal, so padding after the end changes nothing before
    it), one at a time: the reference's, the reference's with the held
    experts left out (what the held part is measured from), and one a
    stand-in. Returns a dict of dicts of numbers, all over the served
    positions of all samples:

    * ``"program"``: ``served_gap``, the widest gap by which a served
      token's logit lies below the reference's best. Where
      ``program_logits`` hands over, a sample, (which k of the sample's
      served tokens, the rows of logits (k, vocab) that the served path's
      own programs picked them from): ``logits_gap``, the norm of those rows'
      difference from the reference's over the norm of the reference's,
      and ``held_part_gap`` (:class:`_Part`) over those positions. Where
      ``program_states`` hands over, a sample, (the tokens the lane had
      consumed, the lane's recurrent states (layers, heads, head size,
      state) read from the engine after them): ``state_gap``, the
      difference of the FIRST state-space layer's state from the
      reference's after the same tokens, a head at a time
      (:class:`_HeadGaps`): that layer's input has passed no routing yet
      (the embedding, a norm and ``W_in``), so it reads the recurrence and
      its bookkeeping and not which way a near tie of a router fell, and
      its limit can lie close over the sound readings;
      ``deep_state_gap``, the same over EVERY state-space layer (the
      root of the mean of the layers' squares), where the drift of the
      layers before is in the reading and the limit has more room: what
      holds the recurrence of the layers after the first.
    * one entry a stand-in (``"fp8"``, ``"bf16_state"``: the controls;
      ``"fault.<name>"``): the same numbers of a reference forward in
      that precision or with that fault in the program's place: the gap
      of ITS greedy pick below the reference's best, its logits and its
      states (in its own forward) against the reference's.
    """
    names = ("program",) + tuple(stand_ins)
    gaps = {n: 0.0 for n in names}
    logit_sums = {n: _Sums() for n in names}
    state_sums = {n: _HeadGaps() for n in names}
    held = {n: _Part() for n in names}
    run_also = tuple(stand_ins)
    if (program_logits is not None or stand_ins) and NO_HELD not in run_also:
        run_also += (NO_HELD,)
    for si, (prompt, served) in enumerate(samples):
        full = tuple(prompt) + tuple(served)
        n_prompt = len(prompt)
        consumed = full
        if program_states is not None:
            consumed = tuple(program_states[si][0])
        padded = min(pad_to, -(-max(len(full), len(consumed)) // PAD) * PAD)

        def run(tokens, snap_at, quant=None, faults=()):
            row = np.zeros((padded,), np.int32)
            row[:len(tokens)] = tokens
            return forward(params, row, model, quant, faults,
                           prompt_len=n_prompt, snap_at=snap_at,
                           rows=(lo, n))
        # position p predicts token p+1: served tokens sit at
        # len(prompt) .. len(full)-1, predicted from one before
        lo, n = n_prompt - 1, len(served)
        # the served tokens whose rows are compared: all, or those handed
        at = np.arange(n) if program_logits is None \
            else np.asarray(program_logits[si][0], np.int64)
        snap = min(len(consumed), len(full))
        ref, ref_states = run(full, snap)
        best = ref.max(axis=-1)
        nxt = jnp.asarray(np.asarray(full[lo + 1:lo + 1 + n], np.int32))
        served_gap = best - jnp.take_along_axis(ref, nxt[:, None], -1)[:, 0]
        gaps["program"] = max(gaps["program"], float(served_gap.max()))
        logits, states = {}, {}
        for name in run_also:
            quant, faults = _stand_in_args(name)
            logits[name], states[name] = run(full, snap, quant, faults)
        if program_states is not None:
            want = ref_states
            if consumed != full[:len(consumed)]:
                # a near tie of the replay fell the other way: the lane
                # consumed other tokens than were served the first time
                _l, want = run(consumed, len(consumed))
            state_sums["program"].add(
                jnp.asarray(program_states[si][1], jnp.float32), want)
        if program_logits is not None and len(at):
            got = jnp.asarray(program_logits[si][1], jnp.float32)
            logit_sums["program"].add(got, ref[at])
            held["program"].add(got, logits[NO_HELD][at], ref[at])
        for name in stand_ins:
            low = logits[name]
            pick = jnp.argmax(low, axis=-1)
            gap = best - jnp.take_along_axis(ref, pick[:, None], -1)[:, 0]
            gaps[name] = max(gaps[name], float(gap.max()))
            logit_sums[name].add(low[at], ref[at])
            held[name].add(low[at], logits[NO_HELD][at], ref[at])
            state_sums[name].add(states[name], ref_states)
    out = {}
    for name in names:
        out[name] = {"served_gap": gaps[name]}
        program = name == "program"
        if not program or program_logits is not None:
            out[name]["logits_gap"] = logit_sums[name].gap
            out[name]["held_part_gap"] = held[name].gap
        if not program or program_states is not None:
            out[name]["state_gap"] = state_sums[name].first
            out[name]["deep_state_gap"] = state_sums[name].gap
            print(f"states by layer {name}: "
                  f"{[round(v, 5) for v in state_sums[name].by_layer]}")
    return out


def served_gaps(params, model, samples, pad_to, control=None):
    """``decoder_lm.served_gaps``'s signature: ``(served_gap,
    control_gap)``; ``control_gap`` is None unless ``control`` names a
    lower precision."""
    got = served_numbers(params, model, samples, pad_to,
                         stand_ins=(control,) if control else ())
    return (got["program"]["served_gap"],
            got[control]["served_gap"] if control else None)
