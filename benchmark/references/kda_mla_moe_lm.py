"""Plain reference for the hybrid of delta-rule linear attention and latent
attention (Ling's block, ``model_type`` "bailing_hybrid"). Straightforward
``jax.numpy`` in float32 at ``highest`` matmul precision: the delta rule a
plain ``lax.scan`` over the tokens from its equation (no chunked form), the
latent attention expanded to every head's keys and values (no absorption)
with a mask over the full scores, a block of query rows at a time; the
router's group limit by sorting each group; a Python loop over the held
experts; no cache, no kernel, no batching, and nothing imported from the
program.

The model, every norm an RMSNorm with a gain and ``rms_norm_eps``::

    x_0 = E[token]
    x <- x + mixer(x)                    (a kda mixer norms x with ln1 first)
    x <- x + ffn(rmsnorm(x))             (dense SwiGLU, or the expert share)
    logits = rmsnorm(x_L) W_head

A ``kda`` mixer (layer i with ``(i + 1) % layer_group_size != 0``), u =
rmsnorm(x): ``[q | k | v | f | g | beta] = u W_in``; each of q, k, v through
a causal depthwise convolution of width ``short_conv_kernel_size`` (zeros
before the first token, no bias), then SiLU; q and k L2-normed a head, q
times ``head_dim ** -0.5``; ``beta = sigmoid(beta)`` a head; the log-decay
``g = kda_lower_bound x sigmoid(exp(A_log) (f + dt_bias))`` a channel;
``S_t = (I - beta_t k_t k_t^T) Diag(exp g_t) S_{t-1} + beta_t k_t v_t^T``
and ``o_t = S_t^T q_t`` a head, S float32 from zeros; ``o <- rmsnorm(o)
sigmoid(g)`` (one group over all channels); ``W_out``. A ``latent`` layer:
h = rmsnorm(x); ``q = rmsnorm_head(h W_q)`` (a head's 192 numbers);
the latent ``rmsnorm(h W_kv_a[:rank])`` and the rotary key ``h
W_kv_a[rank:]``, shared by the heads; the interleaved rotary phases (pairs
(x[2i], x[2i+1])) on q's and the key's last ``qk_rope_head_dim``; keys and
values ``latent W_kv_b`` a head; causal softmax at ``(nope + rope) **
-0.5``; each head's output times ``sigmoid(h W_gate)`` of its own;
``W_o``. The expert share: sigmoid scores of the router in float32, the
choice by score + selection bias limited to the ``topk_group`` best of
``n_group`` groups (a group's worth the sum of its two best), the
``num_experts_per_tok`` best kept, weighed by their scores renormalised and
times ``routed_scaling_factor``, plus the shared expert at weight 1. This
chip's share is the sum over the picked experts it HOLDS
(``experts_held``) plus the shared expert, which every chip computes alike.
What the experts held elsewhere would add is left out, here as in the
program.

Entries: :func:`served_numbers` (what the runner compares) and
:func:`served_gaps` with ``decoder_lm``'s signature. ``"fp8"`` is the
first control, the precision below the configuration's bf16: every weight
matmul's operands rounded to e4m3 under an absmax scale a token and an
output channel, sums in float32 (the router stays in float32).
``"bf16_state"`` is the second: the delta rule's state, which the
configuration states as float32, rounded to bfloat16 after every token.
``FAULTS`` are departures the comparison has to see, each a switch of its
own.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

# the sums of the gaps are the state-space reference's, a state read a head
# at a time whatever its shape
from benchmark.references.ssm_moe_lm import _HeadGaps, _Part, _Sums

# -- the forward pass (tests/kda_mla_moe_reference.py has the same text;
# benchmark/tests/test_kda_mla_moe.py holds the two equal) -----------------

FAULTS = (
    "no_beta",            # beta dropped (1 for every token)
    "unbounded_gate",     # g = -exp(A_log) softplus(f + dt_bias): no bound
    "norm_per_head",      # the output norm a head, not one group
    "tail_dropped",       # the convolutions' tail dropped at a chunk boundary
    "state_not_carried",  # the state starts from zeros at a chunk boundary
    "no_head_gate",       # the latent layer's head-wise gate dropped
    "no_group_limit",     # the router picks among all 512, no group limit
    "half_held",          # the second half of the held experts left out
    "no_held",            # the held experts left out
)
# which faults change which jitted piece (a piece compiles once a set)
KDA_FAULTS = frozenset({"no_beta", "unbounded_gate", "norm_per_head"})
LATENT_FAULTS = frozenset({"no_head_gate"})
ROUTE_FAULTS = frozenset({"no_group_limit"})
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


def _rope_interleaved(x, theta):
    # x (T, H, D); pairs are (x[2i], x[2i + 1]); positions 0..T-1
    t, half = x.shape[0], x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     axis=-1).reshape(x.shape)


def _freeze(model):
    return tuple(sorted((k, v) for k, v in model.items()
                        if isinstance(v, (int, float, str, bool,
                                          type(None)))))


def held_of(model, n_outputs) -> tuple:
    return tuple(model.get("experts_held", (0, n_outputs)))


def kda_mixer(p, u, model, cuts, snap_at, quant=None, faults=()):
    """A delta-rule mixer over its normed input u (T, D): (the mixer's
    output (T, D), the state after token ``snap_at - 1`` (heads, head size,
    head size) float32). ``cuts``: two (T,) int32, the first position whose
    inputs a token's convolutions may see, and the position at which the
    state that reaches the token started from zeros (both 0 everywhere in
    a sound forward; a chunk's first position where a fault cuts the
    sequence there)."""
    t = u.shape[0]
    heads, hd = model["num_attention_heads"], model["head_dim"]
    width, kd = model["short_conv_kernel_size"], heads * hd
    proj = _mm(u, p["w_in"], quant)
    qkv, f, gate, beta = (proj[:, :3 * kd], proj[:, 3 * kd:4 * kd],
                          proj[:, 4 * kd:5 * kd], proj[:, 5 * kd:])
    seq = jnp.pad(qkv, ((width - 1, 0), (0, 0)))
    at = jnp.arange(t)
    cut_tail, cut_state = cuts
    conv = jnp.zeros_like(qkv)
    for j in range(width):
        seen = (at - (width - 1) + j >= cut_tail)[:, None]
        conv = conv + jnp.where(seen, seq[j:j + t], 0.0) * jnp.asarray(
            p["conv_w"], jnp.float32)[None, :, j]
    q, k, v = (x.reshape(t, heads, hd)
               for x in jnp.split(jax.nn.silu(conv), 3, axis=-1))

    def l2norm(x):
        return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), -1, keepdims=True)
                                 + 1e-6)
    q, k = l2norm(q) * hd ** -0.5, l2norm(k)
    a = jnp.exp(jnp.asarray(p["a_log"], jnp.float32))[:, None]
    f = (f + jnp.asarray(p["dt_bias"], jnp.float32)).reshape(t, heads, hd)
    if "unbounded_gate" in faults:
        g = -a * jax.nn.softplus(f)
    else:
        g = float(model["kda_lower_bound"]) * jax.nn.sigmoid(a * f)
    beta = jax.nn.sigmoid(beta)
    if "no_beta" in faults:
        beta = jnp.ones_like(beta)
    reset = (cut_state == at) & (at > 0)

    def step(carry, xs):
        s, snap = carry
        q_t, k_t, v_t, g_t, b_t, reset_t, i = xs
        s = jnp.where(reset_t, 0.0, s)
        s = jnp.exp(g_t)[:, :, None] * s
        err = v_t - jnp.einsum("hkv,hk->hv", s, k_t, precision=_HI)
        s = s + (b_t[:, None] * k_t)[:, :, None] * err[:, None, :]
        if quant == "bf16_state":
            # reduce_precision and not a cast there and back: the TPU's
            # compiler is allowed excess precision and drops such a pair
            s = jax.lax.reduce_precision(s, exponent_bits=8,
                                         mantissa_bits=7)
        snap = jnp.where(i == snap_at - 1, s, snap)
        return (s, snap), jnp.einsum("hkv,hk->hv", s, q_t, precision=_HI)

    zeros = jnp.zeros((heads, hd, hd), jnp.float32)
    (_, snap), o = jax.lax.scan(step, (zeros, zeros),
                                (q, k, v, g, beta, reset, at))
    eps = model["rms_norm_eps"]
    if "norm_per_head" in faults:
        o = _rmsnorm(o, jnp.asarray(p["norm"]).reshape(heads, hd), eps)
    o = o.reshape(t, kd)
    if "norm_per_head" not in faults:
        o = _rmsnorm(o, p["norm"], eps)
    return _mm(o * jax.nn.sigmoid(gate), p["w_out"], quant), snap


def latent_attention(p, x, model, quant=None, faults=()):
    """Latent attention over x (T, D), its own norm first, expanded:
    token t attends every position at or before its own, as a mask over
    the full scores, a block of query rows at a time."""
    t = x.shape[0]
    heads, rank = model["num_attention_heads"], model["kv_lora_rank"]
    nope, rope = model["qk_nope_head_dim"], model["qk_rope_head_dim"]
    vd, eps = model["v_head_dim"], model["rms_norm_eps"]
    theta = float(model["rope_theta"])
    h = _rmsnorm(x, p["ln"], eps)
    q = _rmsnorm(_mm(h, p["wq"], quant).reshape(t, heads, nope + rope),
                 p["q_norm"], eps)
    q = jnp.concatenate([q[..., :nope],
                         _rope_interleaved(q[..., nope:], theta)], -1)
    down = _mm(h, p["wkv_a"], quant)
    latent = _rmsnorm(down[:, :rank], p["kv_norm"], eps)
    k_rope = _rope_interleaved(down[:, None, rank:], theta)
    kvh = _mm(latent, p["wkv_b"], quant).reshape(t, heads, nope + vd)
    k = jnp.concatenate([kvh[..., :nope],
                         jnp.broadcast_to(k_rope, (t, heads, rope))], -1)
    v = kvh[..., nope:]
    scale = (nope + rope) ** -0.5
    qb = min(Q_BLOCK, t)
    blocks = -(-t // qb)
    pad = blocks * qb - t
    at = jnp.arange(t)

    def block(args):
        rows, q_b = args
        s = jnp.einsum("qhd,khd->hqk", q_b, k, precision=_HI) * scale
        mask = at[None, :] <= rows[:, None]
        w = jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", w, v, precision=_HI)
    out = jax.lax.map(block, (
        jnp.arange(blocks * qb).reshape(blocks, qb),
        jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(
            blocks, qb, heads, nope + rope)))
    out = out.reshape(blocks * qb, heads, vd)[:t]
    if "no_head_gate" not in faults:
        out = out * jax.nn.sigmoid(_mm(h, p["w_gate"], quant))[..., None]
    return _mm(out.reshape(t, heads * vd), p["wo"], quant)


def swiglu(w1, w3, w2, h, quant=None):
    return _mm(jax.nn.silu(_mm(h, w1, quant)) * _mm(h, w3, quant), w2, quant)


def route(moe, h, model, faults=()):
    """-> (pick (T, k) int32, weight (T, k) float32): the router in
    float32 whatever the control; sigmoid scores, the choice by score +
    bias among the ``topk_group`` best of ``n_group`` groups."""
    scores = jax.nn.sigmoid(_mm(h, moe["router"]))
    choice = scores + jnp.asarray(moe["bias"], jnp.float32)
    n, width = choice.shape
    groups = int(model.get("n_group", 1))
    if groups > 1 and "no_group_limit" not in faults:
        grouped = choice.reshape(n, groups, width // groups)
        worth = jnp.sort(grouped, axis=-1)[..., -2:].sum(-1)
        best = jnp.argsort(-worth, axis=-1)[:, :int(model["topk_group"])]
        keep = (jnp.arange(groups)[None, :, None]
                == best[:, None, :]).any(-1)
        choice = jnp.where(keep[:, :, None], grouped,
                           -jnp.inf).reshape(n, width)
    _, pick = jax.lax.top_k(choice, model["num_experts_per_tok"])
    weight = jnp.take_along_axis(scores, pick, -1)
    if model.get("norm_topk_prob", True):
        weight = weight / (weight.sum(-1, keepdims=True) + 1e-20)
    return pick, weight * float(model["routed_scaling_factor"])


@functools.partial(jax.jit, static_argnames=("model_t", "quant", "faults"))
def _kda_jit(p, ln, x, cuts, snap_at, model_t, quant, faults):
    model = dict(model_t)
    u = _rmsnorm(x, ln, model["rms_norm_eps"])
    return kda_mixer(p, u, model, cuts, snap_at, quant, faults)


@functools.partial(jax.jit, static_argnames=("model_t", "quant", "faults"))
def _latent_jit(p, x, model_t, quant, faults):
    return latent_attention(p, x, dict(model_t), quant, faults)


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
    """This chip's share of the expert layer for h (T, D): (the held
    experts' part, the shared expert's part), one expert at a time."""
    offset, count = held_of(model, layer_moe["router"].shape[-1])
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
    if "ws1" in layer_moe:
        shared = _swiglu_jit(layer_moe["ws1"], layer_moe["ws3"],
                             layer_moe["ws2"], h, quant)
    return part, shared


def _cuts(model, t, prompt_len, faults):
    """Where a prompt of ``prompt_len`` tokens is cut by the engine (one
    bucket, or chunks of ``prefill_chunk`` where it is longer than the
    largest) as (start (T,) of the convolution, start (T,) of the state):
    0 everywhere in a sound forward, a chunk's first position under the
    faults that cut there."""
    eng = model.get("engine", {})
    buckets = tuple(eng.get("prefill_buckets", ()))
    chunk = int(eng.get("prefill_chunk", 0))
    bound = np.zeros((t,), np.int32)
    n = prompt_len
    if n is not None and chunk and n > (buckets[-1] if buckets else chunk):
        # a token of the prompt sees its chunk's first position; what comes
        # after the prompt sees the last chunk's
        bound = (np.minimum(np.arange(t), n - 1) // chunk * chunk).astype(
            np.int32)
    zeros = np.zeros_like(bound)
    return (jnp.asarray(bound if "tail_dropped" in faults else zeros),
            jnp.asarray(bound if "state_not_carried" in faults else zeros))


def forward(params, tokens, model, quant=None, faults=(), prompt_len=None,
            snap_at=None, each=None, rows=None):
    """tokens (T,) -> (logits (T, vocab) float32, the delta-rule layers'
    states after token ``snap_at - 1`` (layers, heads, head size, head
    size) float32; after the last token by default). ``prompt_len``: where
    the prompt ends, for the faults of the served path's chunks.
    ``each(i, info)`` sees every expert layer's record: its input ``h_moe``
    and the held and shared parts; and every latent layer's: its input
    ``x_latent`` (the residual stream ahead of its norm) and its output
    ``latent``. ``rows`` = (lo, n): the head is applied to positions lo ..
    lo + n alone (n, vocab)."""
    faults = tuple(faults)
    tokens = jnp.asarray(tokens, jnp.int32)
    t = tokens.shape[0]
    model_t, eps = _freeze(model), model["rms_norm_eps"]
    cuts = _cuts(model, t, prompt_len, faults)
    snap_at = t if snap_at is None else snap_at
    x = jnp.asarray(params["embed"], jnp.float32)[tokens]
    states = []
    for i, layer in enumerate(params["layers"]):
        if "kda" in layer:
            out, snap = _kda_jit(layer["kda"], layer["ln1"], x, cuts,
                                 snap_at, model_t, quant,
                                 tuple(sorted(KDA_FAULTS & set(faults))))
            states.append(snap)
        else:
            out = _latent_jit(layer["mla"], x, model_t, quant,
                              tuple(sorted(LATENT_FAULTS & set(faults))))
            if each is not None:
                each(i, {"x_latent": x, "latent": out})
        x = x + out
        h = _norm_jit(x, layer["ln2"], eps)
        if "moe" in layer:
            part, shared = moe(layer["moe"], h, model, quant, faults)
            if each is not None:
                each(i, {"h_moe": h, "part": part, "shared": shared})
            x = x + part + shared
        else:
            x = x + _swiglu_jit(layer["w1"], layer["w3"], layer["w2"], h,
                                quant)
    if rows is not None:
        x = x[rows[0]:rows[0] + rows[1]]
    logits = _head_jit(params["out_norm"], params["lm_head"], x, eps, quant)
    return logits, jnp.stack(states)


@functools.partial(jax.jit, static_argnames=("eps", "quant"))
def _head_jit(out_norm, head, x, eps, quant):
    return _mm(_rmsnorm(x, out_norm, eps), head, quant)

# -- end of the forward pass ------------------------------------------------

PAD = 1024           # a sample is padded to a multiple of this
CONTROLS = ("bf16_state", "fp8")
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


def served_numbers(params, model, samples, pad_to, stand_ins=(),
                   program_logits=None, program_states=None,
                   program_moe=None, program_latent=None) -> dict:
    """``ssm_moe_lm.served_numbers`` over this model (the same numbers, the
    same gaps, a state being a delta-rule layer's (heads, head size, head
    size)): forwards over prompt + served, padded to the next multiple of
    ``PAD`` (at most ``pad_to``), the reference's, the reference's with
    the held experts left out and one a stand-in. ``"program"`` holds
    ``served_gap`` and, where handed over, ``logits_gap`` /
    ``held_part_gap`` on the rows the served path's own programs picked
    their tokens from and ``state_gap`` (the FIRST delta-rule layer's state,
    a head at a time: its input has passed no router) / ``deep_state_gap``
    (every delta-rule layer's) on the lanes' states read after the tokens
    they consumed. Where ``program_moe(layer_index, h) -> (whole, held
    part)`` hands over the program's own expert layer, run on the
    reference's expert-layer input over the served rows (rounded to the
    configuration's precision): ``moe_out_gap`` and ``expert_out_gap``,
    the norm of its difference from the reference's output on that same
    input over the norm of that output, for the whole share and for the
    held experts' part, over every expert layer. Where
    ``program_latent(layer_index, x) -> out`` hands over the program's own
    latent layer, run on the reference's input to it over the whole
    sequence (rounded likewise): ``latent_out_gap``, the same ratio over
    every position of prompt and answer. Each stand-in: the same numbers
    of a reference forward in its precision or with its fault, its layers'
    outputs (in its own forward) against the reference's."""
    names = ("program",) + tuple(stand_ins)
    gaps = {n: 0.0 for n in names}
    logit_sums = {n: _Sums() for n in names}
    state_sums = {n: _HeadGaps() for n in names}
    held = {n: _Part() for n in names}
    layer_sums = {n: {k: _Sums() for k in ("moe", "expert", "latent")}
                  for n in names}
    model_t = _freeze(model)
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

        def run(tokens, snap_at, quant=None, faults=(), each=None):
            row = np.zeros((padded,), np.int32)
            row[:len(tokens)] = tokens
            return forward(params, row, model, quant, faults,
                           prompt_len=n_prompt, snap_at=snap_at,
                           rows=(lo, n), each=each)
        # position p predicts token p+1: served tokens sit at
        # len(prompt) .. len(full)-1, predicted from one before
        lo, n = n_prompt - 1, len(served)
        at = np.arange(n) if program_logits is None \
            else np.asarray(program_logits[si][0], np.int64)
        snap = min(len(consumed), len(full))
        rec = {}

        def of_reference(i, info):
            layer = params["layers"][i]
            if "latent" in info:
                rec[i, "latent"] = info["latent"][:len(full)]
                if program_latent is not None:
                    # the same input for both: the reference's, as the
                    # program's precision holds it
                    x_low = info["x_latent"].astype(jnp.bfloat16)
                    want = _latent_jit(layer["mla"],
                                       x_low.astype(jnp.float32), model_t,
                                       None, ())
                    got = program_latent(i, x_low)
                    layer_sums["program"]["latent"].add(
                        jnp.asarray(got, jnp.float32)[:len(full)],
                        want[:len(full)])
                return
            rec[i, "moe"] = (info["part"][lo:lo + n],
                             info["shared"][lo:lo + n])
            if program_moe is not None:
                h_low = info["h_moe"][lo:lo + n].astype(jnp.bfloat16)
                want_part, want_shared = moe(
                    layer["moe"], h_low.astype(jnp.float32), model)
                whole, part = program_moe(i, h_low)
                layer_sums["program"]["moe"].add(
                    jnp.asarray(whole, jnp.float32), want_part + want_shared)
                layer_sums["program"]["expert"].add(
                    jnp.asarray(part, jnp.float32), want_part)

        ref, ref_states = run(full, snap, each=of_reference)
        best = ref.max(axis=-1)
        nxt = jnp.asarray(np.asarray(full[lo + 1:lo + 1 + n], np.int32))
        served_gap = best - jnp.take_along_axis(ref, nxt[:, None], -1)[:, 0]
        gaps["program"] = max(gaps["program"], float(served_gap.max()))
        # a stand-in's logits are (served tokens, vocabulary) on the device:
        # its gap is taken at once and only the compared rows are kept
        logits, states = {}, {}
        for name in run_also:
            quant, faults = _stand_in_args(name)

            def of_stand_in(i, info, name=name):
                if name not in layer_sums:
                    return
                sums = layer_sums[name]
                if "latent" in info:
                    sums["latent"].add(info["latent"][:len(full)],
                                       rec[i, "latent"])
                    return
                want_part, want_shared = rec[i, "moe"]
                part = info["part"][lo:lo + n]
                sums["moe"].add(part + info["shared"][lo:lo + n],
                                want_part + want_shared)
                sums["expert"].add(part, want_part)

            low, states[name] = run(full, snap, quant, faults,
                                    each=of_stand_in)
            if name in stand_ins:
                pick = jnp.argmax(low, axis=-1)
                gap = best - jnp.take_along_axis(ref, pick[:, None], -1)[:, 0]
                gaps[name] = max(gaps[name], float(gap.max()))
            logits[name] = low[at]
            del low
        rec.clear()
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
            held["program"].add(got, logits[NO_HELD], ref[at])
        for name in stand_ins:
            logit_sums[name].add(logits[name], ref[at])
            held[name].add(logits[name], logits[NO_HELD], ref[at])
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
        if not program or program_moe is not None:
            out[name]["moe_out_gap"] = layer_sums[name]["moe"].gap
            out[name]["expert_out_gap"] = layer_sums[name]["expert"].gap
        if not program or program_latent is not None:
            out[name]["latent_out_gap"] = layer_sums[name]["latent"].gap
    return out


def served_gaps(params, model, samples, pad_to, control=None):
    """``decoder_lm.served_gaps``'s signature: ``(served_gap,
    control_gap)``; ``control_gap`` is None unless ``control`` names a
    lower precision."""
    got = served_numbers(params, model, samples, pad_to,
                         stand_ins=(control,) if control else ())
    return (got["program"]["served_gap"],
            got[control]["served_gap"] if control else None)
