"""Speculative decoding: draft proposes, target verifies in ONE pass.

The reference is training-side only (no inference exists to mirror);
this module extends the framework's serving path (models/generate.py)
with the canonical TPU latency win: a small DRAFT model proposes ``k``
tokens autoregressively (cheap steps), and the TARGET model scores all
``k`` in one batched ``extend`` forward — full-width MXU matmuls
instead of ``k`` sequential single-token dispatches. Greedy
equivalence is exact and pinned by tests/test_speculative.py: the
emitted sequence is BIT-IDENTICAL to target-only greedy decode for any
draft model (the draft only changes how fast tokens come, never which
tokens come).

Design notes, TPU-first:

* ``extend`` is the one new primitive: consume a (1, k) token block
  against the KV cache, returning logits at every block position —
  the same chunked-prefill shape serving stacks use. Attention masks
  by position against the static cache buffer (causal-within-block +
  prefix), so the program is static-shape and compiles once per k.
* The speculation loop is a ``lax.while_loop`` whose body does FIXED
  work (k draft steps + one target extend); only the accepted count is
  dynamic. Cache "rewind" is just the position scalar — stale entries
  beyond it are masked by the position check and overwritten by the
  next round's writes, so rejection costs nothing.
* Batch is restricted to 1: speculation is the LATENCY tool (the
  batch-throughput regime keeps the plain decode scan). Per-row
  acceptance would need per-row cache positions; out of scope.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from akka_allreduce_tpu.models.generate import (
    apply_sample_filters,
    decode_step,
    dequantize_kv,
    init_kv_cache,
    prefill,
    quantize_kv,
)
from akka_allreduce_tpu.models.transformer import (
    TransformerConfig,
    apply_rope,
    lm_logits,
    rmsnorm,
)
from akka_allreduce_tpu.parallel.ep import moe_ffn
from akka_allreduce_tpu.parallel.ring_attention import NEG_INF


def _block_cached_attention(q: jnp.ndarray, k_all: jnp.ndarray,
                            v_all: jnp.ndarray, pos: jnp.ndarray,
                            window: "int | None" = None) -> jnp.ndarray:
    """q: (b, t, h, d) for block positions pos..pos+t-1; k_all/v_all:
    (b, max_seq, h_kv, d) with the block's K/V already written. Masked
    softmax over the static buffer: query j attends cache positions
    <= pos + j (prefix + causal-within-block), minus anything outside
    the sliding window when ``window`` is set. Same scale form, f32
    score/softmax, and cast points as the single-token
    _cached_attention / the full forward, so extend parity is exact."""
    b, t, h, d = q.shape
    h_kv = k_all.shape[2]
    g = h // h_kv
    qg = q.reshape(b, t, h_kv, g, d)
    scale = d ** -0.5
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k_all,
                        preferred_element_type=jnp.float32) * scale
    k_idx = jnp.arange(k_all.shape[1])
    q_pos = pos + jnp.arange(t)
    valid = k_idx[None, :] <= q_pos[:, None]          # (t, max_seq)
    if window is not None:
        valid &= k_idx[None, :] > q_pos[:, None] - window
    scores = jnp.where(valid[None, None, None, :, :], scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", p.astype(v_all.dtype), v_all,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, t, h, d).astype(q.dtype)


def extend(params: dict, cache: dict, tokens: jnp.ndarray,
           cfg: TransformerConfig) -> tuple[dict, jnp.ndarray]:
    """Consume a (b, t) token block starting at ``cache.pos``; return
    (updated cache, logits (b, t, vocab)) — logits[:, j] is the
    next-token distribution after consuming tokens[:, :j+1]. This is
    the chunked-prefill / verification primitive: ``prefill`` is the
    pos=0 special case, ``decode_step`` the t=1 one. Parity with
    sequential decode_step calls is pinned by tests/test_speculative.py."""
    b, t = tokens.shape
    pos = cache["pos"]
    quantized = "k_scale" in cache
    x = params["embed"][tokens]
    if not cfg.rope:
        x = x + lax.dynamic_slice_in_dim(params["pos"], pos, t,
                                         axis=0)[None]
    k_cache, v_cache = cache["k"], cache["v"]
    if quantized:
        k_scales, v_scales = cache["k_scale"], cache["v_scale"]
    positions = pos + jnp.arange(t)
    for i, layer in enumerate(params["layers"]):
        h = rmsnorm(x, layer["ln1"], cfg.norm_eps)
        q = (h @ layer["wq"]).reshape(b, t, cfg.n_heads, cfg.head_dim)
        k = (h @ layer["wk"]).reshape(b, t, cfg.kv_heads, cfg.head_dim)
        v = (h @ layer["wv"]).reshape(b, t, cfg.kv_heads, cfg.head_dim)
        if cfg.rope:
            q = apply_rope(q, positions, cfg.rope_theta)
            k = apply_rope(k, positions, cfg.rope_theta)
        if quantized:
            kq, ks = quantize_kv(k)
            vq, vs = quantize_kv(v)
            k_cache = lax.dynamic_update_slice(
                k_cache, kq[None], (i, 0, pos, 0, 0))
            v_cache = lax.dynamic_update_slice(
                v_cache, vq[None], (i, 0, pos, 0, 0))
            k_scales = lax.dynamic_update_slice(
                k_scales, ks[None], (i, 0, pos, 0))
            v_scales = lax.dynamic_update_slice(
                v_scales, vs[None], (i, 0, pos, 0))
            k_all = dequantize_kv(k_cache[i], k_scales[i], cfg.dtype)
            v_all = dequantize_kv(v_cache[i], v_scales[i], cfg.dtype)
        else:
            k_cache = lax.dynamic_update_slice(
                k_cache, k[None].astype(k_cache.dtype), (i, 0, pos, 0, 0))
            v_cache = lax.dynamic_update_slice(
                v_cache, v[None].astype(v_cache.dtype), (i, 0, pos, 0, 0))
            k_all, v_all = k_cache[i], v_cache[i]
        attn = _block_cached_attention(q, k_all, v_all, pos,
                                       window=cfg.attn_window)
        x = x + attn.reshape(b, t, -1) @ layer["wo"]

        h = rmsnorm(x, layer["ln2"], cfg.norm_eps)
        if "router" in layer:
            y, _aux = moe_ffn(h, layer, cfg.moe, axis_name=None)
            x = x + y
        elif "w3" in layer:
            x = x + (jax.nn.silu(h @ layer["w1"])
                     * (h @ layer["w3"])) @ layer["w2"]
        else:
            x = x + jax.nn.gelu(h @ layer["w1"]) @ layer["w2"]
    logits = lm_logits(
        params, rmsnorm(x, params["out_norm"], cfg.norm_eps), cfg)
    new_cache = {"k": k_cache, "v": v_cache, "pos": pos + t}
    if quantized:
        new_cache["k_scale"], new_cache["v_scale"] = k_scales, v_scales
    return new_cache, logits


@partial(jax.jit, static_argnames=("target_cfg", "draft_cfg", "steps",
                                   "k", "eos_token"))
def speculative_generate(target_params: dict, draft_params: dict,
                         prompt: jnp.ndarray,
                         target_cfg: TransformerConfig,
                         draft_cfg: TransformerConfig,
                         steps: int, k: int = 4,
                         eos_token: Optional[int] = None
                         ) -> tuple[jnp.ndarray, dict]:
    """Greedy speculative decode: ``steps`` tokens after ``prompt``
    (1, t), bit-identical to ``generate(temperature=0)`` on the target
    alone. Returns ``(tokens (1, steps), stats)`` where stats carries
    ``rounds`` (target extend passes) and ``drafted``/``accepted``
    totals — acceptance_rate = accepted / drafted; speedup comes from
    rounds << steps when the draft predicts the target well.

    ``eos_token`` adds early termination: the while_loop's condition
    gains a done flag, so a sequence that emits EOS stops spending
    target passes IMMEDIATELY (batch is 1, so unlike generate()'s
    fixed-shape scan this is a real wall-clock saving, not just
    bookkeeping). The output pads positions after the first EOS with
    ``eos_token`` — the same padding generate() emits, keeping the
    bit-identical contract through the padded tail — and stats gains
    ``length`` (tokens through the first EOS, = steps when none
    fired).

    Per round: the draft proposes g_1..g_k (k cheap steps from the last
    emitted token ``cur``); the target consumes [cur, g_1..g_{k-1}] in
    ONE extend, yielding its argmax at every position; the longest
    matching prefix g_1..g_n is accepted, plus the target's own next
    token as a correction when n < k (so every round emits >= 1 token
    and the sequence equals target-greedy by induction). Both caches
    then rewind their position scalar to the emitted frontier — stale
    entries are masked and overwritten, never cleared.
    """
    if prompt.shape[0] != 1:
        raise ValueError(
            "speculative decode is the batch-1 latency path; run the "
            f"plain decode scan for batch {prompt.shape[0]}")
    if not 1 <= k:
        raise ValueError(f"k must be >= 1, got {k}")
    if eos_token is not None \
            and not 0 <= eos_token < target_cfg.vocab_size:
        raise ValueError(f"eos_token {eos_token} out of vocab "
                         f"[0, {target_cfg.vocab_size})")
    if draft_cfg.vocab_size != target_cfg.vocab_size:
        raise ValueError(
            f"draft and target must share a vocabulary: "
            f"{draft_cfg.vocab_size} != {target_cfg.vocab_size}")
    if prompt.shape[1] + steps + k > target_cfg.max_seq:
        # k of HEADROOM beyond the emitted length: a final round can
        # extend k positions past the second-to-last emitted token, and
        # dynamic_update_slice would silently CLAMP an out-of-range
        # write onto live prefix entries — corrupting the cache while
        # the position mask still trusts it (the one failure mode that
        # would break the bit-identical contract quietly)
        raise ValueError(
            f"target max_seq {target_cfg.max_seq} must cover prompt + "
            f"steps + k = {prompt.shape[1] + steps + k} (speculation "
            f"rounds write up to k positions past the emitted frontier)")
    if prompt.shape[1] + steps + k > draft_cfg.max_seq:
        raise ValueError(
            f"draft max_seq {draft_cfg.max_seq} must cover prompt + "
            f"steps + k = {prompt.shape[1] + steps + k} (the draft can "
            f"run k ahead)")

    t_cache = init_kv_cache(target_cfg, 1)
    d_cache = init_kv_cache(draft_cfg, 1)
    t_cache, t_logits = prefill(target_params, t_cache, prompt,
                                target_cfg)
    d_cache, _ = prefill(draft_params, d_cache, prompt, draft_cfg)
    # the first emitted token is the target's own (greedy start): the
    # draft never gets to choose a token, only to predict the target
    cur0 = jnp.argmax(t_logits, axis=-1).astype(jnp.int32)  # (1,)

    buf_len = steps + k + 1
    out0 = jnp.zeros((buf_len,), jnp.int32)
    out0 = out0.at[0].set(cur0[0])

    def round_body(carry):
        (t_cache, d_cache, out, n_out, cur, done, rounds, drafted,
         accepted) = carry

        # -- draft: k greedy proposals from cur (k cheap steps)
        def draft_one(c, _):
            dc, tok = c
            dc, dl = decode_step(draft_params, dc, tok, draft_cfg)
            nxt = jnp.argmax(dl, axis=-1).astype(jnp.int32)
            return (dc, nxt), nxt

        (d_cache, _), props = lax.scan(draft_one, (d_cache, cur), None,
                                       length=k)
        props = props[:, 0]  # (k,) g_1..g_k

        # -- target: verify all k in ONE extend over [cur, g_1..g_k-1]
        block = jnp.concatenate([cur, props[:-1]])[None]  # (1, k)
        t_cache, t_block_logits = extend(target_params, t_cache, block,
                                         target_cfg)
        t_arg = jnp.argmax(t_block_logits[0], axis=-1).astype(jnp.int32)
        # t_arg[j] = target's token after consuming block[:j+1]; accept
        # the longest prefix where the draft guessed it
        match = t_arg == props
        n_acc = jnp.argmin(jnp.concatenate(
            [match, jnp.zeros((1,), bool)]).astype(jnp.int32))
        # emit g_1..g_n plus the target's correction at position n
        # (when n == k there is no correction: t_arg[k-1] == g_k was
        # accepted and becomes cur for the next round)
        emit_vec = jnp.where(jnp.arange(k) < n_acc, props, t_arg)
        emit_len = jnp.minimum(n_acc + 1, k)
        out = lax.dynamic_update_slice(out, emit_vec, (n_out,))
        new_cur = emit_vec[emit_len - 1][None]
        n_out = n_out + emit_len
        if eos_token is not None:
            done = done | ((emit_vec == eos_token)
                           & (jnp.arange(k) < emit_len)).any()

        # rewind both caches to the emitted frontier: consumed tokens
        # must equal emitted-1 (cur is emitted but not yet consumed)
        frontier = t_cache["pos"] - k + emit_len
        t_cache = {**t_cache, "pos": frontier}
        d_cache = {**d_cache, "pos": frontier}
        return (t_cache, d_cache, out, n_out, new_cur, done, rounds + 1,
                drafted + k, accepted + n_acc)

    def cond(carry):
        return (carry[3] < steps) & ~carry[5]

    done0 = (jnp.asarray(False) if eos_token is None
             else cur0[0] == eos_token)
    init = (t_cache, d_cache, out0, jnp.asarray(1, jnp.int32), cur0,
            done0, jnp.asarray(0, jnp.int32), jnp.asarray(0, jnp.int32),
            jnp.asarray(0, jnp.int32))
    (_, _, out, n_out, _, _, rounds, drafted, accepted) = lax.while_loop(
        cond, round_body, init)
    stats = {"rounds": rounds, "drafted": drafted, "accepted": accepted}
    out = out[:steps]
    if eos_token is not None:
        # a final round can overshoot: accepted draft tokens past the
        # EOS are already in the buffer. Mask everything after the
        # first EOS to EOS — exactly generate()'s done-row padding —
        # so parity holds through the tail
        hit = out == eos_token
        length = jnp.where(hit.any(), jnp.argmax(hit) + 1,
                           jnp.minimum(n_out, steps))
        out = jnp.where(jnp.arange(steps) < length, out,
                        jnp.int32(eos_token))
        stats["length"] = length.astype(jnp.int32)
    return out[None], stats


def _residual_resample(p: jnp.ndarray, q: jnp.ndarray,
                       key: jax.Array) -> jnp.ndarray:
    """Sample from the rejection residual ``norm(max(p - q, 0))`` — the
    distribution that makes draft-accept/resample EXACTLY equivalent to
    sampling from ``p`` (for every token x: q(x)·min(1, p/q) plus the
    total rejection mass times residual(x) sums to p(x); pinned
    analytically in tests/test_speculative.py). Falls back to ``p``
    itself in the measure-zero q==p case (zero residual)."""
    res = jnp.maximum(p - q, 0.0)
    total = jnp.sum(res)
    safe = jnp.where(total > 0, res / jnp.maximum(total, 1e-30), p)
    return jax.random.categorical(key, jnp.log(jnp.maximum(safe, 1e-30)))


def _filtered_probs(logits: jnp.ndarray, temperature: float,
                    top_k: Optional[int],
                    top_p: Optional[float]) -> jnp.ndarray:
    """logits (vocab,) -> the filtered sampling distribution — the SAME
    pipeline generate() (and the serving engine's per-slot sampler)
    samples from, so speculative sampling preserves exactly the
    distribution plain sampling uses."""
    return jax.nn.softmax(
        apply_sample_filters(logits[None], temperature, top_k, top_p),
        axis=-1)[0]


@partial(jax.jit, static_argnames=("target_cfg", "draft_cfg", "steps",
                                   "k", "temperature", "top_k", "top_p"))
def speculative_sample(target_params: dict, draft_params: dict,
                       prompt: jnp.ndarray,
                       target_cfg: TransformerConfig,
                       draft_cfg: TransformerConfig,
                       steps: int, key: jax.Array, k: int = 4,
                       temperature: float = 1.0,
                       top_k: Optional[int] = None,
                       top_p: Optional[float] = None
                       ) -> tuple[jnp.ndarray, dict]:
    """Speculative SAMPLING (temperature > 0): the draft proposes k
    tokens from its filtered distribution q; the target verifies in one
    extend; proposal j is accepted with probability
    ``min(1, p_j(x_j) / q_j(x_j))`` and the first rejection resamples
    from ``norm(max(p - q, 0))`` — the modified-rejection scheme whose
    emitted tokens are distributed EXACTLY as sampling from the target
    alone (same temperature/top-k/top-p pipeline as generate()). Greedy
    is the separate bit-exact path (:func:`speculative_generate`).

    Same loop shape, cache-rewind trick, batch-1 restriction, and stats
    as the greedy path."""
    if prompt.shape[0] != 1:
        raise ValueError(
            "speculative decode is the batch-1 latency path; run the "
            f"plain decode scan for batch {prompt.shape[0]}")
    if not 1 <= k:
        raise ValueError(f"k must be >= 1, got {k}")
    if temperature <= 0.0:
        raise ValueError(
            "speculative_sample needs temperature > 0; use "
            "speculative_generate for greedy")
    if draft_cfg.vocab_size != target_cfg.vocab_size:
        raise ValueError(
            f"draft and target must share a vocabulary: "
            f"{draft_cfg.vocab_size} != {target_cfg.vocab_size}")
    if prompt.shape[1] + steps + k > target_cfg.max_seq:
        raise ValueError(
            f"target max_seq {target_cfg.max_seq} must cover prompt + "
            f"steps + k = {prompt.shape[1] + steps + k}")
    if prompt.shape[1] + steps + k > draft_cfg.max_seq:
        raise ValueError(
            f"draft max_seq {draft_cfg.max_seq} must cover prompt + "
            f"steps + k = {prompt.shape[1] + steps + k}")

    t_cache = init_kv_cache(target_cfg, 1)
    d_cache = init_kv_cache(draft_cfg, 1)
    t_cache, t_logits = prefill(target_params, t_cache, prompt,
                                target_cfg)
    d_cache, _ = prefill(draft_params, d_cache, prompt, draft_cfg)
    key, k0 = jax.random.split(key)
    p0 = _filtered_probs(t_logits[0], temperature, top_k, top_p)
    cur0 = jax.random.categorical(
        k0, jnp.log(jnp.maximum(p0, 1e-30)))[None].astype(jnp.int32)

    buf_len = steps + k + 1
    out0 = jnp.zeros((buf_len,), jnp.int32).at[0].set(cur0[0])

    def round_body(carry):
        (t_cache, d_cache, out, n_out, cur, key, rounds, drafted,
         accepted) = carry
        key, kd, ka, kr = jax.random.split(key, 4)

        # -- draft: k sampled proposals, recording each q distribution
        def draft_one(c, kj):
            dc, tok = c
            dc, dl = decode_step(draft_params, dc, tok, draft_cfg)
            qj = _filtered_probs(dl[0], temperature, top_k, top_p)
            nxt = jax.random.categorical(
                kj, jnp.log(jnp.maximum(qj, 1e-30)))[None].astype(
                    jnp.int32)
            return (dc, nxt), (nxt[0], qj)

        (d_cache, _), (props, qs) = lax.scan(
            draft_one, (d_cache, cur), jax.random.split(kd, k))

        # -- target: one extend over [cur, g_1..g_{k-1}]
        block = jnp.concatenate([cur, props[:-1]])[None]
        t_cache, t_block_logits = extend(target_params, t_cache, block,
                                         target_cfg)
        ps = jax.vmap(
            lambda lg: _filtered_probs(lg, temperature, top_k, top_p))(
                t_block_logits[0])                       # (k, vocab)

        # -- accept test per proposal: u < p(x)/q(x)
        idx = jnp.arange(k)
        p_at = ps[idx, props]
        q_at = qs[idx, props]
        u = jax.random.uniform(ka, (k,))
        ok = u * q_at < p_at                # u < p/q, q>0 where sampled
        n_acc = jnp.argmin(jnp.concatenate(
            [ok, jnp.zeros((1,), bool)]).astype(jnp.int32))

        # first rejection resamples from the residual at that position
        n_res = jnp.minimum(n_acc, k - 1)
        resample = _residual_resample(ps[n_res], qs[n_res], kr).astype(
            jnp.int32)
        emit_vec = jnp.where(idx < n_acc, props, resample)
        emit_len = jnp.minimum(n_acc + 1, k)
        out = lax.dynamic_update_slice(out, emit_vec, (n_out,))
        new_cur = emit_vec[emit_len - 1][None]
        n_out = n_out + emit_len

        frontier = t_cache["pos"] - k + emit_len
        t_cache = {**t_cache, "pos": frontier}
        d_cache = {**d_cache, "pos": frontier}
        return (t_cache, d_cache, out, n_out, new_cur, key, rounds + 1,
                drafted + k, accepted + n_acc)

    def cond(carry):
        return carry[3] < steps

    init = (t_cache, d_cache, out0, jnp.asarray(1, jnp.int32), cur0,
            key, jnp.asarray(0, jnp.int32), jnp.asarray(0, jnp.int32),
            jnp.asarray(0, jnp.int32))
    (_, _, out, _, _, _, rounds, drafted, accepted) = lax.while_loop(
        cond, round_body, init)
    stats = {"rounds": rounds, "drafted": drafted, "accepted": accepted}
    return out[:steps][None], stats
