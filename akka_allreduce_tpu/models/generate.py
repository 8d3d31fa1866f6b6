"""Autoregressive decoding for the flagship transformer: KV cache + scan.

The reference is a training-side system (no inference path exists to
mirror), but a complete framework needs one: this module turns the trained
checkpoint into tokens. TPU-first shape discipline throughout: the KV cache
is a preallocated static ``(layers, batch, max_seq, heads, head_dim)``
buffer updated with ``lax.dynamic_update_slice`` at the decode position,
the decode loop is one ``lax.scan`` inside ``jit`` (no per-token Python,
no host round-trips mid-generation), and attention over the cache masks by
position instead of slicing to a dynamic length, so every step compiles to
the same static-shape program.

Numerics are pinned by a parity test (tests/test_generate.py): for any
prompt, incremental cached decode must reproduce the full-sequence forward
logits (same ops, same cast points) — the cache is an optimization, never
a different model. One documented exception: MoE expert CAPACITY derives
from the local token count (reference-free design choice), so a full
forward over t tokens can drop overflow tokens from popular experts while
single-token decode (capacity from b tokens) never does. Routing weights
are identical; parity is exact whenever capacity does not bind (generous
``capacity_factor``, which generation-time configs should use — dropping
tokens at decode time would be strictly worse, not more faithful).
"""

from __future__ import annotations

import dataclasses
import math
import operator
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from akka_allreduce_tpu.models.transformer import (
    TransformerConfig,
    apply_rope,
    embed_tokens,
    lm_logits,
    mla_project,
    rmsnorm,
)
from akka_allreduce_tpu.ops.pallas_kernels.attention import (
    latent_decode_attention,
    latent_keys_lie_minor,
    pick_latent_tiling,
)
from akka_allreduce_tpu.ops.pallas_kernels.dispatch import say_attention
from akka_allreduce_tpu.parallel.ep import dropless_moe, moe_ffn
from akka_allreduce_tpu.parallel.ring_attention import (
    NEG_INF,
    local_causal_attention,
)
from akka_allreduce_tpu.runtime.tracing import (
    SCOPE_ATTENTION,
    SCOPE_DENSE_FFN,
    SCOPE_KDA_CHUNK,
    SCOPE_KDA_MIXER,
    SCOPE_KDA_STEP,
    SCOPE_MLA_ATTENTION,
    SCOPE_SPARSE_INDEXER,
    SCOPE_SSM_MIXER,
    SCOPE_SSM_SCAN,
    SCOPE_SSM_STEP,
)

# the eps of the index key's LayerNorm (the source family's; no key of a
# config.json states it)
INDEX_NORM_EPS = 1e-6
# query rows that score the index keys, choose and attend at once: what
# bounds a prefill chunk's temporaries (scores of rows x index heads x
# max_seq in f32, rows x index_topk gathered latents)
QUERY_ROWS = 128
# cached rows of one lane that a block of query rows scores at once where
# the attention over an indexer's choice runs masked, in place
# (:func:`selected_attention_path`): f32 scores of 128 rows x 64 heads x
# 1,024 keys are 32 MB a block
KEY_ROWS = 1024


def init_kv_cache(cfg: TransformerConfig, batch: int,
                  kv_dtype: "str | None" = None) -> dict:
    """Static-shape cache: one (batch, max_seq, kv_heads, head_dim) K and V
    buffer per layer, plus the write position. Buffers use the model's
    compute dtype — the parity contract (and, for bf16 models, half the
    cache HBM) depends on the cached K/V matching what the full forward's
    attention consumed. Under grouped-query attention the cache holds only
    the kv_heads — the GQA decode win: cache HBM shrinks by the group
    factor.

    ``kv_dtype="int8"`` switches to a quantized cache: K/V are stored as
    symmetric int8 with one f32 scale per written (position, head) vector
    (the chunk granularity of ops/pallas_kernels/quantized.py, here the
    head is the chunk), quartering (bf16: halving) cache HBM at a bounded
    logit error (pinned by tests/test_generate.py::TestQuantizedKV).
    Scales ride in ``k_scale``/``v_scale`` entries; every cache consumer
    (decode_step / prefill / extend / the serving engine) branches on
    their presence, so the pytree structure IS the format switch."""
    if cfg.kda_layers:
        # the delta-rule hybrid: a "kda" layer holds its matrix state a head
        # (float32, head size squared, ``kda_state[j]`` layer
        # ``kda_layers[j]``'s) and the last ``kda_conv - 1`` inputs of its
        # q, k and v convolutions (``kda_conv``), a buffer a layer, both
        # OVERWRITTEN as the lane advances (:func:`init_kv_cache`'s hybrid
        # below says why a buffer a layer); a "latent" layer a latent a
        # position, as the latent kinds hold it
        if kv_dtype is not None:
            raise NotImplementedError(
                f"kv_dtype={kv_dtype!r}: the delta-rule hybrid's cache has no "
                f"quantized format (missing: a scale a cached latent; the "
                f"recurrent state stays float32)")
        hd = cfg.kda_head_dim
        return {
            "kda_state": tuple(
                jnp.zeros((batch, cfg.kda_heads, hd, hd), jnp.float32)
                for _ in cfg.kda_layers),
            "kda_conv": tuple(
                jnp.zeros((batch, cfg.kda_conv - 1, 3 * cfg.kda_dim),
                          cfg.dtype) for _ in cfg.kda_layers),
            "latent": jnp.zeros((cfg.n_attentions, batch, cfg.max_seq,
                                 cfg.latent_row), cfg.dtype),
            "pos": jnp.zeros((), jnp.int32),
        }
    if cfg.attention == "mla":
        # the latent cache is a pytree key of its own: per attention (two
        # a double layer) and position the normed, scaled latent and the
        # rotary key all heads share - kv_lora_rank + qk_rope_head_dim
        # numbers, whatever the number of heads
        if kv_dtype is not None:
            raise NotImplementedError(
                f"kv_dtype={kv_dtype!r}: the latent cache has no "
                f"quantized format (missing: a scale a cached latent and "
                f"its dequantize-on-read in `_latent_attention`; where an "
                f"indexer chooses, a scale an index key too)")
        cache = {"latent": jnp.zeros((cfg.n_attentions, batch, cfg.max_seq,
                                      cfg.latent_row), cfg.dtype),
                 "pos": jnp.zeros((), jnp.int32)}
        if cfg.indexed:
            # one index key a token for each layer that has an indexer
            cache["index_k"] = jnp.zeros(
                (len(cfg.full_layers), batch, cfg.max_seq,
                 cfg.index_head_dim), cfg.dtype)
        return cache
    if cfg.hybrid:
        # by layer kind: a state-space layer holds what its recurrence has
        # come to (float32, ``ssm_heads`` x ``ssm_head_dim`` x ``ssm_state``
        # a lane; ``ssm_state[j]`` is layer ``ssm_layers[j]``'s) and the
        # last ``ssm_conv - 1`` inputs of its convolution (``conv_state``);
        # both are OVERWRITTEN as the lane advances and neither grows with
        # the context. An attention layer holds keys and values a position.
        if kv_dtype is not None:
            raise NotImplementedError(
                f"kv_dtype={kv_dtype!r}: the hybrid's cache has no quantized "
                f"format (missing: a scale a written key and value of its "
                f"attention layers; the recurrent state stays float32)")
        n_ssm, n_attn = len(cfg.ssm_layers), len(cfg.attention_layers)
        kv_shape = (n_attn, batch, cfg.max_seq, cfg.kv_heads, cfg.head_dim)
        # a buffer a state-space layer, not one stacked over the layers: a
        # step rewrites ALL of a layer's state, so each layer's update is
        # an elementwise program over its own donated buffer. (Stacked, the
        # step is a chain of in-place slice updates of one 2.4 GB buffer
        # from which each layer also reads its slice, and the TPU's
        # compiler rematerialised the first layer's update for its two
        # readers in place: that layer's state advanced twice a step; chip
        # runs, PR 34, PERF.md section 6.)
        return {
            "ssm_state": tuple(
                jnp.zeros((batch, cfg.ssm_heads, cfg.ssm_head_dim,
                           cfg.ssm_state), jnp.float32)
                for _ in range(n_ssm)),
            "conv_state": tuple(
                jnp.zeros((batch, cfg.ssm_conv - 1, cfg.ssm_conv_dim),
                          cfg.dtype) for _ in range(n_ssm)),
            "k": jnp.zeros(kv_shape, cfg.dtype),
            "v": jnp.zeros(kv_shape, cfg.dtype),
            "pos": jnp.zeros((), jnp.int32),
        }
    shape = (cfg.n_layers, batch, cfg.max_seq, cfg.kv_heads, cfg.head_dim)
    if kv_dtype is None:
        return {
            "k": jnp.zeros(shape, cfg.dtype),
            "v": jnp.zeros(shape, cfg.dtype),
            "pos": jnp.zeros((), jnp.int32),
        }
    if str(kv_dtype) not in ("int8",):
        raise ValueError(f"unsupported kv_dtype {kv_dtype!r} "
                         f"(None = model dtype, or 'int8')")
    scale_shape = shape[:-1]
    return {
        "k": jnp.zeros(shape, jnp.int8),
        "v": jnp.zeros(shape, jnp.int8),
        "k_scale": jnp.zeros(scale_shape, jnp.float32),
        "v_scale": jnp.zeros(scale_shape, jnp.float32),
        "pos": jnp.zeros((), jnp.int32),
    }


def init_kv_pool(cfg: TransformerConfig, num_pages: int, page_size: int,
                 kv_dtype: "str | None" = None) -> dict:
    """The PAGED twin of :func:`init_kv_cache`: one flat
    ``(layers, num_pages, page_size, kv_heads, head_dim)`` K and V pool
    shared by every request, addressed through per-request page tables
    (serving/paging.py owns which page belongs to whom). Where the slot
    cache's HBM is ``slots * max_seq`` positions whether or not they
    are used, the pool's is exactly ``num_pages * page_size`` —
    capacity becomes a budget the admission plane spends page by page
    instead of a per-slot reservation.

    Same dtype/format contract as the slot cache: model compute dtype
    by default, ``kv_dtype="int8"`` for the quantized format with
    per-(position, head) f32 scales riding in ``k_scale``/``v_scale``
    (shape ``(layers, num_pages, page_size, kv_heads)``), and the
    pytree structure IS the format switch for every consumer. No
    ``pos`` entry — positions are per-request host state in the paged
    engine."""
    if num_pages < 1 or page_size < 1:
        raise ValueError(f"num_pages/page_size must be >= 1, got "
                         f"{num_pages}/{page_size}")
    if cfg.hybrid:
        raise NotImplementedError(
            f"the paged pool cannot hold {cfg.new_kind} (missing: no page "
            f"holds a recurrent state - it is one buffer a lane that every "
            f"step overwrites, so it has no positions to page, and a shared "
            f"prefix would need a snapshot of it a page boundary)")
    if cfg.new_kind is not None:
        raise NotImplementedError(
            f"the paged pool cannot hold {cfg.new_kind} (missing: a latent "
            f"page, an index-key page where an indexer chooses, and "
            f"cached-block functions that read them through the page "
            f"table)")
    shape = (cfg.n_layers, num_pages, page_size, cfg.kv_heads,
             cfg.head_dim)
    if kv_dtype is None:
        return {"k": jnp.zeros(shape, cfg.dtype),
                "v": jnp.zeros(shape, cfg.dtype)}
    if str(kv_dtype) not in ("int8",):
        raise ValueError(f"unsupported kv_dtype {kv_dtype!r} "
                         f"(None = model dtype, or 'int8')")
    return {"k": jnp.zeros(shape, jnp.int8),
            "v": jnp.zeros(shape, jnp.int8),
            "k_scale": jnp.zeros(shape[:-1], jnp.float32),
            "v_scale": jnp.zeros(shape[:-1], jnp.float32)}


def quantize_kv(x: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(..., head_dim) f32/bf16 -> (int8 values, f32 scales (...,)).

    The quantized.py idiom at KV granularity: symmetric per-chunk scale
    (abs-max / 127, floored at 1e-30 so all-zero vectors divide cleanly),
    clip to [-127, 127] — but ROUND-TO-NEAREST instead of stochastic:
    a cache entry is re-read every step, so the rounding must be
    deterministic (stochastic rounding buys unbiasedness across many
    independent sums, which gradient transport has and a KV reuse does
    not)."""
    xf = x.astype(jnp.float32)
    abs_max = jnp.max(jnp.abs(xf), axis=-1)
    scales = jnp.maximum(abs_max / 127.0, 1e-30)
    q = jnp.clip(jnp.round(xf / scales[..., None]), -127.0, 127.0)
    return q.astype(jnp.int8), scales


def dequantize_kv(values: jnp.ndarray, scales: jnp.ndarray,
                  dtype) -> jnp.ndarray:
    """Inverse of :func:`quantize_kv`, cast to the compute ``dtype``."""
    return (values.astype(jnp.float32) * scales[..., None]).astype(dtype)


def _cached_attention(q: jnp.ndarray, k_all: jnp.ndarray,
                      v_all: jnp.ndarray, pos: jnp.ndarray,
                      window: "int | None" = None) -> jnp.ndarray:
    """q: (b, 1, h, d); k_all/v_all: (b, max_seq, h_kv, d) with positions
    <= pos valid. Masked softmax over the static buffer — the causal
    mask IS the length mask at decode time. GQA (h_kv < h) runs as a
    grouped einsum against the NARROW cache: no repeated K/V is ever
    materialised, so decode reads cache HBM at the reduced width.

    Sliding-window decode gathers only the last ``window`` cache
    positions (a static-size ``dynamic_slice`` anchored at pos) before
    the score einsum, so per-step cost is O(window), not O(max_seq) —
    positions outside the window contribute exactly 0 to the softmax
    either way (NEG_INF underflows to 0.0 in exp), so the slice changes
    cost, not math."""
    # op-for-op the math of local_causal_attention (same scale form, f32
    # score/softmax, same cast points) so cached decode is bit-identical
    # to the full forward at every valid position
    b, one, h, d = q.shape
    h_kv = k_all.shape[2]
    g = h // h_kv
    qg = q.reshape(b, one, h_kv, g, d)
    scale = d ** -0.5
    if window is not None and window < k_all.shape[1]:
        # clamp start into [0, max_seq - window]; early positions keep
        # the full slice and mask the not-yet-written tail below
        start = jnp.clip(pos - (window - 1), 0, k_all.shape[1] - window)
        k_all = lax.dynamic_slice_in_dim(k_all, start, window, axis=1)
        v_all = lax.dynamic_slice_in_dim(v_all, start, window, axis=1)
        k_idx = start + jnp.arange(window)
    else:
        k_idx = jnp.arange(k_all.shape[1])
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k_all,
                        preferred_element_type=jnp.float32) * scale
    # the slice construction guarantees every sliced position is within
    # the window, so `k_idx <= pos` is the whole mask: it cuts the
    # not-yet-written tail (and, pre-slice, positions beyond pos)
    valid = k_idx <= pos
    scores = jnp.where(valid[None, None, None, None, :], scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", p.astype(v_all.dtype), v_all,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, one, h, d).astype(q.dtype)


def _rope_slots(x: jnp.ndarray, positions: jnp.ndarray,
                theta: float) -> jnp.ndarray:
    """apply_rope (models/transformer.py) with a PER-ROW position:
    x (slots, 1, heads, d), positions (slots,). Same formula, f32
    phases, half-split pairing, cast points — the angle for row b here
    is bitwise the angle decode_step computes for its whole batch at
    scalar pos = positions[b], so per-slot rope output matches the
    standalone decode exactly."""
    d = x.shape[-1]
    half = d // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    angles = positions.astype(jnp.float32)[:, None] * freqs[None, :]
    cos = jnp.cos(angles)[:, None, None, :]  # (slots, 1, 1, D/2)
    sin = jnp.sin(angles)[:, None, None, :]
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., :half], xf[..., half:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos],
        axis=-1).astype(x.dtype)


def _slot_cached_attention(q: jnp.ndarray, k_all: jnp.ndarray,
                           v_all: jnp.ndarray, pos: jnp.ndarray,
                           window: "int | None" = None,
                           scale: "float | None" = None) -> jnp.ndarray:
    """``_cached_attention`` with the scalar decode position generalized
    to (slots,): row b masks by ITS ``pos[b]``.
    Same einsum structure, f32 score/softmax, and cast points; the
    contraction runs over the full static ``max_seq`` buffer for every
    row (the mask is per-row data, the shape is not), which is exactly
    the no-window standalone program — so per-row outputs are bitwise
    equal to a batch-1 ``decode_step`` at that position. Sliding-window
    decode keeps the mask-only form (positions outside the window mask
    to NEG_INF; exp underflows to exactly 0.0): per-step cost stays
    O(max_seq) rather than generate()'s O(window) slice, a trade for
    per-row window offsets that only shows at long max_seq.

    ``pos`` (slots, t) gives every one of t queries a row its own
    position (a prefill's or a chunk's queries through the cache: the
    hybrid's attention without positions, where the mask alone orders the
    tokens), :data:`QUERY_ROWS` queries against the whole lane at a time
    (a chunk's 2,048 queries x 32 heads x 6,144 keys in float32 would be
    1.6 GB at once). ``scale`` is a STATED score scale (default
    ``d ** -0.5``)."""
    h, d = q.shape[2:]
    h_kv = k_all.shape[2]
    if scale is None:
        scale = d ** -0.5

    def attend(q, pos):
        qg = q.reshape(q.shape[:2] + (h_kv, h // h_kv, d))
        k_idx = jnp.arange(k_all.shape[1])
        scores = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k_all,
                            preferred_element_type=jnp.float32) * scale
        valid = k_idx[None, :] <= pos[..., None]  # (slots, [t,] max_seq)
        if window is not None:
            valid &= k_idx[None, :] > pos[..., None] - window
        # -> (slots, 1, 1, t, max_seq)
        valid = jnp.expand_dims(valid, (1, 2, 3)[:5 - valid.ndim])
        scores = jnp.where(valid, scores, NEG_INF)
        p = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("bhgqk,bkhd->bqhgd", p.astype(v_all.dtype), v_all,
                         preferred_element_type=jnp.float32)
        return out.reshape(q.shape).astype(q.dtype)

    return _over_query_rows(attend, q, pos)


def _write_slot_rows(cache: jnp.ndarray, layer: int, vals: jnp.ndarray,
                     pos: jnp.ndarray,
                     mask: "jnp.ndarray | None" = None) -> jnp.ndarray:
    """Write ``vals[s]`` at ``cache[layer, s, pos[s]]`` for every slot.
    An unrolled loop of ``dynamic_update_slice`` (slots is small and
    static) rather than one ``.at[layer, rows, pos].set`` scatter: with
    the engine state donated, DUS updates the buffer in place, and the
    XLA:CPU scatter lowering measured ~5x slower per write. Placement
    only — the written values are identical either way. On the v5e at 128
    lanes x 8 latent caches (1,024 unrolled writes) the step takes 36.2 ms
    and with one scatter a cache 49.6 ms (chip runs, PR 26): kept.

    ``mask`` (slots,) bool: a False lane keeps its old cache value at
    ``pos[s]`` (the multi-step block's frozen lanes — the write becomes
    a read-select-write of one tiny row, still a DUS the donation keeps
    in place)."""
    for s in range(vals.shape[0]):
        val = vals[s][None, None, None]
        idx = (layer, s, pos[s]) + (0,) * (vals.ndim - 1)
        if mask is not None:
            old = lax.dynamic_slice(cache, idx, val.shape)
            val = jnp.where(mask[s], val, old)
        cache = lax.dynamic_update_slice(cache, val, idx)
    return cache


# -- one cached-block function per block kind -----------------------------
#
# ``decode_step``, ``prefill`` and the serving engine's per-slot step
# (serving/engine.py ``_slot_decode_step``) run the same block over the
# same cache and differ only in WHERE: which positions the rotary phases
# take, where the new keys land in the buffer, and what the queries attend.
# :class:`CacheOps` is that difference; a block's mathematics is written
# once a kind, in ``_dense_cached_block`` and ``_shortcut_cached_block``.

@dataclasses.dataclass(frozen=True)
class CacheOps:
    """``pos``: None for a prefill of positions 0..t-1 (the queries attend
    the block's fresh keys); a scalar for one decode position of the whole
    batch; (b,) for a position a row (the slot engine). ``write_mask``
    (b,) freezes rows' cache writes (per-row positions only). ``counted``
    (b*t,) bool: the tokens an expert layer's counts see (None: all).
    ``offset`` and ``lane`` (scalars, with ``pos`` None and b = 1) make
    the prefill a CHUNK: positions offset..offset+t-1 of cache lane
    ``lane``, which holds the positions before them (the layer-by-layer
    kinds only: their queries attend through the cache, and a state-space
    layer scans on from the lane's state; at ``offset`` 0 from zeros).
    For a state-space layer ``counted`` also says which tokens advance the
    state and enter the convolution's tail (padding does neither)."""
    pos: Optional[jnp.ndarray] = None
    write_mask: Optional[jnp.ndarray] = None
    counted: Optional[jnp.ndarray] = None
    offset: Optional[jnp.ndarray] = None
    lane: Optional[jnp.ndarray] = None

    def rope(self, x: jnp.ndarray, theta: float) -> jnp.ndarray:
        if self.pos is None:
            at = jnp.arange(x.shape[1])
            return apply_rope(
                x, at if self.offset is None else self.offset + at, theta)
        if self.pos.ndim == 0:
            return apply_rope(x, self.pos[None], theta)
        return _rope_slots(x, self.pos, theta)

    def write(self, buf: jnp.ndarray, i: int,
              vals: jnp.ndarray) -> jnp.ndarray:
        """``vals`` (b, t, ...) into ``buf[i]`` (b, max_seq, ...)."""
        if self.pos is not None and self.pos.ndim:
            return _write_slot_rows(buf, i, vals[:, 0], self.pos,
                                    self.write_mask)
        at = 0 if self.pos is None else self.pos
        if self.offset is not None:
            at = self.offset
        return lax.dynamic_update_slice(
            buf, vals[None], (i, 0 if self.lane is None else self.lane, at)
            + (0,) * (vals.ndim - 2))

    def positions(self, b: int, t: int) -> jnp.ndarray:
        """The (b, t) positions of the block's tokens."""
        if self.pos is None:
            at = jnp.arange(t, dtype=jnp.int32)
            if self.offset is not None:
                at = self.offset + at
            return jnp.broadcast_to(at, (b, t))
        return jnp.broadcast_to(self.pos, (b,))[:, None].astype(jnp.int32)

    def lane_rows(self, buf: jnp.ndarray, i: int) -> jnp.ndarray:
        """``buf[i]`` as the block's batch sees it: (b, max_seq, ...), a
        chunk's one lane alone."""
        if self.lane is None:
            return buf[i]
        return lax.dynamic_index_in_dim(buf[i], self.lane, 0)


def _dense_cached_block(layer: dict, x: jnp.ndarray, kv: dict, i: int,
                        cfg: TransformerConfig, ops: CacheOps):
    """The Llama-family block (transformer_block's math: same layer dict,
    norms, residual order and cast points) with attention served through
    the cache ``kv`` (``k``/``v`` [+ scales], layer ``i``). Returns
    (x, kv, None)."""
    b, t, _ = x.shape
    kv = dict(kv)
    quantized = "k_scale" in kv
    with jax.named_scope(SCOPE_ATTENTION):
        h = rmsnorm(x, layer["ln1"], cfg.norm_eps)
        q = (h @ layer["wq"]).reshape(b, t, cfg.n_heads, cfg.head_dim)
        k = (h @ layer["wk"]).reshape(b, t, cfg.kv_heads, cfg.head_dim)
        v = (h @ layer["wv"]).reshape(b, t, cfg.kv_heads, cfg.head_dim)
        if cfg.rope:
            q = ops.rope(q, cfg.rope_theta)
            k = ops.rope(k, cfg.rope_theta)
        if quantized:
            kq, ks = quantize_kv(k)
            vq, vs = quantize_kv(v)
            kv["k"] = ops.write(kv["k"], i, kq)
            kv["v"] = ops.write(kv["v"], i, vq)
            kv["k_scale"] = ops.write(kv["k_scale"], i, ks)
            kv["v_scale"] = ops.write(kv["v_scale"], i, vs)
        else:
            kv["k"] = ops.write(kv["k"], i, k.astype(kv["k"].dtype))
            kv["v"] = ops.write(kv["v"], i, v.astype(kv["v"].dtype))
        if ops.pos is None:
            # prompt positions attend the freshly-computed block K/V, not
            # the cache, so prefill logits are identical under either
            # cache format — quantization error enters at decode-time
            # REREADS only
            attn = local_causal_attention(q, k, v, window=cfg.attn_window)
        else:
            if quantized:
                k_all = dequantize_kv(kv["k"][i], kv["k_scale"][i],
                                      cfg.dtype)
                v_all = dequantize_kv(kv["v"][i], kv["v_scale"][i],
                                      cfg.dtype)
            else:
                k_all, v_all = kv["k"][i], kv["v"][i]
            attend = (_slot_cached_attention if ops.pos.ndim
                      else _cached_attention)
            attn = attend(q, k_all, v_all, ops.pos, window=cfg.attn_window)
        x = x + attn.reshape(b, t, -1) @ layer["wo"]

    h = rmsnorm(x, layer["ln2"], cfg.norm_eps)
    if "router" in layer:
        y, _aux = moe_ffn(h, layer, cfg.moe, axis_name=None)
        return x + y, kv, None
    with jax.named_scope(SCOPE_DENSE_FFN):
        if "w3" in layer:
            x = x + (jax.nn.silu(h @ layer["w1"])
                     * (h @ layer["w3"])) @ layer["w2"]
        else:
            x = x + jax.nn.gelu(h @ layer["w1"]) @ layer["w2"]
    return x, kv, None


def _latent_attention(q: jnp.ndarray, latent: jnp.ndarray,
                      pos: jnp.ndarray, rank: int,
                      scale: float) -> jnp.ndarray:
    """Decode attention over the latent itself (absorbed projections):
    the pure-JAX formula. It is what runs on the CPU (so every parity pin
    that holds the engine bitwise to ``generate()`` keeps its jaxpr), for
    ``decode_step``'s scalar position on any backend, and as the oracle
    of the fused kernel that takes its place in the slot engine's step on
    the TPU (:func:`latent_decode_path`). It reads every one of the
    ``max_seq`` positions twice and holds the f32 scores between the
    reads, whatever ``pos`` says: the cost the kernel exists to drop.

    q (b, 1, h, rank + rope): each head's query folded through the key
    half of the up-projection, then its rotary part; latent (b, max_seq,
    rank + rope): what the cache holds, positions <= pos valid (a scalar,
    or one a row). Scores are q . latent over all rank + rope columns;
    the value is the latent's first ``rank`` columns. Returns (b, 1, h,
    rank). The weighted sum runs over all the columns and the rotary ones
    are dropped after it: a slice of the buffer ahead of the matmul would
    be a copy of the cache."""
    b = q.shape[0]
    pos = jnp.broadcast_to(pos, (b,))
    # the one query position is squeezed out: a plain batched matmul
    scores = jnp.einsum("bhc,bkc->bhk", q[:, 0], latent,
                        preferred_element_type=jnp.float32) * scale
    valid = jnp.arange(latent.shape[1])[None, :] <= pos[:, None]
    scores = jnp.where(valid[:, None, :], scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhk,bkc->bhc", p.astype(latent.dtype), latent,
                     preferred_element_type=jnp.float32)
    return out[:, None, :, :rank].astype(q.dtype)


def latent_decode_path(pos, latent) -> "tuple[bool, tuple[int, int]] | None":
    """How a decode step attends the latent cache ``latent`` (attentions,
    lanes, max_seq, rank + rope) at ``pos``, from what the code can see:
    None for the pure-JAX :func:`_latent_attention`; else (the fused
    kernel's ``interpret`` flag, its (lanes a grid step, key block)
    tiling). The kernel (ops/pallas_kernels/attention.py
    ``latent_decode_attention``) runs where all of these hold: the default
    backend is the TPU (interpreter-mode Pallas on a CPU would only be
    slower, and the CPU's jaxpr is what the parity pins hold); ``pos`` has
    a position a row (the slot engine; ``decode_step``'s scalar position
    keeps the formula ``generate()`` is the tests' reference with); the
    cache is a float dtype; a tiling exists; and the device keeps the
    cache with its positions minor, the way the kernel reads it (at the
    published 512 + 64 columns it does; where it does not, the kernel's
    view of the cache would be a transpose of all of it a call). No
    option chooses."""
    if (pos.ndim != 1 or jax.default_backend() != "tpu"
            or not jnp.issubdtype(latent.dtype, jnp.floating)):
        return None
    tiling = pick_latent_tiling(*latent.shape[1:], latent.dtype)
    if tiling is None or not latent_keys_lie_minor(
            tuple(latent.shape), jnp.dtype(latent.dtype)):
        return None
    return False, tiling


def _mla_cached_attention(p: dict, x: jnp.ndarray, kv: dict, a: int,
                          cfg: TransformerConfig, ops: CacheOps):
    """Latent attention ``a`` over x (b, t, d) through ``kv["latent"]``:
    the cache takes the normed, scaled latent and the rotary key (after
    RoPE), ``latent_dim`` numbers a token. A prefill expands keys and
    values from its fresh latents; a decode step folds the up-projection's
    key half into the query and attends the cached latent directly. Both
    are the same function of the same cache. The decode's read of the
    cache is :func:`latent_decode_path`'s choice, said once on stderr
    (``attention[latent_decode]``): the fused kernel over each lane's live
    key blocks in the slot engine's step on the TPU, the pure-JAX formula
    over the whole buffer everywhere else; projections, rotary phases and
    the cache write are the same on both. A chunk through the cache (``ops``
    with a ``lane``, the delta-rule hybrid's prefill) attends the lane's
    rows through :func:`_masked_latent_attention`. Where ``p`` has a
    ``w_gate`` each head's output is gated by a sigmoid of the normed
    input (the delta-rule hybrid's latent layers), and ``cfg.rope_interleave`` rotates
    the interleaved pairs. Returns (the attention's output through ``wo``,
    kv)."""
    b, t, _ = x.shape
    heads, rank = cfg.n_heads, cfg.kv_lora_rank
    nope, vd = cfg.qk_nope_head_dim, cfg.v_head_dim
    kv = dict(kv)
    def rope(v):
        if cfg.rope_interleave:
            v = _pairs_apart(v)
        return ops.rope(v, cfg.rope_theta)
    h, _c_q, q_nope, q_rope, c_kv, k_rope = mla_project(p, x, cfg, rope)
    latent = jnp.concatenate([c_kv, k_rope[:, :, 0]], axis=-1)
    kv["latent"] = ops.write(kv["latent"], a,
                             latent.astype(kv["latent"].dtype))
    up = p["wkv_b"].reshape(rank, heads, nope + vd)
    if ops.pos is None and ops.lane is None:
        kv_heads = jnp.einsum("btr,rhe->bthe", c_kv, up)
        k = jnp.concatenate(
            [kv_heads[..., :nope],
             jnp.broadcast_to(k_rope, (b, t, heads, k_rope.shape[-1]))],
            axis=-1)
        out = local_causal_attention(
            jnp.concatenate([q_nope, q_rope], axis=-1), k,
            kv_heads[..., nope:])
    else:
        q_lat = jnp.concatenate(
            [jnp.einsum("bthn,rhn->bthr", q_nope, up[..., :nope]), q_rope],
            axis=-1)
        scale = (nope + q_rope.shape[-1]) ** -0.5
        if ops.pos is None:
            # a chunk through the cache: its queries attend the lane's rows
            # at or before their own positions, those the chunks before it
            # wrote and its own: GLM-5.2's masked pass with causality for
            # membership
            positions = ops.positions(b, t)
            n_seq = kv["latent"].shape[2]
            blk = math.gcd(n_seq, KEY_ROWS)
            say_attention("latent_chunk",
                          "reference:_masked_latent_attention", q_lat,
                          key_block=blk)
            out_lat = _masked_latent_attention(
                q_lat, kv["latent"], a, ops.lane,
                jnp.arange(n_seq)[None, None, :] <= positions[..., None],
                positions, rank, scale, blk)
        elif (path := latent_decode_path(ops.pos, kv["latent"])) is None:
            say_attention("latent_decode", "reference:_latent_attention",
                          q_lat)
            out_lat = _latent_attention(q_lat, kv["latent"][a], ops.pos,
                                        rank, scale)
        else:
            interpret, tiling = path
            say_attention("latent_decode", "latent_decode_attention",
                          q_lat, interpret=interpret, group=tiling[0],
                          blk=tiling[1])
            # the whole cache goes in and the kernel's index maps take
            # ``a``: kv["latent"][a] ahead of a custom call is a copy
            out_lat = latent_decode_attention(
                q_lat[:, 0], kv["latent"], a, ops.pos, rank, scale,
                tiling=tiling, interpret=interpret)[:, None]
        out = jnp.einsum("bthr,rhv->bthv", out_lat, up[..., nope:])
    if "w_gate" in p:
        # the head-wise output gate
        out = out * jax.nn.sigmoid(h @ p["w_gate"])[..., None]
    return out.reshape(b, t, heads * vd) @ p["wo"], kv


def _pairs_apart(x: jnp.ndarray) -> jnp.ndarray:
    """x's last axis with its interleaved rotary pairs (x[2i], x[2i+1])
    moved to the places (x[i], x[i + d/2]) that :func:`apply_rope` rotates
    together: queries and keys alike, so that every q . k is the
    interleaved rotation's."""
    return jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)


def _shortcut_cached_block(layer: dict, x: jnp.ndarray, kv: dict, i: int,
                           cfg: TransformerConfig, ops: CacheOps):
    """The shortcut-connected double layer: two latent attentions
    (cache entries 2i and 2i+1), two dense SwiGLU FFNs, and one expert
    layer that reads the first half's post-attention norm and is added at
    the end of the second half. Returns (x, kv, the expert layer's
    counts: parallel/ep.py ``dropless_moe``)."""
    b, t, d = x.shape

    def ffn(p, h):
        with jax.named_scope(SCOPE_DENSE_FFN):
            return (jax.nn.silu(h @ p["w1"]) * (h @ p["w3"])) @ p["w2"]

    def attention(j, x, kv):
        with jax.named_scope(SCOPE_MLA_ATTENTION):
            return _mla_cached_attention(layer["mla"][j], x, kv, 2 * i + j,
                                         cfg, ops)

    out, kv = attention(0, x, kv)
    x = x + out
    h = rmsnorm(x, layer["ffn"][0]["ln"], cfg.norm_eps)
    m, counts = dropless_moe(h.reshape(b * t, d), layer["moe"],
                             cfg.experts, ops.counted)
    x = x + ffn(layer["ffn"][0], h)
    out, kv = attention(1, x, kv)
    x = x + out
    h = rmsnorm(x, layer["ffn"][1]["ln"], cfg.norm_eps)
    return x + ffn(layer["ffn"][1], h) + m.reshape(b, t, d), kv, counts


def _over_query_rows(fn, *arrays):
    """``fn`` over arrays (b, t, ...) -> (b, t, ...), at most
    :data:`QUERY_ROWS` of the t query rows at a time (one after the other:
    a chunk's temporaries are one block's)."""
    t = arrays[0].shape[1]
    if t <= QUERY_ROWS:
        return fn(*arrays)
    blocks = -(-t // QUERY_ROWS)
    pad = blocks * QUERY_ROWS - t

    def split(a):
        a = jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
        return jnp.moveaxis(a.reshape(
            (a.shape[0], blocks, QUERY_ROWS) + a.shape[2:]), 1, 0)
    out = lax.map(lambda xs: fn(*xs), tuple(split(a) for a in arrays))
    out = jnp.moveaxis(out, 0, 1)
    return out.reshape((out.shape[0], blocks * QUERY_ROWS)
                       + out.shape[3:])[:, :t]


def _layernorm(x: jnp.ndarray, gain: jnp.ndarray, bias: jnp.ndarray,
               eps: float) -> jnp.ndarray:
    """LayerNorm with a gain and a bias, statistics in f32 (rmsnorm's
    precision rule)."""
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mean), axis=-1, keepdims=True)
    return ((xf - mean) * lax.rsqrt(var + eps)).astype(x.dtype) * gain + bias


def _rope_first(x: jnp.ndarray, n: int, ops: CacheOps,
                theta: float) -> jnp.ndarray:
    """RoPE on the first ``n`` of x's (b, t, heads, d) last axis."""
    return jnp.concatenate([ops.rope(x[..., :n], theta), x[..., n:]],
                           axis=-1)


def _index_select(p: dict, c_q: jnp.ndarray, h: jnp.ndarray, kv: dict,
                  f: int, cfg: TransformerConfig, ops: CacheOps,
                  positions: jnp.ndarray):
    """A full layer's indexer over h (b, t, d) and the query's bottleneck
    c_q: writes the tokens' index keys into ``kv["index_k"][f]``, scores
    every cached position of each token's lane, ``I[t, s] = sum_h w[t, h]
    relu(q[t, h] . k[s])``, and picks the ``index_topk`` best among the
    positions at or before the token's own. Returns (chosen (b, t, k)
    int32 positions, kv). Where fewer than k positions are live the rest
    of ``chosen`` lie past the token's position: whoever attends holds
    ``chosen <= position`` to be the live ones. Exact: ``lax.top_k``, no
    approximation; a chunk's queries, which read one lane, score and sort
    the shortest prefix of it that holds their live keys
    (:func:`_key_prefixes`)."""
    b, t, _ = h.shape
    heads, hd = cfg.index_n_heads, cfg.index_head_dim
    kv = dict(kv)
    q = _rope_first((c_q @ p["wq_b"]).reshape(b, t, heads, hd),
                    cfg.qk_rope_head_dim, ops, cfg.rope_theta)
    k = _layernorm(h @ p["wk"], p["k_norm"], p["k_bias"], INDEX_NORM_EPS)
    k = _rope_first(k[:, :, None], cfg.qk_rope_head_dim, ops,
                    cfg.rope_theta)[:, :, 0]
    kv["index_k"] = ops.write(kv["index_k"], f,
                              k.astype(kv["index_k"].dtype))
    w = (h @ p["ww"]).astype(jnp.float32) * (heads ** -0.5 * hd ** -0.5)
    keys = ops.lane_rows(kv["index_k"], f)           # (b, max_seq, hd)
    n_keys = keys.shape[1]
    top = min(cfg.index_topk, n_keys)
    prefixes = _key_prefixes(n_keys, top, ops.lane is not None)

    def among(n: int):
        """The choice among the lane's first ``n`` keys (they hold every
        live one)."""
        def run(q, w, positions):
            scores = jnp.einsum("bthd,bsd->bths", q, keys[:, :n],
                                preferred_element_type=jnp.float32)
            scores = jnp.einsum("bths,bth->bts", jax.nn.relu(scores), w)
            live = jnp.arange(n)[None, None, :] <= positions[:, :, None]
            scores = jnp.where(live, scores, -jnp.inf)
            # the top-k over a matrix of rows: as (b, 1, max_seq) the chip
            # lays one row a tile and the decode step's sort takes 4.8 ms
            # where this takes under 2 (chip runs, PR 31)
            rows = scores.reshape(-1, n)
            return lax.top_k(rows, top)[1].reshape(scores.shape[:2] + (top,))
        return run

    def choose(q, w, positions):
        if len(prefixes) == 1:
            return among(n_keys)(q, w, positions)
        # the shortest prefix past the block's last position: the first
        # whose length exceeds it
        which = jnp.sum(jnp.asarray(prefixes) <= jnp.max(positions))
        return lax.switch(which, [among(n) for n in prefixes], q, w,
                          positions)

    return _over_query_rows(choose, q, w, positions).astype(jnp.int32), kv


def _key_prefixes(n_keys: int, top: int, one_lane: bool) -> tuple:
    """The prefixes of a lane's ``n_keys`` index keys that an indexer may
    score and sort in place of all of them, shortest first, the whole lane
    last. A sort costs what its width costs whatever the lane holds (on
    the v5e 2.2 ms a block of 128 query rows x 24,576 keys, 70 ms of every
    chunk; chip run, PR 33), and a chunk's block of query rows reads ONE
    lane up to one last position: it takes the shortest prefix that holds
    every live key (an eighth, a quarter, a half of the lane; none shorter
    than ``top``, so the top-k's shape is the same), and the same exact
    top-k over it chooses the same positions in the same order. A decode
    step's lanes each end elsewhere and keep the one sort over the lane."""
    if not one_lane:
        return (n_keys,)
    return tuple(sorted({n_keys // d for d in (8, 4, 2, 1)
                         if n_keys % d == 0 and n_keys // d >= top}))


def _selected_latent_attention(q: jnp.ndarray, latent: jnp.ndarray, a: int,
                               lanes: jnp.ndarray, chosen: jnp.ndarray,
                               positions: jnp.ndarray, rank: int,
                               scale: float) -> jnp.ndarray:
    """Attention over the CHOSEN rows of the latent cache and no other,
    each query gathering its own (what a decode step runs: one query a
    lane, ``index_topk`` of the thousands of rows it holds; a chunk's many
    queries of one lane attend the same set through
    :func:`_masked_latent_attention`, and :func:`selected_attention_path`
    says which):
    q (b, t, h, rank + rope) (the absorbed query of
    :func:`_latent_attention`), ``latent`` the whole cache (attentions,
    lanes, max_seq, row), batch row i reading lane ``lanes[i]`` of
    attention ``a`` at ``chosen`` (b, t, k); of those the positions past
    the token's own are not attended. The rows are gathered (k of them a
    query, whatever the lane holds) and the softmax runs over them. The
    gather takes rows of the cache seen as one table of positions (a
    bitcast): on the v5e 1.0 ms for 32 lanes x 2,048 rows of 640 where
    the same gather through a four-axis index takes 1.6 (chip run, PR
    31). Returns (b, t, h, rank)."""
    width = q.shape[-1]
    _n_a, n_lanes, n_seq, row = latent.shape
    table = latent.reshape(-1, row)
    base = ((a * n_lanes + lanes) * n_seq)[:, None, None]

    def attend(q, chosen, positions):
        # (b, t, k, row); every index lies in the table (a position is
        # < max_seq), so no pass over the rows to blank the ones that do not
        rows = table.at[base + chosen].get(mode="promise_in_bounds")
        # gathered ONCE for the scores and the weighted sum: left to itself
        # the compiler gathers the rows a second time for the second matmul
        # sooner than keep them (0.85 ms more a layer of a decode step, 64
        # ms more a layer of a chunk; chip runs, PR 31)
        rows = lax.optimization_barrier(rows)
        scores = jnp.einsum("bthc,btkc->bthk", q, rows[..., :width],
                            preferred_element_type=jnp.float32) * scale
        valid = chosen <= positions[:, :, None]
        scores = jnp.where(valid[:, :, None, :], scores, NEG_INF)
        p = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("bthk,btkc->bthc", p.astype(rows.dtype), rows,
                         preferred_element_type=jnp.float32)
        return out[..., :rank].astype(q.dtype)

    return _over_query_rows(attend, q, chosen, positions)


def selected_attention_path(t: int, k: int, n_seq: int,
                            one_lane: bool) -> "int | None":
    """How ``t`` query rows attend the ``k`` rows of an ``n_seq``-row lane
    that the indexer chose for each, from the shapes alone: None for the
    gather of :func:`_selected_latent_attention` (t x k rows fetched, one
    at a time), else the key block of :func:`_masked_latent_attention`,
    which scores the lane's live rows where they lie, once a block of
    :data:`QUERY_ROWS` queries, and masks the scores to the chosen set. The
    masked pass runs where every query reads ONE lane (a prefill chunk:
    ``CacheOps.lane``) and the gather would fetch at least as many rows as
    those passes can read at most: a chunk of 2,048 queries x 2,048 chosen
    is 4.2 M gathered rows (64 ms a layer on the v5e) against 16 passes
    over at most 24,576. A decode step's lanes hold a query each (32 x
    2,048 chosen of 5k-24k live a lane: the masked pass would read five
    times the rows) and keep the gather. The key block divides the lane, so
    no block straddles its end. No option chooses."""
    if not one_lane or t * k < -(-t // QUERY_ROWS) * n_seq:
        return None
    return math.gcd(n_seq, KEY_ROWS)


def _chosen_mask(chosen: jnp.ndarray, positions: jnp.ndarray,
                 n_seq: int) -> jnp.ndarray:
    """``chosen`` (b, t, k) positions as a membership mask (b, t, n_seq):
    True at s where s is one of ``chosen[b, t]`` and s <= ``positions[b,
    t]`` - exactly the set the gather attends, ties of the indexer's scores
    included, because it is made from the top-k's own answer. No scatter:
    position s = hi x 128 + lo, and the mask is the product of the one-hot
    rows of hi and of lo summed over the k chosen, a batched matmul of 0s
    and 1s (exact: the positions of a row differ), :data:`QUERY_ROWS` rows
    at a time. On the v5e 3.7 ms for a chunk's 2,048 x 2,048 marks in
    24,576 positions where the scatter takes 26 (chip run, PR 33)."""
    lo_n = math.gcd(n_seq, 128)
    hi_n = n_seq // lo_n

    def member(chosen, positions):
        # a position past the token's own falls outside every one-hot row
        at = jnp.where(chosen <= positions[..., None], chosen, n_seq)
        hi = jax.nn.one_hot(at // lo_n, hi_n, dtype=jnp.bfloat16)
        lo = jax.nn.one_hot(at % lo_n, lo_n, dtype=jnp.bfloat16)
        hits = jnp.einsum("btkh,btkl->bthl", hi, lo,
                          preferred_element_type=jnp.float32)
        return (hits > 0).reshape(hits.shape[:2] + (n_seq,))

    return _over_query_rows(member, chosen, positions)


def _masked_latent_attention(q: jnp.ndarray, latent: jnp.ndarray, a: int,
                             lane: jnp.ndarray, member: jnp.ndarray,
                             positions: jnp.ndarray, rank: int, scale: float,
                             blk: int) -> jnp.ndarray:
    """:func:`_selected_latent_attention` for many queries of ONE lane:
    q (1, t, h, rank + rope) attends lane ``lane`` of attention ``a`` at
    the rows ``member`` (1, t, max_seq) marks (:func:`_chosen_mask`: the
    chosen positions at or before the token's own). Nothing is gathered: a
    block of :data:`QUERY_ROWS` queries takes the lane's rows in key blocks
    of ``blk`` up to the block that holds its last position (the blocks
    past it are never scored), scores all its queries and heads against a
    key block in one matmul, masks the scores to ``member``, and carries a
    running softmax (max, sum, weighted sum) from block to block; the
    weighted sum is a second matmul over the same rows. Same products as
    the gather's, summed in another order. On the v5e both matmuls run at
    83-89% of the peak, 0.12 ms a query block and key block (chip run, PR
    33): the cost is the live positions', not ``index_topk``'s. Returns
    (1, t, h, rank)."""
    width, row = q.shape[-1], latent.shape[-1]
    # the ONE lane is cut from the cache here, once (31 MB at 24,576 x 640),
    # and the key blocks from it: cut from the cache inside the loops, the
    # compiler lays the WHOLE cache the way the score matmul wants its keys
    # (positions minor) and copies all of it ahead of the loop, 4.7 GB that
    # do not fit (compiled for the described v5e, PR 33)
    lane_rows = lax.optimization_barrier(lax.dynamic_slice(
        latent, (a, lane, 0, 0), (1, 1) + latent.shape[2:])[0])

    def attend(q, member, positions):
        def block(j, carry):
            m, norm, acc = carry
            rows = lax.dynamic_slice_in_dim(lane_rows, j * blk, blk, axis=1)
            keep = lax.dynamic_slice_in_dim(member, j * blk, blk, axis=2)
            scores = jnp.einsum("bthc,bkc->bthk", q, rows[..., :width],
                                preferred_element_type=jnp.float32) * scale
            scores = jnp.where(keep[:, :, None, :], scores, NEG_INF)
            m_new = jnp.maximum(m, scores.max(axis=-1))
            # a row that has met no chosen key yet carries exp(0) a key;
            # its first chosen key's ``fade`` is exp(NEG_INF - score) = 0
            p = jnp.exp(scores - m_new[..., None])
            fade = jnp.exp(m - m_new)
            acc = fade[..., None] * acc + jnp.einsum(
                "bthk,bkc->bthc", p.astype(rows.dtype), rows,
                preferred_element_type=jnp.float32)
            return m_new, fade * norm + p.sum(axis=-1), acc

        stat = q.shape[:3]
        _m, norm, acc = lax.fori_loop(
            0, jnp.max(positions) // blk + 1, block,
            (jnp.full(stat, NEG_INF, jnp.float32),
             jnp.zeros(stat, jnp.float32),
             jnp.zeros(stat + (row,), jnp.float32)))
        return (acc[..., :rank] / norm[..., None]).astype(q.dtype)

    return _over_query_rows(attend, q, member, positions)


def _layerwise_cached_block(layer: dict, x: jnp.ndarray, kv: dict, i: int,
                            cfg: TransformerConfig, ops: CacheOps,
                            chosen: "jnp.ndarray | None"):
    """One layer of the model described layer by layer: latent attention
    over the positions an indexer chose, then a dense SwiGLU or the expert
    share with its shared expert. A full layer (``layer["indexer"]``)
    chooses; a shared layer attends what ``chosen`` hands it from the
    nearest full layer before. A prefill (of a whole prompt, or of a
    chunk behind what the cache holds) and a decode step are the same
    function of the cache: every token's keys are written first, then
    each token chooses among and attends the cache's rows at or before
    its own position. ``chosen`` is what a full layer hands the shared
    ones: the positions (b, t, k) where the attention gathers them, their
    membership mask (b, t, max_seq) where it runs masked over the lane
    (:func:`selected_attention_path`, the same answer in every layer of
    one program). Returns (x, kv, the expert layer's counts or None,
    chosen)."""
    b, t, d = x.shape
    p = layer["mla"]
    heads, rank = cfg.n_heads, cfg.kv_lora_rank
    nope, vd = cfg.qk_nope_head_dim, cfg.v_head_dim
    positions = ops.positions(b, t)
    lanes = (jnp.arange(b) if ops.lane is None
             else jnp.reshape(ops.lane, (1,)))
    n_seq = kv["latent"].shape[2]
    top = min(cfg.index_topk, n_seq)
    blk = selected_attention_path(t, top, n_seq, ops.lane is not None)
    kv = dict(kv)
    with jax.named_scope(SCOPE_MLA_ATTENTION):
        h, c_q, q_nope, q_rope, c_kv, k_rope = mla_project(
            p, x, cfg, lambda v: ops.rope(v, cfg.rope_theta))
        row = jnp.concatenate([c_kv, k_rope[:, :, 0]], axis=-1)
        row = jnp.pad(row, ((0, 0), (0, 0),
                            (0, kv["latent"].shape[-1] - row.shape[-1])))
        kv["latent"] = ops.write(kv["latent"], i,
                                 row.astype(kv["latent"].dtype))
    if "indexer" in layer:
        with jax.named_scope(SCOPE_SPARSE_INDEXER):
            chosen, kv = _index_select(
                layer["indexer"], c_q, h, kv, cfg.full_layers.index(i), cfg,
                ops, positions)
    with jax.named_scope(SCOPE_MLA_ATTENTION):
        if blk is not None and "indexer" in layer:
            chosen = _chosen_mask(chosen, positions, n_seq)
        up = p["wkv_b"].reshape(rank, heads, nope + vd)
        q_lat = jnp.concatenate(
            [jnp.einsum("bthn,rhn->bthr", q_nope, up[..., :nope]),
             q_rope], axis=-1)
        scale = (nope + q_rope.shape[-1]) ** -0.5
        if blk is None:
            say_attention("sparse_latent",
                          "reference:_selected_latent_attention", q_lat,
                          chosen=top, of=n_seq)
            out_lat = _selected_latent_attention(
                q_lat, kv["latent"], i, lanes, chosen, positions, rank,
                scale)
        else:
            say_attention("sparse_latent",
                          "reference:_masked_latent_attention", q_lat,
                          chosen=top, of=n_seq, key_block=blk)
            out_lat = _masked_latent_attention(
                q_lat, kv["latent"], i, ops.lane, chosen, positions, rank,
                scale, blk)
        out = jnp.einsum("bthr,rhv->bthv", out_lat, up[..., nope:])
        x = x + out.reshape(b, t, heads * vd) @ p["wo"]
    h = rmsnorm(x, layer["ln2"], cfg.norm_eps)
    if "moe" in layer:
        m, counts = dropless_moe(h.reshape(b * t, d), layer["moe"],
                                 cfg.experts, ops.counted)
        return x + m.reshape(b, t, d), kv, counts, chosen
    with jax.named_scope(SCOPE_DENSE_FFN):
        x = x + (jax.nn.silu(h @ layer["w1"])
                 * (h @ layer["w3"])) @ layer["w2"]
    return x, kv, None, chosen


def ssm_scan_path(t: int, chunk: int) -> "int | None":
    """How a state-space mixer runs ``t`` tokens a sequence, from the shape
    alone: None for ONE step of the recurrence (a decode step: the state
    is multiplied and added to, read out once, and nothing is a matmul);
    else the block length of the chunked form (:func:`_ssd_scan`): the
    published ``chunk`` (256), or all ``t`` tokens where they are fewer.
    Same numbers either way. No option chooses."""
    return None if t == 1 else min(t, chunk)


def _ssd_scan(x, dt, a, bm, cm, h0, blk: int, dtype):
    """The recurrence ``H_t = exp(dt_t a) H_{t-1} + dt_t x_t (x) B_t``,
    ``y_t = H_t C_t``, over x (b, t, heads, p), dt (b, t, heads) float32
    (0 where a token is padding: it neither decays the state nor adds to
    it), a (heads,) negative, bm / cm (b, t, n), from h0 (b, heads, p, n)
    float32, in the chunked form: a ``lax.scan`` over blocks of ``blk``
    tokens that carries the state in float32; inside a block every token's
    read-out of the tokens before it in the block is a masked matmul
    (``C B^T`` times the decay between the two tokens), the carried state's
    part one more, the block's own sum into the state a third. Matmul
    operands in ``dtype`` (the configuration's), sums, decays and the state
    in float32. Returns (y (b, t, heads, p) float32, H_t (b, heads, p, n)
    float32)."""
    b, t, heads, p = x.shape
    blocks = -(-t // blk)
    pad = blocks * blk - t

    def split(v):
        v = jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2))
        return jnp.moveaxis(
            v.reshape((b, blocks, blk) + v.shape[2:]), 1, 0)

    xs = (split(x.astype(jnp.float32) * dt[..., None]), split(dt * a),
          split(bm), split(cm))
    causal = jnp.tril(jnp.ones((blk, blk), bool))

    def block(h, args):
        xdt, da, bb, cb = args
        # a head at a time inside a block: (b, heads, tokens, ...), so that
        # the decays between two tokens lie tokens-minor and every product
        # below is one batched matmul over the heads
        xdt = jnp.moveaxis(xdt, 2, 1)                     # (b, heads, s, p)
        cum = jnp.cumsum(da, axis=1).swapaxes(1, 2)       # (b, heads, q)
        # decay from token s to token q >= s, a head: exp of a sum of
        # negatives, never above 1 (masked BEFORE the exp)
        gap = cum[:, :, :, None] - cum[:, :, None, :]     # (b, heads, q, s)
        decay = jnp.exp(jnp.where(causal, gap, -jnp.inf))
        g = jnp.einsum("bqn,bsn->bqs", cb, bb,
                       preferred_element_type=jnp.float32)
        m = (g[:, None] * decay).astype(dtype)
        y = jnp.einsum("bhqs,bhsp->bhqp", m, xdt.astype(dtype),
                       preferred_element_type=jnp.float32)
        # what the carried state adds: C_q . H, faded by the decay since
        # the block began
        y = y + jnp.einsum("bqn,bhpn->bhqp", cb, h.astype(dtype),
                           preferred_element_type=jnp.float32) \
            * jnp.exp(cum)[..., None]
        # the state at the block's end
        to_end = jnp.exp(cum[:, :, -1:] - cum)            # (b, heads, s)
        h = h * jnp.exp(cum[:, :, -1])[:, :, None, None] + jnp.einsum(
            "bhsp,bsn->bhpn", (xdt * to_end[..., None]).astype(dtype), bb,
            preferred_element_type=jnp.float32)
        return h, y

    h, ys = lax.scan(block, h0, xs)          # ys (blocks, b, heads, q, p)
    y = jnp.transpose(ys, (1, 0, 3, 2, 4)).reshape(
        b, blocks * blk, heads, p)
    return y[:, :t], h


def _ssm_mixer(p: dict, u: jnp.ndarray, kv: dict, j: int,
               cfg: TransformerConfig, ops: CacheOps):
    """State-space mixer ``j`` (Mamba-2) over its normed input u (b, t, d)
    through ``kv["ssm_state"][j]`` and ``kv["conv_state"][j]`` (a buffer
    a layer), the first cache entries that are OVERWRITTEN and not
    appended to: ``[z | xBC |
    dt] = u w_in``; a causal depthwise convolution of width ``ssm_conv``
    over xBC, continued from the lane's tail (its last ``ssm_conv - 1``
    inputs), then SiLU; ``H <- exp(delta A) H + delta x (x) B``, ``y = H C +
    D x`` a head, delta = softplus(dt + dt_bias), A = -exp(a_log); ``y <-
    rmsnorm(y * silu(z))`` (the gate BEFORE the norm, one group); ``w_out``.
    A decode step (t = 1) is one step of the recurrence for every row of
    the batch; a prefill or a chunk is :func:`_ssd_scan` from the lane's
    state (a chunk at ``offset`` > 0) or from zeros (``offset`` 0, or a
    whole prefill), and which it is :func:`ssm_scan_path` says once on
    stderr (``attention[ssm_scan]``). Tokens that are not ``ops.counted``
    leave state and tail as the last counted token left them. Returns (the
    mixer's output (b, t, d), kv)."""
    b, t, _ = u.shape
    heads, hd, n = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
    inner, width = cfg.ssm_inner, cfg.ssm_conv
    kv = dict(kv)
    proj = u @ p["w_in"]
    z, xbc, dt = (proj[..., :inner], proj[..., inner:inner + cfg.ssm_conv_dim],
                  proj[..., inner + cfg.ssm_conv_dim:])
    tail, h0 = kv["conv_state"][j], kv["ssm_state"][j]
    if ops.lane is not None:
        tail = lax.dynamic_index_in_dim(tail, ops.lane, 0)
        h0 = lax.dynamic_index_in_dim(h0, ops.lane, 0)
    if ops.pos is None:
        # what a lane held belongs to its last request: a sequence that
        # starts here starts from nothing
        fresh = True if ops.offset is None else ops.offset == 0
        tail = jnp.where(fresh, jnp.zeros_like(tail), tail)
        h0 = jnp.where(fresh, jnp.zeros_like(h0), h0)
    counted = (jnp.ones((b, t), bool) if ops.counted is None or t == 1
               else ops.counted.reshape(b, t))
    seq = jnp.concatenate([tail, xbc], axis=1)       # (b, width - 1 + t, c)
    conv = sum(seq[:, i:i + t].astype(jnp.float32)
               * p["conv_w"][:, i].astype(jnp.float32)
               for i in range(width))
    xbc = jax.nn.silu(conv + p["conv_b"].astype(jnp.float32)).astype(u.dtype)
    if t == 1:
        new_tail = seq[:, 1:]
    else:
        # the last width - 1 inputs at or before the last counted token
        n_valid = counted.sum(axis=1)
        new_tail = jax.vmap(lambda s, at: lax.dynamic_slice_in_dim(
            s, at, width - 1, axis=0))(seq, n_valid)
    x = xbc[..., :inner].reshape(b, t, heads, hd)
    bm, cm = xbc[..., inner:inner + n], xbc[..., inner + n:]
    a = -jnp.exp(p["a_log"].astype(jnp.float32))
    delta = jax.nn.softplus(dt.astype(jnp.float32)
                            + p["dt_bias"].astype(jnp.float32))
    delta = jnp.where(counted[..., None], delta, 0.0)
    blk = ssm_scan_path(t, cfg.ssm_chunk)
    if blk is None:
        say_attention("ssm_scan", "reference:recurrence_step", x)
        with jax.named_scope(SCOPE_SSM_STEP):
            d1, x1 = delta[:, 0], x[:, 0].astype(jnp.float32)
            h = h0 * jnp.exp(d1 * a)[:, :, None, None] \
                + (d1[..., None] * x1)[..., None] \
                * bm[:, 0].astype(jnp.float32)[:, None, None, :]
            y = jnp.einsum("bhpn,bn->bhp", h,
                           cm[:, 0].astype(jnp.float32))[:, None]
    else:
        say_attention("ssm_scan", "reference:_ssd_scan", x, block=blk,
                      blocks=-(-t // blk))
        with jax.named_scope(SCOPE_SSM_SCAN):
            y, h = _ssd_scan(x, delta, a, bm, cm, h0, blk, u.dtype)
    y = y + p["d"].astype(jnp.float32)[:, None] * x.astype(jnp.float32)
    new_tail = new_tail.astype(kv["conv_state"][j].dtype)
    if ops.lane is not None:
        h = lax.dynamic_update_slice(kv["ssm_state"][j], h,
                                     (ops.lane, 0, 0, 0))
        new_tail = lax.dynamic_update_slice(kv["conv_state"][j], new_tail,
                                            (ops.lane, 0, 0))
    for name, new in (("ssm_state", h), ("conv_state", new_tail)):
        kv[name] = kv[name][:j] + (new,) + kv[name][j + 1:]
    y = y.reshape(b, t, inner).astype(u.dtype) * jax.nn.silu(z)
    return rmsnorm(y, p["norm"], cfg.norm_eps) @ p["w_out"], kv


# the block length of the delta rule's chunked form (no key of a config.json
# states one): the public KDA kernel's
KDA_CHUNK = 64
# positions whose decays share one reference point inside such a block: 16 x
# a log-decay of at least -5 keeps every factor within exp(+-80), inside
# float32's range
KDA_SUB = 16


def kda_chunk_path(t: int) -> "int | None":
    """How a delta-rule mixer runs ``t`` tokens a sequence, from the shape
    alone: None for ONE step of the recurrence (a decode step), else the
    block length of the chunked form (:func:`_kda_chunked`):
    :data:`KDA_CHUNK`, or all ``t`` tokens where they are fewer. Same
    numbers either way. No option chooses."""
    return None if t == 1 else min(t, KDA_CHUNK)


def _kda_chunked(q, k, v, g, beta, s0, blk: int):
    """The gated delta rule ``S_t = (I - beta_t k_t k_t^T) Diag(exp g_t)
    S_{t-1} + beta_t k_t v_t^T``, ``o_t = S_t^T q_t``, over q, k (b, t,
    heads, dk), v (b, t, heads, dv), the log-decay g (b, t, heads, dk) <= 0
    and beta (b, t, heads), all float32 (g and beta 0 where a token is
    padding: it neither decays the state nor adds to it), from s0 (b,
    heads, dk, dv) float32, in the chunked (WY) form: a ``lax.scan`` over
    blocks of ``blk`` tokens that carries the state. In a block, with
    ``G_r`` the decay summed from the block's start to token r (a channel),
    the new values u solve ``(I + A) u = beta (v - exp(G) k S)`` with ``A_rs
    = beta_r (k_r exp(G_r - G_s)) . k_s`` below the diagonal, the outputs
    are ``exp(G) q S + P u`` with ``P_rs = (q_r exp(G_r - G_s)) . k_s`` at
    and below it, and the state at the block's end is ``exp(G_end) S +
    (k exp(G_end - G))^T u``. The pairwise decays are never exp(G_r) x
    exp(-G_s) over a block (exp(-G) overflows by 64 x 5): row r takes
    exp(G_r - G_ref), key s exp(G_ref - G_s), with G_ref where the
    :data:`KDA_SUB` rows around r begin, and a key after those rows 0.
    Every product is float32 at the highest precision. Returns (o (b, t,
    heads, dv), the state (b, heads, dk, dv))."""
    b, t, heads, dk = q.shape
    blocks = -(-t // blk)
    pad = blocks * blk - t
    sub = math.gcd(blk, KDA_SUB)
    n_sub = blk // sub
    hi = lax.Precision.HIGHEST

    def split(x):
        # (b, t, heads, ...) -> (blocks, b, heads, blk, ...)
        x = jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
        x = x.reshape((b, blocks, blk) + x.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(x, 1, 0), 3, 2)

    at = jnp.arange(blk)
    # key s is seen by the rows of sub-block j where s ends no later
    seen = at[None, :] < ((jnp.arange(n_sub) + 1) * sub)[:, None]
    below = at[:, None] > at[None, :]

    def block(s, args):
        qb, kb, vb, gb, bb = args
        cum = jnp.cumsum(gb, axis=2)                      # (b, heads, blk, dk)
        ref = jnp.concatenate([jnp.zeros_like(cum[:, :, :1]),
                               cum[:, :, sub - 1:blk - 1:sub]], axis=2)
        rows_up = jnp.exp(cum - jnp.repeat(ref, sub, axis=2))
        # masked BEFORE the exp: past its sub-block a key would overflow
        keys_down = kb[:, :, None] * jnp.exp(jnp.where(
            seen[None, None, :, :, None],
            ref[:, :, :, None] - cum[:, :, None], -jnp.inf))

        def pairs(x):
            x = (x * rows_up).reshape(b, heads, n_sub, sub, dk)
            return jnp.einsum("bhjrd,bhjsd->bhjrs", x, keys_down,
                              precision=hi).reshape(b, heads, blk, blk)

        a = jnp.where(below, pairs(kb), 0.0) * bb[..., None]
        p = jnp.where(below | (at[:, None] == at[None, :]), pairs(qb), 0.0)
        decay = jnp.exp(cum)
        wu = jax.scipy.linalg.solve_triangular(
            a, bb[..., None] * jnp.concatenate([kb * decay, vb], axis=-1),
            lower=True, unit_diagonal=True)
        u = wu[..., dk:] - jnp.einsum("bhck,bhkv->bhcv", wu[..., :dk], s,
                                      precision=hi)
        o = jnp.einsum("bhck,bhkv->bhcv", qb * decay, s, precision=hi) \
            + jnp.einsum("bhcs,bhsv->bhcv", p, u, precision=hi)
        end = cum[:, :, -1]
        s = s * jnp.exp(end)[..., None] + jnp.einsum(
            "bhck,bhcv->bhkv", kb * jnp.exp(end[:, :, None] - cum), u,
            precision=hi)
        return s, o

    s, os_ = lax.scan(block, s0, (split(q), split(k), split(v), split(g),
                                  split(beta[..., None])[..., 0]))
    # (blocks, b, heads, blk, dv) -> (b, t, heads, dv)
    o = jnp.moveaxis(jnp.moveaxis(os_, 0, 1), 3, 2)
    return o.reshape(b, blocks * blk, heads, -1)[:, :t], s


def _kda_mixer(p: dict, u: jnp.ndarray, kv: dict, j: int,
               cfg: TransformerConfig, ops: CacheOps):
    """Delta-rule mixer ``j`` (Kimi delta attention) over its normed input
    u (b, t, d) through ``kv["kda_state"][j]`` and ``kv["kda_conv"][j]`` (a
    buffer a layer, OVERWRITTEN and not appended to): ``[q | k | v | f | g
    | beta] = u w_in``; a causal depthwise convolution of width
    ``kda_conv`` over q, k and v, continued from the lane's tail, then
    SiLU; q and k L2-normed a head, q times ``kda_head_dim ** -0.5``;
    ``beta = sigmoid(beta)`` a head; the log-decay a channel ``g = lower x
    sigmoid(exp(a_log) (f + dt_bias))``, in (``kda_lower_bound``, 0); the
    gated delta rule of :func:`_kda_chunked` from the lane's state (a chunk
    at ``offset`` > 0) or from zeros; ``o <- rmsnorm(o) x sigmoid(g)``
    (one group over all channels); ``w_out``. A decode step (t = 1) is one
    step of the recurrence for every row of the batch, ``S <- exp(g) S; S
    <- S + beta k (v - S^T k)^T; o = S^T q`` (float32, elementwise); which
    form runs
    :func:`kda_chunk_path` says once on stderr (``attention[kda_chunk]``).
    Tokens that are not ``ops.counted`` leave state and tail as the last
    counted token left them. Returns (the mixer's output (b, t, d), kv)."""
    b, t, _ = u.shape
    heads, hd, kd, width = (cfg.kda_heads, cfg.kda_head_dim, cfg.kda_dim,
                            cfg.kda_conv)
    f32 = jnp.float32
    kv = dict(kv)
    # the projections kept in float32: the decay's input is multiplied by
    # exp(a_log) (up to 16) inside a sigmoid, and q, k, v feed a state that
    # sums thousands of tokens
    proj = jnp.matmul(u, p["w_in"], preferred_element_type=f32)
    qkv, f, gate, beta = (proj[..., :3 * kd], proj[..., 3 * kd:4 * kd],
                          proj[..., 4 * kd:5 * kd], proj[..., 5 * kd:])
    tail, s0 = kv["kda_conv"][j], kv["kda_state"][j]
    if ops.lane is not None:
        tail = lax.dynamic_index_in_dim(tail, ops.lane, 0)
        s0 = lax.dynamic_index_in_dim(s0, ops.lane, 0)
    if ops.pos is None:
        # what a lane held belongs to its last request
        fresh = True if ops.offset is None else ops.offset == 0
        tail = jnp.where(fresh, jnp.zeros_like(tail), tail)
        s0 = jnp.where(fresh, jnp.zeros_like(s0), s0)
    counted = (jnp.ones((b, t), bool) if ops.counted is None or t == 1
               else ops.counted.reshape(b, t))
    seq = jnp.concatenate([tail, qkv], axis=1)       # (b, width - 1 + t, c)
    conv = sum(seq[:, i:i + t].astype(f32) * p["conv_w"][:, i].astype(f32)
               for i in range(width))
    if t == 1:
        new_tail = seq[:, 1:]
    else:
        n_valid = counted.sum(axis=1)
        new_tail = jax.vmap(lambda x, at: lax.dynamic_slice_in_dim(
            x, at, width - 1, axis=0))(seq, n_valid)
    q, k, v = (x.reshape(b, t, heads, hd)
               for x in jnp.split(jax.nn.silu(conv), 3, axis=-1))

    def l2norm(x):
        return x * lax.rsqrt(jnp.sum(jnp.square(x), -1, keepdims=True)
                             + 1e-6)
    q, k = l2norm(q) * hd ** -0.5, l2norm(k)
    g = cfg.kda_lower_bound * jax.nn.sigmoid(
        jnp.exp(p["a_log"].astype(f32))[:, None]
        * (f.astype(f32) + p["dt_bias"].astype(f32)).reshape(b, t, heads, hd))
    beta = jax.nn.sigmoid(beta.astype(f32))
    g = jnp.where(counted[..., None, None], g, 0.0)
    beta = jnp.where(counted[..., None], beta, 0.0)
    blk = kda_chunk_path(t)
    if blk is None:
        say_attention("kda_chunk", "reference:delta_rule_step", q)
        with jax.named_scope(SCOPE_KDA_STEP):
            # S^T k and S^T q from ONE pass over the decayed state, and the
            # read-out of the new state from them: S'^T q = S^T q + beta
            # (k . q) (v - S^T k), so the state is read twice (the sums,
            # the update) and written once
            k1, q1, b1 = k[:, 0], q[:, 0], beta[:, 0, :, None]
            s = s0 * jnp.exp(g[:, 0])[..., None]
            sk = jnp.sum(s * k1[..., None], axis=2)
            sq = jnp.sum(s * q1[..., None], axis=2)
            err = v[:, 0] - sk
            o = (sq + b1 * jnp.sum(k1 * q1, -1, keepdims=True) * err)[:, None]
            s = s + (b1 * k1)[..., None] * err[:, :, None]
    else:
        say_attention("kda_chunk", "reference:_kda_chunked", q, block=blk,
                      blocks=-(-t // blk))
        with jax.named_scope(SCOPE_KDA_CHUNK):
            o, s = _kda_chunked(q, k, v, g, beta, s0, blk)
    new_tail = new_tail.astype(kv["kda_conv"][j].dtype)
    if ops.lane is not None:
        s = lax.dynamic_update_slice(kv["kda_state"][j], s,
                                     (ops.lane, 0, 0, 0))
        new_tail = lax.dynamic_update_slice(kv["kda_conv"][j], new_tail,
                                            (ops.lane, 0, 0))
    for name, new in (("kda_state", s), ("kda_conv", new_tail)):
        kv[name] = kv[name][:j] + (new,) + kv[name][j + 1:]
    o = rmsnorm(o.reshape(b, t, kd), p["norm"].astype(f32), cfg.norm_eps)
    o = o * jax.nn.sigmoid(gate.astype(f32))
    return o.astype(u.dtype) @ p["w_out"], kv


def _hybrid_cached_block(layer: dict, x: jnp.ndarray, kv: dict, i: int,
                         cfg: TransformerConfig, ops: CacheOps):
    """One layer of a hybrid: its mixer - a state-space recurrence
    (:func:`_ssm_mixer`), a delta rule (:func:`_kda_mixer`), latent
    attention (:func:`_mla_cached_attention`, its own norm ahead of it) or
    GQA attention WITHOUT any position signal, at the stated score scale,
    through the ``k`` / ``v`` cache as the dense block has it
    (:func:`_slot_cached_attention` over the lane's rows, the fresh ones
    written first: a decode step's query, a prefill's or a chunk's queries
    alike) - then the expert share with its shared expert, or a dense
    SwiGLU; each branch joins the residual times ``residual_scale``.
    Returns (x, kv, the expert layer's counts or None)."""
    b, t, d = x.shape
    scale = jnp.asarray(cfg.residual_scale, x.dtype)
    if "mla" in layer:
        with jax.named_scope(SCOPE_MLA_ATTENTION):
            out, kv = _mla_cached_attention(
                layer["mla"], x, kv, cfg.latent_layers.index(i), cfg, ops)
        x = x + out * scale
        return _hybrid_ffn(layer, x, cfg, ops, scale, kv)
    h = rmsnorm(x, layer["ln1"], cfg.norm_eps)
    if "kda" in layer:
        with jax.named_scope(SCOPE_KDA_MIXER):
            out, kv = _kda_mixer(layer["kda"], h, kv,
                                 cfg.kda_layers.index(i), cfg, ops)
        x = x + out * scale
        return _hybrid_ffn(layer, x, cfg, ops, scale, kv)
    if "ssm" in layer:
        with jax.named_scope(SCOPE_SSM_MIXER):
            out, kv = _ssm_mixer(layer["ssm"], h, kv,
                                 cfg.ssm_layers.index(i), cfg, ops)
    else:
        a = cfg.attention_layers.index(i)
        kv = dict(kv)
        with jax.named_scope(SCOPE_ATTENTION):
            q = (h @ layer["wq"]).reshape(b, t, cfg.n_heads, cfg.head_dim)
            k = (h @ layer["wk"]).reshape(b, t, cfg.kv_heads, cfg.head_dim)
            v = (h @ layer["wv"]).reshape(b, t, cfg.kv_heads, cfg.head_dim)
            kv["k"] = ops.write(kv["k"], a, k.astype(kv["k"].dtype))
            kv["v"] = ops.write(kv["v"], a, v.astype(kv["v"].dtype))
            attn = _slot_cached_attention(
                q, ops.lane_rows(kv["k"], a), ops.lane_rows(kv["v"], a),
                ops.positions(b, t), scale=cfg.attn_scale)
            out = attn.reshape(b, t, -1) @ layer["wo"]
    x = x + out * scale
    return _hybrid_ffn(layer, x, cfg, ops, scale, kv)


def _hybrid_ffn(layer: dict, x: jnp.ndarray, cfg: TransformerConfig,
                ops: CacheOps, scale: jnp.ndarray, kv: dict):
    """A hybrid layer's second half: the expert share (``moe``) or a dense
    SwiGLU after ``ln2``. Returns (x, kv, the expert layer's counts or
    None)."""
    b, t, d = x.shape
    h = rmsnorm(x, layer["ln2"], cfg.norm_eps)
    if "moe" not in layer:
        with jax.named_scope(SCOPE_DENSE_FFN):
            return x + (jax.nn.silu(h @ layer["w1"]) * (h @ layer["w3"])) \
                @ layer["w2"] * scale, kv, None
    m, counts = dropless_moe(h.reshape(b * t, d), layer["moe"], cfg.experts,
                             ops.counted)
    return x + m.reshape(b, t, d) * scale, kv, counts


def cached_blocks(params: dict, x: jnp.ndarray, kv: dict,
                  cfg: TransformerConfig, ops: CacheOps):
    """Every block of the model over x (b, t, d) through the cache:
    (x, kv, counts). ``counts`` is None for the dense kind; for the
    shortcut and the layer-by-layer kinds the expert layers' counts summed
    over the layers (``held`` and ``identity`` a token, (b*t,);
    ``touched`` and ``carried`` a scalar each). The layer-by-layer kind's
    selection goes from a full layer to the shared layers after it here;
    the hybrid's layers each take their mixer's kind from
    ``cfg.layer_mixer``."""
    if cfg.full_latent:
        raise NotImplementedError(
            f"{cfg.new_kind}: no cached block runs it (it trains: "
            f"models/transformer.py `latent_block`)")
    block = (_shortcut_cached_block if cfg.block == "shortcut"
             else _hybrid_cached_block if cfg.hybrid
             else _dense_cached_block)
    total = chosen = None
    for i, layer in enumerate(params["layers"]):
        if cfg.indexed:
            x, kv, counts, chosen = _layerwise_cached_block(
                layer, x, kv, i, cfg, ops, chosen)
        else:
            x, kv, counts = block(layer, x, kv, i, cfg, ops)
        if counts is not None:
            # ``carried`` is a plain number where no branch is built
            total = counts if total is None else jax.tree.map(
                operator.add, total, counts)
    return x, kv, total


def decode_step(params: dict, cache: dict, token: jnp.ndarray,
                cfg: TransformerConfig) -> tuple[dict, jnp.ndarray]:
    """One incremental step: consume ``token`` (b,) int32 at ``cache.pos``,
    return (updated cache, logits (b, vocab)).

    Mirrors transformer_apply's block math exactly (same layer dicts, same
    rmsnorm/residual order) with attention served from the cache; parity
    with the full forward is pinned by tests/test_generate.py.
    """
    pos = cache["pos"]
    x = embed_tokens(params, token, cfg)[:, None, :]
    if cfg.learned_positions:
        x = x + lax.dynamic_slice_in_dim(params["pos"], pos, 1,
                                         axis=0)[None]
    kv = {n: c for n, c in cache.items() if n != "pos"}
    x, kv, _counts = cached_blocks(params, x, kv, cfg, CacheOps(pos=pos))
    logits = lm_logits(
        params, rmsnorm(x, params["out_norm"], cfg.norm_eps), cfg)
    return {**kv, "pos": pos + 1}, logits[:, 0, :]


def prefill_counted(params: dict, cache: dict, prompt: jnp.ndarray,
                    cfg: TransformerConfig,
                    logit_pos: "jnp.ndarray | int | None" = None):
    """:func:`prefill` with the expert layers' counts as a third result
    (None for the dense kind). With ``logit_pos`` the positions after it
    are padding and count nowhere."""
    b, t = prompt.shape
    x = embed_tokens(params, prompt, cfg)
    if cfg.learned_positions:
        x = x + params["pos"][:t][None]
    counted = None
    if logit_pos is not None:
        counted = jnp.broadcast_to(jnp.arange(t) <= logit_pos,
                                   (b, t)).reshape(b * t)
    kv = {n: c for n, c in cache.items() if n != "pos"}
    x, kv, counts = cached_blocks(params, x, kv, cfg,
                                  CacheOps(counted=counted))
    x_last = (x[:, -1:] if logit_pos is None
              else lax.dynamic_slice_in_dim(x, logit_pos, 1, axis=1))
    logits = lm_logits(
        params, rmsnorm(x_last, params["out_norm"], cfg.norm_eps), cfg)
    return ({**kv, "pos": jnp.asarray(t, jnp.int32)}, logits[:, 0, :],
            counts)


def prefill(params: dict, cache: dict, prompt: jnp.ndarray,
            cfg: TransformerConfig,
            logit_pos: "jnp.ndarray | int | None" = None
            ) -> tuple[dict, jnp.ndarray]:
    """Fill the cache from the prompt (b, t) in ONE batched forward —
    full-width matmuls on the MXU instead of t sequential single-token
    steps — and return (cache after the prompt, last-position logits).
    Same block math as decode_step/transformer_apply (parity-pinned).

    ``logit_pos`` (dynamic) returns the logits at that prompt position
    instead of the last — the bucketed-prefill hook (serving/engine.py):
    a prompt of true length n padded to bucket length t reads its
    next-token logits at n-1, while causality keeps positions < n
    untouched by the padding (pad K/V beyond n is garbage the decode
    position mask never admits, and is overwritten as decode advances).
    The returned cache's ``pos`` is always t; bucketed callers own the
    true frontier."""
    return prefill_counted(params, cache, prompt, cfg, logit_pos)[:2]


def multi_step_decode(params: dict, kv: dict, logits: jnp.ndarray,
                      pos: jnp.ndarray, done: jnp.ndarray,
                      remaining: jnp.ndarray, eos_ids: jnp.ndarray,
                      stop_ids: jnp.ndarray, steps: int, decode_fn,
                      sample: Optional[tuple] = None,
                      key_data: Optional[jnp.ndarray] = None,
                      step_idx: Optional[jnp.ndarray] = None):
    """Fuse ``steps`` greedy decode steps into one ``lax.scan`` with
    per-lane finish handling ON DEVICE — the masked multi-step core the
    serving engine dispatches (serving/engine.py ``_engine_multi_step``).

    The single-step engine pays one Python dispatch and one device->host
    readback per emitted token; this core amortizes both across a block
    of ``steps`` tokens (the paper's spend-bandwidth-not-round-trips
    move, pointed at the decode loop). The price is that a lane can
    finish MID-block: its done-mask latches on device and the trailing
    block steps compute garbage for it ("wasted tokens" — the quantity
    the engine's metrics report so operators can tune ``steps``).

    Per scan step, for each lane:

    1. emit ``tok = argmax(logits)`` (greedy — the parity mode), or —
       with ``sample`` set — the seeded per-lane pick
       (:func:`sample_token_rows` over the carried ``step_idx``: the
       per-slot PRNG key threaded through the scan carry, the open
       question flagged since the block-decode PR);
    2. latch ``done`` if the lane was active and ``tok`` is its EOS, one
       of its stop ids, or its last budgeted token (``remaining <= 1``);
    3. run ``decode_fn`` for every lane (static shapes), but a lane that
       is frozen — done before this step, or latched by its just-emitted
       token — neither writes KV (``write_mask``) nor advances ``pos``.
       The S=1 engine runs the finishing token's cache write and then
       discards the lane wholesale on refill, so masking it is
       unobservable; active lanes see bitwise the same per-row math
       either way, which is what keeps block decode bitwise equal to
       the single-step engine and to :func:`generate`.

    ``eos_ids`` (lanes,) and ``stop_ids`` (lanes, K) use -1 for "none"
    (argmax tokens are >= 0, so -1 never matches); ``remaining`` (lanes,)
    counts budgeted tokens left; ``done`` marks lanes (e.g. free engine
    slots) that must not decode at all. ``decode_fn(params, kv, tok,
    pos, write_mask)`` is one masked decode step returning ``(kv,
    logits)`` — the engine passes its per-slot-position step.

    The finite-output guard rides the same scan: before each step's
    argmax, a lane whose carried logits contain a non-finite value
    (NaN-poisoned decode, an overflowed matmul) latches ``bad`` AND
    ``done`` — the poisoned lane freezes exactly like a finished one
    (no KV writes, no pos advance, so the poison is contained to its
    own row) and the flag folds into the caller's packed readback with
    no extra host round-trip. Healthy lanes see one ``isfinite``
    reduction per step and bitwise-unchanged tokens.

    Returns ``((kv, logits, pos, done, remaining, bad), tokens)`` with
    ``tokens`` of shape ``(steps, lanes)``; entries after a lane's latch
    are garbage the caller must not consume, and a ``bad`` lane's whole
    block is garbage (the poison may predate any token in it).

    SAMPLED blocks (ISSUE 10): ``sample`` = the static ``(temperature,
    top_k, top_p)`` triple switches step 1's pick from argmax to
    :func:`sample_token_rows` over per-lane keys — ``key_data``
    (lanes, key_width) raw key bytes (request-seed-derived, so streams
    are churn/slot invariant) and ``step_idx`` (lanes,) the per-lane
    emitted-token index join the scan carry, with ``step_idx``
    advancing exactly where a lane was active (mirroring the host's
    consumed-token replay, restore included). The carry and return
    grow a trailing ``step_idx`` leaf in this mode ONLY — the greedy
    path's program is byte-for-byte what it was (the parity pin)."""

    if sample is not None:
        def one_sampled(carry, _):
            kv, logits, pos, done, remaining, bad, idx = carry
            poisoned = ~done & ~jnp.isfinite(logits).all(axis=-1)
            bad = bad | poisoned
            done = done | poisoned
            tok = sample_token_rows(key_data, logits, idx, sample)
            active = ~done
            finished = active & ((tok == eos_ids)
                                 | (stop_ids == tok[:, None]).any(axis=1)
                                 | (remaining <= 1))
            live = active & ~finished
            remaining = jnp.where(active, remaining - 1, remaining)
            idx = jnp.where(active, idx + 1, idx)
            done = done | finished
            kv, logits = decode_fn(params, kv, tok, pos, live)
            pos = jnp.where(live, pos + 1, pos)
            return (kv, logits, pos, done, remaining, bad, idx), tok

        bad0 = jnp.zeros_like(done)
        return lax.scan(
            one_sampled,
            (kv, logits, pos, done, remaining, bad0, step_idx), None,
            length=steps)

    def one(carry, _):
        kv, logits, pos, done, remaining, bad = carry
        poisoned = ~done & ~jnp.isfinite(logits).all(axis=-1)
        bad = bad | poisoned
        done = done | poisoned
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        active = ~done
        finished = active & ((tok == eos_ids)
                             | (stop_ids == tok[:, None]).any(axis=1)
                             | (remaining <= 1))
        live = active & ~finished
        remaining = jnp.where(active, remaining - 1, remaining)
        done = done | finished
        kv, logits = decode_fn(params, kv, tok, pos, live)
        pos = jnp.where(live, pos + 1, pos)
        return (kv, logits, pos, done, remaining, bad), tok

    bad0 = jnp.zeros_like(done)
    return lax.scan(one, (kv, logits, pos, done, remaining, bad0), None,
                    length=steps)


def _filter_top_k(logits: jnp.ndarray, top_k: int) -> jnp.ndarray:
    """Keep the ``top_k`` largest logits per row, NEG_INF the rest (ties
    at the threshold are kept — harmless, matches common practice)."""
    vals = lax.top_k(logits, top_k)[0]
    return jnp.where(logits < vals[..., -1:], NEG_INF, logits)


def apply_sample_filters(logits: jnp.ndarray, temperature: float,
                         top_k: Optional[int],
                         top_p: Optional[float]) -> jnp.ndarray:
    """The sampling pipeline shared by every sampled decode path
    (``generate``, the engine's per-slot sampling, the speculative
    verify): temperature scaling then optional top-k / top-p (nucleus)
    filtering, row-wise over ``(..., vocab)``. Every filter is a
    per-row operation (top_k / sort / softmax reduce only over the
    vocab axis), so a row's filtered logits are bitwise identical
    whether it rides in a batch of 1 or of ``slots`` — the property
    the engine's sampled-parity contract leans on."""
    x = logits / temperature
    if top_k is not None and top_k < x.shape[-1]:
        x = _filter_top_k(x, top_k)
    if top_p is not None and top_p < 1.0:
        x = _filter_top_p(x, top_p)
    return x


def sample_step_key(key: jax.Array, idx) -> jax.Array:
    """The canonical per-token sampling key: ``fold_in(base, idx)``
    where ``idx`` is the 0-based index of the token being emitted
    (counting from the first generated token, prompt excluded).

    fold_in — not ``split(key, steps)[idx]`` — because the schedule
    must be STEP-COUNT-FREE: the serving engine decodes a request in
    blocks of unknowable size across churn, refill and drain/restore,
    and its per-slot streams can only match ``generate(key=...)``
    bitwise if token ``idx``'s key depends on nothing but (base key,
    idx). Both ``generate`` and the engine derive their keys through
    this one function."""
    return jax.random.fold_in(key, idx)


def sample_token_rows(key_data: jnp.ndarray, logits: jnp.ndarray,
                      idx: jnp.ndarray, sample: tuple) -> jnp.ndarray:
    """Per-lane sampled pick for the serving engine: row ``s`` of
    ``logits`` (lanes, vocab) samples with ``sample_step_key(key_s,
    idx[s])`` where ``key_s`` wraps ``key_data[s]`` (the raw key bytes
    the host uploads per slot — derived from the REQUEST's seed, never
    the slot index, so a surviving lane's stream is invariant to
    admission order and churn). ``sample`` is the static
    ``(temperature, top_k, top_p)`` triple.

    Each lane's categorical runs over a ``(1, vocab)`` row — the exact
    shape ``generate``'s batch-1 pick samples over — so an engine
    lane's tokens are bitwise ``generate(key=key_s, temperature=...)``
    's (pinned by tests/test_sampled_serving.py)."""
    temperature, top_k, top_p = sample
    filtered = apply_sample_filters(logits, temperature, top_k, top_p)

    def one(kd, row, i):
        k = sample_step_key(jax.random.wrap_key_data(kd), i)
        return jax.random.categorical(k, row[None], axis=-1)[0]

    return jax.vmap(one)(key_data, filtered, idx).astype(jnp.int32)


def _filter_top_p(logits: jnp.ndarray, top_p: float) -> jnp.ndarray:
    """Nucleus filter: keep the smallest set of tokens whose probability
    mass reaches ``top_p``. The kept set is found on the descending sort
    via an EXCLUSIVE cumulative sum (so the token that crosses the
    boundary stays in — the set must REACH top_p), then applied to the
    unsorted logits through the threshold logit, keeping shapes static
    for the scan."""
    sorted_desc = -jnp.sort(-logits, axis=-1)
    probs = jax.nn.softmax(sorted_desc, axis=-1)
    mass_before = jnp.cumsum(probs, axis=-1) - probs
    drop = mass_before >= top_p  # never drops the first token
    thresh = jnp.min(jnp.where(drop, jnp.inf, sorted_desc),
                     axis=-1, keepdims=True)
    return jnp.where(logits < thresh, NEG_INF, logits)


@partial(jax.jit, static_argnames=("cfg", "steps", "temperature",
                                   "top_k", "top_p", "eos_token",
                                   "kv_dtype"))
def generate(params: dict, prompt: jnp.ndarray, cfg: TransformerConfig,
             steps: int, key: Optional[jax.Array] = None,
             temperature: float = 0.0, top_k: Optional[int] = None,
             top_p: Optional[float] = None,
             eos_token: Optional[int] = None,
             kv_dtype: Optional[str] = None):
    """Generate ``steps`` tokens after ``prompt`` (b, t) int32. Greedy when
    ``temperature == 0`` (key unused), else temperature sampling with
    optional top-k and/or top-p (nucleus) filtering — both static over the
    sampling mode, so each (mode, shape) pair compiles exactly once.
    Returns (b, steps) int32. One compiled program: prefill + decode scan.

    ``eos_token`` turns on per-sequence early termination: a sequence
    that emits it is DONE — every later step emits ``eos_token`` again
    (the scan keeps its static shape; the done-mask rides the carry) —
    and the return becomes ``(tokens (b, steps), lengths (b,))`` where
    ``lengths[i]`` counts tokens through the first EOS (``steps`` when
    none fired). Finished sequences still occupy their decode lane: the
    scan is the fixed-batch regime; reclaiming the lane for new work is
    the serving engine's job (serving/engine.py).

    ``kv_dtype="int8"`` decodes against the quantized KV cache
    (:func:`init_kv_cache`) — same program shape, a bounded logit error
    (tests/test_generate.py::TestQuantizedKV)."""
    if prompt.shape[1] + steps > cfg.max_seq:
        raise ValueError(
            f"prompt {prompt.shape[1]} + steps {steps} exceeds "
            f"max_seq {cfg.max_seq}")
    if top_k is not None and not 1 <= top_k:
        raise ValueError(f"top_k must be >= 1, got {top_k}")
    if top_p is not None and not 0.0 < top_p <= 1.0:
        raise ValueError(f"top_p must be in (0, 1], got {top_p}")
    if eos_token is not None and not 0 <= eos_token < cfg.vocab_size:
        raise ValueError(f"eos_token {eos_token} out of vocab "
                         f"[0, {cfg.vocab_size})")
    b = prompt.shape[0]
    cache = init_kv_cache(cfg, b, kv_dtype=kv_dtype)
    cache, logits = prefill(params, cache, prompt, cfg)
    if key is None:
        key = jax.random.key(0)

    def pick(logits, k):
        if temperature == 0.0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        logits = apply_sample_filters(logits, temperature, top_k, top_p)
        return jax.random.categorical(k, logits, axis=-1).astype(jnp.int32)

    def one(carry, j):
        cache, logits, done = carry
        # the canonical step-count-free key schedule (sample_step_key):
        # token j's key is fold_in(base, j), which is what lets the
        # serving engine reproduce this exact stream from any block
        # partition of the decode
        tok = pick(logits, sample_step_key(key, j))
        if eos_token is not None:
            # an already-done row keeps emitting EOS (stable padding);
            # rows finishing THIS step keep their freshly-picked EOS
            tok = jnp.where(done, jnp.int32(eos_token), tok)
            done = done | (tok == eos_token)
        cache, logits = decode_step(params, cache, tok, cfg)
        return (cache, logits, done), tok

    done0 = jnp.zeros((b,), bool)
    _, tokens = lax.scan(one, (cache, logits, done0),
                         jnp.arange(steps))
    tokens = tokens.T  # (b, steps)
    if eos_token is None:
        return tokens
    hit = tokens == eos_token
    lengths = jnp.where(hit.any(axis=1),
                        jnp.argmax(hit, axis=1) + 1, steps)
    return tokens, lengths.astype(jnp.int32)
