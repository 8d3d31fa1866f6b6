"""Flagship model: a causal transformer LM, parallelism-aware by design.

Pure-pytree parameters and a functional ``apply`` keep the model a single
traced computation XLA can fuse end-to-end (bf16-friendly matmuls on the
MXU, static shapes throughout). Parallelism is injected, not hard-coded:

* ``attn_fn`` — plain local causal attention on one chip, or ring attention
  over the ``sp`` axis (parallel/ring_attention.py) for sequence sharding.
* ``tp_axis`` — when set, QKV/FF1 are column-parallel shards and the output
  projections row-parallel with one psum each (parallel/tp.py); head count
  and FF width passed in params are the *local* shards.

The same ``apply`` therefore serves the single-chip graft entry, the
dp-only data-parallel trainer, and the full dp x tp x sp training step
(models/train.py).
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from akka_allreduce_tpu.parallel.ep import MoEConfig, init_moe_layer, moe_ffn
from akka_allreduce_tpu.parallel.ring_attention import local_causal_attention
from akka_allreduce_tpu.runtime.tracing import SCOPE_HEAD_LOSS
from akka_allreduce_tpu.parallel.tp import column_parallel_dense, \
    row_parallel_dense, tp_grad_boundary


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 256
    d_model: int = 128
    n_heads: int = 4
    n_layers: int = 2
    d_ff: int = 512
    max_seq: int = 128
    dtype: object = jnp.float32
    # Mixture-of-experts: when ``moe`` is set, every ``moe_every``-th layer
    # (1-indexed: layers i with (i+1) % moe_every == 0) replaces its dense
    # FF with a routed expert FF (parallel/ep.py). moe_every=1 => all layers.
    moe: Optional[MoEConfig] = None
    moe_every: int = 1
    # Llama-family options (the second model family; all orthogonal to the
    # parallel axes):
    # * n_kv_heads < n_heads = grouped-query attention — K/V are projected
    #   to fewer heads and each group of n_heads/n_kv_heads query heads
    #   shares one; shrinks the KV cache and K/V projection by the group
    #   factor (None = multi-head, every query head has its own K/V)
    # * rope = rotary position embeddings applied to q/k inside every
    #   block instead of a learned absolute "pos" table (no "pos" param)
    # * ffn = "swiglu": FF becomes w2(silu(w1 x) * (w3 x)) with a third
    #   gate matrix, vs the default "gelu" two-matrix FF
    n_kv_heads: Optional[int] = None
    rope: bool = False
    rope_theta: float = 10000.0
    ffn: str = "gelu"
    # Sliding-window (Mistral-style) causal attention: each position sees
    # itself plus attn_window-1 predecessors. Served by the flash kernel
    # (banded tiles skipped -> O(T*window) compute) and the local oracle;
    # not composable with sequence parallelism (sp > 1) yet.
    attn_window: Optional[int] = None
    # Weight tying (GPT-2 style): the output head reuses the input
    # embedding transposed — no separate lm_head parameter, vocab x d
    # fewer weights, and both ends of the model train one matrix.
    tie_embeddings: bool = False

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    @property
    def kv_heads(self) -> int:
        return self.n_kv_heads if self.n_kv_heads is not None \
            else self.n_heads

    def is_moe_layer(self, i: int) -> bool:
        return self.moe is not None and (i + 1) % self.moe_every == 0

    def __post_init__(self):
        if self.n_kv_heads is not None and not (
                0 < self.n_kv_heads <= self.n_heads):
            raise ValueError(
                f"n_kv_heads={self.n_kv_heads} must be in "
                f"[1, n_heads={self.n_heads}] (None = multi-head; the "
                f"CLI's 0 sentinel maps to None before reaching here)")
        if self.n_heads % self.kv_heads:
            raise ValueError(
                f"n_kv_heads={self.kv_heads} must divide "
                f"n_heads={self.n_heads}")
        if self.ffn not in ("gelu", "swiglu"):
            raise ValueError(f"unknown ffn {self.ffn!r}")
        if self.rope and self.head_dim % 2:
            raise ValueError(
                f"rope needs an even head_dim, got {self.head_dim} "
                f"(d_model={self.d_model} / n_heads={self.n_heads})")
        if self.attn_window is not None and self.attn_window < 1:
            raise ValueError(
                f"attn_window must be >= 1, got {self.attn_window}")


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray,
               theta: float = 10000.0) -> jnp.ndarray:
    """Rotary position embedding over (B, T, H, D): each head-dim pair
    (x[2i], x[2i+1] in the half-split convention) rotates by
    pos * theta^(-2i/D). Stats in f32, result in x's dtype (same precision
    rule as rmsnorm: position phases must not quantise to bf16)."""
    d = x.shape[-1]
    half = d // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    angles = positions.astype(jnp.float32)[:, None] * freqs[None, :]
    cos = jnp.cos(angles)[None, :, None, :]  # (1, T, 1, D/2)
    sin = jnp.sin(angles)[None, :, None, :]
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., :half], xf[..., half:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos],
        axis=-1).astype(x.dtype)


def rmsnorm(x: jnp.ndarray, scale: jnp.ndarray) -> jnp.ndarray:
    """RMS statistics in f32 regardless of compute dtype (bf16 squares
    lose ~5 bits where the variance needs them), result back in x's."""
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + 1e-6)).astype(x.dtype) * scale


def lm_logits(params: dict, x: jnp.ndarray,
              cfg: TransformerConfig) -> jnp.ndarray:
    """Output head: the lm_head matmul, or the transposed embedding under
    weight tying (one shared matrix serving both ends)."""
    if cfg.tie_embeddings:
        return x @ params["embed"].T
    return x @ params["lm_head"]


def init_transformer(key: jax.Array, cfg: TransformerConfig,
                     tp: int = 1) -> dict:
    """Full (unsharded) parameters when tp=1; per-rank TP shards when the
    caller slices (models/train.py shards via the mesh instead — this
    function always builds the full tree; tp only validates divisibility)."""
    if cfg.n_heads % tp or cfg.d_ff % tp or cfg.kv_heads % tp:
        raise ValueError(
            f"tp={tp} must divide n_heads={cfg.n_heads}, "
            f"n_kv_heads={cfg.kv_heads}, and d_ff={cfg.d_ff}")
    k = iter(jax.random.split(key, 4 + 10 * cfg.n_layers))
    dt = cfg.dtype
    scale = cfg.d_model ** -0.5
    d_kv = cfg.kv_heads * cfg.head_dim
    params = {
        "embed": jax.random.normal(next(k), (cfg.vocab_size, cfg.d_model),
                                   dt) * scale,
        "out_norm": jnp.ones((cfg.d_model,), dt),
        "layers": [],
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = jax.random.normal(
            next(k), (cfg.d_model, cfg.vocab_size), dt) * scale
    if not cfg.rope:
        params["pos"] = jax.random.normal(
            next(k), (cfg.max_seq, cfg.d_model), dt) * scale
    for i in range(cfg.n_layers):
        layer = {
            "ln1": jnp.ones((cfg.d_model,), dt),
            "wq": jax.random.normal(next(k), (cfg.d_model, cfg.d_model),
                                    dt) * scale,
            "wk": jax.random.normal(next(k), (cfg.d_model, d_kv),
                                    dt) * scale,
            "wv": jax.random.normal(next(k), (cfg.d_model, d_kv),
                                    dt) * scale,
            "wo": jax.random.normal(next(k), (cfg.d_model, cfg.d_model),
                                    dt) * scale,
            "ln2": jnp.ones((cfg.d_model,), dt),
        }
        if cfg.is_moe_layer(i):
            layer.update(init_moe_layer(next(k), cfg.d_model, cfg.moe,
                                        dtype=dt))
        else:
            layer["w1"] = jax.random.normal(
                next(k), (cfg.d_model, cfg.d_ff), dt) * scale
            layer["w2"] = jax.random.normal(
                next(k), (cfg.d_ff, cfg.d_model), dt) * scale
            if cfg.ffn == "swiglu":
                layer["w3"] = jax.random.normal(
                    next(k), (cfg.d_model, cfg.d_ff), dt) * scale
        params["layers"].append(layer)
    return params


AttnFn = Callable[[jnp.ndarray, jnp.ndarray, jnp.ndarray], jnp.ndarray]


def transformer_block(layer: dict, x: jnp.ndarray, cfg: TransformerConfig,
                      attn_fn: Optional[AttnFn] = None,
                      tp_axis: Optional[str] = None,
                      ep_axis: Optional[str] = None,
                      positions: Optional[jnp.ndarray] = None
                      ) -> tuple[jnp.ndarray, dict]:
    """One residual block (attention + FF), rank-local. Returns (x, aux);
    aux is empty for dense layers and carries ``aux_loss`` /
    ``dispatch_fraction`` for MoE layers (``layer`` holds a ``router``).
    The single block primitive every apply path composes.

    ``positions`` (global sequence positions of this rank's tokens) is only
    consulted under rope — rotary phases need absolute positions inside
    every block, including under sequence sharding and pipelining. With
    GQA K/V carry cfg.kv_heads heads; ``attn_fn`` receives the narrow K/V
    (the flash kernel consumes them natively, the pure-JAX paths expand).
    MoE layers keep their own expert FF (ffn="swiglu" shapes dense layers
    only)."""
    b, t, _ = x.shape
    if attn_fn is None:  # default oracle, window-aware (see apply)
        def attn_fn(q, k, v):
            return local_causal_attention(q, k, v,
                                          window=cfg.attn_window)
    h = rmsnorm(x, layer["ln1"])
    if tp_axis is not None:
        # identity fwd / psum('tp') bwd: completes dL/dh across the
        # column-parallel shards (parallel/tp.py)
        h = tp_grad_boundary(h, tp_axis)
    q = column_parallel_dense(h, layer["wq"])
    k_ = column_parallel_dense(h, layer["wk"])
    v = column_parallel_dense(h, layer["wv"])
    n_heads_local = q.shape[-1] // cfg.head_dim
    n_kv_local = k_.shape[-1] // cfg.head_dim
    q = q.reshape(b, t, n_heads_local, cfg.head_dim)
    k_ = k_.reshape(b, t, n_kv_local, cfg.head_dim)
    v = v.reshape(b, t, n_kv_local, cfg.head_dim)
    if cfg.rope:
        if positions is None:
            positions = jnp.arange(t)
        q = apply_rope(q, positions, cfg.rope_theta)
        k_ = apply_rope(k_, positions, cfg.rope_theta)
    attn = attn_fn(q, k_, v).reshape(b, t, -1)
    if tp_axis is not None:
        x = x + row_parallel_dense(attn, layer["wo"], tp_axis)
    else:
        x = x + attn @ layer["wo"]

    h = rmsnorm(x, layer["ln2"])
    aux: dict = {}
    if "router" in layer:
        # Routed expert FF: dispatched over ep (parallel/ep.py). Replicated
        # across tp — no column sharding, so no grad boundary needed, but
        # the expert FLOPs are redone per tp rank; scale expert capacity
        # over ep (the axis built for it), not tp. A tp-sharded expert
        # d_ff is the known optimization if tp*MoE becomes the hot config.
        y, aux = moe_ffn(h, layer, cfg.moe, axis_name=ep_axis)
        x = x + y
    else:
        if tp_axis is not None:
            h = tp_grad_boundary(h, tp_axis)
        if "w3" in layer:  # swiglu: gate * up, silu-gated
            hh = jax.nn.silu(column_parallel_dense(h, layer["w1"])) \
                * column_parallel_dense(h, layer["w3"])
        else:
            hh = jax.nn.gelu(column_parallel_dense(h, layer["w1"]))
        if tp_axis is not None:
            x = x + row_parallel_dense(hh, layer["w2"], tp_axis)
        else:
            x = x + hh @ layer["w2"]
    return x, aux


def _merge_aux(total: dict, aux: dict) -> dict:
    if not aux:
        return total
    if not total:
        return {**aux, "_n_moe": jnp.asarray(1.0, jnp.float32)}
    return {
        "aux_loss": total["aux_loss"] + aux["aux_loss"],
        "dispatch_fraction": total["dispatch_fraction"]
        + aux["dispatch_fraction"],
        "_n_moe": total["_n_moe"] + 1.0,
    }


def _finalize_aux(total: dict) -> dict:
    """aux_loss stays a sum over MoE layers; dispatch_fraction becomes the
    mean over them."""
    if not total:
        return {"aux_loss": jnp.asarray(0.0, jnp.float32),
                "dispatch_fraction": jnp.asarray(1.0, jnp.float32)}
    n = total.pop("_n_moe")
    return {"aux_loss": total["aux_loss"],
            "dispatch_fraction": total["dispatch_fraction"] / n}


def transformer_apply_with_aux(params: dict, tokens: jnp.ndarray,
                               cfg: TransformerConfig,
                               positions: Optional[jnp.ndarray] = None,
                               attn_fn: Optional[AttnFn] = None,
                               tp_axis: Optional[str] = None,
                               ep_axis: Optional[str] = None,
                               remat: bool = False
                               ) -> tuple[jnp.ndarray, dict]:
    """:func:`transformer_hidden_with_aux` through the output head:
    (logits (B, T_local, vocab), aux)."""
    x, aux = transformer_hidden_with_aux(
        params, tokens, cfg, positions, attn_fn, tp_axis, ep_axis, remat)
    return lm_logits(params, x, cfg), aux


def transformer_hidden_with_aux(params: dict, tokens: jnp.ndarray,
                                cfg: TransformerConfig,
                                positions: Optional[jnp.ndarray] = None,
                                attn_fn: Optional[AttnFn] = None,
                                tp_axis: Optional[str] = None,
                                ep_axis: Optional[str] = None,
                                remat: bool = False
                                ) -> tuple[jnp.ndarray, dict]:
    """tokens: (B, T_local) int32 → (the normed last hidden state (B,
    T_local, d_model), what the output head reads; aux).

    ``positions``: global sequence positions of this rank's tokens (needed
    under sequence sharding; defaults to 0..T-1). When ``tp_axis`` is set,
    the per-layer weight shards passed in params are already the local tp
    slices and head count is the local count. ``ep_axis`` routes MoE layers
    over that mesh axis (None = all experts local). ``remat`` checkpoints
    each block: activations are recomputed in the backward pass instead of
    stored — O(sqrt)-ish activation memory, the long-context lever
    (gradients are bit-identical; only the schedule changes). aux:
    ``aux_loss`` (sum of MoE load-balance losses, per-token-mean scale) and
    ``dispatch_fraction`` (mean over MoE layers; 1.0 when there are none).
    """
    t = tokens.shape[1]
    if positions is None:
        positions = jnp.arange(t)
    # attn_fn=None resolves inside transformer_block to the window-aware
    # oracle; train-step callers inject their own (kernel) attn_fn
    x = params["embed"][tokens]
    if not cfg.rope:
        x = x + params["pos"][positions]

    def block(layer, h):
        return transformer_block(layer, h, cfg, attn_fn, tp_axis, ep_axis,
                                 positions=positions)

    if remat:
        block = jax.checkpoint(block)

    aux_total: dict = {}
    for layer in params["layers"]:
        x, aux = block(layer, x)
        aux_total = _merge_aux(aux_total, aux)

    return rmsnorm(x, params["out_norm"]), _finalize_aux(aux_total)


def transformer_apply(params: dict, tokens: jnp.ndarray,
                      cfg: TransformerConfig,
                      positions: Optional[jnp.ndarray] = None,
                      attn_fn: Optional[AttnFn] = None,
                      tp_axis: Optional[str] = None,
                      ep_axis: Optional[str] = None) -> jnp.ndarray:
    """Logits-only wrapper over :func:`transformer_apply_with_aux`."""
    logits, _ = transformer_apply_with_aux(
        params, tokens, cfg, positions, attn_fn, tp_axis, ep_axis)
    return logits


def next_token_loss_and_aux(params: dict, tokens: jnp.ndarray,
                            cfg: TransformerConfig,
                            positions: Optional[jnp.ndarray] = None,
                            attn_fn: Optional[AttnFn] = None,
                            tp_axis: Optional[str] = None,
                            ep_axis: Optional[str] = None,
                            targets: Optional[jnp.ndarray] = None,
                            weights: Optional[jnp.ndarray] = None,
                            remat: bool = False
                            ) -> tuple[jnp.ndarray, jnp.ndarray, dict]:
    """Weighted summed next-token cross-entropy, total weight, and MoE aux
    (sums, not means, so multi-rank losses combine exactly via psum). The
    MoE load-balance loss is folded into the returned loss sum scaled by
    the local token weight, keeping the global mean exact under psum.

    Without ``targets``, the shift happens locally (the last token has no
    target and is dropped). With ``targets`` — sequence sharding, where the
    boundary target is the NEXT rank's first token — every position has a
    target and ``weights`` masks the positions that shouldn't count (the
    global final token).
    """
    x, aux = transformer_hidden_with_aux(
        params, tokens, cfg, positions, attn_fn, tp_axis, ep_axis,
        remat=remat)
    # one name for the head's matmul and the loss over it, forward and
    # backward (runtime/tracing.py SCOPES)
    with jax.named_scope(SCOPE_HEAD_LOSS):
        logits = lm_logits(params, x, cfg)
        if targets is None:
            logits = logits[:, :-1]
            tgt = tokens[:, 1:]
        else:
            tgt = targets
        ce_sum, w_sum = weighted_ce(logits, tgt, weights)
    loss_sum = ce_sum + aux["aux_loss"] * w_sum
    return loss_sum, w_sum, aux


def weighted_ce(logits: jnp.ndarray, targets: jnp.ndarray,
                weights: Optional[jnp.ndarray] = None
                ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Summed weighted cross-entropy (f32 log-softmax) and total weight."""
    if weights is None:
        weights = jnp.ones(targets.shape, jnp.float32)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    ll = jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return -(ll * weights).sum(), weights.sum()


def next_token_loss(params: dict, tokens: jnp.ndarray,
                    cfg: TransformerConfig,
                    positions: Optional[jnp.ndarray] = None,
                    attn_fn: Optional[AttnFn] = None,
                    tp_axis: Optional[str] = None,
                    targets: Optional[jnp.ndarray] = None,
                    weights: Optional[jnp.ndarray] = None,
                    ep_axis: Optional[str] = None
                    ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(loss_sum, weight_sum) wrapper over
    :func:`next_token_loss_and_aux` (MoE aux folded into the loss).
    ``ep_axis`` must match how the params were sharded: inside an
    ep-sharded shard_map the expert leaves are local shards and the
    dispatch needs the axis name."""
    loss_sum, w_sum, _ = next_token_loss_and_aux(
        params, tokens, cfg, positions, attn_fn, tp_axis, ep_axis,
        targets=targets, weights=weights)
    return loss_sum, w_sum
