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
import math
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from akka_allreduce_tpu.parallel.ep import (
    ExpertShareConfig,
    MoEConfig,
    dropless_moe,
    init_expert_share,
    init_moe_layer,
    moe_ffn,
)
from akka_allreduce_tpu.parallel.ring_attention import local_causal_attention
from akka_allreduce_tpu.runtime.tracing import (
    SCOPE_DENSE_FFN,
    SCOPE_HEAD_LOSS,
    SCOPE_MLA_ATTENTION,
)
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
    # The published epsilon of every RMSNorm (1e-6: what the program ran
    # before it took the key).
    norm_eps: float = 1e-6
    # What a layer IS, beyond the Llama-family block above (the serving
    # slot path runs both; training runs "standard" only):
    # * attention = "mla": multi-head latent attention. Queries go through
    #   a rank-``q_lora_rank`` bottleneck; keys and values are expanded
    #   from ONE rank-``kv_lora_rank`` latent a token plus one rotary key
    #   of ``qk_rope_head_dim`` that all heads share, so the cache holds
    #   kv_lora_rank + qk_rope_head_dim numbers a token an attention
    #   (models/generate.py ``init_kv_cache``). Head sizes are
    #   qk_nope_head_dim + qk_rope_head_dim for q.k and v_head_dim for v.
    # * block = "shortcut": the shortcut-connected double layer - two
    #   attentions, two dense FFNs of ``d_ff`` and ONE expert layer
    #   (``experts``, parallel/ep.py ``dropless_moe``) read from the first
    #   half's post-attention norm and added at the end of the second
    #   half. ``n_layers`` counts double layers.
    # * block = "standard" with attention = "mla" is the model described
    #   LAYER BY LAYER: ``layer_ffn[i]`` says whether layer i's FFN is a
    #   "dense" SwiGLU of ``d_ff`` or "sparse" (``experts``, with its
    #   shared expert), ``layer_indexer[i]`` whether its attention has an
    #   indexer of its own ("full": ``index_n_heads`` heads of
    #   ``index_head_dim`` score every cached position and the token
    #   attends the ``index_topk`` best) or attends the set the nearest
    #   full layer before it chose ("shared"). The cache holds a latent a
    #   layer and an index key a FULL layer. ``mla_lora_scales`` False
    #   runs the latent attention without its two lora scales.
    # * block = "standard" with ``layer_mixer`` is the HYBRID described layer
    #   by layer: ``layer_mixer[i]`` says whether layer i mixes its tokens
    #   with a state-space recurrence ("ssm": Mamba-2, ``ssm_heads`` heads of
    #   ``ssm_head_dim`` over a state of ``ssm_state`` numbers a channel that
    #   all heads' B and C share, a causal depthwise convolution of width
    #   ``ssm_conv`` ahead of it, ``ssm_chunk`` the published block length
    #   of its chunked form) or with an "attention": GQA (``n_heads`` /
    #   ``n_kv_heads``) with NO position signal (``rope`` False and no
    #   ``pos`` table) and the stated score scale ``attn_scale``. Every
    #   layer's FFN is the ``experts`` share. The cache holds a recurrent
    #   state and a convolution tail a lane an ssm layer (OVERWRITTEN each
    #   step, whatever the context) and keys and values an attention layer.
    #   ``embed_scale`` multiplies the embedding, ``residual_scale`` every
    #   branch before it joins the residual, ``logit_divisor`` divides the
    #   head's output (all 1 for every other kind).
    # * block = "standard" with attention = "mla" and NO ``layer_indexer``
    #   is latent attention over every position, layer by layer
    #   (``layer_ffn`` as above): the kind the full forward and the train
    #   step run (:func:`latent_block`), which no serving path runs yet.
    #   ``mtp_layers`` 1 gives it a multi-token-prediction module
    #   (models/mtp.py), trained as a second objective.
    # * block = "standard" with ``layer_mixer`` "kda" | "latent" is the
    #   hybrid of delta-rule linear attention and latent attention (Ling's
    #   ``bailing_hybrid``): a "kda" layer mixes its tokens with Kimi delta
    #   attention, ``kda_heads`` heads of ``kda_head_dim`` for q, k and v,
    #   each through a causal depthwise convolution of width ``kda_conv``,
    #   over a matrix state a head (float32, ``kda_head_dim`` squared) that
    #   decays a channel at a time, its log-decay bounded below by
    #   ``kda_lower_bound``. A "latent" layer is latent attention (the ``mla``
    #   sizes; ``q_lora_rank`` 0 is a full-rank query normed a head) with
    #   a sigmoid gate a head on its output (``w_gate``).
    #   ``layer_ffn`` says which layers end in a dense SwiGLU and which in
    #   the ``experts`` share. The cache holds a state and a convolution
    #   tail a lane a "kda" layer (OVERWRITTEN each step) and a latent a
    #   position a "latent" layer. ``rope_interleave`` rotates the pairs
    #   (x[2i], x[2i+1]) where the other kinds rotate (x[i], x[i + d/2]).
    attention: str = "gqa"
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    block: str = "standard"
    experts: Optional[ExpertShareConfig] = None
    layer_ffn: tuple = ()
    layer_indexer: tuple = ()
    index_n_heads: int = 0
    index_head_dim: int = 0
    index_topk: int = 0
    mla_lora_scales: bool = True
    layer_mixer: tuple = ()
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    ssm_state: int = 0
    ssm_conv: int = 0
    ssm_chunk: int = 0
    attn_scale: Optional[float] = None
    embed_scale: float = 1.0
    residual_scale: float = 1.0
    logit_divisor: float = 1.0
    mtp_layers: int = 0
    kda_heads: int = 0
    kda_head_dim: int = 0
    kda_conv: int = 0
    kda_lower_bound: float = 0.0
    rope_interleave: bool = False

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    @property
    def latent_dim(self) -> int:
        """What the cache holds a token an attention under ``mla``."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def latent_row(self) -> int:
        """Columns of a cached latent's row: ``latent_dim``, and for the
        layer-by-layer kind that rounded up to whole vector registers of
        128 (zeros), so that the device keeps a position's row contiguous
        and a gather of chosen positions reads those rows alone. (At
        ``latent_dim`` 576 the device would keep ``max_seq`` minor, as
        ops/pallas_kernels/attention.py ``latent_keys_lie_minor`` finds
        for the double layer's cache, whose fused kernel reads it so: a
        gather of rows from that layout touches every tile of the
        lane.)"""
        if not self.indexed:
            return self.latent_dim
        return -(-self.latent_dim // 128) * 128

    @property
    def mla_scales(self) -> tuple[float, float]:
        """The two lora scales, (d_model / rank) ** 0.5 each: on the
        query after its up-projection, on the latent after its norm
        (1, 1 where the configuration has none)."""
        if not self.mla_lora_scales:
            return (1.0, 1.0)
        return ((self.d_model / self.q_lora_rank) ** 0.5,
                (self.d_model / self.kv_lora_rank) ** 0.5)

    @property
    def learned_positions(self) -> bool:
        """Does the model add a learned ``pos`` table to its embedding?
        (Not under rope; not the hybrid, whose state-space layers order
        the tokens and whose attention has no position signal.)"""
        return not self.rope and not self.hybrid

    @property
    def hybrid(self) -> bool:
        """Does ``layer_mixer`` say, layer by layer, which layers carry a
        recurrent state and which attend without positions?"""
        return bool(self.layer_mixer)

    @property
    def indexed(self) -> bool:
        """Latent attention over an indexer's selection in a standard
        block (``layer_ffn`` / ``layer_indexer``)."""
        return self.block == "standard" and self.attention == "mla" \
            and bool(self.layer_indexer)

    @property
    def full_latent(self) -> bool:
        """Latent attention over every position in a standard block,
        layer by layer (``layer_ffn``, no ``layer_indexer``, no
        ``layer_mixer``)."""
        return self.block == "standard" and self.attention == "mla" \
            and not self.layer_indexer and not self.layer_mixer

    @property
    def layerwise(self) -> bool:
        """Is this a model described layer by layer (:attr:`indexed` or
        :attr:`hybrid`)? Its cached block reads and writes the cache in
        place, so its prompts go through the cache, in chunks where they
        are long."""
        return self.indexed or self.hybrid

    @property
    def ssm_layers(self) -> tuple:
        """The layers whose mixer is a state-space recurrence, in order:
        layer ``ssm_layers[j]`` owns state and tail entry ``j``."""
        return tuple(i for i, kind in enumerate(self.layer_mixer)
                     if kind == "ssm")

    @property
    def attention_layers(self) -> tuple:
        """The hybrid's attention layers, in order: layer
        ``attention_layers[a]`` owns ``k`` / ``v`` cache entry ``a``."""
        return tuple(i for i, kind in enumerate(self.layer_mixer)
                     if kind == "attention")

    @property
    def kda_layers(self) -> tuple:
        """The layers whose mixer is Kimi delta attention, in order: layer
        ``kda_layers[j]`` owns state and tail entry ``j``."""
        return tuple(i for i, kind in enumerate(self.layer_mixer)
                     if kind == "kda")

    @property
    def latent_layers(self) -> tuple:
        """The hybrid's latent attentions, in order: layer
        ``latent_layers[a]`` owns latent cache entry ``a``."""
        return tuple(i for i, kind in enumerate(self.layer_mixer)
                     if kind == "latent")

    @property
    def kda_dim(self) -> int:
        """Channels of a delta-rule mixer's q, k and v (heads x head
        size)."""
        return self.kda_heads * self.kda_head_dim

    @property
    def ssm_inner(self) -> int:
        """Channels of a state-space mixer (heads x head size)."""
        return self.ssm_heads * self.ssm_head_dim

    @property
    def ssm_conv_dim(self) -> int:
        """Channels the convolution runs over: x, B and C side by side."""
        return self.ssm_inner + 2 * self.ssm_state

    @property
    def n_attentions(self) -> int:
        """Latent attentions, each with a cache entry of its own."""
        if self.hybrid:
            return len(self.latent_layers)
        return 2 * self.n_layers if self.block == "shortcut" \
            else self.n_layers

    @property
    def full_layers(self) -> tuple:
        """The layers that have an indexer, in order: layer
        ``full_layers[f]`` writes index cache entry ``f``."""
        return tuple(i for i, kind in enumerate(self.layer_indexer)
                     if kind == "full")

    @property
    def n_expert_layers(self) -> int:
        """Expert layers (``experts``) a token passes (the main stack's;
        the multi-token-prediction module has one more)."""
        if self.layerwise or self.full_latent:
            return sum(kind == "sparse" for kind in self.layer_ffn)
        return self.n_layers if self.experts is not None else 0

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
        if self.attention not in ("gqa", "mla"):
            raise ValueError(f"unknown attention {self.attention!r}")
        if self.block not in ("standard", "shortcut"):
            raise ValueError(f"unknown block {self.block!r}")
        if self.attention == "mla":
            sizes = (self.q_lora_rank, self.kv_lora_rank,
                     self.qk_nope_head_dim, self.qk_rope_head_dim,
                     self.v_head_dim)
            if min(sizes[1:]) < 1 or self.qk_rope_head_dim % 2 or (
                    self.q_lora_rank < (1 if self.mla_lora_scales else 0)):
                raise ValueError(
                    f"mla needs its five sizes (q_lora_rank, 0 only for a "
                    f"full-rank query without lora scales, kv_lora_rank, "
                    f"qk_nope_head_dim, qk_rope_head_dim even, v_head_dim), "
                    f"got {sizes}")
            if not self.rope or self.attn_window is not None \
                    or self.n_kv_heads is not None:
                raise ValueError(
                    "mla runs with rope, without a window and without "
                    "n_kv_heads (its keys come from the latent)")
        if self.block == "shortcut" and (
                self.attention != "mla" or self.experts is None):
            raise ValueError(
                "the shortcut double layer comes with latent attention "
                "and an `experts` share")
        if self.experts is not None and self.attention != "mla" \
                and not self.hybrid:
            raise ValueError(
                "an `experts` share comes with latent attention (the "
                "shortcut double layer, or layer by layer) or with "
                "`layer_mixer`")
        described = (self.layer_ffn, self.layer_indexer, self.index_n_heads,
                     self.index_head_dim, self.index_topk)
        hybrid_sizes = (self.ssm_heads, self.ssm_head_dim, self.ssm_state,
                        self.ssm_conv, self.ssm_chunk)
        kda_sizes = (self.kda_heads, self.kda_head_dim, self.kda_conv)
        if self.hybrid and "kda" in self.layer_mixer:
            self._check_kda_hybrid(hybrid_sizes, kda_sizes)
        elif any(kda_sizes) or self.kda_lower_bound \
                or self.rope_interleave:
            raise ValueError(
                "kda_* / rope_interleave describe the hybrid of "
                "`layer_mixer` 'kda' | 'latent'")
        elif self.hybrid:
            self._check_hybrid(hybrid_sizes)
        elif any(hybrid_sizes) or self.attn_scale is not None or (
                self.embed_scale, self.residual_scale,
                self.logit_divisor) != (1.0, 1.0, 1.0):
            raise ValueError(
                "ssm_* / attn_scale / embed_scale / residual_scale / "
                "logit_divisor describe the hybrid of `layer_mixer`")
        elif self.indexed or self.full_latent:
            self._check_layerwise()
        elif any(described) or not self.mla_lora_scales:
            raise ValueError(
                f"layer_ffn / layer_indexer / index_* / mla_lora_scales "
                f"describe latent attention in a standard block "
                f"(block='standard', attention='mla'), got block="
                f"{self.block!r} attention={self.attention!r}")
        if self.block == "shortcut" and (
                self.ffn != "swiglu" or self.moe is not None
                or self.tie_embeddings):
            raise ValueError(
                "the shortcut double layer has swiglu dense FFNs and an "
                "untied head, and its expert layer is `experts`, not `moe`")
        if self.mtp_layers not in (0, 1) or self.mtp_layers and not (
                self.full_latent and self.experts is not None):
            raise ValueError(
                f"mtp_layers={self.mtp_layers}: one multi-token-prediction "
                f"module (a sparse layer) follows latent attention over "
                f"every position with an `experts` share")

    def _check_layerwise(self) -> None:
        n = self.n_layers
        if len(self.layer_ffn) != n \
                or len(self.layer_indexer) not in (0, n) \
                or set(self.layer_ffn) - {"dense", "sparse"} \
                or set(self.layer_indexer) - {"full", "shared"}:
            raise ValueError(
                f"latent attention in a standard block is described layer "
                f"by layer: layer_ffn ('dense' | 'sparse') and, where it "
                f"has an indexer, layer_indexer ('full' | 'shared'), {n} "
                f"entries each, got {self.layer_ffn} and "
                f"{self.layer_indexer}")
        if self.full_latent:
            if self.index_n_heads or self.index_head_dim or self.index_topk:
                raise ValueError("index_* size an indexer, and no layer "
                                 "has one (layer_indexer is empty)")
        elif self.layer_indexer[0] != "full":
            raise ValueError("layer 0 shares an indexer's choice and no "
                             "layer before it has one")
        elif min(self.index_n_heads, self.index_topk) < 1 \
                or self.index_head_dim < self.qk_rope_head_dim:
            raise ValueError(
                f"the indexer needs index_n_heads, index_topk >= 1 and "
                f"index_head_dim >= qk_rope_head_dim, got "
                f"{self.index_n_heads}, {self.index_topk}, "
                f"{self.index_head_dim}")
        if ("sparse" in self.layer_ffn) != (self.experts is not None):
            raise ValueError("`experts` is the sparse layers' share: set "
                             "where a layer is 'sparse', and only there")
        if self.ffn != "swiglu" or self.moe is not None \
                or self.tie_embeddings:
            raise ValueError(
                "the layer-by-layer model has swiglu dense FFNs and an "
                "untied head, and its expert layers are `experts`")

    def _check_hybrid(self, sizes: tuple) -> None:
        n = self.n_layers
        if len(self.layer_mixer) != n \
                or set(self.layer_mixer) - {"ssm", "attention"}:
            raise ValueError(
                f"layer_mixer says 'ssm' | 'attention' for each of the {n} "
                f"layers, got {self.layer_mixer}")
        if min(sizes) < 1:
            raise ValueError(
                f"the state-space mixer needs its five sizes (ssm_heads, "
                f"ssm_head_dim, ssm_state, ssm_conv, ssm_chunk), got {sizes}")
        if self.block != "standard" or self.attention != "gqa" or self.rope \
                or self.attn_window is not None or self.attn_scale is None:
            raise ValueError(
                "the hybrid's attention is GQA in a standard block with no "
                "position signal (rope False), no window and a stated "
                "attn_scale")
        if self.experts is None or self.ffn != "swiglu" \
                or self.moe is not None \
                or self.layer_ffn != ("sparse",) * n or self.layer_indexer \
                or self.index_n_heads or self.index_head_dim \
                or self.index_topk or not self.mla_lora_scales:
            raise ValueError(
                "every layer of the hybrid ends in the `experts` share "
                "(layer_ffn all 'sparse', no indexer, no `moe`)")

    def _check_kda_hybrid(self, ssm_sizes: tuple, sizes: tuple) -> None:
        n = self.n_layers
        if len(self.layer_mixer) != n \
                or set(self.layer_mixer) - {"kda", "latent"}:
            raise ValueError(
                f"layer_mixer says 'kda' | 'latent' for each of the {n} "
                f"layers, got {self.layer_mixer}")
        if min(sizes) < 1 or self.kda_lower_bound >= 0:
            raise ValueError(
                f"the delta-rule mixer needs its three sizes (kda_heads, "
                f"kda_head_dim, kda_conv) and a negative "
                f"kda_lower_bound, got {sizes}, {self.kda_lower_bound}")
        if any(ssm_sizes) or self.attn_scale is not None or (
                self.embed_scale, self.residual_scale,
                self.logit_divisor) != (1.0, 1.0, 1.0):
            raise ValueError("ssm_* / attn_scale / embed_scale / "
                             "residual_scale / logit_divisor describe the "
                             "hybrid of 'ssm' | 'attention' layers")
        if self.attention != "mla" or self.block != "standard" \
                or self.mla_lora_scales:
            raise ValueError(
                "the delta-rule hybrid's attention is latent attention in a "
                "standard block, without lora scales")
        if len(self.layer_ffn) != n \
                or set(self.layer_ffn) - {"dense", "sparse"} \
                or self.layer_indexer or self.index_n_heads \
                or self.index_head_dim or self.index_topk \
                or ("sparse" in self.layer_ffn) != (self.experts is not None):
            raise ValueError(
                "each layer of the delta-rule hybrid ends in a 'dense' "
                "SwiGLU or the 'sparse' `experts` share (layer_ffn, one "
                "entry a layer; no indexer)")
        if self.ffn != "swiglu" or self.moe is not None \
                or self.tie_embeddings or self.mtp_layers:
            raise ValueError(
                "the delta-rule hybrid has swiglu dense FFNs, an untied head, "
                "`experts` as its expert layers and no mtp_layers")

    @property
    def new_kind(self) -> Optional[str]:
        """None for the Llama-family block every path runs; else what a
        path that cannot run this configuration names in its refusal."""
        if self.block == "shortcut":
            return ("the shortcut double layer with latent attention "
                    "(block='shortcut', attention='mla')")
        if self.kda_layers:
            return ("delta-rule linear attention beside latent attention, "
                    "layer by layer (block='standard', layer_mixer 'kda' | "
                    "'latent')")
        if self.hybrid:
            return ("state-space mixers beside attention without "
                    "positions, layer by layer (block='standard', "
                    "layer_mixer)")
        if self.indexed:
            return ("latent attention over an indexer's selection, layer "
                    "by layer (block='standard', attention='mla')")
        if self.full_latent:
            return ("latent attention over every position, layer by layer "
                    "(block='standard', attention='mla', no layer_indexer)")
        return None


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


def rmsnorm(x: jnp.ndarray, scale: jnp.ndarray,
            eps: float = 1e-6) -> jnp.ndarray:
    """RMS statistics in f32 regardless of compute dtype (bf16 squares
    lose ~5 bits where the variance needs them), result back in x's.
    ``eps`` is the configuration's (``TransformerConfig.norm_eps``)."""
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps)).astype(x.dtype) * scale


def lm_logits(params: dict, x: jnp.ndarray,
              cfg: TransformerConfig) -> jnp.ndarray:
    """Output head: the lm_head matmul, or the transposed embedding under
    weight tying (one shared matrix serving both ends); divided by
    ``cfg.logit_divisor`` where the configuration has one."""
    if cfg.tie_embeddings:
        logits = x @ params["embed"].T
    else:
        logits = x @ params["lm_head"]
    if cfg.logit_divisor != 1.0:
        logits = logits / cfg.logit_divisor
    return logits


def embed_tokens(params: dict, tokens: jnp.ndarray,
                 cfg: TransformerConfig) -> jnp.ndarray:
    """The embedding rows of ``tokens``, times ``cfg.embed_scale`` where
    the configuration has one (the learned ``pos`` table, where a model has
    one, is its caller's to add)."""
    x = params["embed"][tokens]
    if cfg.embed_scale != 1.0:
        x = x * jnp.asarray(cfg.embed_scale, x.dtype)
    return x


def init_transformer(key: jax.Array, cfg: TransformerConfig,
                     tp: int = 1) -> dict:
    """Full (unsharded) parameters when tp=1; per-rank TP shards when the
    caller slices (models/train.py shards via the mesh instead — this
    function always builds the full tree; tp only validates divisibility)."""
    if cfg.n_heads % tp or cfg.d_ff % tp or cfg.kv_heads % tp:
        raise ValueError(
            f"tp={tp} must divide n_heads={cfg.n_heads}, "
            f"n_kv_heads={cfg.kv_heads}, and d_ff={cfg.d_ff}")
    if cfg.new_kind is not None:
        if tp != 1:
            raise ValueError(f"tp={tp}: {cfg.new_kind} is not sharded "
                             f"over tp yet")
        return _init_new_kind(key, cfg)
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


def _normal(key, shape, dtype):
    """Normal with std fan_in ** -0.5 (the first axis of a matrix, the
    second of a stack of experts)."""
    return jax.random.normal(key, shape, dtype) * shape[-2] ** -0.5


def init_mla(key: jax.Array, cfg: TransformerConfig) -> dict:
    """One latent attention's leaves: ``ln`` (the norm ahead of it),
    ``wq_a`` -> ``q_norm`` -> ``wq_b`` (the query's bottleneck), ``wkv_a``
    (hidden -> latent + shared rotary key), ``kv_norm``, ``wkv_b`` (latent
    -> every head's nope key and value) and ``wo``. The two
    up-projections out of a low rank take the std of the hidden size, not
    of their own fan-in: under that init the lora scales (d_model / rank)
    ** 0.5 keep q and k at unit variance, which is what they are for (at
    rank ** -0.5 the scales multiply q.k by their product, the softmax
    turns into an argmax and rounding grows threefold a double layer)."""
    d, h, dt = cfg.d_model, cfg.n_heads, cfg.dtype
    qk = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
    k = jax.random.split(key, 5)

    def up(key, rank, width):
        # without the scales: fan-in, like every other matrix
        std = (d if cfg.mla_lora_scales else rank) ** -0.5
        return jax.random.normal(key, (rank, width), dt) * std
    out = {
        "ln": jnp.ones((d,), dt),
        "wkv_a": _normal(k[2], (d, cfg.latent_dim), dt),
        "kv_norm": jnp.ones((cfg.kv_lora_rank,), dt),
        "wkv_b": up(k[3], cfg.kv_lora_rank,
                    h * (cfg.qk_nope_head_dim + cfg.v_head_dim)),
        "wo": _normal(k[4], (h * cfg.v_head_dim, d), dt),
    }
    if not cfg.q_lora_rank:
        # a full-rank query, normed a head (``q_norm`` over its qk numbers),
        # and in the delta-rule hybrid a gate a head on the output
        out.update(wq=_normal(k[0], (d, h * qk), dt),
                   q_norm=jnp.ones((qk,), dt))
        if cfg.kda_layers:
            out["w_gate"] = _normal(k[1], (d, h), dt)
        return out
    out.update(wq_a=_normal(k[0], (d, cfg.q_lora_rank), dt),
               q_norm=jnp.ones((cfg.q_lora_rank,), dt),
               wq_b=up(k[1], cfg.q_lora_rank, h * qk))
    return out


def mla_project(p: dict, x: jnp.ndarray, cfg: TransformerConfig,
                rope: Callable[[jnp.ndarray], jnp.ndarray]) -> tuple:
    """A latent attention's projections of x (b, t, d), the same in every
    path that runs one: (``h``, the normed input; ``c_q``, the query's
    normed bottleneck; ``q_nope`` (b, t, heads, qk_nope_head_dim);
    ``q_rope`` (b, t, heads, qk_rope_head_dim) after ``rope``; ``c_kv``
    (b, t, kv_lora_rank), the normed and scaled latent; ``k_rope`` (b, t,
    1, qk_rope_head_dim), the one rotary key all heads share, after
    ``rope``). ``rope`` rotates (b, t, h, d) at the block's positions."""
    b, t, _ = x.shape
    nope, rank = cfg.qk_nope_head_dim, cfg.kv_lora_rank
    s_q, s_kv = cfg.mla_scales
    h = rmsnorm(x, p["ln"], cfg.norm_eps)
    if cfg.q_lora_rank:
        c_q = rmsnorm(h @ p["wq_a"], p["q_norm"], cfg.norm_eps)
        q = ((c_q @ p["wq_b"]) * s_q).reshape(b, t, cfg.n_heads, -1)
    else:
        c_q = None
        q = rmsnorm((h @ p["wq"]).reshape(b, t, cfg.n_heads, -1),
                    p["q_norm"], cfg.norm_eps)
    q_nope, q_rope = q[..., :nope], rope(q[..., nope:])
    down = h @ p["wkv_a"]
    c_kv = rmsnorm(down[..., :rank], p["kv_norm"], cfg.norm_eps) * s_kv
    return h, c_q, q_nope, q_rope, c_kv, rope(down[:, :, None, rank:])


def init_indexer(key: jax.Array, cfg: TransformerConfig) -> dict:
    """A full layer's indexer: ``wq_b`` (the query's bottleneck ->
    ``index_n_heads`` heads of ``index_head_dim``), ``wk`` (hidden -> the
    one index key a token), ``k_norm`` / ``k_bias`` (the key's LayerNorm)
    and ``ww`` (hidden -> a weight a head)."""
    d, dt = cfg.d_model, cfg.dtype
    k = jax.random.split(key, 3)
    return {
        "wq_b": _normal(k[0], (cfg.q_lora_rank,
                               cfg.index_n_heads * cfg.index_head_dim), dt),
        "wk": _normal(k[1], (d, cfg.index_head_dim), dt),
        "k_norm": jnp.ones((cfg.index_head_dim,), dt),
        "k_bias": jnp.zeros((cfg.index_head_dim,), dt),
        "ww": _normal(k[2], (d, cfg.index_n_heads), dt),
    }


def init_ssm_mixer(key: jax.Array, cfg: TransformerConfig) -> dict:
    """One state-space mixer's leaves (Mamba-2): ``w_in`` (hidden -> z |
    x B C | dt), the depthwise convolution ``conv_w`` (channels, width) and
    ``conv_b``, a head's ``dt_bias``, ``a_log`` and ``d`` (float32), the
    gated norm's gain ``norm`` and ``w_out``. The recurrence's constants
    take the Mamba-2 initialisation: ``A = exp(a_log)`` uniform in 1-16,
    ``softplus(dt_bias)`` log-uniform in 0.001-0.1, ``d`` ones, so that a
    head remembers tens to thousands of tokens."""
    d, dt = cfg.d_model, cfg.dtype
    inner, heads = cfg.ssm_inner, cfg.ssm_heads
    k = jax.random.split(key, 5)
    step = jnp.exp(jax.random.uniform(
        k[3], (heads,), jnp.float32, math.log(1e-3), math.log(1e-1)))
    return {
        "w_in": _normal(k[0], (d, 2 * inner + 2 * cfg.ssm_state + heads),
                        dt),
        "conv_w": jax.random.normal(k[1], (cfg.ssm_conv_dim, cfg.ssm_conv),
                                    dt) * cfg.ssm_conv ** -0.5,
        "conv_b": jnp.zeros((cfg.ssm_conv_dim,), dt),
        # the inverse of softplus
        "dt_bias": step + jnp.log(-jnp.expm1(-step)),
        "a_log": jnp.log(jax.random.uniform(k[4], (heads,), jnp.float32,
                                            1.0, 16.0)),
        "d": jnp.ones((heads,), jnp.float32),
        "norm": jnp.ones((inner,), dt),
        "w_out": _normal(k[2], (inner, d), dt),
    }


def init_kda_mixer(key: jax.Array, cfg: TransformerConfig) -> dict:
    """One delta-rule mixer's leaves (Kimi delta attention, full-rank
    gates): ``w_in`` (hidden -> q | k | v | f, the decay's input, a
    channel | g, the output gate, a channel | beta, a head), the depthwise
    convolution ``conv_w`` (the 3 x ``kda_dim`` channels of q, k and v,
    width; no bias), ``a_log`` (a head) and ``dt_bias`` (a channel), both
    float32, the output norm's gain ``norm`` (one group over all channels)
    and ``w_out``. The decay's constants take the Mamba-2 initialisation
    that the public KDA layer keeps: ``exp(a_log)`` uniform in 1-16,
    ``softplus(dt_bias)`` log-uniform in 0.001-0.1, so that a channel
    remembers a few to thousands of tokens."""
    d, dt, kd = cfg.d_model, cfg.dtype, cfg.kda_dim
    k = jax.random.split(key, 5)
    step = jnp.exp(jax.random.uniform(
        k[3], (kd,), jnp.float32, math.log(1e-3), math.log(1e-1)))
    return {
        "w_in": _normal(k[0], (d, 5 * kd + cfg.kda_heads), dt),
        "conv_w": jax.random.normal(k[1], (3 * kd, cfg.kda_conv), dt)
        * cfg.kda_conv ** -0.5,
        "a_log": jnp.log(jax.random.uniform(k[4], (cfg.kda_heads,),
                                            jnp.float32, 1.0, 16.0)),
        # the inverse of softplus
        "dt_bias": step + jnp.log(-jnp.expm1(-step)),
        "norm": jnp.ones((kd,), dt),
        "w_out": _normal(k[2], (kd, d), dt),
    }


def _init_kda_hybrid(key: jax.Array, cfg: TransformerConfig) -> dict:
    """The delta-rule hybrid's tree: ``layers[i]`` holds either ``ln1`` and
    ``kda`` (:func:`init_kda_mixer`) or ``mla`` (:func:`init_mla`, its
    ``ln`` the layer's first norm), then ``ln2`` and either the dense
    SwiGLU's ``w1`` / ``w3`` / ``w2`` or ``moe``; an untied head."""
    d, dt = cfg.d_model, cfg.dtype
    kg = iter(jax.random.split(key, 2 + 2 * cfg.n_layers))
    params = {"embed": jax.random.normal(next(kg), (cfg.vocab_size, d), dt)
              * d ** -0.5,
              "lm_head": _normal(next(kg), (d, cfg.vocab_size), dt),
              "out_norm": jnp.ones((d,), dt), "layers": []}
    for kind, ffn in zip(cfg.layer_mixer, cfg.layer_ffn):
        if kind == "kda":
            layer = {"ln1": jnp.ones((d,), dt),
                     "kda": init_kda_mixer(next(kg), cfg)}
        else:
            layer = {"mla": init_mla(next(kg), cfg)}
        layer["ln2"] = jnp.ones((d,), dt)
        if ffn == "sparse":
            layer["moe"] = init_expert_share(next(kg), d, cfg.experts, dt)
        else:
            k1, k2, k3 = jax.random.split(next(kg), 3)
            layer.update(w1=_normal(k1, (d, cfg.d_ff), dt),
                         w3=_normal(k2, (d, cfg.d_ff), dt),
                         w2=_normal(k3, (cfg.d_ff, d), dt))
        params["layers"].append(layer)
    return params


def _init_hybrid(key: jax.Array, cfg: TransformerConfig) -> dict:
    """The hybrid's tree: ``layers[i]`` holds ``ln1``, either ``ssm``
    (:func:`init_ssm_mixer`) or the attention's ``wq`` / ``wk`` / ``wv`` /
    ``wo``, ``ln2`` and ``moe`` (parallel/ep.py ``init_expert_share``); a
    tied head has no ``lm_head``."""
    d, dt = cfg.d_model, cfg.dtype
    d_kv = cfg.kv_heads * cfg.head_dim
    kg = iter(jax.random.split(key, 2 + 6 * cfg.n_layers))
    params = {"embed": jax.random.normal(next(kg), (cfg.vocab_size, d), dt)
              * d ** -0.5,
              "out_norm": jnp.ones((d,), dt), "layers": []}
    if not cfg.tie_embeddings:
        params["lm_head"] = _normal(next(kg), (d, cfg.vocab_size), dt)
    for kind in cfg.layer_mixer:
        layer = {"ln1": jnp.ones((d,), dt), "ln2": jnp.ones((d,), dt),
                 "moe": init_expert_share(next(kg), d, cfg.experts, dt)}
        if kind == "ssm":
            layer["ssm"] = init_ssm_mixer(next(kg), cfg)
        else:
            layer.update(wq=_normal(next(kg), (d, d), dt),
                         wk=_normal(next(kg), (d, d_kv), dt),
                         wv=_normal(next(kg), (d, d_kv), dt),
                         wo=_normal(next(kg), (d, d), dt))
        params["layers"].append(layer)
    return params


def _init_new_kind(key: jax.Array, cfg: TransformerConfig) -> dict:
    """The tree of shortcut double layers: ``layers[i]`` holds ``mla``
    and ``ffn`` (two of each; an FFN's ``ln`` is its half's post-attention
    norm) and ``moe`` (parallel/ep.py ``init_expert_share``). Of the
    layer-by-layer model: ``mla`` (one), ``indexer`` in a full layer,
    ``ln2`` and either the dense FFN's ``w1`` / ``w3`` / ``w2`` or
    ``moe``; with ``mtp_layers``, ``mtp`` holds the multi-token-prediction
    module: ``enorm`` / ``hnorm`` (the embedding's and the hidden state's
    norms), ``eh_proj`` (2 x hidden -> hidden), ``layer`` (a sparse layer)
    and ``norm`` (ahead of the shared head)."""
    if cfg.kda_layers:
        return _init_kda_hybrid(key, cfg)
    if cfg.hybrid:
        return _init_hybrid(key, cfg)
    d, dt = cfg.d_model, cfg.dtype
    kg = iter(jax.random.split(key, 2 + 8 * cfg.n_layers))

    def ffn():
        k1, k2, k3 = jax.random.split(next(kg), 3)
        return {"ln": jnp.ones((d,), dt),
                "w1": _normal(k1, (d, cfg.d_ff), dt),
                "w3": _normal(k2, (d, cfg.d_ff), dt),
                "w2": _normal(k3, (cfg.d_ff, d), dt)}

    params = {"embed": jax.random.normal(next(kg), (cfg.vocab_size, d), dt)
              * d ** -0.5,
              "lm_head": _normal(next(kg), (d, cfg.vocab_size), dt),
              "out_norm": jnp.ones((d,), dt), "layers": []}
    def sparse(layer):
        layer["ln2"] = jnp.ones((d,), dt)
        layer["moe"] = init_expert_share(next(kg), d, cfg.experts, dt)
        return layer

    for i in range(cfg.n_layers):
        if cfg.block == "shortcut":
            params["layers"].append({
                "mla": [init_mla(next(kg), cfg) for _ in range(2)],
                "ffn": [ffn() for _ in range(2)],
                "moe": init_expert_share(next(kg), d, cfg.experts, dt)})
            continue
        layer = {"mla": init_mla(next(kg), cfg)}
        if cfg.indexed and cfg.layer_indexer[i] == "full":
            layer["indexer"] = init_indexer(next(kg), cfg)
        if cfg.layer_ffn[i] == "sparse":
            sparse(layer)
        else:
            dense = ffn()
            layer.update(ln2=dense.pop("ln"), **dense)
        params["layers"].append(layer)
    if cfg.mtp_layers:
        params["mtp"] = {
            "enorm": jnp.ones((d,), dt), "hnorm": jnp.ones((d,), dt),
            "eh_proj": _normal(next(kg), (2 * d, d), dt),
            "layer": sparse({"mla": init_mla(next(kg), cfg)}),
            "norm": jnp.ones((d,), dt)}
    return params


def config_from_hf(hf: dict, max_seq: int, dtype=jnp.bfloat16,
                   experts_held: Optional[tuple[int, int]] = None
                   ) -> TransformerConfig:
    """A :class:`TransformerConfig` from a published ``config.json`` (or a
    benchmark configuration file that keeps its keys): the one place that
    knows the key names. Three families are read (the dense block is built
    from the ``--d-model/...`` flags): ``model_type`` "glm_moe_dsa" is the
    model described layer by layer, latent attention over an indexer's
    selection with sigmoid routing and a shared expert
    (:func:`_config_from_glm_moe_dsa`); ``model_type`` "granitemoehybrid"
    is the hybrid of state-space mixers and attention without positions
    (:func:`_config_from_granitemoehybrid`); ``attention_method`` "MLA" with
    ``zero_expert_num`` is the shortcut double layer with latent
    attention; ``model_type`` "glm4_moe_lite" is latent attention over
    every position with sigmoid routing, a shared expert and a
    multi-token-prediction module (:func:`_config_from_glm4_moe_lite`);
    ``model_type`` "bailing_hybrid" is the hybrid of delta-rule linear
    attention and latent attention with group-limited sigmoid routing
    (:func:`_config_from_bailing_hybrid`). ``experts_held`` = (offset,
    count) of the real experts that this chip holds (default: the key
    ``experts_held`` of ``hf``, the one key that no ``config.json`` has;
    else all of them)."""
    if hf.get("model_type") == "glm_moe_dsa":
        return _config_from_glm_moe_dsa(hf, max_seq, dtype, experts_held)
    if hf.get("model_type") == "glm4_moe_lite":
        return _config_from_glm4_moe_lite(hf, max_seq, dtype, experts_held)
    if hf.get("model_type") == "granitemoehybrid":
        return _config_from_granitemoehybrid(hf, max_seq, dtype,
                                             experts_held)
    if hf.get("model_type") == "bailing_hybrid":
        return _config_from_bailing_hybrid(hf, max_seq, dtype, experts_held)
    eps = float(hf.get("rms_norm_eps", 1e-6))
    if hf.get("attention_method") != "MLA":
        raise ValueError(
            f"attention_method {hf.get('attention_method')!r}, model_type "
            f"{hf.get('model_type')!r}: only the MLA shortcut double layer, "
            f"glm_moe_dsa, glm4_moe_lite, granitemoehybrid and "
            f"bailing_hybrid are read from a config.json")
    for key in ("mla_scale_q_lora", "mla_scale_kv_lora"):
        if not hf.get(key, False):
            raise ValueError(f"{key} false: latent attention without its "
                             f"lora scale is not implemented")
    if hf.get("zero_expert_type", "identity") != "identity":
        raise ValueError(f"zero_expert_type {hf['zero_expert_type']!r}: "
                         f"only identity experts are implemented")
    n_real = hf["n_routed_experts"]
    offset, count = experts_held or hf.get("experts_held", (0, n_real))
    return TransformerConfig(
        vocab_size=hf["vocab_size"], d_model=hf["hidden_size"],
        n_heads=hf["num_attention_heads"], n_layers=hf["num_layers"],
        d_ff=hf["ffn_hidden_size"], max_seq=max_seq, dtype=dtype,
        rope=True, rope_theta=float(hf["rope_theta"]), ffn="swiglu",
        norm_eps=eps, attention="mla", q_lora_rank=hf["q_lora_rank"],
        kv_lora_rank=hf["kv_lora_rank"],
        qk_nope_head_dim=hf["qk_nope_head_dim"],
        qk_rope_head_dim=hf["qk_rope_head_dim"],
        v_head_dim=hf["v_head_dim"], block="shortcut",
        experts=ExpertShareConfig(
            n_outputs=n_real + hf["zero_expert_num"],
            n_identity=hf["zero_expert_num"], top_k=hf["moe_topk"],
            scale=float(hf["routed_scaling_factor"]),
            d_ff=hf["expert_ffn_hidden_size"],
            held_offset=int(offset), held_count=int(count)))


_GLM_CAN = (("n_group", 1), ("topk_group", 1), ("topk_method", "noaux_tc"),
            ("scoring_func", "sigmoid"), ("hidden_act", "silu"),
            ("attention_bias", False), ("tie_word_embeddings", False))


def _refuse_by_key(hf: dict, family: str, more: tuple = ()) -> None:
    """Raise, naming the key, where ``hf`` asks for what the GLM families'
    routing and block do not implement (a missing key is what is
    implemented)."""
    for key, can in _GLM_CAN + more:
        if hf.get(key, can) != can:
            raise ValueError(f"{key} {hf[key]!r}: only {can!r} is "
                             f"implemented for {family}")


def _glm_experts(hf: dict, experts_held) -> ExpertShareConfig:
    """The GLM families' expert share: ``n_routed_experts`` sigmoid
    outputs, top ``num_experts_per_tok`` by score + bias, renormalised
    over the picked and scaled, ``n_shared_experts`` shared experts of the
    routed width each; this chip holds ``experts_held``."""
    n_real = hf["n_routed_experts"]
    offset, count = experts_held or hf.get("experts_held", (0, n_real))
    return ExpertShareConfig(
        n_outputs=n_real, top_k=hf["num_experts_per_tok"],
        scale=float(hf["routed_scaling_factor"]),
        d_ff=hf["moe_intermediate_size"], scoring="sigmoid",
        renormalise=bool(hf.get("norm_topk_prob", True)),
        d_shared=hf.get("n_shared_experts", 0) * hf["moe_intermediate_size"],
        held_offset=int(offset), held_count=int(count))


def _config_from_glm4_moe_lite(hf: dict, max_seq: int, dtype,
                               experts_held) -> TransformerConfig:
    """``model_type`` "glm4_moe_lite": latent attention over every position
    in every layer (no indexer, no lora scale), the first
    ``first_k_dense_replace`` layers a dense SwiGLU of
    ``intermediate_size``, every later one the expert share of
    :func:`_glm_experts`, top-level ``rope_theta``, and
    ``num_nextn_predict_layers`` (0 or 1) multi-token-prediction modules.
    What cannot run is refused by the name of its key."""
    n = hf["num_hidden_layers"]
    _refuse_by_key(hf, "glm4_moe_lite", (("partial_rotary_factor", 1),
                                         ("rope_scaling", None)))
    mtp = hf.get("num_nextn_predict_layers", 0)
    if mtp not in (0, 1):
        raise ValueError(f"num_nextn_predict_layers {mtp!r}: one "
                         f"multi-token-prediction module at most")
    dense = hf.get("first_k_dense_replace", 0)
    if not 0 <= dense <= n:
        raise ValueError(f"first_k_dense_replace {dense!r} of "
                         f"num_hidden_layers {n}")
    return TransformerConfig(
        vocab_size=hf["vocab_size"], d_model=hf["hidden_size"],
        n_heads=hf["num_attention_heads"], n_layers=n,
        d_ff=hf["intermediate_size"], max_seq=max_seq, dtype=dtype,
        rope=True, rope_theta=float(hf["rope_theta"]), ffn="swiglu",
        norm_eps=float(hf.get("rms_norm_eps", 1e-6)),
        attention="mla", q_lora_rank=hf["q_lora_rank"],
        kv_lora_rank=hf["kv_lora_rank"],
        qk_nope_head_dim=hf["qk_nope_head_dim"],
        qk_rope_head_dim=hf["qk_rope_head_dim"],
        v_head_dim=hf["v_head_dim"], block="standard",
        layer_ffn=("dense",) * dense + ("sparse",) * (n - dense),
        mla_lora_scales=False, mtp_layers=mtp,
        experts=_glm_experts(hf, experts_held) if dense < n else None)


def _config_from_glm_moe_dsa(hf: dict, max_seq: int, dtype,
                             experts_held) -> TransformerConfig:
    """``model_type`` "glm_moe_dsa": the two per-layer lists
    (``mlp_layer_types``, ``indexer_types``), the indexer's sizes, sigmoid
    routing renormalised over the picked with ``n_shared_experts`` shared
    experts of the routed width each, ``rope_parameters.rope_theta``, and
    no ``mla_scale_*`` key (scale 1). ``n_routed_experts`` is the router's
    width. What cannot run is refused by the name of its key."""
    n = hf["num_hidden_layers"]
    _refuse_by_key(hf, "glm_moe_dsa")
    if hf.get("num_nextn_predict_layers", 0):
        raise ValueError(
            "num_nextn_predict_layers > 0: the multi-token-prediction "
            "layer is a draft head that no engine runs; cut it to 0")
    for key in ("mlp_layer_types", "indexer_types"):
        if len(hf.get(key, ())) != n:
            raise ValueError(f"{key} needs one entry a layer "
                             f"(num_hidden_layers {n})")
    sparse = "sparse" in hf["mlp_layer_types"]
    return TransformerConfig(
        vocab_size=hf["vocab_size"], d_model=hf["hidden_size"],
        n_heads=hf["num_attention_heads"], n_layers=n,
        d_ff=hf["intermediate_size"], max_seq=max_seq, dtype=dtype,
        rope=True, rope_theta=float(hf["rope_parameters"]["rope_theta"]),
        ffn="swiglu", norm_eps=float(hf.get("rms_norm_eps", 1e-6)),
        attention="mla", q_lora_rank=hf["q_lora_rank"],
        kv_lora_rank=hf["kv_lora_rank"],
        qk_nope_head_dim=hf["qk_nope_head_dim"],
        qk_rope_head_dim=hf["qk_rope_head_dim"],
        v_head_dim=hf["v_head_dim"], block="standard",
        layer_ffn=tuple(hf["mlp_layer_types"]),
        layer_indexer=tuple(hf["indexer_types"]),
        index_n_heads=hf["index_n_heads"],
        index_head_dim=hf["index_head_dim"], index_topk=hf["index_topk"],
        mla_lora_scales=False,
        experts=_glm_experts(hf, experts_held) if sparse else None)


def _config_from_granitemoehybrid(hf: dict, max_seq: int, dtype,
                                  experts_held) -> TransformerConfig:
    """``model_type`` "granitemoehybrid": ``layer_types`` ("mamba" |
    "attention" a layer), the Mamba-2 sizes (``mamba_n_heads`` x
    ``mamba_d_head`` = ``mamba_expand`` x hidden, ``mamba_d_state``,
    ``mamba_d_conv``, ``mamba_chunk_size``), GQA without positions at the
    score scale ``attention_multiplier``, after every mixer the expert
    block (``num_local_experts`` outputs of width ``intermediate_size``,
    the ``num_experts_per_tok`` largest weighed by a softmax over THEM -
    the softmax over all renormalised over the picked - and a shared
    expert of ``shared_intermediate_size``), the embedding's, the
    residual's and the logits' multipliers and a tied head.
    ``num_local_experts`` is the router's width, as the source's
    ``config.json`` has it; ``experts_held`` says which of them this chip
    holds. What cannot run is refused by the name of its key."""
    n = hf["num_hidden_layers"]
    for key, can in (("mamba_n_groups", 1),
                     ("position_embedding_type", "nope"),
                     ("mamba_proj_bias", False), ("attention_bias", False),
                     ("mamba_conv_bias", True), ("hidden_act", "silu"),
                     ("normalization_function", "rmsnorm")):
        if hf.get(key, can) != can:
            raise ValueError(f"{key} {hf[key]!r}: only {can!r} is "
                             f"implemented for granitemoehybrid")
    kinds = {"mamba": "ssm", "attention": "attention"}
    types = hf.get("layer_types", ())
    if len(types) != n or set(types) - set(kinds):
        raise ValueError(f"layer_types needs 'mamba' | 'attention' for "
                         f"each of num_hidden_layers {n}, got {types}")
    heads, hd = hf["mamba_n_heads"], hf["mamba_d_head"]
    if heads * hd != hf.get("mamba_expand", 2) * hf["hidden_size"]:
        raise ValueError(
            f"mamba_n_heads {heads} x mamba_d_head {hd} is not mamba_expand "
            f"{hf.get('mamba_expand', 2)} x hidden_size {hf['hidden_size']}")
    n_real = hf["num_local_experts"]
    offset, count = experts_held or hf.get("experts_held", (0, n_real))
    return TransformerConfig(
        vocab_size=hf["vocab_size"], d_model=hf["hidden_size"],
        n_heads=hf["num_attention_heads"],
        n_kv_heads=hf["num_key_value_heads"], n_layers=n,
        d_ff=hf["intermediate_size"], max_seq=max_seq, dtype=dtype,
        rope=False, ffn="swiglu",
        norm_eps=float(hf.get("rms_norm_eps", 1e-6)),
        tie_embeddings=bool(hf.get("tie_word_embeddings", True)),
        layer_mixer=tuple(kinds[t] for t in types),
        layer_ffn=("sparse",) * n,
        ssm_heads=heads, ssm_head_dim=hd, ssm_state=hf["mamba_d_state"],
        ssm_conv=hf["mamba_d_conv"], ssm_chunk=hf["mamba_chunk_size"],
        attn_scale=float(hf["attention_multiplier"]),
        embed_scale=float(hf.get("embedding_multiplier", 1.0)),
        residual_scale=float(hf.get("residual_multiplier", 1.0)),
        logit_divisor=float(hf.get("logits_scaling", 1.0)),
        experts=ExpertShareConfig(
            n_outputs=n_real, top_k=hf["num_experts_per_tok"], scale=1.0,
            d_ff=hf["intermediate_size"], scoring="softmax",
            renormalise=True, d_shared=hf.get("shared_intermediate_size", 0),
            held_offset=int(offset), held_count=int(count)))


# what the bailing_hybrid reader implements, a key at a time (a missing key
# is what is implemented)
_BAILING_CAN = (
    ("score_function", "sigmoid"), ("scoring_func", "sigmoid"),
    ("topk_method", "noaux_tc"), ("hidden_act", "silu"),
    ("use_bias", False), ("use_qkv_bias", False),
    ("tie_word_embeddings", False), ("rope_scaling", None),
    ("q_lora_rank", None), ("use_kda_lora", False), ("no_kda_lora", True),
    ("kda_safe_gate", True), ("linear_silu", True), ("use_qk_norm", True),
    ("num_kv_heads_for_linear_attn", 0), ("use_mla_nope", False),
    ("use_nGPT", False), ("group_norm_size", 1),
    ("gated_attention_proj_granularity_type", "head_wise"),
    ("scale_router_input", False), ("up_proj_norm", False),
    ("value_norm", False), ("num_nextn_predict_layers", 0))


def _config_from_bailing_hybrid(hf: dict, max_seq: int, dtype,
                                experts_held) -> TransformerConfig:
    """``model_type`` "bailing_hybrid" (Ling): layer i mixes with latent
    attention where ``(i + 1) % layer_group_size == 0`` and with Kimi delta
    attention elsewhere (``num_attention_heads`` heads of ``head_dim`` for
    q, k and v, a convolution of ``short_conv_kernel_size`` on each, a
    full-rank decay a channel bounded by ``kda_lower_bound``, a full-rank
    output gate, one norm group); the latent layers have a full-rank query
    normed a head (``q_lora_rank`` null, ``use_qk_norm``), interleaved
    rotary phases on ``qk_rope_head_dim`` and a sigmoid gate a head on
    their output. The first ``first_k_dense_replace`` layers end in a
    dense SwiGLU of ``intermediate_size``, the others in ``num_experts``
    sigmoid outputs with a selection bias, ``n_group`` groups of which the
    ``topk_group`` best (by the sum of each group's two best biased scores)
    hold the ``num_experts_per_tok`` picks, renormalised and scaled by
    ``routed_scaling_factor``, beside ``num_shared_experts`` shared experts
    of ``moe_shared_expert_intermediate_size``. ``num_experts`` is the
    router's width; ``experts_held`` says which this chip holds. What
    cannot run is refused by the name of its key."""
    n = hf["num_hidden_layers"]
    for key, can in _BAILING_CAN:
        if hf.get(key, can) != can:
            raise ValueError(f"{key} {hf[key]!r}: only {can!r} is "
                             f"implemented for bailing_hybrid")
    for key in ("expert_swiglu_limit_list", "share_expert_swiglu_limit_list"):
        if any(hf.get(key, ())):
            raise ValueError(f"{key} has a nonzero entry: the clamp of the "
                             f"SwiGLU is not implemented for bailing_hybrid")
    rotary = hf.get("partial_rotary_factor", 1.0) * hf["head_dim"]
    if rotary != hf["qk_rope_head_dim"]:
        raise ValueError(f"partial_rotary_factor x head_dim = {rotary}: only "
                         f"qk_rope_head_dim {hf['qk_rope_head_dim']} rotary "
                         f"numbers are implemented for bailing_hybrid")
    group, dense = hf["layer_group_size"], hf.get("first_k_dense_replace", 0)
    if not 0 <= dense <= n:
        raise ValueError(f"first_k_dense_replace {dense!r} of "
                         f"num_hidden_layers {n}")
    n_real = hf["num_experts"]
    offset, count = experts_held or hf.get("experts_held", (0, n_real))
    experts = ExpertShareConfig(
        n_outputs=n_real, top_k=hf["num_experts_per_tok"],
        scale=float(hf["routed_scaling_factor"]),
        d_ff=hf["moe_intermediate_size"], scoring="sigmoid",
        renormalise=bool(hf.get("norm_topk_prob", True)),
        d_shared=hf.get("num_shared_experts", 0)
        * hf["moe_shared_expert_intermediate_size"],
        n_group=hf.get("n_group", 1), topk_group=hf.get("topk_group", 1),
        held_offset=int(offset), held_count=int(count))
    return TransformerConfig(
        vocab_size=hf["vocab_size"], d_model=hf["hidden_size"],
        n_heads=hf["num_attention_heads"], n_layers=n,
        d_ff=hf["intermediate_size"], max_seq=max_seq, dtype=dtype,
        rope=True, rope_theta=float(hf["rope_theta"]), ffn="swiglu",
        norm_eps=float(hf.get("rms_norm_eps", 1e-6)), attention="mla",
        q_lora_rank=0, kv_lora_rank=hf["kv_lora_rank"],
        qk_nope_head_dim=hf["qk_nope_head_dim"],
        qk_rope_head_dim=hf["qk_rope_head_dim"],
        v_head_dim=hf["v_head_dim"], block="standard",
        mla_lora_scales=False,
        layer_mixer=tuple("latent" if (i + 1) % group == 0 else "kda"
                          for i in range(n)),
        layer_ffn=("dense",) * dense + ("sparse",) * (n - dense),
        kda_heads=hf["num_attention_heads"], kda_head_dim=hf["head_dim"],
        kda_conv=hf["short_conv_kernel_size"],
        kda_lower_bound=float(hf["kda_lower_bound"]),
        rope_interleave=bool(hf.get("rope_interleave", False)),
        experts=experts if dense < n else None)


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
    only). Latent attention over every position (``cfg.full_latent``) is
    :func:`latent_block`, whose aux is its expert layer's counts."""
    if cfg.full_latent:
        if tp_axis is not None or ep_axis is not None:
            raise NotImplementedError(f"{cfg.new_kind} runs without tp "
                                      f"or ep")
        return latent_block(layer, x, cfg, attn_fn, positions)
    if cfg.new_kind is not None:
        raise NotImplementedError(
            f"{cfg.new_kind} runs on the serving slot path only "
            f"(models/generate.py `prefill` / `decode_step`): the full "
            f"forward and the train step have no such block yet")
    b, t, _ = x.shape
    if attn_fn is None:  # default oracle, window-aware (see apply)
        def attn_fn(q, k, v):
            return local_causal_attention(q, k, v,
                                          window=cfg.attn_window)
    h = rmsnorm(x, layer["ln1"], cfg.norm_eps)
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

    h = rmsnorm(x, layer["ln2"], cfg.norm_eps)
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


def latent_attention(p: dict, x: jnp.ndarray, cfg: TransformerConfig,
                     attn_fn: Optional[AttnFn] = None,
                     positions: Optional[jnp.ndarray] = None
                     ) -> jnp.ndarray:
    """Latent attention over every position of x (b, t, d), causal, under
    ``mla_attention``: :func:`mla_project`, then each head's key
    [nope | the shared rotary key] and value expanded from the latent by
    ``wkv_b``, ``attn_fn`` over q, k, v (one head size for all three, which
    the flash kernel takes, and the score scale 1 / sqrt of it), and
    ``wo``. Returns what joins the residual."""
    b, t, _ = x.shape
    heads, rank = cfg.n_heads, cfg.kv_lora_rank
    nope, vd = cfg.qk_nope_head_dim, cfg.v_head_dim
    if nope + cfg.qk_rope_head_dim != vd:
        raise ValueError(
            f"latent attention over every position runs q.k heads of "
            f"{nope} + {cfg.qk_rope_head_dim} and v heads of {vd}: the "
            f"attention takes one head size for q, k and v")
    if positions is None:
        positions = jnp.arange(t)
    if attn_fn is None:
        attn_fn = local_causal_attention
    with jax.named_scope(SCOPE_MLA_ATTENTION):
        _h, _c_q, q_nope, q_rope, c_kv, k_rope = mla_project(
            p, x, cfg, lambda v: apply_rope(v, positions, cfg.rope_theta))
        kv = jnp.einsum("btr,rhe->bthe", c_kv,
                        p["wkv_b"].reshape(rank, heads, nope + vd))
        k = jnp.concatenate(
            [kv[..., :nope],
             jnp.broadcast_to(k_rope, (b, t, heads, k_rope.shape[-1]))],
            axis=-1)
        out = attn_fn(jnp.concatenate([q_nope, q_rope], axis=-1), k,
                      kv[..., nope:])
        return out.reshape(b, t, heads * vd) @ p["wo"]


def latent_block(layer: dict, x: jnp.ndarray, cfg: TransformerConfig,
                 attn_fn: Optional[AttnFn] = None,
                 positions: Optional[jnp.ndarray] = None
                 ) -> tuple[jnp.ndarray, dict]:
    """One layer of latent attention over every position
    (:func:`latent_attention`) and, after ``ln2``, a dense SwiGLU or the
    expert share with its shared expert (parallel/ep.py ``dropless_moe``,
    its train-step call): what the full forward and the train step both
    run. Returns (x, counts): {} for a dense layer, else the layer's
    ``held`` assignments and ``touched`` experts (scalars) and ``load``,
    the assignments of each router output."""
    b, t, d = x.shape
    x = x + latent_attention(layer["mla"], x, cfg, attn_fn, positions)
    h = rmsnorm(x, layer["ln2"], cfg.norm_eps)
    if "moe" not in layer:
        with jax.named_scope(SCOPE_DENSE_FFN):
            return x + (jax.nn.silu(h @ layer["w1"])
                        * (h @ layer["w3"])) @ layer["w2"], {}
    m, counts = dropless_moe(h.reshape(b * t, d), layer["moe"], cfg.experts,
                             train=True)
    return x + m.reshape(b, t, d), {"held": counts["held"].sum(),
                                    "touched": counts["touched"],
                                    "load": counts["load"]}


def route_aux(counts: list) -> dict:
    """The aux of a stack of :func:`latent_block` layers (no load-balance
    loss, nothing dropped): ``route_held`` / ``route_touched`` summed over
    its expert layers, ``route_load`` (expert layers, router outputs)."""
    zero = jnp.asarray(0, jnp.int32)
    return {"aux_loss": jnp.asarray(0.0, jnp.float32),
            "dispatch_fraction": jnp.asarray(1.0, jnp.float32),
            "route_held": sum((c["held"] for c in counts), zero),
            "route_touched": sum((c["touched"] for c in counts), zero),
            "route_load": jnp.stack([c["load"] for c in counts])
            if counts else jnp.zeros((0, 1), jnp.int32)}


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
    if cfg.learned_positions:
        x = x + params["pos"][positions]

    def block(layer, h):
        return transformer_block(layer, h, cfg, attn_fn, tp_axis, ep_axis,
                                 positions=positions)

    if remat:
        block = jax.checkpoint(block)

    aux_total: dict = {}
    routed = []
    for layer in params["layers"]:
        x, aux = block(layer, x)
        if cfg.full_latent:
            routed += [aux] if aux else []
        else:
            aux_total = _merge_aux(aux_total, aux)

    return (rmsnorm(x, params["out_norm"], cfg.norm_eps),
            route_aux(routed) if cfg.full_latent
            else _finalize_aux(aux_total))


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
