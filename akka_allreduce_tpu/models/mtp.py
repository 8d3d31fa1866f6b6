"""Multi-token prediction as a training objective (DeepSeek-V3,
arXiv:2412.19437 section 2.2), for latent attention over every position
(``TransformerConfig.full_latent`` with ``mtp_layers`` 1).

The module reads the main stack's last hidden state h_i, AFTER the final
norm (what the output head reads), beside the embedding of the next token:

    h'_i  = eh_proj [ enorm(Emb(t_{i+1})) ; hnorm(h_i) ]
    h''   = one sparse layer over h' (latent attention, causal over i, and
            the expert share with its shared expert)
    logits = Head(norm(h''))            Emb and Head are the main model's

and its loss is the mean cross-entropy of those logits against t_{i+2}.
The train step minimises L_main + MTP_WEIGHT x L_mtp. Everything the module runs
is under the ``mtp`` scope (runtime/tracing.py). No serving path runs it:
as a draft head it would need an engine that proposes and verifies.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from akka_allreduce_tpu.models.transformer import (
    AttnFn,
    TransformerConfig,
    embed_tokens,
    latent_block,
    lm_logits,
    rmsnorm,
    route_aux,
    transformer_hidden_with_aux,
    weighted_ce,
)
from akka_allreduce_tpu.runtime.tracing import SCOPE_HEAD_LOSS, SCOPE_MTP

# The weight of the module's loss beside the main one (DeepSeek-V3,
# arXiv:2412.19437 section 2.2: 0.3 for the first 10T tokens).
MTP_WEIGHT = 0.3


def mtp_hidden(params: dict, h_main: jnp.ndarray, next_tokens: jnp.ndarray,
               cfg: TransformerConfig, attn_fn: Optional[AttnFn] = None,
               positions: Optional[jnp.ndarray] = None, remat: bool = False
               ) -> tuple[jnp.ndarray, dict]:
    """The module over the main stack's normed last hidden state h_main
    (b, t, d) and ``next_tokens`` (b, t), the token after each position:
    (the normed state the shared head reads, the sparse layer's counts)."""
    p = params["mtp"]
    e = rmsnorm(embed_tokens(params, next_tokens, cfg), p["enorm"],
                cfg.norm_eps)
    h = rmsnorm(h_main, p["hnorm"], cfg.norm_eps)
    x = jnp.concatenate([e, h], axis=-1) @ p["eh_proj"]

    def block(layer, x):
        return latent_block(layer, x, cfg, attn_fn, positions)

    if remat:
        block = jax.checkpoint(block)
    x, counts = block(p["layer"], x)
    return rmsnorm(x, p["norm"], cfg.norm_eps), counts


def mtp_loss_sums(params: dict, tokens: jnp.ndarray, cfg: TransformerConfig,
                  targets: jnp.ndarray, weights: jnp.ndarray,
                  mtp_targets: jnp.ndarray, mtp_weights: jnp.ndarray,
                  positions: Optional[jnp.ndarray] = None,
                  attn_fn: Optional[AttnFn] = None, remat: bool = False
                  ) -> tuple[jnp.ndarray, jnp.ndarray, dict]:
    """(the main head's weighted cross-entropy sum against ``targets``, the
    module's against ``mtp_targets``, the aux of every expert layer, the
    module's last: parallel to models/transformer.py ``route_aux``).
    ``targets`` (t_{i+1}) are also the tokens the module embeds; a
    position whose token after next is not in the batch has
    ``mtp_weights`` 0."""
    x, aux = transformer_hidden_with_aux(params, tokens, cfg, positions,
                                         attn_fn, remat=remat)
    with jax.named_scope(SCOPE_HEAD_LOSS):
        main, _ = weighted_ce(lm_logits(params, x, cfg), targets, weights)
    with jax.named_scope(SCOPE_MTP):
        xm, counts = mtp_hidden(params, x, targets, cfg, attn_fn, positions,
                                remat)
        with jax.named_scope(SCOPE_HEAD_LOSS):
            mtp, _ = weighted_ce(lm_logits(params, xm, cfg), mtp_targets,
                                 mtp_weights)
    mine = route_aux([counts])
    aux = dict(aux, route_held=aux["route_held"] + mine["route_held"],
               route_touched=aux["route_touched"] + mine["route_touched"],
               route_load=jnp.concatenate([aux["route_load"],
                                           mine["route_load"]]))
    return main, mtp, aux
