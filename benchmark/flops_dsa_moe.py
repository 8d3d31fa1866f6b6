"""Operations and bytes of a served model described layer by layer: latent
attention over the positions an indexer picks, sigmoid routing with a
shared expert, leading dense layers. From shapes and from a run's counts,
by ``flops.py``'s conventions: a matmul of (m, k) by (k, n) counts 2*m*k*n;
every weight matrix is read once a program.

What the selection costs is what ran: a step's attention counts the rows it
READ (``index_selected``: ``min(pos + 1, index_topk)`` a busy lane a
layer), its indexer the keys it SCORED (``index_scanned``: ``pos + 1`` a
busy lane a full layer), both counted by the program on the host from the
positions a dispatch uploads. What the experts cost is what ran, as in
``flops_scmoe_mla.py``: operations for the assignments that fell on HELD
experts, bytes for each held expert that got a row; the shared expert is
one more dense matrix triple every token meets.
"""

from __future__ import annotations


def _sizes(model: dict) -> dict:
    h = model["num_attention_heads"]
    return dict(
        d=model["hidden_size"], h=h, q_rank=model["q_lora_rank"],
        rank=model["kv_lora_rank"], nope=model["qk_nope_head_dim"],
        rope=model["qk_rope_head_dim"], v=model["v_head_dim"],
        ff=model["intermediate_size"], eff=model["moe_intermediate_size"],
        shared=model["n_shared_experts"] * model["moe_intermediate_size"],
        outputs=model.get("published", {}).get(
            "n_routed_experts", model["n_routed_experts"]),
        ih=model["index_n_heads"], idim=model["index_head_dim"],
        topk=model["index_topk"], vocab=model["vocab_size"],
        top_k=model["num_experts_per_tok"], held=model["n_routed_experts"],
        full=sum(k == "full" for k in model["indexer_types"]),
        sparse=sum(k == "sparse" for k in model["mlp_layer_types"]),
        layers=model["num_hidden_layers"])


def mla_params(model: dict) -> int:
    """One latent attention's matrices."""
    s = _sizes(model)
    return (s["d"] * s["q_rank"] + s["q_rank"] * s["h"] * (
        s["nope"] + s["rope"]) + s["d"] * (s["rank"] + s["rope"])
        + s["rank"] * s["h"] * (s["nope"] + s["v"])
        + s["h"] * s["v"] * s["d"])


def indexer_params(model: dict) -> int:
    """One indexer's matrices: the query's up-projection, the key's
    projection and the per-head weights'."""
    s = _sizes(model)
    return (s["q_rank"] * s["ih"] * s["idim"] + s["d"] * s["idim"]
            + s["d"] * s["ih"])


def dense_ffn_params(model: dict) -> int:
    s = _sizes(model)
    return 3 * s["d"] * s["ff"]


def expert_params(model: dict) -> int:
    s = _sizes(model)
    return 3 * s["d"] * s["eff"]


def shared_params(model: dict) -> int:
    s = _sizes(model)
    return 3 * s["d"] * s["shared"]


def router_params(model: dict) -> int:
    s = _sizes(model)
    return s["d"] * s["outputs"]


def body_params(model: dict) -> int:
    """Every matrix a token meets between the embedding and the head,
    outside the routed experts."""
    s = _sizes(model)
    return (s["layers"] * mla_params(model)
            + s["full"] * indexer_params(model)
            + (s["layers"] - s["sparse"]) * dense_ffn_params(model)
            + s["sparse"] * (shared_params(model) + router_params(model)))


def non_expert_params(model: dict) -> int:
    """``body_params`` and the head's slice."""
    s = _sizes(model)
    return body_params(model) + s["d"] * s["vocab"]


def attention_flops(model: dict, selected: float, scanned: float) -> float:
    """The attentions over ``selected`` latent rows (scores over rank +
    rope columns and the weighted sum over rank, in every head) and the
    indexers over ``scanned`` index keys (a dot of ``index_head_dim`` and
    the weighted sum, in every index head)."""
    s = _sizes(model)
    return (2.0 * s["h"] * (2 * s["rank"] + s["rope"]) * selected
            + 2.0 * s["ih"] * (s["idim"] + 1) * scanned)


def decode_step_flops(model: dict, occupied: int, selected: float,
                      scanned: float, held_assignments: int) -> float:
    """One decode step of ``occupied`` busy lanes."""
    return (2.0 * non_expert_params(model) * occupied
            + attention_flops(model, selected, scanned)
            + 2.0 * expert_params(model) * held_assignments)


def decode_step_bytes(model: dict, selected: float, scanned: float,
                      touched: int, itemsize: int = 2) -> float:
    """Bytes one decode step must read: every non-expert matrix once, each
    TOUCHED held expert's three matrices once (``touched`` sums over the
    layers), the selected latents (rank + rope numbers each) and the
    scanned index keys."""
    s = _sizes(model)
    return itemsize * (non_expert_params(model)
                       + expert_params(model) * touched
                       + selected * (s["rank"] + s["rope"])
                       + scanned * s["idim"])


def prefill_selected(model: dict, n: int) -> tuple:
    """(selected, scanned) of a prefill of ``n`` positions from an empty
    lane: position t reads ``min(t + 1, index_topk)`` rows a layer and
    scores ``t + 1`` keys a full layer."""
    s = _sizes(model)
    k = min(n, s["topk"])
    rows = k * (k + 1) / 2 + (n - k) * s["topk"]
    return s["layers"] * rows, s["full"] * n * (n + 1) / 2


def prefill_flops(model: dict, n: int, held_assignments: int,
                  head_positions: int = 1) -> float:
    """One prompt of ``n`` true positions through the cache (whatever the
    chunks: the padding of the last is not counted)."""
    s = _sizes(model)
    selected, scanned = prefill_selected(model, n)
    return (2.0 * body_params(model) * n
            + attention_flops(model, selected, scanned)
            + 2.0 * s["d"] * s["vocab"] * head_positions
            + 2.0 * expert_params(model) * held_assignments)


def weight_bytes(model: dict, itemsize: int = 2) -> int:
    """What the chip holds: every matrix, the held experts, both ends."""
    s = _sizes(model)
    return itemsize * (non_expert_params(model) + s["d"] * s["vocab"]
                       + s["sparse"] * s["held"] * expert_params(model))


def cache_bytes_per_token(model: dict, latent_row: int = None,
                          itemsize: int = 2) -> int:
    """A cached position: a latent a layer and an index key a full layer.
    ``latent_row``: the columns the program keeps a latent in (default:
    the rank + rope numbers that it holds)."""
    s = _sizes(model)
    row = latent_row or s["rank"] + s["rope"]
    return itemsize * (s["layers"] * row + s["full"] * s["idim"])
