"""Operations and bytes of a served shortcut double-layer model with latent
attention and a dropless expert share, from shapes and from a run's route
counts. The benchmark's own arithmetic, by ``flops.py``'s conventions: a
matmul of (m, k) by (k, n) counts 2*m*k*n; attention counts the causal
half; every weight matrix is read once a program.

What the experts cost is what ran: a step's operations count the
assignments that fell on HELD experts (each a SwiGLU of three matrices),
its bytes each held expert that got a row (``touched``) once; identity
experts cost no matmul; experts held on absent chips cost nothing here.
"""

from __future__ import annotations


def _sizes(model: dict) -> dict:
    real = model.get("published", {}).get("n_routed_experts",
                                          model["n_routed_experts"])
    h = model["num_attention_heads"]
    return dict(
        d=model["hidden_size"], h=h, q_rank=model["q_lora_rank"],
        rank=model["kv_lora_rank"], nope=model["qk_nope_head_dim"],
        rope=model["qk_rope_head_dim"], v=model["v_head_dim"],
        ff=model["ffn_hidden_size"], eff=model["expert_ffn_hidden_size"],
        outputs=real + model["zero_expert_num"],
        vocab=model["vocab_size"], layers=model["num_layers"],
        top_k=model["moe_topk"], held=model["n_routed_experts"])


def mla_params(model: dict) -> int:
    """One latent attention's matrices."""
    s = _sizes(model)
    return (s["d"] * s["q_rank"] + s["q_rank"] * s["h"] * (
        s["nope"] + s["rope"]) + s["d"] * (s["rank"] + s["rope"])
        + s["rank"] * s["h"] * (s["nope"] + s["v"])
        + s["h"] * s["v"] * s["d"])


def expert_params(model: dict) -> int:
    s = _sizes(model)
    return 3 * s["d"] * s["eff"]


def non_expert_params(model: dict) -> int:
    """Every matrix a token meets outside the experts: per double layer
    two attentions, two dense FFNs and the router; the head's slice."""
    s = _sizes(model)
    layer = 2 * mla_params(model) + 2 * 3 * s["d"] * s["ff"] \
        + s["d"] * s["outputs"]
    return s["layers"] * layer + s["d"] * s["vocab"]


def decode_step_flops(model: dict, occupied: int, live_positions: float,
                      held_assignments: int) -> float:
    """One decode step of ``occupied`` busy lanes that attend
    ``live_positions`` cached positions in sum. Attention per position:
    scores over rank + rope columns and the weighted sum over rank, in
    every head."""
    s = _sizes(model)
    attn = 2 * s["layers"] * 2 * s["h"] * (2 * s["rank"] + s["rope"])
    return (2.0 * non_expert_params(model) * occupied
            + attn * live_positions
            + 2.0 * expert_params(model) * held_assignments)


def decode_step_bytes(model: dict, live_positions: float, touched: int,
                      itemsize: int = 2) -> float:
    """Bytes one decode step must read: every non-expert matrix once, each
    TOUCHED held expert's three matrices once (``touched`` sums over the
    layers), and the latents of the live positions in every attention."""
    s = _sizes(model)
    return itemsize * (non_expert_params(model)
                       + expert_params(model) * touched
                       + live_positions * 2 * s["layers"]
                       * (s["rank"] + s["rope"]))


def prefill_flops(model: dict, n: int, held_assignments: int,
                  head_positions: int = 1) -> float:
    """One prefill of ``n`` true positions (expanded keys and values)."""
    s = _sizes(model)
    body = non_expert_params(model) - s["d"] * s["vocab"]
    pairs = n * (n + 1) / 2
    attn = 2 * s["layers"] * 2 * s["h"] * pairs * (
        s["nope"] + s["rope"] + s["v"])
    return (2.0 * body * n + attn
            + 2.0 * s["d"] * s["vocab"] * head_positions
            + 2.0 * expert_params(model) * held_assignments)


def prefill_bytes(model: dict, n: int, touched: int,
                  itemsize: int = 2) -> float:
    """Weights once, each touched expert once, the latents written."""
    s = _sizes(model)
    return itemsize * (non_expert_params(model)
                       + expert_params(model) * touched
                       + n * 2 * s["layers"] * (s["rank"] + s["rope"]))


def weight_bytes(model: dict, itemsize: int = 2) -> int:
    """What the chip holds: every matrix, the held experts, both ends."""
    s = _sizes(model)
    return itemsize * (non_expert_params(model) + s["d"] * s["vocab"]
                       + s["layers"] * s["held"] * expert_params(model))
