"""Operations and bytes of the served hybrid of delta-rule linear attention
(Kimi delta attention) and latent attention, a dense SwiGLU or the
group-limited expert share with its shared expert after each mixer. From
shapes and from a run's counts, by ``flops.py``'s conventions: a matmul of
(m, k) by (k, n) counts 2*m*k*n; every weight matrix is read once a
program.

What the delta rule's state costs is what ran: a decode step reads and
writes the state of every lane-layer it advanced for a request
(``kda_lanes``, counted by the program on the host: busy lanes x delta-rule
layers), ``state_bytes`` each way; a prefill's chunked form is reckoned at
a block of :data:`CHUNK` over the positions it counted (``kda_tokens``:
prompt positions x delta-rule layers), whatever implements it. The latent
layer reads a cached latent row a live position in a decode step and, in a
prefill, scores and sums every causal pair over the latent (absorbed). What
the experts cost is what ran: operations for the assignments that fell on
HELD experts, bytes for each held expert that got a row.
"""

from __future__ import annotations

from benchmark.weights_kda_mla_moe import layer_kinds, model_dims

CHUNK = 64           # the chunked form's block the count is reckoned at


def _sizes(model: dict) -> dict:
    s = model_dims(model)
    kinds = layer_kinds(model)
    s.update(kd=s["heads"] * s["hd"], top_k=model["num_experts_per_tok"],
             n_kda=sum(m == "kda" for m, _f in kinds),
             n_latent=sum(m == "latent" for m, _f in kinds),
             n_dense=sum(f == "dense" for _m, f in kinds),
             n_sparse=sum(f == "sparse" for _m, f in kinds))
    return s


def kda_matrix_params(model: dict) -> int:
    """One delta-rule mixer's two matrices (``w_in``, ``w_out``)."""
    s = _sizes(model)
    return s["d"] * (5 * s["kd"] + s["heads"]) + s["kd"] * s["d"]


def kda_params(model: dict) -> int:
    """All of one delta-rule mixer: the matrices, the convolutions' taps,
    ``A_log`` a head, ``dt_bias`` a channel and the norm's gain."""
    s = _sizes(model)
    return (kda_matrix_params(model) + 3 * s["kd"] * s["conv"] + s["heads"]
            + 2 * s["kd"])


def latent_matrix_params(model: dict) -> int:
    s = _sizes(model)
    h, qk = s["heads"], s["nope"] + s["rope"]
    return (s["d"] * h * qk + s["d"] * (s["rank"] + s["rope"])
            + s["rank"] * h * (s["nope"] + s["vd"]) + s["d"] * h
            + h * s["vd"] * s["d"])


def latent_params(model: dict) -> int:
    """All of one latent mixer but the norm ahead of it: the matrices, the
    query's norm a head and the latent's norm."""
    s = _sizes(model)
    return latent_matrix_params(model) + s["nope"] + s["rope"] + s["rank"]


def dense_params(model: dict) -> int:
    s = _sizes(model)
    return 3 * s["d"] * s["ff"]


def expert_params(model: dict) -> int:
    s = _sizes(model)
    return 3 * s["d"] * s["expert_ff"]


def shared_params(model: dict) -> int:
    s = _sizes(model)
    return 3 * s["d"] * s["shared_ff"]


def router_params(model: dict) -> int:
    """The router and its selection bias."""
    s = _sizes(model)
    return s["d"] * s["outputs"] + s["outputs"]


def body_params(model: dict) -> int:
    """Every matrix a token meets between the embedding and the head,
    outside the routed experts."""
    s = _sizes(model)
    return (s["n_kda"] * kda_matrix_params(model)
            + s["n_latent"] * latent_matrix_params(model)
            + s["n_dense"] * dense_params(model)
            + s["n_sparse"] * (shared_params(model)
                               + s["d"] * s["outputs"]))


def non_expert_params(model: dict) -> int:
    """``body_params`` and the head's slice."""
    s = _sizes(model)
    return body_params(model) + s["d"] * s["vocab"]


def total_params(model: dict) -> int:
    """What the chip holds: every parameter of the cut (embedding and head
    slices, the two norms' gains of every layer, the last norm's and the
    selection biases among them)."""
    s = _sizes(model)
    return (s["n_kda"] * kda_params(model)
            + s["n_latent"] * latent_params(model)
            + s["n_dense"] * dense_params(model)
            + s["n_sparse"] * (shared_params(model) + router_params(model)
                               + s["held"] * expert_params(model))
            + (s["n_kda"] + s["n_latent"]) * 2 * s["d"]
            + 2 * s["d"] * s["vocab"] + s["d"])


def state_bytes(model: dict) -> int:
    """One lane-layer's delta-rule state (float32, heads x head size x head
    size)."""
    s = _sizes(model)
    return 4 * s["heads"] * s["hd"] * s["hd"]


def latent_bytes_per_token(model: dict, itemsize: int = 2) -> int:
    """A cached position: the latent row of every latent layer."""
    s = _sizes(model)
    return itemsize * s["n_latent"] * (s["rank"] + s["rope"])


def step_state_flops(model: dict, lane_layers: float) -> float:
    """The recurrence's one step a lane-layer: decay, S^T k, the rank-one
    update, the read-out S^T q over heads x head size x head size numbers
    (1 + 2 + 2 + 2 operations)."""
    return 7.0 * lane_layers * state_bytes(model) / 4


def decode_step_flops(model: dict, occupied: int, live_positions: float,
                      kda_lanes: float, held_assignments: int) -> float:
    """One decode step of ``occupied`` busy lanes over ``live_positions``
    cached positions (summed over the lanes): the latent attention's
    scores over rank + rope and its sum over rank, a head a position."""
    s = _sizes(model)
    return (2.0 * non_expert_params(model) * occupied
            + 2.0 * s["n_latent"] * s["heads"]
            * (2 * s["rank"] + s["rope"]) * live_positions
            + step_state_flops(model, kda_lanes)
            + 2.0 * expert_params(model) * held_assignments)


def decode_step_bytes(model: dict, live_positions: float, kda_lanes: float,
                      touched: int, itemsize: int = 2) -> float:
    """Bytes one decode step must move: every non-expert matrix once, each
    TOUCHED held expert's three matrices once (``touched`` sums over the
    layers), the state of every advanced lane-layer read AND written, the
    live latents."""
    return (itemsize * (non_expert_params(model)
                        + expert_params(model) * touched)
            + 2.0 * kda_lanes * state_bytes(model)
            + live_positions * latent_bytes_per_token(model, itemsize))


def chunk_flops(model: dict, kda_tokens: float) -> float:
    """The chunked form over ``kda_tokens`` layer-positions at block C =
    :data:`CHUNK`, a head: a token's pairs with its block through k (the
    triangular system's A) and through q (the outputs' P), the solve's
    products for W and U over the block, and three products with the
    head's state (W S, q S, the block's sum into S): 2 x (4 C dk + 3 dk
    dv) a layer-position and head."""
    s = _sizes(model)
    return (2.0 * kda_tokens * s["heads"]
            * (4 * CHUNK * s["hd"] + 3 * s["hd"] * s["hd"]))


def chunk_bytes(model: dict, kda_tokens: float, itemsize: int = 2) -> float:
    """Least traffic of those chunks: q, k, v in and the output out a
    layer-position and head in the configuration's precision, the decay
    (a channel) and beta in float32, and the float32 state in and out once
    a block."""
    s = _sizes(model)
    per_token = s["heads"] * (itemsize * 4 * s["hd"] + 4 * (s["hd"] + 1))
    return kda_tokens * (per_token + 2.0 * state_bytes(model) / CHUNK)


def prefill_flops(model: dict, n: int, held_assignments: int,
                  head_positions: int = 1) -> float:
    """One prompt of ``n`` true positions through the cache (whatever the
    buckets and chunks: padding is not counted)."""
    s = _sizes(model)
    return (2.0 * body_params(model) * n
            + 2.0 * s["n_latent"] * s["heads"] * (2 * s["rank"] + s["rope"])
            * n * (n + 1) / 2
            + chunk_flops(model, s["n_kda"] * n)
            + 2.0 * s["d"] * s["vocab"] * head_positions
            + 2.0 * expert_params(model) * held_assignments)


def weight_bytes(model: dict, itemsize: int = 2) -> int:
    return itemsize * total_params(model)
