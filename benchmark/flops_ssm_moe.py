"""Operations and bytes of the served hybrid described layer by layer:
state-space mixers (Mamba-2) beside GQA attention without positions, every
layer followed by the expert share with its shared expert. From shapes and
from a run's counts, by ``flops.py``'s conventions: a matmul of (m, k) by
(k, n) counts 2*m*k*n; every weight matrix is read once a program.

What the recurrent state costs is what ran: a decode step reads and writes
the state of every lane-layer it advanced for a request (``ssm_lanes``,
counted by the program on the host: busy lanes x state-space layers),
``state_bytes`` each way; a prefill's scan is reckoned as the chunked form
at the published block (``mamba_chunk_size``) over the positions it
counted (``scan_tokens``: prompt positions x state-space layers), whatever
implements it. What the experts cost is what ran, as in
``flops_dsa_moe.py``: operations for the assignments that fell on HELD
experts, bytes for each held expert that got a row; the shared expert is
one more dense matrix triple every token meets.
"""

from __future__ import annotations


def _sizes(model: dict) -> dict:
    heads, hd = model["mamba_n_heads"], model["mamba_d_head"]
    h = model["num_attention_heads"]
    d = model["hidden_size"]
    types = model["layer_types"]
    return dict(
        d=d, h=h, kvh=model["num_key_value_heads"], hd=d // h,
        inner=heads * hd, ssm_heads=heads, p=hd,
        n=model["mamba_d_state"], conv=model["mamba_d_conv"],
        blk=model["mamba_chunk_size"], eff=model["intermediate_size"],
        shared=model["shared_intermediate_size"],
        outputs=model.get("published", {}).get(
            "num_local_experts", model["num_local_experts"]),
        held=model.get("experts_held",
                       (0, model["num_local_experts"]))[1],
        top_k=model["num_experts_per_tok"], vocab=model["vocab_size"],
        ssm=sum(k == "mamba" for k in types),
        attn=sum(k == "attention" for k in types),
        layers=model["num_hidden_layers"])


def ssm_matrix_params(model: dict) -> int:
    """One state-space mixer's two matrices (``w_in``, ``w_out``)."""
    s = _sizes(model)
    return (s["d"] * (2 * s["inner"] + 2 * s["n"] + s["ssm_heads"])
            + s["inner"] * s["d"])


def ssm_params(model: dict) -> int:
    """All of one state-space mixer: the matrices, the convolution's taps
    and bias, ``A``, ``dt_bias`` and ``D`` a head, the gated norm's gain."""
    s = _sizes(model)
    conv_dim = s["inner"] + 2 * s["n"]
    return (ssm_matrix_params(model) + conv_dim * (s["conv"] + 1)
            + 3 * s["ssm_heads"] + s["inner"])


def attention_params(model: dict) -> int:
    s = _sizes(model)
    return 2 * s["d"] * s["h"] * s["hd"] + 2 * s["d"] * s["kvh"] * s["hd"]


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
    return (s["ssm"] * ssm_matrix_params(model)
            + s["attn"] * attention_params(model)
            + s["layers"] * (shared_params(model) + router_params(model)))


def non_expert_params(model: dict) -> int:
    """``body_params`` and the head's slice (tied: the embedding)."""
    s = _sizes(model)
    return body_params(model) + s["d"] * s["vocab"]


def total_params(model: dict) -> int:
    """What the chip holds: every parameter of the cut (the tied
    embedding once, the norms' gains among them)."""
    s = _sizes(model)
    return (s["ssm"] * ssm_params(model) + s["attn"] * attention_params(model)
            + s["layers"] * (shared_params(model) + router_params(model)
                             + s["held"] * expert_params(model)
                             + 2 * s["d"])
            + s["d"] * s["vocab"] + s["d"])


def state_bytes(model: dict) -> int:
    """One lane-layer's recurrent state (float32)."""
    s = _sizes(model)
    return 4 * s["ssm_heads"] * s["p"] * s["n"]


def kv_bytes_per_token(model: dict, itemsize: int = 2) -> int:
    """A cached position: keys and values of the attention layers."""
    s = _sizes(model)
    return itemsize * s["attn"] * 2 * s["kvh"] * s["hd"]


def step_state_flops(model: dict, lane_layers: float) -> float:
    """The recurrence's one step a lane-layer: decay, add and read out
    each of heads x head size x state numbers (2 + 1 + 2 operations)."""
    return 5.0 * lane_layers * state_bytes(model) / 4


def decode_step_flops(model: dict, occupied: int, live_positions: float,
                      ssm_lanes: float, held_assignments: int) -> float:
    """One decode step of ``occupied`` busy lanes over ``live_positions``
    cached positions (summed over the lanes)."""
    s = _sizes(model)
    return (2.0 * non_expert_params(model) * occupied
            + 4.0 * s["attn"] * s["h"] * s["hd"] * live_positions
            + step_state_flops(model, ssm_lanes)
            + 2.0 * expert_params(model) * held_assignments)


def decode_step_bytes(model: dict, live_positions: float, ssm_lanes: float,
                      touched: int, itemsize: int = 2) -> float:
    """Bytes one decode step must move: every non-expert matrix once, each
    TOUCHED held expert's three matrices once (``touched`` sums over the
    layers), the state of every advanced lane-layer read AND written, the
    live keys and values."""
    return (itemsize * (non_expert_params(model)
                        + expert_params(model) * touched)
            + 2.0 * ssm_lanes * state_bytes(model)
            + live_positions * kv_bytes_per_token(model, itemsize))


def scan_flops(model: dict, scan_tokens: float) -> float:
    """The chunked form over ``scan_tokens`` layer-positions at block
    ``q`` = ``mamba_chunk_size``: a token's ``C . B`` against its block
    (q x state), the masked weights times the block's inputs (q x head
    size, a head), the carried state's read-out (state x head size, a
    head) and the block's sum into the state (the same): 2 x (q x n + q x
    inner + 2 x n x inner) a layer-position."""
    s = _sizes(model)
    q = s["blk"]
    return 2.0 * scan_tokens * (q * s["n"] + q * s["inner"]
                                + 2 * s["n"] * s["inner"])


def scan_bytes(model: dict, scan_tokens: float, itemsize: int = 2) -> float:
    """Least traffic of those scans: x, B, C and dt in, y out a
    layer-position, and the float32 state in and out once a block."""
    s = _sizes(model)
    per_token = itemsize * (2 * s["inner"] + 2 * s["n"]) + 4 * s["ssm_heads"]
    return scan_tokens * (per_token + 2.0 * state_bytes(model) / s["blk"])


def prefill_flops(model: dict, n: int, held_assignments: int,
                  head_positions: int = 1) -> float:
    """One prompt of ``n`` true positions through the cache (whatever the
    buckets and chunks: padding is not counted)."""
    s = _sizes(model)
    return (2.0 * body_params(model) * n
            + 4.0 * s["attn"] * s["h"] * s["hd"] * n * (n + 1) / 2
            + scan_flops(model, s["ssm"] * n)
            + 2.0 * s["d"] * s["vocab"] * head_positions
            + 2.0 * expert_params(model) * held_assignments)


def weight_bytes(model: dict, itemsize: int = 2) -> int:
    return itemsize * total_params(model)
