"""Operations and bytes of a train step of latent attention over every
position with a sigmoid expert share and a multi-token-prediction module
(``configs/glm-4.7-flash-train.json``), from shapes, by ``flops.py``'s
conventions: a matmul of (m, k) by (k, n) counts 2*m*k*n, attention the
causal pairs, a backward pass twice the forward, recomputation nothing.
The experts count as run: the held assignments the step reports.
"""

from __future__ import annotations

from benchmark.flops import attended_pairs
from benchmark.weights_dsa_moe import router_outputs


def _qk(m: dict) -> int:
    return m["qk_nope_head_dim"] + m["qk_rope_head_dim"]


def mla_params(m: dict) -> int:
    """One latent attention's matrices (its norms' gains aside)."""
    d, h = m["hidden_size"], m["num_attention_heads"]
    q, kv = m["q_lora_rank"], m["kv_lora_rank"]
    return (d * q + q * h * _qk(m) + d * (kv + m["qk_rope_head_dim"])
            + kv * h * (m["qk_nope_head_dim"] + m["v_head_dim"])
            + h * m["v_head_dim"] * d)


def dense_ffn_params(m: dict) -> int:
    return 3 * m["hidden_size"] * m["intermediate_size"]


def expert_params(m: dict) -> int:
    """One routed expert (a SwiGLU of ``moe_intermediate_size``)."""
    return 3 * m["hidden_size"] * m["moe_intermediate_size"]


def shared_params(m: dict) -> int:
    return m["n_shared_experts"] * expert_params(m)


def router_params(m: dict) -> int:
    return m["hidden_size"] * router_outputs(m)


def sparse_outside_params(m: dict) -> int:
    """A sparse layer outside its routed experts: attention, shared expert,
    router."""
    return mla_params(m) + shared_params(m) + router_params(m)


def mtp_outside_params(m: dict) -> int:
    """The module outside its routed experts: ``eh_proj`` and a sparse
    layer."""
    return 2 * m["hidden_size"] ** 2 + sparse_outside_params(m)


def n_dense(m: dict) -> int:
    return m.get("first_k_dense_replace", 0)


def n_expert_layers(m: dict) -> int:
    """Expert layers a token passes: the main stack's and the module's."""
    return (m["num_hidden_layers"] - n_dense(m)
            + m.get("num_nextn_predict_layers", 0))


def n_attentions(m: dict) -> int:
    return m["num_hidden_layers"] + m.get("num_nextn_predict_layers", 0)


def heads(m: dict) -> int:
    """Output heads applied at every position: the main one and the
    module's (the same matrix)."""
    return 1 + m.get("num_nextn_predict_layers", 0)


def matmul_params(m: dict) -> int:
    """Weights every token is multiplied by, the routed experts aside: the
    layers, the module and the heads (the embedding is a lookup)."""
    d, v = m["hidden_size"], m["vocab_size"]
    mtp = mtp_outside_params(m) if m.get("num_nextn_predict_layers") else 0
    return (n_dense(m) * (mla_params(m) + dense_ffn_params(m))
            + (m["num_hidden_layers"] - n_dense(m)) * sparse_outside_params(m)
            + mtp + heads(m) * d * v)


def attention_flops(m: dict, seq: int, rows: int) -> float:
    """Every latent attention's q.k and p.v over the causal pairs, one
    forward: 2 x heads x (q.k head size + v head size) a pair."""
    return (rows * n_attentions(m) * 2 * m["num_attention_heads"]
            * (_qk(m) + m["v_head_dim"]) * attended_pairs(seq))


def forward_flops(m: dict, seq: int, rows: int, held_rows: float) -> float:
    """One forward over ``rows`` sequences of ``seq`` tokens, with
    ``held_rows`` assignments on held experts over all the expert
    layers."""
    return (2.0 * matmul_params(m) * rows * seq
            + attention_flops(m, seq, rows)
            + 2.0 * expert_params(m) * held_rows)


def train_step_flops(m: dict, seq: int, rows: int, held_rows: float
                     ) -> float:
    """Forward and backward, no recomputation counted."""
    return 3.0 * forward_flops(m, seq, rows, held_rows)


def flash_attention_flops(m: dict, seq: int, rows: int) -> float:
    """The attention kernel alone, forward and backward (backward = 2 x
    forward; the flash backward's recomputed q.k counts nothing)."""
    return 3.0 * attention_flops(m, seq, rows)


def flash_attention_bytes(m: dict, seq: int, rows: int,
                          itemsize: int = 2) -> float:
    """Least traffic of the kernel, every attention, forward and backward:
    the forward reads q, k, v and writes o; the backward reads q, k, v, o,
    do and writes dq, dk, dv; each a head size of ``_qk`` (q, k) or
    ``v_head_dim`` (v, o) a head a token."""
    h = m["num_attention_heads"]
    qk, vd = h * _qk(m), h * m["v_head_dim"]
    fwd = 2 * qk + 2 * vd
    bwd = 2 * qk + 3 * vd + 2 * qk + vd
    return float(rows * seq * n_attentions(m) * (fwd + bwd) * itemsize)
