"""Operations and bytes the mathematics needs, from shapes; and the chip's
peaks. The benchmark's own arithmetic: the program's ``models/flops.py``
is not read. A matmul of (m, k) by (k, n) counts 2*m*k*n; attention counts
the causal half (and the window, where it binds); a backward pass counts
twice the forward; recomputation counts nothing.
"""

from __future__ import annotations

import json
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


class UnknownDevice(KeyError):
    """The device is not in the peaks table: an error, never a default."""


def peaks(device_kind: str) -> dict:
    """Peaks of one chip, by ``device_kind`` exactly; unknown is an error."""
    with open(os.path.join(_HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table or device_kind == "source":
        raise UnknownDevice(
            f"no peaks for device_kind {device_kind!r} in peaks.json; add "
            f"the chip with its source rather than assuming one")
    return table[device_kind]


def _dims(model: dict) -> tuple:
    d = model["hidden_size"]
    h = model["num_attention_heads"]
    hd = model.get("head_dim", d // h)
    return (d, h * hd, model["num_key_value_heads"] * hd,
            model["intermediate_size"], model["vocab_size"],
            model["num_hidden_layers"])


def matmul_params(model: dict) -> int:
    """Weights every token is multiplied by: all layers and the head (the
    embedding is a lookup)."""
    d, dq, dkv, ff, v, n = _dims(model)
    return n * (2 * d * dq + 2 * d * dkv + 3 * d * ff) + d * v


def attended_pairs(t: int, window=None) -> float:
    """(query, key) pairs one causal sequence of ``t`` tokens attends."""
    if window is None or window >= t:
        return t * (t + 1) / 2
    return (t - window) * window + window * (window + 1) / 2


def forward_flops(model: dict, seq: int, rows: int = 1,
                  head_positions=None) -> float:
    """One forward over ``rows`` sequences of ``seq`` tokens. The head is
    applied at ``head_positions`` per row (default: every position)."""
    d, dq, dkv, ff, v, n = _dims(model)
    per_tok = 2 * n * (2 * d * dq + 2 * d * dkv + 3 * d * ff)
    attn = 4 * n * dq * attended_pairs(seq, model.get("sliding_window"))
    head = 2 * d * v * (seq if head_positions is None else head_positions)
    return rows * (per_tok * seq + attn + head)


def train_step_flops(model: dict, seq: int, rows: int) -> float:
    """Forward and backward, no recomputation counted."""
    return 3.0 * forward_flops(model, seq, rows)


def decode_token_flops(model: dict, context: float) -> float:
    """One decoded token that attends ``context`` cached positions."""
    d, dq, dkv, ff, v, n = _dims(model)
    w = model.get("sliding_window")
    ctx = context if w is None else min(context, w)
    return 2 * matmul_params(model) + 4 * n * dq * ctx


def flash_attention_flops(model: dict, seq: int, rows: int) -> float:
    """Causal attention alone (QK^T and PV over the attended pairs),
    forward and backward, by the same convention as the whole step:
    backward = 2 x forward. (A flash backward also recomputes QK^T; that
    is recomputation and counts nothing.)"""
    _, dq, _, _, _, n = _dims(model)
    return 3.0 * rows * n * 4 * dq * attended_pairs(
        seq, model.get("sliding_window"))


def flash_attention_bytes(model: dict, seq: int, rows: int,
                          itemsize: int = 2) -> float:
    """Least traffic of attention forward and backward: forward reads q,
    k, v and writes o; backward reads q, k, v, o, do and writes dq, dk,
    dv."""
    _, dq, dkv, _, _, n = _dims(model)
    fwd = 2 * dq + 2 * dkv
    bwd = 3 * dq + 2 * dkv + dq + 2 * dkv
    return float(rows * seq * n * (fwd + bwd) * itemsize)


def decode_step_bytes(model: dict, live_positions: float,
                      itemsize: int = 2, cache_itemsize: int = 2) -> float:
    """Bytes one decode step must read: every matmul weight once, and the
    keys and values of the ``live_positions`` cached positions (summed over
    the lanes in use) in every layer."""
    _, _, dkv, _, _, n = _dims(model)
    return (matmul_params(model) * itemsize
            + live_positions * n * 2 * dkv * cache_itemsize)


def roofline_seconds(flops: float, nbytes: float, peak: dict) -> float:
    """The least time the chip could take for this work."""
    return max(flops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])
