"""``benchmark/flops.py`` against counts made by hand for both models."""

import json
import os

import pytest

from benchmark import flops, harness


def _cfg(name):
    return json.load(open(os.path.join(harness.HERE, "configs", name)))


def test_mistral_by_hand():
    m = _cfg("mistral-7b-v0.1-serve.json")
    # per layer: wq 4096x4096, wk/wv 4096x1024 (8 KV heads x 128, GQA),
    # wo 4096x4096, three SwiGLU matrices 4096x14336
    layer = 2 * 4096 * 4096 + 2 * 4096 * 1024 + 3 * 4096 * 14336
    assert layer == 218_103_808
    params = 16 * layer + 4096 * 32000          # 16 layers + the head
    assert flops.matmul_params(m) == params == 3_620_732_928
    # a decoded token at 300 cached positions: 2 flops a weight, and
    # QK^T + PV over the context in every layer (32 heads x 128)
    assert flops.decode_token_flops(m, 300) == \
        2 * params + 16 * 4 * 4096 * 300
    # a decode step reads every weight once (bf16) and the live keys and
    # values: 2 x 8 x 128 bf16 a position a layer
    live = 32 * 300
    assert flops.decode_step_bytes(m, live) == \
        2 * params + live * 16 * 2 * 1024 * 2
    # prefill of 192 tokens, head at one position, causal half
    pairs = 192 * 193 / 2
    assert flops.forward_flops(m, 192, head_positions=1) == pytest.approx(
        2 * 16 * layer * 192 + 4 * 16 * 4096 * pairs + 2 * 4096 * 32000)
    # the window binds only past 4096
    assert flops.attended_pairs(1024, 4096) == 1024 * 1025 / 2
    assert flops.attended_pairs(8192, 4096) == \
        4096 * 4096 + 4096 * 4097 / 2


def test_internlm2_by_hand():
    m = _cfg("internlm2-1.8b-train.json")
    layer = 2 * 2048 * 2048 + 2 * 2048 * 1024 + 3 * 2048 * 8192
    assert layer == 62_914_560
    n = m["num_hidden_layers"]
    tokens, seq, rows = 3 * 4096, 4096, 3
    fwd = (2 * (n * layer + 2048 * 92544) * tokens
           + rows * 4 * n * 2048 * (seq * (seq + 1) / 2))
    assert flops.forward_flops(m, seq, rows) == pytest.approx(fwd)
    assert flops.train_step_flops(m, seq, rows) == pytest.approx(3 * fwd)
    # attention alone, forward + backward = 3 x the forward's two matmuls
    assert flops.flash_attention_flops(m, seq, rows) == pytest.approx(
        3 * rows * n * 4 * 2048 * (seq * (seq + 1) / 2))
    # q, o: 2048 wide; k, v: 1024 wide (8 of 16 heads), bf16
    assert flops.flash_attention_bytes(m, seq, rows) == \
        rows * seq * n * 2 * ((2 * 2048 + 2 * 1024)
                              + (4 * 2048 + 4 * 1024))


def test_roofline_takes_the_larger_bound():
    peak = flops.peaks("TPU v5 lite")
    assert flops.roofline_seconds(197e12, 1.0, peak) == pytest.approx(1.0)
    assert flops.roofline_seconds(1.0, 819e9, peak) == pytest.approx(1.0)
