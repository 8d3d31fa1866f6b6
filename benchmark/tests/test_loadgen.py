"""The trace is the cell's: the same ``trace_seed`` gives the same
arrivals and lengths whatever ``--seed``; ``--seed`` makes the token ids."""

import json
import os

import numpy as np

from benchmark import harness, loadgen


def _chat():
    return json.load(open(os.path.join(harness.HERE, "traffic",
                                       "chat.json")))


def test_trace_does_not_change_with_the_seed():
    p = _chat()
    a = loadgen.serve_trace(p, 30.0)
    b = loadgen.serve_trace(dict(p), 30.0)
    assert a == b and len(a) > 100
    # --seed reaches only the token ids
    t1 = loadgen.prompt_tokens(1, a[0].rid, a[0].prompt_len, 32000)
    t2 = loadgen.prompt_tokens(2**31 + 5, a[0].rid, a[0].prompt_len, 32000)
    assert len(t1) == len(t2) == a[0].prompt_len and t1 != t2
    assert t1 == loadgen.prompt_tokens(1, a[0].rid, a[0].prompt_len, 32000)


def test_another_trace_seed_is_another_trace():
    p = _chat()
    assert loadgen.serve_trace(p, 30.0) != loadgen.serve_trace(
        dict(p, trace_seed=p["trace_seed"] + 1), 30.0)


def test_rate_changes_only_the_clock():
    p = _chat()
    a = loadgen.serve_trace(p, 40.0)
    b = loadgen.serve_trace(dict(p, rate_per_s=2 * p["rate_per_s"]), 20.0)
    assert [(x.prompt_len, x.output_len) for x in a] == \
        [(x.prompt_len, x.output_len) for x in b]
    assert np.allclose([x.due for x in a], [2 * x.due for x in b])


def test_lengths_are_inside_the_engine():
    p = _chat()
    cfg = json.load(open(os.path.join(
        harness.HERE, "configs", "mistral-7b-v0.1-serve.json")))
    tr = loadgen.serve_trace(p, 300.0)
    assert max(a.prompt_len for a in tr) <= max(
        cfg["engine"]["prefill_buckets"])
    assert max(a.prompt_len + a.output_len for a in tr) <= \
        cfg["engine"]["max_seq"]
    med = np.median([a.prompt_len for a in tr])
    assert 150 < med < 240
    rate = len(tr) / 300.0
    assert abs(rate - p["rate_per_s"]) < 0.1 * p["rate_per_s"]


def test_train_rows_all_differ_and_follow_the_seed():
    a = loadgen.train_batch(7, 0, 8, 64, 1000)
    assert a.shape == (8, 64) and a.dtype == np.int32
    assert len({bytes(r) for r in a}) == 8
    assert (a == loadgen.train_batch(7, 0, 8, 64, 1000)).all()
    assert (a != loadgen.train_batch(7, 1, 8, 64, 1000)).any()
    assert (a != loadgen.train_batch(2**31 + 7, 0, 8, 64, 1000)).any()
