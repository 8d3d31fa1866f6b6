"""The hybrid described layer by layer - state-space mixers beside attention
without positions, every layer followed by the expert share with its shared
expert (models/generate.py ``_hybrid_cached_block`` / ``_ssm_mixer``,
parallel/ep.py ``dropless_moe``) - against the plain reference
(models/ssm_moe_reference.py), on the CPU at toy size, comparing logits and
recurrent states.

Tolerances. float32: 2e-5 on logits of order 0.4 and on states of order 0.5
(the chunked scan and the reference's token-by-token recurrence sum the
same products in another order; measured to 1e-7). bfloat16: the band 0.02
on the same logits (measured to 2e-3; every planted fault but the two that
need a chunk boundary reads above 0.05 in float32).
"""

import dataclasses
import json
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from akka_allreduce_tpu.models import generate as G
from akka_allreduce_tpu.models import ssm_moe_reference as ref
from akka_allreduce_tpu.models.generate import (
    decode_step,
    init_kv_cache,
    init_kv_pool,
    prefill,
)
from akka_allreduce_tpu.models.transformer import (
    TransformerConfig,
    config_from_hf,
    init_transformer,
    transformer_apply,
)
from akka_allreduce_tpu.parallel.ep import dropless_moe, init_expert_share
from akka_allreduce_tpu.runtime import tracing as T
from akka_allreduce_tpu.serving import Request, ServingMetrics
from akka_allreduce_tpu.serving import engine as eng

HF = dict(
    model_type="granitemoehybrid", vocab_size=64, hidden_size=32,
    num_hidden_layers=4,
    layer_types=["mamba", "attention", "mamba", "mamba"],
    mamba_n_heads=4, mamba_d_head=16, mamba_expand=2, mamba_d_state=8,
    mamba_d_conv=4, mamba_chunk_size=8, mamba_n_groups=1,
    mamba_conv_bias=True, mamba_proj_bias=False, attention_bias=False,
    num_attention_heads=4, num_key_value_heads=2,
    attention_multiplier=0.125, position_embedding_type="nope",
    num_local_experts=6, num_experts_per_tok=3, intermediate_size=16,
    shared_intermediate_size=24, embedding_multiplier=12,
    residual_multiplier=0.22, logits_scaling=16, rms_norm_eps=1e-5,
    tie_word_embeddings=True, hidden_act="silu")
F32_TOL, BF16_BAND = 2e-5, 0.02
MAX_SEQ = 56
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def _model(dtype=jnp.float32, seed=0, held=None, max_seq=MAX_SEQ, **hf):
    cfg = config_from_hf({**HF, **hf}, max_seq, dtype, experts_held=held)
    return cfg, init_transformer(jax.random.key(seed), cfg)


def _ref_model(held=None, **extra):
    return {**HF, **extra, **({"experts_held": held} if held else {})}


def _tokens(n, seed=1):
    return np.asarray(jax.random.randint(jax.random.key(seed), (n,), 0,
                                         HF["vocab_size"]), np.int32)


def _engine(cfg, params, slots=3, chunk=8, buckets=(8,), **kw):
    return eng.ServingEngine(params, cfg, eng.EngineConfig(
        num_slots=slots, prefill_buckets=buckets, prefill_chunk=chunk),
        **kw)


def _engine_logits(e, rid, prompt, n_new, others=()):
    """(the logits the engine picked each of ``rid``'s tokens from, the
    tokens, the lane)."""
    for o_rid, o_prompt in others:
        e.admit(Request(rid=o_rid, prompt=tuple(o_prompt),
                        max_new_tokens=n_new + 3))
    slot = e.admit(Request(rid=rid, prompt=tuple(prompt),
                           max_new_tokens=n_new))
    rows, toks = [], None
    for _ in range(n_new):
        rows.append(np.asarray(e._state["logits"][slot], np.float32))
        for _s, req, emitted, _why in e.step():
            if req.rid == rid:
                toks = list(emitted)
    return np.stack(rows), toks, slot


def _lane(e, name, slot):
    """A lane's entry of every state-space layer, stacked."""
    return np.stack([np.asarray(x[slot], np.float32)
                     for x in e._state[name]])


def _reference_rows(params, prompt, toks, model=None):
    """The reference's logits at the rows the engine picked ``toks`` from,
    and its states after prompt + toks."""
    full = np.concatenate([prompt, np.asarray(toks, np.int32)])
    logits, states = ref.forward(params, full, model or _ref_model())
    lo = len(prompt) - 1
    return np.asarray(logits)[lo:lo + len(toks)], np.asarray(states)


# -- the configuration --------------------------------------------------

def _catalog_row():
    with open(CATALOG) as f:
        return next(json.loads(line)["config"] for line in f
                    if '"granite-4.0-h-small"' in line)


def test_config_from_hf_reads_the_catalog_row():
    cfg = config_from_hf(_catalog_row(), 6144)
    assert (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.kv_heads,
            cfg.head_dim) == (40, 4096, 32, 8, 128)
    assert cfg.attention_layers == (5, 15, 25, 35)
    assert len(cfg.ssm_layers) == 36 and cfg.n_expert_layers == 40
    assert (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state, cfg.ssm_conv,
            cfg.ssm_chunk) == (128, 64, 128, 4, 256)
    assert (cfg.ssm_inner, cfg.ssm_conv_dim) == (8192, 8448)
    assert (cfg.attn_scale, cfg.embed_scale, cfg.residual_scale,
            cfg.logit_divisor) == (0.0078125, 12.0, 0.22, 16.0)
    assert cfg.tie_embeddings and not cfg.rope \
        and not cfg.learned_positions
    ex = cfg.experts
    assert (ex.n_outputs, ex.top_k, ex.d_ff, ex.d_shared, ex.scoring,
            ex.renormalise, ex.scale, ex.n_identity) == (
        72, 10, 768, 1536, "softmax", True, 1.0, 0)
    assert cfg.hybrid and cfg.layerwise and not cfg.indexed
    assert "state-space" in cfg.new_kind


def test_hand_counts():
    """ISSUE 34's arithmetic for the cut this chip holds: layers 0-9,
    experts 0-35, half the vocabulary."""
    row = _catalog_row()
    cut = {**row, "num_hidden_layers": 10,
           "layer_types": row["layer_types"][:10], "vocab_size": 50176}
    cfg = config_from_hf(cut, 6144, experts_held=(0, 36))
    params = jax.eval_shape(lambda k: init_transformer(k, cfg),
                            jax.random.key(0))
    mixer = params["layers"][0]["ssm"]
    assert sum(x.size for x in jax.tree.leaves(mixer)) == 102_286_976 \
        == 4096 * 16768 + 8192 * 4096 + 8448 * 5 + 3 * 128 + 8192
    attn = params["layers"][5]
    assert sum(attn[n].size for n in ("wq", "wk", "wv", "wo")) \
        == 41_943_040
    moe = params["layers"][0]["moe"]
    assert sum(moe[n].size for n in ("ws1", "ws3", "ws2")) == 18_874_368
    assert moe["router"].size == 294_912
    assert sum(moe[n].size for n in ("we1", "we3", "we2")) \
        == 36 * 9_437_184
    # the selection bias (72 zeros a layer) is the program's, not the
    # source's: it is no parameter of the model
    total = sum(x.size for x in jax.tree.leaves(params)) - 10 * 72
    assert total == 4_757_211_776 == (
        9 * 121_464_448 + 61_120_512 + 10 * 36 * 9_437_184 + 205_524_992)
    state = jax.eval_shape(lambda: init_kv_cache(cfg, 64))
    assert [x.shape for x in state["ssm_state"]] == [(64, 128, 64, 128)] * 9
    assert all(x.dtype == jnp.float32 for x in state["ssm_state"])
    assert sum(x.size for x in state["ssm_state"]) * 4 // 64 == 37_748_736
    assert [x.shape for x in state["conv_state"]] == [(64, 3, 8448)] * 9
    assert state["k"].shape == state["v"].shape == (1, 64, 6144, 8, 128)
    assert (state["k"].size + state["v"].size) * 2 == 64 * 6144 * 4096


@pytest.mark.parametrize("key,value", [
    ("mamba_n_groups", 8), ("position_embedding_type", "rope"),
    ("mamba_proj_bias", True), ("attention_bias", True)])
def test_config_from_hf_refuses_by_the_name_of_the_key(key, value):
    with pytest.raises(ValueError, match=key):
        config_from_hf({**HF, key: value}, MAX_SEQ)


def test_config_from_hf_refuses_lists_of_another_length():
    with pytest.raises(ValueError, match="layer_types"):
        config_from_hf({**HF, "layer_types": ["mamba"] * 3}, MAX_SEQ)
    with pytest.raises(ValueError, match="mamba_expand"):
        config_from_hf({**HF, "mamba_d_head": 8}, MAX_SEQ)


@pytest.mark.parametrize("change", [
    dict(layer_mixer=("ssm", "conv", "ssm", "ssm")),
    dict(ssm_state=0), dict(rope=True), dict(attn_scale=None),
    dict(experts=None), dict(layer_ffn=("dense",) * 4),
    dict(layer_indexer=("full",) * 4)])
def test_the_hybrids_description_is_whole_or_refused(change):
    cfg, _ = _model()
    with pytest.raises(ValueError):
        dataclasses.replace(cfg, **change)


def test_the_description_belongs_to_the_hybrid_alone():
    for stray in (dict(ssm_heads=4), dict(embed_scale=12.0),
                  dict(attn_scale=0.1)):
        with pytest.raises(ValueError, match="layer_mixer"):
            TransformerConfig(**stray)


# -- the forward pass ---------------------------------------------------------

@pytest.mark.parametrize("dtype,tol", [(jnp.float32, F32_TOL),
                                       (jnp.bfloat16, BF16_BAND)])
def test_prefill_then_decode_step_equal_the_full_forward(dtype, tol):
    cfg, params = _model(dtype)
    toks = _tokens(31, 2)
    want, states = ref.forward(params, toks, _ref_model())
    cache = init_kv_cache(cfg, 1)
    cache, logits = prefill(params, cache, jnp.asarray(toks[None, :22]), cfg)
    assert np.abs(np.asarray(logits[0], np.float32)
                  - np.asarray(want[21])).max() <= tol
    for i in range(22, 31):
        cache, logits = decode_step(params, cache,
                                    jnp.asarray(toks[i:i + 1]), cfg)
        assert np.abs(np.asarray(logits[0], np.float32)
                      - np.asarray(want[i])).max() <= tol, i
    assert all(x.dtype == jnp.float32 for x in cache["ssm_state"])
    scale = float(np.abs(states).max())
    assert np.abs(np.stack([np.asarray(x[0]) for x in cache["ssm_state"]])
                  - np.asarray(states)).max() <= (
        F32_TOL if dtype == jnp.float32 else 0.02 * scale)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, F32_TOL),
                                       (jnp.bfloat16, BF16_BAND)])
@pytest.mark.parametrize("chunk,buckets,n_prompt", [
    pytest.param(8, (32,), 29, id="one_bucket_padded"),
    pytest.param(8, (32,), 32, id="one_bucket_full"),
    pytest.param(1, (), 9, id="chunks_of_1"),
    pytest.param(7, (), 29, id="chunks_of_7_last_padded"),
    pytest.param(7, (), 28, id="chunks_of_7_whole"),
    pytest.param(56, (), 29, id="the_whole_lane_padded"),
    pytest.param(8, (4,), 29, id="past_the_bucket_in_chunks"),
])
def test_engine_logits_and_state_equal_the_reference(chunk, buckets,
                                                     n_prompt, dtype, tol):
    """Prefill in a bucket or in chunks, then decode: every row of logits
    the engine picks a token from is the reference's full forward's, and
    the lane's states after the last token are the reference's."""
    cfg, params = _model(dtype, seed=3)
    prompt = _tokens(n_prompt, 4)
    with _engine(cfg, params, chunk=chunk, buckets=buckets) as e:
        rows, toks, slot = _engine_logits(e, 1, prompt, 6)
        got = _lane(e, "ssm_state", slot)
        tail = _lane(e, "conv_state", slot)
    want, states = _reference_rows(params, prompt, toks)
    assert np.abs(rows - want).max() <= tol
    if dtype == jnp.float32:
        assert toks == [int(t) for t in want.argmax(-1)]
        assert np.abs(got - states).max() <= F32_TOL
        assert np.abs(tail).max() > 0
    else:
        assert np.abs(got - states).max() <= 0.03 * np.abs(states).max()


def test_padding_advances_neither_the_state_nor_the_tail():
    """The same prompt through a bucket of its own length and through one
    four times as long leaves the same state and the same tail."""
    cfg, params = _model()
    prompt = _tokens(8, 6)
    seen = []
    for buckets in ((8,), (32,)):
        with _engine(cfg, params, buckets=buckets) as e:
            slot = e.admit(Request(rid=1, prompt=tuple(prompt),
                                   max_new_tokens=2))
            seen.append({n: _lane(e, n, slot)
                         for n in ("ssm_state", "conv_state")})
    for n in ("ssm_state", "conv_state"):
        assert np.abs(seen[0][n] - seen[1][n]).max() <= F32_TOL, n


def test_a_chunk_equals_the_recurrence_token_by_token():
    """``_ssd_scan`` against a plain loop of the recurrence, with padding
    in the middle of a block and a carried state."""
    b, t, heads, p, n = 2, 21, 3, 4, 5
    ks = jax.random.split(jax.random.key(0), 6)
    x = jax.random.normal(ks[0], (b, t, heads, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, t, heads)))
    dt = dt.at[:, 13:].set(0.0)
    a = -jnp.exp(jax.random.normal(ks[2], (heads,)))
    bm = jax.random.normal(ks[3], (b, t, n))
    cm = jax.random.normal(ks[4], (b, t, n))
    h = h0 = jax.random.normal(ks[5], (b, heads, p, n))
    want = []
    for i in range(t):
        h = h * jnp.exp(dt[:, i] * a)[:, :, None, None] \
            + (dt[:, i, :, None] * x[:, i])[..., None] \
            * bm[:, i, None, None, :]
        want.append(jnp.einsum("bhpn,bn->bhp", h, cm[:, i]))
    for blk in (4, 8, 21):
        y, got = G._ssd_scan(x, dt, a, bm, cm, h0, blk, jnp.float32)
        np.testing.assert_allclose(y, jnp.stack(want, 1), atol=2e-5)
        np.testing.assert_allclose(got, h, atol=2e-5)


@pytest.mark.parametrize("t,chunk,want", [
    (1, 256, None), (2, 256, 2), (512, 256, 256), (2048, 256, 256),
    (7, 8, 7)])
def test_the_scan_is_chosen_from_the_shape(t, chunk, want):
    assert G.ssm_scan_path(t, chunk) == want


def test_no_position_signal_enters_the_attention():
    """Permuting the tokens BEFORE a query permutes nothing the attention
    layer's output can see but the state-space layers': in a model of ONE
    attention layer and no other, the last position's logits do not
    change."""
    cfg, params = _model(layer_types=["attention"], num_hidden_layers=1)
    toks = _tokens(12, 7)
    swapped = toks.copy()
    swapped[[2, 9]] = swapped[[9, 2]]
    outs = []
    for seq in (toks, swapped):
        cache = init_kv_cache(cfg, 1)
        outs.append(prefill(params, cache, jnp.asarray(seq[None]), cfg)[1])
    np.testing.assert_allclose(outs[0], outs[1], atol=F32_TOL)
    assert "pos" not in params


# -- a lane's state is the lane's history ----------------------------------

def test_a_lane_reused_gives_the_logits_of_a_fresh_engine():
    """After a finished request, and after steps the lane ran parked, the
    next request's logits and state are a fresh engine's."""
    cfg, params = _model()
    prompt = _tokens(29, 8)
    # a lane stays free throughout: with none the engine launches ahead,
    # and the carried logits are then a step ahead of the tokens
    with _engine(cfg, params, slots=3) as e:
        fresh, want, slot = _engine_logits(e, 1, prompt, 5)
        state = _lane(e, "ssm_state", slot)
    with _engine(cfg, params, slots=3) as e:
        # lane 0: a request that ends; lane 1 runs on, so lane 0 is
        # stepped parked (its state advances with no one in it)
        e.admit(Request(rid=7, prompt=tuple(_tokens(11, 9)),
                        max_new_tokens=2))
        e.admit(Request(rid=8, prompt=tuple(_tokens(20, 10)),
                        max_new_tokens=12))
        for _ in range(6):
            e.step()
        assert e._slots[0] is None and e._slots[1] is not None
        assert float(np.abs(_lane(e, "ssm_state", 0)).max()) > 0  # left
        used, toks, slot = _engine_logits(e, 1, prompt, 5)
        assert slot == 0
        again = _lane(e, "ssm_state", slot)
    assert toks == want
    np.testing.assert_array_equal(fresh, used)
    np.testing.assert_array_equal(state, again)


def test_a_dispatch_launched_ahead_of_an_ended_request_leaks_nothing():
    """With every lane busy the engine launches ahead; a request that ends
    on a stop token is stepped once more by the dispatch in flight. The
    request admitted into its lane next serves a fresh engine's tokens."""
    cfg, params = _model()
    prompt = _tokens(17, 11)
    with _engine(cfg, params, slots=1) as e:
        _rows, want, _ = _engine_logits(e, 1, prompt, 6)
    other = _tokens(13, 12)
    with _engine(cfg, params, slots=1) as e:
        _rows, first, _ = _engine_logits(e, 2, other, 4)
    with _engine(cfg, params, slots=1) as e:
        e.admit(Request(rid=2, prompt=tuple(other), max_new_tokens=9,
                        stop_tokens=(first[1],)))
        done = []
        while not done:
            done = e.step()
        assert done[0][3] == "stop" and e._flight is not None
        e.admit(Request(rid=1, prompt=tuple(prompt), max_new_tokens=6))
        got = None
        while e.occupied:
            for _s, _req, emitted, _why in e.step():
                got = list(emitted)
        assert e.discarded_lane_steps >= 1
    assert got == want


def test_drain_and_restore_keep_the_state_exact():
    """A drained request is replayed through the scan (prompt + what it
    had generated) into a rebuilt state, and goes on with the tokens the
    uninterrupted engine serves; the replayed state is the stepped one."""
    cfg, params = _model()
    prompt = _tokens(29, 13)
    with _engine(cfg, params, slots=2) as e:
        _rows, want, _ = _engine_logits(e, 1, prompt, 9)
    with _engine(cfg, params, slots=2) as e:
        slot = e.admit(Request(rid=1, prompt=tuple(prompt),
                               max_new_tokens=9))
        for _ in range(4):
            e.step()
        assert e.harvest() == []        # a lane is free: nothing in flight
        stepped = _lane(e, "ssm_state", slot)
        (rr,) = e.drain()
        assert list(rr.generated) == want[:4]
        fresh = e._fresh_state()
        assert jax.tree.map(lambda v: (v.shape, v.dtype), fresh) \
            == jax.tree.map(lambda v: (v.shape, v.dtype), e._state)
        assert {"ssm_state", "conv_state", "k", "v"} <= set(fresh)
        e._state = fresh
        slot = e.restore(rr)
        # the state after prompt + 4 tokens, scanned, against the same
        # state stepped: the restore consumed 3 of the 4 (the 4th is the
        # next step's), so step once on both sides of the comparison
        got = None
        while e.occupied:
            for _s, _req, emitted, _why in e.step():
                got = list(emitted)
    assert got == want
    del stepped


def test_the_watchdogs_rebuild_starts_every_lane_from_zeros():
    from akka_allreduce_tpu.runtime.faults import FaultPlan, FaultPoint
    cfg, params = _model()
    prompt = _tokens(21, 14)
    with _engine(cfg, params, slots=2) as e:
        _rows, want, _ = _engine_logits(e, 1, prompt, 5)
    with _engine(cfg, params, slots=2) as e:
        e.admit(Request(rid=5, prompt=tuple(_tokens(9, 15)),
                        max_new_tokens=8))
        e.step()
        plan = FaultPlan([FaultPoint("engine.dispatch", "raise", hit=1)])
        with plan.armed():
            failed = e.step()
        assert [why for _s, _r, _t, why in failed] == ["fault"]
        assert all(float(np.abs(np.asarray(x)).max()) == 0
                   for x in e._state["ssm_state"])
        _rows, got, _ = _engine_logits(e, 1, prompt, 5)
    assert got == want


def test_a_lanes_logits_do_not_depend_on_the_other_lanes():
    cfg, params = _model()
    prompt = _tokens(29, 3)
    with _engine(cfg, params) as e:
        alone, toks_alone, _ = _engine_logits(e, 1, prompt, 5)
    with _engine(cfg, params, slots=4) as e:
        shared, toks_shared, _ = _engine_logits(
            e, 1, prompt, 5,
            others=[(2, _tokens(40, 4)), (3, _tokens(5, 5))])
    assert toks_alone == toks_shared
    assert np.abs(alone - shared).max() <= F32_TOL


# -- marks --------------------------------------------------------------------

def test_state_and_scan_counts_equal_what_the_dispatches_say():
    cfg, params = _model()
    metrics = ServingMetrics()
    tracer = T.Tracer()
    with _engine(cfg, params, slots=3, chunk=8, buckets=(8,),
                 metrics=metrics, tracer=tracer) as e:
        e.admit(Request(rid=1, prompt=tuple(_tokens(21, 1)), submitted_at=0.0,
                        max_new_tokens=3))       # 3 chunks of 8, 3 padded
        e.admit(Request(rid=2, prompt=tuple(_tokens(5, 2)), submitted_at=0.0,
                        max_new_tokens=2))       # a bucket of 8, 3 padded
        while e.occupied:
            e.step()
    n_ssm = len(cfg.ssm_layers)
    assert n_ssm == 3
    assert metrics.scan_tokens == n_ssm * (21 + 5)
    assert metrics.scan_padded == n_ssm * (3 + 3)
    # steps: 2 busy, 2 busy (rid 2 ends), 1 busy (rid 1 ends)
    assert metrics.ssm_lanes == n_ssm * (2 + 2 + 1)
    assert metrics.ssm_idle_lanes == n_ssm * (1 + 1 + 2)
    assert metrics.summary()["ssm"] == {
        "lanes": 15, "idle_lanes": 12, "scan_tokens": 78, "scan_padded": 18}
    steps = [ev.fields for ev in tracer.events if ev.kind == T.SERVE_STEP]
    assert [ev[T.SSM_LANES] for ev in steps] == [6, 6, 3]
    assert [ev[T.SSM_IDLE_LANES] for ev in steps] == [3, 3, 6]
    chunks = [ev.fields for ev in tracer.events
              if ev.kind == T.SERVE_PREFILL_CHUNK]
    assert [(ev[T.SCAN_TOKENS], ev[T.SCAN_PADDED]) for ev in chunks] == [
        (24, 0), (24, 0), (15, 9), (15, 9)]
    assert all(ev[T.SSM_LANES] > 0 for ev in steps) \
        and all(ev[T.SCAN_TOKENS] > 0 for ev in chunks)


def test_other_models_count_no_state_and_no_scan():
    dense = TransformerConfig(vocab_size=64, d_model=32, n_heads=2,
                              n_layers=1, d_ff=64, max_seq=MAX_SEQ,
                              rope=True)
    params = init_transformer(jax.random.key(0), dense)
    metrics = ServingMetrics()
    tracer = T.Tracer()
    with eng.ServingEngine(params, dense, eng.EngineConfig(num_slots=2),
                           metrics=metrics, tracer=tracer) as e:
        e.admit(Request(rid=1, prompt=tuple(_tokens(9, 1)), submitted_at=0.0,
                        max_new_tokens=3))
        while e.occupied:
            e.step()
    assert (metrics.ssm_lanes, metrics.ssm_idle_lanes, metrics.scan_tokens,
            metrics.scan_padded) == (0, 0, 0, 0)
    assert "ssm" not in metrics.summary()
    steps = [ev.fields for ev in tracer.events if ev.kind == T.SERVE_STEP]
    assert steps and all(ev[T.SSM_LANES] == ev[T.SSM_IDLE_LANES] == 0
                         for ev in steps)


def test_the_scopes_are_in_the_decode_and_the_chunk_programs():
    cfg, params = _model()
    e = _engine(cfg, params)
    step = eng._engine_step.lower(
        params, e._state, jnp.asarray(e._pos), cfg).compile().as_text()
    i32 = jnp.asarray(3, jnp.int32)
    chunk = eng._engine_prefill_chunk.lower(
        params, e._state, jnp.zeros((1, 16), jnp.int32), i32, i32, i32,
        cfg).compile().as_text()
    e.close()
    for hlo, inner, other in ((step, T.SCOPE_SSM_STEP, T.SCOPE_SSM_SCAN),
                              (chunk, T.SCOPE_SSM_SCAN, T.SCOPE_SSM_STEP)):
        names = " ".join(re.findall(r'op_name="([^"]*)"', hlo))
        for sc in (T.SCOPE_SSM_MIXER, T.SCOPE_ATTENTION, T.SCOPE_MOE_ROUTER,
                   T.SCOPE_MOE_EXPERTS, T.SCOPE_MOE_SHARED):
            assert f"/{sc}/" in names, sc
        assert f"/{T.SCOPE_SSM_MIXER}/{inner}/" in names
        assert f"/{other}/" not in names
        assert "/mla_attention/" not in names


def test_the_choice_of_scan_is_said_once(capfd):
    from akka_allreduce_tpu.ops.pallas_kernels import dispatch
    cfg, params = _model(seed=5, max_seq=64)  # a program no test has traced
    dispatch._said.clear()
    with _engine(cfg, params) as e:
        _engine_logits(e, 1, _tokens(21, 1), 3)
        _engine_logits(e, 2, _tokens(19, 2), 3)
    err = capfd.readouterr().err
    said = [line for line in err.splitlines()
            if line.startswith("attention[ssm_scan]")]
    assert len(said) == len(set(said)) == 2
    assert sum("reference:_ssd_scan" in line and "block=8" in line
               for line in said) == 1
    assert sum("reference:recurrence_step" in line for line in said) == 1


# -- refusals ---------------------------------------------------------------

def _dense_draft():
    dense = TransformerConfig(vocab_size=64, d_model=32, n_heads=2,
                              n_layers=1, d_ff=64, max_seq=MAX_SEQ,
                              rope=True)
    return init_transformer(jax.random.key(0), dense), dense


@pytest.mark.parametrize("what,build", [
    ("a page that holds a recurrent state",
     lambda c, p: eng.PagedServingEngine(p, c, eng.PagedEngineConfig())),
    ("rolled-back recurrent state",
     lambda c, p: eng.SpeculativeEngine(p, c, *_dense_draft())),
    ("rolled-back recurrent state",
     lambda c, p: eng.PagedSpeculativeEngine(p, c, *_dense_draft())),
    ("decode_steps", lambda c, p: eng.ServingEngine(
        p, c, eng.EngineConfig(decode_steps=4))),
    ("float32 state", lambda c, p: eng.ServingEngine(
        p, c, eng.EngineConfig(kv_dtype="int8"))),
    ("no page holds a recurrent state", lambda c, p: init_kv_pool(c, 8, 4)),
    ("the recurrent state stays float32",
     lambda c, p: init_kv_cache(c, 1, kv_dtype="int8")),
    ("serving slot path", lambda c, p: transformer_apply(
        p, jnp.zeros((1, 4), jnp.int32), c)),
])
def test_refusals_name_what_is_missing(what, build):
    cfg, params = _model()
    with pytest.raises(NotImplementedError) as e:
        build(cfg, params)
    assert what in str(e.value), str(e.value)


# -- the expert block -------------------------------------------------------

def test_the_shares_add_up():
    """The 6 experts in shares of 3 and 3 (the deployment's two chips a
    layer): the shares' held parts plus the shared expert ONCE equal the
    uncut layer, in the program and in the reference."""
    ex = config_from_hf(HF, 8).experts
    p = init_expert_share(jax.random.key(0), 32, ex)
    h = jax.random.normal(jax.random.key(1), (13, 32))
    part, shared = ref.moe(p, h, _ref_model())
    whole, counts = dropless_moe(h, p, ex)
    np.testing.assert_allclose(whole, part + shared, atol=F32_TOL)
    assert int(counts["held"].sum()) == 13 * 3
    total = 0.0
    for offset, count in ((0, 3), (3, 3)):
        share = dataclasses.replace(ex, held_offset=offset,
                                    held_count=count)
        mine = {**p, **{n: p[n][offset:offset + count]
                        for n in ("we1", "we3", "we2")}}
        y, _got = dropless_moe(h, mine, share)
        w_part, w_shared = ref.moe(mine, h, _ref_model((offset, count)))
        np.testing.assert_allclose(w_shared, shared, atol=F32_TOL)
        np.testing.assert_allclose(y, w_part + w_shared, atol=F32_TOL)
        total = total + (np.asarray(y) - np.asarray(shared))
    np.testing.assert_allclose(total, part, atol=5 * F32_TOL)


def test_the_picked_are_weighed_by_a_softmax_over_themselves():
    from akka_allreduce_tpu.parallel.ep import dropless_route
    ex = config_from_hf(HF, 8).experts
    p = init_expert_share(jax.random.key(2), 32, ex)
    h = jax.random.normal(jax.random.key(3), (9, 32))
    pick, w = dropless_route(h, p, ex)
    logits = h @ p["router"]
    top, idx = jax.lax.top_k(logits, 3)
    np.testing.assert_array_equal(pick, idx)
    np.testing.assert_allclose(w, jax.nn.softmax(top, axis=-1), rtol=1e-5)


# -- faults: the comparison that passes the sound program fails each --------

def _fault_model():
    # the engine's cuts as the reference's bookkeeping faults read them
    return _ref_model((0, 4), engine={"prefill_buckets": [8],
                                      "prefill_chunk": 8})


@pytest.mark.parametrize("fault", ref.FAULTS + ("fp8", "bf16_state"))
def test_each_planted_fault_comes_out_not_correct(fault):
    """29 prompt tokens (three chunks of 8 and a padded fourth) and 6
    served: the sound program is inside the float32 tolerance on logits
    and states, every fault and both controls are outside one of them."""
    cfg, params = _model(held=(0, 4), seed=6)
    prompt = _tokens(29, 11)
    model = _fault_model()
    with _engine(cfg, params) as e:
        rows, toks, slot = _engine_logits(e, 1, prompt, 6)
        got = _lane(e, "ssm_state", slot)
    full = np.concatenate([prompt, np.asarray(toks, np.int32)])
    want, states = ref.forward(params, full, model, prompt_len=29)
    want = np.asarray(want)[28:34]
    sound = max(np.abs(rows - want).max(),
                np.abs(got - np.asarray(states)).max())
    if fault in ("fp8", "bf16_state"):
        broken, b_states = ref.forward(params, full, model, quant=fault,
                                       prompt_len=29)
    else:
        broken, b_states = ref.forward(params, full, model, faults=(fault,),
                                       prompt_len=29)
    gap = max(np.abs(np.asarray(broken)[28:34] - want).max(),
              np.abs(np.asarray(b_states) - np.asarray(states)).max()
              / np.abs(np.asarray(states)).max())
    # at toy size one attention layer at a score scale of 1/8 is nearly a
    # mean, so rotary phases move little; a bfloat16 state rounds at 2e-3
    floor = {"bf16_state": 1e-3, "rope": 2e-3}.get(fault, 5e-3)
    assert sound <= F32_TOL < floor < gap, (fault, sound, gap)
