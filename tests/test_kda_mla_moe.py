"""The hybrid of delta-rule linear attention and latent attention - Kimi
delta attention in five layers of six, latent attention in the sixth, a
dense SwiGLU or the group-limited sigmoid expert share after each
(models/generate.py ``_hybrid_cached_block`` / ``_kda_mixer`` /
``_mla_cached_attention``, parallel/ep.py ``dropless_route``) - against the
plain reference (tests/kda_mla_moe_reference.py), on the CPU at toy size,
comparing logits and the delta rule's states.

Tolerances. float32: 5e-5 on logits of order 3 and on states of order 1
(the chunked form and the reference's token-by-token recurrence sum the
same products in another order, and a triangular solve stands for the
recurrence inside a block; measured to 1e-5). bfloat16: the band 0.25 on
the same logits of a model whose every layer ends in a dense SwiGLU (the
weights and activations in bfloat16, the state in float32; measured to
0.06: at width 32 a rounding of the decay's input is multiplied by
exp(A_log) up to 16 inside a sigmoid; with the expert share a rounding
turns a near tie of the router's top-4 of 16 the other way and a logit
moves by 1, so the expert layers are held in float32 alone); every
planted fault reads above 5e-3.
"""

import dataclasses
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from akka_allreduce_tpu.models import generate as G
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
)
from akka_allreduce_tpu.parallel.ep import (
    ExpertShareConfig,
    dropless_moe,
    dropless_route,
    init_expert_share,
)
from akka_allreduce_tpu.runtime import tracing as T
from akka_allreduce_tpu.serving import Request, ServingMetrics
from akka_allreduce_tpu.serving import engine as eng
from tests import kda_mla_moe_reference as ref

HF = dict(
    model_type="bailing_hybrid", vocab_size=64, hidden_size=32,
    num_hidden_layers=8, layer_group_size=3, first_k_dense_replace=1,
    num_attention_heads=4, head_dim=8, short_conv_kernel_size=4,
    kda_lower_bound=-5, kv_lora_rank=16, qk_nope_head_dim=8,
    qk_rope_head_dim=4, v_head_dim=8, partial_rotary_factor=0.5,
    rope_theta=10000, rope_interleave=True, q_lora_rank=None,
    intermediate_size=48, moe_intermediate_size=16,
    moe_shared_expert_intermediate_size=16, num_shared_experts=1,
    num_experts=16, num_experts_per_tok=4, n_group=4, topk_group=2,
    routed_scaling_factor=2.5, norm_topk_prob=True, rms_norm_eps=1e-6,
    tie_word_embeddings=False, hidden_act="silu", use_qk_norm=True,
    kda_safe_gate=True, no_kda_lora=True, use_kda_lora=False,
    linear_silu=True, group_norm_size=1,
    gated_attention_proj_granularity_type="head_wise",
    num_kv_heads_for_linear_attn=0, num_nextn_predict_layers=0,
    expert_swiglu_limit_list=[0] * 8,
    share_expert_swiglu_limit_list=[0] * 8)
F32_TOL, BF16_BAND = 5e-5, 0.25
MAX_SEQ = 96
PUBLISHED = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark", "tests", "data",
    "ling-3.0-flash-published.json")


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
    """A lane's entry of every delta-rule layer, stacked."""
    return np.stack([np.asarray(x[slot], np.float32)
                     for x in e._state[name]])


def _reference_rows(params, prompt, toks, model=None):
    full = np.concatenate([prompt, np.asarray(toks, np.int32)])
    logits, states = ref.forward(params, full, model or _ref_model())
    lo = len(prompt) - 1
    return np.asarray(logits)[lo:lo + len(toks)], np.asarray(states)


# -- the configuration --------------------------------------------------

def _published():
    with open(PUBLISHED) as f:
        return json.load(f)["config"]


def test_config_from_hf_reads_the_published_config():
    cfg = config_from_hf(
        {**_published(), "num_nextn_predict_layers": 0,
         "expert_swiglu_limit_list": [0] * 42,
         "share_expert_swiglu_limit_list": [0] * 42}, 24576)
    assert (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.d_ff) == (
        42, 2560, 32, 6144)
    assert cfg.latent_layers == (5, 11, 17, 23, 29, 35, 41)
    assert len(cfg.kda_layers) == 35 and cfg.n_expert_layers == 40
    assert cfg.layer_ffn[:3] == ("dense", "dense", "sparse")
    assert (cfg.kda_heads, cfg.kda_head_dim, cfg.kda_conv,
            cfg.kda_lower_bound) == (32, 128, 4, -5.0)
    assert (cfg.q_lora_rank, cfg.kv_lora_rank, cfg.qk_nope_head_dim,
            cfg.qk_rope_head_dim, cfg.v_head_dim) == (0, 512, 128, 64, 128)
    assert cfg.rope_interleave and cfg.rope_theta == 6e6 \
        and not cfg.mla_lora_scales
    assert cfg.latent_row == 576 and not cfg.full_latent
    ex = cfg.experts
    assert (ex.n_outputs, ex.top_k, ex.d_ff, ex.d_shared, ex.scoring,
            ex.renormalise, ex.scale, ex.n_group, ex.topk_group) == (
        512, 8, 768, 768, "sigmoid", True, 2.5, 8, 4)
    assert "delta-rule" in cfg.new_kind


@pytest.mark.parametrize("key,value", [
    ("num_nextn_predict_layers", 1), ("use_kda_lora", True),
    ("no_kda_lora", False), ("use_mla_nope", True), ("use_nGPT", True),
    ("num_kv_heads_for_linear_attn", 4), ("q_lora_rank", 768),
    ("kda_safe_gate", False), ("group_norm_size", 4),
    ("gated_attention_proj_granularity_type", "element_wise"),
    ("score_function", "softmax"), ("topk_method", "greedy"),
    ("tie_word_embeddings", True), ("rope_scaling", {"type": "yarn"}),
    ("expert_swiglu_limit_list", [0] * 7 + [4]),
    ("share_expert_swiglu_limit_list", [5] + [0] * 7),
    ("partial_rotary_factor", 1.0)])
def test_config_from_hf_refuses_by_the_name_of_the_key(key, value):
    with pytest.raises(ValueError, match=key):
        config_from_hf({**HF, key: value}, 64)


@pytest.mark.parametrize("change", [
    dict(mla_lora_scales=True), dict(kda_lower_bound=0.0), dict(kda_conv=0),
    dict(layer_ffn=("dense",) * 8),
    dict(layer_mixer=("kda", "ssm") * 4)])
def test_the_hybrids_description_is_whole_or_refused(change):
    cfg, _ = _model()
    with pytest.raises(ValueError):
        dataclasses.replace(cfg, **change)


def test_the_description_belongs_to_the_delta_rule_hybrid_alone():
    dense = dict(vocab_size=64, d_model=32, n_heads=2, n_layers=1, d_ff=64,
                 max_seq=16, rope=True)
    for stray in (dict(kda_heads=2), dict(rope_interleave=True)):
        with pytest.raises(ValueError, match="kda_"):
            TransformerConfig(**dense, **stray)


# -- the forward pass ---------------------------------------------------------

@pytest.mark.parametrize("dtype,tol,hf", [
    (jnp.float32, F32_TOL, {}),
    (jnp.bfloat16, BF16_BAND, {"first_k_dense_replace": 8})])
def test_prefill_then_decode_step_equal_the_full_forward(dtype, tol, hf):
    cfg, params = _model(dtype, **hf)
    toks = _tokens(41, 2)
    want, states = ref.forward(params, toks, _ref_model(**hf))
    cache = init_kv_cache(cfg, 1)
    cache, logits = prefill(params, cache, jnp.asarray(toks[None, :30]), cfg)
    assert np.abs(np.asarray(logits[0], np.float32)
                  - np.asarray(want[29])).max() <= tol
    for i in range(30, 41):
        cache, logits = decode_step(params, cache,
                                    jnp.asarray(toks[i:i + 1]), cfg)
        assert np.abs(np.asarray(logits[0], np.float32)
                      - np.asarray(want[i])).max() <= tol, i
    assert all(x.dtype == jnp.float32 for x in cache["kda_state"])
    got = np.stack([np.asarray(x[0]) for x in cache["kda_state"]])
    scale = float(np.abs(states).max())
    assert np.abs(got - np.asarray(states)).max() <= (
        F32_TOL if dtype == jnp.float32 else 0.1 * scale)


@pytest.mark.parametrize("chunk,buckets,n_prompt", [
    pytest.param(8, (32,), 29, id="one_bucket_padded"),
    pytest.param(8, (32,), 32, id="one_bucket_full"),
    pytest.param(1, (), 9, id="chunks_of_1"),
    pytest.param(8, (), 24, id="chunks_at_0_C_2C"),
    pytest.param(6, (), 29, id="chunks_of_6_last_padded"),
    pytest.param(96, (), 29, id="the_whole_lane_padded"),
    pytest.param(16, (8,), 45, id="past_the_bucket_in_chunks"),
])
def test_engine_logits_and_state_equal_the_reference(chunk, buckets,
                                                     n_prompt):
    """Prefill in a bucket or in chunks (each chunk's latent layers attend
    the lane's rows at offset > 0), then decode: every row of logits the
    engine picks a token from is the reference's full forward's, and the
    lane's states after the last token are the reference's."""
    cfg, params = _model(seed=3)
    prompt = _tokens(n_prompt, 4)
    with _engine(cfg, params, chunk=chunk, buckets=buckets) as e:
        rows, toks, slot = _engine_logits(e, 1, prompt, 6)
        got = _lane(e, "kda_state", slot)
        tail = _lane(e, "kda_conv", slot)
    want, states = _reference_rows(params, prompt, toks)
    assert np.abs(rows - want).max() <= F32_TOL
    assert toks == [int(t) for t in want.argmax(-1)]
    assert np.abs(got - states).max() <= F32_TOL
    assert np.abs(tail).max() > 0


@pytest.mark.parametrize("n_prompt", [8, 16, 24])
def test_the_state_after_each_chunk_is_the_recurrences(n_prompt):
    """The state a prompt leaves in its lane after chunks at offsets 0, C
    and 2C (no decode step yet) is the recurrence's after the prompt."""
    cfg, params = _model(seed=8)
    prompt = _tokens(n_prompt, 9)
    with _engine(cfg, params, chunk=8, buckets=()) as e:
        slot = e.admit(Request(rid=1, prompt=tuple(prompt),
                               max_new_tokens=2))
        got = _lane(e, "kda_state", slot)
    _l, states = ref.forward(params, prompt, _ref_model())
    np.testing.assert_allclose(got, states, atol=F32_TOL)


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
                         for n in ("kda_state", "kda_conv")})
    for n in ("kda_state", "kda_conv"):
        assert np.abs(seen[0][n] - seen[1][n]).max() <= F32_TOL, n


@pytest.mark.parametrize("blk", [4, 16, 48, 64])
def test_a_chunk_equals_the_recurrence_token_by_token(blk):
    """``_kda_chunked`` against a plain loop of the equation, with padding
    in the middle of a block, a carried state, and decays down to the
    bound (-5 a token in some channels: exp(-G) over a block of 64 would
    overflow float32 three times over)."""
    b, t, heads, dk, dv = 2, 75, 3, 8, 6
    ks = jax.random.split(jax.random.key(0), 6)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa
    q = unit(jax.random.normal(ks[0], (b, t, heads, dk))) * dk ** -0.5
    k = unit(jax.random.normal(ks[1], (b, t, heads, dk)))
    v = jax.random.normal(ks[2], (b, t, heads, dv))
    g = -5.0 * jax.nn.sigmoid(4 * jax.random.normal(ks[3], (b, t, heads, dk)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, t, heads)))
    g, beta = g.at[:, 60:].set(0.0), beta.at[:, 60:].set(0.0)
    s = s0 = jax.random.normal(ks[5], (b, heads, dk, dv))
    want = []
    for i in range(t):
        s = jnp.exp(g[:, i])[..., None] * s
        err = v[:, i] - jnp.einsum("bhkv,bhk->bhv", s, k[:, i],
                                   precision="highest")
        s = s + (beta[:, i, :, None] * k[:, i])[..., None] * err[:, :, None]
        want.append(jnp.einsum("bhkv,bhk->bhv", s, q[:, i],
                               precision="highest"))
    o, got = G._kda_chunked(q, k, v, g, beta, s0, blk)
    assert np.isfinite(np.asarray(o)).all()
    np.testing.assert_allclose(o, jnp.stack(want, 1), atol=2e-5)
    np.testing.assert_allclose(got, s, atol=2e-5)


@pytest.mark.parametrize("t,want", [
    (1, None), (2, 2), (63, 63), (512, 64), (2048, 64)])
def test_the_form_is_chosen_from_the_shape(t, want):
    assert G.kda_chunk_path(t) == want


def test_a_lane_reused_gives_the_logits_of_a_fresh_engine():
    cfg, params = _model(seed=4)
    first, second = _tokens(21, 1), _tokens(13, 2)
    with _engine(cfg, params, slots=1) as e:
        _engine_logits(e, 1, first, 4)
        rows, _t, slot = _engine_logits(e, 2, second, 4)
    with _engine(cfg, params, slots=1) as e:
        fresh, _t, _s = _engine_logits(e, 2, second, 4)
    np.testing.assert_allclose(rows, fresh, atol=F32_TOL)


def test_a_lanes_logits_do_not_depend_on_the_other_lanes():
    cfg, params = _model(seed=5)
    prompt = _tokens(19, 3)
    with _engine(cfg, params) as e:
        alone, toks_alone, _s = _engine_logits(e, 1, prompt, 5)
    # a lane free, so that no dispatch is launched ahead of the readback
    # (the logits in the engine's state would then be the next dispatch's)
    with _engine(cfg, params, slots=4) as e:
        beside, toks_beside, _s = _engine_logits(
            e, 1, prompt, 5, others=((7, _tokens(27, 5)), (8, _tokens(3, 6))))
    assert toks_alone == toks_beside
    np.testing.assert_allclose(alone, beside, atol=F32_TOL)


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


def test_drain_and_restore_go_on_with_the_same_tokens():
    """A drained request is replayed (prompt + what it had generated)
    through the chunked form into a rebuilt state, and goes on with the
    tokens the uninterrupted engine serves."""
    cfg, params = _model()
    prompt = _tokens(29, 13)
    with _engine(cfg, params, slots=2) as e:
        _rows, want, _ = _engine_logits(e, 1, prompt, 9)
    with _engine(cfg, params, slots=2) as e:
        e.admit(Request(rid=1, prompt=tuple(prompt), max_new_tokens=9))
        for _ in range(4):
            e.step()
        assert e.harvest() == []        # a lane is free: nothing in flight
        (rr,) = e.drain()
        assert list(rr.generated) == want[:4]
        fresh = e._fresh_state()
        assert jax.tree.map(lambda v: (v.shape, v.dtype), fresh) \
            == jax.tree.map(lambda v: (v.shape, v.dtype), e._state)
        assert {"kda_state", "kda_conv", "latent"} <= set(fresh)
        e._state = fresh
        e.restore(rr)
        got = None
        while e.occupied:
            for _s, _req, emitted, _why in e.step():
                got = list(emitted)
    assert got == want


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
                   for x in e._state["kda_state"] + e._state["kda_conv"])
        assert e.kv_cache_bytes() == sum(
            int(x.size * x.dtype.itemsize) for n in ("kda_state", "kda_conv",
                                                     "latent")
            for x in jax.tree.leaves(e._state[n]))
        _rows, got, _ = _engine_logits(e, 1, prompt, 5)
    assert got == want


# -- counts, spans and scopes -------------------------------------------------

def test_state_and_chunk_counts_equal_what_the_dispatches_say():
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
    n_kda = len(cfg.kda_layers)
    assert n_kda == 6
    assert metrics.kda_tokens == n_kda * (21 + 5)
    assert metrics.kda_padded == n_kda * (3 + 3)
    # steps: 2 busy, 2 busy (rid 2 ends), 1 busy (rid 1 ends)
    assert metrics.kda_lanes == n_kda * (2 + 2 + 1)
    assert metrics.kda_idle_lanes == n_kda * (1 + 1 + 2)
    assert metrics.summary()["kda"] == {
        "lanes": 30, "idle_lanes": 24, "tokens": 156, "padded": 36}
    assert (metrics.ssm_lanes, metrics.scan_tokens) == (0, 0)
    steps = [ev.fields for ev in tracer.events if ev.kind == T.SERVE_STEP]
    assert [ev[T.KDA_LANES] for ev in steps] == [12, 12, 6]
    assert [ev[T.KDA_IDLE_LANES] for ev in steps] == [6, 6, 12]
    chunks = [ev.fields for ev in tracer.events
              if ev.kind == T.SERVE_PREFILL_CHUNK]
    assert [(ev[T.KDA_TOKENS], ev[T.KDA_PADDED]) for ev in chunks] == [
        (48, 0), (48, 0), (30, 18), (30, 18)]
    assert all(ev[T.SSM_LANES] == 0 for ev in steps)


def test_other_models_count_no_delta_rule():
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
    assert (metrics.kda_lanes, metrics.kda_idle_lanes, metrics.kda_tokens,
            metrics.kda_padded) == (0, 0, 0, 0)
    assert "kda" not in metrics.summary()
    steps = [ev.fields for ev in tracer.events if ev.kind == T.SERVE_STEP]
    assert steps and all(ev[T.KDA_LANES] == ev[T.KDA_IDLE_LANES] == 0
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
    for hlo, inner, other in ((step, T.SCOPE_KDA_STEP, T.SCOPE_KDA_CHUNK),
                              (chunk, T.SCOPE_KDA_CHUNK, T.SCOPE_KDA_STEP)):
        names = " ".join(re.findall(r'op_name="([^"]*)"', hlo))
        for sc in (T.SCOPE_KDA_MIXER, T.SCOPE_MLA_ATTENTION,
                   T.SCOPE_MOE_ROUTER, T.SCOPE_MOE_EXPERTS,
                   T.SCOPE_MOE_SHARED, T.SCOPE_DENSE_FFN):
            assert f"/{sc}/" in names, sc
        assert f"/{T.SCOPE_KDA_MIXER}/{inner}/" in names
        assert f"/{other}/" not in names
        assert "/ssm_mixer/" not in names
    assert {T.SCOPE_KDA_MIXER, T.SCOPE_KDA_CHUNK, T.SCOPE_KDA_STEP} \
        <= T.SERVING_SCOPES
    scopes = list(T.SCOPES)
    assert scopes.index(T.SCOPE_KDA_CHUNK) < scopes.index(T.SCOPE_KDA_MIXER)
    assert scopes.index(T.SCOPE_KDA_STEP) < scopes.index(T.SCOPE_KDA_MIXER)


def test_the_choice_of_form_is_said_once(capfd):
    from akka_allreduce_tpu.ops.pallas_kernels import dispatch
    cfg, params = _model(seed=5, max_seq=80)  # a program no test has traced
    dispatch._said.clear()
    with _engine(cfg, params) as e:
        _engine_logits(e, 1, _tokens(21, 1), 3)
        _engine_logits(e, 2, _tokens(19, 2), 3)
    err = capfd.readouterr().err
    said = [line for line in err.splitlines()
            if line.startswith("attention[kda_chunk]")]
    assert len(said) == len(set(said)) == 2
    assert sum("reference:_kda_chunked" in line and "block=8" in line
               for line in said) == 1
    assert sum("reference:delta_rule_step" in line for line in said) == 1


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
    ("no page holds a recurrent state", lambda c, p: init_kv_pool(c, 8, 4)),
    ("the recurrent state stays float32",
     lambda c, p: init_kv_cache(c, 1, kv_dtype="int8")),
])
def test_refusals_name_what_is_missing(what, build):
    cfg, params = _model()
    with pytest.raises(NotImplementedError) as e:
        build(cfg, params)
    assert what in str(e.value), str(e.value)


# -- the expert share ---------------------------------------------------------

def test_the_shares_add_up():
    """The 16 experts in four shares of 4 (the deployment's four chips a
    layer): the shares' held parts plus the shared expert ONCE equal the
    uncut layer, in the program and in the reference."""
    ex = config_from_hf(HF, 8).experts
    p = init_expert_share(jax.random.key(0), 32, ex)
    h = jax.random.normal(jax.random.key(1), (13, 32))
    part, shared = ref.moe(p, h, _ref_model())
    whole, counts = dropless_moe(h, p, ex)
    np.testing.assert_allclose(whole, part + shared, atol=F32_TOL)
    assert int(counts["held"].sum()) == 13 * 4
    total = 0.0
    for offset in (0, 4, 8, 12):
        share = dataclasses.replace(ex, held_offset=offset, held_count=4)
        mine = {**p, **{n: p[n][offset:offset + 4]
                        for n in ("we1", "we3", "we2")}}
        y, _got = dropless_moe(h, mine, share)
        w_part, w_shared = ref.moe(mine, h, _ref_model((offset, 4)))
        np.testing.assert_allclose(w_shared, shared, atol=F32_TOL)
        np.testing.assert_allclose(y, w_part + w_shared, atol=F32_TOL)
        total = total + (np.asarray(y) - np.asarray(shared))
    np.testing.assert_allclose(total, part, atol=5 * F32_TOL)


def _brute_force_picks(scores, bias, groups, keep, k):
    """Every token's picks by hand: each group's worth, the best groups,
    then the k best outputs among them (sets, not orders)."""
    out = []
    for s in np.asarray(scores + bias, np.float64):
        per = s.reshape(groups, -1)
        worth = np.sort(per, axis=-1)[:, -2:].sum(-1)
        best = set(np.argsort(-worth)[:keep])
        allowed = [e for e in range(s.size) if e // per.shape[1] in best]
        out.append(set(sorted(allowed, key=lambda e: -s[e])[:k]))
    return out


def test_the_group_limit_against_a_brute_force_choice():
    ex = ExpertShareConfig(n_outputs=32, top_k=4, d_ff=8, scoring="sigmoid",
                           renormalise=True, scale=2.5, n_group=8,
                           topk_group=3, held_count=32)
    p = init_expert_share(jax.random.key(2), 16, ex)
    h = jax.random.normal(jax.random.key(3), (40, 16))
    bias = jax.random.normal(jax.random.key(4), (32,)) * 0.1
    p = {**p, "bias": bias}
    # token 0's best output alone lies in a group whose other outputs are
    # worst: its group's worth drops it from the groups kept
    h = h.at[0].set(0.0)
    router = p["router"].at[:, 0].set(0.0)
    p = {**p, "router": router, "bias": bias.at[0].set(2.0)
         .at[1:4].set(-3.0)}
    pick, weight = dropless_route(h, p, ex)
    scores = jax.nn.sigmoid(jnp.matmul(h, p["router"], precision="highest"))
    want = _brute_force_picks(scores, p["bias"], 8, 3, 4)
    assert [set(map(int, r)) for r in np.asarray(pick)] == want
    assert 0 not in want[0] and int(jnp.argmax(scores[0] + p["bias"])) == 0
    w = np.take_along_axis(np.asarray(scores), np.asarray(pick), -1)
    np.testing.assert_allclose(weight, 2.5 * w / w.sum(-1, keepdims=True),
                               rtol=1e-5)
    r_pick, _w = ref.route(p, h, _ref_model(n_group=8, topk_group=3), ())
    assert [set(map(int, r)) for r in np.asarray(r_pick)] == want


def test_one_group_is_todays_router_bit_for_bit():
    """``n_group`` 1 runs the router line it ran before the group limit
    came: the same picks and the same weights, to the bit."""
    ex = config_from_hf({**HF, "n_group": 1, "topk_group": 1}, 8).experts
    assert (ex.n_group, ex.topk_group) == (1, 1)
    p = init_expert_share(jax.random.key(5), 32, ex)
    p = {**p, "bias": jax.random.normal(jax.random.key(6), (16,)) * 0.1}
    h = jax.random.normal(jax.random.key(7), (23, 32)).astype(jnp.bfloat16)

    def before(h, params):
        logits = jnp.matmul(h.astype(jnp.float32),
                            params["router"].astype(jnp.float32),
                            precision=lax.Precision.HIGHEST)
        scores = jax.nn.sigmoid(logits)
        _, pick = lax.top_k(scores + params["bias"], ex.top_k)
        weight = jnp.take_along_axis(scores, pick, axis=-1)
        weight = weight / (weight.sum(-1, keepdims=True) + 1e-20)
        return pick.astype(jnp.int32), weight * ex.scale
    got = jax.jit(lambda h, p: dropless_route(h, p, ex))(h, p)
    want = jax.jit(before)(h, p)
    for a, b in zip(got, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert str(jax.make_jaxpr(lambda h, p: dropless_route(h, p, ex))(
        h, p)) == str(jax.make_jaxpr(before)(h, p))


# -- faults: the comparison that passes the sound program fails each --------

def _fault_model():
    # the engine's cuts as the reference's bookkeeping faults read them
    return _ref_model((0, 8), engine={"prefill_buckets": [8],
                                      "prefill_chunk": 8})


@pytest.mark.parametrize("fault", ref.FAULTS + ("fp8", "bf16_state"))
def test_each_planted_fault_comes_out_not_correct(fault):
    """29 prompt tokens (three chunks of 8 and a padded fourth) and 6
    served: the sound program is inside the float32 tolerance on logits
    and states, every fault and both controls are outside one of them."""
    cfg, params = _model(held=(0, 8), seed=6)
    prompt = _tokens(29, 11)
    model = _fault_model()
    with _engine(cfg, params) as e:
        rows, toks, slot = _engine_logits(e, 1, prompt, 6)
        got = _lane(e, "kda_state", slot)
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
    # a bfloat16 state rounds at 2e-3 of its largest number
    floor = {"bf16_state": 1e-3}.get(fault, 5e-3)
    assert sound <= F32_TOL < floor < gap, (fault, sound, gap)
