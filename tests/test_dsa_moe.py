"""The model described layer by layer - latent attention over the positions
an indexer picks, sigmoid routing with a shared expert, leading dense
layers (models/generate.py ``_layerwise_cached_block``, parallel/ep.py
``dropless_moe``) - against the plain reference
(models/dsa_moe_reference.py), on the CPU at toy size, comparing logits.

Tolerances. float32: 2e-5 on logits of order 1 (the program and the
reference sum the same products in another order; measured to 4e-6).
bfloat16: the band 0.3 on the same logits (weights and activations carry 8
bits; measured to 0.12 over the seeds here; every planted fault reads above
2 in float32) WHERE THE CHOICE IS THE SAME: at hidden 64 and 8 chosen of 40
a near tie of the indexer's that falls the other way swaps an eighth of an
attention and moves a logit by 0.5 to 2.4 (measured over 8 seeds; where no
selection binds the same program reads 0.05 to 0.12). So in bfloat16 the
reference is handed the program's choices and held to the band, and the
choices are compared as sets: at least 0.7 of the program's are the
reference's own (measured 0.81 to 0.93).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from akka_allreduce_tpu.models import dsa_moe_reference as ref
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
    transformer_apply,
)
from akka_allreduce_tpu.parallel.ep import (
    dropless_moe,
    dropless_route,
    init_expert_share,
)
from akka_allreduce_tpu.runtime import tracing as T
from akka_allreduce_tpu.serving import Request, ServingMetrics
from akka_allreduce_tpu.serving import engine as eng

HF = dict(
    model_type="glm_moe_dsa", vocab_size=256, hidden_size=64,
    intermediate_size=128, moe_intermediate_size=32, num_hidden_layers=5,
    num_attention_heads=4, kv_lora_rank=16, q_lora_rank=24,
    qk_rope_head_dim=8, qk_nope_head_dim=16, v_head_dim=16,
    index_n_heads=4, index_head_dim=16, index_topk=8,
    indexer_types=["full", "shared", "shared", "shared", "full"],
    mlp_layer_types=["dense", "sparse", "sparse", "sparse", "sparse"],
    n_routed_experts=16, n_shared_experts=1, num_experts_per_tok=4,
    norm_topk_prob=True, routed_scaling_factor=2.5, scoring_func="sigmoid",
    topk_method="noaux_tc", n_group=1, topk_group=1, rms_norm_eps=1e-5,
    rope_parameters={"rope_theta": 8e6, "rope_type": "default"},
    num_nextn_predict_layers=0)
F32_TOL, BF16_BAND = 2e-5, 0.3
MAX_SEQ = 64


def _model(dtype=jnp.float32, seed=0, held=None, max_seq=MAX_SEQ, **hf):
    cfg = config_from_hf({**HF, **hf}, max_seq, dtype, experts_held=held)
    return cfg, init_transformer(jax.random.key(seed), cfg)


def _ref_model(held=None, **hf):
    return {**HF, **hf, **({"experts_held": held} if held else {})}


def _tokens(n, seed=1):
    return np.asarray(jax.random.randint(jax.random.key(seed), (n,), 0,
                                         HF["vocab_size"]), np.int32)


def _engine(cfg, params, slots=3, chunk=16, buckets=(8,), **kw):
    return eng.ServingEngine(params, cfg, eng.EngineConfig(
        num_slots=slots, prefill_buckets=buckets, prefill_chunk=chunk),
        **kw)


def _engine_logits(e, rid, prompt, n_new, others=()):
    """The logits the engine picked each of ``rid``'s tokens from."""
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
    return np.stack(rows), toks


# -- the configuration --------------------------------------------------

def test_config_from_hf_builds_the_layers_one_by_one():
    cfg, params = _model(held=(4, 8))
    assert (cfg.block, cfg.attention, cfg.layerwise) == (
        "standard", "mla", True)
    assert cfg.layer_ffn == tuple(HF["mlp_layer_types"])
    assert cfg.layer_indexer == tuple(HF["indexer_types"])
    assert cfg.full_layers == (0, 4) and cfg.n_expert_layers == 4
    assert cfg.mla_scales == (1.0, 1.0) and cfg.rope_theta == 8e6
    assert (cfg.latent_dim, cfg.latent_row) == (24, 128)
    ex = cfg.experts
    assert (ex.n_outputs, ex.n_identity, ex.top_k, ex.scale, ex.d_ff,
            ex.scoring, ex.renormalise, ex.d_shared, ex.held_offset,
            ex.held_count) == (16, 0, 4, 2.5, 32, "sigmoid", True, 32, 4, 8)
    kinds = [("indexer" in l, "moe" in l, "w1" in l)
             for l in params["layers"]]
    assert kinds == [(True, False, True)] + [(False, True, False)] * 3 + [
        (True, True, False)]
    assert params["layers"][1]["moe"]["we1"].shape == (8, 64, 32)
    assert params["layers"][1]["moe"]["ws1"].shape == (64, 32)
    cache = init_kv_cache(cfg, 3)
    assert cache["latent"].shape == (5, 3, MAX_SEQ, 128)
    assert cache["index_k"].shape == (2, 3, MAX_SEQ, 16)


def test_the_published_row_is_padded_to_whole_registers():
    """512 + 64 columns are kept in rows of 640: at 576 the chip keeps
    ``max_seq`` minor and a gather of rows reads the whole lane
    (tests/test_compile_for_chip.py asks the compiler)."""
    cfg = config_from_hf({**HF, "kv_lora_rank": 512, "qk_rope_head_dim": 64,
                          "index_head_dim": 128}, 32)
    assert (cfg.latent_dim, cfg.latent_row) == (576, 640)


@pytest.mark.parametrize("key,value", [
    ("n_group", 2), ("topk_group", 2), ("topk_method", "greedy"),
    ("scoring_func", "softmax"), ("hidden_act", "gelu"),
    ("attention_bias", True), ("tie_word_embeddings", True),
    ("num_nextn_predict_layers", 1)])
def test_config_from_hf_refuses_by_the_name_of_the_key(key, value):
    with pytest.raises(ValueError, match=key):
        config_from_hf({**HF, key: value}, 32)


def test_config_from_hf_refuses_lists_of_another_length():
    with pytest.raises(ValueError, match="indexer_types"):
        config_from_hf({**HF, "indexer_types": ["full"] * 4}, 32)


@pytest.mark.parametrize("change", [
    dict(layer_indexer=("shared",) + ("full",) * 4),
    dict(layer_ffn=("dense",) * 4),
    dict(layer_ffn=("dense",) * 5),          # experts without a sparse layer
    dict(index_topk=0), dict(index_head_dim=4), dict(experts=None),
    dict(block="shortcut")])
def test_the_layerwise_description_is_whole_or_refused(change):
    cfg, _ = _model()
    with pytest.raises(ValueError):
        dataclasses.replace(cfg, **change)


def test_the_description_belongs_to_latent_attention_alone():
    with pytest.raises(ValueError, match="layer_ffn"):
        TransformerConfig(rope=True, ffn="swiglu", layer_ffn=("dense",) * 2)


# -- prefill in chunks, then decode, equals the full forward ---------------

@pytest.mark.parametrize("dtype,tol,seed,n_prompt", [
    (jnp.float32, F32_TOL, 0, 5), (jnp.float32, F32_TOL, 1, 5),
    (jnp.float32, F32_TOL, 0, 37), (jnp.float32, F32_TOL, 1, 37),
    (jnp.bfloat16, BF16_BAND, 0, 5), (jnp.bfloat16, BF16_BAND, 1, 5)])
def test_engine_logits_equal_the_reference(dtype, tol, seed, n_prompt):
    """37 + 6 positions are five times the toy ``index_topk`` 8 (the
    selection binds, through three chunks of 16); 5 + 2 stay below it (it
    does not: every live position is attended, through one bucket)."""
    cfg, params = _model(dtype, seed, held=(4, 8))
    prompt = _tokens(n_prompt, seed + 1)
    n_new = 6 if n_prompt > 8 else 2
    with _engine(cfg, params) as e:
        rows, toks = _engine_logits(e, 7, prompt, n_new)
        assert e.prefill_dispatches == (3 if n_prompt > 8 else 1)
    full = np.concatenate([prompt, np.asarray(toks, np.int32)])
    want = np.asarray(ref.forward(params, full, _ref_model((4, 8))))
    at = slice(n_prompt - 1, n_prompt - 1 + n_new)
    assert np.abs(rows - want[at]).max() <= tol
    if dtype == jnp.float32:
        assert toks == list(np.argmax(want[at], -1))


def _spy_on_choices(monkeypatch):
    """[(attention, chosen (b, t, k))] of every attention run eagerly."""
    seen = []
    attend = G._selected_latent_attention

    def spy(q, latent, a, lanes, chosen, positions, rank, scale):
        seen.append((a, np.asarray(chosen)))
        return attend(q, latent, a, lanes, chosen, positions, rank, scale)
    monkeypatch.setattr(G, "_selected_latent_attention", spy)
    return seen


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_in_bfloat16_the_model_holds_given_the_programs_choices(
        monkeypatch, seed):
    """Where the selection binds (see the module's note on tolerances)."""
    cfg, params = _model(jnp.bfloat16, seed, held=(4, 8))
    toks = _tokens(43, seed + 1)
    seen = _spy_on_choices(monkeypatch)
    with jax.disable_jit():
        _cache, lg = prefill(params, init_kv_cache(cfg, 1),
                             jnp.asarray(toks[None]), cfg)
    choices = {a: chosen[0] for a, chosen in seen if a in cfg.full_layers}
    model = _ref_model((4, 8))
    want = np.asarray(ref.forward(params, toks, model, choices=choices))
    assert np.abs(np.asarray(lg[0], np.float32) - want[-1]).max() \
        <= BF16_BAND
    x = jnp.asarray(params["embed"], jnp.float32)[toks]
    _x, own, _info = ref.layer_forward(params["layers"], 0, x, None, model)
    same = [len(set(choices[0][t]) & set(np.asarray(own[t]))) / 8
            for t in range(8, 43)]
    assert np.mean(same) >= 0.7


def test_below_index_topk_the_selection_is_everything():
    cfg, params = _model()
    toks = _tokens(7, 3)
    want = ref.forward(params, toks, _ref_model())
    same = ref.forward(params, toks, _ref_model(), faults=("no_selection",))
    np.testing.assert_allclose(want, same, atol=F32_TOL)
    longer = _tokens(30, 3)
    assert np.abs(np.asarray(
        ref.forward(params, longer, _ref_model()) - ref.forward(
            params, longer, _ref_model(), faults=("no_selection",))
    )).max() > 1.0


@pytest.mark.parametrize("chunk,buckets", [(8, (4,)), (32, (8,)),
                                           (0, ())])
def test_chunked_prefill_equals_itself_at_another_chunk_size(chunk,
                                                              buckets):
    """Against chunks of 16: chunks of 8 and of 32, and the whole prompt
    in one program of its own length."""
    cfg, params = _model()
    prompt = _tokens(37, 2)
    with _engine(cfg, params) as e:
        want, toks = _engine_logits(e, 1, prompt, 4)
    with _engine(cfg, params, chunk=chunk, buckets=buckets) as e:
        got, again = _engine_logits(e, 1, prompt, 4)
        assert e.prefill_dispatches == (-(-37 // chunk) if chunk else 1)
    assert toks == again
    assert np.abs(got - want).max() <= F32_TOL


def test_prefill_then_decode_step_equal_the_full_forward():
    """``generate.py``'s own entry points (a scalar position)."""
    cfg, params = _model()
    toks = _tokens(30, 4)
    cache, lg = prefill(params, init_kv_cache(cfg, 1),
                        jnp.asarray(toks[None, :20]), cfg)
    out = [lg[0]]
    for t in toks[20:]:
        cache, lg = decode_step(params, cache, jnp.asarray([t]), cfg)
        out.append(lg[0])
    want = np.asarray(ref.forward(params, toks, _ref_model()))
    assert np.abs(np.asarray(jnp.stack(out)) - want[19:]).max() <= F32_TOL


def test_a_shared_layer_attends_exactly_the_full_layers_set(monkeypatch):
    cfg, params = _model()
    seen = _spy_on_choices(monkeypatch)
    toks = _tokens(30, 5)
    with jax.disable_jit():
        prefill(params, init_kv_cache(cfg, 1), jnp.asarray(toks[None]), cfg)
    assert [a for a, _ in seen] == [0, 1, 2, 3, 4]
    for _a, chosen in seen[1:4]:
        np.testing.assert_array_equal(chosen, seen[0][1])
    assert not np.array_equal(seen[4][1], seen[0][1])
    # and it is the reference's choice, as a set a token
    x = jnp.asarray(params["embed"], jnp.float32)[toks]
    _x, want, _info = ref.layer_forward(params["layers"], 0, x, None,
                                        _ref_model())
    for t in range(8, 30):      # the selection binds from position 8 on
        assert set(seen[0][1][0, t]) == set(np.asarray(want[t]))


def test_no_position_past_a_lanes_own_is_ever_attended():
    """A lane that held a longer request before: what lies past ``pos`` in
    its rows is the old occupant's, and changes nothing."""
    cfg, params = _model()
    prompt = _tokens(20, 6)
    with _engine(cfg, params, slots=1) as e:
        fresh, toks = _engine_logits(e, 1, prompt, 4)
    with _engine(cfg, params, slots=1) as e:
        _engine_logits(e, 9, _tokens(45, 7), 5)     # fills the lane's rows
        used, again = _engine_logits(e, 1, prompt, 4)
    assert toks == again
    np.testing.assert_array_equal(fresh, used)


def test_a_lanes_logits_do_not_depend_on_the_other_lanes():
    cfg, params = _model()
    prompt = _tokens(29, 3)
    with _engine(cfg, params) as e:
        alone, toks_alone = _engine_logits(e, 1, prompt, 5)
    # a lane stays free: with none the engine launches ahead, and the
    # carried logits are then a step ahead of the tokens handed out
    with _engine(cfg, params, slots=4) as e:
        shared, toks_shared = _engine_logits(
            e, 1, prompt, 5,
            others=[(2, _tokens(40, 4)), (3, _tokens(5, 5))])
    assert toks_alone == toks_shared
    assert np.abs(alone - shared).max() <= F32_TOL


def test_drain_and_restore_continue_the_stream_through_both_caches():
    """A drained request is replayed through the chunks (prompt + what it
    had generated) into a rebuilt state that has both cache keys, and
    goes on with the tokens the uninterrupted engine serves."""
    cfg, params = _model()
    prompt = _tokens(29, 8)
    with _engine(cfg, params, slots=2) as e:
        _rows, want = _engine_logits(e, 1, prompt, 9)
    with _engine(cfg, params, slots=2) as e:
        e.admit(Request(rid=1, prompt=tuple(prompt), max_new_tokens=9))
        for _ in range(4):
            e.step()
        assert e.harvest() == []        # a lane is free: nothing in flight
        (rr,) = e.drain()
        assert list(rr.generated) == want[:4]
        fresh = e._fresh_state()
        assert {k: (v.shape, v.dtype) for k, v in fresh.items()} == {
            k: (v.shape, v.dtype) for k, v in e._state.items()}
        assert {"latent", "index_k"} <= set(fresh)
        e.restore(rr)
        got = None
        while e.occupied:
            for _s, _req, emitted, _why in e.step():
                got = list(emitted)
    assert got == want



# -- the two realisations of the attention over the chosen rows -----------

def _attention_case(case, dtype):
    """(q, latent, a, lane, chosen, positions) of one lane's block of
    queries: ``case`` is (t, offset, k, tie): t queries at positions
    offset.., k chosen a query by ``lax.top_k`` over random index scores
    (rounded to eighths where ``tie``, so that the k-th place is shared by
    several positions and the top-k takes the first of them)."""
    t, offset, k, tie = case
    n_seq, heads, rank, rope, row = 256, 4, 16, 8, 32
    kq, kl, ks = jax.random.split(jax.random.key(t + offset + k), 3)
    q = jax.random.normal(kq, (1, t, heads, rank + rope), dtype)
    latent = jax.random.normal(kl, (2, 3, n_seq, row), dtype)
    positions = offset + jnp.arange(t, dtype=jnp.int32)[None]
    scores = jax.random.normal(ks, (1, t, n_seq))
    if tie:
        scores = jnp.round(scores * 8) / 8
    live = jnp.arange(n_seq)[None, None] <= positions[..., None]
    scores = jnp.where(live, scores, -jnp.inf)
    chosen = jax.lax.top_k(scores, k)[1].astype(jnp.int32)
    if tie:
        kth = jnp.take_along_axis(scores, chosen[..., -1:], -1)
        assert int(((scores == kth) & live).sum(-1).max()) > 1
    return q, latent, 1, jnp.asarray(2, jnp.int32), chosen, positions


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-6),
                                       (jnp.bfloat16, 2e-2)])
@pytest.mark.parametrize("case", [
    pytest.param((40, 0, 16, False), id="a_chunk_at_offset_0"),
    pytest.param((40, 120, 16, False), id="a_chunk_behind_3x_its_length"),
    pytest.param((40, 0, 64, False), id="fewer_live_than_index_topk"),
    pytest.param((40, 120, 16, True), id="ties_at_the_kth_place"),
    pytest.param((160, 64, 16, True), id="more_than_a_block_of_query_rows"),
])
def test_the_masked_attention_equals_the_gather(monkeypatch, case, dtype,
                                                tol):
    """Same cache, same ``chosen``: the pass over the lane's key blocks
    under the membership mask attends exactly the set the gather fetches
    (the mask is that set, checked against a scatter of ``chosen``), in
    key blocks of 64 so that a query block crosses several."""
    monkeypatch.setattr(G, "KEY_ROWS", 64)
    q, latent, a, lane, chosen, positions = _attention_case(case, dtype)
    t, n_seq = q.shape[1], latent.shape[2]
    blk = G.selected_attention_path(t, chosen.shape[-1], n_seq, True)
    assert blk == 64
    member = G._chosen_mask(chosen, positions, n_seq)
    want = np.zeros((1, t, n_seq), bool)
    for i in range(t):
        row = np.asarray(chosen[0, i])
        want[0, i, row[row <= int(positions[0, i])]] = True
    np.testing.assert_array_equal(np.asarray(member), want)
    assert want.sum(-1).min() >= 1
    gathered = G._selected_latent_attention(
        q, latent, a, lane[None], chosen, positions, 16, 24 ** -0.5)
    masked = G._masked_latent_attention(
        q, latent, a, lane, member, positions, 16, 24 ** -0.5, blk)
    assert masked.shape == gathered.shape == (1, t, 4, 16)
    assert masked.dtype == gathered.dtype == dtype
    assert np.abs(np.asarray(masked, np.float32)
                  - np.asarray(gathered, np.float32)).max() <= tol


@pytest.mark.parametrize("n_keys,top,one_lane,want", [
    (24576, 2048, True, (3072, 6144, 12288, 24576)),    # the cell's chunk
    (24576, 2048, False, (24576,)),         # a decode step: one sort
    (4096, 2048, True, (2048, 4096)),       # none shorter than index_topk
    (64, 8, True, (8, 16, 32, 64)),
    (80, 8, True, (10, 20, 40, 80)),
])
def test_a_chunk_sorts_a_prefix_that_holds_its_live_keys(n_keys, top,
                                                         one_lane, want):
    assert G._key_prefixes(n_keys, top, one_lane) == want


@pytest.mark.parametrize("offset", [0, 3, 8, 24, 47])
def test_the_prefix_chooses_what_the_whole_lane_chooses(offset):
    """A chunk's indexer over the shortest prefix of its lane against the
    same indexer over the whole lane (``CacheOps.lane`` unset, the lane
    handed over as a batch of one): the same positions in the same order,
    at every prefix, with ties (index keys that repeat) and with fewer
    live keys than ``index_topk``."""
    cfg, params = _model()
    idx = params["layers"][0]["indexer"]
    t, n_seq = 16, cfg.max_seq
    kh, kc, kk = jax.random.split(jax.random.key(offset), 3)
    h = jax.random.normal(kh, (1, t, cfg.d_model))
    c_q = jax.random.normal(kc, (1, t, cfg.q_lora_rank))
    lane = jax.random.normal(kk, (n_seq, cfg.index_head_dim))
    lane = lane.at[1::3].set(lane[0])       # equal keys: equal scores
    cache = jnp.zeros((2, 3, n_seq, cfg.index_head_dim)).at[0, 1].set(lane)
    chunk = G.CacheOps(offset=jnp.asarray(offset, jnp.int32),
                       lane=jnp.asarray(1, jnp.int32))
    got, _kv = G._index_select(idx, c_q, h, {"index_k": cache}, 0, cfg,
                               chunk, chunk.positions(1, t))
    whole = G.CacheOps(offset=jnp.asarray(offset, jnp.int32))
    want, _kv = G._index_select(idx, c_q, h, {"index_k": cache[:, 1:2]}, 0,
                                cfg, whole, whole.positions(1, t))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("t,k,n_seq,one_lane,want", [
    (1, 2048, 24576, False, None),      # the decode step: a query a lane
    (1, 2048, 24576, True, None),       # one query: 2,048 rows < a lane's
    (2048, 2048, 24576, True, 1024),    # the cell's chunk
    (2048, 2048, 24576, False, None),   # queries of several lanes
    (2560, 2048, 4096, True, 1024),     # chip_smoke's bucket
    (16, 8, 80, True, 16),              # the key block divides the lane
    (4, 8, 64, True, None),             # 32 gathered rows < the lane's 64
])
def test_the_path_is_chosen_from_the_shapes(t, k, n_seq, one_lane, want):
    assert G.selected_attention_path(t, k, n_seq, one_lane) == want


def test_the_chunk_attends_in_place_and_the_step_gathers(monkeypatch):
    """Through the engine: the chunk program never calls the gather, the
    decode step never the masked pass, and chunks in key blocks of 16
    serve the logits the gathering chunks serve."""
    cfg, params = _model()
    prompt = _tokens(37, 2)
    calls = []
    for name in ("_selected_latent_attention", "_masked_latent_attention"):
        real = getattr(G, name)
        monkeypatch.setattr(G, name, lambda *a, _n=name, _f=real, **kw: (
            calls.append((_n, a[0].shape[1])), _f(*a, **kw))[1])
    monkeypatch.setattr(G, "KEY_ROWS", 16)
    eng._engine_prefill_chunk.clear_cache()
    eng._engine_step.clear_cache()
    with _engine(cfg, params) as e:
        got, toks = _engine_logits(e, 1, prompt, 4)
    assert {(n, t) for n, t in calls} == {
        ("_masked_latent_attention", 16), ("_selected_latent_attention", 1)}
    monkeypatch.setattr(G, "selected_attention_path", lambda *a: None)
    eng._engine_prefill_chunk.clear_cache()
    with _engine(cfg, params) as e:
        want, again = _engine_logits(e, 1, prompt, 4)
    eng._engine_prefill_chunk.clear_cache()
    eng._engine_step.clear_cache()
    assert toks == again
    assert np.abs(got - want).max() <= F32_TOL


# -- the counts ----------------------------------------------------------

def test_index_counts_equal_what_the_positions_say():
    cfg, params = _model()
    tracer, m = T.Tracer(), ServingMetrics()
    with _engine(cfg, params, metrics=m, tracer=tracer) as e:
        e.admit(Request(rid=1, prompt=tuple(_tokens(5)), max_new_tokens=9,
                        submitted_at=0.0))
        e.admit(Request(rid=2, prompt=tuple(_tokens(37)), max_new_tokens=2,
                        submitted_at=0.0))
        for _ in range(3):
            e.step()
    steps = [ev.fields for ev in tracer.events if ev.kind == T.SERVE_STEP]
    # two full layers score pos + 1 keys a busy lane; five attentions
    # read min(pos + 1, 8) rows a busy lane
    want = [(2 * (6 + 38), 5 * (6 + 8)), (2 * (7 + 39), 5 * (7 + 8)),
            (2 * 8, 5 * 8)]
    assert [(s[T.INDEX_SCANNED], s[T.INDEX_SELECTED])
            for s in steps] == want
    assert m.summary()["index"] == {
        "scanned": sum(w[0] for w in want),
        "selected": sum(w[1] for w in want)}
    text = m.registry.to_prometheus_text()
    assert 'serve_index_positions_total{kind="selected"}' in text \
        or 'kind="selected"' in text
    admits = [ev.fields for ev in tracer.events if ev.kind == T.SERVE_ADMIT]
    assert [a["chunks"] for a in admits] == [1, 3]
    chunks = [ev.fields["offset"] for ev in tracer.events
              if ev.kind == T.SERVE_PREFILL_CHUNK]
    assert chunks == [0, 0, 16, 32]


def test_key_block_counts_equal_what_the_chunks_say(monkeypatch):
    """Key blocks of 16 in lanes of 64: a chunk of 16 at offset o scores
    the blocks up to the one that holds o + 15, in each of five layers."""
    monkeypatch.setattr(G, "KEY_ROWS", 16)
    eng._engine_prefill_chunk.clear_cache()
    cfg, params = _model()
    tracer, m = T.Tracer(), ServingMetrics()
    with _engine(cfg, params, metrics=m, tracer=tracer) as e:
        e.admit(Request(rid=1, prompt=tuple(_tokens(5)), max_new_tokens=2,
                        submitted_at=0.0))
        e.admit(Request(rid=2, prompt=tuple(_tokens(37)), max_new_tokens=2,
                        submitted_at=0.0))
        e.step()
    eng._engine_prefill_chunk.clear_cache()
    chunks = [ev.fields for ev in tracer.events
              if ev.kind == T.SERVE_PREFILL_CHUNK]
    # the bucket of 8 (8 x 8 gathered rows are the lane's 64), then the
    # three chunks of 16
    assert [(c["offset"], c[T.KEY_BLOCKS_LIVE], c[T.KEY_BLOCKS_SKIPPED])
            for c in chunks] == [(0, 5, 15), (0, 5, 15), (16, 10, 10),
                                 (32, 15, 5)]
    assert m.summary()["key_blocks"] == {
        "live": 35, "skipped": 45, "skipped_share": round(45 / 80, 4)}
    text = m.registry.to_prometheus_text()
    assert 'serve_key_blocks_total{kind="live"} 35' in text
    assert 'serve_key_blocks_total{kind="skipped"} 45' in text
    steps = [ev.fields for ev in tracer.events if ev.kind == T.SERVE_STEP]
    assert all(T.KEY_BLOCKS_LIVE not in s for s in steps)


def test_a_gathering_chunk_counts_no_key_block():
    """A bucket of 4 queries x 8 chosen gathers 32 rows where the lane
    holds 64: the chunk keeps the gather and counts nothing."""
    cfg, params = _model()
    tracer, m = T.Tracer(), ServingMetrics()
    with _engine(cfg, params, buckets=(4,), chunk=0, metrics=m,
                 tracer=tracer) as e:
        e.admit(Request(rid=1, prompt=tuple(_tokens(3)), max_new_tokens=2,
                        submitted_at=0.0))
        e.step()
    chunks = [ev.fields for ev in tracer.events
              if ev.kind == T.SERVE_PREFILL_CHUNK]
    assert [(c[T.KEY_BLOCKS_LIVE], c[T.KEY_BLOCKS_SKIPPED])
            for c in chunks] == [(0, 0)]
    assert "key_blocks" not in m.summary()
    assert "serve_key_blocks" not in m.registry.to_prometheus_text()


def test_other_models_count_no_index_position():
    cfg = TransformerConfig(vocab_size=64, d_model=32, n_heads=2,
                            n_layers=1, d_ff=64, max_seq=16, rope=True,
                            ffn="swiglu")
    params = init_transformer(jax.random.key(0), cfg)
    tracer, m = T.Tracer(), ServingMetrics()
    with eng.ServingEngine(params, cfg, eng.EngineConfig(num_slots=2),
                           metrics=m, tracer=tracer) as e:
        e.admit(Request(rid=1, prompt=(1, 2, 3), max_new_tokens=2,
                        submitted_at=0.0))
        e.step()
    step = [ev for ev in tracer.events if ev.kind == T.SERVE_STEP][0]
    assert step.fields[T.INDEX_SCANNED] == step.fields[
        T.INDEX_SELECTED] == 0
    assert "index" not in m.summary()
    assert "serve_index_positions" not in m.registry.to_prometheus_text()
    # nor a key block: its prefill is no chunk through the cache
    assert not [ev for ev in tracer.events
                if ev.kind == T.SERVE_PREFILL_CHUNK]
    assert "key_blocks" not in m.summary()
    assert "serve_key_blocks" not in m.registry.to_prometheus_text()


def test_route_counts_see_the_sparse_layers_only():
    """4 sparse layers x top-4 a token: a padded chunk's padding counts
    nowhere."""
    cfg, params = _model(held=(4, 8))
    with _engine(cfg, params) as e:
        e.admit(Request(rid=1, prompt=tuple(_tokens(21)), max_new_tokens=3))
        e.step()
        route = e.last_route
    for phase, n in (("prefill", 21), ("decode", 1)):
        r = route[phase]
        assert r["held"] + r["absent"] == n * 4 * 4 and r["identity"] == 0
    full = _tokens(21)
    x = jnp.asarray(params["embed"], jnp.float32)[full]
    chosen, held = None, 0
    for i in range(5):
        x, chosen, info = ref.layer_forward(params["layers"], i, x, chosen,
                                            _ref_model((4, 8)))
        if "h_moe" in info:
            held += ref.moe(params["layers"][i]["moe"], info["h_moe"],
                            _ref_model((4, 8)))[2]["held"]
    assert route["prefill"]["held"] == held


# -- what cannot run the kind refuses it ------------------------------------

def _dense_draft():
    dense = TransformerConfig(vocab_size=256, d_model=32, n_heads=2,
                              n_layers=1, d_ff=64, max_seq=MAX_SEQ,
                              rope=True)
    return init_transformer(jax.random.key(0), dense), dense


@pytest.mark.parametrize("what,build", [
    ("index-key page", lambda c, p: eng.PagedServingEngine(
        p, c, eng.PagedEngineConfig())),
    ("index cache", lambda c, p: eng.SpeculativeEngine(p, c,
                                                       *_dense_draft())),
    ("index cache", lambda c, p: eng.PagedSpeculativeEngine(
        p, c, *_dense_draft())),
    ("decode_steps", lambda c, p: eng.ServingEngine(
        p, c, eng.EngineConfig(decode_steps=4))),
    ("index cache", lambda c, p: eng.ServingEngine(
        p, c, eng.EngineConfig(kv_dtype="int8"))),
    ("index-key page", lambda c, p: init_kv_pool(c, 8, 4)),
    ("index key", lambda c, p: init_kv_cache(c, 1, kv_dtype="int8")),
    ("serving slot path", lambda c, p: transformer_apply(
        p, jnp.zeros((1, 4), jnp.int32), c)),
])
def test_refusals_name_what_is_missing(what, build):
    cfg, params = _model()
    with pytest.raises(NotImplementedError) as e:
        build(cfg, params)
    assert what in str(e.value), str(e.value)


def test_chunks_are_refused_where_a_prefill_attends_its_fresh_keys():
    params, dense = _dense_draft()
    with pytest.raises(NotImplementedError, match="in chunks"):
        eng.ServingEngine(params, dense, eng.EngineConfig(prefill_chunk=8))
    cfg, params = _model()
    with pytest.raises(ValueError, match="must divide max_seq"):
        eng.ServingEngine(params, cfg, eng.EngineConfig(prefill_chunk=24))


# -- the expert layer -----------------------------------------------------

def _layer(held=None, seed=0):
    ex = config_from_hf(HF, 8, experts_held=held).experts
    p = init_expert_share(jax.random.key(seed), 64, ex)
    h = jax.random.normal(jax.random.key(seed + 1), (13, 64))
    return ex, p, h


def test_the_shares_add_up():
    """The 16 experts in shares of 4, 8 and 4: the shares' held parts plus
    the shared expert ONCE equal the uncut reference's layer."""
    ex, p, h = _layer()
    part, shared, counts = ref.moe(p, h, _ref_model())
    whole, _ = dropless_moe(h, p, ex)
    np.testing.assert_allclose(whole, part + shared, atol=F32_TOL)
    assert counts["absent"] == 0
    total, held = 0.0, 0
    for offset, count in ((0, 4), (4, 8), (12, 4)):
        share = dataclasses.replace(ex, held_offset=offset,
                                    held_count=count)
        mine = {**p, **{n: p[n][offset:offset + count]
                        for n in ("we1", "we3", "we2")}}
        y, got = dropless_moe(h, mine, share)
        w_part, w_shared, want = ref.moe(mine, h,
                                         _ref_model((offset, count)))
        np.testing.assert_allclose(w_shared, shared, atol=F32_TOL)
        np.testing.assert_allclose(y, w_part + w_shared, atol=F32_TOL)
        assert int(got["held"].sum()) == want["held"]
        assert int(got["identity"].sum()) == 0
        assert int(got["touched"]) == want["touched"]
        total = total + (np.asarray(y) - np.asarray(shared))
        held += int(got["held"].sum())
    np.testing.assert_allclose(total, part, atol=5 * F32_TOL)
    assert held == counts["held"] == 13 * 4


def test_a_bias_changes_the_choice_and_never_a_weight():
    ex, p, h = _layer()
    pick0, w0 = dropless_route(h, p, ex)
    bias = jnp.zeros((16,)).at[5].set(1.0)       # lifts output 5 to the top
    pick1, w1 = dropless_route(h, {**p, "bias": bias}, ex)
    assert bool((pick1 == 5).any(-1).all()) and not bool(
        (pick0 == 5).any(-1).all())
    scores = jax.nn.sigmoid(h @ p["router"])
    for pick, w in ((pick0, w0), (pick1, w1)):
        picked = jnp.take_along_axis(scores, pick, -1)
        np.testing.assert_allclose(
            w, ex.scale * picked / picked.sum(-1, keepdims=True), rtol=1e-5)
        np.testing.assert_allclose(w.sum(-1), ex.scale, rtol=1e-5)


def test_the_softmax_routing_is_what_it_was():
    """The other routing of ``dropless_route``: not renormalised."""
    ex, p, h = _layer()
    soft = dataclasses.replace(ex, scoring="softmax", renormalise=False)
    pick, w = dropless_route(h, p, soft)
    scores = jax.nn.softmax(h @ p["router"], axis=-1) * ex.scale
    np.testing.assert_allclose(w, jnp.take_along_axis(scores, pick, -1),
                               rtol=1e-5)


# -- faults: the comparison that passes the sound program fails each --------

@pytest.mark.parametrize("fault", ref.FAULTS + ("fp8",))
def test_each_planted_fault_comes_out_not_correct(fault):
    cfg, params = _model(held=(4, 8))
    toks = _tokens(43, 11)
    model = _ref_model((4, 8))
    want = np.asarray(ref.forward(params, toks, model))
    with _engine(cfg, params) as e:
        rows, served = _engine_logits(e, 1, toks[:37], 1)
    assert served == [int(np.argmax(want[36]))]
    sound = np.abs(rows - want[36:37]).max()
    if fault == "fp8":
        broken = ref.forward(params, toks, model, quant="fp8")
    else:
        broken = ref.forward(params, toks, model, faults=(fault,))
    gap = np.abs(np.asarray(broken) - want)[36:].max()
    assert sound <= F32_TOL < BF16_BAND < gap, (fault, sound, gap)


# -- the scopes ---------------------------------------------------------------

def test_the_scopes_are_in_the_decode_and_the_chunk_programs():
    import re
    cfg, params = _model()
    e = _engine(cfg, params)
    step = eng._engine_step.lower(
        params, e._state, jnp.asarray(e._pos), cfg).compile().as_text()
    i32 = jnp.asarray(3, jnp.int32)
    chunk = eng._engine_prefill_chunk.lower(
        params, e._state, jnp.zeros((1, 16), jnp.int32), i32, i32, i32,
        cfg).compile().as_text()
    e.close()
    for hlo in (step, chunk):
        names = " ".join(re.findall(r'op_name="([^"]*)"', hlo))
        for sc in T.SERVING_SCOPES - {T.SCOPE_SSM_MIXER, T.SCOPE_SSM_SCAN,
                                      T.SCOPE_SSM_STEP}:
            assert f"/{sc}/" in names, sc
        assert "/attention/" not in names and "/ssm_mixer/" not in names
        # the choice (a top-k beside the router's) is the indexer's; in
        # the chunk program it lies inside the switch over the lane's
        # prefixes, under the same scope
        top_ks = {next(sc for sc in T.SERVING_SCOPES if f"/{sc}/" in name)
                  for name in re.findall(r'op_name="([^"]*/top_k)', hlo)}
        assert top_ks == {T.SCOPE_SPARSE_INDEXER, T.SCOPE_MOE_ROUTER}


def test_the_choice_of_path_is_said_once(capfd):
    from akka_allreduce_tpu.ops.pallas_kernels import dispatch
    cfg, params = _model(max_seq=80)     # a program no test has traced
    dispatch._said.clear()
    with _engine(cfg, params) as e:
        _engine_logits(e, 1, _tokens(20), 3)
    err = capfd.readouterr().err
    said = [ln for ln in err.splitlines()
            if ln.startswith("attention[sparse_latent]")]
    assert said and all("chosen=8 of=80" in ln for ln in said)
    assert len(said) == len(set(said))
    # a chunk's 16 queries share a lane and attend it in place; the step's
    # one query a lane gathers its rows
    chunk = [ln for ln in said if "q=1x16x" in ln]
    step = [ln for ln in said if "q=3x1x" in ln]
    assert len(chunk) == len(step) == 1
    assert "reference:_masked_latent_attention" in chunk[0] \
        and "key_block=16" in chunk[0]
    assert "reference:_selected_latent_attention" in step[0]
