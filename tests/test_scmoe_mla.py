"""The shortcut double layer with latent attention and a dropless expert
share (models/generate.py ``_shortcut_cached_block``, parallel/ep.py
``dropless_moe``) against the plain reference (models/scmoe_reference.py),
on the CPU at toy size, comparing logits.

Tolerances. float32: 1e-5 on logits of order 1 (the program and the
reference sum the same products in another order; measured to 3e-6).
bfloat16: the band 0.25 on the same logits (weights and activations carry 8
bits; measured to 0.11 over the seeds here, and every planted fault reads
above 0.5 in float32).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from akka_allreduce_tpu.models import scmoe_reference as ref
from akka_allreduce_tpu.models.generate import (
    decode_step,
    init_kv_cache,
    init_kv_pool,
    prefill,
    prefill_counted,
)
from akka_allreduce_tpu.models.transformer import (
    TransformerConfig,
    config_from_hf,
    init_transformer,
    rmsnorm,
    transformer_apply,
)
from akka_allreduce_tpu.parallel import ep
from akka_allreduce_tpu.parallel.ep import (
    ExpertShareConfig,
    dropless_moe,
    dropless_route,
    init_expert_share,
)
from akka_allreduce_tpu.serving import Request
from akka_allreduce_tpu.serving import engine as eng

HF = dict(
    vocab_size=256, hidden_size=64, ffn_hidden_size=128,
    expert_ffn_hidden_size=32, num_layers=2, num_attention_heads=4,
    kv_lora_rank=16, q_lora_rank=24, qk_rope_head_dim=8, v_head_dim=16,
    qk_nope_head_dim=16, mla_scale_q_lora=True, mla_scale_kv_lora=True,
    routed_scaling_factor=6, n_routed_experts=16, rms_norm_eps=1e-5,
    rope_theta=1e7, attention_method="MLA", zero_expert_num=8,
    zero_expert_type="identity", moe_topk=4)
F32_TOL, BF16_BAND = 1e-5, 0.25


def _model(dtype=jnp.float32, seed=0, held=None, max_seq=48):
    cfg = config_from_hf(HF, max_seq, dtype, experts_held=held)
    return cfg, init_transformer(jax.random.key(seed), cfg)


def _tokens(n, seed=1):
    return np.asarray(jax.random.randint(jax.random.key(seed), (n,), 0,
                                         HF["vocab_size"]), np.int32)


def _serve(cfg, params, toks, n_prompt):
    """Prefill ``toks[:n_prompt]`` then decode the rest through the latent
    cache: the logits at positions n_prompt-1 .. len(toks)-1."""
    cache, lg = prefill(params, init_kv_cache(cfg, 1),
                        jnp.asarray(toks[None, :n_prompt]), cfg)
    out = [lg[0]]
    for t in toks[n_prompt:]:
        cache, lg = decode_step(params, cache, jnp.asarray([t]), cfg)
        out.append(lg[0])
    return np.asarray(jnp.stack(out), np.float32)


# -- the configuration --------------------------------------------------

def test_config_from_hf_builds_the_double_layer():
    cfg, params = _model()
    assert (cfg.block, cfg.attention, cfg.norm_eps) == (
        "shortcut", "mla", 1e-5)
    assert cfg.latent_dim == 24 and cfg.mla_scales == (
        (64 / 24) ** 0.5, 2.0)
    ex = cfg.experts
    assert (ex.n_outputs, ex.n_identity, ex.top_k, ex.scale, ex.d_ff,
            ex.held_offset, ex.held_count) == (24, 8, 4, 6.0, 32, 0, 16)
    layer = params["layers"][0]
    assert len(layer["mla"]) == len(layer["ffn"]) == 2
    assert layer["moe"]["we1"].shape == (16, 64, 32)
    assert layer["moe"]["router"].shape == (64, 24)


def test_config_from_hf_refuses_what_it_cannot_read():
    with pytest.raises(ValueError, match="attention_method"):
        config_from_hf(dict(
            vocab_size=256, hidden_size=64, num_attention_heads=4,
            num_key_value_heads=2, num_hidden_layers=2,
            intermediate_size=128, rope_theta=1e4), 32)


@pytest.mark.parametrize("change", [
    dict(attention="mla"), dict(block="shortcut"),
    dict(experts=ExpertShareConfig())])
def test_the_new_kinds_come_together_or_not_at_all(change):
    with pytest.raises(ValueError):
        TransformerConfig(rope=True, ffn="swiglu", **change)


@pytest.mark.parametrize("bad", [
    dict(held_offset=12, held_count=8), dict(held_count=0),
    dict(top_k=0), dict(n_identity=24)])
def test_expert_share_config_refuses(bad):
    with pytest.raises(ValueError):
        ExpertShareConfig(**{**dict(n_outputs=24, n_identity=8, top_k=4,
                                    held_count=16), **bad})


@pytest.mark.parametrize("eps", [1e-6, 1e-5, 1e-2])
def test_rmsnorm_takes_eps(eps):
    x = jax.random.normal(jax.random.key(0), (3, 16)) * 0.01
    want = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
    np.testing.assert_allclose(rmsnorm(x, jnp.ones(16), eps), want,
                               rtol=1e-6)
    if eps == 1e-6:   # the default is what the program ran before
        np.testing.assert_array_equal(rmsnorm(x, jnp.ones(16)),
                                      rmsnorm(x, jnp.ones(16), eps))


def test_config_carries_eps_into_the_dense_block():
    cfg = TransformerConfig(vocab_size=64, d_model=32, n_heads=2,
                            n_layers=1, d_ff=64, max_seq=8)
    params = init_transformer(jax.random.key(0), cfg)
    toks = jnp.arange(8)[None] % 64
    a = transformer_apply(params, toks, cfg)
    b = transformer_apply(params, toks,
                          dataclasses.replace(cfg, norm_eps=1e-2))
    assert float(jnp.abs(a - b).max()) > 1e-4


# -- the served path against the reference's full forward ----------------

@pytest.mark.parametrize("dtype,tol", [(jnp.float32, F32_TOL),
                                       (jnp.bfloat16, BF16_BAND)])
@pytest.mark.parametrize("seed", [0, 1])
def test_prefill_then_decode_equals_the_full_forward(dtype, tol, seed):
    cfg, params = _model(dtype, seed)
    toks = _tokens(20, seed + 10)
    got = _serve(cfg, params, toks, 12)
    want, _ = ref.forward(params, toks, cfg)
    assert np.abs(got - np.asarray(want)[11:]).max() <= tol


def test_absorbed_decode_equals_expanded_prefill_on_the_same_cache():
    cfg, params = _model()
    toks = _tokens(16)
    served = _serve(cfg, params, toks, 6)
    for n in (7, 11, 16):        # a prefill of n expands what decode folds
        _c, lg = prefill(params, init_kv_cache(cfg, 1),
                         jnp.asarray(toks[None, :n]), cfg)
        assert np.abs(served[n - 6] - np.asarray(lg[0])).max() <= F32_TOL


def test_decode_reads_what_prefill_cached():
    cfg, params = _model()
    toks = _tokens(9)
    cache, _ = prefill(params, init_kv_cache(cfg, 1),
                       jnp.asarray(toks[None]), cfg)
    assert set(cache) == {"latent", "pos"}
    assert cache["latent"].shape == (4, 1, cfg.max_seq, cfg.latent_dim)
    lat = np.asarray(cache["latent"])
    assert np.abs(lat[:, 0, :9]).min(axis=-1).max() > 0     # written
    assert not lat[:, 0, 9:].any()                          # and no more


def test_a_padded_bucket_and_the_exact_length_give_the_same_logits():
    cfg, params = _model()
    toks = _tokens(11)
    _c, exact, n_exact = prefill_counted(
        params, init_kv_cache(cfg, 1), jnp.asarray(toks[None]), cfg)
    padded = np.zeros((1, 16), np.int32)
    padded[0, :11] = toks
    _c, got, n_pad = prefill_counted(
        params, init_kv_cache(cfg, 1), jnp.asarray(padded), cfg,
        logit_pos=jnp.asarray(10))
    assert np.abs(np.asarray(got) - np.asarray(exact)).max() <= F32_TOL
    # and the padding is not counted
    assert int(n_pad["held"].sum()) == int(n_exact["held"].sum())
    assert int(n_pad["identity"].sum()) == int(n_exact["identity"].sum())
    assert int(n_pad["touched"]) == int(n_exact["touched"])


# -- the engine ------------------------------------------------------------

def _engine(cfg, params, slots=4, **kw):
    return eng.ServingEngine(params, cfg, eng.EngineConfig(
        num_slots=slots, prefill_buckets=(8, 16, 32), **kw))


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
        for s, req, emitted, _why in e.step():
            if req.rid == rid:
                toks = list(emitted)
    return np.stack(rows), toks


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, F32_TOL),
                                       (jnp.bfloat16, BF16_BAND)])
def test_engine_logits_equal_the_reference(dtype, tol):
    cfg, params = _model(dtype)
    prompt = _tokens(11)
    with _engine(cfg, params) as e:
        rows, toks = _engine_logits(e, 7, prompt, 6)
    full = np.concatenate([prompt, np.asarray(toks, np.int32)])
    want, _ = ref.forward(params, full, cfg)
    assert np.abs(rows - np.asarray(want)[10:16]).max() <= tol
    if dtype == jnp.float32:
        assert toks == list(np.argmax(np.asarray(want)[10:16], -1))


def test_a_lanes_logits_do_not_depend_on_the_other_lanes():
    """Fails capacity routing (``moe_ffn``): there a padded prefill bucket
    and a busy decode step derive other capacities and drop other tokens."""
    cfg, params = _model()
    prompt = _tokens(9, 3)
    with _engine(cfg, params) as e:
        alone, toks_alone = _engine_logits(e, 1, prompt, 5)
    with _engine(cfg, params) as e:
        shared, toks_shared = _engine_logits(
            e, 1, prompt, 5,
            others=[(2, _tokens(14, 4)), (3, _tokens(5, 5))])
    assert toks_alone == toks_shared
    assert np.abs(alone - shared).max() <= F32_TOL


def test_engine_counts_where_routing_sent_the_tokens():
    class Sink:
        registry = None

        def __init__(self):
            self.routes = []

        def on_route(self, phase, **counts):
            self.routes.append((phase, counts))

        def __getattr__(self, name):
            if name.startswith("on_"):
                return lambda *a, **k: None
            raise AttributeError(name)

    cfg, params = _model(held=(4, 8))
    sink = Sink()
    prompt = _tokens(11)
    e = eng.ServingEngine(params, cfg, eng.EngineConfig(
        num_slots=4, prefill_buckets=(16,)), metrics=sink)
    e.admit(Request(rid=1, prompt=tuple(prompt), max_new_tokens=3))
    e.step()
    phases = dict(sink.routes)
    k, layers = cfg.experts.top_k, cfg.n_layers
    # prefill: the 11 true positions, never the 5 of padding
    pre = phases["prefill"]
    assert pre["held"] + pre["identity"] + pre["absent"] == 11 * k * layers
    _lg, want = ref.forward(params, prompt, cfg)
    # the rows the grouped matmuls ran over, padding or not: the bucket's
    # one buffer a layer (no branch at 16 x k assignments)
    assert pre.pop("carried") == layers * ep._row_buffer(16 * k)
    assert pre == {n: int(want[n]) for n in pre}
    # decode: the one busy lane of four
    dec = phases["decode"]
    assert dec["held"] + dec["identity"] + dec["absent"] == k * layers
    assert dec["touched"] <= dec["held"]
    assert e.last_route["decode"] == dec
    e.step()
    assert [p for p, _c in sink.routes].count("prefill") == 1
    e.close()


def test_route_counts_reach_the_registry():
    from akka_allreduce_tpu.serving.metrics import ServingMetrics
    m = ServingMetrics()
    assert "serve_route" not in m.registry.to_prometheus_text()
    m.on_route("decode", held=3, identity=4, absent=5, touched=2)
    m.on_route("prefill", held=1, identity=0, absent=3, touched=1,
               carried=128)
    text = m.registry.to_prometheus_text()
    for kind, n in (("held", 4), ("identity", 4), ("absent", 8),
                    ("carried", 128)):
        assert f'serve_route_assignments_total{{kind="{kind}"}} {n}' in text
    assert "serve_route_experts_touched_total 3" in text


# -- the fused latent decode attention, forced on (interpret mode) ----------
#
# On the CPU ``latent_decode_path`` chooses the formula, so these tests
# patch the choice (never an option of the program) and take a ``max_seq``
# no other test uses: ``_engine_step``'s trace is cached a configuration.

def _force_kernel(monkeypatch):
    from akka_allreduce_tpu.models import generate as G
    from akka_allreduce_tpu.ops.pallas_kernels.attention import (
        pick_latent_tiling)
    monkeypatch.setattr(G, "latent_decode_path", lambda pos, latent: (
        None if pos.ndim != 1 else
        (True, pick_latent_tiling(*latent.shape[1:], latent.dtype))))


def _all_eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _all_eqns(sub)


def _serve_two(cfg, params, n_new, sink=None, tracer=None):
    """A 100-token prompt (so its answer crosses the first 128-position
    key block), a short one beside it, two lanes parked: every request's
    tokens, and the logits each of rid 1's tokens was picked from."""
    e = eng.ServingEngine(params, cfg, eng.EngineConfig(
        num_slots=4, prefill_buckets=(16, 128)), metrics=sink,
        tracer=tracer)
    long_slot = e.admit(Request(rid=1, prompt=tuple(_tokens(100, 3)),
                                max_new_tokens=n_new, submitted_at=0.0))
    e.admit(Request(rid=2, prompt=tuple(_tokens(9, 4)),
                    max_new_tokens=n_new, submitted_at=0.0))
    rows, toks, uploaded = [], {}, []
    for _ in range(n_new):
        rows.append(np.asarray(e._state["logits"][long_slot], np.float32))
        uploaded.append(e._pos.copy())
        for _s, req, emitted, _why in e.step():
            toks[req.rid] = list(emitted)
    e.close()
    return toks, np.stack(rows), uploaded


def test_the_kernel_serves_the_tokens_the_formula_serves(monkeypatch):
    cfg, params = _model(max_seq=256)
    want_toks, want_rows, _ = _serve_two(cfg, params, 40)
    cfg, params = _model(max_seq=384)      # block 128 either way
    ref_toks, ref_rows, _ = _serve_two(cfg, params, 40)
    assert ref_toks == want_toks           # max_seq alone changes nothing
    _force_kernel(monkeypatch)
    cfg, params = _model(max_seq=640)
    got_toks, got_rows, _ = _serve_two(cfg, params, 40)
    assert got_toks == want_toks and len(got_toks[1]) == 40
    assert np.abs(got_rows - want_rows).max() <= F32_TOL


def test_one_kernel_an_attention_under_its_scope_and_none_elsewhere(
        monkeypatch):
    _force_kernel(monkeypatch)
    cfg, params = _model(max_seq=896)
    e = _engine(cfg, params)
    step = jax.make_jaxpr(
        lambda p, s, pos: eng._engine_step.__wrapped__(p, s, pos, cfg))(
        params, e._state, jnp.asarray(e._pos))
    pre = str(jax.make_jaxpr(
        lambda p, s, t: eng._engine_prefill.__wrapped__(
            p, s, t, jnp.asarray(5), jnp.asarray(0), cfg, True))(
        params, e._state, jnp.zeros((1, 8), jnp.int32)))
    e.close()
    scopes = [str(eqn.source_info.name_stack)
              for eqn in _all_eqns(step.jaxpr)
              if eqn.primitive.name == "pallas_call"]
    assert len(scopes) == 2 * cfg.n_layers      # one an attention
    assert all("mla_attention" in sc for sc in scopes), scopes
    assert "pallas_call" not in pre        # the prefill expands its keys
    dense = TransformerConfig(vocab_size=64, d_model=32, n_heads=2,
                              n_layers=1, d_ff=64, max_seq=16, rope=True,
                              ffn="swiglu")
    dp = init_transformer(jax.random.key(0), dense)
    d = eng.ServingEngine(dp, dense, eng.EngineConfig(num_slots=2))
    dense_jaxpr = str(jax.make_jaxpr(
        lambda p, s, pos: eng._engine_step.__wrapped__(p, s, pos, dense))(
        dp, d._state, jnp.asarray(d._pos)))
    d.close()
    assert "pallas_call" not in dense_jaxpr


def test_the_choice_is_said_once(monkeypatch, capfd):
    from akka_allreduce_tpu.ops.pallas_kernels import dispatch
    monkeypatch.setattr(dispatch, "_said", set())
    cfg, params = _model(max_seq=1152)
    with _engine(cfg, params) as e:
        e.admit(Request(rid=1, prompt=tuple(_tokens(9)), max_new_tokens=2))
        e.step()
    lines = [ln for ln in capfd.readouterr().err.splitlines()
             if ln.startswith("attention[latent_decode]")]
    assert len(lines) == 1 and "reference:_latent_attention" in lines[0]
    _force_kernel(monkeypatch)
    cfg, params = _model(max_seq=1408)
    with _engine(cfg, params) as e:
        e.admit(Request(rid=1, prompt=tuple(_tokens(9)), max_new_tokens=3))
        e.step()
        e.step()
    lines = [ln for ln in capfd.readouterr().err.splitlines()
             if ln.startswith("attention[latent_decode]")]
    assert len(lines) == 1, lines          # four call sites, one line
    assert "interpret:latent_decode_attention" in lines[0]
    assert "group=4 blk=128" in lines[0]


def test_the_key_blocks_read_and_skipped_are_counted_from_pos(monkeypatch):
    from akka_allreduce_tpu.runtime import tracing as T
    from akka_allreduce_tpu.serving.metrics import ServingMetrics
    _force_kernel(monkeypatch)
    cfg, params = _model(max_seq=640)      # five key blocks of 128 a lane
    tracer, m = T.Tracer(), ServingMetrics()
    _toks, _rows, uploaded = _serve_two(cfg, params, 40, sink=m,
                                        tracer=tracer)
    steps = [ev for ev in tracer.events if ev.kind == T.SERVE_STEP]
    assert len(steps) == len(uploaded) == 40
    attentions = 2 * cfg.n_layers
    live = skipped = 0
    for ev, pos in zip(steps, uploaded):
        want = attentions * int(sum(p // 128 + 1 for p in pos))
        assert ev.fields[T.KV_BLOCKS_LIVE] == want
        assert ev.fields[T.KV_BLOCKS_SKIPPED] == attentions * 4 * 5 - want
        live, skipped = live + want, skipped + attentions * 20 - want
    # a step before the long request crosses position 128, and one after
    assert steps[0].fields[T.KV_BLOCKS_LIVE] == attentions * 4
    assert steps[-1].fields[T.KV_BLOCKS_LIVE] == attentions * 5
    assert (m.kv_blocks_live, m.kv_blocks_skipped) == (live, skipped)
    assert m.summary()["kv_blocks"] == {
        "live": live, "skipped": skipped,
        "skipped_share": round(skipped / (live + skipped), 4)}
    text = m.registry.to_prometheus_text()
    assert f'serve_kv_blocks_total{{kind="live"}} {live}' in text
    assert f'serve_kv_blocks_total{{kind="skipped"}} {skipped}' in text
    assert {T.KV_BLOCKS_LIVE, T.KV_BLOCKS_SKIPPED} <= set(
        T.SPAN_FIELDS[T.SERVE_STEP])


def test_on_the_formulas_path_no_key_block_is_counted():
    from akka_allreduce_tpu.runtime import tracing as T
    from akka_allreduce_tpu.serving.metrics import ServingMetrics
    cfg, params = _model()
    tracer, m = T.Tracer(), ServingMetrics()
    e = eng.ServingEngine(params, cfg, eng.EngineConfig(
        num_slots=4, prefill_buckets=(16,)), metrics=m, tracer=tracer)
    e.admit(Request(rid=1, prompt=tuple(_tokens(9)), max_new_tokens=2,
                    submitted_at=0.0))
    e.step()
    e.close()
    step = [ev for ev in tracer.events if ev.kind == T.SERVE_STEP][0]
    assert step.fields[T.KV_BLOCKS_LIVE] == 0
    assert step.fields[T.KV_BLOCKS_SKIPPED] == 0
    assert "kv_blocks" not in m.summary()
    assert "serve_kv_blocks" not in m.registry.to_prometheus_text()


# -- what cannot run the new kinds refuses them ------------------------------

def _dense_draft():
    dense = TransformerConfig(vocab_size=256, d_model=32, n_heads=2,
                              n_layers=1, d_ff=64, max_seq=48, rope=True)
    return init_transformer(jax.random.key(0), dense), dense


def _paged(cfg, params, **kw):
    return eng.PagedServingEngine(params, cfg, eng.PagedEngineConfig(**kw))


@pytest.mark.parametrize("what,build", [
    ("PagedServingEngine", lambda c, p: _paged(c, p)),
    ("SpeculativeEngine",
     lambda c, p: eng.SpeculativeEngine(p, c, *_dense_draft())),
    ("PagedSpeculativeEngine",
     lambda c, p: eng.PagedSpeculativeEngine(p, c, *_dense_draft())),
    ("decode_steps", lambda c, p: _engine(c, p, decode_steps=4)),
    ("kv_dtype", lambda c, p: _engine(c, p, kv_dtype="int8")),
    ("latent page", lambda c, p: init_kv_pool(c, 8, 4)),
    ("quantized format", lambda c, p: init_kv_cache(c, 1, kv_dtype="int8")),
    ("serving slot path", lambda c, p: transformer_apply(
        p, jnp.zeros((1, 4), jnp.int32), c)),
])
def test_refusals_name_what_is_missing(what, build):
    cfg, params = _model()
    with pytest.raises(NotImplementedError) as e:
        build(cfg, params)
    assert what in str(e.value)


def test_a_draft_of_the_new_kind_is_refused_too():
    cfg, params = _model()
    dense_params, dense = _dense_draft()
    with pytest.raises(NotImplementedError) as e:
        eng.SpeculativeEngine(dense_params, dense, params, cfg)
    assert "draft" in str(e.value) and "missing" in str(e.value)


# -- the expert layer --------------------------------------------------------

def _layer(held=None, seed=0):
    ex = config_from_hf(HF, 8, experts_held=held).experts
    p = init_expert_share(jax.random.key(seed), 64, ex)
    h = jax.random.normal(jax.random.key(seed + 1), (13, 64))
    return ex, p, h


def test_the_shares_add_up():
    """Over a partition of the experts into shares, the shares' expert
    parts plus the identity part once equal the uncut layer."""
    ex, p, h = _layer()
    whole_part, identity, whole_counts = ref.moe(p, h, ex)
    whole, _ = dropless_moe(h, p, ex)
    np.testing.assert_allclose(whole, whole_part + identity, atol=F32_TOL)
    total, held = 0.0, 0
    for offset, count in ((0, 4), (4, 8), (12, 4)):
        share = dataclasses.replace(ex, held_offset=offset,
                                    held_count=count)
        mine = {**p, **{n: p[n][offset:offset + count]
                        for n in ("we1", "we3", "we2")}}
        y, counts = dropless_moe(h, mine, share)
        part, ident, want = ref.moe(mine, h, share)
        np.testing.assert_allclose(ident, identity, atol=F32_TOL)
        np.testing.assert_allclose(y, part + ident, atol=F32_TOL)
        assert int(counts["held"].sum()) == int(want["held"])
        total = total + (np.asarray(y) - np.asarray(identity))
        held += int(counts["held"].sum())
    np.testing.assert_allclose(total, whole_part, atol=5 * F32_TOL)
    assert held == int(whole_counts["held"])


@pytest.mark.parametrize("held", [None, (0, 4), (10, 6)])
def test_the_three_counts_sum_to_top_k_times_tokens(held):
    ex, p, h = _layer(held)
    if held:
        p = {**p, **{n: p[n][:held[1]] for n in ("we1", "we3", "we2")}}
    _y, counts = dropless_moe(h, p, ex)
    _part, _ident, want = ref.moe(p, h, ex)
    got_held = int(counts["held"].sum())
    got_identity = int(counts["identity"].sum())
    absent = ex.top_k * h.shape[0] - got_held - got_identity
    assert (got_held, got_identity, absent, int(counts["touched"])) == (
        int(want["held"]), int(want["identity"]), int(want["absent"]),
        int(want["touched"]))
    if held is None:
        assert absent == 0


def test_touched_sees_counted_tokens_only():
    ex, p, h = _layer()
    counted = jnp.arange(13) < 2
    _y, some = dropless_moe(h, p, ex, counted)
    _y, alone = dropless_moe(h[:2], p, ex)
    assert int(some["touched"]) == int(alone["touched"])


def test_a_bias_changes_the_choice_and_never_a_weight():
    ex, p, h = _layer()
    pick0, w0 = dropless_route(h, p, ex)
    bias = jnp.zeros((24,)).at[5].set(1.0)       # lifts output 5 to the top
    pick1, w1 = dropless_route(h, {**p, "bias": bias}, ex)
    assert bool((pick1 == 5).any(-1).all()) and not bool(
        (pick0 == 5).any(-1).all())
    scores = jax.nn.softmax(h @ p["router"], axis=-1) * ex.scale
    np.testing.assert_allclose(
        w1, jnp.take_along_axis(scores, pick1, -1), rtol=1e-5)
    np.testing.assert_allclose(
        w0, jnp.take_along_axis(scores, pick0, -1), rtol=1e-5)
    assert not np.allclose(np.asarray(w0).sum(-1), ex.scale)  # no renorm


def test_an_output_does_not_depend_on_who_shares_the_batch():
    ex, p, h = _layer()
    y, _ = dropless_moe(h, p, ex)
    for i in (0, 7, 12):
        alone, _ = dropless_moe(h[i:i + 1], p, ex)
        np.testing.assert_allclose(alone[0], y[i], atol=F32_TOL)


# -- faults: the comparison that passes the sound program fails each --------

@pytest.mark.parametrize("fault", ["no_held", "no_identity", "no_scale",
                                   "no_kv_scale", "renorm", "fp8"])
def test_each_planted_fault_comes_out_not_correct(fault):
    cfg, params = _model()
    toks = _tokens(20, 11)
    want, _ = ref.forward(params, toks, cfg)
    sound = np.abs(_serve(cfg, params, toks, 12)
                   - np.asarray(want)[11:]).max()
    broken, _ = ref.forward(params, toks, cfg, faults=(fault,))
    gap = np.abs(np.asarray(broken) - np.asarray(want))[11:].max()
    assert sound <= F32_TOL < BF16_BAND < gap, (fault, sound, gap)


# -- the scopes --------------------------------------------------------------

def test_the_new_scopes_are_in_the_decode_and_prefill_programs():
    import re
    from akka_allreduce_tpu.runtime import tracing as T
    cfg, params = _model()
    e = _engine(cfg, params)
    step = eng._engine_step.lower(
        params, e._state, jnp.asarray(e._pos), cfg).compile().as_text()
    pre = eng._engine_prefill.lower(
        params, e._state, jnp.zeros((1, 8), jnp.int32),
        jnp.asarray(5, jnp.int32), jnp.asarray(0, jnp.int32), cfg,
        gather=True).compile().as_text()
    e.close()
    for hlo in (step, pre):
        names = " ".join(re.findall(r'op_name="([^"]*)"', hlo))
        # the double layer has no indexer, no shared expert and no
        # state-space mixer
        for sc in T.SERVING_SCOPES - {T.SCOPE_SPARSE_INDEXER,
                                      T.SCOPE_MOE_SHARED, T.SCOPE_SSM_MIXER,
                                      T.SCOPE_SSM_SCAN, T.SCOPE_SSM_STEP,
                                      T.SCOPE_KDA_MIXER, T.SCOPE_KDA_CHUNK,
                                      T.SCOPE_KDA_STEP}:
            assert f"/{sc}/" in names, sc
        assert "/attention/" not in names
        assert "sparse_indexer" not in names and "moe_shared" not in names


def test_the_dense_programs_carry_attention_and_dense_ffn():
    import re
    cfg = TransformerConfig(vocab_size=64, d_model=32, n_heads=2,
                            n_layers=1, d_ff=64, max_seq=16, rope=True,
                            ffn="swiglu")
    params = init_transformer(jax.random.key(0), cfg)
    e = eng.ServingEngine(params, cfg, eng.EngineConfig(num_slots=2))
    assert set(e._state) == {"k", "v", "logits"}       # as before
    hlo = eng._engine_step.lower(
        params, e._state, jnp.asarray(e._pos), cfg).compile().as_text()
    e.close()
    names = " ".join(re.findall(r'op_name="([^"]*)"', hlo))
    assert "/attention/" in names and "/dense_ffn/" in names
    assert "moe_" not in names and "mla_" not in names
