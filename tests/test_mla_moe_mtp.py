"""Latent attention over every position with a dropless sigmoid expert share
and a multi-token-prediction module, trained (models/transformer.py
``latent_block``, models/mtp.py, parallel/ep.py ``dropless_moe``'s train
call, models/train.py ``grad_local_latent``) against the plain reference
(models/mla_moe_mtp_reference.py), on the CPU at toy size.

Tolerances. Everything here is float32 against the float32 reference: the
two sum the same products in another order (the grouped matmul over the
sorted assignments against a loop over the experts, the program's
attention against the reference's blocks of query rows). Logits of order 1
agree to 2e-5 (measured to 4e-6); the loss to 1e-5 relative and every
leaf's gradient to 1e-4 of its largest entry (measured to 2e-6); after one
AdamW step every entry whose gradient is at least a thousandth of its
leaf's largest to 1e-6 (measured to 1e-7); the step moves a weight by
about the learning rate, 1e-3, whatever the gradient's size, so where the
gradient is near 0 it is a ratio of two small numbers and is held only to
the step's size (one entry of 8,192 moved 1e-5 apart). The selection bias
after the step agrees exactly (integer loads, the same picks). The choice
of experts is discrete: a near tie of the router that fell the other way
would move a token's output by a whole expert; at these sizes and seeds
none does in float32 (the loads agree exactly).
"""

import json
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from akka_allreduce_tpu.models import mla_moe_mtp_reference as ref
from akka_allreduce_tpu.models.generate import init_kv_cache, prefill
from akka_allreduce_tpu.models.mtp import mtp_hidden, mtp_loss_sums
from akka_allreduce_tpu.models.train import (TrainConfig, make_optimizer,
                                             make_train_step,
                                             param_specs, place_opt_state,
                                             shard_params)
from akka_allreduce_tpu.models.transformer import (
    config_from_hf,
    init_transformer,
    lm_logits,
    transformer_apply,
    transformer_hidden_with_aux,
)
from akka_allreduce_tpu.parallel.ep import (ExpertShareConfig, dropless_moe,
                                            init_expert_share,
                                            selection_bias_update,
                                            shared_expert_ffn)
from akka_allreduce_tpu.parallel.mesh import MeshSpec, make_device_mesh

HF = dict(
    model_type="glm4_moe_lite", vocab_size=256, hidden_size=64,
    intermediate_size=128, moe_intermediate_size=32, num_hidden_layers=3,
    first_k_dense_replace=1, num_attention_heads=4, num_key_value_heads=4,
    q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=12,
    qk_rope_head_dim=4, v_head_dim=16, n_routed_experts=8,
    n_shared_experts=1, num_experts_per_tok=2, norm_topk_prob=True,
    routed_scaling_factor=1.8, topk_method="noaux_tc", n_group=1,
    topk_group=1, rms_norm_eps=1e-5, rope_theta=1e6,
    num_nextn_predict_layers=1, partial_rotary_factor=1, rope_scaling=None,
    tie_word_embeddings=False, attention_bias=False, hidden_act="silu",
    experts_held=[2, 4])
# the published config.json's numbers (GLM-4.7-Flash)
PUBLISHED = dict(
    HF, vocab_size=154880, hidden_size=2048, intermediate_size=10240,
    moe_intermediate_size=1536, num_hidden_layers=47,
    num_attention_heads=20, num_key_value_heads=20, q_lora_rank=768,
    kv_lora_rank=512, qk_nope_head_dim=192, qk_rope_head_dim=64,
    v_head_dim=256, n_routed_experts=64, num_experts_per_tok=4,
    max_position_embeddings=202752, experts_held=[0, 8])
T = 32
LR, WD = 1e-3, 1e-4


def _model(seed=0, **hf):
    """The program's configuration and weights, each selection bias drawn
    away from 0 so that it takes part in the choice."""
    cfg = config_from_hf({**HF, **hf}, T, jnp.float32)
    params = init_transformer(jax.random.key(seed), cfg)

    def bias(path, leaf):
        if getattr(path[-1], "key", None) != "bias":
            return leaf
        k = jax.random.fold_in(jax.random.key(seed + 7), hash(str(path)) % 997)
        return 0.05 * jax.random.normal(k, leaf.shape, jnp.float32)
    return cfg, jax.tree_util.tree_map_with_path(bias, params)


def _tokens(b=2, seed=1):
    return jax.random.randint(jax.random.key(seed), (b, T), 0,
                              HF["vocab_size"]).astype(jnp.int32)


def _targets(tok):
    w = jnp.ones(tok.shape, jnp.float32).at[:, -1].set(0.0)
    tgt = jnp.roll(tok, -1, axis=1)
    return tgt, w, jnp.roll(tgt, -1, axis=1), w.at[:, -2].set(0.0)


def _program_loss(params, tok, cfg, lam=0.3):
    tgt, w, tgt2, w2 = _targets(tok)
    main, mtp, aux = mtp_loss_sums(params, tok, cfg, tgt, w, tgt2, w2)
    return main / w.sum() + lam * mtp / w2.sum(), aux


@pytest.mark.parametrize("seed", [0, 3])
def test_logits_and_mtp_logits_match_the_reference(seed):
    cfg, params = _model(seed)
    tok = _tokens(seed=seed + 1)
    with jax.default_matmul_precision("highest"):
        logits = transformer_apply(params, tok, cfg)
        x, _aux = transformer_hidden_with_aux(params, tok, cfg)
        xm, _counts = mtp_hidden(params, x, jnp.roll(tok, -1, axis=1), cfg)
        mtp_logits = lm_logits(params, xm, cfg)
        rx, rm, _loads = ref.hidden(params, tok, HF)
    np.testing.assert_allclose(logits, rx @ params["lm_head"], atol=2e-5)
    np.testing.assert_allclose(mtp_logits, rm @ params["lm_head"],
                               atol=2e-5)


def test_loss_and_every_gradient_match_the_reference():
    cfg, params = _model()
    tok = _tokens()
    with jax.default_matmul_precision("highest"):
        (loss, aux), grads = jax.value_and_grad(
            _program_loss, has_aux=True)(params, tok, cfg)
    (want, (_main, _mtp, loads)), want_g = ref.value_and_grad(params, tok,
                                                              HF)
    assert abs(float(loss) - float(want)) <= 1e-5 * abs(float(want))
    np.testing.assert_array_equal(aux["route_load"], loads)
    flat = jax.tree_util.tree_flatten_with_path(grads)[0]
    theirs = jax.tree.leaves(want_g)
    assert len(flat) == len(theirs)
    for (path, g), w in zip(flat, theirs):
        scale = float(jnp.abs(w).max())
        if "bias" in jax.tree_util.keystr(path):
            assert scale == 0.0 and float(jnp.abs(g).max()) == 0.0
            continue
        assert float(jnp.abs(g - w).max()) <= 1e-4 * scale + 1e-9, \
            jax.tree_util.keystr(path)


def test_the_module_is_part_of_the_objective():
    """Without its weight the module's leaves get no gradient; with it
    they all do, and the main stack's gradient changes."""
    cfg, params = _model()
    tok = _tokens()
    with jax.default_matmul_precision("highest"):
        g0 = jax.grad(lambda p: _program_loss(p, tok, cfg, 0.0)[0])(params)
        g1 = jax.grad(lambda p: _program_loss(p, tok, cfg, 0.3)[0])(params)
    mtp0 = [float(jnp.abs(x).max()) for x in jax.tree.leaves(g0["mtp"])]
    mtp1 = [float(jnp.abs(x).max()) for x in jax.tree.leaves(g1["mtp"])]
    assert max(mtp0) == 0.0 and sum(m > 0 for m in mtp1) >= len(mtp1) - 1
    assert float(jnp.abs(g0["embed"] - g1["embed"]).max()) > 1e-4


def _step(cfg, params, lr=LR):
    mesh = make_device_mesh(MeshSpec(dp=1), devices=jax.devices()[:1])
    tc = TrainConfig(model=cfg, learning_rate=lr, weight_decay=WD,
                     compute_dtype="f32", bucket_elems=4096)
    opt = make_optimizer(tc)
    placed = shard_params(params, param_specs(cfg), mesh)
    opt_state = place_opt_state(opt, jax.jit(opt.init)(placed), placed,
                                mesh)
    return make_train_step(tc, mesh, opt), placed, opt_state


def test_one_step_and_the_bias_update_match_the_reference():
    cfg, params = _model()
    tok = _tokens()
    step, placed, opt_state = _step(cfg, params)
    with jax.default_matmul_precision("highest"):
        new, _opt_state, metrics = step(placed, opt_state, tok)
    zeros = jax.tree.map(jnp.zeros_like, params)
    want, _m, _v, want_loss = ref.train_step(params, zeros, zeros, tok, 1,
                                             HF, LR, WD)
    assert abs(float(metrics["loss"]) - float(want_loss)) <= 1e-5 * abs(
        float(want_loss))
    assert "route_load" not in metrics
    # two picks a token over every position, on held experts 2-5 a share
    held = int(metrics["route_held"])
    assert 0 < held < 2 * tok.size * 3
    assert 0 < int(metrics["route_touched"]) <= 3 * 4
    _value, grads = ref.value_and_grad(params, tok, HF)
    for (path, a), b, g in zip(
            jax.tree_util.tree_flatten_with_path(new)[0],
            jax.tree.leaves(want), jax.tree.leaves(grads)):
        name = jax.tree_util.keystr(path)
        if "bias" in name:
            np.testing.assert_array_equal(a, b, err_msg=name)
            continue
        # where the gradient is near 0 the step is a ratio of two small
        # numbers (see the module's docstring)
        steady = np.abs(np.asarray(g)) >= 1e-3 * float(jnp.abs(g).max())
        gap = np.abs(np.asarray(a) - np.asarray(b))
        assert float(gap[steady].max()) <= 1e-6, name
        assert float(gap.max()) <= 2 * LR, name
    moved = new["layers"][1]["moe"]["bias"] - params["layers"][1]["moe"][
        "bias"]
    assert set(np.round(np.asarray(moved) / 1e-3).tolist()) <= {-1, 0, 1}
    assert float(jnp.abs(moved).max()) > 0


def test_selection_bias_moves_towards_the_mean_load():
    bias = jnp.zeros((4,), jnp.float32)
    got = selection_bias_update(bias, jnp.asarray([6, 2, 4, 4]))
    np.testing.assert_allclose(got, [-1e-3, 1e-3, 0.0, 0.0])


def test_the_shares_of_a_sparse_layer_add_up_to_the_uncut_layer():
    """Eight chips hold one expert each: their parts, the shared expert
    counted once, are the layer with every expert held, which is the
    reference's uncut layer."""
    whole = ExpertShareConfig(n_outputs=8, top_k=2, scale=1.8, d_ff=32,
                              scoring="sigmoid", renormalise=True,
                              d_shared=32, held_offset=0, held_count=8)
    params = init_expert_share(jax.random.key(4), 64, whole)
    params["bias"] = 0.05 * jax.random.normal(jax.random.key(5), (8,))
    h = jax.random.normal(jax.random.key(6), (48, 64), jnp.float32)
    with jax.default_matmul_precision("highest"):
        uncut, counts = dropless_moe(h, params, whole, train=True)
        shared = shared_expert_ffn(h, params)
        parts = []
        for e in range(8):
            cfg = ExpertShareConfig(**{**whole.__dict__, "held_offset": e,
                                       "held_count": 1})
            mine = dict(params, **{k: params[k][e:e + 1]
                                   for k in ("we1", "we3", "we2")})
            parts.append(dropless_moe(h, mine, cfg, train=True)[0])
    total = sum(p - shared for p in parts) + shared
    np.testing.assert_allclose(total, uncut, atol=2e-5)
    want, load = ref.moe(params, h[None], {**HF, "experts_held": [0, 8]})
    np.testing.assert_allclose(uncut, want[0], atol=2e-5)
    np.testing.assert_array_equal(counts["load"], load)
    assert int(counts["load"].sum()) == 48 * 2


def test_config_from_hf_reads_the_published_config():
    cfg = config_from_hf(PUBLISHED, 8192)
    assert cfg.full_latent and not cfg.indexed and cfg.mtp_layers == 1
    assert cfg.layer_ffn == ("dense",) + ("sparse",) * 46
    assert (cfg.q_lora_rank, cfg.kv_lora_rank, cfg.qk_nope_head_dim,
            cfg.qk_rope_head_dim, cfg.v_head_dim) == (768, 512, 192, 64, 256)
    assert cfg.mla_scales == (1.0, 1.0) and cfg.rope_theta == 1e6
    assert cfg.norm_eps == 1e-5 and cfg.d_ff == 10240
    e = cfg.experts
    assert (e.n_outputs, e.top_k, e.scale, e.d_ff, e.d_shared, e.scoring,
            e.renormalise, e.held_offset, e.held_count) == (
        64, 4, 1.8, 1536, 1536, "sigmoid", True, 0, 8)
    assert cfg.n_expert_layers == 46


@pytest.mark.parametrize("key,value", [
    ("n_group", 2), ("topk_group", 2), ("scoring_func", "softmax"),
    ("topk_method", "greedy"), ("num_nextn_predict_layers", 2),
    ("partial_rotary_factor", 0.5), ("tie_word_embeddings", True),
    ("first_k_dense_replace", 4)])
def test_config_from_hf_refuses_by_key(key, value):
    with pytest.raises(ValueError, match=key):
        config_from_hf({**HF, key: value}, T)


def test_no_serving_path_runs_it():
    cfg, params = _model()
    with pytest.raises(NotImplementedError, match="no cached block"):
        prefill(params, init_kv_cache(cfg, 1), _tokens(1), cfg)


def test_the_train_step_refuses_what_it_does_not_go_through():
    cfg, params = _model()
    mesh = make_device_mesh(MeshSpec(dp=1), devices=jax.devices()[:1])
    tc = TrainConfig(model=cfg, grad_accum=2)
    with pytest.raises(ValueError, match="grad_accum"):
        make_train_step(tc, mesh, make_optimizer(tc))


def test_cli_trains_from_a_model_config(tmp_path, monkeypatch, capsys):
    """Two steps on the test suite's 8 (virtual) devices, dp 8."""
    from akka_allreduce_tpu.cli import main
    path = tmp_path / "config.json"
    path.write_text(json.dumps(HF))
    monkeypatch.setattr(sys, "argv", [
        "aat", "train", "--model-config", str(path), "--steps", "2",
        "--seq", "32", "--batch", "8", "--log-every", "1", "--bf16"])
    assert main() == 0
    out = capsys.readouterr().out
    assert "step    2: loss" in out


def test_the_backward_pass_reads_no_row_past_the_last_group(monkeypatch):
    """On the TPU the grouped matmul leaves the rows of its buffer past the
    last group unwritten, in its forward and in its transpose for the rows'
    gradient; here a grouped matmul that writes NaN there stands in for it:
    the train path's gradients stay what they are (the CPU writes zeros)."""
    from jax import lax
    real = lax.ragged_dot

    @jax.custom_vjp
    def poisoned(lhs, rhs, sizes):
        dead = jnp.arange(lhs.shape[0]) >= sizes.sum()
        return jnp.where(dead[:, None], jnp.nan, real(lhs, rhs, sizes))

    def fwd(lhs, rhs, sizes):
        return poisoned(lhs, rhs, sizes), (lhs, rhs, sizes)

    def bwd(res, g):
        lhs, rhs, sizes = res
        _, vjp = jax.vjp(lambda a, b: real(a, b, sizes), lhs, rhs)
        d_lhs, d_rhs = vjp(g)
        dead = jnp.arange(lhs.shape[0]) >= sizes.sum()
        return (jnp.where(dead[:, None], jnp.nan, d_lhs), d_rhs,
                np.zeros(sizes.shape, jax.dtypes.float0))

    poisoned.defvjp(fwd, bwd)
    cfg = ExpertShareConfig(n_outputs=8, top_k=2, scale=1.8, d_ff=32,
                            scoring="sigmoid", renormalise=True, d_shared=32,
                            held_offset=2, held_count=4)
    params = init_expert_share(jax.random.key(4), 64, cfg)
    h = jax.random.normal(jax.random.key(6), (48, 64), jnp.float32)

    def loss(h, p):
        y, _ = dropless_moe(h, p, cfg, train=True)
        return jnp.sum(y ** 2)
    want = jax.grad(loss, argnums=(0, 1))(h, params)
    monkeypatch.setattr(lax, "ragged_dot", poisoned)
    got = jax.grad(loss, argnums=(0, 1))(h, params)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6)
