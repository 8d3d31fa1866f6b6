"""The fused latent decode attention (ops/pallas_kernels/attention.py
``latent_decode_attention``) in interpret mode on the CPU, against the
pure-JAX formula it replaces in the slot engine's step on the TPU
(models/generate.py ``_latent_attention``) and against the float32
reference of the whole attention (models/scmoe_reference.py ``mla``).

Tolerances. The kernel sums what the formula sums in another order (an
online softmax a key block at a time). Against the formula, on outputs up
to 3.6: float32 2e-5 (measured to 5e-7); bfloat16 0.04, a little over one
rounding of the output (2 ** -7 relative; measured to 0.016). Against the
float32 reference, on the attention's output through ``wo`` (up to 0.9):
float32 2e-5 (measured to 9e-7), bfloat16 0.02 (measured to 0.0043: the
weights and the cache carry 8 bits).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from akka_allreduce_tpu.models import generate as G
from akka_allreduce_tpu.models import scmoe_reference as ref
from akka_allreduce_tpu.models.transformer import config_from_hf, init_mla
from akka_allreduce_tpu.ops.pallas_kernels.attention import (
    latent_block_index,
    latent_decode_attention,
    latent_keys_lie_minor,
    pick_latent_tiling,
)

TOL = {jnp.float32: 2e-5, jnp.bfloat16: 0.04}
# (heads, rank, rope): a toy width, and the published 512 + 64 x 64 heads
WIDTHS = {"toy": (4, 16, 8), "published": (64, 512, 64)}
PUBLISHED = dict(
    vocab_size=256, hidden_size=6144, ffn_hidden_size=128,
    expert_ffn_hidden_size=32, num_layers=1, num_attention_heads=64,
    kv_lora_rank=512, q_lora_rank=1536, qk_rope_head_dim=64, v_head_dim=128,
    qk_nope_head_dim=128, mla_scale_q_lora=True, mla_scale_kv_lora=True,
    routed_scaling_factor=6, n_routed_experts=16, rms_norm_eps=1e-5,
    rope_theta=1e7, attention_method="MLA", zero_expert_num=8,
    zero_expert_type="identity", moe_topk=4)
TOY = {**PUBLISHED, "hidden_size": 64, "num_attention_heads": 4,
       "kv_lora_rank": 16, "q_lora_rank": 24, "qk_rope_head_dim": 8,
       "v_head_dim": 16, "qk_nope_head_dim": 16}


def _operands(width, dtype, lanes, max_seq, attentions=2, seed=0):
    heads, rank, rope = WIDTHS.get(width, width)
    kq, kc = jax.random.split(jax.random.key(seed))
    q = jax.random.normal(kq, (lanes, heads, rank + rope),
                          jnp.float32).astype(dtype)
    cache = jax.random.normal(kc, (attentions, lanes, max_seq, rank + rope),
                              jnp.float32).astype(dtype)
    return q, cache, rank, (rank + rope) ** -0.5


def _formula(q, cache, a, pos, rank, scale):
    return G._latent_attention(q[:, None], cache[a], pos, rank,
                               scale)[:, 0]


def _gap(got, want):
    return float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                 - want.astype(jnp.float32))))


def _edge_positions(blk, max_seq):
    """Eight lanes: every edge of a key block, a parked lane (0) among
    busy ones, and a mix."""
    return jnp.asarray([0, blk - 1, blk, blk + 1, max_seq - 1, 0,
                        max_seq // 3, 1], jnp.int32) % max_seq


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("width", list(WIDTHS))
def test_matches_the_formula_at_every_edge_of_a_block(width, dtype):
    max_seq, tiling = 512, (4, 128)
    q, cache, rank, scale = _operands(width, dtype, 8, max_seq)
    pos = _edge_positions(tiling[1], max_seq)
    for a in range(cache.shape[0]):
        got = latent_decode_attention(q, cache, a, pos, rank, scale,
                                      tiling=tiling, interpret=True)
        assert got.shape == (8, q.shape[1], rank) and got.dtype == dtype
        assert _gap(got, _formula(q, cache, a, pos, rank, scale)) \
            <= TOL[dtype]


# what ``pick_latent_tiling`` returns, over every branch of its rule: the
# block it aims at, the next one down, a whole short buffer, a group cut
# by the lanes and a group cut by the VMEM that wide float32 rows take.
# (lanes, max_seq, (heads, rank, rope), dtype) -> (group, blk)
RULE = [
    ((128, 2048, WIDTHS["published"], jnp.bfloat16), (4, 256)),  # the cell
    ((8, 512, WIDTHS["toy"], jnp.bfloat16), (4, 256)),
    ((8, 384, WIDTHS["toy"], jnp.bfloat16), (4, 128)),
    ((8, 48, WIDTHS["toy"], jnp.float32), (4, 48)),
    ((6, 512, WIDTHS["toy"], jnp.bfloat16), (2, 256)),
    ((3, 512, WIDTHS["toy"], jnp.bfloat16), (1, 256)),
    ((8, 512, WIDTHS["published"], jnp.float32), (4, 256)),
    ((8, 512, (2, 1920, 128), jnp.float32), (2, 256)),
]


@pytest.mark.parametrize(
    "shape,want", RULE,
    ids=[f"{s[0]}x{s[1]}x{s[2][1] + s[2][2]}-{jnp.dtype(s[3]).name}"
         for s, _w in RULE])
def test_every_tiling_the_rule_returns_runs(shape, want):
    lanes, max_seq, width, dtype = shape
    assert pick_latent_tiling(lanes, max_seq, width[1] + width[2],
                              dtype) == want
    if lanes > 8:
        return      # the cell's own shape is compiled for the chip, not run
    q, cache, rank, scale = _operands(width, dtype, lanes, max_seq, 1)
    pos = _edge_positions(want[1], max_seq)[:lanes]
    got = latent_decode_attention(q, cache, 0, pos, rank, scale,
                                  interpret=True)     # the rule's tiling
    assert _gap(got, _formula(q, cache, 0, pos, rank, scale)) <= TOL[dtype]


def test_the_rule_refuses_what_cannot_fit():
    # one lane's two buffers of a whole odd buffer, past the VMEM share
    assert pick_latent_tiling(8, 9999, 576, jnp.float32) is None
    q, cache, rank, scale = _operands("toy", jnp.float32, 4, 64)
    pos = jnp.zeros((4,), jnp.int32)
    with pytest.raises(ValueError, match="does not divide"):
        latent_decode_attention(q, cache, 0, pos, rank, scale,
                                tiling=(3, 16), interpret=True)
    with pytest.raises(ValueError, match="float caches only"):
        latent_decode_attention(q, cache.astype(jnp.int8), 0, pos, rank,
                                scale, interpret=True)


@pytest.mark.parametrize("poison", [float("nan"), 1e30],
                         ids=["nan", "1e30"])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
def test_nothing_past_a_lanes_position_is_used(dtype, poison):
    """The dead region poisoned: inside the block that holds the position
    (resident, masked) and in the blocks after it (never named)."""
    max_seq, tiling = 512, (4, 128)
    q, cache, rank, scale = _operands("toy", dtype, 8, max_seq)
    pos = _edge_positions(tiling[1], max_seq)
    dead = jnp.arange(max_seq)[None, :] > pos[:, None]
    poisoned = jnp.where(dead[None, :, :, None], poison,
                         cache.astype(jnp.float32)).astype(dtype)
    kw = dict(tiling=tiling, interpret=True)
    clean = latent_decode_attention(q, cache, 1, pos, rank, scale, **kw)
    got = latent_decode_attention(q, poisoned, 1, pos, rank, scale, **kw)
    assert bool(jnp.isfinite(got.astype(jnp.float32)).all())
    assert _gap(got, clean) == 0.0


@pytest.mark.parametrize("blk", [128, 256, 48])
def test_a_lane_names_exactly_its_live_blocks(blk):
    max_seq = blk * 8
    pos = np.asarray([0, 1, blk - 1, blk, blk + 1, 3 * blk + 7,
                      max_seq - 1], np.int32)
    for lane, p in enumerate(pos):
        named = [tuple(int(x) for x in latent_block_index(
            5, lane, j, pos, blk)) for j in range(max_seq // blk)]
        assert {n[:3] for n in named} == {(5, lane, 0)}
        blocks = [n[3] for n in named]
        # in order, then the last live block again: no copy for a dead step
        live = int(p) // blk + 1
        assert blocks == list(range(live)) + [live - 1] * (
            max_seq // blk - live)
        assert len(set(blocks)) == live


@pytest.mark.parametrize("hf,dtype,tol", [
    (TOY, jnp.float32, 2e-5), (TOY, jnp.bfloat16, 0.02),
    (PUBLISHED, jnp.float32, 2e-5)], ids=["toy-f32", "toy-bf16",
                                           "published-f32"])
def test_the_attention_through_the_kernel_meets_the_float32_reference(
        monkeypatch, hf, dtype, tol):
    """``_mla_cached_attention`` with the kernel forced on: two lanes
    prefilled with 299 positions, then one decode step with lane 0 at
    position 299 (its second key block) and lane 1 back at 37, its later
    positions stale in the cache. Each lane's output is the reference's
    full forward over what that lane may attend."""
    max_seq, t = 512, 300
    cfg = config_from_hf(hf, max_seq, dtype)
    monkeypatch.setattr(G, "latent_decode_path", lambda pos, latent: (
        None if pos.ndim != 1 else
        (True, pick_latent_tiling(*latent.shape[1:], latent.dtype))))
    p = init_mla(jax.random.key(0), cfg)
    x = jax.random.normal(jax.random.key(1), (2, t, cfg.d_model),
                          jnp.float32).astype(dtype)
    kv = {"latent": jnp.zeros((2, 2, max_seq, cfg.latent_dim), dtype)}
    _out, kv = G._mla_cached_attention(p, x[:, :t - 1], kv, 1, cfg,
                                       G.CacheOps())
    pos = jnp.asarray([t - 1, 37], jnp.int32)
    got, _kv = G._mla_cached_attention(p, x[:, t - 1:], kv, 1, cfg,
                                       G.CacheOps(pos=pos))
    x32 = np.asarray(x, np.float32)
    for lane, at in enumerate(np.asarray(pos)):
        seen = np.concatenate([x32[lane, :at], x32[lane, t - 1:]])
        want = ref.mla(p, jnp.asarray(seen), cfg)[at]
        assert _gap(got[lane, 0], want) <= tol


def test_on_the_cpu_the_formula_runs():
    latent = jnp.zeros((2, 4, 64, 24), jnp.bfloat16)
    assert G.latent_decode_path(jnp.zeros((4,), jnp.int32), latent) is None
    assert G.latent_decode_path(jnp.zeros((), jnp.int32), latent) is None
    # and the CPU keeps the cache as the program writes it, rows of width
    assert not latent_keys_lie_minor((2, 4, 64, 24), jnp.dtype(jnp.bfloat16))
