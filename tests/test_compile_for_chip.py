"""The new pieces of the serving step compile for the chip at the published
widths: the TPU's compiler is installed here and compiles for a v5e that is
described and not attached (nothing runs; no time is read). One file, so
one worker loads the TPU's library; the topology is described inside a
fixture, never at import.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from akka_allreduce_tpu.models import generate as G
from akka_allreduce_tpu.models.transformer import config_from_hf, init_mla
from akka_allreduce_tpu.parallel import ep

HF = dict(
    vocab_size=16384, hidden_size=6144, ffn_hidden_size=12288,
    expert_ffn_hidden_size=2048, num_layers=1, num_attention_heads=64,
    kv_lora_rank=512, q_lora_rank=1536, qk_rope_head_dim=64, v_head_dim=128,
    qk_nope_head_dim=128, mla_scale_q_lora=True, mla_scale_kv_lora=True,
    routed_scaling_factor=6, n_routed_experts=512, rms_norm_eps=1e-5,
    rope_theta=1e7, attention_method="MLA", zero_expert_num=256,
    zero_expert_type="identity", moe_topk=12, experts_held=[0, 16])
LANES, MAX_SEQ = 128, 2048


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - whatever keeps it from us
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def cfg():
    return config_from_hf(HF, MAX_SEQ, jnp.bfloat16)


def _on(sharding, tree):
    return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
        x.shape, x.dtype, sharding=sharding), tree)


def _compile(fn, *args, donate=()):
    """Compiled for the described chip, with the persistent cache off:
    what it would write there cannot be read back without a chip."""
    from jax.experimental.compilation_cache import compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        return jax.jit(fn, donate_argnums=donate).lower(*args).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()


def test_the_expert_share_compiles_to_one_grouped_matmul_a_stack(one_chip,
                                                                  cfg):
    moe = jax.eval_shape(lambda k: ep.init_expert_share(
        k, cfg.d_model, cfg.experts, cfg.dtype), jax.random.key(0))
    h = jax.ShapeDtypeStruct((LANES, cfg.d_model), cfg.dtype)
    compiled = _compile(lambda m, x: ep.dropless_moe(x, m, cfg.experts),
                        _on(one_chip, moe), _on(one_chip, h))
    hlo = compiled.as_text()
    # 128-row tiles: what the odd multiple of 128 rows buys (ep._row_buffer)
    assert re.findall(r'ragged_dot_tiling="(\d+),', hlo) == ["128"] * 3
    # no held expert's stack is copied or widened on its way in
    assert not re.search(r"= (bf16|f32)\[16,(6144,2048|2048,6144)\]\S* "
                         r"(copy|convert)\(", hlo)
    assert compiled.memory_analysis().temp_size_in_bytes < 512 << 20


def test_the_absorbed_decode_attends_the_latent_without_copying_it(one_chip,
                                                                    cfg):
    p = jax.eval_shape(lambda k: init_mla(k, cfg), jax.random.key(0))
    kv = {"latent": jax.ShapeDtypeStruct(
        (2, LANES, MAX_SEQ, cfg.latent_dim), cfg.dtype)}
    x = jax.ShapeDtypeStruct((LANES, 1, cfg.d_model), cfg.dtype)
    pos = jax.ShapeDtypeStruct((LANES,), jnp.int32)

    def step(p, x, kv, pos):
        return G._mla_cached_attention(p, x, kv, 1, cfg, G.CacheOps(pos=pos))
    compiled = _compile(step, *_on(one_chip, (p, x, kv, pos)), donate=(2,))
    hlo = compiled.as_text()
    entry = hlo[hlo.index("ENTRY "):]
    whole = rf"bf16\[(2,)?{LANES},{MAX_SEQ},{cfg.latent_dim}\]"
    assert not re.search(rf"= {whole}\S* copy\(", entry)
    # the cache (604 MB here) is updated in place, never doubled
    assert compiled.memory_analysis().temp_size_in_bytes < 400 << 20


def test_the_chip_keeps_the_published_cache_with_its_positions_minor(
        one_chip):
    """What ``latent_decode_path`` asks the compiler on the TPU, asked of
    the described chip: the layout it gives a jitted program's argument of
    the cell's cache shape (a v5e said the same of a live array, PERF.md
    section 6, PR 30). The kernel reads the cache that way; were this to
    change, the kernel would stand down and the formula run."""
    cache = jax.ShapeDtypeStruct((8, LANES, MAX_SEQ, 576), jnp.bfloat16,
                                 sharding=one_chip)
    formats = _compile(lambda x: x, cache).input_formats
    assert tuple(formats[0][0].layout.major_to_minor) == (0, 1, 3, 2)


def test_the_fused_decode_reads_the_latent_where_it_lies(one_chip, cfg,
                                                         monkeypatch):
    """The slot engine's decode attention with the kernel engaged as on the
    TPU (Mosaic, not the interpreter): one custom call, its view of the
    cache a bitcast, no copy, slice or transpose of the cache, and no
    f32 score tensor over the whole buffer."""
    from akka_allreduce_tpu.ops.pallas_kernels.attention import (
        pick_latent_tiling)
    tiling = pick_latent_tiling(LANES, MAX_SEQ, cfg.latent_dim, cfg.dtype)
    assert tiling == (4, 256)
    monkeypatch.setattr(G, "latent_decode_path",
                        lambda pos, latent: (False, tiling))
    p = jax.eval_shape(lambda k: init_mla(k, cfg), jax.random.key(0))
    kv = {"latent": jax.ShapeDtypeStruct(
        (2, LANES, MAX_SEQ, cfg.latent_dim), cfg.dtype)}
    x = jax.ShapeDtypeStruct((LANES, 1, cfg.d_model), cfg.dtype)
    pos = jax.ShapeDtypeStruct((LANES,), jnp.int32)

    def step(p, x, kv, pos):
        return G._mla_cached_attention(p, x, kv, 1, cfg, G.CacheOps(pos=pos))
    compiled = _compile(step, *_on(one_chip, (p, x, kv, pos)), donate=(2,))
    hlo = compiled.as_text()
    entry = hlo[hlo.index("ENTRY "):]
    assert len(re.findall(r'custom_call_target="tpu_custom_call"',
                          entry)) == 1
    whole = rf"bf16\[(2,)?{LANES},({MAX_SEQ},{cfg.latent_dim}|" \
            rf"{cfg.latent_dim},{MAX_SEQ})\]"
    assert not re.search(
        rf"= {whole}\S* (copy|transpose|slice|dynamic-slice)\(", entry)
    assert re.search(rf"= {whole}\S* bitcast\(", entry)
    assert not re.search(rf"f32\[{LANES},{cfg.n_heads},{MAX_SEQ}\]", hlo)
    # nothing the size of a cache (604 MB here) or of its f32 scores (67 MB)
    assert compiled.memory_analysis().temp_size_in_bytes < 32 << 20
