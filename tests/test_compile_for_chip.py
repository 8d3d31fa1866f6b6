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
from jax import lax
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


def _engine_state(cfg, lanes):
    """The slot engine's state for ``lanes`` lanes, as shapes."""
    from akka_allreduce_tpu.serving.engine import _PREFILL_ROUTE

    def state():
        base = G.init_kv_cache(cfg, lanes)
        del base["pos"]
        return {**base, "route": jnp.zeros((len(_PREFILL_ROUTE),), jnp.int32),
                "logits": jnp.zeros((lanes, cfg.vocab_size), cfg.dtype)}
    return jax.eval_shape(state)


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


@pytest.fixture
def chip_rule(monkeypatch):
    """``ep.held_experts_path`` as the chip answers it: the rule asks for
    the default backend, which here is the CPU."""
    real = ep.held_experts_path

    def on_chip(h, params):
        with pytest.MonkeyPatch.context() as m:
            m.setattr(jax, "default_backend", lambda: "tpu")
            return real(h, params)
    monkeypatch.setattr(ep, "held_experts_path", on_chip)


def _step_on_chip(params, state, pos, cfg):
    """The slot engine's decode step compiled for the described chip,
    traced afresh (the engine's own jit may hold a trace of the CPU's
    rule)."""
    from akka_allreduce_tpu.serving import engine as eng
    step = jax.jit(lambda p, s, q: eng._engine_step.__wrapped__(p, s, q, cfg),
                   donate_argnums=(1,))
    return _lower_off_cache(step.lower(params, state, pos))


def _streams(hlo: str) -> list:
    """The streaming kernel's custom calls, with the scope each is under."""
    return re.findall(r"custom-call\(.*custom_call_target=\"tpu_custom_call"
                      r"\".*op_name=\"([^\"]*held_experts_stream[^\"]*)\"",
                      hlo)


def test_the_decode_expert_share_compiles_to_one_streaming_kernel(
        one_chip, cfg, chip_rule):
    """LongCat's 128 lanes x top-12 over 16 held experts: one custom call
    of the streaming kernel under ``moe_experts`` in blocks of 512 of
    d_ff, no grouped matmul and no sort of assignments left; no held
    expert's stack copied or widened on its way in."""
    moe = jax.eval_shape(lambda k: ep.init_expert_share(
        k, cfg.d_model, cfg.experts, cfg.dtype), jax.random.key(0))
    h = jax.ShapeDtypeStruct((LANES, cfg.d_model), cfg.dtype)
    assert ep.held_experts_path(h, moe) == (False, 512)
    compiled = _compile(lambda m, x: ep.dropless_moe(x, m, cfg.experts),
                        _on(one_chip, moe), _on(one_chip, h))
    hlo = compiled.as_text()
    scopes = _streams(hlo)
    assert len(scopes) == 1 and "/moe_experts/" in scopes[0]
    assert "ragged_dot_tiling" not in hlo and "ragged-dot" not in hlo
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


# -- the layer-by-layer model at its cell's sizes (PR 31) --------------------

GLM = dict(
    model_type="glm_moe_dsa", vocab_size=19360, hidden_size=6144,
    intermediate_size=12288, moe_intermediate_size=2048,
    num_hidden_layers=5, num_attention_heads=64, kv_lora_rank=512,
    q_lora_rank=2048, qk_rope_head_dim=64, qk_nope_head_dim=192,
    v_head_dim=256, index_n_heads=32, index_head_dim=128, index_topk=2048,
    indexer_types=["full", "shared", "shared", "shared", "full"],
    mlp_layer_types=["dense", "sparse", "sparse", "sparse", "sparse"],
    n_routed_experts=256, n_shared_experts=1, num_experts_per_tok=8,
    norm_topk_prob=True, routed_scaling_factor=2.5, scoring_func="sigmoid",
    topk_method="noaux_tc", n_group=1, topk_group=1, rms_norm_eps=1e-5,
    rope_parameters={"rope_theta": 8000000, "rope_type": "default"},
    num_nextn_predict_layers=0, experts_held=[0, 16])
GLM_LANES, GLM_MAX_SEQ, GLM_CHUNK = 32, 24576, 2048
CHIP_BYTES = int(15.75 * 2 ** 30)


@pytest.fixture(scope="module")
def glm(one_chip):
    """(cfg, params, engine state, both on the described chip as shapes)."""
    from akka_allreduce_tpu.models.transformer import init_transformer
    cfg = config_from_hf(GLM, GLM_MAX_SEQ, jnp.bfloat16)
    params = jax.eval_shape(lambda k: init_transformer(k, cfg),
                            jax.random.key(0))
    return (cfg, _on(one_chip, params),
            _on(one_chip, _engine_state(cfg, GLM_LANES)))


def _lower_off_cache(lowered):
    from jax.experimental.compilation_cache import compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        return lowered.compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()


def _device_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.temp_size_in_bytes
            + m.output_size_in_bytes - m.alias_size_in_bytes)


def test_the_chip_keeps_a_padded_latent_row_contiguous(one_chip):
    """Why ``TransformerConfig.latent_row`` pads 576 to 640: rows of whole
    registers stay row-major, so a gather of chosen positions reads rows;
    at 576 the chip keeps ``max_seq`` minor (on the v5e the same gather
    takes 16 ms where this takes 1.0: PERF.md section 6, PR 31)."""
    for width, order in ((640, (0, 1, 2, 3)), (576, (0, 1, 3, 2))):
        cache = jax.ShapeDtypeStruct(
            (5, GLM_LANES, GLM_MAX_SEQ, width), jnp.bfloat16,
            sharding=one_chip)
        formats = _compile(lambda x: x, cache).input_formats
        assert tuple(formats[0][0].layout.major_to_minor) == order, width


def test_the_sparse_decode_step_fits_and_copies_no_cache(one_chip, glm,
                                                         chip_rule):
    cfg, params, state = glm
    hand = {"params": 7_763_036_160, "cache": 5_435_817_984}
    assert sum(x.size * x.dtype.itemsize
               for x in jax.tree.leaves(params)) == hand["params"]
    assert sum(state[n].size * 2 for n in ("latent", "index_k")) \
        == hand["cache"] == GLM_LANES * GLM_MAX_SEQ * (5 * 640 + 2 * 128) * 2
    pos = jax.ShapeDtypeStruct((GLM_LANES,), jnp.int32, sharding=one_chip)
    compiled = _step_on_chip(params, state, pos, cfg)
    assert _device_bytes(compiled) <= CHIP_BYTES
    # the four sparse layers' held experts stream under moe_experts
    assert len(_streams(compiled.as_text())) == 4
    # the selected rows (32 x 2,048 x 640 a layer) and a full layer's
    # scores, never a lane's cache: nothing near 0.9 GB of temporaries
    assert compiled.memory_analysis().temp_size_in_bytes < 256 << 20
    entry = compiled.as_text()
    entry = entry[entry.index("ENTRY "):]
    whole = rf"bf16\[(5,)?{GLM_LANES},{GLM_MAX_SEQ},640\]"
    assert not re.search(rf"= {whole}\S* (copy|transpose)\(", entry)
    # the indexers' exact top-k sorts a matrix of rows (8 rows a tile): as
    # (lanes, 1, max_seq) the chip lays a row a tile and the sort takes
    # 4.8 ms a full layer where this takes a third (chip runs, PR 31)
    sorts = re.findall(r"sort\(.*sparse_indexer/top_k", entry)
    assert len(sorts) == 2
    assert len(re.findall(
        rf"= \(f32\[{GLM_LANES},{GLM_MAX_SEQ}\]\{{1,0:T\(8,128\)\}}, .*"
        rf"sparse_indexer/top_k", entry)) == 2
    # the gather of the chosen rows carries no pass that blanks rows, and
    # runs once a layer (the compiler would sooner gather again for the
    # second matmul than keep 84 MB: `_selected_latent_attention`)
    assert "broadcast_select_fusion" not in entry
    assert len(re.findall(rf"= bf16\[{GLM_LANES * 2048},640\]\S* fusion\(",
                          entry)) == 5
    # a query a lane: the step keeps the gather (a masked pass over every
    # live row would read five times the rows)
    assert G.selected_attention_path(1, 2048, GLM_MAX_SEQ, False) is None


def test_the_chunk_program_fits_beside_weights_and_cache(one_chip, glm):
    from akka_allreduce_tpu.serving import engine as eng
    cfg, params, state = glm
    i32 = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    tokens = jax.ShapeDtypeStruct((1, GLM_CHUNK), jnp.int32,
                                  sharding=one_chip)
    compiled = _lower_off_cache(eng._engine_prefill_chunk.lower(
        params, state, tokens, i32, i32, i32, cfg))
    assert _device_bytes(compiled) <= CHIP_BYTES
    # a block of 128 query rows at a time, against one key block of the
    # lane: no more than the gathering program's 0.93 GB (PR 31)
    assert compiled.memory_analysis().temp_size_in_bytes <= 930 << 20
    hlo = compiled.as_text()
    # the chunk's queries share a lane and attend it in place: no gather of
    # a block's 128 x 2,048 chosen rows (5.4 GB a layer); the cache is
    # neither copied nor re-laid (what the key blocks are cut from is the
    # ONE lane, 31 MB a layer, which the compiler lays as its matmuls want:
    # sliced inside the loops it re-lays all 4.7 GB and the program no
    # longer fits, PERF.md section 6, PR 33)
    assert G.selected_attention_path(GLM_CHUNK, 2048, GLM_MAX_SEQ,
                                     True) == G.KEY_ROWS
    assert not re.search(r"= bf16\[(1,)?262144,640\]", hlo)
    assert not re.search(r"= bf16\[(1,)*128,2048,640\]", hlo)
    whole = rf"bf16\[(5,|1,)?{GLM_LANES},{GLM_MAX_SEQ},640\]"
    assert not re.search(rf"= {whole}\S* (copy|transpose|slice)\(", hlo)
    lane = rf"= bf16\[1,{GLM_MAX_SEQ},640\]\S* copy\("
    assert len(re.findall(lane, hlo)) <= cfg.n_layers


# -- the hybrid of state-space mixers at the published widths -------------

GRANITE = dict(
    model_type="granitemoehybrid", vocab_size=50176, hidden_size=4096,
    num_hidden_layers=10, layer_types=["mamba"] * 5 + ["attention"]
    + ["mamba"] * 4, mamba_n_heads=128, mamba_d_head=64, mamba_expand=2,
    mamba_d_state=128, mamba_d_conv=4, mamba_chunk_size=256,
    mamba_n_groups=1, mamba_conv_bias=True, mamba_proj_bias=False,
    attention_bias=False, num_attention_heads=32, num_key_value_heads=8,
    attention_multiplier=0.0078125, position_embedding_type="nope",
    num_local_experts=72, num_experts_per_tok=10, intermediate_size=768,
    shared_intermediate_size=1536, embedding_multiplier=12,
    residual_multiplier=0.22, logits_scaling=16, rms_norm_eps=1e-5,
    tie_word_embeddings=True, hidden_act="silu", experts_held=[0, 36])
GRN_LANES, GRN_MAX_SEQ, GRN_CHUNK = 64, 6144, 2048
GRN_STATE = 9 * GRN_LANES * 128 * 64 * 128 * 4


@pytest.fixture(scope="module")
def granite(one_chip):
    """(cfg, params, engine state, both on the described chip as shapes)."""
    from akka_allreduce_tpu.models.transformer import init_transformer
    cfg = config_from_hf(GRANITE, GRN_MAX_SEQ, jnp.bfloat16)
    params = jax.eval_shape(lambda k: init_transformer(k, cfg),
                            jax.random.key(0))
    return (cfg, _on(one_chip, params),
            _on(one_chip, _engine_state(cfg, GRN_LANES)))


def test_the_hybrid_decode_step_fits_and_holds_one_copy_of_the_state(
        one_chip, granite, chip_rule):
    cfg, params, state = granite
    assert sum(x.size for x in state["ssm_state"]) * 4 == GRN_STATE \
        == 2_415_919_104
    assert (state["k"].size + state["v"].size) * 2 == 1_610_612_736
    weights = sum(x.size * x.dtype.itemsize
                  for x in jax.tree.leaves(params))
    assert 9.51e9 < weights < 9.52e9
    pos = jax.ShapeDtypeStruct((GRN_LANES,), jnp.int32, sharding=one_chip)
    compiled = _step_on_chip(params, state, pos, cfg)
    m = compiled.memory_analysis()
    assert _device_bytes(compiled) <= CHIP_BYTES
    # the state is donated and updated in place: the step's temporaries
    # hold one layer's lanes of it at most, never a second copy of 2.4 GB
    assert m.alias_size_in_bytes >= GRN_STATE
    assert m.temp_size_in_bytes < GRN_STATE // 2
    hlo = compiled.as_text()
    # the ten expert layers' held experts: one streaming kernel each, under
    # moe_experts, so that grn_experts_device_pct books it by scope; no
    # held expert's stack copied or widened on its way in, and the
    # temporaries under the grouped matmuls' program's 0.08 GB (PR 34)
    scopes = _streams(hlo)
    assert len(scopes) == 10 and all("/moe_experts/" in sc for sc in scopes)
    assert "ragged-dot" not in hlo
    assert not re.search(r"= (bf16|f32)\[36,(4096,768|768,4096)\]\S* "
                         r"(copy|convert)\(", hlo)
    assert m.temp_size_in_bytes < 80_000_000
    entry = hlo
    entry = entry[entry.index("ENTRY "):]
    layer = rf"f32\[{GRN_LANES},128,64,128\]"
    assert not re.search(rf"= {layer}\S* (copy|transpose)\(", entry)
    # each layer's state is written by ONE fusion: stacked in one buffer,
    # the compiler rematerialised the first layer's in-place update for
    # its two readers and the state advanced twice a step (chip runs,
    # PR 34: `init_kv_cache`)
    # (now one fusion a layer reads the state once and yields both the
    # read-out and the new state)
    writes = re.findall(rf"(\S+) = \([^=]*{layer}[^=]*\) fusion\(", entry)
    assert len(writes) == 9 and "remat" not in entry


def test_the_hybrid_chunk_program_fits_beside_weights_and_state(one_chip,
                                                                granite):
    from akka_allreduce_tpu.serving import engine as eng
    cfg, params, state = granite
    i32 = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    tokens = jax.ShapeDtypeStruct((1, GRN_CHUNK), jnp.int32,
                                  sharding=one_chip)
    compiled = _lower_off_cache(eng._engine_prefill_chunk.lower(
        params, state, tokens, i32, i32, i32, cfg))
    m = compiled.memory_analysis()
    assert _device_bytes(compiled) <= CHIP_BYTES
    # what ISSUE 34 leaves a chunk beside 13.57 GB: 3.3 GB. The scores of
    # the attention layer come a block of 128 query rows at a time, a
    # scan's decays a block of 256 tokens at a time
    assert m.temp_size_in_bytes < int(3.3e9)
    assert m.alias_size_in_bytes >= GRN_STATE
    hlo = compiled.as_text()
    layer = rf"f32\[{GRN_LANES},128,64,128\]"
    assert not re.search(rf"= {layer}\S* (copy|transpose)\(", hlo)
    assert G.ssm_scan_path(GRN_CHUNK, cfg.ssm_chunk) == 256


# -- the expert share's buffer at a chunk's and a step's assignments -------

# (short prefix, whole buffer) of Granite's chunk, the short one's tile and
# the share's temporaries there (ep._row_prefixes; chip runs, PR 35)
GRN_ROWS, GRN_TILE, GRN_SHARE_TEMP = (11520, 20608), "256", 900_000_000


def test_a_chunks_expert_share_runs_a_prefix_tiled_for_its_rows(one_chip):
    """Granite's chunk (2,048 tokens x top-10 over 36 of 72 experts, ~285
    rows an expert): two branches, the short prefix of the sorted order at
    the tile the chip chose for hundreds of rows an expert, the whole buffer
    at the decode step's 128; no expert stack copied or widened on the way
    into either, and the temporaries are the short branch's plus the
    whole's, not every assignment's at float32 twice over."""
    ex = config_from_hf(GRANITE, GRN_MAX_SEQ, jnp.bfloat16).experts
    rows = GRN_CHUNK * ex.top_k
    short, whole = ep._row_prefixes(rows, ex)
    assert (short, whole) == GRN_ROWS and whole == ep._row_buffer(rows)
    moe = jax.eval_shape(lambda k: ep.init_expert_share(
        k, 4096, ex, jnp.bfloat16), jax.random.key(0))
    h = jax.ShapeDtypeStruct((GRN_CHUNK, 4096), jnp.bfloat16)
    compiled = _compile(lambda m, x: ep.dropless_moe(x, m, ex),
                        _on(one_chip, moe), _on(one_chip, h))
    hlo = compiled.as_text()
    tiles = sorted(re.findall(r'ragged_dot_tiling="(\d+),', hlo))
    assert tiles == sorted(["128"] * 3 + [GRN_TILE] * 3)
    assert not re.search(r"= (bf16|f32)\[36,(4096,768|768,4096)\]\S* "
                         r"(copy|convert)\(", hlo)
    assert compiled.memory_analysis().temp_size_in_bytes < GRN_SHARE_TEMP


def _primitives(jaxpr) -> list:
    """The primitives of a jaxpr and of the jaxprs inside it, a kernel's
    body left out (a ``pallas_call`` is listed with its name)."""
    out = []
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name == "pallas_call":
            out.append(f"pallas_call:{eqn.params['name']}")
            continue
        out.append(name)
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    out += _primitives(inner)
    return out


@pytest.mark.parametrize("which, f_block", [
    ("longcat", 512), ("glm", 512), ("granite", 768)])
def test_the_decode_programs_stream_their_held_experts(which, f_block,
                                                      request, chip_rule):
    """The three expert cells' decode steps at their published widths and
    lanes (128, 32, 64: N <= 128), with the chip's rule: every expert
    layer's held experts go through the streaming kernel at the ``d_ff``
    block the widths give, and nothing of the grouped matmuls is left -
    no ``ragged_dot``, no sort of assignments, no branch."""
    from akka_allreduce_tpu.models.transformer import init_transformer
    from akka_allreduce_tpu.serving import engine as eng
    if which == "longcat":
        cfg = request.getfixturevalue("cfg")
        params = jax.eval_shape(lambda k: init_transformer(k, cfg),
                                jax.random.key(0))
        state = _engine_state(cfg, LANES)
    else:
        cfg, params, state = request.getfixturevalue(which)
    lanes = state["logits"].shape[0]
    pos = jax.ShapeDtypeStruct((lanes,), jnp.int32)
    prims = _primitives(jax.make_jaxpr(
        lambda p, s, q: eng._engine_step.__wrapped__(p, s, q, cfg))(
            params, state, pos).jaxpr)
    assert [n for n in prims if n.startswith("pallas_call")].count(
        "pallas_call:held_experts_stream") == cfg.n_expert_layers
    assert not {"ragged_dot_general", "ragged_dot", "cond", "sort"} \
        & set(prims)
    moe = next(lyr["moe"] for lyr in params["layers"] if "moe" in lyr)
    h = jax.ShapeDtypeStruct((lanes, cfg.d_model), cfg.dtype)
    assert ep.held_experts_path(h, moe) == (False, f_block)


# GLM-4.7-Flash's widths (config.json), one dense and one sparse layer of
# the cell's five, and its multi-token-prediction module
FLASH = dict(
    model_type="glm4_moe_lite", vocab_size=19360, hidden_size=2048,
    intermediate_size=10240, moe_intermediate_size=1536, num_hidden_layers=2,
    first_k_dense_replace=1, num_attention_heads=20, q_lora_rank=768,
    kv_lora_rank=512, qk_nope_head_dim=192, qk_rope_head_dim=64,
    v_head_dim=256, n_routed_experts=64, n_shared_experts=1,
    num_experts_per_tok=4, routed_scaling_factor=1.8, rms_norm_eps=1e-5,
    rope_theta=1e6, num_nextn_predict_layers=1, experts_held=[0, 8])
FLASH_SEQ = 2048


def test_the_latent_train_step_compiles_with_the_kernel_at_head_size_256(
        one_chip, monkeypatch):
    """The train step of latent attention over every position at published
    widths, bf16 compute over float32 masters: the flash kernel runs
    forward and backward at head size 256 with the default block (512: at
    1,024 its dk/dv tiles exceed the 16M of scoped VMEM) and its custom
    calls sit under ``mla_attention`` (the module's under ``mtp`` too);
    the experts go through the grouped matmul, never the decode step's
    streaming kernel."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from akka_allreduce_tpu.models.train import (TrainConfig, make_optimizer,
                                                 make_train_step)
    from akka_allreduce_tpu.models.transformer import init_transformer
    from akka_allreduce_tpu.ops.pallas_kernels.attention import (
        default_flash_block)
    from akka_allreduce_tpu.parallel.mesh import MeshSpec, make_device_mesh
    assert default_flash_block(jnp.bfloat16, 256) == 512
    assert default_flash_block(jnp.bfloat16, 128) == 1024
    cfg = config_from_hf(FLASH, FLASH_SEQ, jnp.float32)
    tc = TrainConfig(model=cfg, compute_dtype="bf16", attn_impl="flash",
                     bucket_elems=1 << 22)
    mesh = make_device_mesh(MeshSpec(dp=1), devices=list(one_chip.device_set))
    rep = NamedSharding(mesh, P())
    params = jax.eval_shape(lambda: init_transformer(jax.random.key(0), cfg))
    opt = make_optimizer(tc)
    state = jax.eval_shape(opt.init, params)
    with monkeypatch.context() as m:
        m.setattr(jax, "default_backend", lambda: "tpu")
        step = make_train_step(tc, mesh, opt, donate=True)
        lowered = step.lower(_on(rep, params), _on(rep, state),
                             jax.ShapeDtypeStruct((1, FLASH_SEQ), jnp.int32,
                                                  sharding=rep))
    hlo = _lower_off_cache(lowered).as_text()
    # the Pallas kernels' custom calls, their op_name's path with JAX's
    # transform wrappers (jvp(...), transpose(...)) taken off
    kernels = ["/" + re.sub(r"[\w.-]+\(|\)", "", k) + "/"
               for k in re.findall(r'custom_call_target="tpu_custom_call".*?'
                                   r'op_name="([^"]*pallas_call[^"]*)"', hlo)]
    # forward, dq and dk/dv of each of the three attentions
    assert len(kernels) >= 9
    assert all("/mla_attention/" in k for k in kernels), kernels
    assert sum("/mtp/" in k for k in kernels) >= 3
    # the compiler names the kernel's calls after the scope, and the
    # grouped matmuls' ragged-dot-*: benchmark/metrics/gfl_attn_roofline.json
    # tells the two apart by that name
    names = re.findall(r'^\s*%(\S+) = .*custom_call_target="tpu_custom_call"'
                       r'.*op_name="[^"]*pallas_call', hlo, re.M)
    assert len(names) == len(kernels)
    assert all("mla_attention" in n for n in names), names
    assert "ragged-dot-none" in hlo
    assert "held_experts_stream" not in hlo


# -- the hybrid of delta-rule and latent attention at the published widths --

LING = dict(
    model_type="bailing_hybrid", vocab_size=39296, hidden_size=2560,
    num_hidden_layers=8, layer_group_size=6, first_k_dense_replace=2,
    num_attention_heads=32, head_dim=128, short_conv_kernel_size=4,
    kda_lower_bound=-5, kv_lora_rank=512, qk_nope_head_dim=128,
    qk_rope_head_dim=64, v_head_dim=128, partial_rotary_factor=0.5,
    rope_theta=6000000, rope_interleave=True, q_lora_rank=None,
    intermediate_size=6144, moe_intermediate_size=768,
    moe_shared_expert_intermediate_size=768, num_shared_experts=1,
    num_experts=512, num_experts_per_tok=8, n_group=8, topk_group=4,
    routed_scaling_factor=2.5, norm_topk_prob=True, rms_norm_eps=1e-6,
    experts_held=[0, 128])
LNG_LANES, LNG_MAX_SEQ, LNG_CHUNK = 64, 24576, 2048
LNG_STATE = 7 * LNG_LANES * 32 * 128 * 128 * 4
LNG_LATENT = LNG_LANES * LNG_MAX_SEQ * 576 * 2


@pytest.fixture(scope="module")
def ling(one_chip):
    """(cfg, params, engine state, both on the described chip as shapes)."""
    from akka_allreduce_tpu.models.transformer import init_transformer
    cfg = config_from_hf(LING, LNG_MAX_SEQ, jnp.bfloat16)
    params = jax.eval_shape(lambda k: init_transformer(k, cfg),
                            jax.random.key(0))
    return (cfg, _on(one_chip, params),
            _on(one_chip, _engine_state(cfg, LNG_LANES)))


def test_the_delta_rule_decode_step_fits_and_updates_its_state_in_place(
        one_chip, ling, chip_rule, monkeypatch):
    """64 lanes x 24,576 beside 10.68 GB of weights: the step's arguments
    are 80% of the chip, each layer's state is donated and updated in
    place (no second copy of 0.94 GB), the held experts stream and the
    latent layer's decode is the fused kernel over the cache as it lies."""
    from akka_allreduce_tpu.ops.pallas_kernels.attention import (
        pick_latent_tiling)
    cfg, params, state = ling
    assert sum(x.size for x in state["kda_state"]) * 4 == LNG_STATE \
        == 939_524_096
    assert state["latent"].size * 2 == LNG_LATENT == 1_811_939_328
    weights = sum(x.size * x.dtype.itemsize
                  for x in jax.tree.leaves(params))
    # 5,342,058,912 parameters in bf16, the float32 ones twice over: the
    # six selection biases and seven layers' A_log and dt_bias
    assert weights == 2 * 5_342_058_912 + 2 * (6 * 512 + 7 * (32 + 4096))
    tiling = pick_latent_tiling(LNG_LANES, LNG_MAX_SEQ, 576, jnp.bfloat16)
    assert tiling == (4, 256)
    monkeypatch.setattr(G, "latent_decode_path",
                        lambda pos, latent: (False, tiling))
    pos = jax.ShapeDtypeStruct((LNG_LANES,), jnp.int32, sharding=one_chip)
    compiled = _step_on_chip(params, state, pos, cfg)
    m = compiled.memory_analysis()
    assert _device_bytes(compiled) <= CHIP_BYTES
    assert m.argument_size_in_bytes > 13.4e9
    assert m.alias_size_in_bytes >= LNG_STATE + LNG_LATENT
    assert m.temp_size_in_bytes < 100_000_000
    hlo = compiled.as_text()
    assert len(_streams(hlo)) == 6 and "ragged-dot" not in hlo
    entry = hlo[hlo.index("ENTRY "):]
    layer = rf"f32\[{LNG_LANES},32,128,128\]"
    assert not re.search(rf"= {layer}\S* (copy|transpose)\(", entry)
    assert len(re.findall(r'custom_call_target="tpu_custom_call"',
                          entry)) == 7


def test_the_delta_rule_chunk_program_fits_beside_weights_and_state(
        one_chip, ling):
    from akka_allreduce_tpu.serving import engine as eng
    cfg, params, state = ling
    i32 = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    tokens = jax.ShapeDtypeStruct((1, LNG_CHUNK), jnp.int32,
                                  sharding=one_chip)
    compiled = _lower_off_cache(eng._engine_prefill_chunk.lower(
        params, state, tokens, i32, i32, i32, cfg))
    m = compiled.memory_analysis()
    assert _device_bytes(compiled) <= CHIP_BYTES
    # the chunk's temporaries: the latent layer's lane (28 MB) and a block
    # of 128 queries' scores against a key block, a block of 64 positions'
    # pairwise decays in the delta rule, the expert share's buffer (0.51 GB
    # compiled for the described v5e)
    assert m.temp_size_in_bytes < 700_000_000
    assert m.alias_size_in_bytes >= LNG_STATE + LNG_LATENT
    hlo = compiled.as_text()
    layer = rf"f32\[{LNG_LANES},32,128,128\]"
    assert not re.search(rf"= {layer}\S* (copy|transpose)\(", hlo)
    assert G.kda_chunk_path(LNG_CHUNK) == 64
