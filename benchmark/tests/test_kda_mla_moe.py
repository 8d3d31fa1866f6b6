"""The cell ``serve-ling-long-decode``: its files agree with each other and
with the source (a copy of the published config in ``tests/data``), its
arithmetic with hand counts, its reference with the program's tests' plain
reference (the forward pass is one text in both places), its readers with
hand-made records, and its ``correct`` can fail: the fp8 control and every
planted fault come out not correct by the comparison that passes the sound
program (toy sizes, ``--rehearse-cpu``)."""

import json
import os
import types

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import flops_kda_mla_moe as F
from benchmark import harness, weights_kda_mla_moe
from benchmark import run as run_mod
from benchmark.references import kda_mla_moe_lm as ref

CELL = "serve-ling-long-decode"
HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# the published Ling-3.0-flash config.json and its source, as released
PUBLISHED = os.path.join(HERE, "tests", "data",
                         "ling-3.0-flash-published.json")
FLOOD = ("flood_occupancy_pct", "flood_decode_device_ms_p50",
         "flood_step_mfu_pct")
# compared; ``served_gap`` and ``logits_gap`` are printed and not compared
# (limits/serve-ling-long-decode.json, ``not_compared``)
NUMBERS = ("held_part_gap", "state_gap", "deep_state_gap", "latent_out_gap",
           "moe_out_gap", "expert_out_gap")


def _config():
    with open(os.path.join(HERE, "configs", "ling-3.0-flash-serve.json")) \
            as f:
        return json.load(f)


def _run(capsys, extra=()):
    rc = run_mod.main(["--workload", CELL, "--seed", "2147483659",
                       "--seconds", "2", "--trace", "0", "--rehearse-cpu",
                       *extra])
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def _failed(result):
    return sorted(k for k, c in result["compared"].items() if not c["ok"])


def test_config_keeps_every_published_number_but_the_reduced():
    with open(PUBLISHED) as f:
        row = json.load(f)
    cfg = _config()
    assert cfg["source"] == row["source_url"]
    for key, want in row["config"].items():
        if key in cfg["reduced"]:
            assert cfg["published"][key] == want and cfg[key] != want
        else:
            assert cfg[key] == want, key
    assert sorted(cfg["reduced"]) == sorted(cfg["published"])
    assert cfg["experts_held"] == [0, cfg["num_experts"]]
    # the clamp lists cut to the kept layers, where every entry is 0
    assert cfg["expert_swiglu_limit_list"] == [0] * 8
    assert cfg["share_expert_swiglu_limit_list"] == [0] * 8


def test_cell_and_entries_agree():
    bench = harness.Benchmark()
    cell = bench.cell(CELL)
    assert cell.chips == 1 and cell.traffic["kind"] == "serve_hybrid_kda"
    assert (cell.config_name, cell.traffic_name) == (
        "ling-3.0-flash-serve", "long-decode-flood")
    assert [m["name"] for m in cell.end_to_end] == ["out_tok_s", "setup_s"]
    names = [m["name"] for m in cell.per_layer]
    assert sorted(names) == sorted(FLOOD + (
        "lng_decode_roofline", "lng_kda_chunk_roofline",
        "lng_kda_device_pct", "lng_mla_device_pct",
        "lng_experts_device_pct", "lng_kda_padded_pct",
        "lng_rows_per_expert"))
    for m in cell.per_layer:
        own = m["name"].startswith("lng_")
        assert CELL in m["workloads"]
        assert (m["workloads"] == [CELL]) == own
        assert m["moves"] == "out_tok_s"
        bench.metric_file(m["name"])
    entry = next(c for c in bench.spec["configs"]
                 if c["name"] == cell.config_name)
    assert entry["reduced"] == cell.config["reduced"]
    assert entry["source"] == cell.config["source"]
    eng, traffic = cell.config["engine"], cell.traffic
    assert (eng["slots"], eng["max_seq"], eng["prefill_buckets"],
            eng["prefill_chunk"]) == (64, 24576, [512, 1024, 2048], 2048)
    assert eng["max_seq"] % eng["prefill_chunk"] == 0
    assert (traffic["prompt_len"]["max"]
            + traffic["output_len"]["max"]) <= eng["max_seq"]
    assert traffic["prompt_len"] == {
        "dist": "int_lognormal", "median": 1024, "sigma": 0.8,
        "min": 256, "max": 8192}
    assert traffic["output_len"] == {
        "dist": "int_lognormal", "median": 4096, "sigma": 0.5,
        "min": 1024, "max": 16384}
    others = {json.load(open(os.path.join(HERE, "traffic", f))).get(
        "trace_seed") for f in os.listdir(os.path.join(HERE, "traffic"))
        if f != "long-decode-flood.json"}
    assert traffic["trace_seed"] not in others


def test_hand_counts():
    m = _config()
    assert F.kda_params(m) == (2560 * 20512 + 4096 * 2560 + 12288 * 4 + 32
                               + 4096 + 4096) == 63_053_856
    assert F.latent_params(m) == (2560 * 6144 + 2560 * 576 + 512 * 8192
                                  + 2560 * 32 + 4096 * 2560 + 192 + 512) \
        == 31_965_888
    assert F.shared_params(m) + F.router_params(m) == 7_209_472
    assert F.expert_params(m) == 3 * 2560 * 768 == 5_898_240
    assert F.dense_params(m) + F.kda_params(m) + 2 * 2560 == 110_244_896
    kda_layer = 63_053_856 + 7_209_472 + 128 * 5_898_240 + 2 * 2560
    latent_layer = 31_965_888 + 7_209_472 + 128 * 5_898_240 + 2 * 2560
    assert (kda_layer, latent_layer) == (825_243_168, 794_155_200)
    assert F.total_params(m) == (2 * 110_244_896 + 5 * kda_layer
                                 + latent_layer + 2 * 39296 * 2560 + 2560) \
        == 5_342_058_912
    assert round(F.weight_bytes(m) / 1e9, 2) == 10.68
    # a lane: seven float32 states and a latent row a position of the one
    # latent layer
    assert F.state_bytes(m) == 32 * 128 * 128 * 4 == 2_097_152
    assert F.latent_bytes_per_token(m) == 576 * 2 == 1152
    eng = m["engine"]
    assert round(eng["slots"] * 7 * F.state_bytes(m) / 1e9, 2) == 0.94
    assert round(eng["slots"] * eng["max_seq"] * 1152 / 1e9, 2) == 1.81
    # the chunked form's count at C = 64, a position, head and layer
    assert F.chunk_flops(m, 1) == 32 * 2 * (4 * 64 * 128 + 3 * 128 * 128)
    # the smallest trainable cut of the floors: one dense layer, one period
    # of six expert layers with 8 experts each, an eighth of the vocabulary
    dense_layer = 110_244_896
    per_expert_layer = 7_209_472 + 8 * 5_898_240 + 2 * 2560
    trainable = (dense_layer + 5 * (63_053_856 + per_expert_layer)
                 + 31_965_888 + per_expert_layer + 2 * 19648 * 2560 + 2560)
    assert round(trainable / 1e6, 1) == 884.5


def test_a_hand_worked_step():
    """64 busy lanes at 5,000 live positions each, 80 touched experts:
    the step's bytes are the weights outside the experts, the touched
    experts, the seven layers' states both ways and the latents."""
    m = _config()
    got = F.decode_step_bytes(m, 64 * 5000, 7 * 64, 80)
    want = (2 * F.non_expert_params(m) + 2 * 80 * 5_898_240
            + 2 * 7 * 64 * 2_097_152 + 64 * 5000 * 1152)
    assert got == want
    assert 4.6e9 < got < 4.7e9


def test_weights_have_the_programs_tree_and_depend_on_the_seed_alone():
    from akka_allreduce_tpu.models.transformer import init_transformer
    from benchmark.runners.serve_hybrid_kda import program_config
    model = _config()["rehearsal"]
    cfg = program_config(model, model["engine"])
    a = weights_kda_mla_moe.make_params(7, model, jnp.bfloat16)
    b = weights_kda_mla_moe.make_params(7, model, jnp.bfloat16)
    c = weights_kda_mla_moe.make_params(2 ** 31 + 7, model, jnp.bfloat16)
    theirs = init_transformer(jax.random.key(0), cfg)
    assert jax.tree.structure(a) == jax.tree.structure(theirs)
    assert jax.tree.map(lambda x: (x.shape, x.dtype), a) == jax.tree.map(
        lambda x: (x.shape, x.dtype), theirs)
    assert all(jax.tree.leaves(jax.tree.map(
        lambda x, y: bool((x == y).all()), a, b)))
    w_out = [layer["kda"]["w_out"] for layer in a["layers"]
             if "kda" in layer]
    assert not bool((w_out[0] == c["layers"][0]["kda"]["w_out"]).all())
    assert not bool((w_out[0] == w_out[1]).all())
    moe = a["layers"][-1]["moe"]
    assert moe["router"].shape == (64, 16)          # the source's width
    assert moe["we1"].shape == (8, 64, 32)          # the share held
    assert ["kda" in layer for layer in a["layers"]] == [
        True, True, False, True, True, False, True, True]
    kda = a["layers"][0]["kda"]
    step = np.asarray(jax.nn.softplus(kda["dt_bias"]))
    assert 1e-3 * 0.99 <= step.min() and step.max() <= 1e-1 * 1.01
    decay = np.exp(np.asarray(kda["a_log"]))
    assert 1.0 <= decay.min() and decay.max() <= 16.0


def test_routers_are_cleared_of_their_inputs_mean_direction():
    """Each expert layer's router has no part along the mean of its input
    over the clearing prompts, run by the plain reference, and every
    column keeps the norm it was drawn with."""
    model = _config()["rehearsal"]
    params = weights_kda_mla_moe.make_params(7, model, jnp.float32)
    tokens = weights_kda_mla_moe.clearing_tokens(
        weights_kda_mla_moe.seed_key(7), model)
    inputs = {}
    for row in tokens:
        ref.forward(params, row, model, each=lambda i, info: inputs.setdefault(
            i, []).append(info["h_moe"]) if "h_moe" in info else None)
    assert inputs
    for i, hs in inputs.items():
        router = params["layers"][i]["moe"]["router"]
        mean = jnp.concatenate(hs).mean(0)
        along = jnp.abs(mean @ router) / jnp.linalg.norm(mean)
        assert float(along.max()) < 1e-4 * float(
            jnp.linalg.norm(router, axis=0).min())
        raw = weights_kda_mla_moe._make_layer(
            jax.random.fold_in(weights_kda_mla_moe.seed_key(7), 16 + i),
            tuple(sorted(weights_kda_mla_moe.model_dims(model).items())),
            *weights_kda_mla_moe.layer_kinds(model)[i],
            jnp.float32)["moe"]["router"]
        assert np.allclose(jnp.linalg.norm(router, axis=0),
                           jnp.linalg.norm(raw, axis=0), rtol=1e-5)
        assert not np.allclose(router, raw)


def test_each_layer_is_held_on_the_references_input():
    """The per-layer numbers where a latent layer also ends in the expert
    share (layer 5 at the cell's size): the program's latent and expert
    layers on the reference's inputs read bfloat16's rounding, and a
    stand-in's layers, in its own forward, read its fault."""
    from benchmark.runners.serve_hybrid_kda import (_program_latent,
                                                    _program_moe,
                                                    program_config)
    model = {**_config()["rehearsal"], "first_k_dense_replace": 2}
    eng = model["engine"]
    cfg = program_config(model, eng)
    params = weights_kda_mla_moe.make_params(5, model, jnp.bfloat16)
    prompt = tuple(int(t) for t in np.arange(40) * 7 % model["vocab_size"])
    served = tuple(int(t) for t in np.arange(8) * 5 % model["vocab_size"])
    got = ref.served_numbers(
        params, model, [(prompt, served)], eng["max_seq"],
        stand_ins=("fault.no_head_gate", "fault.no_group_limit"),
        program_moe=_program_moe(params, cfg),
        program_latent=_program_latent(params, cfg, eng["prefill_chunk"]))
    for name in ("latent_out_gap", "moe_out_gap", "expert_out_gap"):
        assert 0 < got["program"][name] < 0.02, name
    assert got["fault.no_head_gate"]["latent_out_gap"] > 0.3
    assert got["fault.no_group_limit"]["latent_out_gap"] > 0
    assert got["fault.no_group_limit"]["expert_out_gap"] > 0.1


def test_the_reference_is_the_programs_plain_reference():
    """One text in two places: the forward pass of the tests' plain
    reference and of the benchmark's are the same lines."""
    def between(path, start, end):
        with open(path) as f:
            text = f.read()
        return text[text.index(start):text.index(end)]
    mine = between(os.path.join(HERE, "references", "kda_mla_moe_lm.py"),
                   "FAULTS = (", "# -- end of the forward pass")
    theirs = between(os.path.join(harness.ROOT, "tests",
                                  "kda_mla_moe_reference.py"),
                     "FAULTS = (", "# -- end of the forward pass")
    assert mine == theirs
    model = {**_config()["rehearsal"],
             "engine": {"prefill_buckets": [16], "prefill_chunk": 16}}
    params = weights_kda_mla_moe.make_params(11, model, jnp.float32)
    toks = jnp.asarray(np.arange(60) * 7 % model["vocab_size"], jnp.int32)
    sound, states = ref.forward(params, toks, model, prompt_len=40)
    for fault in ref.FAULTS:
        broken, other = ref.forward(params, toks, model, faults=(fault,),
                                    prompt_len=40)
        gap = max(float(jnp.abs(broken - sound)[39:].max()),
                  float(jnp.abs(other - states).max()))
        assert gap > 2e-3, fault


def test_readers_find_nothing_where_the_program_marks_nothing():
    """A parent that lacks the counts and the scopes: the new readers
    return None and do not raise."""
    from benchmark import readers
    bench = harness.Benchmark()
    cell = bench.cell("serve-flood")
    run = harness.Run(cell, "TPU v5 lite", 45.0, 30.0, counters={
        "busy_lane_steps": 10, "lane_steps": 10}, steps=[
        {"t0": 0.0, "t1": 1.0, "occupied": 1, "live_positions": 5}],
        trace_span=(0.0, 2.0), model=cell.config)
    assert readers.get("hybrid_kda_decode_roofline_pct")(
        run, pattern="jit_") is None
    assert readers.get("kda_chunk_roofline_pct")(
        run, scope="kda_chunk", pattern="jit_") is None


def test_the_readers_read_what_the_counts_say():
    """The two rooflines from hand-made records: a step whose device time
    is twice the least reads 50; chunks whose time under the scope is four
    times the least read 25."""
    from benchmark import flops, readers
    bench = harness.Benchmark()
    cell = bench.cell(CELL)
    model = cell.config
    peak = flops.peaks("TPU v5 lite")
    step = {"t0": 0.1, "t1": 0.2, "occupied": 64,
            "live_positions": 64 * 5000, "kda_lanes": 7 * 64,
            "route": {"held": 768, "touched": 480}}
    least = flops.roofline_seconds(
        F.decode_step_flops(model, 64, 64 * 5000, 448, 768),
        F.decode_step_bytes(model, 64 * 5000, 448, 480), peak)
    trace = types.SimpleNamespace(
        module_durations=lambda pattern: [2 * least])
    run = harness.Run(cell, "TPU v5 lite", 45.0, 30.0, steps=[step],
                      trace=trace, trace_span=(0.0, 1.0), model=model)
    got = readers.get("hybrid_kda_decode_roofline_pct")(
        run, pattern="jit__engine_step")
    assert abs(got - 50.0) < 1e-6
    chunk_least = flops.roofline_seconds(
        F.chunk_flops(model, 7 * 2048), F.chunk_bytes(model, 7 * 2048), peak)
    run.kda_chunks = [(7 * 2048, 0)]
    run.program = types.SimpleNamespace(
        _scope_s=lambda scope, kind: 4 * chunk_least,
        programs=lambda pattern: [0.1])
    got = readers.get("kda_chunk_roofline_pct")(
        run, scope="kda_chunk", pattern="jit__engine_prefill_chunk")
    assert abs(got - 25.0) < 1e-6


# -- correct can fail --------------------------------------------------------

def test_sound_run_is_correct(capsys):
    r = _run(capsys)
    assert r["correct"] is True and r["rehearsal"] is True, _failed(r)
    assert r["failed"] == 0 and r["metrics"] == {}
    assert set(NUMBERS) | {"replay_compiles", "compiles_in_window",
                           "failed"} <= set(r["compared"])
    assert r["compared"]["replay_compiles"]["value"] == 0


def test_the_fp8_control_and_every_fault_come_out_not_correct(capsys):
    r = _run(capsys, extra=("--control", "fp8"))
    assert r["correct"] is True
    assert r["control_correct"] is False
    # the bfloat16 state needs the cell's contexts of thousands of
    # positions (limits/serve-ling-long-decode.json, rehearsal.logits_gap)
    assert "bf16_state_correct" in r
    for fault in ref.FAULTS:
        assert r[f"fault.{fault}_correct"] is False, fault
    failed = _failed(r)
    assert r["compared"]["fault.no_held.held_part_gap"]["value"] == 1.0
    for fault in ("no_beta", "unbounded_gate", "tail_dropped",
                  "state_not_carried"):
        assert f"fault.{fault}.state_gap" in failed
    for k in failed:      # held to the very limit the program is held to
        pre, _, name = k.rpartition(".")
        assert pre and r["compared"][k]["limit"] == \
            r["compared"][name]["limit"]
