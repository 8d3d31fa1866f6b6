"""The cell ``serve-granite-rag-flood``: its files agree with each other and
with the source, its arithmetic with hand counts, its reference with the
program's own plain reference (the forward pass is one text in both
places), and its ``correct`` can fail: both controls and every planted
fault come out not correct by the comparison that passes the sound program
(toy sizes, ``--rehearse-cpu``)."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import flops_ssm_moe as F
from benchmark import harness, weights_ssm_moe
from benchmark import run as run_mod
from benchmark.references import ssm_moe_lm as ref

CELL = "serve-granite-rag-flood"
HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
FLOOD = ("flood_occupancy_pct", "flood_decode_device_ms_p50",
         "flood_step_mfu_pct")
NUMBERS = ("served_gap", "logits_gap", "held_part_gap", "state_gap",
           "deep_state_gap")


def _config():
    with open(os.path.join(HERE, "configs",
                           "granite-4.0-h-small-serve.json")) as f:
        return json.load(f)


def _run(capsys, plant=None, extra=()):
    rc = run_mod.main(["--workload", CELL, "--seed", "2147483659",
                       "--seconds", "1", "--trace", "0", "--rehearse-cpu",
                       *extra], plant=plant)
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def _failed(result):
    return sorted(k for k, c in result["compared"].items() if not c["ok"])


# -- the files -------------------------------------------------------------

def test_config_keeps_every_published_number_but_the_reduced():
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    with open(CATALOG) as f:
        rows = [json.loads(line) for line in f]
    row = next(r for r in rows if r["name"] == "granite-4.0-h-small")
    cfg = _config()
    assert cfg["source"] == row["source_url"]
    for key, want in row["config"].items():
        if key in cfg["reduced"]:
            assert cfg["published"][key] == want and cfg[key] != want
        else:
            assert cfg[key] == want, key
    assert sorted(cfg["reduced"]) == sorted(cfg["published"]) == sorted(
        ["num_hidden_layers", "layer_types", "num_local_experts",
         "vocab_size"])
    # one whole period of the published pattern, nine to one
    assert cfg["layer_types"] == row["config"]["layer_types"][:10] \
        == ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
    assert cfg["experts_held"] == [0, 36] and cfg["num_local_experts"] == 36
    assert cfg["vocab_size"] * 2 == row["config"]["vocab_size"]
    for key in ("deployment", "assumed", "published", "engine"):
        assert cfg[key], key


def test_cell_and_entries_agree():
    bench = harness.Benchmark()
    cell = bench.cell(CELL)
    assert cell.chips == 1 and cell.traffic["kind"] == "serve_hybrid_ssm"
    assert (cell.config_name, cell.traffic_name) == (
        "granite-4.0-h-small-serve", "rag-flood")
    assert [m["name"] for m in cell.end_to_end] == ["out_tok_s", "setup_s"]
    names = [m["name"] for m in cell.per_layer]
    assert sorted(names) == sorted(FLOOD + (
        "grn_decode_roofline", "grn_scan_roofline", "grn_ssm_device_pct",
        "grn_experts_device_pct", "grn_scan_padded_pct"))
    for m in cell.per_layer:
        own = m["name"].startswith("grn_")
        # a later cell may be appended after this one: no test of the LAST
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
            eng["prefill_chunk"]) == (64, 6144, [512, 1024, 2048], 2048)
    assert eng["max_seq"] % eng["prefill_chunk"] == 0
    assert (traffic["prompt_len"]["max"]
            + traffic["output_len"]["max"]) <= eng["max_seq"]
    assert (traffic["ramp_s"], traffic["drain"], traffic["check_requests"],
            traffic["trace_settle_s"], traffic["trace_window_s"]) == (
        30.0, "none", 3, 1.5, 4.0)
    assert traffic["prompt_len"] == {
        "dist": "int_lognormal", "median": 2048, "sigma": 0.6,
        "min": 512, "max": 5120}
    assert traffic["output_len"] == {
        "dist": "int_lognormal", "median": 256, "sigma": 0.6,
        "min": 64, "max": 1024}
    others = {json.load(open(os.path.join(HERE, "traffic", f))).get(
        "trace_seed") for f in os.listdir(os.path.join(HERE, "traffic"))
        if f != "rag-flood.json"}
    assert traffic["trace_seed"] not in others


def test_hand_counts():
    m = _config()
    # ISSUE 34's arithmetic, from the row's config
    assert F.ssm_params(m) == (4096 * 16768 + 8192 * 4096 + 8448 * 5
                               + 3 * 128 + 8192) == 102_286_976
    assert F.attention_params(m) == (2 * 4096 * 4096 + 2 * 4096 * 1024) \
        == 41_943_040
    assert F.shared_params(m) == 3 * 4096 * 1536 == 18_874_368
    assert F.router_params(m) == 4096 * 72 == 294_912
    assert F.expert_params(m) == 3 * 4096 * 768 == 9_437_184
    mamba_layer = 102_286_976 + 18_874_368 + 294_912 + 8_192
    attn_layer = 41_943_040 + 18_874_368 + 294_912 + 8_192
    assert (mamba_layer, attn_layer) == (121_464_448, 61_120_512)
    assert F.total_params(m) == (9 * mamba_layer + attn_layer
                                 + 360 * 9_437_184 + 50176 * 4096 + 4096) \
        == 4_757_211_776
    assert round(F.weight_bytes(m) / 1e9, 2) == 9.51
    # whole: 36 Mamba layers and 4 attention layers with all 72 experts
    whole = (36 * mamba_layer + 4 * attn_layer + 40 * 72 * 9_437_184
             + 100352 * 4096 + 4096)
    assert round(whole / 1e9, 1) == 32.2
    # a lane: nine float32 states, nine tails, and a position's keys and
    # values of the ONE attention layer
    assert F.state_bytes(m) == 128 * 64 * 128 * 4 == 4_194_304
    assert 9 * F.state_bytes(m) == 37_748_736
    assert F.kv_bytes_per_token(m) == 2 * 8 * 128 * 2 == 4096
    eng = m["engine"]
    assert round(eng["slots"] * 9 * F.state_bytes(m) / 1e9, 2) == 2.42
    assert round(eng["slots"] * eng["max_seq"] * 4096 / 1e9, 2) == 1.61


def test_a_hand_worked_step_and_scan():
    """64 busy lanes at 2,500 live positions each: nine layers advance 64
    states, one attention layer reads 160,000 cached positions; 320 rows a
    layer fall on held experts and touch all 36."""
    m = _config()
    live, lanes = 64 * 2500, 9 * 64
    nbytes = F.decode_step_bytes(m, live, lanes, touched=10 * 36)
    assert nbytes == (2 * (F.non_expert_params(m) + 360 * 9_437_184)
                      + 2 * lanes * 4_194_304 + live * 4096)
    # 2.7 GB outside the experts (0.41 of it the head), 6.8 GB of experts,
    # 4.8 GB of state, 0.66 GB of keys and values
    assert round(2 * F.non_expert_params(m) / 1e9, 2) == 2.72
    assert round(2 * 360 * 9_437_184 / 1e9, 2) == 6.79
    assert round(2 * lanes * 4_194_304 / 1e9, 2) == 4.83
    assert round(live * 4096 / 1e9, 2) == 0.66
    ops = F.decode_step_flops(m, 64, live, lanes, 10 * 320)
    assert ops == (2 * F.non_expert_params(m) * 64 + 4 * 4096 * live
                   + 5 * lanes * 1_048_576 + 2 * 9_437_184 * 3200)
    assert F.decode_step_flops(m, 0, 0, 0, 0) == 0
    # a chunk's scans: 2,048 positions x nine layers at block 256
    tokens = 9 * 2048
    assert F.scan_flops(m, tokens) == 2 * tokens * (
        256 * 128 + 256 * 8192 + 2 * 128 * 8192)
    assert round(F.scan_flops(m, tokens) / 1e9, 1) == 155.8
    assert F.scan_bytes(m, tokens) == tokens * (
        2 * (2 * 8192 + 256) + 4 * 128 + 2 * 4_194_304 / 256)
    # a prompt of 2,048: the matrices, one attention layer, the scans
    assert F.prefill_flops(m, 2048, 0) == (
        2 * F.body_params(m) * 2048 + 4 * 4096 * 2048 * 2049 / 2
        + F.scan_flops(m, tokens) + 2 * 4096 * 50176)
    assert round(F.prefill_flops(m, 2048, 0) / 1e12, 2) == 4.92


def test_weights_have_the_programs_tree_and_depend_on_the_seed_alone():
    from akka_allreduce_tpu.models.transformer import init_transformer
    from benchmark.runners.serve_hybrid_ssm import program_config
    model = _config()["rehearsal"]
    cfg = program_config(model, model["engine"])
    a = weights_ssm_moe.make_params(7, model, jnp.bfloat16)
    b = weights_ssm_moe.make_params(7, model, jnp.bfloat16)
    c = weights_ssm_moe.make_params(2 ** 31 + 7, model, jnp.bfloat16)
    theirs = init_transformer(jax.random.key(0), cfg)
    assert jax.tree.structure(a) == jax.tree.structure(theirs)
    assert jax.tree.map(lambda x: (x.shape, x.dtype), a) == jax.tree.map(
        lambda x: (x.shape, x.dtype), theirs)
    assert all(jax.tree.leaves(jax.tree.map(
        lambda x, y: bool((x == y).all()), a, b)))
    w_out = [layer["ssm"]["w_out"] for layer in a["layers"]
             if "ssm" in layer]
    assert not bool((w_out[0] == c["layers"][0]["ssm"]["w_out"]).all())
    assert not bool((w_out[0] == w_out[1]).all())
    moe = a["layers"][1]["moe"]
    assert moe["router"].shape == (64, 8)           # the source's width
    assert moe["we1"].shape == (4, 64, 32)          # the share held
    assert moe["ws1"].shape == (64, 48)             # the shared expert
    assert ["ssm" in layer for layer in a["layers"]] == [
        True, False, True, True]
    assert "lm_head" not in a                       # tied
    # the recurrence's constants: A in 1-16, softplus(dt_bias) in
    # 0.001-0.1, D ones
    ssm = a["layers"][0]["ssm"]
    step = np.asarray(jax.nn.softplus(ssm["dt_bias"]))
    assert 1e-3 * 0.99 <= step.min() and step.max() <= 1e-1 * 1.01
    decay = np.exp(np.asarray(ssm["a_log"]))
    assert 1.0 <= decay.min() and decay.max() <= 16.0
    assert bool((ssm["d"] == 1).all())


def test_the_reference_is_the_programs_plain_reference():
    """One text in two places: the forward pass of the program's plain
    reference and of the benchmark's are the same lines."""
    def between(path, start, end):
        with open(path) as f:
            text = f.read()
        return text[text.index(start):text.index(end)]
    mine = between(os.path.join(HERE, "references", "ssm_moe_lm.py"),
                   "FAULTS = (", "# -- end of the forward pass")
    theirs = between(os.path.join(
        harness.ROOT, "akka_allreduce_tpu", "models",
        "ssm_moe_reference.py"), "FAULTS = (", "# -- end of the forward pass")
    assert mine == theirs
    model = _config()["rehearsal"]
    params = weights_ssm_moe.make_params(11, model, jnp.float32)
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
    assert readers.get("hybrid_ssm_decode_roofline_pct")(
        run, pattern="jit_") is None
    assert readers.get("ssm_scan_roofline_pct")(
        run, scope="ssm_scan", pattern="jit_") is None
    assert readers.get("counter_ratio_pct")(
        run, num="scan_padded", den="scan_all") is None


def test_the_readers_read_what_the_counts_say():
    """The two rooflines from hand-made records: a step whose device time
    is twice the least reads 50; scans whose time under the scope is four
    times the least read 25."""
    import types
    from benchmark import flops, readers
    bench = harness.Benchmark()
    cell = bench.cell(CELL)
    model = cell.config
    peak = flops.peaks("TPU v5 lite")
    step = {"t0": 0.1, "t1": 0.2, "occupied": 64,
            "live_positions": 64 * 2500, "ssm_lanes": 9 * 64,
            "route": {"held": 3200, "touched": 360}}
    least = flops.roofline_seconds(
        F.decode_step_flops(model, 64, 64 * 2500, 576, 3200),
        F.decode_step_bytes(model, 64 * 2500, 576, 360), peak)
    assert 0.017 < least < 0.019        # 15.0 GB at 819 GB/s
    trace = types.SimpleNamespace(
        module_durations=lambda pattern: [2 * least])
    run = harness.Run(cell, "TPU v5 lite", 45.0, 30.0, steps=[step],
                      trace=trace, trace_span=(0.0, 1.0), model=model)
    got = readers.get("hybrid_ssm_decode_roofline_pct")(
        run, pattern="jit__engine_step")
    assert abs(got - 50.0) < 1e-6
    scan_least = flops.roofline_seconds(
        F.scan_flops(model, 9 * 2048), F.scan_bytes(model, 9 * 2048), peak)
    run.scans = [(9 * 2048, 0)]
    run.program = types.SimpleNamespace(
        _scope_s=lambda scope, kind: 4 * scan_least,
        programs=lambda pattern: [0.1])
    got = readers.get("ssm_scan_roofline_pct")(
        run, scope="ssm_scan", pattern="jit__engine_prefill_chunk")
    assert abs(got - 25.0) < 1e-6


# -- correct can fail --------------------------------------------------------

def test_sound_run_is_correct(capsys):
    r = _run(capsys)
    assert r["correct"] is True and r["rehearsal"] is True, _failed(r)
    assert r["failed"] == 0 and r["metrics"] == {}
    assert set(NUMBERS) | {"replay_compiles", "compiles_in_window",
                           "failed"} <= set(r["compared"])
    assert r["compared"]["replay_compiles"]["value"] == 0


def test_controls_and_every_fault_come_out_not_correct(capsys):
    r = _run(capsys, extra=("--control", "fp8"))
    assert r["correct"] is True
    assert r["control_correct"] is False
    # the second control needs the cell's contexts of thousands of
    # positions (limits/serve-granite-rag-flood.json, rehearsal.state_gap)
    assert "bf16_state_correct" in r
    for fault in ref.FAULTS:
        assert r[f"fault.{fault}_correct"] is False, fault
    failed = _failed(r)
    assert r["compared"]["fault.no_held.held_part_gap"]["value"] == 1.0
    assert "fault.half_held.held_part_gap" in failed
    assert "fault.no_logit_scale.logits_gap" in failed
    for fault in ("stale_state", "padding_advances", "state_not_carried"):
        assert f"fault.{fault}.state_gap" in failed
    for k in failed:      # held to the very limit the program is held to
        pre, _, name = k.rpartition(".")
        assert pre and r["compared"][k]["limit"] == \
            r["compared"][name]["limit"]


def test_fault_token_altered(capsys):
    def plant(drv):
        drv.alter = lambda rid, toks: [(t + 1) % 256 for t in toks]
    r = _run(capsys, plant=plant)
    assert r["correct"] is False and "served_gap" in _failed(r)


@pytest.mark.parametrize("fault,through", [
    ("stale", "state_gap"), ("padding", "state_gap"),
    ("twice_deep", "deep_state_gap")])
def test_fault_in_the_served_paths_state(capsys, monkeypatch, fault,
                                         through):
    """The timed path broken underneath: the engine's own programs, traced
    anew, leave a lane's state as its last request left it at admission,
    or let padding advance it, or advance the LAST state-space layer's
    state twice a decode step (what the chip's compiler made of a state
    stacked in one buffer, PR 34, there in the first layer). The number
    read from those programs' state says so; the first layer's alone
    cannot see the last."""
    from akka_allreduce_tpu.models import generate as G
    from akka_allreduce_tpu.serving import engine
    real = G._ssm_mixer

    def broken(p, u, kv, j, cfg, ops):
        import dataclasses
        if fault == "stale" and ops.offset is not None:
            ops = dataclasses.replace(ops, offset=ops.offset + 1)
        if fault == "padding" and ops.counted is not None:
            ops = dataclasses.replace(ops, counted=None)
        if fault == "twice_deep" and ops.pos is not None \
                and j == len(cfg.ssm_layers) - 1:
            _out, once = real(p, u, kv, j, cfg, ops)
            kv = {**once, "conv_state": kv["conv_state"]}
        return real(p, u, kv, j, cfg, ops)
    monkeypatch.setattr(G, "_ssm_mixer", broken)
    programs = (engine._engine_step, engine._engine_prefill_chunk)
    for f in programs:
        f.clear_cache()
    try:
        r = _run(capsys)
    finally:
        for f in programs:     # the next test traces the sound path again
            f.clear_cache()
    assert r["correct"] is False
    assert through in _failed(r)
    assert fault != "twice_deep" or "state_gap" not in _failed(r)


# -- the second cell of PR 34: data files over what the benchmark had -------

def test_the_burst_cell_is_serve_chat_under_a_burst_curve():
    """``serve-chat-burst``: ``serve-chat``'s configuration, lengths, mean
    rate, drain and limits; another arrival curve and a trace of its own."""
    from benchmark import loadgen
    bench = harness.Benchmark()
    chat, burst = bench.cell("serve-chat"), bench.cell("serve-chat-burst")
    assert burst.config_name == chat.config_name and burst.chips == 1
    same = ("kind", "loop", "rate_per_s", "ramp_s", "drain", "prompt_len",
            "output_len", "check_requests", "trace_settle_s",
            "trace_window_s")
    assert {k: burst.traffic[k] for k in same} == {
        k: chat.traffic[k] for k in same}
    assert (burst.traffic["arrival"], burst.traffic["burst_multiplier"],
            burst.traffic["burst_length_s"],
            burst.traffic["burst_period_s"]) == ("burst", 4, 0.5, 4.0)
    assert burst.traffic["trace_seed"] != chat.traffic["trace_seed"]
    # the same mean: 7.27 requests/s between bursts, 29.1 inside them
    assert round(loadgen._burst_base(burst.traffic), 2) == 7.27
    assert round(loadgen._peak_rate(burst.traffic), 1) == 29.1
    n = len(loadgen.serve_trace(burst.traffic, 400.0))
    assert abs(n / 400.0 - 10.0) < 0.5
    assert [m["name"] for m in burst.end_to_end] == [
        m["name"] for m in chat.end_to_end]
    assert [m["name"] for m in burst.per_layer] == [
        m["name"] for m in chat.per_layer]
    for m in burst.end_to_end + burst.per_layer:
        if "workloads" in m:
            assert "serve-chat-burst" in m["workloads"]
    with open(os.path.join(HERE, "limits", "serve-chat.json")) as f:
        want = json.load(f)
    with open(os.path.join(HERE, "limits", "serve-chat-burst.json")) as f:
        assert json.load(f) == want
