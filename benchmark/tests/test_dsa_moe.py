"""The cell ``serve-glm-long-reason``: its files agree with each other and
with the source, its arithmetic with hand counts, its reference with the
program's own plain reference (the forward pass is one text in both
places), and its ``correct`` can fail: the control and every planted fault
come out not correct by the comparison that passes the sound program (toy
sizes, ``--rehearse-cpu``)."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import flops_dsa_moe as F
from benchmark import harness, weights_dsa_moe
from benchmark import run as run_mod
from benchmark.references import dsa_moe_lm as ref

CELL = "serve-glm-long-reason"
HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
FLOOD = ("flood_occupancy_pct", "flood_decode_device_ms_p50",
         "flood_step_mfu_pct")


def _config():
    with open(os.path.join(HERE, "configs", "glm-5.2-serve.json")) as f:
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
    row = next(r for r in rows if r["name"] == "GLM-5.2")
    cfg = _config()
    assert cfg["source"] == row["source_url"]
    for key, want in row["config"].items():
        if key in cfg["reduced"]:
            assert cfg["published"][key] == want and cfg[key] != want
        else:
            assert cfg[key] == want, key
    assert sorted(cfg["reduced"]) == sorted(cfg["published"])
    # the lists are cut to the layers kept, in the published ratio
    assert cfg["mlp_layer_types"] == ["dense"] + ["sparse"] * 4
    assert cfg["indexer_types"] == ["full", "shared", "shared", "shared",
                                    "full"]
    assert cfg["indexer_types"] == row["config"]["indexer_types"][2:7]
    assert cfg["mlp_layer_types"] == row["config"]["mlp_layer_types"][2:7]


def test_cell_and_entries_agree():
    bench = harness.Benchmark()
    cell = bench.cell(CELL)
    assert cell.chips == 1 and cell.traffic["kind"] == "serve_sparse_latent"
    assert [m["name"] for m in cell.end_to_end] == ["out_tok_s", "setup_s"]
    names = [m["name"] for m in cell.per_layer]
    assert sorted(names) == sorted(FLOOD + (
        "glm_decode_roofline", "glm_indexer_device_pct",
        "glm_mla_device_pct", "glm_experts_device_pct", "glm_selected_pct"))
    for m in cell.per_layer:
        own = m["name"].startswith("glm_")
        assert m["workloads"][-1] == CELL
        assert (m["workloads"] == [CELL]) == own
        assert m["moves"] == "out_tok_s"
        bench.metric_file(m["name"])
    entry = next(c for c in bench.spec["configs"]
                 if c["name"] == cell.config_name)
    assert entry["reduced"] == cell.config["reduced"]
    eng, traffic = cell.config["engine"], cell.traffic
    assert traffic["prompt_len"]["min"] > eng["prefill_chunk"]
    assert eng["max_seq"] % eng["prefill_chunk"] == 0
    assert (traffic["prompt_len"]["max"]
            + traffic["output_len"]["max"]) <= eng["max_seq"]
    assert (traffic["trace_seed"], traffic["ramp_s"], traffic["drain"],
            traffic["check_requests"]) == (20261002, 60.0, "none", 3)
    assert traffic["prompt_len"] == {
        "dist": "int_lognormal", "median": 8192, "sigma": 0.5,
        "min": 4096, "max": 16384}
    assert traffic["output_len"] == {
        "dist": "int_lognormal", "median": 3072, "sigma": 0.5,
        "min": 1024, "max": 8192}


def test_hand_counts():
    m = _config()
    # ISSUE 31's arithmetic, from the row's config
    assert F.mla_params(m) == (6144 * 2048 + 2048 * 16384 + 6144 * 576
                               + 512 * 64 * 448 + 16384 * 6144) \
        == 165_019_648
    assert F.indexer_params(m) == (2048 * 4096 + 6144 * 128 + 6144 * 32) \
        == 9_371_648
    assert F.dense_ffn_params(m) == 3 * 6144 * 12288 == 226_492_416
    assert F.expert_params(m) == F.shared_params(m) == 3 * 6144 * 2048 \
        == 37_748_736
    assert F.router_params(m) == 6144 * 256 == 1_572_864
    dense = 165_019_648 + 9_371_648 + 226_492_416
    sparse = 165_019_648 + 1_572_864 + 37_748_736          # outside experts
    assert round(dense / 1e6, 1) == 400.9
    assert round(sparse / 1e6, 1) == 204.3
    assert round((sparse + 16 * 37_748_736) / 1e6, 1) == 808.3
    assert F.body_params(m) == dense + 4 * sparse + 9_371_648
    layers = F.body_params(m) + 4 * 16 * 37_748_736
    assert round(layers / 1e6, 1) == 3643.5
    assert round(2 * 19360 * 6144 / 1e6, 1) == 237.9
    assert round(F.weight_bytes(m) / 2 / 1e9, 2) == 3.88
    assert round(F.weight_bytes(m) / 1e9, 2) == 7.76
    # a cached token: 5 x 576 + 2 x 128 numbers; the program keeps a latent
    # in a row of 640
    assert F.cache_bytes_per_token(m) == 6272
    assert F.cache_bytes_per_token(m, latent_row=640) == 6912
    eng = m["engine"]
    assert eng["slots"] * eng["max_seq"] == 786_432
    assert round(786_432 * 6912 / 1e9, 2) == 5.44


def test_a_hand_worked_step():
    """32 busy lanes at 11,000 live positions each: five attentions read
    2,048 rows a lane, two indexers scan 11,001 keys a lane; 16 rows a
    layer fall on held experts and touch 10 of the 16."""
    m = _config()
    selected, scanned = 5 * 32 * 2048, 2 * 32 * 11_001
    nbytes = F.decode_step_bytes(m, selected, scanned, touched=4 * 10)
    assert nbytes == 2 * (F.non_expert_params(m) + 40 * 37_748_736
                          + selected * 576 + scanned * 128)
    # 2.69 GB of non-expert matrices, 3.0 GB of touched experts, 0.38 GB
    # of selected latents, 0.18 GB of index keys
    assert round(2 * F.non_expert_params(m) / 1e9, 2) == 2.69
    assert round(2 * selected * 576 / 1e9, 2) == 0.38
    assert round(2 * scanned * 128 / 1e9, 2) == 0.18
    ops = F.decode_step_flops(m, 32, selected, scanned, 4 * 16)
    assert ops == (2 * F.non_expert_params(m) * 32
                   + 2 * 64 * (2 * 512 + 64) * selected
                   + 2 * 32 * 129 * scanned + 2 * 37_748_736 * 64)
    # absent experts cost nothing; an idle step of no lane costs nothing
    assert F.decode_step_flops(m, 0, 0, 0, 0) == 0
    # a prompt of 8,192: 2,048 rows a position once past 2,048
    sel, scan = F.prefill_selected(m, 8192)
    assert sel == 5 * (2048 * 2049 // 2 + 6144 * 2048)
    assert scan == 2 * 8192 * 8193 // 2
    assert F.prefill_flops(m, 8192, 0) == (
        2 * F.body_params(m) * 8192 + F.attention_flops(m, sel, scan)
        + 2 * 6144 * 19360)


def test_weights_have_the_programs_tree_and_depend_on_the_seed_alone():
    from akka_allreduce_tpu.models.transformer import init_transformer
    from benchmark.runners.serve_sparse_latent import program_config
    model = _config()["rehearsal"]
    cfg = program_config(model, model["engine"])
    a = weights_dsa_moe.make_params(7, model, jnp.bfloat16)
    b = weights_dsa_moe.make_params(7, model, jnp.bfloat16)
    c = weights_dsa_moe.make_params(2 ** 31 + 7, model, jnp.bfloat16)
    theirs = init_transformer(jax.random.key(0), cfg)
    assert jax.tree.structure(a) == jax.tree.structure(theirs)
    assert jax.tree.map(lambda x: (x.shape, x.dtype), a) == jax.tree.map(
        lambda x: (x.shape, x.dtype), theirs)
    assert all(jax.tree.leaves(jax.tree.map(
        lambda x, y: bool((x == y).all()), a, b)))
    wo = [layer["mla"]["wo"] for layer in a["layers"]]
    assert not bool((wo[0] == c["layers"][0]["mla"]["wo"]).all())
    assert not bool((wo[0] == wo[1]).all())
    moe = a["layers"][1]["moe"]
    assert moe["router"].shape == (64, 16)          # the source's width
    assert moe["we1"].shape == (4, 64, 32)          # the share held
    assert moe["ws1"].shape == (64, 32)             # the shared expert
    assert ["indexer" in layer for layer in a["layers"]] == [
        True, False, False, False, True]


def test_the_reference_is_the_programs_plain_reference():
    """One text in two places: the forward pass of the program's plain
    reference and of the benchmark's are the same lines."""
    def between(path, start, end):
        with open(path) as f:
            text = f.read()
        return text[text.index(start):text.index(end)]
    mine = between(os.path.join(HERE, "references", "dsa_moe_lm.py"),
                   "FAULTS = (", "# -- end of the forward pass")
    theirs = between(os.path.join(
        harness.ROOT, "akka_allreduce_tpu", "models",
        "dsa_moe_reference.py"), "FAULTS = (", "# -- end of the forward pass")
    assert mine == theirs
    model = _config()["rehearsal"]
    params = weights_dsa_moe.make_params(11, model, jnp.float32)
    toks = jnp.asarray(np.arange(40) * 7 % model["vocab_size"], jnp.int32)
    sound = ref.forward(params, toks, model)
    for fault in ref.FAULTS:
        broken = ref.forward(params, toks, model, faults=(fault,))
        assert float(jnp.abs(broken - sound)[20:].max()) > 0.05, fault


def test_readers_find_nothing_where_the_program_marks_nothing():
    """A parent that lacks the counts: the new reader returns None and does
    not raise."""
    from benchmark import readers
    bench = harness.Benchmark()
    cell = bench.cell("serve-flood")
    run = harness.Run(cell, "TPU v5 lite", 45.0, 30.0, counters={
        "busy_lane_steps": 10, "lane_steps": 10}, steps=[
        {"t0": 0.0, "t1": 1.0, "occupied": 1, "live_positions": 5}],
        trace_span=(0.0, 2.0), model=cell.config)
    assert readers.get("sparse_latent_decode_roofline_pct")(
        run, pattern="jit_") is None
    assert readers.get("counter_ratio_pct")(
        run, num="index_selected", den="attention_live_rows") is None


# -- correct can fail --------------------------------------------------------

def test_sound_run_is_correct(capsys):
    r = _run(capsys)
    assert r["correct"] is True and r["rehearsal"] is True, _failed(r)
    assert r["failed"] == 0 and r["metrics"] == {}
    assert {"served_gap", "moe_out_gap", "expert_out_gap", "index_miss",
            "held_part_gap", "selection_part_gap", "replay_compiles"} <= set(
        r["compared"])


def test_control_and_every_fault_come_out_not_correct(capsys):
    r = _run(capsys, extra=("--control", "fp8"))
    assert r["correct"] is True
    assert r["control_correct"] is False
    for fault in ref.FAULTS:
        assert r[f"fault.{fault}_correct"] is False, fault
    failed = _failed(r)
    assert "fault.no_held.expert_out_gap" in failed
    assert r["compared"]["fault.no_held.held_part_gap"]["value"] == 1.0
    assert "fault.no_selection.selection_part_gap" in failed
    assert r["compared"]["fault.no_selection.selection_part_gap"][
        "value"] == 1.0
    assert "fault.no_relu.index_miss" in failed
    for k in failed:      # held to the very limit the program is held to
        pre, _, name = k.rpartition(".")
        assert pre and r["compared"][k]["limit"] == \
            r["compared"][name]["limit"]


def test_fault_token_altered(capsys):
    def plant(drv):
        drv.alter = lambda rid, toks: [(t + 1) % 256 for t in toks]
    r = _run(capsys, plant=plant)
    assert r["correct"] is False and "served_gap" in _failed(r)


def test_fault_in_the_served_paths_selection(capsys, monkeypatch):
    """The timed path broken underneath: the engine's own programs, traced
    anew, attend the most recent positions in the chosen ones' place. The
    number that comes from those programs says so."""
    from akka_allreduce_tpu.models import generate as G
    from akka_allreduce_tpu.serving import engine
    real = G._selected_latent_attention

    def recent(q, latent, a, lanes, chosen, positions, rank, scale):
        k = chosen.shape[-1]
        last = jnp.maximum(positions[..., None] - jnp.arange(k), 0)
        return real(q, latent, a, lanes, last.astype(chosen.dtype),
                    positions, rank, scale)
    monkeypatch.setattr(G, "_selected_latent_attention", recent)
    programs = (engine._engine_step, engine._engine_prefill_chunk)
    for f in programs:
        f.clear_cache()
    try:
        r = _run(capsys)
    finally:
        for f in programs:     # the next test traces the sound path again
            f.clear_cache()
    assert r["correct"] is False
    assert "selection_part_gap" in _failed(r)
