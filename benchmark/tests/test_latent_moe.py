"""The cell ``serve-longcat-reason``: its files agree with each other and
with the source, its arithmetic with hand counts, its reference with the
program's own plain reference, and its ``correct`` can fail: the control
and every planted fault come out not correct by the comparison that passes
the sound program (toy sizes, ``--rehearse-cpu``)."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import flops_scmoe_mla as F
from benchmark import harness, weights_scmoe_mla
from benchmark import run as run_mod
from benchmark.references import scmoe_mla_lm as ref

CELL = "serve-longcat-reason"
HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def _config():
    with open(os.path.join(HERE, "configs",
                           "longcat-flash-chat-serve.json")) as f:
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
    row = next(r for r in rows if r["name"] == "LongCat-Flash-Chat")
    cfg = _config()
    assert cfg["source"] == row["source_url"]
    for key, want in row["config"].items():
        if key in cfg["reduced"]:
            assert cfg["published"][key] == want and cfg[key] != want
        else:
            assert cfg[key] == want, key
    assert sorted(cfg["reduced"]) == sorted(cfg["published"])


def test_cell_and_entries_agree():
    bench = harness.Benchmark()
    cell = bench.cell(CELL)
    assert cell.chips == 1 and cell.traffic["kind"] == "serve_latent_moe"
    assert [m["name"] for m in cell.end_to_end] == ["out_tok_s", "setup_s"]
    assert len(cell.per_layer) == 8
    for m in cell.per_layer:
        # three are the flood cell's own entries, read the same way here
        own = m["name"].startswith("lcr_")
        assert m["workloads"] == ([CELL] if own else ["serve-flood", CELL])
        assert m["moves"] == "out_tok_s"
        bench.metric_file(m["name"])
    entry = next(c for c in bench.spec["configs"]
                 if c["name"] == cell.config_name)
    assert entry["reduced"] == cell.config["reduced"]
    eng = cell.config["engine"]
    assert cell.traffic["prompt_len"]["max"] <= eng["prefill_buckets"][-1]
    assert (cell.traffic["prompt_len"]["max"]
            + cell.traffic["output_len"]["max"]) <= eng["max_seq"]


def test_hand_counts():
    m = _config()
    # ISSUE 26's arithmetic, from the row's config
    assert F.mla_params(m) == (6144 * 1536 + 1536 * 12288 + 6144 * 576
                               + 512 * 16384 + 8192 * 6144) == 90_570_752
    assert F.expert_params(m) == 3 * 6144 * 2048 == 37_748_736
    layer = 2 * 90_570_752 + 2 * 3 * 6144 * 12288 + 6144 * 768
    assert round(layer / 1e6, 1) == 638.8
    assert F.non_expert_params(m) == 4 * layer + 6144 * 16384
    assert round(F.weight_bytes(m) / 1e9, 2) == 10.35
    # a step of 128 busy lanes, 32 rows a layer, 14 of 16 experts touched
    nbytes = F.decode_step_bytes(m, 128 * 700, 4 * 14)
    assert nbytes == 2 * (F.non_expert_params(m) + 56 * 37_748_736
                          + 128 * 700 * 8 * 576)
    ops = F.decode_step_flops(m, 128, 128 * 700, 4 * 32)
    assert ops == (2 * F.non_expert_params(m) * 128
                   + 8 * 2 * 64 * (2 * 512 + 64) * 128 * 700
                   + 2 * 37_748_736 * 128)
    # identity experts and absent experts cost nothing
    assert F.decode_step_flops(m, 128, 0, 0) == 2 * F.non_expert_params(
        m) * 128
    assert F.prefill_flops(m, 1, 0) > 2 * F.non_expert_params(m) - 1


def test_weights_have_the_programs_tree_and_depend_on_the_seed_alone():
    from akka_allreduce_tpu.models.transformer import init_transformer
    from benchmark.runners.serve_latent_moe import program_config
    model = _config()["rehearsal"]
    cfg = program_config(model, model["engine"])
    a = weights_scmoe_mla.make_params(7, model, jnp.bfloat16)
    b = weights_scmoe_mla.make_params(7, model, jnp.bfloat16)
    c = weights_scmoe_mla.make_params(2 ** 31 + 7, model, jnp.bfloat16)
    assert jax.tree.structure(a) == jax.tree.structure(
        init_transformer(jax.random.key(0), cfg))
    shapes = jax.tree.map(lambda x: x.shape, a)
    assert shapes == jax.tree.map(
        lambda x: x.shape, init_transformer(jax.random.key(0), cfg))
    assert all(jax.tree.leaves(jax.tree.map(
        lambda x, y: bool((x == y).all()), a, b)))
    assert not bool((a["layers"][0]["mla"][0]["wo"]
                     == c["layers"][0]["mla"][0]["wo"]).all())
    assert not bool((a["layers"][0]["mla"][0]["wo"]
                     == a["layers"][0]["mla"][1]["wo"]).all())
    moe = a["layers"][1]["moe"]
    assert moe["router"].shape == (64, 16 + 8)      # the source's width
    assert moe["we1"].shape == (4, 64, 32)          # the share held


def test_the_reference_is_the_programs_plain_reference():
    """Two plain references, written apart (the benchmark's imports
    nothing from the program), give one answer."""
    from akka_allreduce_tpu.models import scmoe_reference as theirs
    from benchmark.runners.serve_latent_moe import program_config
    model = _config()["rehearsal"]
    cfg = program_config(model, model["engine"])
    params = weights_scmoe_mla.make_params(11, model, jnp.float32)
    toks = jnp.asarray(np.arange(24) * 7 % model["vocab_size"], jnp.int32)
    mine = ref.forward(params, toks, model)
    want, _counts = theirs.forward(params, toks, cfg)
    assert float(jnp.abs(mine - want).max()) < 1e-4
    for fault in ref.FAULTS:
        broken = ref.forward(params, toks, model, faults=(fault,))
        assert float(jnp.abs(broken - mine).max()) > 0.05, fault


def test_readers_find_nothing_where_the_program_marks_nothing():
    """A parent that lacks the scopes and the counters: each new reader
    returns None and does not raise."""
    from benchmark import readers
    bench = harness.Benchmark()
    cell = bench.cell("serve-flood")
    run = harness.Run(cell, "TPU v5 lite", 45.0, 30.0, counters={
        "busy_lane_steps": 10, "lane_steps": 10}, steps=[
        {"t0": 0.0, "t1": 1.0, "occupied": 1, "live_positions": 5}],
        trace_span=(0.0, 2.0), model=cell.config)
    for name in ("counter_ratio", "latent_moe_decode_roofline_pct",
                 "scopes_share_of_busy_pct"):
        spec = {"counter_ratio": {"num": "route_held", "den": "x"},
                "latent_moe_decode_roofline_pct": {"pattern": "jit_"},
                "scopes_share_of_busy_pct": {"scopes": ["moe_experts"]}}
        assert readers.get(name)(run, **spec[name]) is None


# -- correct can fail --------------------------------------------------------

def test_sound_run_is_correct(capsys):
    r = _run(capsys)
    assert r["correct"] is True and r["rehearsal"] is True, _failed(r)
    assert r["failed"] == 0 and r["metrics"] == {}
    assert {"served_gap", "moe_out_gap", "expert_out_gap", "held_part_gap",
            "replay_compiles"} <= set(r["compared"])


def test_control_and_every_fault_come_out_not_correct(capsys):
    r = _run(capsys, extra=("--control", "fp8"))
    assert r["correct"] is True
    assert r["control_correct"] is False
    for fault in ref.FAULTS:
        assert r[f"fault.{fault}_correct"] is False, fault
    failed = _failed(r)
    # the held experts carry a fraction of the routed mass: the numbers
    # that see them are the expert layer's own and the logits' share of
    # the held part
    assert "fault.no_held.expert_out_gap" in failed
    assert r["compared"]["fault.no_held.held_part_gap"]["value"] == 1.0
    for k in failed:      # held to the very limit the program is held to
        pre, _, name = k.rpartition(".")
        assert pre and r["compared"][k]["limit"] == \
            r["compared"][name]["limit"]


def test_fault_token_altered(capsys):
    def plant(drv):
        drv.alter = lambda rid, toks: [(t + 1) % 256 for t in toks]
    r = _run(capsys, plant=plant)
    assert r["correct"] is False and _failed(r) == ["served_gap"]


@pytest.mark.parametrize("factor, reads", [(0.0, 1.0), (0.5, 0.5)])
def test_fault_in_the_served_paths_expert_layer(capsys, monkeypatch,
                                                factor, reads):
    """The timed path broken underneath: the engine's own programs, traced
    anew, leave the held experts' part out (or halve it). The number that
    comes from those programs says so, and by how much."""
    from akka_allreduce_tpu.parallel import ep
    from akka_allreduce_tpu.serving import engine
    real = ep.held_experts_ffn
    monkeypatch.setattr(ep, "held_experts_ffn",
                        lambda *a, **k: factor * real(*a, **k))
    programs = (engine._engine_step, engine._engine_prefill)
    for f in programs:
        f.clear_cache()
    try:
        r = _run(capsys)
    finally:
        for f in programs:     # the next test traces the sound layer again
            f.clear_cache()
    assert r["correct"] is False
    assert {"held_part_gap", "expert_out_gap"} <= set(_failed(r))
    assert abs(r["compared"]["held_part_gap"]["value"] - reads) < 0.1
