"""The cell ``train-glm-flash-8k``: its files agree with each other and with
the source, its arithmetic with hand counts, its reference with the
program's own plain reference (the loss is one text in both places), and
its ``correct`` can fail: the control and both planted faults come out not
correct by the comparison that passes the sound program (toy sizes,
``--rehearse-cpu``)."""

import json
import os

import jax
import jax.numpy as jnp

from benchmark import flops_mla_moe_mtp as F
from benchmark import harness, weights_mla_moe_mtp
from benchmark import run as run_mod

CELL = "train-glm-flash-8k"
HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# the published GLM-4.7-Flash config.json and its source, as released
PUBLISHED = os.path.join(HERE, "tests", "data", "glm-4.7-flash-published.json")


def _config():
    with open(os.path.join(HERE, "configs", "glm-4.7-flash-train.json")) as f:
        return json.load(f)


def _run(capsys, extra=()):
    rc = run_mod.main(["--workload", CELL, "--seed", "2147483659",
                       "--seconds", "1", "--trace", "0", "--rehearse-cpu",
                       *extra])
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


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
    assert cfg["experts_held"] == [0, cfg["n_routed_experts"]]


def test_cell_and_entries_agree():
    bench = harness.Benchmark()
    cell = bench.cell(CELL)
    assert cell.chips == 1 and cell.traffic["kind"] == "train_latent_moe"
    assert [m["name"] for m in cell.end_to_end] == ["train_tok_s", "setup_s"]
    names = sorted(m["name"] for m in cell.per_layer)
    assert names == sorted([
        "train_step_mfu_pct", "train_host_gap_ms_p50", "gfl_mla_device_pct",
        "gfl_experts_device_pct", "gfl_mtp_device_pct", "gfl_attn_roofline",
        "gfl_rows_per_expert"])
    for m in cell.per_layer:
        assert m["moves"] == "train_tok_s" and m["workloads"][-1] == CELL
        bench.metric_file(m["name"])
    entry = next(c for c in bench.spec["configs"]
                 if c["name"] == cell.config_name)
    assert entry["reduced"] == cell.config["reduced"]
    assert (cell.traffic["seq"], cell.traffic["rows_per_chip"]) == (8192, 1)


def test_hand_counts():
    """The parameter table of the cut, from the configuration's numbers."""
    m = _config()
    assert F.mla_params(m) == (2048 * 768 + 768 * 20 * 256 + 2048 * 576
                               + 512 * 20 * 448 + 20 * 256 * 2048) \
        == 21_757_952
    assert round((F.mla_params(m) + F.dense_ffn_params(m)) / 1e6, 2) == 84.67
    assert F.expert_params(m) == F.shared_params(m) == 9_437_184
    assert F.router_params(m) == 2048 * 64
    assert round(F.sparse_outside_params(m) / 1e6, 2) == 31.33
    params = jax.eval_shape(lambda: weights_mla_moe_mtp.make_params(
        1, m, jnp.float32))

    def count(tree):
        return sum(x.size for x in jax.tree.leaves(tree))
    # the tree's counts hold the norms' gains too
    assert round(count(params["layers"][0]) / 1e6, 2) == 84.68
    assert round(count(params["layers"][1]) / 1e6, 2) == 106.83
    assert round(count(params["mtp"]) / 1e6, 2) == 115.22
    assert round((count(params["embed"]) + count(params["lm_head"])) / 1e6,
                 2) == 79.30
    assert round(count(params) / 1e6, 1) == 706.5
    assert round(16 * count(params) / 1e9, 2) == 11.30
    # every weight of a token's path but the routed experts, both heads
    assert F.matmul_params(m) == (
        F.mla_params(m) + F.dense_ffn_params(m)
        + 4 * F.sparse_outside_params(m) + F.mtp_outside_params(m)
        + 2 * 2048 * 19360)
    assert F.n_expert_layers(m) == 5 and F.n_attentions(m) == 6
    # 8,192 tokens x top-4 x 8/64 held: 512 rows a held expert a layer
    assert 8192 * 4 * 8 // 64 // 8 == 512


def test_a_hand_worked_step():
    m = _config()
    pairs = 8192 * 8193 / 2
    attn = 6 * 2 * 20 * (256 + 256) * pairs
    assert F.attention_flops(m, 8192, 1) == attn
    held = 5 * 8192 * 4 * 8 / 64
    assert F.train_step_flops(m, 8192, 1, held) == 3 * (
        2 * F.matmul_params(m) * 8192 + attn + 2 * 9_437_184 * held)
    # the kernel: q, k of 20 x 256, v, o of 20 x 256, bf16, six attentions
    assert F.flash_attention_bytes(m, 8192, 1) == 8192 * 6 * 12 * 5120 * 2
    assert F.flash_attention_flops(m, 8192, 1) == 3 * attn


def test_weights_have_the_programs_tree_and_depend_on_the_seed_alone():
    from akka_allreduce_tpu.models.transformer import (config_from_hf,
                                                       init_transformer)
    from benchmark.runners.train_latent_moe import program_model
    model = _config()["rehearsal"]
    cfg = config_from_hf(program_model(model), 64, jnp.float32)
    a = weights_mla_moe_mtp.make_params(7, model, jnp.float32)
    b = weights_mla_moe_mtp.make_params(7, model, jnp.float32)
    c = weights_mla_moe_mtp.make_params(2 ** 31 + 7, model, jnp.float32)
    theirs = init_transformer(jax.random.key(0), cfg)
    assert jax.tree.map(lambda x: (x.shape, x.dtype), a) == jax.tree.map(
        lambda x: (x.shape, x.dtype), theirs)
    assert all(jax.tree.leaves(jax.tree.map(
        lambda x, y: bool((x == y).all()), a, b)))
    assert not bool((a["mtp"]["layer"]["mla"]["wo"]
                     == c["mtp"]["layer"]["mla"]["wo"]).all())
    assert a["layers"][1]["moe"]["router"].shape == (64, 8)
    assert a["layers"][1]["moe"]["we1"].shape == (4, 64, 32)
    zero = weights_mla_moe_mtp.delta_norms(a, 7, model)
    assert max(jax.tree.leaves(zero)) == 0.0


def test_the_reference_is_the_programs_plain_reference():
    """One text in two places: the loss of the program's plain reference
    and of the benchmark's are the same lines."""
    def between(path):
        with open(path) as f:
            text = f.read()
        return text[text.index("Q_BLOCK = "):text.index("# -- end of the "
                                                          "loss")]
    mine = between(os.path.join(HERE, "references", "mla_moe_mtp_lm.py"))
    theirs = between(os.path.join(harness.ROOT, "akka_allreduce_tpu",
                                  "models", "mla_moe_mtp_reference.py"))
    assert mine == theirs


def test_readers_find_nothing_where_the_program_marks_nothing():
    """A parent that lacks the scopes and the counters: the new metrics
    read None and do not raise."""
    from benchmark import readers
    bench = harness.Benchmark()
    cell = bench.cell(CELL)
    run = harness.Run(cell, "TPU v5 lite", 45.0, 30.0, counters={
        "steps": 3}, steps=[{"t0": 0.0, "t1": 1.0}], trace_span=(0.0, 2.0),
        model=cell.config, rows=1, seq=8192)
    run.program = None
    for m in cell.per_layer:
        spec = bench.metric_file(m["name"])
        if m["name"].startswith("gfl_"):
            assert readers.get(spec["reader"])(
                run, **spec.get("args", {})) is None, m["name"]


# -- correct can fail --------------------------------------------------------

def test_sound_run_is_correct(capsys):
    r = _run(capsys)
    assert r["correct"] is True and r["rehearsal"] is True
    assert "rehearsal.train_tok_s" in r["rehearsal_readings"]


def test_control_and_both_faults_come_out_not_correct(capsys):
    r = _run(capsys, ("--control", "fp8"))
    assert r["correct"] is True
    for pre in ("control", "fault.half_batch", "fault.no_mtp"):
        assert r[pre + "_correct"] is False, pre
