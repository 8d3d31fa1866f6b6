"""The harness is driven by data: a cell, a traffic mix, a configuration
and a metric are added as files plus entries, with no edit to a file that
is there; names and units the driver refuses are rejected."""

import json
import os
import shutil

import pytest

from benchmark import harness, loadgen


@pytest.fixture()
def copy(tmp_path):
    """BENCHMARK.json and the benchmark's data files in a temp root."""
    root = tmp_path / "root"
    (root / "benchmark").mkdir(parents=True)
    shutil.copy(os.path.join(harness.ROOT, "BENCHMARK.json"), root)
    for d in ("configs", "traffic", "metrics", "limits"):
        shutil.copytree(os.path.join(harness.HERE, d),
                        root / "benchmark" / d)
    return root


def _digest(root):
    out = {}
    for base, _dirs, files in os.walk(root / "benchmark"):
        for f in files:
            p = os.path.join(base, f)
            out[p] = open(p, "rb").read()
    return out


def test_added_files_are_found_by_name(copy):
    before = _digest(copy)
    spec = json.load(open(copy / "BENCHMARK.json"))
    cfg = json.load(open(copy / "benchmark/configs/mistral-7b-v0.1-serve.json"))
    cfg["engine"]["slots"] = 8
    json.dump(cfg, open(copy / "benchmark/configs/new-model.json", "w"))
    json.dump({"kind": "serve", "rate_per_s": 2.0, "trace_seed": 5,
               "arrival": "burst", "burst_period_s": 4.0,
               "burst_length_s": 0.5, "burst_multiplier": 4.0,
               "prompt_len": {"median": 64, "sigma": 0.5, "min": 8,
                              "max": 128},
               "output_len": {"median": 32, "sigma": 0.5, "min": 4,
                              "max": 64}},
              open(copy / "benchmark/traffic/bursty.json", "w"))
    json.dump({"reader": "series_percentile",
               "args": {"series": "gap_ms", "q": 99}},
              open(copy / "benchmark/metrics/gap_p99_ms.json", "w"))
    spec["configs"].append({"name": "new-model", "source": "https://x/y",
                            "file": "benchmark/configs/new-model.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "new-cell", "config": "new-model",
                              "traffic": "bursty", "chips": 1,
                              "why": "test"})
    spec["per_layer"].append({"name": "gap_p99_ms", "unit": "ms",
                              "better": "lower", "source": "host_clock",
                              "layer": "service, client view",
                              "moves": "gap_p95_ms",
                              "workloads": ["new-cell"]})
    for m in spec["end_to_end"]:
        if m["name"] == "gap_p95_ms":
            m["workloads"].append("new-cell")
    json.dump(spec, open(copy / "BENCHMARK.json", "w"))

    bench = harness.Benchmark(root=str(copy))
    cell = bench.cell("new-cell")
    assert cell.config["engine"]["slots"] == 8
    assert cell.traffic["arrival"] == "burst"
    assert [m["name"] for m in cell.per_layer] == ["gap_p99_ms"]
    assert {m["name"] for m in cell.end_to_end} == {"gap_p95_ms", "setup_s"}
    run = harness.Run(cell, "TPU v5 lite", 10.0, 1.0,
                      series={"gap_ms": [float(i) for i in range(101)]})
    got = harness.read_metrics(bench, run, cell.per_layer)
    assert got == {"gap_p99_ms": {"value": 99.0, "unit": "ms"}}
    # the general generator reads the new mix; bursts keep the mean rate
    trace = loadgen.serve_trace(cell.traffic, 400.0)
    assert 1.7 < len(trace) / 400.0 < 2.3
    # nothing that was there was touched
    after = _digest(copy)
    assert all(after[p] == b for p, b in before.items())
    # the old cells are as they were
    assert bench.cell("serve-chat").traffic["kind"] == "serve"


def test_every_committed_cell_resolves():
    bench = harness.Benchmark()
    for w in bench.spec["workloads"]:
        cell = bench.cell(w["name"])
        bench.runner(cell.traffic["kind"])
        bench.reference(cell.config["reference"])
        assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
        assert len(cell.end_to_end) >= 2 and cell.per_layer
        for m in cell.end_to_end + cell.per_layer:
            from benchmark import readers
            readers.get(bench.metric_file(m["name"])["reader"])
        for m in cell.per_layer:
            assert m["moves"] in {e["name"] for e in cell.end_to_end}
        limits = json.load(open(os.path.join(
            bench.dir, "limits", w["name"] + ".json")))
        assert limits["limits"] and limits["rehearsal"]


@pytest.mark.parametrize("bad", ["tokens per s", "a,b", "a/b", "µs", "",
                                 "-lead", "x" * 65])
def test_refused_names(bad):
    with pytest.raises(harness.BenchmarkError):
        harness.check_name(bad)


@pytest.mark.parametrize("bad", ["tokens per second", "µs", "", "a,b",
                                 "x" * 17])
def test_refused_units(bad):
    with pytest.raises(harness.BenchmarkError):
        harness.check_unit(bad)


@pytest.mark.parametrize("good", ["tokens/s", "ms", "%", "s", "GB/s"])
def test_accepted_units(good):
    assert harness.check_unit(good) == good


def test_bad_entry_is_refused(copy):
    spec = json.load(open(copy / "BENCHMARK.json"))
    spec["per_layer"][0]["moves"] = "no_such_metric"
    json.dump(spec, open(copy / "BENCHMARK.json", "w"))
    with pytest.raises(harness.BenchmarkError):
        harness.Benchmark(root=str(copy))


def test_contract_shape_of_benchmark_json():
    spec = harness.Benchmark().spec
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    four = [w for w in spec["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(spec["workloads"]) // 4)
    for m in spec["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in spec["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    for c in spec["configs"]:
        assert os.path.exists(os.path.join(harness.ROOT, c["file"]))
        assert len(c["why"]) <= 200 and len(c["source"]) <= 200
    for w in spec["workloads"]:
        assert len(w["why"]) <= 200, (w["name"], len(w["why"]))


def test_peaks_refuse_unknown_device():
    from benchmark import flops
    assert flops.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(flops.UnknownDevice):
        flops.peaks("TPU v5")
    with pytest.raises(flops.UnknownDevice):
        flops.peaks("cpu")


def test_configs_state_what_runs():
    """``reduced`` in BENCHMARK.json is the file's own list; every key it
    names is in the file; a training configuration's token batch is the
    one its cells' traffic feeds; a departure that is no cut of size is
    named with its cause."""
    bench = harness.Benchmark()
    for c in bench.spec["configs"]:
        cfg = json.load(open(os.path.join(harness.ROOT, c["file"])))
        assert c["reduced"] == cfg["reduced"], c["name"]
        assert c["source"] == cfg["source"]
        for key in c["reduced"]:
            assert key in cfg, (c["name"], key)
            assert key in cfg["published"] or key in cfg["asked"]
        for key in cfg.get("departures", {}):
            assert key in c["reduced"]
    for w in bench.spec["workloads"]:
        cell = bench.cell(w["name"])
        if "tokens_per_chip_step" in cell.config:
            assert cell.config["tokens_per_chip_step"] == (
                cell.traffic["rows_per_chip"] * cell.traffic["seq"])
