"""The data-driven part: what ``BENCHMARK.json`` names is found by name.

A cell names a configuration and a traffic mix; each is one file
(``configs/<name>.json``, ``traffic/<name>.json``). A metric is one file
(``metrics/<name>.json``) that names a reader registered in ``readers/``
and its arguments. The traffic file's ``kind`` names the runner
(``runners/<kind>.py``); the configuration's ``reference`` names its plain
reference (``references/<name>.py``). Adding a cell, a mix, a
configuration or a metric over an existing source is adding files and
entries: nothing here knows any of their names.
"""

from __future__ import annotations

import dataclasses
import importlib
import json
import os
import re
import sys
from typing import Optional

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.dirname(os.path.abspath(__file__))

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


class BenchmarkError(Exception):
    """The benchmark's files contradict each other or the contract."""


def check_name(name: str, what: str = "name") -> str:
    if not isinstance(name, str) or not NAME_RE.match(name):
        raise BenchmarkError(
            f"{what} {name!r}: 1-64 of letters, digits, '_', '.', '-', "
            f"starting with a letter, a digit or '_'")
    return name


def check_unit(unit: str) -> str:
    if not isinstance(unit, str) or not UNIT_RE.match(unit):
        raise BenchmarkError(
            f"unit {unit!r}: 1-16 of letters, digits, '_', '/', '%', '.', "
            f"'-', no space")
    return unit


def _load(path: str) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise BenchmarkError(f"missing file {path}") from None


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    traffic_name: str
    config: dict
    traffic: dict
    end_to_end: list        # metric entries of BENCHMARK.json, this cell's
    per_layer: list


class Benchmark:
    """``BENCHMARK.json`` of ``root`` with the files under ``bench_dir``."""

    def __init__(self, root: str = ROOT, bench_dir: Optional[str] = None):
        self.root = root
        self.spec = _load(os.path.join(root, "BENCHMARK.json"))
        self.dir = bench_dir or os.path.join(root, self.spec["paths"][0])
        self._validate()

    def _validate(self) -> None:
        s = self.spec
        seen = set()
        for group in ("configs", "workloads", "end_to_end", "per_layer"):
            for e in s[group]:
                check_name(e["name"], f"{group} name")
                if (group, e["name"]) in seen:
                    raise BenchmarkError(f"{group}: {e['name']} twice")
                seen.add((group, e["name"]))
        for w in s["workloads"]:
            check_name(w["config"], "config")
            check_name(w["traffic"], "traffic")
            if w["chips"] not in (1, 4):
                raise BenchmarkError(f"{w['name']}: chips must be 1 or 4")
        e2e = {m["name"] for m in s["end_to_end"]}
        for m in s["end_to_end"] + s["per_layer"]:
            check_unit(m["unit"])
            if m["better"] not in ("lower", "higher"):
                raise BenchmarkError(f"{m['name']}: better={m['better']!r}")
            if m["source"] not in SOURCES:
                raise BenchmarkError(f"{m['name']}: source {m['source']!r}")
        for m in s["per_layer"]:
            if m["moves"] not in e2e:
                raise BenchmarkError(
                    f"{m['name']}: moves {m['moves']!r}, no such "
                    f"end-to-end metric")

    def metrics_of(self, group: str, cell_name: str) -> list:
        """The metrics of ``group`` that ``cell_name`` reports: those that
        list it under ``workloads``; one without the key is reported by
        every cell (end-to-end) or by every cell that reports the metric
        it moves (per-layer)."""
        e2e_here = {m["name"] for m in self.spec["end_to_end"]
                    if cell_name in m.get("workloads", [cell_name])}
        out = []
        for m in self.spec[group]:
            if "workloads" in m:
                if cell_name in m["workloads"]:
                    out.append(m)
            elif group == "end_to_end" or m["moves"] in e2e_here:
                out.append(m)
        return out

    def cell(self, name: str) -> Cell:
        for w in self.spec["workloads"]:
            if w["name"] == name:
                break
        else:
            raise BenchmarkError(
                f"no workload {name!r} in BENCHMARK.json (has: "
                f"{', '.join(w['name'] for w in self.spec['workloads'])})")
        files = {c["name"]: c["file"] for c in self.spec["configs"]}
        if w["config"] not in files:
            raise BenchmarkError(f"{name}: no config {w['config']!r}")
        config = _load(os.path.join(self.root, files[w["config"]]))
        traffic = _load(os.path.join(self.dir, "traffic",
                                     w["traffic"] + ".json"))
        return Cell(name, w["chips"], w["config"], w["traffic"], config,
                    traffic, self.metrics_of("end_to_end", name),
                    self.metrics_of("per_layer", name))

    def metric_file(self, name: str) -> dict:
        return _load(os.path.join(self.dir, "metrics", name + ".json"))

    def runner(self, kind: str):
        check_name(kind, "traffic kind")
        return importlib.import_module(f"benchmark.runners.{kind}")

    def reference(self, name: str):
        check_name(name, "reference")
        return importlib.import_module(f"benchmark.references.{name}")


# -- what a run hands to the readers --------------------------------------

@dataclasses.dataclass
class Run:
    """One run's raw material. ``series`` are per-sample lists on the
    benchmark's clock, ``counters`` plain counts, ``steps`` one record per
    call into the program's step (dicts with ``t0``, ``t1`` on the window's
    clock and whatever the runner counts), ``trace`` the reduced device
    trace of the traced part of the window (``--trace 1`` only)."""
    cell: Cell
    device_kind: str
    window_s: float
    setup_s: float
    series: dict = dataclasses.field(default_factory=dict)
    counters: dict = dataclasses.field(default_factory=dict)
    steps: list = dataclasses.field(default_factory=list)
    trace: Optional[object] = None
    trace_span: Optional[tuple] = None   # (t0, t1) on the window's clock
    model: Optional[dict] = None         # the configuration's sizes as run
    rows: int = 0                        # training: sequences a step
    seq: int = 0                         # training: tokens a sequence


def percentile(values, q: float) -> Optional[float]:
    """Nearest-rank-interpolated percentile (numpy's default), or None."""
    if not values:
        return None
    v = sorted(values)
    pos = (len(v) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (pos - lo)


def read_metrics(bench: Benchmark, run: Run, entries: list,
                 rehearsal: bool = False) -> dict:
    """Each entry's reader, by the name in its metric file. A reader that
    finds nothing to read returns None and the metric is left out. Off the
    chip (a rehearsal) a share of a peak has no peak to stand on and is
    left out too; on the chip an unknown device is an error."""
    from benchmark import flops, readers
    out = {}
    for m in entries:
        spec = bench.metric_file(m["name"])
        fn = readers.get(spec["reader"])
        try:
            value = fn(run, **spec.get("args", {}))
        except flops.UnknownDevice:
            if not rehearsal:
                raise
            print(f"rehearsal: {m['name']} needs the chip's peaks; left out")
            continue
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def emit(result: dict, compared: dict) -> None:
    """The last lines: every number compared beside its limit on standard
    error, and the result as one JSON line, ``compared`` last in it."""
    for name, c in compared.items():
        print(f"compared {name}: {c['value']!r} limit {c['limit']!r} "
              f"{'ok' if c['ok'] else 'FAIL'}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps({**result, "compared": compared}))
    sys.stdout.flush()
