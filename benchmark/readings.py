#!/usr/bin/env python3
"""The readings the limits of ``correct`` are set from: the cell's own
comparison over many seeds in one process (set-up is long, the programs
compile once), with the control - the reference in the precision below
the configuration's - and the planted faults put in the program's place
on some of them, each judged by the same comparison.

    python3 benchmark/readings.py --workload train-1chip --seeds 12 \
        --control-seeds 3 --seconds 2

Prints one JSON line a seed (every number read; ``control.*`` and
``fault.*`` with their verdicts, ``control_correct`` and so on, where
asked for) and a summary: for each number the largest reading of the
program (the lower reading) and the smallest of the control and of each
fault; then, for each stand-in, on how many seeds it came out not correct
and through which numbers. Exits 1 if the program came out not correct, or
a control or a fault correct, on any seed. Not run by the benchmark's own
runs.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", type=int, default=12)
    p.add_argument("--first-seed", type=int, default=3_000_000_019)
    p.add_argument("--control-seeds", type=int, default=3)
    p.add_argument("--control", default="fp8")
    p.add_argument("--seconds", type=float, default=2.0)
    p.add_argument("--rehearse-cpu", action="store_true")
    p.add_argument("--set-traffic", action="append", default=[])
    args = p.parse_args(argv)

    from benchmark import harness, run as run_mod
    bench = harness.Benchmark()
    cell = bench.cell(args.workload)
    run_mod.setup_jax(args.rehearse_cpu, cell.chips)
    runner = bench.runner(cell.traffic["kind"])
    traffic = run_mod.traffic_of(cell, args.rehearse_cpu, args.set_traffic)
    rows, verdicts = [], {}
    for i in range(args.seeds):
        seed = args.first_seed + 7_919 * i
        ctx = run_mod.Context(
            bench, cell, seed, args.seconds, False, args.rehearse_cpu,
            time.perf_counter(), traffic,
            args.control if i < args.control_seeds else None)
        out = runner.run(ctx)
        row = {**out.get("numbers", {}),
               **{k: v["value"] for k, v in out["compared"].items()}}
        ok = all(c["ok"] for c in out["compared"].values())
        said = {"correct": ok}
        verdicts.setdefault("program", []).append(
            (seed, ok, [k for k, c in out["compared"].items()
                        if not c["ok"]]))
        for pre, other in out.get("stand_ins", {}).items():
            row.update({f"{pre}.{k}": v
                        for k, v in other["numbers"].items()})
            said[pre + "_correct"] = other["correct"]
            verdicts.setdefault(pre, []).append(
                (seed, other["correct"],
                 [k for k, c in other["compared"].items() if not c["ok"]]))
        rows.append(row)
        print(json.dumps({"seed": seed, **said, **row}), flush=True)
    names = [k for k in rows[0] if "." not in k]
    print("summary (lower = program's largest; then the smallest of each "
          "control or fault):")
    for k in names:
        vals = [r[k] for r in rows if r.get(k) is not None]
        line = f"  {k}: lower {max(vals)!r} (median {sorted(vals)[len(vals) // 2]!r}, n={len(vals)})"
        for pre in sorted({c.rsplit(".", 1)[0] for r in rows for c in r
                           if c.endswith("." + k)}):
            other = [r[pre + "." + k] for r in rows if pre + "." + k in r]
            line += f"; {pre} min {min(other)!r}"
        print(line)
    print("verdicts of the cell's own comparison (the program has to come "
          "out correct; a control or a fault not):")
    bad = 0
    for who, got in verdicts.items():
        want = who == "program"
        wrong = [seed for seed, ok, _ in got if ok != want]
        bad += len(wrong)
        through = sorted({k for _s, _ok, failed in got for k in failed})
        print(f"  {who}: correct on {sum(ok for _s, ok, _ in got)} of "
              f"{len(got)} seeds; failed through {through or 'nothing'}"
              + (f"; WRONG VERDICT on seeds {wrong}" if wrong else ""))
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
