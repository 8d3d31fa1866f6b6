#!/usr/bin/env python3
"""One run of one cell, in a new process:

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

Fails at once without a TPU (no fallback), makes the weights on the device
from ``--seed``, warms only this cell's shapes (set-up), measures for
``--seconds``, checks what the timed path produced against the plain
reference, and prints one JSON object as its last line. ``--trace 0``
reports the cell's end-to-end metrics, ``--trace 1`` its per-layer metrics
(the profiler runs over a short tail after the measured window).

``--rehearse-cpu`` is the dry run: toy sizes from the configuration's
``rehearsal`` block on the CPU, output labelled a rehearsal, no number
under the name of a device metric.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

T_START = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


@dataclasses.dataclass
class Context:
    bench: object
    cell: object
    seed: int
    seconds: float
    trace: bool
    rehearsal: bool
    t_start: float
    traffic: dict = None       # the traffic file, with what was --set
    control: str = None        # readings only: the lower precision
    keep_trace: str = None     # copy the raw trace here
    plant: object = None       # tests only: plants a fault


def parse(argv):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--rehearse-cpu", action="store_true")
    p.add_argument("--set-traffic", action="append", default=[],
                   metavar="KEY=JSON", help="trials only (the knee sweep, "
                   "sizing): override one key of the traffic file")
    p.add_argument("--control", default=None,
                   help="readings: also run the reference at this precision")
    p.add_argument("--keep-trace", default=None)
    return p.parse_args(argv)


def setup_jax(rehearsal: bool, chips: int = 1) -> None:
    """The platform and the one compile cache, before the first compile.
    Whether the chips are there is the runner's first question
    (``common.require_device``)."""
    if not os.path.isdir(os.path.join(ROOT, "akka_allreduce_tpu")):
        raise SystemExit("benchmark: the program (akka_allreduce_tpu/) is "
                         "not in this checkout; nothing to measure")
    if rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
        if "xla_force_host_platform_device_count" not in os.environ.get(
                "XLA_FLAGS", ""):
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + f" --xla_force_host_platform_device_count={chips}")
    from akka_allreduce_tpu.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()


def traffic_of(cell, rehearsal: bool, overrides=()) -> dict:
    """The cell's traffic file as it is run: a rehearsal's toy sizes over
    it, then each ``KEY=JSON`` of a trial."""
    traffic = dict(cell.traffic)
    if rehearsal:
        traffic.update(traffic.get("rehearsal", {}))
    for kv in overrides:
        key, _, val = kv.partition("=")
        traffic[key] = json.loads(val)
        print(f"note trial: traffic {key} = {traffic[key]!r} (not the "
              f"cell as committed)")
    return traffic


def main(argv=None, plant=None) -> int:
    args = parse(argv)
    from benchmark import harness
    bench = harness.Benchmark()
    cell = bench.cell(args.workload)
    setup_jax(args.rehearse_cpu, cell.chips)
    ctx = Context(bench, cell, args.seed, args.seconds, bool(args.trace),
                  args.rehearse_cpu, T_START,
                  traffic_of(cell, args.rehearse_cpu, args.set_traffic),
                  args.control, args.keep_trace, plant)
    runner = bench.runner(cell.traffic["kind"])
    out = runner.run(ctx)
    return finish(bench, cell, ctx, out)


def finish(bench, cell, ctx, out) -> int:
    from benchmark import harness
    run = out["run"]
    entries = cell.per_layer if ctx.trace else cell.end_to_end
    metrics = harness.read_metrics(bench, run, entries, ctx.rehearsal)
    device = dict(out["device"])
    result = {"correct": all(c["ok"] for c in out["compared"].values()),
              "attempted": out["attempted"], "failed": out["failed"]}
    if ctx.trace and run.trace is not None:
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
        result["breakdown"] = {"device_ops": run.trace.top_ops(10),
                               "idle_gaps": run.trace.top_gaps(10)}
        for name, evs in sorted(run.trace.modules.items()):
            d = sorted(x * 1e3 for _s, x in evs)
            print(f"note program {name}: n={len(d)} p50={d[len(d) // 2]:.3f} "
                  f"min={d[0]:.3f} max={d[-1]:.3f} ms")
    for k, v in out.get("notes", {}).items():
        print(f"note {k}: {v}")
    if ctx.rehearsal:
        result.update(rehearsal=True, metrics={}, device=device,
                      rehearsal_readings={"rehearsal." + k: v
                                          for k, v in metrics.items()})
    else:
        missing = [m["name"] for m in entries if m["name"] not in metrics
                   and (not ctx.trace or m["source"] != "device_trace"
                        or run.trace is not None)]
        if missing:
            print(f"benchmark: nothing read for {missing}", file=sys.stderr)
            result["correct"] = False
        result.update(metrics=metrics, device=device)
    # readings and tests only (--control): what stood in for the program
    # and how the same comparison judged it, e.g. "control_correct": false
    shown = dict(out["compared"])
    for pre, other in out.get("stand_ins", {}).items():
        result[pre + "_correct"] = other["correct"]
        shown.update({f"{pre}.{k}": c for k, c in other["compared"].items()})
    harness.emit(result, shown)
    return 0


if __name__ == "__main__":
    sys.exit(main())
