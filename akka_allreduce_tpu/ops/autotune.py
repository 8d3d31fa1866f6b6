"""Topology-aware collective autotuner (ISSUE 13).

The repo has a schedule x wire matrix — (fused / windowed / swing /
hierarchical) x (f32 / bf16 / int8 / ef8) — chosen until now by
hand-set flags, with DESIGN.md §14's crossover table as the operator's
only guide. Swing (arxiv 2401.09356) and Optimal Non-pipelined
Reduce-scatter/Allreduce (arxiv 2410.14234) both show the winner FLIPS
with payload size and group count: latency-bound small buckets want
log-step schedules, bandwidth-bound large buckets want the two-phase
family. This module turns that table into code:

* :func:`measure_plan` times every FEASIBLE (schedule, windows) arm per
  bucket-size class — seeded, warmup-discarded, median-of-k two-point
  deltas, measured inside jit under a ``shard_map`` over the exact mesh
  axes the train step will use — and records each class's winner.
* :class:`CollectivePlan` is the deterministic result: canonical JSON
  (sorted keys, fixed rounding), so the same measurements serialize to
  byte-identical plans, content-hashed for the logs.
* :func:`save_plan` / :func:`load_plan` persist it as a JSON sidecar
  through ``runtime/checkpoint.py``'s atomic write-then-rename, and
  :func:`load_or_measure` reloads instead of re-measuring on restart
  (fingerprint mismatch — mesh axes, wire, shape classes, version —
  re-measures; matching plans reload byte-for-byte).
* :func:`resolve_schedule` is the dispatch half: ``GradSyncConfig
  .transport_schedule="auto"`` resolves each bucket matrix's class
  against the plan AT TRACE TIME, so a frozen plan always lowers the
  same programs — the zero-recompile contract holds exactly as it does
  for a hand-set flag (pinned under ``no_recompiles``).

A measurement cell that raises falls back to the hand-flag default
(``fused``) with the error recorded in the entry's note: the autotuner
may never be WORSE than not having one.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import time
from functools import partial
from typing import Any, Callable, Optional, Sequence

PLAN_VERSION = 1
PLAN_SIDECAR = "collective_plan"

# arms are identified as "fused", "windowed:<W>", "swing",
# "hierarchical" — the windowed arm carries its window count because
# the window count IS part of the lowered program


def _arm_schedule(arm: str) -> tuple[str, int]:
    if arm.startswith("windowed:"):
        return "windowed", int(arm.split(":", 1)[1])
    return arm, 1


@dataclasses.dataclass(frozen=True)
class PlanEntry:
    """One bucket-size class's verdict: the winning schedule (+ window
    count when windowed), every arm's measured median round time in
    microseconds, and a free-form note (fallback reasons, errors)."""

    schedule: str
    num_windows: int
    timings_us: dict
    note: str = ""

    def as_dict(self) -> dict:
        return {"schedule": self.schedule,
                "num_windows": self.num_windows,
                "timings_us": {k: round(float(v), 3)
                               for k, v in sorted(self.timings_us.items())},
                "note": self.note}

    @staticmethod
    def from_dict(d: dict) -> "PlanEntry":
        return PlanEntry(schedule=d["schedule"],
                         num_windows=int(d["num_windows"]),
                         timings_us=dict(d.get("timings_us", {})),
                         note=d.get("note", ""))


def plan_key(rows: int, cols: int) -> str:
    """The bucket-size-class key: the static (num_buckets, bucket_elems)
    shape of one sync's bucket matrix. Dense and expert syncs land in
    different classes exactly when their shapes differ."""
    return f"{int(rows)}x{int(cols)}"


@dataclasses.dataclass
class CollectivePlan:
    """The serialized autotuner verdict. ``axes`` is the ordered
    (axis_name, size) tuple of the sync group the plan was measured
    under — part of the fingerprint, so a plan never silently crosses
    meshes. ``wire`` is the transport it was measured with."""

    wire: str
    axes: tuple
    entries: dict
    version: int = PLAN_VERSION

    def lookup(self, rows: int, cols: int) -> Optional[PlanEntry]:
        return self.entries.get(plan_key(rows, cols))

    # -- canonical serialization (same measurements => same bytes) ------

    def to_json(self) -> dict:
        return {
            "version": self.version,
            "wire": self.wire,
            "axes": [[str(a), int(n)] for a, n in self.axes],
            "entries": {k: self.entries[k].as_dict()
                        for k in sorted(self.entries)},
        }

    def canonical_bytes(self) -> bytes:
        return json.dumps(self.to_json(), sort_keys=True,
                          separators=(",", ":")).encode()

    @property
    def plan_hash(self) -> str:
        return hashlib.sha256(self.canonical_bytes()).hexdigest()[:16]

    @staticmethod
    def from_json(doc: dict) -> "CollectivePlan":
        return CollectivePlan(
            wire=doc["wire"],
            axes=tuple((str(a), int(n)) for a, n in doc["axes"]),
            entries={k: PlanEntry.from_dict(v)
                     for k, v in doc.get("entries", {}).items()},
            version=int(doc.get("version", PLAN_VERSION)),
        )


def feasible_arms(wire: str, live_sizes: Sequence[int], rows: int,
                  num_windows: int = 4) -> list:
    """The arms a (wire, group, shape) cell may legally run — mirrors
    the validation in parallel/dp.py so the autotuner never measures a
    program the sync could not dispatch. ``live_sizes``: the >1 axis
    sizes of the sync group, mesh order (outer first)."""
    two_axis_quant = len(live_sizes) == 2 and wire in ("int8", "ef8")
    # the quantized two-phase cannot span two axes (parallel/dp.py
    # raises) — on that geometry the ef8 hierarchical hybrid is the
    # ONLY dispatchable arm, so don't measure a guaranteed failure
    arms = [] if two_axis_quant else ["fused"]
    if len(live_sizes) == 1:
        n = live_sizes[0]
        w = min(int(num_windows), int(rows))
        if w > 1:
            arms.append(f"windowed:{w}")
        if n & (n - 1) == 0:
            arms.append("swing")
    elif len(live_sizes) == 2 and wire == "ef8":
        arms.append("hierarchical")
    return arms


def _default_measure_cell(mesh, axis_name, wire: str, arm: str,
                          rows: int, cols: int, *, rounds_hi: int,
                          rounds_lo: int, reps: int, seed: int) -> float:
    """Median-of-``reps`` two-point-delta round time (seconds) of one
    (arm, shape) cell: all rounds inside ONE jitted ``lax.scan`` under a
    ``shard_map`` over the exact mesh axes, chained through the carry
    via ``abs`` so XLA cannot collapse the chain, first run discarded
    as compile+warmup."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from akka_allreduce_tpu.parallel.dp import (GradSyncConfig,
                                                allreduce_gradients)

    schedule, windows = _arm_schedule(arm)
    axes = (axis_name,) if isinstance(axis_name, str) else tuple(axis_name)
    cfg = GradSyncConfig(
        bucket_elems=cols, axis_name=axes if len(axes) > 1 else axes[0],
        average=True, rescale_target=1.0, return_elem_counts=False,
        transport=wire, transport_schedule=schedule, num_windows=windows)
    quantized = wire in ("int8", "ef8")
    ef = wire == "ef8"

    def run_rounds(rounds):
        @partial(jax.shard_map, mesh=mesh, in_specs=(P(), P()),
                 out_specs=P(), check_vma=False)
        def run(x0, resid0):
            base_key = jax.random.key(seed)

            def one(carry, i):
                x, r = carry
                g = {"g": jnp.abs(x) + 1e-12}
                res = allreduce_gradients(
                    g, cfg,
                    quant_key=(jax.random.fold_in(base_key, i)
                               if quantized else None),
                    residual=(r if ef else None))
                return (res.grads["g"], res.residual if ef else r), None

            (xf, _), _ = lax.scan(one, (x0, resid0),
                                  jnp.arange(rounds, dtype=jnp.uint32))
            return xf

        return jax.jit(run)

    x0 = jnp.zeros((rows * cols,), jnp.float32)
    resid0 = (jnp.zeros((rows, cols), jnp.float32) if ef
              else jnp.zeros((1, 1), jnp.float32))

    def timed(rounds):
        f = run_rounds(rounds)
        np.asarray(jax.device_get(f(x0, resid0)))[:4]  # compile + warm
        ts = []
        for i in range(reps):
            t0 = time.perf_counter()
            out = f(x0 + float(i) * 1e-3, resid0)
            np.asarray(jax.device_get(out))[:4]
            ts.append(time.perf_counter() - t0)
        ts.sort()
        return ts[len(ts) // 2]  # median-of-k

    per_round = (timed(rounds_hi) - timed(rounds_lo)) \
        / (rounds_hi - rounds_lo)
    if per_round <= 0:
        # noise swamped the delta: widen once, then report the floor —
        # a cell must yield SOME ordering signal or fall back upstream
        wide = 4 * rounds_hi
        per_round = (timed(wide) - timed(rounds_lo)) / (wide - rounds_lo)
    if per_round <= 0:
        raise RuntimeError(
            f"two-point timing failed twice for arm {arm!r} at "
            f"{rows}x{cols}: host too noisy for this cell")
    return per_round


def measure_plan(mesh, axis_name, shapes: Sequence, wire: str = "f32",
                 num_windows: int = 4,
                 rounds_hi: Optional[int] = None,
                 rounds_lo: Optional[int] = None,
                 reps: int = 3, seed: int = 11,
                 measure_cell: Optional[Callable] = None,
                 log: Optional[Callable] = None) -> CollectivePlan:
    """Measure every feasible arm per bucket-size class and emit the
    deterministic :class:`CollectivePlan`.

    ``shapes``: iterable of ``(rows, cols)`` bucket-matrix classes —
    the exact static shapes the train step's syncs will dispatch
    (``dense_bucket_count`` x ``bucket_elems``, plus the expert class
    for MoE). ``measure_cell(arm, rows, cols) -> seconds`` overrides
    the timing harness (tests inject fixed values; same injected
    measurements => byte-identical plan). A cell that RAISES records
    the error and the class falls back to the surviving arms — or to
    the hand-flag default ``fused`` when nothing survived.
    """
    import jax

    on_tpu = jax.devices()[0].platform == "tpu"
    if rounds_hi is None:
        rounds_hi = 30 if on_tpu else 6
    if rounds_lo is None:
        rounds_lo = max(1, rounds_hi // 4)
    axes = (axis_name,) if isinstance(axis_name, str) else tuple(axis_name)
    sizes = [int(mesh.shape[a]) for a in axes]
    live = [(a, n) for a, n in zip(axes, sizes) if n > 1]
    live_sizes = [n for _, n in live]
    cell = measure_cell or partial(
        _default_measure_cell, mesh, axes if len(axes) > 1 else axes[0],
        wire, rounds_hi=rounds_hi, rounds_lo=rounds_lo, reps=reps,
        seed=seed)
    entries = {}
    for rows, cols in shapes:
        rows, cols = int(rows), int(cols)
        timings: dict = {}
        notes: list = []
        for arm in feasible_arms(wire, live_sizes, rows, num_windows):
            try:
                t = float(cell(arm, rows, cols))
            except Exception as exc:  # noqa: BLE001 — the fallback IS
                # the contract: a broken cell must not take the plan
                # (or the train run behind it) down
                notes.append(f"{arm}: {type(exc).__name__}: {exc}")
                continue
            timings[arm] = round(t * 1e6, 3)
            if log:
                log(f"autotune: {plan_key(rows, cols)} {arm} "
                    f"{t * 1e6:.1f} us/round")
        if timings:
            win = min(sorted(timings), key=lambda a: timings[a])
            schedule, windows = _arm_schedule(win)
            note = "; ".join(notes)
        else:
            schedule, windows = "fused", 1
            note = ("no feasible arm, hand-flag default" if not notes
                    else "all cells failed, hand-flag default: "
                    + "; ".join(notes))
        entries[plan_key(rows, cols)] = PlanEntry(
            schedule=schedule, num_windows=windows, timings_us=timings,
            note=note)
    return CollectivePlan(wire=wire, axes=tuple(live), entries=entries)


# -- sidecar persistence (runtime/checkpoint.py atomics) ----------------

def save_plan(directory: str, plan: CollectivePlan,
              name: str = PLAN_SIDECAR) -> str:
    """Atomic write-then-rename JSON sidecar (a preemption mid-save
    leaves the previous complete plan, never a torn one)."""
    from akka_allreduce_tpu.runtime.checkpoint import save_state_json
    return save_state_json(directory, name, plan.to_json())


def load_plan(directory: str,
              name: str = PLAN_SIDECAR) -> Optional[CollectivePlan]:
    from akka_allreduce_tpu.runtime.checkpoint import load_state_json
    doc = load_state_json(directory, name)
    if doc is None:
        return None
    try:
        return CollectivePlan.from_json(doc)
    except (KeyError, TypeError, ValueError):
        return None  # corrupt sidecar: caller re-measures


def load_or_measure(directory: Optional[str], mesh, axis_name,
                    shapes: Sequence, wire: str = "f32",
                    log: Optional[Callable] = None,
                    **measure_kw) -> tuple:
    """The restart contract: reload the sidecar instead of re-measuring
    when its fingerprint (version, wire, sync-group axes, every
    requested shape class) still matches; anything else re-measures and
    re-saves. Returns ``(plan, reused)``. ``directory=None`` measures
    without persisting (narrated by the caller)."""
    axes = (axis_name,) if isinstance(axis_name, str) else tuple(axis_name)
    live = tuple((a, int(mesh.shape[a])) for a in axes
                 if int(mesh.shape[a]) > 1)
    want = {plan_key(r, c) for r, c in shapes}
    if directory is not None:
        plan = load_plan(directory)
        if (plan is not None and plan.version == PLAN_VERSION
                and plan.wire == wire and tuple(plan.axes) == live
                and want <= set(plan.entries)):
            return plan, True
    plan = measure_plan(mesh, axis_name, shapes, wire=wire, log=log,
                        **measure_kw)
    if directory is not None:
        save_plan(directory, plan)
    return plan, False


# -- trace-time dispatch ------------------------------------------------

def resolve_schedule(plan: Optional[CollectivePlan], rows: int, cols: int,
                     live_sizes: Sequence[int], wire: str,
                     default_windows: int = 4) -> tuple:
    """``transport_schedule="auto"`` -> the concrete (schedule, windows)
    this bucket matrix dispatches. Pure trace-time Python: a frozen plan
    resolves identically on every trace, so the lowered program set is a
    function of the plan — the zero-recompile contract.

    Missing plan, missing class, or a winner the live mesh cannot run
    (group shrank, axis folded) all fall back to the hand-flag default
    — ``("fused", default_windows)``, except on the (ef8, two >1 axes)
    geometry where the quantized two-phase cannot dispatch and
    ``hierarchical`` IS the hand flag an operator would have set —
    so auto is never worse than that flag."""
    n_live = len([n for n in live_sizes if n > 1])
    fallback = ("hierarchical" if wire == "ef8" and n_live == 2
                else "fused", default_windows)
    if plan is None:
        return fallback
    entry = plan.lookup(rows, cols)
    if entry is None:
        return fallback
    s = entry.schedule
    if s in ("windowed", "swing") and n_live != 1:
        return fallback
    if s == "swing":
        n = next(sz for sz in live_sizes if sz > 1)  # n_live == 1 here
        if n & (n - 1):
            return fallback
    if s == "hierarchical" and (n_live != 2 or wire != "ef8"):
        return fallback
    if s == "fused" and wire in ("int8", "ef8") and n_live == 2:
        return fallback  # quantized two-phase cannot span two axes
    return s, (entry.num_windows if s == "windowed" else default_windows)


# -- operator surface ---------------------------------------------------

def plan_markdown_table(plan: CollectivePlan) -> str:
    """DESIGN.md §14's crossover table, generated from a measured plan
    dump (table-from-code): one row per bucket-size class, every arm's
    median round time, winner starred."""
    group = " x ".join(f"{a}={n}" for a, n in plan.axes) or "1 rank"
    arms: list = []
    for e in plan.entries.values():
        for a in e.timings_us:
            if a not in arms:
                arms.append(a)
    arms.sort(key=lambda a: ("fused", "windowed", "swing",
                             "hierarchical").index(_arm_schedule(a)[0]))
    lines = [
        f"| bucket class ({group}, wire {plan.wire}) | "
        + " | ".join(f"{a} (us/round)" for a in arms) + " | winner |",
        "|" + "---|" * (len(arms) + 2),
    ]
    def _k(item):
        r, c = item[0].split("x")
        return int(r) * int(c), item[0]
    for key, e in sorted(plan.entries.items(), key=_k):
        rows, cols = key.split("x")
        win = (e.schedule if e.schedule != "windowed"
               else f"windowed:{e.num_windows}")
        cells = [f"{e.timings_us[a]:.1f}" if a in e.timings_us else "—"
                 for a in arms]
        lines.append(f"| {rows} x {cols} | " + " | ".join(cells)
                     + f" | **{win}** |")
    return "\n".join(lines)


def _main() -> int:
    """``python -m akka_allreduce_tpu.ops.autotune`` — measure a plan on
    the current backend and print its markdown table + JSON (how the
    DESIGN.md §14 table is regenerated)."""
    import argparse

    import jax

    from akka_allreduce_tpu.parallel.mesh import single_axis_mesh

    ap = argparse.ArgumentParser()
    ap.add_argument("--wire", default="f32",
                    choices=("f32", "bf16", "int8", "ef8"))
    ap.add_argument("--shapes", default="8x40960,8x327680,8x1310720,"
                                        "8x3145728",
                    help="comma list of ROWSxCOLS bucket classes")
    ap.add_argument("--out-dir", default=None,
                    help="persist the sidecar here (atomic)")
    args = ap.parse_args()
    shapes = [tuple(map(int, s.split("x")))
              for s in args.shapes.split(",")]
    mesh = single_axis_mesh("dp")
    plan = measure_plan(mesh, "dp", shapes, wire=args.wire, log=print)
    print(f"plan hash {plan.plan_hash} over {len(jax.devices())} "
          f"device(s)")
    print(plan_markdown_table(plan))
    print(json.dumps(plan.to_json(), indent=1))
    if args.out_dir:
        print("wrote", save_plan(args.out_dir, plan))
    return 0


if __name__ == "__main__":
    raise SystemExit(_main())
