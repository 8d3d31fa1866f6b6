"""Readers for a served model with latent attention and a dropless expert
share (``runners/serve_latent_moe.py``): the decode step's roofline from
the run's route counts, shares of the traced tail's device time under the
program's scopes (read off the kept profile by ``program_trace.py``), and
a plain ratio of two counters. Each returns None where there is nothing to
read: a program that lacks the scopes or the counters, a run without a
trace."""

from __future__ import annotations

import re

from benchmark import flops, flops_scmoe_mla
from benchmark.readers import reader
from benchmark.readers.device_trace import _steps_in_trace


@reader
def counter_ratio(run, num: str, den: str):
    n, d = run.counters.get(num), run.counters.get(den)
    if n is None or not d:
        return None
    return n / d


@reader
def latent_moe_decode_roofline_pct(run, pattern: str):
    """The least time the chip could take for the traced decode steps -
    the bytes each must read (every non-expert matrix once, each held
    expert that got a row once, the live latents) or its operations (with
    the held assignments it really ran), whichever takes longer - over
    the device time those steps took."""
    if run.trace is None:
        return None
    durations = run.trace.module_durations(pattern)
    steps = [s for s in _steps_in_trace(run) if "route" in s]
    if not durations or not steps:
        return None
    peak = flops.peaks(run.device_kind)
    least = sum(flops.roofline_seconds(
        flops_scmoe_mla.decode_step_flops(
            run.model, s["occupied"], s["live_positions"],
            s["route"]["held"]),
        flops_scmoe_mla.decode_step_bytes(
            run.model, s["live_positions"], s["route"]["touched"]), peak)
        for s in steps)
    # the host's step records and the device's executions are the same
    # steps only as far as both counts agree
    return 100.0 * (least * len(durations) / len(steps)) / sum(durations)


@reader
def scopes_share_of_busy_pct(run, scopes: list, also_ops: str = None):
    """Self time of the device ops under the program's ``scopes`` over the
    traced tail's busy device time. ``also_ops``: a pattern of ops that
    belong to the layer and that the compiler names itself, so that they
    carry no scope (the grouped matmul a ``lax.ragged_dot`` becomes is
    ``ragged-dot-none``, with that as its whole ``op_name``)."""
    pt = getattr(run, "program", None)
    if pt is None or pt.busy_s <= 0:
        return None
    got = sum(pt._scope_s(sc, "all") for sc in scopes)
    if also_ops and got > 0:
        got += sum(v for name, v in pt.unscoped_ops.items()
                   if re.search(also_ops, name))
    return 100.0 * got / pt.busy_s if got > 0 else None
