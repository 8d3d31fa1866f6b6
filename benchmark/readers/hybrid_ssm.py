"""Readers for a served hybrid whose state-space layers carry a recurrent
state a lane (``runners/serve_hybrid_ssm.py``): the decode step's roofline
from the run's counts of the states it advanced and the keys it read, and
the roofline of the prefills' scans from the positions they counted and
the device time under the program's ``ssm_scan`` scope. Each returns None
where there is nothing to read: a program whose steps carry no such
counts or that lacks the scope, a run without a trace."""

from __future__ import annotations

from benchmark import flops, flops_ssm_moe
from benchmark.readers import reader
from benchmark.readers.device_trace import _steps_in_trace


@reader
def hybrid_ssm_decode_roofline_pct(run, pattern: str):
    """The least time the chip could take for the traced decode steps -
    the bytes each must move (every non-expert matrix once, each held
    expert that got a row once, the recurrent state of every lane-layer
    it advanced for a request read AND written, the live keys and values)
    or its operations, whichever takes longer - over the device time those
    steps took. A step that moves the state of idle lanes too, or all of
    the key-value buffer, reads LOW here."""
    if run.trace is None:
        return None
    durations = run.trace.module_durations(pattern)
    steps = [s for s in _steps_in_trace(run)
             if "route" in s and "ssm_lanes" in s]
    if not durations or not steps:
        return None
    peak = flops.peaks(run.device_kind)
    least = sum(flops.roofline_seconds(
        flops_ssm_moe.decode_step_flops(
            run.model, s["occupied"], s["live_positions"], s["ssm_lanes"],
            s["route"]["held"]),
        flops_ssm_moe.decode_step_bytes(
            run.model, s["live_positions"], s["ssm_lanes"],
            s["route"]["touched"]), peak)
        for s in steps)
    # the host's step records and the device's executions are the same
    # steps only as far as both counts agree
    return 100.0 * (least * len(durations) / len(steps)) / sum(durations)


@reader
def ssm_scan_roofline_pct(run, scope: str, pattern: str):
    """The least time the chip could take for the scans of the traced
    tail's prefill dispatches - the operations of the chunked form at the
    published block over the positions the scans counted, or the bytes
    they must move, whichever takes longer - over the device time under
    ``scope`` in the programs that match ``pattern``. Padding the scans ran
    over counts for nothing, so a padded chunk reads LOW."""
    pt = getattr(run, "program", None)
    scans = getattr(run, "scans", None)
    if pt is None or not scans:
        return None
    dev = pt._scope_s(scope, "all")
    n_dev = len(pt.programs(pattern))
    if dev <= 0 or not n_dev:
        return None
    counted = sum(n for n, _p in scans)
    peak = flops.peaks(run.device_kind)
    least = flops.roofline_seconds(
        flops_ssm_moe.scan_flops(run.model, counted),
        flops_ssm_moe.scan_bytes(run.model, counted), peak)
    # the host's dispatch records and the device's executions are the same
    # dispatches only as far as both counts agree
    return 100.0 * (least * n_dev / len(scans)) / dev
