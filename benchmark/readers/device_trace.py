"""Readers over the reduced profiler trace (``--trace 1`` only). Each
returns None where the trace has nothing for it: never 0 for a share."""

from __future__ import annotations

from benchmark import flops
from benchmark.harness import percentile
from benchmark.readers import reader


def _steps_in_trace(run):
    if not run.trace_span or run.trace_span[0] is None:
        return []
    t0, t1 = run.trace_span
    return [s for s in run.steps if s["t0"] >= t0 and s["t1"] <= t1]


@reader
def module_ms_percentile(run, pattern: str, q: float):
    """``q``-th percentile of the device time of one execution of the
    programs whose name matches ``pattern``."""
    if run.trace is None:
        return None
    d = run.trace.module_durations(pattern)
    return percentile([x * 1e3 for x in d], q) if d else None


@reader
def module_gap_ms_percentile(run, pattern: str, q: float):
    """Idle between one execution's end and the next one's start."""
    if run.trace is None:
        return None
    evs = run.trace.module_events(pattern)
    gaps = [(evs[i + 1][0] - (evs[i][0] + evs[i][1])) * 1e3
            for i in range(len(evs) - 1)]
    return percentile(gaps, q) if gaps else None


@reader
def decode_roofline_pct(run, pattern: str):
    """The least time the chip could take for the traced decode steps (the
    bytes each must read: weights once, the live cache positions; and its
    operations) over the device time those steps took."""
    if run.trace is None:
        return None
    dev = sum(run.trace.module_durations(pattern))
    steps = _steps_in_trace(run)
    if dev <= 0 or not steps:
        return None
    peak = flops.peaks(run.device_kind)
    least = sum(flops.roofline_seconds(
        s["occupied"] * flops.decode_token_flops(
            run.model, s["live_positions"] / max(1, s["occupied"])),
        flops.decode_step_bytes(run.model, s["live_positions"]), peak)
        for s in steps)
    # the host's step records and the device's executions are the same
    # steps only as far as both counts agree
    n_dev = len(run.trace.module_durations(pattern))
    return 100.0 * (least * n_dev / len(steps)) / dev


@reader
def flash_roofline_pct(run, pattern: str):
    """Mosaic flash-attention events, forward and backward, against the
    larger of operations over peak and bytes over bandwidth."""
    if run.trace is None:
        return None
    dev = run.trace.op_seconds_matching(pattern)
    n = len(_steps_in_trace(run))
    if dev <= 0 or not n:
        return None
    peak = flops.peaks(run.device_kind)
    rows = run.rows / run.cell.chips
    least = n * flops.roofline_seconds(
        flops.flash_attention_flops(run.model, run.seq, rows),
        flops.flash_attention_bytes(run.model, run.seq, rows), peak)
    return 100.0 * least / dev


@reader
def exposed_collective_pct(run):
    """Collective-op time during which no compute op ran on that device,
    as a share of the traced window."""
    if run.trace is None or run.trace.collective_s <= 0:
        return None
    return 100.0 * run.trace.exposed_collective_s / run.trace.window_s
