"""Readers over the benchmark's own clock: per-sample series stamped at
the token hook and around calls, and rates over the whole window."""

from __future__ import annotations

from benchmark.harness import percentile
from benchmark.readers import reader


@reader
def series_percentile(run, series: str, q: float):
    """``q``-th percentile of every sample of ``series``."""
    return percentile(run.series.get(series) or [], q)


@reader
def counter_rate(run, counter: str):
    """``counter`` over the window's seconds."""
    n = run.counters.get(counter)
    if not n or run.window_s <= 0:
        return None
    return n / run.window_s


@reader
def setup_seconds(run):
    return run.setup_s


@reader
def counter_ratio_pct(run, num: str, den: str):
    n, d = run.counters.get(num), run.counters.get(den)
    if n is None or not d:
        return None
    return 100.0 * n / d


@reader
def window_mfu_pct(run, counter: str = "model_flops"):
    """Model operations of all the window's work over what the chips
    could have done in the window at their peak."""
    from benchmark.flops import peaks
    flops = run.counters.get(counter)
    if not flops or run.window_s <= 0:
        return None
    peak = peaks(run.device_kind)["bf16_flops_per_s"]
    return 100.0 * flops / (run.window_s * run.cell.chips * peak)
