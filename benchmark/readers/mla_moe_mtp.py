"""Readers for training latent attention over every position with an expert
share and a multi-token-prediction module (``runners/train_latent_moe.py``):
the flash kernel's roofline at the model's head sizes. Returns None where
there is nothing to read: a run without a trace, a trace without the
kernel's events."""

from __future__ import annotations

from benchmark import flops, flops_mla_moe_mtp
from benchmark.readers import reader
from benchmark.readers.device_trace import _steps_in_trace


@reader
def latent_flash_roofline_pct(run, pattern: str):
    """The kernel's events (``pattern``: the compiler names its custom
    calls after the ``mla_attention`` scope; the grouped matmuls, custom
    calls too, are named ``ragged-dot-*``), forward and backward, of every
    latent attention of the traced steps - the main stack's and the
    module's - against the larger of their operations over peak and their
    bytes over bandwidth (``flops_mla_moe_mtp``)."""
    if run.trace is None:
        return None
    dev = run.trace.op_seconds_matching(pattern)
    n = len(_steps_in_trace(run))
    if dev <= 0 or not n:
        return None
    peak = flops.peaks(run.device_kind)
    rows = run.rows / run.cell.chips
    least = n * flops.roofline_seconds(
        flops_mla_moe_mtp.flash_attention_flops(run.model, run.seq, rows),
        flops_mla_moe_mtp.flash_attention_bytes(run.model, run.seq, rows),
        peak)
    return 100.0 * least / dev
