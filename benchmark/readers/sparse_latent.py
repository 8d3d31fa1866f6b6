"""Readers for a served model whose attentions read an indexer's selection
(``runners/serve_sparse_latent.py``): the decode step's roofline from the
run's counts of what its attentions read and its indexers scored. Returns
None where there is nothing to read: a program whose steps carry no such
counts, a run without a trace."""

from __future__ import annotations

from benchmark import flops, flops_dsa_moe
from benchmark.readers import reader
from benchmark.readers.device_trace import _steps_in_trace


@reader
def sparse_latent_decode_roofline_pct(run, pattern: str):
    """The least time the chip could take for the traced decode steps -
    the bytes each must read (every non-expert matrix once, each held
    expert that got a row once, the latent rows its attentions selected,
    the index keys its indexers scanned) or its operations (the matrices a
    busy lane meets, the held assignments really run, the attentions and
    the indexers over what they read), whichever takes longer - over the
    device time those steps took. A step that reads more than it selected
    reads LOW here."""
    if run.trace is None:
        return None
    durations = run.trace.module_durations(pattern)
    steps = [s for s in _steps_in_trace(run)
             if "route" in s and "index_selected" in s]
    if not durations or not steps:
        return None
    peak = flops.peaks(run.device_kind)
    least = sum(flops.roofline_seconds(
        flops_dsa_moe.decode_step_flops(
            run.model, s["occupied"], s["index_selected"],
            s["index_scanned"], s["route"]["held"]),
        flops_dsa_moe.decode_step_bytes(
            run.model, s["index_selected"], s["index_scanned"],
            s["route"]["touched"]), peak)
        for s in steps)
    # the host's step records and the device's executions are the same
    # steps only as far as both counts agree
    return 100.0 * (least * len(durations) / len(steps)) / sum(durations)
